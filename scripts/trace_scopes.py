"""Split a profiler trace's device time by the walk programs' named
scopes.

    python scripts/trace_scopes.py --trace T.xplane.pb [--hlo DIR]
    python scripts/trace_scopes.py --record DIR --workload pincell.source \
        --seed N [--moves K]
    python scripts/trace_scopes.py --cut T.xplane.pb --out C.xplane.pb \
        --hlo DIR [--ops-ms MS]

The device programs wrap their parts in ``jax.named_scope``
(ops/walk.py): ``walk.gather``, ``walk.geometry``, ``walk.scatter``,
``walk.compact``, ``source.sample``, ``source.physics``,
``tally.batch_sd`` and ``obs.fold``. An ``XLA Ops`` event's scope path
is read from the event's stats where the trace carries it, else from the
``op_name`` metadata of the same instruction in the compiled module's
HLO text (``--hlo``: a directory of XLA's ``*after_optimizations.txt``
dumps). Each op counts its self time (its duration less the ops nested
inside it, so a while loop keeps only what its body's ops leave) and
goes to the innermost scope on its path, ``unscoped`` when none. The
split is per program (the ``XLA Modules`` event the op runs in) and
covers the whole trace, printed as one JSON object.

``--record`` runs one cell of the benchmark (its configuration, traffic
and warm-up, as ``benchmark/run.py`` builds them) on the chip, with the
persistent compile cache in ``--cache`` (default ``DIR/cache``, new, so
that XLA compiles, and dumps, the walk programs; a later recording may
share it). It traces one batch (the init and ``--moves`` moves) under
the benchmark's span names and writes to DIR: ``trace/`` (the profile),
``hlo/`` (the dumps), ``records.json`` (each call's walk flight record,
with the lane-slots ``utils/ladder.simulate_ladder`` predicts for the
realized schedule beside the measured ones) and ``split.json``.

``--cut`` writes a copy of a trace small enough to commit: the device's
program line and the host's ``bench.`` / ``PumiTally.`` spans whole, the
op line cut to the ops that end within the first ``--ops-ms`` ms of each
program, each op's name cut to ``%name = type[shape]`` and its scope
path (from ``--hlo``) kept as the stat ``scope``. It reads the trace through TensorFlow's
XPlane protobuf, so run it off the chip.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SCOPES = ("walk.gather", "walk.geometry", "walk.scatter", "walk.compact",
          "source.sample", "source.physics", "tally.batch_sd", "obs.fold")
# The stat this script's --cut writes; a TPU v5e trace under JAX 0.9
# carries no op path among its ops' stats, so there the HLO join decides.
SCOPE_STAT = "scope"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%\S+|[\w.\-]+) = .*?"
                    r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def scope_of(path: str) -> str | None:
    """The innermost known scope on an ``op_name`` path."""
    best, at = None, -1
    for s in SCOPES:
        i = path.rfind(s)
        if i > at:
            best, at = s, i
    return best


def module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def hlo_op_names(hlo_dir: str) -> dict:
    """{module name: {instruction name: op_name}} from the optimized HLO
    dumps; an instruction two modules of one name disagree on maps to
    None."""
    out: dict = {}
    for path in sorted(glob.glob(os.path.join(hlo_dir, "**",
                                              "*after_optimizations.txt"),
                                 recursive=True)):
        with open(path) as f:
            text = f.read()
        m = re.search(r"^HloModule (\S+?),", text, re.M)
        if not m:
            continue
        table = out.setdefault(m.group(1), {})
        for line in text.splitlines():
            im = _INSTR.match(line)
            if im:
                name = im.group(1)
                name = name if name.startswith("%") else "%" + name
                if table.get(name, im.group(2)) != im.group(2):
                    table[name] = None
                else:
                    table[name] = im.group(2)
    return out


def op_key(event_name: str) -> str:
    return event_name.split(" ", 1)[0]


def device_ops(path: str):
    """Per chip: (ops [(start, end, name, path-from-stats)], modules
    [(start, end, name)]), times in ns."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        ops, mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                         dict(e.stats).get(SCOPE_STAT))
                        for e in line.events]
            elif line.name == "XLA Modules":
                mods += [(e.start_ns, e.start_ns + e.duration_ns,
                          module_name(e.name)) for e in line.events]
        if ops:
            chips.append((ops, sorted(mods)))
    return chips


def self_times(ops: list) -> np.ndarray:
    """Each op's duration less the durations of the ops directly nested
    inside it (ops sorted by start, longest first on ties)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = np.array([e - s for s, e, *_ in ops], np.float64)
    stack: list = []
    for i in order:
        s, e = ops[i][0], ops[i][1]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def split(trace_path: str, hlo_dir: str | None = None) -> dict:
    """{program: {"device_s", "scopes": {scope: s}, "share": {scope: %},
    "sources": {"stats": n, "hlo": n, "none": n}, "unscoped_ops": {op
    kind: s}}} over every chip and the whole trace."""
    names = hlo_op_names(hlo_dir) if hlo_dir else {}
    out: dict = {}
    for ops, mods in device_ops(trace_path):
        own = self_times(ops)
        starts = np.array([m[0] for m in mods], np.float64)
        for (s, e, name, path), t in zip(ops, own):
            mid = 0.5 * (s + e)
            j = int(np.searchsorted(starts, mid, side="right")) - 1
            prog = mods[j][2] if j >= 0 and mid <= mods[j][1] else "none"
            rec = out.setdefault(prog, {
                "device_s": 0.0, "scopes": {},
                "sources": {"stats": 0, "hlo": 0, "none": 0},
                "unscoped_ops": {}})
            src = "stats"
            if path is None:
                path = names.get(prog, {}).get(op_key(name))
                src = "hlo" if path else "none"
            rec["sources"][src] += 1
            scope = scope_of(path or "") or "unscoped"
            sec = float(t) * 1e-9
            rec["device_s"] += sec
            rec["scopes"][scope] = rec["scopes"].get(scope, 0.0) + sec
            if scope == "unscoped":
                kind = re.sub(r"[.\d]+$", "", op_key(name).lstrip("%"))
                rec["unscoped_ops"][kind] = (
                    rec["unscoped_ops"].get(kind, 0.0) + sec)
    for rec in out.values():
        tot = rec["device_s"] or 1.0
        rec["share"] = {k: 100.0 * v / tot for k, v in
                        sorted(rec["scopes"].items(), key=lambda x: -x[1])}
        rec["unscoped_ops"] = dict(sorted(
            rec["unscoped_ops"].items(), key=lambda x: -x[1])[:8])
    return out


# --------------------------------------------------------------------- #
# --record: one traced batch of a benchmark cell
# --------------------------------------------------------------------- #
def predicted_slots(rec: dict, alive: int, n: int, stages, unroll: int,
                    max_crossings: int) -> float | None:
    """Lane-slots ``simulate_ladder`` predicts for one move of ``alive``
    lanes at the move's measured mean crossings (exponential decay)."""
    from pumiumtally_tpu.utils.ladder import exp_survivors, simulate_ladder

    if not alive or not rec.get("segments"):
        return None
    mean = max(rec["segments"] / alive - 1.0, 0.25)
    slots, _ = simulate_ladder(exp_survivors(alive, mean), n, stages,
                               unroll=unroll, max_crossings=max_crossings)
    return float(slots)


def dump_hlo_to(hlo: str) -> None:
    """Have XLA dump the walk programs' HLO text (set before JAX starts
    its backend)."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" --xla_dump_to={hlo}"
        " --xla_dump_hlo_as_text"
        " --xla_dump_hlo_module_re=.*(megastep|trace_packed).*").strip()


def record(out_dir: str, workload: str, seed: int, moves: int,
           cache: str | None = None, bench=None,
           need_chip: bool = True) -> dict:
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      cache or os.path.join(out_dir, "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from benchmark import drivers, mesh, run
    from benchmark import trace as tracing
    from pumiumtally_tpu import PumiTally, TallyConfig
    from pumiumtally_tpu.mesh.core import TetMesh
    from pumiumtally_tpu.utils.profiling import annotate, profile_trace

    bench = bench or run.Bench.load()
    cell = bench.cell(workload)
    cfg = bench.config(cell["config"])
    traffic = bench.json("traffic", cell["traffic"] + ".json")
    if need_chip:
        run.require_chip(cell["chips"])
    dtype = jax.numpy.dtype(cfg["dtype"])
    coords, tets, class_id = mesh.build(cfg["mesh"])
    tmesh = TetMesh.from_numpy(coords, tets, class_id, dtype=dtype)
    groups = int(cfg["groups"])
    driver = drivers.load(traffic, coords[tets].mean(axis=1), class_id,
                          groups, seed)
    tally = PumiTally(tmesh, driver.lanes,
                      TallyConfig(n_groups=groups, dtype=dtype,
                                  **cfg["tally"]))
    for call in driver.calls(tally, warm=True):
        call.run()
    logdir = os.path.join(out_dir, "trace")
    calls = driver.calls(tally)
    records = []
    with profile_trace(logdir), annotate("bench.window"):
        for _ in range(1 + moves):
            call = next(calls)
            with annotate("bench." + call.kind):
                call.run()
            records.append(tally._telemetry.recorder.tail(1)[0])
    n = tally.num_particles
    stages = tally._compact_stages or (
        ((tally._compact[0], tally._compact[1]),)
        if tally._compact[0] is not None else ())
    alive = n
    for r in records:
        if r.get("kind") in ("move", "megastep"):
            r["predicted_lane_slots"] = predicted_slots(
                r, alive, n, stages, tally.config.unroll,
                tally._max_crossings)
            alive = r.get("alive", alive)
    with open(os.path.join(out_dir, "records.json"), "w") as f:
        json.dump({"workload": workload, "seed": seed, "lanes": n,
                   "stages": (stages if isinstance(stages, str)
                              else [list(s) for s in stages]),
                   "unroll": tally.config.unroll, "records": records},
                  f, indent=1, default=str)
    result = split(tracing.find(logdir), os.path.join(out_dir, "hlo"))
    with open(os.path.join(out_dir, "split.json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


# --------------------------------------------------------------------- #
# --cut: a committable copy
# --------------------------------------------------------------------- #
def short_op(name: str) -> str:
    m = re.match(r"(%\S+ = \(?[a-z]\w*\[[\d,]*\])", name)
    return m.group(1) if m else name.split(" ", 1)[0]


def cut(src: str, dst: str, hlo_dir: str, ops_ms: float) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    names = hlo_op_names(hlo_dir)
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and {"XLA Ops",
                                                   "XLA Modules"} <= set(lines):
            _cut_device(out.planes.add(), plane, lines["XLA Modules"],
                        lines["XLA Ops"], names, ops_ms)
        elif plane.name.startswith("/host:") and lines:
            _cut_host(out.planes.add(), plane)
    data = out.SerializeToString()
    with open(dst, "wb") as f:
        f.write(data)
    return len(data)


def _copy_line(dst, line, events):
    """``line``'s header and ``events`` (with no stats) into ``dst``."""
    dst.id, dst.display_id, dst.name = line.id, line.display_id, line.name
    dst.timestamp_ns, dst.duration_ps = line.timestamp_ns, line.duration_ps
    for e in events:
        ne = dst.events.add()
        ne.metadata_id, ne.offset_ps = e.metadata_id, e.offset_ps
        ne.duration_ps = e.duration_ps
    return dst


def _cut_host(dst, plane) -> None:
    """The ``bench.`` / ``PumiTally.`` spans of a host plane."""
    dst.id, dst.name = plane.id, plane.name
    keep = {i for i, m in plane.event_metadata.items()
            if m.name.startswith(("bench.", "PumiTally."))}
    for line in plane.lines:
        evs = [e for e in line.events if e.metadata_id in keep]
        if evs:
            _copy_line(dst.lines.add(), line, evs)
    for i in keep:
        dst.event_metadata[i].id = i
        dst.event_metadata[i].name = plane.event_metadata[i].name


def _cut_device(dst, plane, mods, ops, names: dict, ops_ms: float) -> None:
    """A device plane's program line whole, and the ops that end within
    the first ``ops_ms`` ms of each program, each with its scope path."""
    dst.id, dst.name = plane.id, plane.name
    md = plane.event_metadata
    t0 = mods.timestamp_ns * 1000
    spans = sorted((t0 + e.offset_ps, module_name(md[e.metadata_id].name))
                   for e in mods.events)
    starts = np.array([s for s, _ in spans], np.float64)
    kept = []
    for e in ops.events:
        t = ops.timestamp_ns * 1000 + e.offset_ps
        j = int(np.searchsorted(starts, t, side="right")) - 1
        # Whole ops only: a loop that outlives the cut would keep the
        # time of the body ops cut away as its own.
        if j >= 0 and t + e.duration_ps - spans[j][0] <= ops_ms * 1e9:
            kept.append((e, spans[j][1]))
    _copy_line(dst.lines.add(), mods, mods.events)
    line = _copy_line(dst.lines.add(), ops, [e for e, _ in kept])
    scope_id = 1
    dst.stat_metadata[scope_id].id = scope_id
    dst.stat_metadata[scope_id].name = SCOPE_STAT
    for ne, (e, prog) in zip(line.events, kept):
        path = names.get(prog, {}).get(op_key(md[e.metadata_id].name))
        if path:
            st = ne.stats.add()
            st.metadata_id, st.str_value = scope_id, path
    for i in {e.metadata_id for e in mods.events} | {
            e.metadata_id for e, _ in kept}:
        name = md[i].name
        dst.event_metadata[i].id = i
        dst.event_metadata[i].name = (short_op(name) if name.startswith("%")
                                      else name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace", help="an .xplane.pb to split")
    ap.add_argument("--hlo", help="directory of optimized-HLO text dumps")
    ap.add_argument("--record", metavar="DIR",
                    help="record one traced batch of a cell into DIR")
    ap.add_argument("--workload", default="pincell.source")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--moves", type=int, default=None,
                    help="moves to trace (default: the traffic's batch)")
    ap.add_argument("--cache", help="persistent compile cache directory")
    ap.add_argument("--cut", metavar="TRACE", help="trace to cut")
    ap.add_argument("--out", help="the cut copy's path")
    ap.add_argument("--ops-ms", type=float, default=2.0)
    args = ap.parse_args(argv)
    if args.record:
        dump_hlo_to(os.path.join(args.record, "hlo"))
        moves = args.moves
        if moves is None:
            from benchmark import run

            b = run.Bench.load()
            moves = b.json("traffic", b.cell(args.workload)["traffic"]
                           + ".json")["batch_moves"]
        result = record(args.record, args.workload, args.seed, moves,
                        args.cache)
    elif args.cut:
        size = cut(args.cut, args.out, args.hlo, args.ops_ms)
        result = {"out": args.out, "bytes": size}
    elif args.trace:
        result = split(args.trace, args.hlo)
    else:
        ap.error("give --trace, --record or --cut")
    print(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
