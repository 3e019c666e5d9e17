#!/usr/bin/env python
"""Bring-up smoke of the tally's main path on a TPU.

One process runs every phase in order; any failed check exits non-zero.

  python chip_smoke.py             # one chip: phases A-D
  python chip_smoke.py --chips 4   # four chips: the partitioned phase only

Phases (one JSON line each on stdout; the last line is the verdict):

  A  event loop, BASELINE config 2 at full width (998,250 tets,
     1,048,576 lanes, 8 groups, f32): initialize_particle_location and
     3 x move_to_next_location with host arrays, the reference's calling
     pattern (cpp:221-264). Checks track-length conservation, flying,
     finiteness and the written-back material ids.
  B  the megastep (run_source_moves) on the same mesh and width,
     device-sourced; checks its on-device conservation ledger.
  C  the Pallas kernel at config 1 (10,368 tets, 4,096 lanes, 1 group):
     kernel="auto" must resolve to it; one move against the XLA walk on
     identical inputs.
  D  the served path: the scheduler + AOT program bank the way
     ``scripts/serve.py --demo`` drives them, twice over one fixed bank
     directory; the second pass must compile nothing.
  P  (--chips 4) PartitionedTally over 4 chips against a single-chip
     PumiTally on device 0, same particles and moves as phase A.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

CONSERVATION_RTOL = 1e-4
MEAN_PATH = 0.08


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def check(phase: str, name: str, ok: bool, **values) -> None:
    """A failed check ends the run: no phase is allowed to go on."""
    if not ok:
        emit(phase, check=name, ok=False, **values)
        raise SystemExit(f"[{phase}] check {name!r} failed: {values}")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and its
    persistent-cache lookups, from its own monitoring events (a cache
    hit adds no backend compile)."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )
    CACHE = {
        "/jax/compilation_cache/compile_requests_use_cache": "lookups",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "writes",
    }

    def __init__(self):
        import jax

        self.total = 0.0
        self.cache = dict.fromkeys(self.CACHE.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration

    def _on_event(self, event, **_):
        if event in self.CACHE:
            self.cache[self.CACHE[event]] += 1

    def mark(self):
        return self.total, dict(self.cache)

    def since(self, mark):
        """Compile seconds and cache lookups since ``mark()``."""
        total, cache = mark
        return {
            "compile_s": self.total - total,
            "cache": {k: v - cache[k] for k, v in self.cache.items()},
        }


def dir_size(path):
    """(files, bytes) under ``path``: what the compile cache held."""
    files = size = 0
    for root, _, names in os.walk(path):
        for name in names:
            files += 1
            size += os.path.getsize(os.path.join(root, name))
    return files, size


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def box_particles(mesh, n, rng):
    """Host f64 start positions at the centroids of random elements."""
    cents = np.asarray(mesh.centroids(), np.float64)
    return cents[rng.integers(0, mesh.ntet, n)]


def next_dest(prev, rng):
    """Destinations about MEAN_PATH away, clipped into the box (the
    bench's move generator)."""
    d = rng.normal(0.0, 1.0, prev.shape)
    d /= np.linalg.norm(d, axis=1, keepdims=True)
    length = rng.exponential(MEAN_PATH, (prev.shape[0], 1))
    return np.clip(prev + d * length, 0.01, 0.99)


def event_loop(tally, pos0, groups, moves, rng, phase):
    """Drive the 4-call contract with host arrays; return per-call wall
    seconds and segments, the host f64 path length, the final positions
    and the written-back material ids."""
    n = pos0.shape[0]
    t0 = time.perf_counter()
    tally.initialize_particle_location(pos0.reshape(-1).copy())
    walls = {"init": time.perf_counter() - t0}
    segs = {}
    prev = pos0.copy()
    path = 0.0
    mats = np.full(n, -1, np.int32)
    for i in range(moves):
        buf = next_dest(prev, rng).reshape(-1).copy()
        flying = np.ones(n, np.int8)
        t0 = time.perf_counter()
        tally.move_to_next_location(
            buf, flying, np.ones(n), groups, mats
        )
        walls[f"move{i + 1}"] = time.perf_counter() - t0
        segs[f"move{i + 1}"] = int(tally.total_segments) - sum(segs.values())
        final = buf.reshape(n, 3)
        check(phase, "finite_positions", bool(np.isfinite(final).all()))
        check(phase, "flying_zero", not flying.any(),
              still_flying=int(np.count_nonzero(flying)))
        path += float(np.linalg.norm(final - prev, axis=1).sum())
        prev = final.copy()
    return walls, segs, path, prev, mats


def check_mats(phase, mesh, mats):
    allowed = set(np.asarray(mesh.class_values).tolist()) | {-1}
    bad = set(np.unique(mats).tolist()) - allowed
    check(phase, "material_ids_in_range", not bad, bad=sorted(bad),
          allowed=sorted(allowed))


def check_conservation(phase, scored, path):
    rel = abs(scored - path) / max(path, 1e-30)
    check(phase, "conservation", rel <= CONSERVATION_RTOL,
          scored=scored, path=path, rel=rel, limit=CONSERVATION_RTOL)
    return rel


# --------------------------------------------------------------------- #
def phase_a(mesh, n, n_groups, seed, clock, dev):
    from pumiumtally_tpu import PumiTally, TallyConfig
    from pumiumtally_tpu.ops.walk import resolve_tally_scatter

    rng = np.random.default_rng(seed)
    c0 = clock.mark()
    tally = PumiTally(mesh, n, TallyConfig(n_groups=n_groups))
    scatter = resolve_tally_scatter("auto", tally.flux)
    check("A", "kernel_xla", tally._kernel == "xla", kernel=tally._kernel)
    pos0 = box_particles(mesh, n, rng)
    groups = rng.integers(0, n_groups, n).astype(np.int32)
    t0 = time.perf_counter()
    walls, segs, path, _, mats = event_loop(tally, pos0, groups, 3, rng,
                                            "A")
    wall = time.perf_counter() - t0
    raw = tally.raw_flux
    check("A", "finite_flux", bool(np.isfinite(raw).all()))
    scored = float(raw[..., 0].astype(np.float64).sum())
    rel = check_conservation("A", scored, path)
    check_mats("A", mesh, mats)
    nflux = tally.normalized_flux()
    check("A", "finite_normalized_flux", bool(np.isfinite(nflux).all()))
    steady_s = walls["move2"] + walls["move3"]
    steady_segs = segs["move2"] + segs["move3"]
    emit(
        "A", ok=True, device_kind=dev.device_kind, ntet=mesh.ntet,
        particles=n, groups=n_groups, kernel=tally._kernel,
        tally_scatter=scatter, **clock.since(c0), wall_s=wall,
        call_wall_s=walls, call_segments=segs,
        segments=int(tally.total_segments),
        segments_per_s_moves_2_3=steady_segs / steady_s,
        scored_flux=scored, host_path=path, conservation_rel=rel,
        conservation_limit=CONSERVATION_RTOL,
        peak_bytes_in_use=peak_bytes(dev),
    )
    tally.close()


def phase_b(mesh, n, n_groups, moves, seed, clock, dev):
    from pumiumtally_tpu import PumiTally, TallyConfig
    from pumiumtally_tpu.ops.source import SourceParams

    rng = np.random.default_rng(seed + 1)
    c0 = clock.mark()
    tally = PumiTally(
        mesh, n,
        TallyConfig(n_groups=n_groups, integrity="warn", megastep=moves),
    )
    tally.initialize_particle_location(
        box_particles(mesh, n, rng).reshape(-1).copy()
    )
    before = float(tally.raw_flux[..., 0].astype(np.float64).sum())
    seq0 = tally._telemetry.recorder.total_recorded
    t0 = time.perf_counter()
    res = tally.run_source_moves(
        moves,
        SourceParams(default_sigma_t=1.0 / MEAN_PATH, seed=seed),
        weights=np.ones(n),
        groups=rng.integers(0, n_groups, n).astype(np.int32),
        alive=np.ones(n, bool),
    )
    wall = time.perf_counter() - t0
    raw = tally.raw_flux
    check("B", "finite_flux", bool(np.isfinite(raw).all()))
    scored = float(raw[..., 0].astype(np.float64).sum()) - before
    ledger = [
        r for r in tally._telemetry.recorder.records()
        if r["kind"] == "integrity" and r["seq"] >= seq0
    ]
    check("B", "ledger_recorded", bool(ledger), records=len(ledger))
    path = sum(r["path_wlen"] for r in ledger)
    device_scored = sum(r["scored_wlen"] for r in ledger)
    violations = sorted({v for r in ledger for v in r["violations"]})
    check("B", "no_violations", not violations, violations=violations)
    check("B", "no_truncation", res["truncated"] == 0,
          truncated=res["truncated"])
    rel = check_conservation("B", scored, path)
    segs = int(res["segments"])
    emit(
        "B", ok=True, device_kind=dev.device_kind, ntet=mesh.ntet,
        particles=n, groups=n_groups, moves=res["moves"],
        megastep_k=tally.config.resolve_megastep(),
        **clock.since(c0), wall_s=wall, segments=segs,
        segments_per_s=segs / wall, collisions=res["collisions"],
        escaped=res["escaped"], scored_flux=scored,
        device_scored_wlen=device_scored, device_path_wlen=path,
        conservation_rel=rel, conservation_limit=CONSERVATION_RTOL,
        peak_bytes_in_use=peak_bytes(dev),
    )
    tally.close()


def phase_c(seed, clock, dev):
    """The Pallas kernel against the XLA walk on identical inputs."""
    import jax
    import jax.numpy as jnp

    from pumiumtally_tpu import PumiTally, TallyConfig, build_box
    from pumiumtally_tpu.ops.walk_pallas import (
        kernel_vmem_bytes,
        select_backend,
    )

    n, n_groups = 4096, 1
    mesh = build_box(1.0, 1.0, 1.0, 12, 12, 12, dtype=jnp.float32)
    vmem = kernel_vmem_bytes(mesh.ntet, n, n_groups, 4)
    auto = select_backend(
        "auto", ntet=mesh.ntet, n_particles=n, n_groups=n_groups,
        dtype=jnp.float32, packed=mesh.geo20 is not None,
        platform=jax.default_backend(),
    )
    check("C", "auto_selects_pallas", auto == "pallas", resolved=auto,
          vmem_mib=vmem / 2**20)
    rng = np.random.default_rng(seed + 2)
    pos0 = box_particles(mesh, n, rng)
    dest = next_dest(pos0, rng)
    groups = np.zeros(n, np.int32)
    out = {}
    c0 = clock.mark()
    t0 = time.perf_counter()
    for kernel in ("auto", "xla"):
        tally = PumiTally(
            mesh, n,
            # compact_after=None: the XLA walk runs its flat loop, the
            # kernel's schedule, so the two walks are comparable lane
            # for lane.
            TallyConfig(n_groups=n_groups, kernel=kernel,
                        compact_after=None),
        )
        tally.initialize_particle_location(pos0.reshape(-1).copy())
        buf = dest.reshape(-1).copy()
        flying = np.ones(n, np.int8)
        mats = np.full(n, -1, np.int32)
        tally.move_to_next_location(buf, flying, np.ones(n), groups, mats)
        check("C", f"flying_zero_{kernel}", not flying.any())
        out[tally._kernel] = (
            buf.reshape(n, 3), tally.element_ids.copy(), tally.raw_flux,
            mats,
        )
        tally.close()
    wall = time.perf_counter() - t0
    check("C", "ran_both", set(out) == {"pallas", "xla"}, ran=sorted(out))
    (pp, pe, pf, pm), (xp, xe, xf, xm) = out["pallas"], out["xla"]
    pos_diff = float(np.abs(pp - xp).max())
    elem_mismatch = int(np.count_nonzero(pe != xe))
    mat_mismatch = int(np.count_nonzero(pm != xm))
    flux_diff = float(np.abs(pf - xf).max())
    flux_rel = flux_diff / max(float(np.abs(xf).max()), 1e-30)
    emit(
        "C", device_kind=dev.device_kind, ntet=mesh.ntet, particles=n,
        groups=n_groups, vmem_mib=vmem / 2**20, auto_resolves=auto,
        **clock.since(c0), wall_s=wall,
        max_abs_position_diff=pos_diff, element_mismatches=elem_mismatch,
        material_mismatches=mat_mismatch, max_abs_flux_diff=flux_diff,
        max_rel_flux_diff=flux_rel,
        scored_pallas=float(pf[..., 0].astype(np.float64).sum()),
        scored_xla=float(xf[..., 0].astype(np.float64).sum()),
    )
    check("C", "elements_equal", elem_mismatch == 0,
          mismatches=elem_mismatch)
    check("C", "materials_equal", mat_mismatch == 0,
          mismatches=mat_mismatch)
    check("C", "positions_equal", pos_diff == 0.0, max_abs=pos_diff)
    check("C", "flux_equal", flux_rel <= 1e-6, max_rel=flux_rel,
          limit=1e-6)
    emit("C", ok=True)


def phase_d(bank_dir, seed, clock, dev):
    """Four jobs of one shape class through the scheduler and the bank,
    twice; the second pass loads every program from the bank."""
    import jax.numpy as jnp

    from pumiumtally_tpu import TallyConfig, build_box
    from pumiumtally_tpu.serving import run_saturation

    mesh = build_box(1.0, 1.0, 1.0, 12, 12, 12, dtype=jnp.float32)
    cfg = TallyConfig(n_groups=2, tolerance=1e-6)
    # Pass 1 starts from an empty bank, whatever an earlier run left.
    shutil.rmtree(bank_dir, ignore_errors=True)
    passes = []
    for p in (1, 2):
        c0 = clock.mark()
        t0 = time.perf_counter()
        out = run_saturation(
            mesh, cfg, bank=bank_dir, n_jobs=4, class_sizes=(1024,),
            n_moves=8, seed=seed, max_resident=2, quantum_moves=4,
        )
        wall = time.perf_counter() - t0
        outcomes = [r["outcome"] for r in out["per_job"]]
        check("D", f"pass{p}_completed",
              all(o == "completed" for o in outcomes), outcomes=outcomes)
        aot = out["scheduler"]["aot"]
        passes.append(out)
        emit(
            "D", device_kind=dev.device_kind, pass_=p, bank=bank_dir,
            jobs=len(outcomes), outcomes=outcomes,
            bank_compiles=aot["misses"] + aot["rewrites"],
            bank_hits=aot["hits"], bank_compile_s=aot["compile_seconds"],
            **clock.since(c0), wall_s=wall,
            jobs_per_s=out["jobs_per_sec"],
            peak_bytes_in_use=peak_bytes(dev),
        )
    aot2 = passes[1]["scheduler"]["aot"]
    check("D", "pass2_zero_compiles",
          aot2["misses"] + aot2["rewrites"] == 0 and aot2["hits"] > 0,
          aot=aot2)
    r1, r2 = passes[0]["results"], passes[1]["results"]
    same = set(r1) == set(r2) and all(
        np.array_equal(np.asarray(r1[k]), np.asarray(r2[k])) for k in r1
    )
    check("D", "bank_results_bitwise", same)
    emit("D", ok=True)


def phase_partitioned(mesh, n, n_groups, seed, clock, devices):
    """PartitionedTally on 4 chips against PumiTally on device 0, same
    particles, same 3 moves."""
    import jax

    from pumiumtally_tpu import PumiTally, TallyConfig
    from pumiumtally_tpu.parallel.partitioned_api import PartitionedTally

    cfg = TallyConfig(n_groups=n_groups)
    results = {}
    c0 = clock.mark()
    for name in ("single", "partitioned"):
        m = clock.mark()
        rng = np.random.default_rng(seed)
        if name == "single":
            with jax.default_device(devices[0]):
                tally = PumiTally(mesh, n, cfg)
        else:
            tally = PartitionedTally(mesh, n, cfg, n_parts=4,
                                     halo_layers=2)
        pos0 = box_particles(mesh, n, rng)
        groups = rng.integers(0, n_groups, n).astype(np.int32)
        t0 = time.perf_counter()
        walls, segs, path, final, mats = event_loop(
            tally, pos0, groups, 3, rng, "P"
        )
        raw = tally.raw_flux
        check("P", f"finite_flux_{name}", bool(np.isfinite(raw).all()))
        scored = float(raw[..., 0].astype(np.float64).sum())
        rel = check_conservation("P", scored, path)
        check_mats("P", mesh, mats)
        dropped = [
            r.get("dropped", 0)
            for r in tally._telemetry.recorder.records()
        ]
        check("P", f"no_drops_{name}", not any(dropped),
              dropped=sum(x or 0 for x in dropped))
        results[name] = (raw, final, scored, rel)
        if name == "partitioned":
            # While the parts are alive: each of the 4 chips holds one.
            mem = {
                str(d.id): {
                    k: (d.memory_stats() or {}).get(k)
                    for k in ("bytes_in_use", "peak_bytes_in_use")
                }
                for d in devices[:4]
            }
        emit(
            "P", facade=name, device_kind=devices[0].device_kind,
            chips=4 if name == "partitioned" else 1, ntet=mesh.ntet,
            particles=n, groups=n_groups, **clock.since(m),
            wall_s=time.perf_counter() - t0,
            call_wall_s=walls, call_segments=segs,
            scored_flux=scored, host_path=path, conservation_rel=rel,
        )
        tally.close()
    s_raw, s_final, s_scored, _ = results["single"]
    p_raw, p_final, p_scored, _ = results["partitioned"]
    s_elem = s_raw[..., 0].astype(np.float64).sum(axis=1)
    p_elem = p_raw[..., 0].astype(np.float64).sum(axis=1)
    diff = np.abs(p_elem - s_elem)
    # Per-element sums agree to CONSERVATION_RTOL of themselves, or of
    # the mean element flux. The robust walk leaves each zero-progress
    # bump unscored, up to 32 ulps of the coordinate scale (``hop``,
    # ops/walk.py forward_bump: 7.6e-6 in the unit box), and the two
    # walks meet different degenerate crossings. The floor (2.4e-5 at
    # config 2) holds three such hops in one element; a misplaced
    # segment (~0.08 long) is thousands of times above it. worst_by_*
    # print the chip's readings against this bound.
    mean = float(s_elem.mean())
    floor = CONSERVATION_RTOL * mean
    limit = CONSERVATION_RTOL * s_elem + floor
    off = diff > limit
    rel = diff / np.maximum(s_elem, 1e-30)
    total_rel = abs(p_scored - s_scored) / s_scored
    hop = 32 * float(np.finfo(np.float32).eps) * (
        1.0 + float(np.abs(s_final).max())
    )

    def worst(key):
        """The chip's own readings for the 5 elements worst by ``key``."""
        return [
            {"elem": int(e), "abs_diff": float(diff[e]), "rel": float(rel[e]),
             "flux_single": float(s_elem[e]),
             "flux_partitioned": float(p_elem[e]),
             "limit": float(limit[e])}
            for e in np.argsort(key)[-5:][::-1]
        ]

    emit(
        "P", **clock.since(c0), flux_total_rel=total_rel,
        flux_max_elem_abs_diff=float(diff.max()),
        flux_max_elem_rel=float(rel.max()),
        flux_elem_abs_diff_rms=float(np.sqrt(np.mean(diff**2))),
        flux_mean_elem=mean, flux_elem_floor=floor, hop=hop,
        flux_max_elem_abs_diff_hops=float(diff.max()) / hop,
        flux_elems_over_rtol=int((diff > CONSERVATION_RTOL * s_elem).sum()),
        flux_elems_off=int(off.sum()),
        flux_min_margin=float((limit / np.maximum(diff, 1e-30)).min()),
        worst_by_rel=worst(rel), worst_by_abs=worst(diff),
        max_abs_position_diff=float(np.abs(p_final - s_final).max()),
        memory_stats=mem,
    )
    check("P", "flux_total_matches", total_rel <= CONSERVATION_RTOL,
          rel=total_rel, limit=CONSERVATION_RTOL)
    check("P", "flux_per_element_matches", not off.any(),
          elements_off=int(off.sum()), rtol=CONSERVATION_RTOL,
          floor=floor)
    check("P", "all_chips_hold_a_part",
          all((m["bytes_in_use"] or 0) > 0 for m in mem.values()),
          memory=mem)
    emit("P", ok=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax
    import jax.numpy as jnp

    from pumiumtally_tpu.utils.platform import (
        DEFAULT_BANK_DIR,
        require_tpu,
        use_compile_cache,
    )

    devices = require_tpu()
    dev = devices[0]
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} but JAX sees "
                         f"{len(devices)} device(s)")

    from pumiumtally_tpu import build_box

    cache = use_compile_cache()
    clock = CompileClock()
    files, size = dir_size(cache)
    emit("setup", platform=dev.platform, device_kind=dev.device_kind,
         devices=len(devices), chips=args.chips, compile_cache=cache,
         cache_files_at_start=files, cache_bytes_at_start=size,
         jax=jax.__version__)
    t0 = time.perf_counter()
    mesh = build_box(1.0, 1.0, 1.0, 55, 55, 55, dtype=jnp.float32)
    emit("mesh", ntet=mesh.ntet, build_s=time.perf_counter() - t0)
    n, n_groups = 1_048_576, 8
    if args.chips == 4:
        phase_partitioned(mesh, n, n_groups, args.seed, clock, devices)
    else:
        phase_a(mesh, n, n_groups, args.seed, clock, dev)
        phase_b(mesh, n, n_groups, 4, args.seed, clock, dev)
        del mesh
        phase_c(args.seed, clock, dev)
        phase_d(os.path.join(DEFAULT_BANK_DIR, "chip_smoke"), args.seed,
                clock, dev)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": args.chips,
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
