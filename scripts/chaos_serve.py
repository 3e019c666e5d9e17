"""Serving chaos campaign: fault-isolated multi-tenant scheduling
under fire, each scenario with a DECLARED outcome.

Every scenario drives a mixed-class job fleet through the
``TallyScheduler`` (serving/scheduler.py) with a composed per-job
fault schedule (resilience/faultinject.py: poison_job /
transient_quantum / kill_server_at_quantum) and asserts the serving
contracts:

  * **isolation** — a poison job finishes ``outcome="poisoned"`` and
    EVERY other job's flux is bitwise-identical to the fault-free
    reference (jobs are facade-isolated; one bad request never taints
    a neighbor);
  * **bitwise replay** — a transient quantum is absorbed by the
    bounded per-job retry, flux bitwise vs fault-free;
  * **crash-safe recovery** — a mid-run server KILL (subprocess
    scenario: scripts/serve.py dies on the injected kill) followed by
    a ``--resume`` restart loses ZERO jobs: every job reaches a
    terminal outcome, unaffected fluxes are bitwise vs the fault-free
    reference, and the restarted process compiles NO program family
    (the AOT bank is warm — summary ``aot.misses == 0``);
  * **postmortem trace** — every scenario leaves at least one readable
    black-box dump (obs/trace.py span ring, atomically written), and
    in kill_restart EVERY job — including the poisoned one — passes
    ``teleview.py --job <id> --check`` against the journal directory:
    a single causally-ordered trace spanning BOTH process lifetimes,
    stitched by the persisted trace_id + ``recovered`` link.  The
    kill_restart reference run serves with ``PUMI_TPU_TRACE=off``, so
    its bitwise flux comparison doubles as the tracing-on-vs-off
    physics-parity gate.

Scenarios (run all by default; ``--only NAME`` to pick one,
``--list`` to enumerate):

  poison_isolation   one poison job in a mixed-class fleet;
  transient_replay   one transient quantum, retried bitwise;
  storm              poison + transient composed in one fleet;
  kill_restart       fault storm + server kill + journal recovery
                     (subprocess: serve.py --journal/--resume).

Usage: python scripts/chaos_serve.py [--jobs N] [--only NAME] [--list]
Exit code 0 = every scenario met its declared contract.
"""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "scripts"))

from teleview import check_job_trace, job_trace, load_trace_records

import numpy as np

import jax

jax.config.update("jax_platforms", "cpu")

from pumiumtally_tpu import TallyConfig, build_box
from pumiumtally_tpu.resilience import ChaosInjector, ChaosPlan
from pumiumtally_tpu.serving import run_saturation

CELLS = 2
CLASSES = (40, 100)
N_MOVES = 8     # a multiple of QUANTUM: resumed chunks reuse the same
QUANTUM = 4     # compiled megastep-K entry (zero-compile restart pin)
SEED = 3


def build():
    mesh = build_box(1.0, 1.0, 1.0, CELLS, CELLS, CELLS)
    cfg = TallyConfig(tolerance=1e-6)
    return mesh, cfg


def fleet(mesh, cfg, n_jobs, **kw):
    return run_saturation(
        mesh, cfg, n_jobs=n_jobs, class_sizes=CLASSES,
        n_moves=N_MOVES, seed=SEED, max_resident=2,
        quantum_moves=QUANTUM, **kw,
    )


def readable_postmortems(dirpath: str) -> list[str]:
    """Names of the readable black-box dumps in ``dirpath`` (valid
    JSON, ``kind == "blackbox"``, a ``records`` list) — the
    "each scenario produced a readable postmortem" gate."""
    found = []
    if not os.path.isdir(dirpath):
        return found
    for fname in sorted(os.listdir(dirpath)):
        if not fname.endswith(".blackbox.json"):
            continue
        try:
            with open(os.path.join(dirpath, fname)) as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        if doc.get("kind") == "blackbox" and isinstance(
            doc.get("records"), list
        ):
            found.append(fname)
    return found


def check_in_process(name, mesh, cfg, ref, plan, n_jobs,
                     poisoned: set, workdir: str) -> bool:
    """One in-process scenario: run the fleet under the chaos plan and
    assert poisoned-set exactness + survivor bitwise parity + a
    readable black-box postmortem in ``workdir``."""
    out = fleet(
        mesh, cfg, n_jobs, faults=ChaosInjector(plan), job_retries=2,
        blackbox_dir=workdir,
    )
    rows = {r["job"]: r for r in out["per_job"]}
    got_poisoned = {j for j, r in rows.items() if r["outcome"] == "poisoned"}
    want_poisoned = {f"sat-{i:04d}" for i in poisoned}
    ok = got_poisoned == want_poisoned
    survivors_bitwise = True
    for jid, r in rows.items():
        if jid in want_poisoned:
            continue
        if r["outcome"] != "completed":
            survivors_bitwise = False
            break
        if out["results"][jid].tobytes() != ref["results"][jid].tobytes():
            survivors_bitwise = False
            break
    ok = ok and survivors_bitwise
    retries = out["scheduler"]["retries"]
    if plan.transient_quantum is not None:
        ok = ok and retries >= 1
    # Every scenario must leave a readable postmortem: poison paths
    # dump the poisoned job's span ring, and close() always dumps the
    # shutdown black box, so even the fault-absorbed scenarios
    # (transient_replay) leave one.
    dumps = readable_postmortems(workdir)
    ok = ok and len(dumps) >= 1
    if want_poisoned:
        ok = ok and any(
            f.startswith(tuple(want_poisoned)) for f in dumps
        )
    print(
        f"[chaos-serve] {name}: {plan.describe()} | "
        f"poisoned={sorted(got_poisoned)} retries={retries} "
        f"survivors_bitwise={survivors_bitwise} "
        f"postmortems={dumps} "
        f"{'OK' if ok else 'FAIL'}",
        flush=True,
    )
    return ok


def serve_cmd(journal, bank, n_jobs, resume=False):
    cmd = [
        sys.executable, os.path.join(ROOT, "scripts", "serve.py"),
        "--demo", str(n_jobs), "--cells", str(CELLS),
        "--classes", ",".join(map(str, CLASSES)),
        "--moves", str(N_MOVES), "--quantum", str(QUANTUM),
        "--max-resident", "2", "--retries", "2",
        "--seed", str(SEED), "--bank", bank, "--journal", journal,
    ]
    if resume:
        cmd.append("--resume")
    return cmd


def run_serve(journal, bank, n_jobs, faults="", resume=False,
              trace=None):
    env = {
        k: v for k, v in os.environ.items()
        if not k.startswith("PUMI_TPU_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    if faults:
        env["PUMI_TPU_FAULTS"] = faults
    if trace is not None:
        # The reference run serves with tracing off so its flux
        # comparison doubles as the tracing-on/off bitwise gate.
        env["PUMI_TPU_TRACE"] = trace
    proc = subprocess.run(
        serve_cmd(journal, bank, n_jobs, resume=resume),
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT,
    )
    summary = None
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            summary = json.loads(line).get("summary")
            break
        except (json.JSONDecodeError, AttributeError):
            continue
    return proc, summary


def check_kill_restart(name, tmpdir, n_jobs) -> bool:
    """The acceptance scenario: a fault storm (poison + transient) plus
    a mid-run server kill, then a --resume restart over the same
    journal and warm bank.  Zero jobs lost, unaffected fluxes bitwise,
    zero program-family compiles in the restarted process."""
    bank = os.path.join(tmpdir, "bank")
    ref_j = os.path.join(tmpdir, "ref-journal")
    j = os.path.join(tmpdir, "journal")
    # Fault-free reference: also populates the AOT bank and persists
    # per-job fluxes beside its own journal.  Tracing is OFF here —
    # the bitwise comparison below is then the tracing-on-vs-off
    # physics-parity acceptance gate too.
    ref_proc, ref_sum = run_serve(ref_j, bank, n_jobs, trace="off")
    if ref_proc.returncode != 0:
        print(f"[chaos-serve] {name}: reference run failed "
              f"rc={ref_proc.returncode}\n{ref_proc.stderr[-2000:]}")
        return False
    # The storm: poison job 1, one transient on job 2, server killed
    # before its 4th quantum.
    storm = "poison_job:1,transient_quantum:2,kill_server_at_quantum:4"
    kill_proc, _ = run_serve(j, bank, n_jobs, faults=storm)
    killed = kill_proc.returncode != 0
    # Restart: same fleet, --resume. The poison clause stays (the job
    # is poison because of WHAT it is, not when it runs); the kill
    # clause does not (the 'hardware' recovered).
    res_proc, res_sum = run_serve(
        j, bank, n_jobs, faults="poison_job:1", resume=True
    )
    if res_proc.returncode != 3 or res_sum is None:
        print(f"[chaos-serve] {name}: restart rc={res_proc.returncode} "
              f"(want 3)\n{res_proc.stderr[-2000:]}")
        return False
    with open(os.path.join(j, "JOBS.json")) as fh:
        jobs = json.load(fh)["jobs"]
    poisoned = {i for i, e in jobs.items() if e["outcome"] == "poisoned"}
    terminal = all(e["state"] == "done" for e in jobs.values())
    zero_compiles = (res_sum["aot"] or {}).get("misses", -1) == 0
    recovered = res_sum.get("recovered", 0) > 0
    bitwise = True
    n_compared = 0
    for jid, e in jobs.items():
        if jid in poisoned:
            continue
        if e["outcome"] != "completed":
            bitwise = False
            break
        got = np.load(os.path.join(j, f"{jid}.flux.npy"))
        want = np.load(os.path.join(ref_j, f"{jid}.flux.npy"))
        if got.tobytes() != want.tobytes():
            bitwise = False
            break
        n_compared += 1
    # The postmortem/trace acceptance gate: from the journal dir alone
    # (TRACE.jsonl + black-box dumps), EVERY job — the poisoned one
    # included — must reconstruct as one causally-ordered trace
    # spanning both process lifetimes (teleview --job <id> --check).
    dumps = readable_postmortems(j)
    records = load_trace_records(j)
    trace_problems = []
    for jid in jobs:
        for p in check_job_trace(job_trace(records, jid), jid):
            trace_problems.append(f"{jid}: {p}")
    traced = not trace_problems
    ok = (
        killed and terminal and zero_compiles and recovered
        and bitwise and poisoned == {"sat-0001"}
        and len(jobs) == n_jobs and traced and len(dumps) >= 1
    )
    for p in trace_problems:
        print(f"[chaos-serve] {name}: trace check: {p}", flush=True)
    print(
        f"[chaos-serve] {name}: {storm} | killed={killed} "
        f"jobs={len(jobs)} poisoned={sorted(poisoned)} "
        f"recovered={res_sum.get('recovered')} "
        f"aot_misses={(res_sum['aot'] or {}).get('misses')} "
        f"bitwise({n_compared} survivors)={bitwise} "
        f"traces({len(jobs)} jobs)={traced} postmortems={dumps} "
        f"{'OK' if ok else 'FAIL'}",
        flush=True,
    )
    return ok


SCENARIOS = {
    "poison_isolation": (ChaosPlan(poison_job=1), {1}),
    "transient_replay": (ChaosPlan(transient_quantum=0), set()),
    "storm": (ChaosPlan(poison_job=2, transient_quantum=0), {2}),
    "kill_restart": None,  # subprocess scenario
}


def main() -> int:
    import tempfile

    args = sys.argv[1:]
    n_jobs = 6
    if "--jobs" in args:
        i = args.index("--jobs")
        n_jobs = int(args[i + 1])
        del args[i:i + 2]
    if "--list" in args:
        for name in SCENARIOS:
            print(name)
        return 0
    names = list(SCENARIOS)
    if "--only" in args:
        i = args.index("--only")
        names = [args[i + 1]]
        del args[i:i + 2]
    mesh, cfg = build()
    ref = None
    fails = 0
    with tempfile.TemporaryDirectory(prefix="chaos_serve_") as tmpdir:
        for name in names:
            if SCENARIOS[name] is None:
                ok = check_kill_restart(name, tmpdir, n_jobs)
            else:
                if ref is None:
                    ref = fleet(mesh, cfg, n_jobs)
                plan, poisoned = SCENARIOS[name]
                workdir = os.path.join(tmpdir, name)
                os.makedirs(workdir, exist_ok=True)
                ok = check_in_process(
                    name, mesh, cfg, ref, plan, n_jobs, poisoned,
                    workdir,
                )
            fails += 0 if ok else 1
    print(
        "SERVING CHAOS CAMPAIGN",
        "PASS" if fails == 0 else f"{fails} FAILURES",
    )
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
