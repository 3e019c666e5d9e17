#!/usr/bin/env python
"""Tally-as-a-service entrypoint (ROADMAP item 3).

Stand up the shape-bucketed scheduler over a box mesh with a
persistent AOT program bank and serve a synthetic many-job workload:

  python scripts/serve.py --demo 8                 # 8 jobs, bank at
                                                   # .pumi_bank/serve:
                                                   # run it twice — the
                                                   # second process is
                                                   # the warm, zero-
                                                   # compile regime
  python scripts/serve.py --demo 8 --bank BANK/    # bank elsewhere
  python scripts/serve.py --demo 8 --prom-port 9464  # live /metrics
  python scripts/serve.py --demo 8 --journal J/    # crash-safe journal
  python scripts/serve.py --demo 8 --journal J/ --resume
                                                   # restart a killed
                                                   # server: recover
                                                   # every job from
                                                   # JOBS.json and
                                                   # drain bitwise
  python scripts/serve.py --demo 8 --fleet 3 --port 0 --journal F/
                                                   # multi-chip fleet:
                                                   # N member
                                                   # schedulers behind
                                                   # the HTTP gateway,
                                                   # FLEET.json routing
                                                   # journal in F/
                                                   # (--resume recovers
                                                   # the whole fleet)

The demo drives the SAME ``run_saturation`` workload driver bench.py's
``BENCH_SERVE`` probe uses, so the printed ``jobs_per_sec`` is
directly comparable to the committed bench rows.  The full JSON lands
on stdout (and ``--out`` when given), followed by one compact
per-outcome summary line (the last stdout line is always valid JSON).

Exit codes:
  0  every job completed or converged;
  3  some jobs poisoned (persistent per-job failure isolated) or
     rejected (admission backpressure) — the SERVER stayed healthy;
  1  anything else (crash, injected server kill, unfinished jobs).

The scheduler admits up to ``--max-resident`` jobs (and at most
``--max-queued`` waiting), time-slices at megastep ``--quantum``
granularity, replays transient quanta up to ``--retries`` times from
per-job snapshots, arms a ``--deadline`` watchdog around every
quantum, evicts converged jobs early when ``--convergence`` is set,
and checkpoint-preempts long residents when ``--preempt-after`` is
set.  ``--bank off`` serves from the jit path (every fresh process
pays compile cost — the baseline the bank exists to beat).  Per-job
fault injection (poison_job / transient_quantum /
kill_server_at_quantum) rides the ``PUMI_TPU_FAULTS`` env.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

#: Outcomes that leave the exit code at 0.
GOOD = ("completed", "converged")
#: Outcomes that mean "job failed / shed / was told to stop, server
#: healthy" — exit 3.
ISOLATED = ("poisoned", "rejected", "cancelled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--demo", type=int, default=8, metavar="N_JOBS",
                    help="serve N synthetic jobs and exit (default 8)")
    ap.add_argument("--cells", type=int, default=4,
                    help="box subdivisions per axis (ntet = 6*cells^3)")
    ap.add_argument("--groups", type=int, default=2)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--bank", default=None, metavar="DIR|off",
                    help="AOT program-bank root (default: "
                         ".pumi_bank/serve in the checkout; 'off' = "
                         "jit path)")
    ap.add_argument("--classes", default="96,192",
                    help="comma list of request particle counts (each "
                         "pads to its own shape bucket)")
    ap.add_argument("--moves", type=int, default=8,
                    help="device-sourced moves per job")
    ap.add_argument("--quantum", type=int, default=4,
                    help="megastep moves per scheduling quantum")
    ap.add_argument("--max-resident", type=int, default=2)
    ap.add_argument("--max-queued", type=int, default=None,
                    help="admission backpressure: submissions beyond "
                         "this queue depth finish outcome=rejected")
    ap.add_argument("--retries", type=int, default=2,
                    help="bounded per-quantum transient replays before "
                         "a job is poisoned")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-quantum dispatch watchdog deadline "
                         "(seconds); a timeout classifies as transient")
    ap.add_argument("--journal", default=None, metavar="DIR",
                    help="crash-safe JOBS.json write-ahead journal "
                         "directory (enables --resume)")
    ap.add_argument("--resume", action="store_true",
                    help="recover the job table from --journal before "
                         "serving (the restart path of a killed server)")
    ap.add_argument("--preempt-after", type=int, default=None,
                    help="quanta before a resident job yields its slot "
                         "to queued work (checkpoint preemption)")
    ap.add_argument("--convergence", action="store_true",
                    help="enable convergence observability + early "
                         "eviction at the target precision")
    ap.add_argument("--rel-err-target", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prom-port", type=int, default=None,
                    help="serve live Prometheus /metrics on this port")
    ap.add_argument("--fleet", type=int, default=None, metavar="N",
                    help="serve through a FleetRouter with N member "
                         "schedulers behind the HTTP gateway (the "
                         "multi-chip path; --journal names the fleet "
                         "directory)")
    ap.add_argument("--port", type=int, default=0, metavar="P",
                    help="gateway ingress port with --fleet "
                         "(default 0: ephemeral)")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args()

    if args.prom_port is not None:
        os.environ["PUMI_TPU_PROM_PORT"] = str(args.prom_port)
    if args.resume and not args.journal:
        ap.error("--resume needs --journal DIR")
    if args.fleet is not None and args.fleet < 1:
        ap.error("--fleet needs at least one member")

    from pumiumtally_tpu import TallyConfig, build_box
    from pumiumtally_tpu.serving import (
        run_fleet_saturation,
        run_saturation,
    )
    from pumiumtally_tpu.utils.platform import (
        DEFAULT_BANK_DIR,
        use_compile_cache,
    )

    use_compile_cache()

    mesh = build_box(
        1.0, 1.0, 1.0, args.cells, args.cells, args.cells,
        dtype=args.dtype,
    )
    cfg = TallyConfig(
        n_groups=args.groups, dtype=args.dtype, tolerance=1e-6,
        convergence=args.convergence,
        rel_err_target=args.rel_err_target,
    )
    # The bank rides as a PATH: the scheduler then constructs it on
    # its own registry, so the pumi_aot_* counters land on the same
    # Prometheus endpoint as the job metrics.
    tmp_ck = None
    if args.bank == "off":
        bank = None
    else:
        bank = args.bank or os.path.join(DEFAULT_BANK_DIR, "serve")
    ck_dir = None
    if (args.preempt_after is not None and args.journal is None
            and args.fleet is None):
        tmp_ck = ck_dir = tempfile.mkdtemp(prefix="pumi_serve_ck_")
    tmp_fleet = None
    if args.fleet is not None and args.journal is None:
        tmp_fleet = tempfile.mkdtemp(prefix="pumi_fleet_")
    try:
        if args.fleet is not None:
            out = run_fleet_saturation(
                mesh, cfg, bank=bank, n_jobs=args.demo,
                fleet_dir=args.journal or tmp_fleet,
                n_members=args.fleet, port=args.port,
                class_sizes=tuple(
                    int(x) for x in args.classes.split(",")
                ),
                n_moves=args.moves, seed=args.seed,
                resume=args.resume,
                max_resident=args.max_resident,
                quantum_moves=args.quantum,
                preempt_after=args.preempt_after,
                max_queued=args.max_queued,
                job_retries=args.retries,
                quantum_deadline_s=args.deadline,
            )
        else:
            out = run_saturation(
                mesh, cfg, bank=bank, n_jobs=args.demo,
                class_sizes=tuple(
                    int(x) for x in args.classes.split(",")
                ),
                n_moves=args.moves, seed=args.seed,
                max_resident=args.max_resident,
                quantum_moves=args.quantum,
                preempt_after=args.preempt_after,
                checkpoint_dir=ck_dir,
                max_queued=args.max_queued,
                job_retries=args.retries,
                quantum_deadline_s=args.deadline,
                journal_dir=args.journal,
                resume=args.resume,
            )
    finally:
        for d in (tmp_ck, tmp_fleet):
            if d is not None:
                shutil.rmtree(d, ignore_errors=True)
    out.pop("results")  # raw flux arrays — not JSON material
    text = json.dumps(out, indent=1, sort_keys=True)
    print(text)
    if args.out:
        # Atomic write (PUMI008): the results file lands beside the
        # journal a restart resumes from — a torn JSON under the real
        # name would read as a corrupt run instead of a missing one.
        from pumiumtally_tpu.utils.checkpoint import atomic_write_json

        atomic_write_json(args.out, out)
    outcomes: dict = {}
    for row in out["per_job"]:
        outcomes[row["outcome"]] = outcomes.get(row["outcome"], 0) + 1
    bad = [r for r in out["per_job"] if r["outcome"] not in GOOD]
    if not bad:
        rc = 0
    elif all(r["outcome"] in ISOLATED for r in bad):
        rc = 3  # jobs failed/shed in isolation; the server is healthy
    else:
        rc = 1
    sched = out["fleet"] if args.fleet is not None else out["scheduler"]
    # The per-outcome summary line: always the LAST stdout line,
    # always one valid JSON object (chaos drivers parse it).
    summary = {
        "outcomes": outcomes,
        "jobs": len(out["per_job"]),
        "recovered": sched.get("recovered", 0),
        "retries": sched.get("retries", 0),
        "aot": sched.get("aot"),
        "exit": rc,
    }
    if args.fleet is not None:
        summary["members"] = sched["members"]
        summary["alive"] = sched["alive"]
        summary["placements"] = sched["placements"]
        summary["migrations"] = sched["migrations"]
    print(json.dumps({"summary": summary}, sort_keys=True))
    return rc


if __name__ == "__main__":
    sys.exit(main())
