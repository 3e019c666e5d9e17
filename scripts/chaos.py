"""Chaos campaign driver: multi-fault scenarios against the supervised
partitioned run, each with a DECLARED outcome.

Every scenario drives a small partitioned campaign on the 8-device
virtual CPU mesh through ``ResilientRunner`` with a composed fault
schedule (resilience/faultinject.py) and asserts one of the two
declared contracts:

  * **bitwise replay** — the completed run's flux is bit-identical to
    the fault-free reference on the same layout (transient storms,
    torn-generation fallback + replay, eviction + auto-resume);
  * **graceful degradation** — the run completes on a SHRUNKEN mesh
    and the flux matches the fault-free reference at the shrunk part
    count within the layout-independence tolerance (chip loss, chip
    loss composed with other faults).

Scenarios (run all by default; ``--only NAME`` to pick one,
``--list`` to enumerate):

  transient_storm          three transients at distinct moves;
  chip_down                one chip lost mid-campaign → elastic shrink;
  fault_during_recovery    a transient striking the same move as the
                           chip loss (the post-reshard replay absorbs
                           it);
  torn_generation_resume   the newest generation torn + an eviction:
                           resume must skip it, restore the older one,
                           and replay bitwise;
  corrupt_manifest_chip_down  a torn generation AND a chip loss in one
                           campaign — the shrink anchors on the
                           in-memory last-good state while the torn
                           generation is skipped at the next resume.

Usage: python scripts/chaos.py [--moves M] [--only NAME] [--list]
Exit code 0 = every scenario met its declared contract.
"""
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

if "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

import numpy as np

import jax

jax.config.update("jax_platforms", "cpu")

# f64 end to end: the shrink contract compares flux ACROSS partition
# layouts, where summation-order differences are the only allowed
# delta — the layout-independence tolerance (1e-9) assumes double.
jax.config.update("jax_enable_x64", True)

from pumiumtally_tpu import TallyConfig
from pumiumtally_tpu.mesh.box import build_box_arrays
from pumiumtally_tpu.mesh.core import TetMesh
from pumiumtally_tpu.parallel.partitioned_api import PartitionedTally
from pumiumtally_tpu.resilience import (
    ChaosInjector,
    ChaosPlan,
    InjectedKill,
    ResilientRunner,
)

N = 64
N_PARTS = 8


def build_mesh():
    coords, tets = build_box_arrays(1.0, 1.0, 1.0, 4, 4, 4)
    cid = (coords[tets].mean(1)[:, 0] > 0.5).astype(np.int32)
    return TetMesh.from_numpy(coords, tets, cid, dtype=np.float64)


def _inputs(i):
    r = np.random.default_rng(7000 + i)
    return (
        r.uniform(0.05, 0.95, (N, 3)).ravel().copy(),
        np.ones(N, np.int8),
        r.uniform(0.5, 2.0, N),
        r.integers(0, 2, N).astype(np.int32),
        np.full(N, -1, np.int32),
    )


def _pos():
    return np.random.default_rng(42).uniform(0.1, 0.9, (N, 3)).ravel()


def reference_flux(mesh, n_parts, moves):
    t = PartitionedTally(
        mesh, N, TallyConfig(n_groups=2, dtype=np.float64, tolerance=1e-8),
        n_parts=n_parts,
    )
    t.initialize_particle_location(_pos())
    for i in range(1, moves + 1):
        t.move_to_next_location(*_inputs(i))
    return np.asarray(t.raw_flux, np.float64)


def drive_campaign(mesh, plan, ckdir, moves):
    """One supervised campaign under the chaos plan, transparently
    auto-resuming across evictions (a fresh runner per 'process').
    Returns (final runner, evictions seen)."""
    cfg = TallyConfig(n_groups=2, dtype=np.float64, tolerance=1e-8)
    t = PartitionedTally(mesh, N, cfg, n_parts=N_PARTS)
    run = ResilientRunner(
        t, ckdir, every_moves=1, handle_signals=False,
        sleep=lambda s: None, faults=ChaosInjector(plan),
    )
    evictions = 0
    run.initialize_particle_location(_pos())
    i = 1
    while i <= moves:
        if run.tally.iter_count >= i:
            i += 1
            continue
        try:
            run.move_to_next_location(*_inputs(i))
        except InjectedKill:
            evictions += 1
            t = PartitionedTally(
                mesh, N, cfg, n_parts=run.tally.n_parts
            )
            run = ResilientRunner(
                t, ckdir, every_moves=1, handle_signals=False,
                sleep=lambda s: None,
            )
            continue
        i += 1
    return run, evictions


def check(name, mesh, plan, moves, expect, tmpdir):
    """Run one scenario and assert its declared contract. ``expect`` is
    "bitwise" or ("shrink", expected_parts)."""
    ckdir = os.path.join(tmpdir, name)
    run, evictions = drive_campaign(mesh, plan, ckdir, moves)
    parts = run.tally.n_parts
    got = np.asarray(run.raw_flux, np.float64)
    if expect == "bitwise":
        want_parts, atol = N_PARTS, 0.0
    else:
        # The layout-independence contract's tolerance (f64), the same
        # bound tests/test_elastic.py and the chaos soak pin.
        want_parts, atol = expect[1], 1e-11
    want = reference_flux(mesh, want_parts, moves)
    ok = parts == want_parts and np.allclose(
        got, want, rtol=0, atol=atol
    )
    st = run.recovery_stats
    print(
        f"[chaos] {name}: {plan.describe() or 'no faults'} | "
        f"parts {N_PARTS}->{parts} rollbacks={st['rollbacks']} "
        f"reshards={st['reshards']} evictions={evictions} "
        f"max|dflux|={np.abs(got - want).max():.3e} "
        f"(contract={'bitwise' if expect == 'bitwise' else 'shrink'}) "
        f"{'OK' if ok else 'FAIL'}",
        flush=True,
    )
    return ok


SCENARIOS = {
    # Fault storm: three transients, same layout → bitwise.
    "transient_storm": (
        ChaosPlan(transient_moves=(2, 3, 5)), "bitwise",
    ),
    # One chip down mid-campaign → shrink to 7 parts, physics-equal.
    "chip_down": (
        ChaosPlan(chip_down_move=3), ("shrink", 7),
    ),
    # Fault during recovery: the transient fires on the post-reshard
    # replay of the SAME move.
    "fault_during_recovery": (
        ChaosPlan(transient_moves=(3,), chip_down_move=3),
        ("shrink", 7),
    ),
    # Torn newest generation + eviction: resume skips it, restores the
    # older generation, replays bitwise.
    "torn_generation_resume": (
        ChaosPlan(preempt_move=4, torn_generation=3), "bitwise",
    ),
    # Composition: a torn generation AND a chip loss in one campaign.
    "corrupt_manifest_chip_down": (
        ChaosPlan(chip_down_move=4, torn_generation=2),
        ("shrink", 7),
    ),
}


def main() -> int:
    import tempfile

    args = sys.argv[1:]
    moves = 6
    if "--moves" in args:
        i = args.index("--moves")
        moves = int(args[i + 1])
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
    mesh = build_mesh()
    fails = 0
    with tempfile.TemporaryDirectory(prefix="chaos_") as tmpdir:
        for name in names:
            plan, expect = SCENARIOS[name]
            ok = check(name, mesh, plan, moves, expect, tmpdir)
            fails += 0 if ok else 1
    print("CHAOS CAMPAIGN", "PASS" if fails == 0 else f"{fails} FAILURES")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
