"""Extended walk soak: random jittered meshes x adversarial rays x the
full strategy-knob grid (robust/tally_scatter/gathers, staged ladder
with per-stage unroll). Asserts termination (robust mode), fail-safe
truncation (fast mode), the per-particle conservation ledger, and the
ledger-vs-flux total. A manual, longer-running complement to
tests/test_jittered_mesh.py — run before shipping walk changes.

Usage: python scripts/soak_walk.py [n_seeds] [--audit-every N]
       python scripts/soak_walk.py --chaos <spec> [--chaos-moves M]

--audit-every N additionally shadow-audits every N-th seed: an 8-lane
random sample of finished walks is re-walked through the independent
float64 host reference (pumiumtally_tpu/integrity/audit.py) and the
kernel's positions/track lengths must agree within the dtype-aware
audit tolerance — the soak-scale exercise of the production SDC
detector.

--chaos <spec> switches to the CHAOS soak: a randomized-but-seeded
fault schedule (resilience/faultinject.chaos_plan grammar, e.g.
"transients:3,chip_down:1,seed:7") is driven through a long supervised
PARTITIONED run on the 8-device CPU mesh, and the final flux is
verified against a fault-free reference run — bitwise when the layout
never changed, the layout-independence tolerance (1e-11) after an
elastic mesh-shrink. Same spec → same schedule → exact reproduction
of any failure.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--chaos" in sys.argv and (
    "xla_force_host_platform_device_count"
    not in os.environ.get("XLA_FLAGS", "")
):
    # The chaos soak drives the partitioned facade: force the 8-device
    # virtual CPU mesh BEFORE jax initializes.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

import jax

jax.config.update("jax_platforms", "cpu")  # a CPU soak
import jax.numpy as jnp
from pumiumtally_tpu import make_flux
from pumiumtally_tpu.mesh.box import build_box_arrays
from pumiumtally_tpu.mesh.core import TetMesh
from pumiumtally_tpu.ops.walk import trace_impl

from pumiumtally_tpu.integrity.audit import HostReference, audit_sample
from pumiumtally_tpu.integrity.invariants import audit_tolerance, mesh_scale

def chaos_soak(spec: str, n_moves: int) -> int:
    """Drive the chaos schedule through a supervised partitioned run
    and verify the final flux against a fault-free reference. Returns
    the number of failures (0 = PASS)."""
    import tempfile

    from pumiumtally_tpu import TallyConfig
    from pumiumtally_tpu.parallel.partitioned_api import PartitionedTally
    from pumiumtally_tpu.resilience import (
        ChaosInjector,
        InjectedKill,
        ResilientRunner,
        chaos_plan,
    )

    plan = chaos_plan(spec, n_moves)
    print(f"[chaos] schedule: {plan.describe()}", flush=True)
    coords, tets = build_box_arrays(1.0, 1.0, 1.0, 4, 4, 4)
    cid = (coords[tets].mean(1)[:, 0] > 0.5).astype(np.int32)
    jax.config.update("jax_enable_x64", True)  # cross-layout flux
    # comparisons assume double (the layout-independence tolerance)
    mesh = TetMesh.from_numpy(coords, tets, cid, dtype=np.float64)
    n = 64
    cfg = TallyConfig(n_groups=2, dtype=np.float64, tolerance=1e-8)
    pos = np.random.default_rng(42).uniform(0.1, 0.9, (n, 3)).ravel()

    def inputs(i):
        r = np.random.default_rng(5000 + i)
        return (
            r.uniform(0.05, 0.95, (n, 3)).ravel().copy(),
            np.ones(n, np.int8),
            r.uniform(0.5, 2.0, n),
            r.integers(0, 2, n).astype(np.int32),
            np.full(n, -1, np.int32),
        )

    ckdir = tempfile.mkdtemp(prefix="chaos_soak_")
    t = PartitionedTally(mesh, n, cfg, n_parts=8)
    run = ResilientRunner(
        t, ckdir, every_moves=2, handle_signals=False,
        sleep=lambda s: None, faults=ChaosInjector(plan),
    )
    evicted = False
    run.initialize_particle_location(pos.copy())
    for i in range(1, n_moves + 1):
        try:
            run.move_to_next_location(*inputs(i))
        except InjectedKill:
            # Eviction: the next "process" auto-resumes from the
            # flushed generation and replays the remaining schedule.
            evicted = True
            t2 = PartitionedTally(
                mesh, n, cfg, n_parts=run.tally.n_parts
            )
            run = ResilientRunner(
                t2, ckdir, every_moves=2, handle_signals=False,
                sleep=lambda s: None,
            )
            for j in range(1, n_moves + 1):
                if run.tally.iter_count >= j:
                    continue
                run.move_to_next_location(*inputs(j))
            break
    final_parts = run.tally.n_parts
    st = run.recovery_stats

    ref = PartitionedTally(mesh, n, cfg, n_parts=final_parts)
    ref.initialize_particle_location(pos.copy())
    for i in range(1, n_moves + 1):
        ref.move_to_next_location(*inputs(i))

    got = np.asarray(run.raw_flux, np.float64)
    want = np.asarray(ref.raw_flux, np.float64)
    shrunk = final_parts != 8
    # Same-layout replay (even across an eviction+resume) is bitwise;
    # only a mesh-shrink moves to the layout-independence tolerance.
    atol = 1e-11 if shrunk else 0.0
    ok = np.allclose(got, want, rtol=0, atol=atol)
    print(
        f"[chaos] moves={run.tally.iter_count}/{n_moves} "
        f"parts=8->{final_parts} rollbacks={st['rollbacks']} "
        f"reshards={st['reshards']} evicted={evicted} "
        f"max|Δflux|={np.abs(got - want).max():.3e} (atol={atol}) "
        f"{'OK' if ok else 'FAIL'}",
        flush=True,
    )
    print("CHAOS SOAK", "PASS" if ok else "1 FAILURE")
    return 0 if ok else 1


args = sys.argv[1:]
audit_every = 0
chaos_spec = None
chaos_moves = 12
if "--chaos" in args:
    i = args.index("--chaos")
    chaos_spec = args[i + 1]
    del args[i:i + 2]
if "--chaos-moves" in args:
    i = args.index("--chaos-moves")
    chaos_moves = int(args[i + 1])
    del args[i:i + 2]
if chaos_spec is not None:
    sys.exit(chaos_soak(chaos_spec, chaos_moves))
if "--audit-every" in args:
    i = args.index("--audit-every")
    audit_every = int(args[i + 1])
    del args[i:i + 2]
n_seeds = int(args[0]) if args else 12

fails = 0
for seed in range(n_seeds):
    rng = np.random.default_rng(1000 + seed)
    nx = int(rng.integers(3, 8))
    jitter = float(rng.uniform(0.0, 0.28))
    coords, tets = build_box_arrays(1.0, 1.0, 1.0, nx, nx, nx)
    interior = ((coords > 1e-9).all(1) & (coords < 1 - 1e-9).all(1))
    c = coords.copy()
    c[interior] += rng.uniform(-jitter/nx, jitter/nx, (interior.sum(), 3))
    cid = (c[tets].mean(1)[:, 0] > 0.5).astype(np.int32)
    try:
        mesh = TetMesh.from_numpy(c, tets, cid, dtype=jnp.float32)
    except ValueError:
        continue  # tangled — correctly rejected
    n = 256
    elem = jnp.asarray(rng.integers(0, mesh.ntet, n).astype(np.int32))
    origin = np.asarray(mesh.centroids())[np.asarray(elem)]
    dest = rng.uniform(-0.05, 1.05, (n, 3))
    verts = np.asarray(mesh.coords)
    dest[:64] = verts[rng.integers(0, len(verts), 64)] + rng.normal(0, 1e-7, (64, 3))
    dest[64:96, 1:] = origin[64:96, 1:]
    robust = bool(seed % 2)
    scatter = ["pair", "interleaved"][seed % 2]
    gath = ["merged", "split"][(seed // 2) % 2]
    r = trace_impl(
        mesh, jnp.asarray(origin, jnp.float32), jnp.asarray(dest, jnp.float32),
        elem, jnp.ones(n, bool), jnp.ones(n, jnp.float32),
        jnp.zeros(n, jnp.int32), jnp.full(n, -1, jnp.int32),
        make_flux(mesh.ntet, 1, jnp.float32),
        initial=False, max_crossings=mesh.ntet + 64, tolerance=1e-6,
        robust=robust, tally_scatter=scatter, gathers=gath,
        compact_stages=((6, max(n//2, 32)), (12, max(n//4, 32), 4)), unroll=2,
    )
    pos = np.asarray(r.position)
    tl = np.asarray(r.track_length)
    ok = (np.isfinite(pos).all()
          and np.allclose(tl, np.linalg.norm(pos - origin, axis=1), atol=3e-4)
          and np.isclose(float(np.asarray(r.flux)[..., 0].sum()), tl.sum(), rtol=1e-4)
          and (not robust or bool(np.asarray(r.done).all())))
    audit_note = ""
    if audit_every and seed % audit_every == 0:
        done_h = np.asarray(r.done)
        rows = np.nonzero(done_h)[0]
        rng_a = np.random.default_rng(seed)
        sel = rng_a.choice(rows, size=min(8, rows.size), replace=False)
        out = audit_sample(
            HostReference(mesh),
            origin[sel].astype(np.float64),
            dest[sel].astype(np.float64),
            np.asarray(elem)[sel],
            pos[sel], tl[sel],
            tolerance=1e-6, max_crossings=mesh.ntet + 64,
            tol=audit_tolerance(
                None, np.float32, mesh_scale(mesh.coords), 1e-6
            ),
        )
        ok = ok and out.mismatches == 0
        audit_note = (
            f" audit={out.audited - out.mismatches}/{out.audited}"
            f"(+{out.skipped} skipped)"
        )
    print(f"seed {seed}: nx={nx} jitter={jitter:.2f} robust={robust} "
          f"{scatter}/{gath} done={int(np.asarray(r.done).sum())}/{n} "
          f"{'OK' if ok else 'FAIL'}{audit_note}", flush=True)
    fails += 0 if ok else 1
print("SOAK", "PASS" if fails == 0 else f"{fails} FAILURES")
