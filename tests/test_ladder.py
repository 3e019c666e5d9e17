"""Executional ladder planner (utils/ladder.py).

Pins: (1) the simulator reproduces the round-4 hardware grid's RANKING
of schedules (the reconciliation VERDICT r4 weak #5 asked for — the
old slot model's dp_r250k pick measured 6.93 Mseg/s vs the dense
ladder's 7.60 because its round cost was 5x too cheap and its width
pinning excluded dense's shape); (2) the planner beats the dense
ladder under its own executional score and adapts to the mesh; (3)
planned schedules are valid and bit-identical in walk results (pure
scheduling)."""
import numpy as np
import pytest

import jax.numpy as jnp

from pumiumtally_tpu import build_box, make_flux, trace
from pumiumtally_tpu.ops.geometry import locate_points
from pumiumtally_tpu.ops.walk import normalize_compact_stages
from pumiumtally_tpu.utils.config import TallyConfig, dense_ladder
from pumiumtally_tpu.utils.ladder import (
    exp_survivors,
    plan_stages,
    simulate_ladder,
    survivors,
)

M = 1048576
# Round-4 hardware grid (BENCH_GRID_r04.md, sweep_stages): name -> (schedule,
# measured ms/step). The simulator must reproduce the measured ordering
# of the three structurally distinct families.
GRID = {
    "default_r2": (((16, M // 2), (24, M // 4), (40, M // 8)), 3437.9),
    "dense": (
        ((8, 5 * M // 8), (16, 3 * M // 8), (24, M // 4), (32, M // 8),
         (48, M // 16), (64, M // 32), (96, M // 64)),
        2188.8,
    ),
    "dp_r250k": (
        ((16, M // 2), (24, M // 4), (40, M // 8), (48, M // 16),
         (56, M // 32), (76, 8192)),
        2400.1,
    ),
}
# Round-4 hardware fit (scripts/fit_ladder_model.py): ~81-85 ns/slot,
# ~110 ms/round. Only the RATIO matters for ranking.
ROUND_COST = 1.3 * M


def _score(stages, act):
    slots, rounds = simulate_ladder(act, M, stages, unroll=8)
    return slots + ROUND_COST * rounds


def test_simulator_reproduces_hardware_ranking():
    act = exp_survivors(M, 14.9)
    scores = {k: _score(v[0], act) for k, v in GRID.items()}
    meas = {k: v[1] for k, v in GRID.items()}
    assert (
        sorted(scores, key=scores.get) == sorted(meas, key=meas.get)
    ), (scores, meas)


def test_simulator_on_measured_counts_matches_analytic_family():
    # A synthetic exponential sample's survivors curve must score
    # schedules like the analytic curve of the same mean (shared
    # downstream path for measured decay inputs).
    rng = np.random.default_rng(0)
    counts = rng.exponential(14.9, 65536).astype(int)
    act_m = survivors(counts) * (M / 65536)
    act_a = exp_survivors(M, 14.9)
    for sched, _ in GRID.values():
        sm = _score(sched, act_m)
        sa = _score(sched, act_a)
        assert abs(sm - sa) / sa < 0.15, (sched, sm, sa)


def test_planner_beats_dense_under_executional_score():
    act = exp_survivors(M, 14.9)
    planned = plan_stages(M, 14.9)
    assert planned, "planner must produce a ladder at bench stats"
    assert _score(planned, act) <= _score(dense_ladder(M), act)


def test_planner_adapts_to_mesh_density():
    bench = plan_stages(M, 14.9)
    coarse = plan_stages(65536, 3.3)  # config-1 10k-tet profile
    denser = plan_stages(M, 32.6)  # 119-cell 10M-tet profile
    assert coarse, "short walks still get a (short) ladder"
    # Shorter walks end their ladder earlier; denser meshes stretch it.
    assert coarse[-1][0] < bench[-1][0] < denser[-1][0]
    # Schedules are valid by the walk's own rules.
    for s in (bench, coarse, denser):
        normalize_compact_stages(s, None, None, M, M // 8)


def test_config_plan_mode_resolves_and_scales():
    cfg = TallyConfig(compact_stages="plan")
    sched = cfg.resolve_compact_stages(M, ntet=998250)
    assert sched and all(len(s) >= 2 for s in sched)
    # Denser mesh -> later final boundary, same as the bench scaling.
    sched10m = cfg.resolve_compact_stages(M, ntet=10_110_954)
    assert sched10m[-1][0] > sched[-1][0]
    # "auto" stays the measured-best dense ladder, starts
    # density-scaled ((ntet/998250)^(1/3) — bench.py's cells/55).
    auto = TallyConfig(compact_stages="auto")
    a1 = auto.resolve_compact_stages(M, ntet=998250)
    assert a1 == dense_ladder(M)
    a10 = auto.resolve_compact_stages(M, ntet=10_110_954)
    assert a10[0][0] > a1[0][0]
    assert [w for _, w in a10] == [w for _, w in a1]


def test_planned_schedule_walk_is_bit_identical():
    mesh = build_box(1.0, 1.0, 1.0, 6, 6, 6, dtype=jnp.float32)
    n = 2048
    rng = np.random.default_rng(3)
    origin = jnp.asarray(rng.uniform(0.05, 0.95, (n, 3)), jnp.float32)
    elem = locate_points(mesh, origin, 1e-12)
    dest = jnp.asarray(
        np.clip(
            np.asarray(origin) + rng.normal(0, 0.2, (n, 3)), 0.01, 0.99
        ),
        jnp.float32,
    )
    args = (
        mesh, origin, dest, elem, jnp.ones(n, bool),
        jnp.ones(n, jnp.float32), jnp.zeros(n, jnp.int32),
        jnp.full(n, -1, jnp.int32),
    )
    kw = dict(initial=False, max_crossings=512, tolerance=1e-6)
    flat = trace(*args, make_flux(mesh.ntet, 1, jnp.float32), **kw)
    sched = plan_stages(n, 5.0)
    assert sched, "planner should ladder a 2048-lane batch"
    ladd = trace(
        *args, make_flux(mesh.ntet, 1, jnp.float32),
        compact_stages=sched, **kw,
    )
    np.testing.assert_array_equal(
        np.asarray(flat.position), np.asarray(ladd.position)
    )
    np.testing.assert_allclose(
        np.asarray(flat.flux), np.asarray(ladd.flux), rtol=0, atol=1e-5
    )
    assert int(flat.n_segments) == int(ladd.n_segments)


def test_adaptive_mode_replans_from_measured_crossings():
    """compact_stages='adaptive' re-plans after the first move from the
    measured crossings/move; results match 'plan' up to fp summation
    order (schedules group the scatter adds differently)."""
    from pumiumtally_tpu.api import PumiTally, TallyConfig

    mesh = build_box(1.0, 1.0, 1.0, 6, 6, 6, dtype=jnp.float64)
    cents = np.asarray(mesh.centroids())
    N = 2048

    def drive(mode, moves=2):
        t = PumiTally(
            mesh, N,
            TallyConfig(dtype=jnp.float64, n_groups=2,
                        compact_stages=mode),
        )
        rng = np.random.default_rng(4)
        elem = rng.integers(0, mesh.ntet, N).astype(np.int32)
        pos = cents[elem].astype(np.float64)
        t.initialize_particle_location(pos.reshape(-1).copy())
        prev = pos.copy()
        for _ in range(moves):
            d = rng.normal(0, 1, (N, 3))
            d /= np.linalg.norm(d, axis=1, keepdims=True)
            # LONG moves: the density estimate (mesh-only) cannot see
            # this — the measured mean crossings is far higher.
            ln = rng.exponential(0.8, (N, 1))
            buf = np.clip(prev + d * ln, 0.01, 0.99).reshape(-1).copy()
            t.move_to_next_location(
                buf, np.ones(N, np.int8), np.ones(N),
                np.zeros(N, np.int32), np.full(N, -1, np.int32),
            )
            prev = buf.reshape(N, 3)
        return t

    t_plan = drive("plan")
    t_adapt = drive("adaptive")
    # Identical physics regardless of schedule (flux to f64 rounding:
    # different schedules group the scatter adds differently, so the
    # accumulation ORDER differs — observed max 1.8e-15).
    np.testing.assert_allclose(
        np.asarray(t_adapt.raw_flux), np.asarray(t_plan.raw_flux),
        rtol=0, atol=1e-12,
    )
    # The adaptive schedule reflects the measured (long-move) profile:
    # it must differ from the density-only plan and end LATER (more
    # crossings/move -> later final boundary).
    assert t_adapt._replanned
    sched_a = t_adapt._compact_stages
    sched_p = t_plan._compact_stages
    assert sched_a != sched_p
    assert sched_a is None or sched_p is None or (
        sched_a[-1][0] > sched_p[-1][0]
    )


def test_adaptive_mode_rejected_where_it_cannot_replan():
    from pumiumtally_tpu.models.pipeline import StreamingTallyPipeline
    from pumiumtally_tpu.parallel.partitioned_api import PartitionedTally

    mesh = build_box(1.0, 1.0, 1.0, 3, 3, 3, dtype=jnp.float64)
    cfg = TallyConfig(dtype=jnp.float64, compact_stages="adaptive")
    with pytest.raises(NotImplementedError, match="adaptive"):
        PartitionedTally(mesh, 64, cfg, n_parts=8)
    with pytest.raises(NotImplementedError, match="adaptive"):
        StreamingTallyPipeline(mesh, config=cfg)
