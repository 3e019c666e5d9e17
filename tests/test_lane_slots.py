"""The walk's lane-slot counter (obs/walk_stats.py ``lane_slots``): one
lane swept through one walk-loop iteration, n per full-width iteration
plus S per iteration of each compaction round, on every walk path — the
XLA trace, the packed-I/O trace, the megastep's sum over its fused
moves, the Pallas kernel (interpreted) and the partitioned walk.

The oracle is a host replay of the schedule from each lane's iteration
count (a lane that reaches its destination in a clean single-region box
runs one iteration per crossing plus the one that reaches it): the
full-width phase runs to the first stage's start, an intermediate stage
runs one round of its first S active lanes (by index) for at most its
span, and the final stage loops rounds until every lane is done. The
fit ladder's oracle is ``utils/ladder.simulate_fit`` on the histogram of
the same counts, the model its planner uses."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pumiumtally_tpu import PumiTally, TallyConfig, build_box, make_flux
from pumiumtally_tpu.obs import stats_to_dict
from pumiumtally_tpu.obs.walk_stats import reduce_chip_stats
from pumiumtally_tpu.ops import staging
from pumiumtally_tpu.ops.source import SourceParams
from pumiumtally_tpu.ops.walk import trace, trace_packed
from pumiumtally_tpu.utils.ladder import fit_widths, simulate_fit

N = 64
GROUPS = 2
STAGES = ((3, 24), (6, 8))  # a two-stage ladder


@pytest.fixture(scope="module")
def box():
    return build_box(1.0, 1.0, 1.0, 3, 3, 3, dtype=jnp.float64)


def _batch(mesh, n=N, seed=5):
    rng = np.random.default_rng(seed)
    elem = rng.integers(0, mesh.ntet, n).astype(np.int32)
    origin = np.asarray(mesh.centroids())[elem]
    dest = rng.uniform(0.02, 0.98, (n, 3))  # long moves, all inside
    return elem, origin, dest


def _args(mesh, elem, origin, dest):
    n = len(elem)
    return (
        mesh, jnp.asarray(origin), jnp.asarray(dest), jnp.asarray(elem),
        jnp.ones(n, bool), jnp.ones(n, jnp.float64),
        jnp.zeros(n, jnp.int32), jnp.full(n, -1, jnp.int32),
        make_flux(mesh.ntet, GROUPS, jnp.float64, flat=True),
    )


KW = dict(initial=False, tolerance=1e-8, n_groups=GROUPS, unroll=1,
          tally_scatter="pair")


def replay(iters, stages, max_crossings):
    """(lane-slots, loop iterations) of the schedule, from each lane's
    iteration count."""
    rem = np.asarray(iters, np.int64).copy()
    n = rem.size
    it = min(stages[0][0], max_crossings, int(rem.max()))
    slots = n * it
    rem = np.maximum(rem - it, 0)
    total = it
    for i, (start, size) in enumerate(stages):
        S = min(n, size)
        rounds = 0
        while (rem > 0).any():
            if i + 1 < len(stages) and rounds == 1:
                break
            if i + 1 == len(stages) and rounds >= -(-n // S) + 1:
                break
            sel = np.flatnonzero(rem > 0)[:S]
            span = (stages[i + 1][0] - start if i + 1 < len(stages)
                    else max_crossings)
            run = min(span, int(rem[sel].max()))
            rem[sel] = np.maximum(rem[sel] - run, 0)
            slots += S * run
            total += run
            rounds += 1
    return slots, total


def replay_fit(iters, n, max_crossings, unroll=1):
    """(lane-slots, loop iterations, ladder rounds) of the fit ladder,
    from each lane's iteration count (0 for a parked lane)."""
    slots, rounds, loops = simulate_fit(
        np.bincount(iters), n, unroll=unroll, max_crossings=max_crossings
    )
    return slots, loops, rounds


def _iterations(mesh, elem, origin, dest):
    # One recorded point is enough: the count runs on past the buffer.
    r = trace(*_args(mesh, elem, origin, dest), max_crossings=mesh.ntet,
              record_xpoints=1, **KW)
    assert bool(np.all(np.asarray(r.done)))
    return np.asarray(r.n_xpoints) + 1


def test_lane_slots_two_stage_ladder_trace(box):
    elem, origin, dest = _batch(box)
    iters = _iterations(box, elem, origin, dest)
    assert iters.max() > STAGES[1][0]  # the final stage has work
    r = trace(*_args(box, elem, origin, dest), max_crossings=box.ntet,
              compact_stages=STAGES, **KW)
    d = stats_to_dict(r.stats)
    want, loops = replay(iters, STAGES, box.ntet)
    assert d["loop_iters"] == loops
    assert d["lane_slots"] == want
    # The flat loop sweeps all n lanes every iteration.
    flat = stats_to_dict(trace(*_args(box, elem, origin, dest),
                               max_crossings=box.ntet, **KW).stats)
    assert flat["lane_slots"] == N * flat["loop_iters"] == N * iters.max()


def test_lane_slots_trace_packed_readback(box):
    """The packed program's readback carries the same count as the
    unpacked trace, through the int64 tail encoding."""
    elem, origin, dest = _batch(box, seed=9)
    ref = stats_to_dict(trace(*_args(box, elem, origin, dest),
                              max_crossings=box.ntet,
                              compact_stages=STAGES, **KW).stats)
    rec = staging.pack_move_record(
        staging.HostStager(), dest, np.ones(N), np.zeros(N, np.int32),
        np.ones(N, bool), jnp.float64,
    )
    out = trace_packed(
        box, jnp.asarray(origin), jnp.asarray(elem),
        jnp.full(N, -1, jnp.int32), jax.device_put(rec),
        make_flux(box.ntet, GROUPS, jnp.float64, flat=True),
        max_crossings=box.ntet, compact_stages=STAGES, **KW,
    )
    tail = staging.split_trace_readback(
        np.asarray(out[1]), N, jnp.float64)[3]
    assert stats_to_dict(tail)["lane_slots"] == ref["lane_slots"] > 0


def _source_tally(mesh, megastep, n=1024):
    t = PumiTally(mesh, n, TallyConfig(
        dtype=jnp.float64, n_groups=GROUPS, tolerance=1e-8, unroll=1,
        compact_stages=((4, 256), (8, 64)), megastep=megastep))
    t.initialize_particle_location(
        np.random.default_rng(1).uniform(0.1, 0.9, (n, 3)).ravel())
    return t


def test_lane_slots_megastep_sums_fused_moves(box):
    """One K=3 megastep reports the sum of its three moves' lane-slots:
    the same as three one-move dispatches (fusion is pure control
    flow)."""
    src = SourceParams(sigma_t={0: 2.0}, absorption={0: 0.1}, seed=4)
    recs = {}
    for k in (1, 3):
        t = _source_tally(box, k)
        t.run_source_moves(3, src)
        recs[k] = [r for r in t.telemetry()["per_move"]
                   if r["kind"] == "megastep"]
    assert [len(recs[1]), len(recs[3])] == [3, 1]
    per_move = [r["lane_slots"] for r in recs[1]]
    assert all(s > 0 for s in per_move)
    assert recs[3][0]["lane_slots"] == sum(per_move)
    assert recs[3][0]["loop_iters"] == sum(r["loop_iters"] for r in recs[1])
    # Compaction ran: the slots are fewer than n lanes for every
    # iteration, and at least one lane per scored segment.
    one = recs[3][0]
    assert one["occ_slots"] > 0
    assert one["segments"] <= one["lane_slots"] < 1024 * one["loop_iters"]


def test_lane_slots_pallas_interpret(box):
    """The Pallas kernel (interpreted on the CPU) sweeps the batch's n
    lanes every crossing, and counts the same as the flat XLA loop."""
    from pumiumtally_tpu.ops.walk_pallas import trace_pallas_impl

    mesh = build_box(1.0, 1.0, 1.0, 3, 3, 3, dtype=jnp.float32)
    elem, origin, dest = _batch(mesh, n=40, seed=3)
    args = list(_args(mesh, elem, origin, dest))
    args[1:3] = [a.astype(jnp.float32) for a in args[1:3]]
    args[5] = args[5].astype(jnp.float32)
    args[8] = make_flux(mesh.ntet, GROUPS, jnp.float32, flat=True)
    kw = dict(KW, max_crossings=mesh.ntet)
    pal = stats_to_dict(trace_pallas_impl(*args, lane_block=16, **kw).stats)
    args[8] = make_flux(mesh.ntet, GROUPS, jnp.float32, flat=True)
    xla = stats_to_dict(trace(*args, **kw).stats)
    assert pal["lane_slots"] == 40 * pal["loop_iters"] > 0
    assert pal["lane_slots"] == xla["lane_slots"]


def test_lane_slots_partitioned_per_chip(box):
    """Per chip: cap lanes per full-width iteration of the first walk,
    and the follow-up rounds' width per iteration of each exchange
    round's walk (round_stats row 5); summed over chips."""
    from test_mesh_partition import N_DEV, _partitioned, _random_batch
    from pumiumtally_tpu.parallel.mesh_partition import partition_mesh

    mesh = build_box(1.0, 1.0, 1.0, 4, 4, 4, dtype=jnp.float64)
    part = partition_mesh(mesh, N_DEV)
    elem, origin, dest, weight, group = _random_batch(mesh, 64, seed=17)
    res, got = _partitioned(mesh, part, elem, origin, dest, weight, group)
    assert got["done"].all()
    stats = np.asarray(res.stats)
    rounds = np.asarray(res.round_stats)[:, 5, :].sum(axis=1)
    cap = res.position.shape[0] // N_DEV
    s_follow = min(max(cap // 16, 64), cap)
    assert rounds.sum() > 0  # the exchange rounds walked
    for chip in range(N_DEV):
        d = stats_to_dict(stats[chip])
        first = d["loop_iters"] - int(rounds[chip])
        assert d["lane_slots"] == cap * first + s_follow * int(rounds[chip])
    total = reduce_chip_stats(stats)["lane_slots"]
    assert total == sum(stats_to_dict(row)["lane_slots"] for row in stats)


@pytest.mark.parametrize("unroll, max_crossings", [(1, None), (4, None),
                                                   (1, 15)])
def test_lane_slots_fit_ladder_trace(box, unroll, max_crossings):
    """The fit ladder's lane-slots, loop iterations and rounds are the
    replay's exactly — with unrolled chunks, and with walks cut at
    max_crossings."""
    n = 1 << 18  # fit_widths: 262144, 32768, 4096
    elem, origin, dest = _batch(box, n=n, seed=12)
    iters = _iterations(box, elem, origin, dest)
    parked = np.random.default_rng(12).random(n) < 0.1
    iters[parked] = 0
    mc = box.ntet if max_crossings is None else max_crossings
    args = list(_args(box, elem, origin, dest))
    args[4] = jnp.asarray(~parked)
    kw = dict(KW, unroll=unroll, max_crossings=mc)
    d = stats_to_dict(trace(*args, compact_stages="fit", **kw).stats)
    slots, loops, rounds = replay_fit(iters, n, mc, unroll)
    assert (d["lane_slots"], d["loop_iters"], d["ladder_rounds"]) == (
        slots, loops, rounds)
    assert rounds >= 1 and len(fit_widths(n)) == 3
    assert d["truncated"] == int((iters > loops).sum())
    args[8] = make_flux(box.ntet, GROUPS, jnp.float64, flat=True)
    flat = stats_to_dict(trace(*args, **kw).stats)
    # The narrower stages' chunks divide the full width's, so rounded up
    # to it the ladder's iterations are the flat loop's.
    assert -(-loops // unroll) * unroll == flat["loop_iters"]
    assert flat["ladder_rounds"] == 0
    assert d["lane_slots"] < flat["lane_slots"]


def test_fit_ladder_small_batch_schedule(box):
    """A batch of 1,024 to 65,535 lanes still leaves its full width: one
    narrower stage of max(n/8, 256) lanes (256 is the single-stage
    schedule's subset floor), replayed exactly; the default config
    resolves such a batch to the fit ladder."""
    assert fit_widths(256) == (256,)
    assert fit_widths(1024) == (1024, 256)
    assert fit_widths(4096) == (4096, 512)
    assert fit_widths(16384) == (16384, 2048)
    assert fit_widths(1 << 16) == (1 << 16, 8192, 1024)
    assert fit_widths(1 << 20) == (1 << 20, 131072, 16384, 2048)
    n = 4096
    assert TallyConfig().resolve_compact_stages(n) == "fit"
    elem, origin, dest = _batch(box, n=n, seed=13)
    iters = _iterations(box, elem, origin, dest)
    d = stats_to_dict(trace(*_args(box, elem, origin, dest),
                            compact_stages="fit", max_crossings=box.ntet,
                            **KW).stats)
    assert (d["lane_slots"], d["loop_iters"], d["ladder_rounds"]) == (
        replay_fit(iters, n, box.ntet))
    assert d["ladder_rounds"] == 1
    assert d["lane_slots"] < n * d["loop_iters"]


def test_ladder_rounds_megastep_sums_fused_moves(box):
    """Under the default (fit) config, one K=3 megastep reports the sum
    of its three moves' ladder rounds and lane-slots."""
    src = SourceParams(sigma_t={0: 2.0}, absorption={0: 0.1}, seed=4)
    n = 16384  # fit_widths: 16384, 2048
    recs = {}
    for k in (1, 3):
        t = PumiTally(box, n, TallyConfig(
            dtype=jnp.float64, n_groups=GROUPS, tolerance=1e-8, unroll=1,
            megastep=k))
        t.initialize_particle_location(
            np.random.default_rng(1).uniform(0.1, 0.9, (n, 3)).ravel())
        t.run_source_moves(3, src)
        recs[k] = [r for r in t.telemetry()["per_move"]
                   if r["kind"] == "megastep"]
    per_move = [r["ladder_rounds"] for r in recs[1]]
    assert all(r >= 1 for r in per_move)
    assert recs[3][0]["ladder_rounds"] == sum(per_move)
    assert recs[3][0]["lane_slots"] == sum(r["lane_slots"] for r in recs[1])


# Lane-slots the paths that keep their schedules counted before the fit
# ladder existed (the same scenarios, run on the tree before it).
UNCHANGED = {
    "stages": 824, "compact_after": 864, "flat": 1024, "pallas": 600,
    # The first count is the facade's search, started from grid seeds
    # (ops/seed_grid.py; 9,600 and 10,240 from where the lanes stood).
    "facade_stages": [7936, 6976, 5440, 5248],
    "facade_compact_after": [8832, 8320, 5248, 4864],
    "partitioned": [35072, 208256],
}


def _unchanged(case, box):
    elem, origin, dest = _batch(box)
    one = dict(stages=dict(compact_stages=STAGES),
               compact_after=dict(compact_after=2, compact_size=16),
               flat={})
    if case in one:
        return stats_to_dict(trace(*_args(box, elem, origin, dest),
                                   max_crossings=box.ntet, **one[case],
                                   **KW).stats)["lane_slots"]
    if case == "pallas":
        from pumiumtally_tpu.ops.walk_pallas import trace_pallas_impl

        mesh = build_box(1.0, 1.0, 1.0, 3, 3, 3, dtype=jnp.float32)
        elem, origin, dest = _batch(mesh, n=40, seed=3)
        args = list(_args(mesh, elem, origin, dest))
        args[1:3] = [a.astype(jnp.float32) for a in args[1:3]]
        args[5] = args[5].astype(jnp.float32)
        args[8] = make_flux(mesh.ntet, GROUPS, jnp.float32, flat=True)
        return stats_to_dict(trace_pallas_impl(
            *args, lane_block=16, **dict(KW, max_crossings=mesh.ntet),
        ).stats)["lane_slots"]
    if case == "partitioned":
        from pumiumtally_tpu.parallel.partitioned_api import PartitionedTally
        from test_mesh_partition import N_DEV

        n = 2048
        t = PartitionedTally(
            build_box(1.0, 1.0, 1.0, 4, 4, 4, dtype=jnp.float64), n,
            TallyConfig(dtype=jnp.float64, n_groups=GROUPS, tolerance=1e-8,
                        unroll=1), n_parts=N_DEV)
        rng = np.random.default_rng(2)
        t.initialize_particle_location(
            rng.uniform(0.1, 0.9, (n, 3)).ravel())
        t.move_to_next_location(
            rng.uniform(0.05, 0.95, (n, 3)).ravel(), np.ones(n, np.int8),
            np.ones(n), np.zeros(n, np.int32), np.full(n, -1, np.int32))
    else:
        kw = (dict(compact_stages=((4, 256), (8, 64)))
              if case == "facade_stages"
              else dict(compact_after=4, compact_size=128))
        t = PumiTally(box, 1024, TallyConfig(
            dtype=jnp.float64, n_groups=GROUPS, tolerance=1e-8, unroll=1,
            **kw))
        t.initialize_particle_location(
            np.random.default_rng(1).uniform(0.1, 0.9, (1024, 3)).ravel())
        t.run_source_moves(
            3, SourceParams(sigma_t={0: 2.0}, absorption={0: 0.1}, seed=4))
    return [r["lane_slots"] for r in t.telemetry()["per_move"]
            if "lane_slots" in r]


@pytest.mark.parametrize("case", sorted(UNCHANGED))
def test_lane_slots_unchanged_schedules(box, case):
    """Explicit schedules (a stage tuple, compact_after/compact_size, on
    the walk and through the facade's search and megastep moves), the
    flat loop, the Pallas kernel and the partitioned facade under the
    default config count exactly the lane-slots they did before."""
    assert _unchanged(case, box) == UNCHANGED[case]


def test_ladder_rounds_per_move_reader():
    """The benchmark's ``ladder_rounds_per_move``: rounds over the moves
    of the window's move and megastep records; nothing where the records
    carry no such counter (a program without it)."""
    import importlib.util
    import os
    import types

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics",
        "ladder_rounds_per_move.py")
    spec = importlib.util.spec_from_file_location("ladder_rounds", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    run = types.SimpleNamespace(records=[
        {"kind": "initial_search", "ladder_rounds": 5},
        {"kind": "megastep", "moves": 3, "ladder_rounds": 10},
        {"kind": "move", "ladder_rounds": 2},
    ])
    assert mod.read(run) == 12 / 4
    run.records = [{"kind": "megastep", "moves": 1, "lane_slots": 9},
                   {"kind": "initial_search", "lane_slots": 4}]
    assert mod.read(run) is None
