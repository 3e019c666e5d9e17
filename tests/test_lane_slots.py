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
span, and the final stage loops rounds until every lane is done."""
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


def _iterations(mesh, elem, origin, dest):
    r = trace(*_args(mesh, elem, origin, dest), max_crossings=mesh.ntet,
              record_xpoints=mesh.ntet, **KW)
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
