"""Checkpoint/resume round trip: a run interrupted and resumed must produce
bit-identical tallies and particle state to an uninterrupted run."""
from __future__ import annotations

import numpy as np
import pytest

from pumiumtally_tpu import PumiTally, TallyConfig, build_box


def _drive(tally, moves, seed):
    rng = np.random.default_rng(seed)
    n = tally.num_particles
    for _ in range(moves):
        dest = rng.uniform(0.05, 0.95, (n, 3))
        flying = np.ones(n, np.int8)
        weights = rng.uniform(0.5, 2.0, n)
        groups = rng.integers(0, tally.config.n_groups, n).astype(np.int32)
        mats = np.full(n, -1, np.int32)
        tally.move_to_next_location(dest, flying, weights, groups, mats)


def _fresh(n=16):
    mesh = build_box(1.0, 1.0, 1.0, 3, 3, 3)
    t = PumiTally(mesh, n, TallyConfig(tolerance=1e-6))
    rng = np.random.default_rng(42)
    t.initialize_particle_location(rng.uniform(0.1, 0.9, (n, 3)).ravel())
    return t


def test_round_trip_matches_uninterrupted(tmp_path):
    ckpt = str(tmp_path / "tally.npz")

    a = _fresh()
    _drive(a, 3, seed=1)
    a.save_checkpoint(ckpt)
    _drive(a, 2, seed=2)

    b = _fresh()
    b.restore_checkpoint(ckpt)
    assert b.iter_count == 3
    _drive(b, 2, seed=2)

    np.testing.assert_array_equal(a.raw_flux, b.raw_flux)
    np.testing.assert_array_equal(a.element_ids, b.element_ids)
    np.testing.assert_array_equal(
        np.asarray(a.state.origin), np.asarray(b.state.origin)
    )
    assert a.total_segments == b.total_segments


def test_adaptive_replan_state_rides_checkpoints(tmp_path):
    """compact_stages='adaptive' replans the compaction ladder from the
    FIRST move's measured stats; a resumed run must reuse that ladder
    (not replan from a post-resume move) or the scatter grouping — and
    thus the flux, to ~1e-15 — drifts from the uninterrupted run."""
    ckpt = str(tmp_path / "tally.npz")
    mesh = build_box(1.0, 1.0, 1.0, 3, 3, 3)
    n = 1024
    cfg = TallyConfig(tolerance=1e-6, compact_stages="adaptive")

    def fresh():
        t = PumiTally(mesh, n, cfg)
        rng = np.random.default_rng(7)
        t.initialize_particle_location(
            rng.uniform(0.1, 0.9, (n, 3)).ravel()
        )
        return t

    a = fresh()
    _drive(a, 1, seed=11)
    assert a._replanned
    a.save_checkpoint(ckpt)

    b = fresh()
    b.restore_checkpoint(ckpt)
    assert b._replanned
    assert b._compact_stages == a._compact_stages

    _drive(a, 1, seed=12)
    _drive(b, 1, seed=12)
    np.testing.assert_array_equal(a.raw_flux, b.raw_flux)


@pytest.mark.parametrize("fmt", ["tally.npz", "tally.shards"])
def test_default_fit_ladder_round_trips(tmp_path, fmt):
    """The default config at a batch wide enough for the fit ladder: a
    checkpoint (plain and sharded) and the runner's in-memory snapshot
    both restore it, and the resumed move matches the uninterrupted one
    bit for bit."""
    from pumiumtally_tpu.utils.checkpoint import restore_state, snapshot_state

    ckpt = str(tmp_path / fmt)
    n = 16384
    a = _fresh(n)
    assert a._compact_stages == "fit"
    _drive(a, 1, seed=1)
    a.save_checkpoint(ckpt)
    snap = snapshot_state(a)
    _drive(a, 1, seed=2)
    after = np.array(a.raw_flux)

    restore_state(a, snap)
    assert a._compact_stages == "fit" and a.iter_count == 1
    _drive(a, 1, seed=2)
    np.testing.assert_array_equal(a.raw_flux, after)

    b = _fresh(n)
    b.restore_checkpoint(ckpt)
    assert b._compact_stages == "fit" and b.iter_count == 1
    _drive(b, 1, seed=2)
    np.testing.assert_array_equal(b.raw_flux, after)


def test_mesh_mismatch_rejected(tmp_path):
    ckpt = str(tmp_path / "tally.npz")
    a = _fresh()
    a.save_checkpoint(ckpt)
    other = PumiTally(
        build_box(1.0, 1.0, 1.0, 2, 2, 2), a.num_particles,
        TallyConfig(tolerance=1e-6),
    )
    with pytest.raises(ValueError, match="different mesh"):
        other.restore_checkpoint(ckpt)


def test_shape_mismatches_rejected(tmp_path):
    ckpt = str(tmp_path / "tally.npz")
    a = _fresh()
    a.save_checkpoint(ckpt)

    mesh = build_box(1.0, 1.0, 1.0, 3, 3, 3)
    wrong_n = PumiTally(mesh, 8, TallyConfig(tolerance=1e-6))
    with pytest.raises(ValueError, match="particles"):
        wrong_n.restore_checkpoint(ckpt)

    wrong_g = PumiTally(
        mesh, a.num_particles, TallyConfig(tolerance=1e-6, n_groups=5)
    )
    with pytest.raises(ValueError, match="energy groups"):
        wrong_g.restore_checkpoint(ckpt)
