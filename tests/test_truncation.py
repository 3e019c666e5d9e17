"""Truncated-walk coverage: the warn-and-report path
(`_n_truncated`/`_warn_if_truncated`), the bounded re-walk escalation
(`ops/walk.py rewalk_truncated`, `TallyConfig.truncation_retries`) on
both facades, and the `stuck>=4` frozen-lane contract the partitioned
exchange reads (a lane frozen for migration mid-chase keeps its
zero-progress counter across the cut)."""
from __future__ import annotations

import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from pumiumtally_tpu import PumiTally, TallyConfig, build_box
from pumiumtally_tpu.mesh.box import build_box_arrays
from pumiumtally_tpu.mesh.core import TetMesh
from pumiumtally_tpu.ops.walk import (
    bump_length,
    chase_face_choice,
    forward_bump,
)
from pumiumtally_tpu.parallel.partitioned_api import PartitionedTally

N = 32


@pytest.fixture(scope="module")
def mesh():
    return build_box(1.0, 1.0, 1.0, 5, 5, 5)


@pytest.fixture(scope="module")
def mesh64():
    coords, t2v = build_box_arrays(1.0, 1.0, 1.0, 5, 5, 5)
    return TetMesh.from_numpy(coords, t2v, dtype=jnp.float64)


def _init(t):
    rng = np.random.default_rng(42)
    t.initialize_particle_location(
        rng.uniform(0.1, 0.9, (t.num_particles, 3)).ravel()
    )
    return t


def _inputs(i, n=N):
    rng = np.random.default_rng(300 + i)
    return (
        # Long moves: many boundary crossings per walk, so a tiny
        # max_crossings bound reliably truncates.
        rng.uniform(0.02, 0.98, (n, 3)).ravel().copy(),
        np.ones(n, np.int8),
        rng.uniform(0.5, 2.0, n),
        rng.integers(0, 2, n).astype(np.int32),
        np.full(n, -1, np.int32),
    )


# ===================================================================== #
# Warn-and-report path (the pre-escalation contract)
# ===================================================================== #
def test_truncated_walks_warn_and_count(mesh):
    t = _init(
        PumiTally(
            mesh, N, TallyConfig(tolerance=1e-6, max_crossings=2)
        )
    )
    with pytest.warns(RuntimeWarning, match="truncated"):
        t.move_to_next_location(*_inputs(1))
    tm = t.telemetry()
    assert tm["totals"]["truncated"] > 0
    assert tm["totals"]["lost"] == tm["totals"]["truncated"]
    assert tm["totals"]["rewalked"] == 0


def test_truncated_fallback_host_scan(mesh):
    """walk_stats=False removes the on-device truncation counter; the
    facade's host scan of ``done`` must still warn."""
    t = _init(
        PumiTally(
            mesh, N,
            TallyConfig(
                tolerance=1e-6, max_crossings=2, walk_stats=False
            ),
        )
    )
    with pytest.warns(RuntimeWarning, match="truncated"):
        t.move_to_next_location(*_inputs(1))


# ===================================================================== #
# Escalation: re-walk only the truncated lanes, bounded retries
# ===================================================================== #
def test_escalation_recovers_truncated_walks(mesh):
    """With retries, a tiny-bound run must recover every lane (no
    RuntimeWarning) and reproduce the ample-bound flux."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        t = _init(
            PumiTally(
                mesh, N,
                TallyConfig(
                    tolerance=1e-6, max_crossings=2,
                    truncation_retries=5,
                ),
            )
        )
        for i in range(1, 4):
            t.move_to_next_location(*_inputs(i))
    ref = _init(
        PumiTally(mesh, N, TallyConfig(tolerance=1e-6))
    )
    for i in range(1, 4):
        ref.move_to_next_location(*_inputs(i))
    np.testing.assert_allclose(
        np.asarray(t.raw_flux), np.asarray(ref.raw_flux), atol=1e-5
    )
    np.testing.assert_array_equal(t.element_ids, ref.element_ids)
    tm = t.telemetry()["totals"]
    assert tm["rewalked"] > 0 and tm["lost"] == 0


def test_escalation_bounded_then_lost(mesh):
    """One retry on a hopeless bound: some lanes recover, the rest are
    declared lost — with the warning and the lost counter agreeing."""
    t = _init(
        PumiTally(
            mesh, N,
            TallyConfig(
                tolerance=1e-6, max_crossings=1, truncation_retries=1
            ),
        )
    )
    with pytest.warns(RuntimeWarning, match="truncated"):
        t.move_to_next_location(*_inputs(1))
    tm = t.telemetry()["totals"]
    assert tm["rewalked"] > 0
    assert tm["lost"] > 0


def test_escalation_composes_with_xpoints(mesh):
    """The re-walk appends its crossing points after the prior
    attempt's, so the recorded path matches an uninterrupted walk."""
    cfg = dict(tolerance=1e-6, record_xpoints=8)
    t = _init(
        PumiTally(
            mesh, N,
            TallyConfig(
                max_crossings=2, truncation_retries=6, **cfg
            ),
        )
    )
    ref = _init(PumiTally(mesh, N, TallyConfig(**cfg)))
    for tally in (t, ref):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            tally.move_to_next_location(*_inputs(1))
    xp_t, c_t = t.intersection_points()
    xp_r, c_r = ref.intersection_points()
    np.testing.assert_array_equal(c_t, c_r)
    np.testing.assert_allclose(xp_t, xp_r, atol=1e-5)


def test_partitioned_escalation_recovers(mesh64):
    """The partitioned escalation (re-arming the same compiled step on
    the truncated lanes) must reproduce the unbounded run's flux."""
    cfg = TallyConfig(
        dtype=jnp.float64, tolerance=1e-8, truncation_retries=8
    )
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        t = PartitionedTally(
            mesh64, N, cfg, n_parts=8, max_rounds=1
        )
        _init(t)
        for i in range(1, 3):
            t.move_to_next_location(*_inputs(i))
    ref = PartitionedTally(
        mesh64, N,
        TallyConfig(dtype=jnp.float64, tolerance=1e-8),
        n_parts=8,
    )
    _init(ref)
    for i in range(1, 3):
        ref.move_to_next_location(*_inputs(i))
    np.testing.assert_allclose(
        t.raw_flux, ref.raw_flux, rtol=0, atol=1e-11
    )
    tm = t.telemetry()["totals"]
    assert tm["rewalked"] > 0 and tm["lost"] == 0


def test_partitioned_escalation_batch_sd_folds_once_per_move(mesh64):
    """sd_mode='batch' + escalation: slot 1 must accumulate ONE squared
    delta per MOVE (the merged total), not one per re-walk attempt —
    i.e. the escalated run's squares equal the unbounded run's."""
    def drive(**kw):
        t = PartitionedTally(
            mesh64, N,
            TallyConfig(
                dtype=jnp.float64, tolerance=1e-8, sd_mode="batch",
                **kw.pop("cfg", {}),
            ),
            n_parts=8, **kw,
        )
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            _init(t)
            for i in range(1, 3):
                t.move_to_next_location(*_inputs(i))
        return t

    esc = drive(max_rounds=1, cfg=dict(truncation_retries=8))
    ref = drive()
    assert esc.telemetry()["totals"]["rewalked"] > 0
    np.testing.assert_allclose(
        esc.raw_flux[..., 1], ref.raw_flux[..., 1], rtol=0, atol=1e-11
    )
    np.testing.assert_allclose(
        esc.raw_flux[..., 0], ref.raw_flux[..., 0], rtol=0, atol=1e-11
    )


def test_partitioned_truncation_warns_without_retries(mesh64):
    t = PartitionedTally(
        mesh64, N,
        TallyConfig(dtype=jnp.float64, tolerance=1e-8),
        n_parts=8, max_rounds=1,
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # initial search truncates too
        _init(t)
    with pytest.warns(RuntimeWarning, match="truncated"):
        t.move_to_next_location(*_inputs(1))
    assert t.telemetry()["totals"]["lost"] > 0


# ===================================================================== #
# The stuck>=4 frozen-lane contract
# ===================================================================== #
def test_escalated_bump_frozen_lane_contract():
    """The partitioned exchange freezes mid-walk lanes for migration and
    reads ``stuck>=4`` on the far side to know a lane froze mid-chase
    (walk bodies: ``chase = active & (stuck >= 4) & ~contained``). The
    contract that makes this sound: a NON-continuing (frozen) lane
    KEEPS its zero-progress counter; only real progress resets it. The
    bump (forward_bump) is one fixed length, 32 ulps of the coordinate,
    whatever the count: it no longer doubles, and a frozen lane or a
    real step gets none. The destinations lie far (1 along x), so no
    bump lands on one."""
    dtype = jnp.float64
    tol_floor = 8 * float(jnp.finfo(dtype).eps)
    n = 5
    stuck = jnp.array([0, 2, 5, 48, 3], jnp.int32)
    contained = jnp.zeros(n, bool)
    #            zero-step  zero-step  FROZEN  zero-step  real-step
    continuing = jnp.array([True, True, False, True, True])
    t_step = jnp.array([0.0, 0.0, 0.0, 0.0, 0.5], dtype)
    cur = jnp.ones((n, 3), dtype)
    dirv = jnp.zeros((n, 3), dtype).at[:, 0].set(1.0)
    dnorm = jnp.ones(n, dtype)
    moved, nxt, bumped = forward_bump(
        stuck, contained, continuing, t_step, tol_floor, cur, dirv,
        cur + dirv, dnorm,
    )
    nxt = np.asarray(nxt)
    assert nxt[0] == 1   # zero-progress increments
    assert nxt[1] == 3
    assert nxt[2] == 5   # FROZEN lane keeps its count across the cut
    assert nxt[3] == 49  # no cap: nothing doubles with the count
    assert nxt[4] == 0   # real progress resets
    extra = np.asarray(moved)[:, 0] - 1.0
    floor = float(bump_length(tol_floor, cur)[0])
    assert floor == 4 * tol_floor * 2.0  # 32 ulps of |cur| = 1
    np.testing.assert_allclose(extra, [floor, floor, 0, floor, 0],
                               rtol=1e-3)
    np.testing.assert_array_equal(
        np.asarray(bumped), [True, True, False, True, False])
    np.testing.assert_array_equal(np.asarray(moved)[:, 1:], 1.0)


def test_escalated_bump_resets_on_containment():
    """A genuinely contained lane resets even at zero step — chase
    recovery ends the moment containment is restored."""
    dtype = jnp.float64
    n = 2
    stuck = jnp.array([6, 6], jnp.int32)
    contained = jnp.array([True, False])
    continuing = jnp.array([True, True])
    t_step = jnp.zeros(n, dtype)
    cur = jnp.ones((n, 3), dtype)
    _, nxt, bumped = forward_bump(
        stuck, contained, continuing, t_step,
        8 * float(jnp.finfo(dtype).eps), cur, cur, 2 * cur,
        jnp.full(n, np.sqrt(3.0), dtype),
    )
    nxt = np.asarray(nxt)
    assert nxt[0] == 0 and nxt[1] == 7
    assert np.asarray(bumped).all()  # both advance by the bump


def test_chase_face_choice_excludes_boundary_faces():
    """A mislocated but in-domain particle must never be chased out of
    the domain: boundary faces are excluded while any interior
    candidate exists."""
    dtype = jnp.float64
    sd = jnp.array([[1.0, 2.0, 0.5, 0.1]], dtype)  # face 1 most violated
    interior = jnp.array([[True, False, True, True]])  # face 1 = boundary
    for it in range(8):  # any iteration's pseudo-random weights
        face = chase_face_choice(
            sd, jnp.array([7], jnp.int32), jnp.int32(it), dtype,
            interior,
        )
        assert bool(interior[0, int(face[0])])
    # With NO interior candidate the exclusion lifts (any face valid).
    none_interior = jnp.zeros((1, 4), bool)
    face = chase_face_choice(
        sd, jnp.array([7], jnp.int32), jnp.int32(0), dtype,
        none_interior,
    )
    assert 0 <= int(face[0]) < 4


# ===================================================================== #
# Flight-record surface: dispatches and the truncated lanes named
# ===================================================================== #
def _count_dispatches(t):
    """Wrap the facade's one walk entry point with a counter."""
    calls = []
    inner = t._trace

    def counted(*args, **kwargs):
        calls.append(1)
        return inner(*args, **kwargs)

    t._trace = counted
    return calls


def test_dispatches_count_rewalks(mesh):
    """A move's record counts its compiled walk launches: 1, plus one
    per escalation re-walk — the launches the facade actually made."""
    t = _init(PumiTally(mesh, N, TallyConfig(
        tolerance=1e-6, max_crossings=2, truncation_retries=5)))
    calls = _count_dispatches(t)
    t.move_to_next_location(*_inputs(1))
    rec = [r for r in t.telemetry()["per_move"] if r["kind"] == "move"][-1]
    assert rec["dispatches"] == len(calls) > 1
    assert rec["lanes"] == N


def test_truncated_lanes_named_in_record_and_warning(mesh):
    """Lanes still unfinished are named, up to 8, by particle id, parent
    element and position, in the move record and the warning."""
    t = _init(PumiTally(mesh, N, TallyConfig(tolerance=1e-6,
                                             max_crossings=2)))
    args = _inputs(1)
    with pytest.warns(RuntimeWarning, match="Truncated lanes") as w:
        t.move_to_next_location(*args)
    rec = [r for r in t.telemetry()["per_move"] if r["kind"] == "move"][-1]
    named = rec["truncated_lanes"]
    assert 0 < len(named) == min(8, rec["truncated"])
    pos = args[0].reshape(N, 3)  # written back in place
    for ln in named:
        assert t.element_ids[ln["pid"]] == ln["elem"]
        np.testing.assert_allclose(ln["pos"], pos[ln["pid"]])
    assert str(named[0]["pid"]) in str(w[0].message)
