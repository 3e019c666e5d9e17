"""The walk's end-of-flight rule (ops/walk.py ``end_of_flight`` and
``forward_bump``) on destinations that many tets share.

A float32 lane sent to a point on a cube's Freudenthal diagonal, on a
shared face diagonal or on a mesh vertex meets tets that all report an
exit at t≈0 near that point. An uncapped forward bump (32 ulps of the
coordinate) carries such a lane past its destination, the next ray
points back, and the lane oscillates until ``max_crossings``. The bump
now lands a lane it would carry past its destination on it. Every lane
must finish under a cap of 200 and agree with the plain float64
reference (``benchmark/reference.py``): final positions, scored track
lengths and segment counts. Lanes whose destination lies just past a
region face, within one bump of their last crossing, must stop on that
face with the far region's material, as the reference stops them.

The pinned lanes are three lanes that a pin-cell event batch trapped
(55^3 cubes, destinations clipped to [0.01, 0.99], float32), walked here
on a corner of that mesh with the same vertex coordinates.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark import reference
from benchmark.mesh import _CELL_TETS
from pumiumtally_tpu import PumiTally, TallyConfig, make_flux
from pumiumtally_tpu.integrity.audit import HostReference
from pumiumtally_tpu.mesh.core import TetMesh
from pumiumtally_tpu.obs import stats_to_dict
from pumiumtally_tpu.obs.walk_stats import WALK_STATS_FIELDS
from pumiumtally_tpu.ops.walk import (
    bump_length,
    end_of_flight,
    forward_bump,
    trace,
)

CAP = 200
F32_FLOOR = 8 * float(np.finfo(np.float32).eps)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _box(axes):
    """Coordinates and tets of the box whose vertex planes are ``axes``
    (three 1-D arrays), 6 Freudenthal tets per cube, vertex ids
    x-fastest and elements cell-major, as ``benchmark/mesh.py``."""
    nx, ny, nz = (len(a) - 1 for a in axes)
    gz, gy, gx = np.meshgrid(axes[2], axes[1], axes[0], indexing="ij")
    coords = np.stack([gx.ravel(), gy.ravel(), gz.ravel()], axis=1)
    ck, cj, ci = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                             indexing="ij")
    ci, cj, ck = ci.ravel(), cj.ravel(), ck.ravel()
    tets = np.empty((ci.size, 6, 4), np.int64)
    for t in range(6):
        for v in range(4):
            dx, dy, dz = _CELL_TETS[t, v]
            tets[:, t, v] = (ci + dx) + (nx + 1) * (
                (cj + dy) + (ny + 1) * (ck + dz))
    return coords, tets.reshape(-1, 4)


def _locate(coords, tets, pts):
    """The first tet holding each point (float64, 1e-12 outside)."""
    T = reference.Tables(coords, tets, np.zeros(len(tets), np.int64))
    sd = np.einsum("tfc,kc->ktf", T.normals, pts) - T.d[None]
    inside = sd.max(axis=2) <= 1e-12
    assert inside.any(axis=1).all()
    return inside.argmax(axis=1)


def _diagonal_points(axes, cube, rng, k):
    """Points on the cube's Freudenthal diagonal, on the face diagonals
    its tets share ((0,0,0)-(1,1,0), (0,0,0)-(1,0,1), (0,0,0)-(0,1,1) in
    cube-local coordinates), and its vertices."""
    lo = np.array([a[c] for a, c in zip(axes, cube)])
    h = np.array([a[c + 1] - a[c] for a, c in zip(axes, cube)])
    s = rng.uniform(0.05, 0.95, (k, 1))
    diag = lo + s * h
    faces = [lo + s * h * np.array(m, float)
             for m in ((1, 1, 0), (1, 0, 1), (0, 1, 1))]
    verts = lo + h * np.array([[1, 1, 1], [1, 0, 0], [0, 1, 1], [1, 1, 0]])
    return np.concatenate([diag, *faces, verts])


def _starts(dest, axes, rng, spread):
    """Starts around each destination, from every side, inside the box."""
    lo = np.array([a[0] for a in axes]) + 1e-3
    hi = np.array([a[-1] for a in axes]) - 1e-3
    d = rng.normal(size=dest.shape)
    d /= np.linalg.norm(d, axis=1, keepdims=True)
    return np.clip(dest + d * spread * rng.uniform(0.2, 1.0, (len(dest), 1)),
                   lo, hi)


UNIT8 = [np.linspace(0.0, 1.0, 9)] * 3
# The assembly's 15:1 cubes (1/136 across, 1/9 high): its far 4 x 4 x 2
# corner, the same vertex coordinates as the 136 x 136 x 9 mesh.
ANISO = ([np.linspace(0.0, 1.0, 137)[-5:]] * 2
         + [np.linspace(0.0, 1.0, 10)[-3:]])
# A corner of the 55^3 pin-cell mesh: cubes 40-54 on every axis, the
# same vertex coordinates as the whole mesh.
PIN55 = [np.linspace(0.0, 1.0, 56)[40:]] * 3

# Trapped lanes of a pin-cell event batch (float32 start, parent element
# in the 55^3 mesh, destination): under the uncapped bump each ran to
# max_crossings near (0.99, 0.99, 0.99).
PINNED = [
    ((0.8630161285400391, 0.8695781230926514, 0.9900000095367432), 995892),
    ((0.9641443490982056, 0.8780084848403931, 0.9787395000457764), 978110),
    ((0.9446831941604614, 0.8945346474647522, 0.9900000095367432), 996580),
]


def _pinned_elem(e):
    """An element of the 55^3 mesh, numbered in the PIN55 corner."""
    cell, t = divmod(e, 6)
    ci, cj, ck = cell % 55 - 40, cell // 55 % 55 - 40, cell // 3025 - 40
    return 6 * (ci + 15 * (cj + 15 * ck)) + t


# Each case: its mesh, the seed of its points and starts, and how far
# the starts lie from their destinations. Every case traps at least one
# lane under the uncapped bump.
CASES = {
    "clip_unit8": (UNIT8, 0, 0.02),
    "clip_pin55": (PIN55, 0, 0.15),
    "shared_unit8": (UNIT8, 1, 0.02),
    "shared_aniso": (ANISO, 0, 0.01),
}
CLIP = np.array([[0.99] * 3, [0.01] * 3, [0.99, 0.01, 0.99],
                 [0.01, 0.99, 0.01]])


def _case(name):
    if name == "pinned":
        start = np.array([p for p, _ in PINNED])
        elem = np.array([_pinned_elem(e) for _, e in PINNED])
        return PIN55, start, elem, np.full((3, 3), 0.99)
    axes, seed, spread = CASES[name]
    rng = np.random.default_rng(seed)
    if name == "clip_unit8":
        dest = np.repeat(CLIP, 48, axis=0)
    elif name == "clip_pin55":
        dest = np.full((48, 3), 0.99)  # the only clip corner in PIN55
    else:
        cubes = ([(7, 7, 7), (3, 4, 5), (0, 0, 0)] if axes is UNIT8
                 else [(3, 3, 1), (1, 2, 0)])
        dest = np.repeat(np.concatenate(
            [_diagonal_points(axes, c, rng, 3) for c in cubes]), 12, axis=0)
    return axes, _starts(dest, axes, rng, spread), None, dest


def _walk(axes, start, elem, dest):
    """The float32 program and the float64 reference on the same lanes,
    each lane's reference walked from the program's float32 start."""
    coords, tets = _box(axes)
    mesh = TetMesh.from_numpy(coords, tets, dtype=jnp.float32)
    start = np.asarray(start, np.float32).astype(np.float64)
    dest = np.asarray(np.asarray(dest, np.float32), np.float64)
    if elem is None:
        elem = _locate(coords, tets, start)
    n = len(start)
    res = trace(
        mesh, jnp.asarray(start, jnp.float32), jnp.asarray(dest, jnp.float32),
        jnp.asarray(elem, jnp.int32), jnp.ones(n, bool),
        jnp.ones(n, jnp.float32), jnp.zeros(n, jnp.int32),
        jnp.full(n, -1, jnp.int32),
        make_flux(mesh.ntet, 1, jnp.float32, flat=True),
        initial=False, max_crossings=CAP, tolerance=1e-8, n_groups=1,
        record_xpoints=CAP,
    )
    T = reference.Tables(coords, tets, np.zeros(len(tets), np.int64))
    want = [reference.walk(T, start[i:i + 1], elem[i:i + 1], dest[i:i + 1],
                           np.ones(1), np.zeros(1, np.int64), groups=1,
                           tol_floor=F32_FLOOR, max_steps=10 * CAP)
            for i in range(n)]
    return res, want, dest


@pytest.mark.parametrize("name", ["pinned", *CASES])
def test_shared_destination_lanes_finish(name):
    """Every lane ends on its destination under a cap of 200, with the
    reference's final position, scored length (but for what the
    recovery carried) and segments."""
    axes, start, elem, dest = _case(name)
    res, want, dest = _walk(axes, start, elem, dest)
    start = np.asarray(start, np.float32).astype(np.float64)
    d = stats_to_dict(res.stats)
    assert bool(np.asarray(res.done).all()), d
    assert d["truncated"] == 0
    assert all(w.finished.all() for w in want)
    pos = np.asarray(res.position, np.float64)
    ref_pos = np.concatenate([w.position for w in want])
    assert np.abs(pos - ref_pos).max() <= 1e-5
    assert np.abs(pos - dest).max() <= 1e-5
    # A bump's hop, a landing included (each under 32 ulps, about
    # 7.6e-6 here), is not scored: the scored lengths agree to those
    # the batch took.
    ref_len = np.array([w.scores.sum() for w in want])
    gap = np.abs(np.asarray(res.track_length, np.float64) - ref_len)
    recovered = d["bumps"] + len(start)
    assert gap.sum() <= recovered * 8 * F32_FLOOR
    # Segment counts: per lane, the segments longer than a bump agree;
    # the rest are hops of zero length at shared points, which float32
    # and float64 take differently.
    kx = np.asarray(res.n_xpoints)
    xp = np.asarray(res.xpoints, np.float64)
    for i, w in enumerate(want):
        pts = np.concatenate([start[i:i + 1], xp[i, :kx[i]], pos[i:i + 1]])
        seg = np.linalg.norm(np.diff(pts, axis=0), axis=1)
        assert (seg > 1e-5).sum() == (w.scores > 1e-5).sum(), i


def test_pinned_lanes_met_the_floor():
    """The pinned lanes come within the bump's length (the floor of a
    lane's progress per crossing) of their destination and finish within
    a few dozen iterations, through bumps (the landing on the
    destination among them), where the uncapped bump kept them to the
    cap."""
    axes, start, elem, dest = _case("pinned")
    res, _, _ = _walk(axes, start, elem, dest)
    d = stats_to_dict(res.stats)
    assert d["truncated"] == 0 and d["bumps"] >= 1
    assert d["loop_iters"] < 60, d


# Lanes whose destination lies ``past`` beyond the region face x = 0.875
# of UNIT8, and whose last crossing before it (the Freudenthal plane
# x = y of the cube below the face, set by ``y``) lies ``before`` short
# of it: past + before stays under one bump (about 7.1e-6 there), so
# the lane's last ray inside its region is shorter than a bump.
REGION_X = 0.875


def _region_lanes(past, before):
    z = np.array([0.8, 0.78, 0.83])
    y = np.full(3, REGION_X - before)
    fwd_start = np.stack([np.full(3, 0.80), y, z], axis=1)
    fwd_dest = np.stack([np.full(3, REGION_X + past), y, z], axis=1)
    # The same from the other side: x = y planes of the cube above the
    # face, the lane heading down into region 0.
    yb = np.full(3, 0.75 + before)
    back_start = np.stack([np.full(3, 0.95), yb, z], axis=1)
    back_dest = np.stack([np.full(3, REGION_X - past), yb, z], axis=1)
    return (np.concatenate([fwd_start, back_start]),
            np.concatenate([fwd_dest, back_dest]))


@pytest.mark.parametrize("past, before", [
    (2e-6, 3e-6), (3e-6, 3e-6), (5e-6, 1e-6), (2e-6, 1e-6),
])
def test_region_face_near_destination(past, before):
    """A lane stops on the region face with the far region's material
    and element, as the reference stops it, not on its destination in
    the near region: only a bump's overshoot is capped, never a real
    crossing."""
    coords, tets = _box(UNIT8)
    cent = coords[tets].mean(axis=1)
    region = (cent[:, 0] > REGION_X).astype(np.int64)
    mesh = TetMesh.from_numpy(coords, tets, class_id=region,
                              dtype=jnp.float32)
    start, dest = _region_lanes(past, before)
    start = np.asarray(start, np.float32).astype(np.float64)
    dest = np.asarray(dest, np.float32).astype(np.float64)
    elem = _locate(coords, tets, start)
    n = len(start)
    res = trace(
        mesh, jnp.asarray(start, jnp.float32), jnp.asarray(dest, jnp.float32),
        jnp.asarray(elem, jnp.int32), jnp.ones(n, bool),
        jnp.ones(n, jnp.float32), jnp.zeros(n, jnp.int32),
        jnp.full(n, -1, jnp.int32),
        make_flux(mesh.ntet, 1, jnp.float32, flat=True),
        initial=False, max_crossings=CAP, tolerance=1e-8, n_groups=1,
    )
    T = reference.Tables(coords, tets, region)
    want = reference.walk(T, start, elem, dest, np.ones(n),
                          np.zeros(n, np.int64), groups=1,
                          tol_floor=F32_FLOOR, max_steps=10 * CAP)
    assert bool(np.asarray(res.done).all()) and want.finished.all()
    # Every lane crosses the face: the reference stops it there.
    np.testing.assert_array_equal(want.material, region[want.elem])
    np.testing.assert_array_equal(np.asarray(res.material_id), want.material)
    np.testing.assert_array_equal(np.asarray(res.elem), want.elem)
    pos = np.asarray(res.position, np.float64)
    assert np.abs(pos - want.position).max() <= 1e-6
    assert np.abs(pos[:, 0] - REGION_X).max() <= 1e-6


@pytest.mark.parametrize("t_exit, has_exit, dnorm, reached", [
    (0.0, True, 1e-7, False),     # exit at t≈0, 1e-7 left: cross
    (0.0, True, 1e-3, False),     # exit at t≈0, far: cross and bump
    (0.5, True, 1e-7, False),     # an exit within a bump: cross
    (1.0, True, 1e-3, True),      # no exit before the destination
    (np.inf, False, 1e-3, True),  # no exit at all
    (np.inf, False, 0.0, True),   # a zero-length ray (a landed lane)
])
def test_end_of_flight_rule(t_exit, has_exit, dnorm, reached):
    """The reached test on single lanes, in JAX and on NumPy (the host
    audit's mirror): a lane ends when no exit lies before its
    destination, however close that is; a real exit is always
    crossed."""
    for xp in (jnp, np):
        got, t_step = end_of_flight(
            xp.asarray([t_exit], np.float32), xp.asarray([has_exit]),
            xp.asarray([dnorm], np.float32), 1e-8, F32_FLOOR, xp=xp)
        assert bool(got[0]) is reached
        assert float(t_step[0]) == min(t_exit, 1.0)


def test_floor_end_is_carried_onto_its_destination():
    """A lane whose remaining flight is under the bump's length (the
    floor of its progress per crossing) is carried exactly onto its
    destination by forward_bump; the others are bumped by the bump's
    length, and lanes that did not continue stay where they are."""
    cur = jnp.full((4, 3), 0.99, jnp.float32)
    dest = cur + jnp.array([1e-7, 1e-3, 1e-3, 1e-7], jnp.float32)[:, None]
    dirv = dest - cur
    dnorm = jnp.linalg.norm(dirv, axis=-1)
    t_step = jnp.array([0.0, 0.0, 0.5, 0.0], jnp.float32)
    continuing = jnp.array([True, True, True, False])
    nxt, stuck, bumped = forward_bump(
        jnp.zeros(4, jnp.int32), jnp.ones(4, bool), continuing, t_step,
        F32_FLOOR, cur, dirv, dest, dnorm)
    np.testing.assert_array_equal(np.asarray(bumped),
                                  [True, True, False, False])
    nxt = np.asarray(nxt)
    np.testing.assert_array_equal(nxt[0], np.asarray(dest[0]))
    hop = float(bump_length(F32_FLOOR, cur)[1])
    # The bump's length, but for the rounding of float32 positions.
    np.testing.assert_allclose(np.linalg.norm(nxt[1] - np.asarray(cur[1])),
                               hop, rtol=0, atol=2 * np.spacing(np.float32(1)))
    np.testing.assert_array_equal(nxt[2:], np.asarray(cur[2:]))
    np.testing.assert_array_equal(np.asarray(stuck), [0, 0, 0, 0])


def test_host_audit_mirror_agrees_with_the_walk():
    """The audit's float64 re-walk (integrity/audit.py) and the float64
    XLA walk agree on lanes sent to shared destinations: both finish, on
    the same final positions and scored lengths."""
    axes, start, _, dest = _case("shared_unit8")
    coords, tets = _box(axes)
    mesh = TetMesh.from_numpy(coords, tets, dtype=jnp.float64)
    elem = _locate(coords, tets, start)
    n = len(start)
    res = trace(
        mesh, jnp.asarray(start), jnp.asarray(dest), jnp.asarray(elem),
        jnp.ones(n, bool), jnp.ones(n), jnp.zeros(n, jnp.int32),
        jnp.full(n, -1, jnp.int32),
        make_flux(mesh.ntet, 1, jnp.float64, flat=True),
        initial=False, max_crossings=CAP, tolerance=1e-8, n_groups=1,
    )
    assert bool(np.asarray(res.done).all())
    ref = HostReference(mesh)
    for i in range(n):
        pos, _, track, finished = ref.walk_lane(
            start[i], dest[i], int(elem[i]), 1e-8, CAP)
        assert finished
        assert np.abs(pos - np.asarray(res.position[i])).max() <= 1e-9
        assert abs(track - float(res.track_length[i])) <= 1e-9


# --------------------------------------------------------------------- #
# The recovery counters in every walk body
# --------------------------------------------------------------------- #
def test_stats_vector_ends_with_recovery_fields():
    # The recovery counter is the last a walk body fills; the search's
    # seed count (written after the walk) follows it.
    assert WALK_STATS_FIELDS[-2:] == ("bumps", "grid_seeded")


def _corner_batch(n=96, seed=7):
    rng = np.random.default_rng(seed)
    corners = np.array([[0.99] * 3, [0.01] * 3, [0.99, 0.01, 0.99]])
    dest = corners[rng.integers(0, 3, n)]
    start = np.clip(dest + rng.normal(0, 0.15, (n, 3)), 0.02, 0.98)
    return start, dest


def test_recovery_counters_agree_across_walks():
    """``bumps`` and ``chase_hops`` are filled, and equal, in the XLA
    walk, the Pallas kernel (interpreted) and the partitioned walk (8
    parts) on a float32 batch of clip-corner destinations, where bumps
    occur; each walk run through its facade from the same starts."""
    from pumiumtally_tpu.parallel.partitioned_api import PartitionedTally

    coords, tets = _box(UNIT8)
    mesh = TetMesh.from_numpy(coords, tets, dtype=jnp.float32)
    start, dest = _corner_batch()
    n = len(start)

    def cfg(**kw):
        return TallyConfig(dtype=jnp.float32, n_groups=1, max_crossings=CAP,
                           **kw)

    got = {}
    for name, t in (
        ("xla", PumiTally(mesh, n, cfg())),
        ("pallas", PumiTally(mesh, n, cfg(kernel="pallas"))),
        ("partitioned", PartitionedTally(mesh, n, cfg(), n_parts=8)),
    ):
        # The partitioned search starts where each lane stands; so do
        # these, without the single-chip facade's seed grid: a start on
        # a face many of these clipped starts sit on lands in the
        # element its ray enters last, so the moves compared here start
        # in the same elements only from the same search start.
        if hasattr(t, "_seed_grid"):
            t._seed_grid = None
        t.initialize_particle_location(start.ravel().copy())
        t.move_to_next_location(
            dest.ravel().copy(), np.ones(n, np.int8), np.ones(n),
            np.zeros(n, np.int32), np.full(n, -1, np.int32))
        rec = t.telemetry()["per_move"][-1]
        got[name] = (rec["bumps"], rec["chase_hops"], rec["truncated"])
    assert got["xla"][0] > 0 and got["xla"][2] == 0
    assert got["pallas"] == got["xla"] == got["partitioned"]


def test_facade_records_carry_recovery_counters():
    """The flight records of the search, a facade move and a megastep
    carry both recovery counters (the benchmark's readers take them
    from there)."""
    from pumiumtally_tpu.ops.source import SourceParams

    coords, tets = _box(UNIT8)
    mesh = TetMesh.from_numpy(coords, tets, dtype=jnp.float32)
    start, dest = _corner_batch(n=64)
    t = PumiTally(mesh, 64, TallyConfig(dtype=jnp.float32, n_groups=2))
    t.initialize_particle_location(start.ravel().copy())
    t.move_to_next_location(dest.ravel().copy(), np.ones(64, np.int8),
                            np.ones(64), np.zeros(64, np.int32),
                            np.full(64, -1, np.int32))
    t.run_source_moves(2, SourceParams(sigma_t={0: 12.5},
                                       absorption={0: 0.3}, seed=3))
    recs = t.telemetry()["per_move"]
    kinds = {r.get("kind") for r in recs if "bumps" in r}
    assert {"initial_search", "move", "megastep"} <= kinds
    assert all("chase_hops" in r for r in recs if "bumps" in r)
    move = [r for r in recs if r.get("kind") == "move"][-1]
    assert move["bumps"] > 0


# --------------------------------------------------------------------- #
# The benchmark's readers
# --------------------------------------------------------------------- #
def _reader(name):
    path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Run:
    def __init__(self, records):
        self.records = records


@pytest.mark.parametrize("name, field", [
    ("walk_bumps_per_move", "bumps"),
    ("walk_chase_hops_per_move", "chase_hops"),
])
def test_recovery_readers(name, field):
    """Counts of the search, moves and megasteps over the moves they
    cover; nothing where the records carry no such field."""
    read = _reader(name)
    recs = [
        {"kind": "initial_search", field: 40},
        {"kind": "megastep", "moves": 3, field: 20},
        {"kind": "move", field: 6},
        {"kind": "readback"},
    ]
    assert read(_Run(recs)) == (40 + 20 + 6) / 4
    bare = [{k: v for k, v in r.items() if k != field} for r in recs]
    assert read(_Run(bare)) is None
    assert read(_Run([{"kind": "initial_search", field: 5}])) is None
    assert read(_Run([])) is None
