"""The batch-start search's seed grid (ops/seed_grid.py).

Contracts:

  * UNCHANGED RESULTS — a search started from grid seeds ends where the
    search from the lanes' own starts ends: source points inside a tet
    land in the element ``locate_points`` finds, points on shared faces,
    edges and vertices in an element holding them (within the
    tolerance), all on the same position (to the tolerance; exactly,
    inside a tet) with the same material; lanes
    outside the bounding box, in a hole, or not flying come back bit for
    bit as without the grid. On a convex box, a thin-tet box and a box
    with a notch cut out.
  * SAME STATE — XLA, Pallas (interpret) and legacy-io inits leave the
    same particle state.
  * FEWER SLOTS — the ``initial_search`` record's lane-slots a lane fall
    where the old walk is long; ``grid_seeded`` counts every lane.
  * SMALL TABLE — under 1 MB at 1M tets, for both benchmark meshes.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

import jax.numpy as jnp

from pumiumtally_tpu import PumiTally, TallyConfig, make_flux
from pumiumtally_tpu.mesh.box import build_box, build_box_arrays
from pumiumtally_tpu.mesh.core import FACE_LOCAL_VERTS, TetMesh
from pumiumtally_tpu.obs.walk_stats import stats_to_dict
from pumiumtally_tpu.ops.geometry import locate_points
from pumiumtally_tpu.ops.seed_grid import build_seed_grid, seed_grid_dims
from pumiumtally_tpu.ops.walk import trace

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
TOL = 1e-8
PER_KIND = 48


def _notched_mesh():
    """A 6^3-cube box with the cubes of x > 0.5 and y > 0.5 removed: an
    L-shaped prism, non-convex, with empty cells in the notch."""
    nx = 6
    coords, t2v = build_box_arrays(1.0, 1.0, 1.0, nx, nx, nx)
    cube = np.arange(len(t2v)) // 6
    ci, cj = cube % nx, (cube // nx) % nx
    t2v = t2v[~((ci >= nx // 2) & (cj >= nx // 2))]
    used, t2v = np.unique(t2v, return_inverse=True)
    return TetMesh.from_numpy(
        coords[used], t2v.reshape(-1, 4), dtype=jnp.float64
    )


MESHES = {
    "box": lambda: build_box(1, 1, 1, 6, 6, 6, dtype=jnp.float64),
    # 15:1 tets, as in the 17x17 assembly (136 x 136 x 9 cubes).
    "thin": lambda: build_box(1, 1, 1, 15, 15, 1, dtype=jnp.float64),
    "notched": _notched_mesh,
}


def _points(mesh, rng):
    """Source points by kind: tet centroids, points on interior faces,
    on edges and on vertices, outside the bounding box, and (notched
    mesh) in the notch."""
    c = np.asarray(mesh.coords, np.float64)
    t2v = np.asarray(mesh.tet2vert)
    t2t = np.asarray(mesh.tet2tet)
    pick = lambda a: a[rng.choice(len(a), PER_KIND, replace=len(a) < PER_KIND)]
    tets = pick(np.arange(len(t2v)))
    inner_t, inner_f = np.nonzero(t2t >= 0)
    k = pick(np.arange(len(inner_t)))
    face = c[t2v[inner_t[k, None], FACE_LOCAL_VERTS[inner_f[k]]]].mean(1)
    a, b = rng.integers(0, 4, (2, PER_KIND))
    b = np.where(a == b, (b + 1) % 4, b)
    edge = 0.5 * (c[t2v[tets, a]] + c[t2v[tets, b]])
    out = {
        "centroid": c[t2v[tets]].mean(1),
        "face": face,
        "edge": edge,
        "vertex": c[t2v[tets, a]],
    }
    outside = rng.uniform(0.0, 1.0, (PER_KIND, 3))
    axis = rng.integers(0, 3, PER_KIND)
    outside[np.arange(PER_KIND), axis] = np.where(
        rng.random(PER_KIND) < 0.5,
        rng.uniform(-0.5, -0.01, PER_KIND),
        rng.uniform(1.01, 1.5, PER_KIND),
    )
    out["outside"] = outside
    if len(t2v) < 6 * 6**3:
        notch = rng.uniform(0.51, 0.99, (PER_KIND, 3))
        notch[:, 2] = rng.uniform(0.0, 1.0, PER_KIND)
        out["hole"] = notch
    return out


def _search(mesh, start_elem, dest, fly, grid):
    n = len(dest)
    cen = np.asarray(mesh.centroids())
    return trace(
        mesh,
        jnp.asarray(cen[start_elem]),
        jnp.asarray(dest),
        jnp.asarray(start_elem, jnp.int32),
        jnp.asarray(fly),
        jnp.ones(n, mesh.dtype),
        jnp.zeros(n, jnp.int32),
        jnp.full(n, 7, jnp.int32),
        make_flux(mesh.ntet, 1, mesh.dtype, flat=True),
        initial=True,
        max_crossings=4 * mesh.ntet,
        tolerance=TOL,
        n_groups=1,
        seed_grid=grid,
    )


@pytest.fixture(scope="module", params=sorted(MESHES))
def searched(request):
    """One mesh's searches, with and without the grid, every kind of
    point in one batch. Lanes start at the element-0 centroid, as after
    construction: the notched mesh is star-shaped from there, so the
    search without the grid reaches every point of the mesh; every
    eleventh lane is not flying."""
    mesh = MESHES[request.param]()
    rng = np.random.default_rng(5)
    kinds = _points(mesh, rng)
    dest = np.concatenate(list(kinds.values()))
    sl, at = {}, 0
    for name, pts in kinds.items():
        sl[name] = slice(at, at + len(pts))
        at += len(pts)
    start = np.zeros(len(dest), np.int64)
    fly = np.ones(len(dest), bool)
    fly[::11] = False
    grid = build_seed_grid(mesh)
    seeded = _search(mesh, start, dest, fly, grid)
    plain = _search(mesh, start, dest, fly, None)
    return dict(mesh=mesh, name=request.param, dest=dest, sl=sl,
                start=start, fly=fly, grid=grid, seeded=seeded, plain=plain)


def _np(r):
    return {k: np.asarray(getattr(r, k))
            for k in ("position", "elem", "material_id", "done")}


@pytest.mark.parametrize("kind", ["centroid", "face", "edge", "vertex"])
def test_seeded_search_lands_on_the_same_point(searched, kind):
    s, p = _np(searched["seeded"]), _np(searched["plain"])
    m = searched["sl"][kind]
    fly = searched["fly"][m]
    dest = searched["dest"][m]
    mesh = searched["mesh"]
    assert s["done"][m].all()
    np.testing.assert_array_equal(s["material_id"][m], p["material_id"][m])
    # A point on a face is reached by a crossing onto it, a few ulps
    # off; a point inside a tet is reached exactly, by either start.
    np.testing.assert_allclose(s["position"][m], p["position"][m],
                               rtol=0, atol=TOL)
    np.testing.assert_allclose(s["position"][m][fly], dest[fly],
                               rtol=0, atol=TOL)
    if kind == "centroid":
        np.testing.assert_array_equal(s["position"][m], p["position"][m])
        np.testing.assert_array_equal(s["position"][m][fly], dest[fly])
    # The element holds the point, within the tolerance.
    e = s["elem"][m][fly]
    n = np.asarray(mesh.face_normals)[e]
    sd = (np.einsum("pfc,pc->pf", n, dest[fly])
          - np.asarray(mesh.face_d)[e]) / np.linalg.norm(n, axis=-1)
    assert sd.max() <= TOL
    if kind == "centroid":
        want = np.asarray(locate_points(mesh, jnp.asarray(dest[fly]), TOL))
        np.testing.assert_array_equal(e, want)
        np.testing.assert_array_equal(s["elem"][m], p["elem"][m])


def test_lanes_without_a_seed_walk_as_before(searched):
    """Outside the bounding box, or in the notch (a cell no seed
    serves): the lane keeps its start, and the search is bit for bit
    the old one."""
    s, p = _np(searched["seeded"]), _np(searched["plain"])
    for kind in ("outside", "hole"):
        m = searched["sl"].get(kind)
        if m is None:
            continue
        for k in s:
            np.testing.assert_array_equal(s[k][m], p[k][m])


def test_lanes_not_flying_stay_untouched(searched):
    s, p = _np(searched["seeded"]), _np(searched["plain"])
    off = ~searched["fly"]
    cen = np.asarray(searched["mesh"].centroids())
    start = searched["start"][off]
    np.testing.assert_array_equal(s["elem"][off], start)
    np.testing.assert_array_equal(s["position"][off], cen[start])
    for k in s:
        np.testing.assert_array_equal(s[k][off], p[k][off])


def test_grid_seeded_counts_the_seeded_lanes(searched):
    """Every flying lane inside the box whose cell holds a seed, and no
    other: all of them on the convex meshes."""
    g, dest, fly = searched["grid"], searched["dest"], searched["fly"]
    lo, inv = np.asarray(g.lo), np.asarray(g.inv_cell)
    dims = np.asarray(g.dims)
    idx = np.clip(np.floor((dest - lo) * inv), 0, dims - 1).astype(int)
    cell = np.ravel_multi_index(idx.T, dims)
    inside = ((dest >= lo - TOL) & (dest <= np.asarray(g.hi) + TOL)).all(1)
    want = fly & inside & (np.asarray(g.seed)[cell] >= 0)
    got = stats_to_dict(searched["seeded"].stats)
    assert got["grid_seeded"] == want.sum()
    assert stats_to_dict(searched["plain"].stats)["grid_seeded"] == 0
    if searched["name"] != "notched":
        assert want.sum() == (fly & inside).sum()


def test_cells_next_to_a_hole_keep_no_seed(searched):
    """A convex box seeds every cell; the notched mesh leaves empty the
    cells in the notch and those its faces touch."""
    seed = np.asarray(searched["grid"].seed)
    if searched["name"] != "notched":
        assert (seed >= 0).all()
        return
    g = searched["grid"]
    dims = np.asarray(g.dims)
    centre = (np.stack(np.unravel_index(np.arange(seed.size), dims), 1)
              + 0.5) / np.asarray(g.inv_cell)
    near = (centre[:, 0] > 0.5 - 1 / dims[0]) & (
        centre[:, 1] > 0.5 - 1 / dims[1])
    assert (seed[near] == -1).all()
    assert (seed[~near] >= 0).all()

def test_seeds_sit_in_their_cells(searched):
    """Every seed's centroid lies in its cell, and the walk's start
    point lies strictly inside the seed."""
    g, mesh = searched["grid"], searched["mesh"]
    seed, dims = np.asarray(g.seed), np.asarray(g.dims)
    ok = seed >= 0
    cen = np.asarray(mesh.centroids())[seed[ok]]
    cell = np.floor((cen - np.asarray(g.lo)) * np.asarray(g.inv_cell))
    np.testing.assert_array_equal(
        np.ravel_multi_index(cell.astype(int).T, dims), np.flatnonzero(ok)
    )
    start = np.asarray(g.origin).T[ok]
    n = np.asarray(mesh.face_normals)[seed[ok]]
    sd = (np.einsum("pfc,pc->pf", n, start)
          - np.asarray(mesh.face_d)[seed[ok]]) / np.linalg.norm(n, axis=-1)
    assert sd.max() < -1e-3 * np.asarray(1 / g.inv_cell).min()


def test_seed_grid_is_for_the_search_only():
    mesh = MESHES["box"]()
    n = 4
    with pytest.raises(ValueError, match="initial=True"):
        trace(
            mesh, mesh.centroids()[:n], mesh.centroids()[:n],
            jnp.arange(n, dtype=jnp.int32), jnp.ones(n, bool),
            jnp.ones(n, mesh.dtype), jnp.zeros(n, jnp.int32),
            jnp.zeros(n, jnp.int32),
            make_flux(mesh.ntet, 1, mesh.dtype, flat=True),
            initial=False, max_crossings=64, n_groups=1,
            seed_grid=build_seed_grid(mesh),
        )


# --------------------------------------------------------------------- #
# Through the facade
# --------------------------------------------------------------------- #
N = 96


@pytest.fixture(scope="module")
def mesh3():
    coords, t2v = build_box_arrays(1.0, 1.0, 1.0, 3, 3, 3)
    cen = coords[t2v].mean(axis=1)
    cls = np.where(cen[:, 0] < 0.5, 1, 2).astype(np.int32)
    return TetMesh.from_numpy(coords, t2v, class_id=cls, dtype=jnp.float64)


def _init_twice(t, seed=3):
    """Two batch starts at drawn tet centroids, as the benchmark's
    sources are."""
    rng = np.random.default_rng(seed)
    cen = np.asarray(t.mesh.centroids(), np.float64)
    for _ in range(2):
        pos = cen[rng.integers(0, len(cen), t.num_particles)]
        t.initialize_particle_location(pos.ravel().copy())
    s = t.state
    recs = [r for r in t.telemetry()["per_move"]
            if r.get("kind") == "initial_search"]
    return (np.asarray(s.origin), np.asarray(s.elem),
            np.asarray(s.material_id)), recs


@pytest.fixture(scope="module")
def xla_packed(mesh3):
    return _init_twice(PumiTally(mesh3, N, TallyConfig(
        n_groups=2, dtype=jnp.float64, kernel="xla")))


@pytest.mark.parametrize("kernel, io", [
    ("pallas", "packed"), ("xla", "legacy"),
])
def test_inits_leave_the_same_state(mesh3, xla_packed, kernel, io):
    state_a, recs_a = xla_packed
    t = PumiTally(mesh3, N, TallyConfig(
        n_groups=2, dtype=jnp.float64, kernel=kernel, io_pipeline=io))
    assert t._kernel == kernel
    state_b, recs_b = _init_twice(t)
    for a, b in zip(state_a, state_b):
        np.testing.assert_array_equal(b, a)
    assert [r["grid_seeded"] for r in recs_b] == [N, N]
    assert [r["grid_seeded"] for r in recs_a] == [N, N]


def test_search_slots_fall(mesh3):
    """On a mesh where the old walk is long, the seeded search sweeps a
    fraction of its lane-slots and ends on the same state; moves leave
    ``grid_seeded`` at 0."""
    mesh = build_box(1, 1, 1, 12, 12, 12, dtype=jnp.float32)
    cfg = TallyConfig(dtype=jnp.float32)
    a, b = PumiTally(mesh, 2048, cfg), PumiTally(mesh, 2048, cfg)
    b._seed_grid = None  # the reference's start: where the lane stands
    (sa, ra), (sb, rb) = _init_twice(a), _init_twice(b)
    # Same elements and materials; a position the last step reaches as
    # cur + (dest - cur) may round an ulp apart from another start.
    np.testing.assert_array_equal(sa[1], sb[1])
    np.testing.assert_array_equal(sa[2], sb[2])
    np.testing.assert_allclose(sa[0], sb[0], rtol=0, atol=1e-6)
    per_lane = [r["lane_slots"] / r["lanes"] for r in ra + rb]
    assert per_lane[1] < per_lane[3] / 4, per_lane
    assert [r["grid_seeded"] for r in ra] == [2048, 2048]
    n = a.num_particles
    dest = np.clip(sa[0] + 0.05, 0, 1).astype(np.float64).ravel()
    a.move_to_next_location(dest, np.ones(n, np.int8), np.ones(n),
                            np.zeros(n, np.int32), np.zeros(n, np.int32))
    move = [r for r in a.telemetry()["per_move"] if r.get("kind") == "move"]
    assert len(move) == 1 and move[0]["grid_seeded"] == 0


# --------------------------------------------------------------------- #
# Size
# --------------------------------------------------------------------- #
def seed_grid_nbytes(dims, itemsize):
    """The table's bytes: one int32 seed and three mesh-float
    coordinates a cell."""
    return int(np.prod(dims)) * (4 + 3 * itemsize)


@pytest.mark.parametrize("cubes, dims", [
    ((55, 55, 55), (27, 27, 27)),  # pincell-1m: 998,250 tets
    ((136, 136, 9), (68, 68, 4)),  # assembly-1m-g70: 998,784 tets
])
@pytest.mark.parametrize("itemsize", [4, 8])
def test_grid_stays_under_a_megabyte(cubes, dims, itemsize):
    """Computed for the 1M-tet benchmark meshes, not allocated: a box of
    Freudenthal tets spans one cube on each axis."""
    cubes = np.asarray(cubes)
    got = seed_grid_dims(np.ones(3), 1.0 / cubes)
    np.testing.assert_array_equal(got, dims)
    assert seed_grid_nbytes(got, itemsize) < 1 << 20


def test_built_grid_matches_its_sizing():
    mesh = build_box(1, 1, 1, 10, 8, 3, dtype=jnp.float32)
    g = build_seed_grid(mesh)
    np.testing.assert_array_equal(np.asarray(g.dims), (5, 4, 1))
    assert g.seed.nbytes + g.origin.nbytes == seed_grid_nbytes((5, 4, 1), 4)


# --------------------------------------------------------------------- #
# The benchmark's reader
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


def test_search_seeded_share_reader():
    read = _reader("search_seeded_share")
    recs = [
        {"kind": "initial_search", "lanes": 100, "grid_seeded": 100},
        {"kind": "initial_search", "lanes": 300, "grid_seeded": 200},
        {"kind": "move", "lanes": 50, "grid_seeded": 0},
    ]
    assert read(_Run(recs)) == 75.0
    assert read(_Run([{k: v for k, v in r.items() if k != "grid_seeded"}
                      for r in recs])) is None
    assert read(_Run([{"kind": "initial_search", "lanes": 0,
                       "grid_seeded": 0}])) is None
    assert read(_Run([])) is None
