"""Seed grid of the batch-start search.

The parent-element search (``initialize_particle_location``; the
reference's ``search_initial_elements``, cpp:360-385) finds each lane's
element by walking a straight ray to its source point. Nothing is
tallied and only the domain boundary stops it (ops/walk.py,
``initial=True``), so where the ray starts changes nothing a caller
reads: a ray between two points of one convex piece of the domain ends
in the element that holds the destination, on the destination (to an
ulp: the last step lands at ``cur + (dest - cur)``). The
reference starts from wherever the previous batch left the lane, a
walk as long as the distance between two random points of the mesh
(hundreds of crossings on a 1M-tet mesh).

This module starts it next door instead. A uniform grid over the
mesh's bounding box holds, per cell, one seed element: the tet whose
centroid lies in the cell nearest the cell's centre. Before the walk
(``seed_starts``, inside the init's compiled program) each flying lane
whose destination falls in a cell with a seed starts from a fixed
point inside that seed, in the seed; the walk then crosses a few
elements, not hundreds. The point is off the centroid
(``_START_WEIGHTS``): on a structured mesh the ray from one centroid to
another (sources are often centroids) runs through shared edges and
vertices, where the walk needs its degeneracy recovery.

A cell holds no seed (-1), and its lanes keep their start, where that
could change the answer:

  * no centroid lies in it (outside the mesh, or a thin sliver);
  * a boundary face of the mesh that does not lie on the bounding box
    touches it (a hole, a notch, a curved outer surface, a crack). Such
    a face could stand between the seed and the destination, and the
    walk would stop on it. A boundary face on the bounding box cannot:
    the ray between two points of the box never crosses the box's
    surface. So in a cell no other boundary face touches, every point
    of the box lies in the mesh, and the ray from the seed stays in it.

Lanes whose destination lies outside the bounding box by more than the
walk's tolerance keep their start too: where such a lane is clipped
depends on where its ray starts, and the reference's start decides it.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..mesh.core import FACE_LOCAL_VERTS

# Cell edge on each axis, in the mesh's mean tet extent on that axis.
# Chosen by the search's lane-slots a lane (65,536 lanes to random tet
# centroids, a second batch; box meshes of 20^3 cubes and of 45 x 45 x 3
# cubes of 15:1 tets; 82.7 and 143.1 without seeds): 1, 1.5 and 2 give
# 8.0-8.25 (one unrolled chunk), 3 and 4 give 16. Below 2 the table
# grows past 1 MB at 1M float64 tets (1.5: ntet/20 cells); at 2 it holds
# about ntet/48 cells, 20k at 1M tets.
SEED_CELL_TETS = 2.0

# Barycentric weights of a seed's start point: all positive (inside the
# tet, off every face) and unequal, so that the ray to a centroid or a
# lattice point of a structured mesh crosses no edge or vertex exactly.
_START_WEIGHTS = np.array([0.2, 0.24, 0.27, 0.29])

# A non-bounding-box boundary face invalidates every cell its bounding
# box reaches, widened by this share of a cell on each side: a device
# float32 cell index can round a point across a cell face.
_FACE_MARGIN = 0.01


class SeedGrid(NamedTuple):
    """Device-resident seed table (a pytree; the init program's
    argument, never a move program's)."""

    lo: jax.Array  # [3] bounding-box low corner (mesh dtype)
    hi: jax.Array  # [3] bounding-box high corner
    inv_cell: jax.Array  # [3] cells per unit length on each axis
    dims: jax.Array  # [3] int32 cells on each axis
    seed: jax.Array  # [ncell] int32 seed element; -1 keeps the start
    # [3, ncell] the start point in the seed, one row per axis: a 1-D
    # gather per axis keeps the lanes on a TPU tile's minor dimension,
    # where a row gather of [ncell, 3] lays out [n, 3] with the 3 minor
    # and pads it 128-wide (512 MB at 1M lanes).
    origin: jax.Array


def seed_grid_dims(span, extent):
    """Cells on each axis: the bounding box's ``span`` over
    ``SEED_CELL_TETS`` mean tet ``extent``s, rounded down so a cell is
    never narrower than that (at least one cell)."""
    span = np.asarray(span, np.float64)
    extent = np.asarray(extent, np.float64)
    return np.maximum(
        1, np.floor(span / (SEED_CELL_TETS * np.maximum(extent, 1e-300)))
    ).astype(np.int64)


def _cell_index(p, lo, cell, dims):
    return np.clip(np.floor((p - lo) / cell), 0, dims - 1).astype(np.int64)


def build_seed_grid(mesh) -> SeedGrid:
    """Build the seed grid of ``mesh`` on the host, once per tally, and
    place it on the device in the mesh's dtype."""
    coords = np.asarray(mesh.coords, np.float64)
    t2v = np.asarray(mesh.tet2vert)
    t2t = np.asarray(mesh.tet2tet)
    v = coords[t2v.T]  # [4, ntet, 3]: reductions over the 4 run contiguous
    lo, hi = coords.min(0), coords.max(0)
    vmax = np.maximum(np.maximum(v[0], v[1]), np.maximum(v[2], v[3]))
    vmin = np.minimum(np.minimum(v[0], v[1]), np.minimum(v[2], v[3]))
    dims = seed_grid_dims(hi - lo, (vmax - vmin).mean(0))
    cell = np.maximum(hi - lo, 1e-300) / dims
    cen = (v[0] + v[1] + v[2] + v[3]) / 4.0
    del v, vmax, vmin

    # Per cell, the centroid nearest the cell's centre: sort by the cell
    # index plus the squared distance in cell units (under 1); a stable
    # sort gives a tie (symmetric meshes have many) to the lowest tet.
    idx = _cell_index(cen, lo, cell, dims)
    flat = np.ravel_multi_index(idx.T, dims)
    d2 = (((cen - (lo + (idx + 0.5) * cell)) / cell) ** 2).sum(1)
    order = np.argsort(flat + np.minimum(d2, 0.75), kind="stable")
    fo = flat[order]
    first = order[np.r_[True, fo[1:] != fo[:-1]]]
    ncell = int(np.prod(dims))
    seed = np.full(ncell, -1, np.int64)
    seed[flat[first]] = first

    # Cells a boundary face off the bounding box touches keep no seed.
    bt, bf = np.nonzero(t2t < 0)
    fv = coords[t2v[bt[:, None], FACE_LOCAL_VERTS[bf]]]  # [nb, 3, 3]
    atol = 1e-9 * max(float((hi - lo).max()), 1.0)
    on_box = (
        np.all(np.abs(fv - lo) <= atol, axis=1)
        | np.all(np.abs(fv - hi) <= atol, axis=1)
    ).any(1)
    fv = fv[~on_box]
    if len(fv):
        margin = _FACE_MARGIN * cell
        i0 = _cell_index(fv.min(1) - margin, lo, cell, dims)
        i1 = _cell_index(fv.max(1) + margin, lo, cell, dims)
        reach = (i1 - i0).max(0)
        for dx in range(reach[0] + 1):
            for dy in range(reach[1] + 1):
                for dz in range(reach[2] + 1):
                    i = np.minimum(i0 + (dx, dy, dz), i1)
                    seed[np.ravel_multi_index(i.T, dims)] = -1

    dt = mesh.dtype
    ok = seed >= 0
    origin = np.zeros((3, ncell))
    origin[:, ok] = (_START_WEIGHTS @ coords[t2v[seed[ok]]]).T
    return SeedGrid(
        lo=jnp.asarray(lo, dt),
        hi=jnp.asarray(hi, dt),
        inv_cell=jnp.asarray(1.0 / cell, dt),
        dims=jnp.asarray(dims, jnp.int32),
        seed=jnp.asarray(seed, jnp.int32),
        origin=jnp.asarray(origin, dt),
    )


def seed_starts(grid: SeedGrid, origin, elem, dest, in_flight, tolerance):
    """Move each flying lane's search start to the seed of its
    destination's cell (device side, inside the init program).

    Returns ``(origin, elem, n_seeded)``: lanes that are not flying,
    whose destination lies outside the bounding box by more than
    ``tolerance``, or whose cell holds no seed keep theirs."""
    inside = jnp.all(
        (dest >= grid.lo - tolerance) & (dest <= grid.hi + tolerance),
        axis=-1,
    )
    idx = jnp.clip(
        jnp.floor((dest - grid.lo) * grid.inv_cell).astype(jnp.int32),
        0, grid.dims - 1,
    )
    cell = (idx[:, 0] * grid.dims[1] + idx[:, 1]) * grid.dims[2] + idx[:, 2]
    s = grid.seed[cell]
    use = in_flight.astype(bool) & inside & (s >= 0)
    origin = jnp.stack(
        [jnp.where(use, grid.origin[k][cell], origin[:, k]) for k in range(3)],
        axis=-1,
    )
    elem = jnp.where(use, s, elem)
    return origin, elem, jnp.sum(use)
