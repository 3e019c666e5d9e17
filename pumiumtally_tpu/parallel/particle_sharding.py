"""Multi-chip execution: particle-axis data parallelism over a device mesh.

TPU-native replacement for the reference's MPI-rank parallelism
(SURVEY.md §2c.4, §5): the reference runs full-mesh-replicated ranks
(owners=0, pumipic_particle_data_structure.cpp:865-876) with a global tally
reduction and parallel VTK at the end. Here the particle axis is sharded
over a `jax.sharding.Mesh` with `shard_map`; the geometry mesh is replicated
per chip; each chip accumulates a *partial* flux array, and the global
reduction (the MPI all-reduce analog) is a single `jnp.sum` over the
device-sharded leading axis — XLA lowers it to an all-reduce over ICI —
executed lazily at read/write time rather than per move.

Works identically on real TPU meshes and on the virtual CPU mesh used in
tests (XLA_FLAGS=--xla_force_host_platform_device_count=N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.walk import TraceResult, trace_impl

PARTICLE_AXIS = "p"

shard_map = jax.shard_map


def make_device_mesh(n_devices: int | None = None) -> Mesh:
    """1-D device mesh over the particle axis.

    Raises if fewer devices exist than requested — a silently truncated
    mesh would run "multi-chip" code on one chip and hide sharding bugs.
    The virtual CPU mesh comes from ``JAX_PLATFORMS=cpu`` and
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, set before
    JAX is imported."""
    devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"requested a {n_devices}-device mesh but only "
                f"{len(devices)} device(s) are visible; for a virtual CPU "
                "mesh set JAX_PLATFORMS=cpu and XLA_FLAGS=--xla_force_host_"
                f"platform_device_count={n_devices} before importing JAX"
            )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (PARTICLE_AXIS,))


def mesh_from_devices(devices) -> Mesh:
    """1-D particle-axis mesh over an EXPLICIT device list — the
    elastic-recovery entry point (resilience/elastic.py): after a chip
    loss the surviving devices are not a prefix of ``jax.devices()``,
    so ``make_device_mesh``'s count-based slicing cannot express the
    shrunken fleet."""
    devices = list(devices)
    if not devices:
        raise ValueError("mesh_from_devices needs at least one device")
    return Mesh(np.asarray(devices), (PARTICLE_AXIS,))


def n_shards(device_mesh: Mesh) -> int:
    return device_mesh.shape[PARTICLE_AXIS]


def make_sharded_flux(
    device_mesh: Mesh,
    ntet: int,
    n_groups: int,
    dtype=jnp.float32,
    flat: bool = False,
) -> jax.Array:
    """Per-chip partial tallies sharded on the leading device axis:
    [n_dev, ntet, n_groups, 2], or with flat=True [n_dev, ntet*n_groups*2]
    (each chip owns one flat slab — the TPU production layout, see
    core.tally.make_flux on the 64× minor-dim tile padding)."""
    nd = n_shards(device_mesh)
    sharding = NamedSharding(device_mesh, P(PARTICLE_AXIS))
    shape = (
        (nd, ntet * n_groups * 2) if flat else (nd, ntet, n_groups, 2)
    )
    return jax.device_put(jnp.zeros(shape, dtype=dtype), sharding)


def shard_particles(device_mesh: Mesh, *arrays):
    """Place per-particle arrays with the leading axis sharded over chips.
    Sizes must divide evenly by the device count (pad upstream with parked
    particles if needed)."""
    sharding = NamedSharding(device_mesh, P(PARTICLE_AXIS))
    out = tuple(jax.device_put(a, sharding) for a in arrays)
    return out if len(out) != 1 else out[0]


def replicate(device_mesh: Mesh, tree):
    """Replicate a pytree (e.g. the TetMesh) on every chip."""
    sharding = NamedSharding(device_mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), tree
    )


def make_sharded_trace(
    device_mesh: Mesh,
    *,
    initial: bool,
    max_crossings: int,
    score_squares: bool = True,
    tolerance: float = 1e-8,
    compact_after: int | None = None,
    compact_size: int | None = None,
    unroll: int = 8,
    n_groups: int | None = None,
):
    """Build the multi-chip fused trace step.

    Per-particle inputs are sharded over the device mesh; the TetMesh is
    replicated; `flux` carries a leading device axis ([n_dev, ntet, g, 2])
    holding each chip's partial sums. No collective runs inside the step —
    cross-chip reduction happens only in `reduce_flux`. The walk scheduling
    knobs (unroll / straggler compaction, see ops/walk.py) apply per shard.
    """
    kernel = functools.partial(
        trace_impl,
        initial=initial,
        max_crossings=max_crossings,
        score_squares=score_squares,
        tolerance=tolerance,
        compact_after=compact_after,
        compact_size=compact_size,
        unroll=unroll,
        n_groups=n_groups,
    )

    def shard_body(
        mesh, origin, dest, elem, in_flight, weight, group, material_id, flux
    ):
        r = kernel(
            mesh, origin, dest, elem, in_flight, weight, group,
            material_id, flux[0],
        )
        return TraceResult(
            position=r.position,
            elem=r.elem,
            material_id=r.material_id,
            flux=r.flux[None],
            n_segments=r.n_segments[None],
            n_crossings=r.n_crossings[None],
            done=r.done,
            track_length=r.track_length,
            stats=r.stats[None],
        )

    mapped = shard_map(
        shard_body,
        mesh=device_mesh,
        in_specs=(
            P(),              # TetMesh: replicated
            P(PARTICLE_AXIS), # origin
            P(PARTICLE_AXIS), # dest
            P(PARTICLE_AXIS), # elem
            P(PARTICLE_AXIS), # in_flight
            P(PARTICLE_AXIS), # weight
            P(PARTICLE_AXIS), # group
            P(PARTICLE_AXIS), # material_id
            P(PARTICLE_AXIS), # flux (leading device axis)
        ),
        out_specs=TraceResult(
            position=P(PARTICLE_AXIS),
            elem=P(PARTICLE_AXIS),
            material_id=P(PARTICLE_AXIS),
            flux=P(PARTICLE_AXIS),
            n_segments=P(PARTICLE_AXIS),
            n_crossings=P(PARTICLE_AXIS),
            done=P(PARTICLE_AXIS),
            track_length=P(PARTICLE_AXIS),
            stats=P(PARTICLE_AXIS),  # [n_dev, 8] per-shard stats vectors
        ),
    )
    return jax.jit(mapped, donate_argnums=(8,))


@jax.jit
def reduce_flux(sharded_flux: jax.Array) -> jax.Array:
    """Global tally reduction: sum the per-chip partial slabs. This is the
    MPI tally all-reduce analog (SURVEY.md §5 distributed backend); XLA
    emits the collective over ICI."""
    return jnp.sum(sharded_flux, axis=0)
