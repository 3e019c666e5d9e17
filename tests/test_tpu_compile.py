"""Compile-only checks against a described TPU v5e (``v5e:2x2``).

The TPU compiler is installed here and compiles for a chip that is
described, not attached: what it refuses here (a Pallas primitive Mosaic
cannot lower, a program over the device's HBM, a missing collective)
costs no chip time. Nothing runs, so these say nothing about results or
speed — chip_smoke.py does that on the chip.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and under
pytest-xdist every worker imports this file. The persistent compile
cache is off around these compiles (an entry written for a described
chip cannot be read back without one), and so is x64, as on the chip.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from pumiumtally_tpu import TallyConfig, build_box
from pumiumtally_tpu.ops.walk import trace_impl
from pumiumtally_tpu.ops.walk_pallas import (
    kernel_vmem_bytes,
    select_backend,
    trace_pallas_impl,
)

F32 = jnp.float32
HBM_BYTES = 16 * 10**9  # one v5e chip
CONFIG2_CELLS = 55  # 6·55³ = 998,250 tets (BASELINE config 2)
CONFIG2_LANES = 1_048_576


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    # The chip runs these programs in f32 without x64 (conftest turns
    # x64 on for the CPU oracles).
    was = jax.config.jax_enable_compilation_cache, jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh2():
    """The config-2 mesh, f32 (host arrays; only its shapes are used)."""
    return build_box(1.0, 1.0, 1.0, *(CONFIG2_CELLS,) * 3, dtype=F32)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def _lanes(n, ntet, n_groups, sharding):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    return (
        s((n, 3), F32), s((n, 3), F32), s((n,), jnp.int32),
        s((n,), jnp.bool_), s((n,), F32), s((n,), jnp.int32),
        s((n,), jnp.int32), s((ntet * n_groups * 2,), F32),
    )


def _facade_walk_kwargs(mesh, n, n_groups):
    """The walk statics PumiTally resolves with default knobs, with the
    tally scatter the TPU resolves ``auto`` to (this process's backend
    is the CPU, so the test steers it)."""
    cfg = TallyConfig(n_groups=n_groups)
    ca, cs = cfg.resolve_compaction(n)
    return dict(
        initial=False,
        max_crossings=cfg.resolve_max_crossings(mesh.ntet),
        score_squares=cfg.score_squares,
        tolerance=cfg.tolerance,
        unroll=cfg.unroll,
        compact_after=ca,
        compact_size=cs,
        compact_stages=cfg.resolve_compact_stages(n, ntet=mesh.ntet),
        tally_scatter="interleaved",
        n_groups=n_groups,
    )


def _compile_walk(mesh, n, n_groups, sharding):
    o, d, e, f, w, g, m, flux = _lanes(n, mesh.ntet, n_groups, sharding)
    fn = functools.partial(
        trace_impl, **_facade_walk_kwargs(mesh, n, n_groups)
    )
    return (
        jax.jit(fn, donate_argnames=("flux",))
        .lower(_shapes(mesh, sharding), o, d, e, f, w, g, m, flux)
        .compile()
    )


@pytest.mark.parametrize("n_groups", [8, 64])
def test_xla_walk_config2_compiles(one_chip, mesh2, n_groups):
    """(a) config 2 and (b) config 4's 64 groups: the facade's walk
    program at full width fits one chip's HBM."""
    compiled = _compile_walk(mesh2, CONFIG2_LANES, n_groups, one_chip)
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < HBM_BYTES, (n_groups, need)


def test_pallas_kernel_config1_compiles(one_chip):
    """(c) the Pallas kernel at config 1 (10,368 tets, 4,096 lanes, one
    group) — the workload ``kernel="auto"`` sends to it on a TPU — is
    accepted by Mosaic and appears in the program as a TPU custom call."""
    mesh = build_box(1.0, 1.0, 1.0, 12, 12, 12, dtype=F32)
    n, n_groups = 4096, 1
    assert kernel_vmem_bytes(mesh.ntet, n, n_groups, 4) < 8 * 2**20
    assert select_backend(
        "auto", ntet=mesh.ntet, n_particles=n, n_groups=n_groups,
        dtype=F32, packed=True, platform="tpu",
    ) == "pallas"
    fn = functools.partial(
        trace_pallas_impl, initial=False, max_crossings=mesh.ntet + 64,
        tolerance=1e-6, n_groups=n_groups, interpret=False,
    )
    compiled = (
        jax.jit(fn)
        .lower(_shapes(mesh, one_chip), *_lanes(n, mesh.ntet, n_groups,
                                                 one_chip))
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_partitioned_step_compiles_with_all_to_all(topo, mesh2, monkeypatch):
    """(d) the partitioned walk over the 4 described chips (config-2
    mesh, 4 parts, halo 2): the halo fold and particle migration lower
    to ICI all-to-alls."""
    from pumiumtally_tpu.ops.walk_partitioned import make_partitioned_step
    from pumiumtally_tpu.parallel.mesh_partition import partition_mesh

    n_parts, n_groups, cap = 4, 8, 65536
    dmesh = Mesh(np.asarray(topo.devices[:n_parts]), ("p",))
    spec = NamedSharding(dmesh, P("p"))
    part = partition_mesh(mesh2, n_parts, halo_layers=2)
    # The step places its tables on the device mesh when it is built; a
    # described chip holds no arrays, so the test hands it shapes.
    with monkeypatch.context() as m:
        m.setattr(
            jax, "device_put",
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        )
        step = make_partitioned_step(
            dmesh, part, n_groups=n_groups, max_crossings=mesh2.ntet + 64,
            tolerance=1e-6, tally_scatter="interleaved",
        )
    rows = n_parts * cap
    s = functools.partial(jax.ShapeDtypeStruct, sharding=spec)
    particles = (
        s((rows, 3), F32), s((rows, 3), F32), s((rows,), jnp.int32),
        s((rows,), jnp.bool_), s((rows,), jnp.int32), s((rows,), F32),
        s((rows,), jnp.int32), s((rows,), jnp.int32), s((rows,), jnp.bool_),
        s((n_parts, part.max_local * n_groups * 2), F32),
    )
    compiled = step.jitted.lower(*step.table_shapes, *particles).compile()
    assert "all-to-all" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
