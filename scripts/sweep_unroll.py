"""Sweep while-loop unroll factor and particle batch size on real hardware.

The walk is dispatch-bound (profile_walk.py: the no-tally walk costs ~4 ms
per while-loop iteration at 131k lanes — far above its bandwidth cost), so
throughput should rise with both unroll (fewer iterations) and batch size
(more work per iteration at ~constant dispatch cost).

Usage: python scripts/sweep_unroll.py [cells] [steps]
"""
from __future__ import annotations

import functools
import sys
import time

import numpy as np


def main():
    from pumiumtally_tpu.utils.platform import require_tpu

    require_tpu()  # chip timings only: no silent CPU fallback
    import jax
    import jax.numpy as jnp

    from pumiumtally_tpu import build_box, make_flux
    from pumiumtally_tpu.ops.walk import trace_impl

    cells = int(sys.argv[1]) if len(sys.argv) > 1 else 55
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    n_groups = 8
    dtype = jnp.float32

    t0 = time.perf_counter()
    mesh = build_box(1.0, 1.0, 1.0, cells, cells, cells, dtype=dtype)
    print(f"mesh: {mesh.ntet} tets, build {time.perf_counter()-t0:.1f}s",
          flush=True)

    def run(n, **kw):
        rng = np.random.default_rng(0)
        elem0 = jnp.asarray(rng.integers(0, mesh.ntet, n).astype(np.int32))
        origin0 = jnp.asarray(
            np.asarray(mesh.centroids())[np.asarray(elem0)], dtype
        )
        in_flight = jnp.ones(n, bool)
        weight = jnp.ones(n, dtype)
        group = jnp.asarray(rng.integers(0, n_groups, n).astype(np.int32))
        material = jnp.full(n, -1, jnp.int32)

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
        def step(key, origin, elem, flux):
            kd, kl = jax.random.split(key)
            d = jax.random.normal(kd, (n, 3), dtype)
            d = d / jnp.linalg.norm(d, axis=1, keepdims=True)
            ln = jax.random.exponential(kl, (n, 1), dtype) * 0.08
            dest = jnp.clip(origin + d * ln, 0.01, 0.99)
            r = trace_impl(
                mesh, origin, dest, elem, in_flight, weight, group, material,
                flux, initial=False, max_crossings=mesh.ntet + 64,
                tolerance=1e-6, **kw)
            return r.position, r.elem, r.flux, r.n_segments, r.n_crossings

        key = jax.random.key(0)
        flux = make_flux(mesh.ntet, n_groups, dtype)
        t0 = time.perf_counter()
        pos, elem, flux, nseg, _ = step(key, origin0, elem0, flux)
        jax.block_until_ready(pos)
        compile_s = time.perf_counter() - t0
        keys = jax.random.split(key, steps)
        total = 0
        t0 = time.perf_counter()
        for i in range(steps):
            pos, elem, flux, nseg, ncross = step(keys[i], pos, elem, flux)
            total += nseg
        # Force a host readback of a value that depends on every step — a
        # stricter fence than block_until_ready on one output buffer.
        total = int(np.asarray(total))
        dt = time.perf_counter() - t0
        return total / dt / 1e6, dt / steps * 1e3, int(np.asarray(ncross)), compile_s

    M = 1048576
    variants = [
        ("u8", M, dict(compact_after=32, unroll=8)),
        ("u16", M, dict(compact_after=32, unroll=16)),
        ("u8_2m", 2 * M, dict(compact_after=32, unroll=8)),
    ]
    for name, n, kw in variants:
        mseg, ms, iters, cs = run(n, **kw)
        print(
            f"{name:12s} {mseg:8.2f} Mseg/s ({ms:8.1f} ms/step, "
            f"iters={iters}, compile {cs:.0f}s)",
            flush=True,
        )


if __name__ == "__main__":
    main()
