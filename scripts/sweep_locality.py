"""Measure how gather locality affects walk throughput at 1M particles.

The per-crossing cost at 1M lanes (~19 ns/lane) is ~170x the streaming-
bandwidth cost of the gathered bytes — HBM random access dominates. Two
locality levers, measured here on real hardware:

  baseline    — particles parked on uniformly random elements.
  sorted      — same particles, sorted by parent element once at step
                start (walk hops keep indices approximately clustered).
  sorted_u1   — sorted, no unroll (separates dispatch vs gather effects).
  notally     — sorted + initial=True (no scatter): walk-only cost.

Usage: python scripts/sweep_locality.py [cells] [steps]
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
    n = 1048576
    n_groups = 8
    dtype = jnp.float32

    mesh = build_box(1.0, 1.0, 1.0, cells, cells, cells, dtype=dtype)
    print(f"mesh: {mesh.ntet} tets", flush=True)

    def run(sort, **kw):
        rng = np.random.default_rng(0)
        elem0 = rng.integers(0, mesh.ntet, n).astype(np.int32)
        if sort:
            elem0 = np.sort(elem0)
        elem0 = jnp.asarray(elem0)
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
                flux, max_crossings=mesh.ntet + 64, tolerance=1e-6, **kw)
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
        total = int(np.asarray(total))
        dt = time.perf_counter() - t0
        seg = max(total, 1)
        return seg / dt / 1e6, dt / steps * 1e3, int(np.asarray(ncross)), compile_s

    variants = [
        ("baseline", False, dict(initial=False, compact_after=32, unroll=8)),
        ("sorted", True, dict(initial=False, compact_after=32, unroll=8)),
        ("sorted_u1", True, dict(initial=False, compact_after=32)),
        ("notally", True, dict(initial=True, compact_after=32, unroll=8)),
    ]
    for name, sort, kw in variants:
        mseg, ms, iters, cs = run(sort, **kw)
        print(
            f"{name:10s} {mseg:8.2f} Mseg/s ({ms:8.1f} ms/step, "
            f"iters={iters}, compile {cs:.0f}s)",
            flush=True,
        )


if __name__ == "__main__":
    main()
