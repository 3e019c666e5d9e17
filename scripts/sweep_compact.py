"""Tune the straggler-compaction schedule at 1M particles.

At 1M lanes only ~25% of executed lane-crossings are useful (mean walk
length ~16, phase-1 runs 32 full-batch crossings, the tail runs at n/8).
Per-iteration cost is dispatch-bound below ~131k lanes on v5e, so subsets
smaller than that don't get cheaper — the knobs are when to compact and to
what width.

Usage: python scripts/sweep_compact.py [cells] [steps]
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

    rng0 = np.random.default_rng(0)
    elem_h = np.sort(rng0.integers(0, mesh.ntet, n).astype(np.int32))
    elem0 = jnp.asarray(elem_h)
    origin0 = jnp.asarray(np.asarray(mesh.centroids())[elem_h], dtype)
    in_flight = jnp.ones(n, bool)
    weight = jnp.ones(n, dtype)
    group = jnp.asarray(rng0.integers(0, n_groups, n).astype(np.int32))
    material = jnp.full(n, -1, jnp.int32)

    def run(**kw):
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
                tolerance=1e-6, unroll=8, **kw)
            return r.position, r.elem, r.flux, r.n_segments, r.n_crossings

        key = jax.random.key(0)
        flux = make_flux(mesh.ntet, n_groups, dtype)
        t0 = time.perf_counter()
        pos, elem, flux, nseg, _ = step(key, origin0 + 0, elem0 + 0, flux)
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
        return total / dt / 1e6, dt / steps * 1e3, int(np.asarray(ncross)), compile_s

    variants = [
        ("ca32_s128k", dict(compact_after=32, compact_size=131072)),
        ("ca16_s256k", dict(compact_after=16, compact_size=262144)),
        ("ca16_s128k", dict(compact_after=16, compact_size=131072)),
        ("ca24_s128k", dict(compact_after=24, compact_size=131072)),
        ("ca16_s512k", dict(compact_after=16, compact_size=524288)),
        ("ca8_s512k", dict(compact_after=8, compact_size=524288)),
    ]
    for name, kw in variants:
        mseg, ms, iters, cs = run(**kw)
        print(
            f"{name:12s} {mseg:8.2f} Mseg/s ({ms:8.1f} ms/step, "
            f"iters={iters}, compile {cs:.0f}s)",
            flush=True,
        )


if __name__ == "__main__":
    main()
