"""Isolate the cost components of the fused walk on real hardware.

Variants timed on the same ~1M-tet mesh / particle batch as bench.py:
  notally   — initial=True: same walk, no flux scatter (lower bound)
  nosq      — score_squares=False: one scatter-add per crossing, not two
  full      — bench.py defaults
  flat      — no straggler compaction
  ca8/ca64  — compaction threshold sweep
  cs32k     — larger straggler subset

Usage: python scripts/profile_walk.py [cells] [n_particles] [steps]
"""
from __future__ import annotations

import sys
import time

import numpy as np


def main():
    from pumiumtally_tpu.utils.platform import require_tpu

    require_tpu()  # chip timings only: no silent CPU fallback
    import functools

    import jax
    import jax.numpy as jnp

    from pumiumtally_tpu import build_box, make_flux
    from pumiumtally_tpu.ops.walk import trace_impl

    cells = int(sys.argv[1]) if len(sys.argv) > 1 else 55
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 131072
    steps = int(sys.argv[3]) if len(sys.argv) > 3 else 3
    n_groups = 8
    dtype = jnp.float32

    t0 = time.perf_counter()
    mesh = build_box(1.0, 1.0, 1.0, cells, cells, cells, dtype=dtype)
    print(f"mesh: {mesh.ntet} tets, build {time.perf_counter()-t0:.1f}s",
          flush=True)

    rng = np.random.default_rng(0)
    elem0 = jnp.asarray(rng.integers(0, mesh.ntet, n).astype(np.int32))
    origin0 = jnp.asarray(np.asarray(mesh.centroids())[np.asarray(elem0)], dtype)
    in_flight = jnp.ones(n, bool)
    weight = jnp.ones(n, dtype)
    group = jnp.asarray(rng.integers(0, n_groups, n).astype(np.int32))
    material = jnp.full(n, -1, jnp.int32)

    def make_step(**kw):
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
        return step

    variants = {
        "notally": dict(initial=True, compact_after=32),
        "nosq": dict(initial=False, score_squares=False, compact_after=32),
        "full": dict(initial=False, compact_after=32),
        "flat": dict(initial=False, compact_after=None),
        "ca8": dict(initial=False, compact_after=8),
        "ca64": dict(initial=False, compact_after=64),
        "cs32k": dict(initial=False, compact_after=16, compact_size=32768),
    }
    key = jax.random.key(0)
    for name, kw in variants.items():
        step = make_step(**kw)
        flux = make_flux(mesh.ntet, n_groups, dtype)
        t0 = time.perf_counter()
        # Fresh copies per variant: step donates its inputs.
        pos, elem, flux, nseg, _ = step(key, origin0 + 0, elem0 + 0, flux)
        jax.block_until_ready(pos)
        compile_s = time.perf_counter() - t0
        keys = jax.random.split(key, steps)
        total = 0
        t0 = time.perf_counter()
        for i in range(steps):
            pos, elem, flux, nseg, ncross = step(keys[i], pos, elem, flux)
            total += nseg
        jax.block_until_ready(pos)
        dt = time.perf_counter() - t0
        total = int(np.asarray(total))
        print(
            f"{name:8s} {total/dt/1e6:8.2f} Mseg/s  "
            f"({dt/steps*1e3:7.1f} ms/step, {total} seg, "
            f"iters={int(np.asarray(ncross))}, compile {compile_s:.0f}s)",
            flush=True,
        )


if __name__ == "__main__":
    main()
