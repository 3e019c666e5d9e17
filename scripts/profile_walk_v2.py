"""Cost split of the v2 walk at the bench config (1M lanes, staged
compaction): how much of a step is the tally scatter now that the gather
side was halved in round 2?

Variants:
  full    — bench default (pair (c, c²) scatter per crossing)
  fast    — full tally, robust=False (degeneracy-recovery machinery off:
            no entry-face mask / chase / bump — isolates the hardening
            cost, which never fires on this box mesh)
  notally — initial=True (no scatter at all; walk lower bound)
  nosq    — one scatter-add per crossing

Usage: python scripts/profile_walk_v2.py [cells] [n_particles] [steps]
"""
from __future__ import annotations

import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from pumiumtally_tpu.utils.platform import require_tpu

    require_tpu()  # chip timings only: no silent CPU fallback
    import jax
    import jax.numpy as jnp

    from pumiumtally_tpu import build_box, make_flux
    from pumiumtally_tpu.ops.walk import trace_impl

    cells = int(sys.argv[1]) if len(sys.argv) > 1 else 55
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 1048576
    steps = int(sys.argv[3]) if len(sys.argv) > 3 else 5
    n_groups = 8
    dtype = jnp.float32

    t0 = time.perf_counter()
    mesh = build_box(1.0, 1.0, 1.0, cells, cells, cells, dtype=dtype)
    print(f"mesh: {mesh.ntet} tets, build {time.perf_counter()-t0:.1f}s",
          flush=True)

    from pumiumtally_tpu.utils.config import dense_ladder

    # Same schedule as the bench headline, including the stage-start
    # stretch with mesh density (bench.py: crossings/move ~ cells).
    scale = max(1.0, cells / 55.0)
    stages = tuple(
        (int(round(start * scale)), *rest)
        for start, *rest in dense_ladder(n)
    )

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
                flux, max_crossings=mesh.ntet + 64, tolerance=1e-6,
                compact_stages=stages, unroll=8, **kw)
            return r.position, r.elem, r.flux, r.n_segments, r.n_crossings
        return step

    variants = {
        "full": dict(initial=False),
        "fast": dict(initial=False, robust=False),
        "notally": dict(initial=True),
        "nosq": dict(initial=False, score_squares=False),
    }
    key = jax.random.key(0)
    for name, kw in variants.items():
        step = make_step(**kw)
        flux = make_flux(mesh.ntet, n_groups, dtype)
        t0 = time.perf_counter()
        pos, elem, flux, nseg, _ = step(key, origin0 + 0, elem0 + 0, flux)
        int(np.asarray(nseg))  # readback fence
        compile_s = time.perf_counter() - t0
        keys = jax.random.split(key, steps)
        total = 0
        t0 = time.perf_counter()
        for i in range(steps):
            pos, elem, flux, nseg, ncross = step(keys[i], pos, elem, flux)
            total += nseg
        total = int(np.asarray(total))  # readback fence
        dt = time.perf_counter() - t0
        # notally scores nothing; report crossings-based rate for it
        ncr = int(np.asarray(ncross))
        print(
            f"{name:8s} {dt/steps*1e3:8.1f} ms/step  "
            f"({total} seg, iters={ncr}, compile {compile_s:.0f}s)",
            flush=True,
        )


if __name__ == "__main__":
    main()
