"""Benchmark multi-stage compaction schedules at 1M particles.

Single-stage compaction makes every compacted subset carry the walk's full
~170-crossing tail at its width; a staged schedule narrows the batch as
lanes finish (1M → n/2 at 16 → n/8 at 32 → tail), saving the wasted
full-width crossings between 16 and 32.

Usage: python scripts/sweep_stages.py [cells] [steps] [particles]
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
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    n = int(sys.argv[3]) if len(sys.argv) > 3 else 1048576
    n_groups = 8
    dtype = jnp.float32

    mesh = build_box(1.0, 1.0, 1.0, cells, cells, cells, dtype=dtype)
    print(f"mesh: {mesh.ntet} tets", flush=True)

    rng0 = np.random.default_rng(0)
    elem_h = rng0.integers(0, mesh.ntet, n).astype(np.int32)
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

    M = n
    # Round-3 candidates: the round-1 sweep that settled on the r2
    # default used ARGSORT compaction (expensive rounds); the cumsum
    # partition made rounds ~free, so denser/earlier/longer ladders are
    # back on the table. Active lanes ≈ n·exp(-k/16.6) at crossing k, so
    # the slot waste lives in (a) phase 1 running all lanes to 16 ≈ the
    # mean, and (b) the final stage running n/8 lanes for the whole tail.
    variants = [
        ("default_r2", dict(
            compact_stages=((16, M // 2), (24, M // 4), (40, M // 8)))),
        ("tail64", dict(
            compact_stages=((16, M // 2), (24, M // 4), (40, M // 8),
                            (64, M // 32)))),
        ("tail64_96", dict(
            compact_stages=((16, M // 2), (24, M // 4), (40, M // 8),
                            (64, M // 32), (96, M // 64)))),
        ("early8", dict(
            compact_stages=((8, 5 * M // 8), (16, 3 * M // 8), (24, M // 4),
                            (40, M // 8), (64, M // 32)))),
        ("dense", dict(
            compact_stages=((8, 5 * M // 8), (16, 3 * M // 8), (24, M // 4),
                            (32, M // 8), (48, M // 16), (64, M // 32),
                            (96, M // 64)))),
        # Per-stage unroll: narrow tail stages are while-iteration-bound,
        # so give them a larger factor (third tuple element).
        ("dense_u32tail", dict(
            compact_stages=((8, 5 * M // 8), (16, 3 * M // 8), (24, M // 4),
                            (32, M // 8), (48, M // 16, 16),
                            (64, M // 32, 16), (96, M // 64, 32)))),
        ("tail64_96_u32", dict(
            compact_stages=((16, M // 2), (24, M // 4), (40, M // 8),
                            (64, M // 32, 16), (96, M // 64, 32)))),
        # Round-4 DP optima (scripts/plan_ladder.py optimize_ladder —
        # exact under the slot model with widths pinned >= the live
        # count, so none of their cost is unpriced overflow; dense's
        # early stages sit slightly BELOW the live count and model
        # fake-cheap). Two round-cost assumptions; hardware arbitrates.
        ("dp_r250k", dict(
            compact_stages=((16, M // 2), (24, M // 4), (40, M // 8),
                            (48, M // 16), (56, M // 32), (76, 8192)))),
        ("dp_r2m", dict(
            compact_stages=((16, M // 2), (24, M // 4), (44, M // 16),
                            (76, 8192)))),
    ]
    for name, kw in variants:
        mseg, ms, iters, cs = run(**kw)
        print(
            f"{name:14s} {mseg:8.2f} Mseg/s ({ms:8.1f} ms/step, "
            f"iters={iters}, compile {cs:.0f}s)",
            flush=True,
        )


if __name__ == "__main__":
    main()
