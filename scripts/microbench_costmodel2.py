"""Round-3 cost model: gather and scatter-add cost vs index count, row
width, bin count, sortedness, and dropped-row fraction — the inputs to the
walk's scheduling decisions (how dense to make the compaction ladder, and
whether a merged 20-wide gather beats 16-wide + flat-topo).

Usage: python scripts/microbench_costmodel2.py
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def fence(x):
    return float(jnp.sum(x))


def timeit(f, *args, reps=10):
    out = f(*args)
    fence(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
    fence(out)
    return (time.perf_counter() - t0) / reps


def timeit_donated(f, state0, *args, reps=10):
    """Time f(state, *args) -> state with state donated (rebind each call)."""
    state = f(state0, *args)
    fence(state)
    t0 = time.perf_counter()
    for _ in range(reps):
        state = f(state, *args)
    fence(state)
    return (time.perf_counter() - t0) / reps


def main():
    from pumiumtally_tpu.utils.platform import require_tpu

    require_tpu()  # chip timings only: no silent CPU fallback
    ntet = 998_250
    rng = np.random.default_rng(0)

    if os.environ.get("CM2_GATHER"):
        run_gather = True
    else:
        run_gather = False
    print("== gather: table [ntet, W] f32, idx random ==")
    if not run_gather:
        print("  (skipped; set CM2_GATHER=1)")
    for W in ((1, 4, 16, 20, 24, 32) if run_gather else ()):
        tab = jnp.asarray(rng.random((ntet, max(W, 1))).astype(np.float32))
        if W == 1:
            tab = tab[:, 0]
        for n in (16_384, 65_536, 131_072, 262_144, 524_288, 1_048_576):
            idx = jnp.asarray(rng.integers(0, ntet, n).astype(np.int32))
            f = jax.jit(lambda t, i: t[i])
            dt = timeit(f, tab, idx)
            print(f"  W={W:2d} n={n:>8d}  {dt*1e3:7.2f} ms", flush=True)

    print("== scatter-add: flux[bins] f32, n rows ==")
    for bins in (65_536, 998_250, ntet * 8, ntet * 64):
        for n in (131_072, 1_048_576, 8 * 1_048_576):
            idx = jnp.asarray(rng.integers(0, bins, n).astype(np.int32))
            c = jnp.asarray(rng.random(n).astype(np.float32))

            def f(flux, i, c):
                return flux.at[i].add(c, mode="drop")

            fj = jax.jit(f, donate_argnums=(0,))
            z = jnp.zeros(bins, jnp.float32)
            dt = timeit_donated(fj, z, idx, c)
            print(
                f"  bins={bins:>9d} n={n:>8d}  {dt*1e3:7.2f} ms "
                f"({n/dt/1e6:7.1f} Mupd/s)",
                flush=True,
            )

    print("== scatter-add variants at n=8M, bins=ntet*8 ==")
    bins = ntet * 8
    n = 8 * 1_048_576
    idx = jnp.asarray(rng.integers(0, bins, n).astype(np.int32))
    c = jnp.asarray(rng.random(n).astype(np.float32))

    def plain(flux, i, c):
        return flux.at[i].add(c, mode="drop")

    def z():
        return jnp.zeros(bins, jnp.float32)
    dt = timeit_donated(jax.jit(plain, donate_argnums=(0,)), z(), idx, c)
    print(f"  unsorted        {dt*1e3:8.2f} ms")

    idx_s = jnp.sort(idx)
    dt = timeit_donated(jax.jit(plain, donate_argnums=(0,)), z(), idx_s, c)
    print(f"  pre-sorted      {dt*1e3:8.2f} ms")

    def plain_hint(flux, i, c):
        import jax.lax as lax

        return lax.scatter_add(
            flux,
            i[:, None],
            c,
            lax.ScatterDimensionNumbers((), (0,), (0,)),
            indices_are_sorted=True,
            unique_indices=False,
            mode=lax.GatherScatterMode.FILL_OR_DROP,
        )

    dt = timeit_donated(jax.jit(plain_hint, donate_argnums=(0,)), z(), idx_s, c)
    print(f"  sorted+hint     {dt*1e3:8.2f} ms")

    half = jnp.where(jnp.arange(n) % 2 == 0, idx, bins)  # 50% dropped
    dt = timeit_donated(jax.jit(plain, donate_argnums=(0,)), z(), half, c)
    print(f"  50% dropped     {dt*1e3:8.2f} ms")

    def seg_sorted(flux, i, c):
        return flux + jax.ops.segment_sum(
            c, i, num_segments=bins, indices_are_sorted=True
        )

    dt = timeit_donated(jax.jit(seg_sorted, donate_argnums=(0,)), z(), idx_s, c)
    print(f"  segsum(sorted)  {dt*1e3:8.2f} ms")

    def sort_cost(i, c):
        order = jnp.argsort(i)
        return c[order]

    dt = timeit(jax.jit(sort_cost), idx, c)
    print(f"  argsort+permute {dt*1e3:8.2f} ms")


if __name__ == "__main__":
    main()
