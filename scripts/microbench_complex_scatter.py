"""Can the (c, c²) tally pair ride ONE scatter instead of two?

complex64 on TPU is a pair of f32s, and complex addition adds the
components independently — so scatter-adding complex(c, c²) into a
complex64 flux accumulates Σc and Σc² in one scatter pass. If scatter
cost is per-row (measured ~8-11 ns/row regardless of payload width), this
halves the tally cost.

Measured in-loop (inside one jitted while_loop, like the walk).

Usage: python scripts/microbench_complex_scatter.py [n] [K] [bins]
"""
from __future__ import annotations

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp


def timeit_donated(f, state0, *args, reps=5):
    state = f(state0, *args)
    tot = float(jnp.sum(jnp.abs(state)))
    t0 = time.perf_counter()
    for _ in range(reps):
        state = f(state, *args)
    tot = float(jnp.sum(jnp.abs(state)))
    return (time.perf_counter() - t0) / reps, tot


def main():
    from pumiumtally_tpu.utils.platform import require_tpu

    require_tpu()  # chip timings only: no silent CPU fallback
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_048_576
    K = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    bins = int(sys.argv[3]) if len(sys.argv) > 3 else 998_250 * 8
    rng = np.random.default_rng(0)
    key0 = jnp.asarray(rng.integers(0, bins, n).astype(np.int32))
    c0 = jnp.asarray(rng.random(n).astype(np.float32))

    def next_key(k, i):
        return ((k * 1664525 + 1013904223 + i) % bins).astype(jnp.int32)

    def pair(flux, key0, c0):
        def body(carry):
            flux, i = carry
            k = next_key(key0, i)
            flux = flux.at[k, 0].add(c0, mode="drop")
            flux = flux.at[k, 1].add(c0 * c0, mode="drop")
            return flux, i + 1

        flux, _ = jax.lax.while_loop(lambda c: c[1] < K, body, (flux, jnp.int32(0)))
        return flux

    def cplx(flux, key0, c0):
        def body(carry):
            flux, i = carry
            k = next_key(key0, i)
            v = jax.lax.complex(c0, c0 * c0)
            flux = flux.at[k].add(v, mode="drop")
            return flux, i + 1

        flux, _ = jax.lax.while_loop(lambda c: c[1] < K, body, (flux, jnp.int32(0)))
        return flux

    def wide(flux, key0, c0):
        def body(carry):
            flux, i = carry
            k = next_key(key0, i)
            v = jnp.stack([c0, c0 * c0], axis=-1)
            flux = flux.at[k].add(v, mode="drop")
            return flux, i + 1

        flux, _ = jax.lax.while_loop(lambda c: c[1] < K, body, (flux, jnp.int32(0)))
        return flux

    def interleave(flux, key0, c0):
        # one 2n-row scalar scatter: keys [2k, 2k+1], vals [c, c²]
        def body(carry):
            flux, i = carry
            k = next_key(key0, i)
            kk = jnp.concatenate([k * 2, k * 2 + 1])
            vv = jnp.concatenate([c0, c0 * c0])
            flux = flux.at[kk].add(vv, mode="drop")
            return flux, i + 1

        flux, _ = jax.lax.while_loop(lambda c: c[1] < K, body, (flux, jnp.int32(0)))
        return flux

    print(f"n={n} K={K} bins={bins}")
    dt, tot = timeit_donated(
        jax.jit(pair, donate_argnums=(0,)), jnp.zeros((bins, 2), jnp.float32),
        key0, c0,
    )
    print(f"pair f32     {dt*1e3:9.2f} ms  ({dt/K*1e3:6.2f} ms/iter, sum {tot:.4e})")
    dt, tot = timeit_donated(
        jax.jit(wide, donate_argnums=(0,)), jnp.zeros((bins, 2), jnp.float32),
        key0, c0,
    )
    print(f"wide2 f32    {dt*1e3:9.2f} ms  ({dt/K*1e3:6.2f} ms/iter, sum {tot:.4e})")
    dt, tot = timeit_donated(
        jax.jit(interleave, donate_argnums=(0,)),
        jnp.zeros(bins * 2, jnp.float32), key0, c0,
    )
    print(f"interleave   {dt*1e3:9.2f} ms  ({dt/K*1e3:6.2f} ms/iter, sum {tot:.4e})")
    try:
        dt, tot = timeit_donated(
            jax.jit(cplx, donate_argnums=(0,)), jnp.zeros(bins, jnp.complex64),
            key0, c0,
        )
        print(f"complex64    {dt*1e3:9.2f} ms  ({dt/K*1e3:6.2f} ms/iter, sum {tot:.4e})")
    except Exception as e:  # complex64 unimplemented on some TPU backends
        print(f"complex64    UNSUPPORTED ({type(e).__name__})")


if __name__ == "__main__":
    main()
