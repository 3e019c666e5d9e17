"""Chained (loop-carried) timing of compaction primitives.

The standalone-call timing pattern is unreliable on the remote TPU
runtime (async dispatch makes independent calls overlap or collapse), so
every op here runs ITERS times inside one jitted fori_loop with a
loop-carried data dependency, like scripts/microbench_ops.py.
"""
from __future__ import annotations

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

ITERS = 20


def timeit(name, fn, *args):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    comp = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    dt = (time.perf_counter() - t0) / ITERS
    print(f"{name:28s} {dt*1e3:9.3f} ms/call  (compile {comp:4.1f}s)",
          flush=True)


def main():
    from pumiumtally_tpu.utils.platform import require_tpu

    require_tpu()  # chip timings only: no silent CPU fallback
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_048_576
    rng = np.random.default_rng(0)
    done0 = jnp.asarray(rng.random(n) < 0.7)
    st8 = jnp.asarray(rng.standard_normal((n, 8)).astype(np.float32))
    sub0 = jnp.asarray(rng.integers(0, n, n // 8).astype(np.int32))

    @jax.jit
    def argsort_loop(done):
        def body(i, acc):
            idx = jnp.argsort(done != (i % 2 == 1))
            return acc + idx[0]
        return jax.lax.fori_loop(0, ITERS, body, jnp.int32(0))

    @jax.jit
    def partition_loop(done):
        def body(i, acc):
            d = done != (i % 2 == 1)
            di = d.astype(jnp.int32)
            n_active = jnp.sum(1 - di)
            pos_active = jnp.cumsum(1 - di) - 1
            pos_done = n_active + jnp.cumsum(di) - 1
            dst = jnp.where(d, pos_done, pos_active)
            perm = jnp.zeros(n, jnp.int32).at[dst].set(
                jnp.arange(n, dtype=jnp.int32)
            )
            return acc + perm[0]
        return jax.lax.fori_loop(0, ITERS, body, jnp.int32(0))

    @jax.jit
    def active_indices_loop(done):
        # cheapest form when only the first S actives are needed:
        # dst for active lanes only, scatter lane ids
        def body(i, acc):
            d = done != (i % 2 == 1)
            active = ~d
            pos = jnp.cumsum(active.astype(jnp.int32)) - 1
            dst = jnp.where(active, pos, n)
            idx = jnp.full(n, 0, jnp.int32).at[dst].set(
                jnp.arange(n, dtype=jnp.int32), mode="drop"
            )
            return acc + idx[0]
        return jax.lax.fori_loop(0, ITERS, body, jnp.int32(0))

    @jax.jit
    def state_gather_loop(sub):
        def body(i, carry):
            acc, sub = carry
            sub = (sub + 7919) % n
            x = st8[sub]
            return acc + jnp.sum(x, axis=1), sub
        out, _ = jax.lax.fori_loop(
            0, ITERS, body, (jnp.zeros(n // 8), sub)
        )
        return out

    @jax.jit
    def state_scatterback_loop(sub):
        def body(i, carry):
            acc, sub = carry
            sub = (sub + 7919) % n
            acc = acc.at[sub].set(jnp.ones((n // 8, 8)))
            return acc, sub
        out, _ = jax.lax.fori_loop(
            0, ITERS, body, (jnp.zeros((n, 8)), sub)
        )
        return out

    timeit("argsort_bool", argsort_loop, done0)
    timeit("partition_perm", partition_loop, done0)
    timeit("active_indices", active_indices_loop, done0)
    timeit("state_gather [n/8]x8", state_gather_loop, sub0)
    timeit("state_scatback [n/8]x8", state_scatterback_loop, sub0)


if __name__ == "__main__":
    main()
