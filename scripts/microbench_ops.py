"""Micro-benchmarks of the walk's primitive ops on real hardware.

Pins down where a while-loop iteration's ~4ms goes (profile_walk.py showed
the no-tally walk at 737ms/step ≈ gathers, scatter ~300ms):
  gN       — gather [n] rows from [ntet,4,3] normals table (the status quo:
             one of ~4 separate per-crossing gathers)
  gBig     — gather [n] rows from a combined [ntet,32] table (everything a
             crossing needs in ONE row fetch)
  gSplit   — the full status-quo gather set (normals+d+t2t+class)
  scat2    — two scatter-adds into [ntet,G,2] (status quo)
  scat1    — one scatter-add of [n,2] rows into [ntet*G,2]
  scatSort — sort indices then one scatter-add with indices_are_sorted

Each op runs ITERS times inside a fori_loop with the index vector rotated
per iteration; reported as time per call.
"""
from __future__ import annotations

import sys
import time

import numpy as np


def timeit(name, fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    dt = time.perf_counter() - t0
    print(f"{name:10s} {dt/ITERS*1e3:8.3f} ms/call  (compile {compile_s:.0f}s)",
          flush=True)
    return out


ITERS = 50


def main():
    global ITERS
    from pumiumtally_tpu.utils.platform import require_tpu

    require_tpu()  # chip timings only: no silent CPU fallback
    import jax
    import jax.numpy as jnp

    ntet = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 131072
    G = 8
    rng = np.random.default_rng(0)
    elem = jnp.asarray(rng.integers(0, ntet, n).astype(np.int32))
    face = jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
    group = jnp.asarray(rng.integers(0, G, n).astype(np.int32))
    contrib = jnp.asarray(rng.uniform(size=n).astype(np.float32))

    normals = jnp.asarray(rng.standard_normal((ntet, 4, 3)).astype(np.float32))
    faced = jnp.asarray(rng.standard_normal((ntet, 4)).astype(np.float32))
    t2t = jnp.asarray(rng.integers(0, ntet, (ntet, 4)).astype(np.int32))
    cls = jnp.asarray(rng.integers(0, 4, ntet).astype(np.int32))
    big = jnp.asarray(rng.standard_normal((ntet, 32)).astype(np.float32))
    flux = jnp.zeros((ntet, G, 2), jnp.float32)
    fluxflat = jnp.zeros((ntet * G, 2), jnp.float32)

    def rot(i, idx):
        return (idx + i * 7919) % ntet

    @jax.jit
    def gN(elem):
        def body(i, acc):
            x = normals[rot(i, elem)]
            return acc + jnp.sum(x, axis=(1, 2))
        return jax.lax.fori_loop(0, ITERS, body, jnp.zeros(n))

    @jax.jit
    def gBig(elem):
        def body(i, acc):
            x = big[rot(i, elem)]
            return acc + jnp.sum(x, axis=1)
        return jax.lax.fori_loop(0, ITERS, body, jnp.zeros(n))

    @jax.jit
    def gSplit(elem):
        def body(i, acc):
            e = rot(i, elem)
            x = normals[e]
            d = faced[e]
            nx = t2t[e, face]
            c = cls[jnp.maximum(nx, 0)] + cls[e]
            return (acc + jnp.sum(x, axis=(1, 2)) + jnp.sum(d, axis=1)
                    + c.astype(jnp.float32))
        return jax.lax.fori_loop(0, ITERS, body, jnp.zeros(n))

    @jax.jit
    def scat2(flux):
        def body(i, flux):
            e = rot(i, elem)
            flux = flux.at[e, group, 0].add(contrib)
            flux = flux.at[e, group, 1].add(contrib * contrib)
            return flux
        return jax.lax.fori_loop(0, ITERS, body, flux)

    @jax.jit
    def scat1(fluxflat):
        rows = jnp.stack([contrib, contrib * contrib], axis=1)
        def body(i, f):
            idx = rot(i, elem) * G + group
            return f.at[idx].add(rows)
        return jax.lax.fori_loop(0, ITERS, body, fluxflat)

    @jax.jit
    def scatSort(fluxflat):
        rows = jnp.stack([contrib, contrib * contrib], axis=1)
        def body(i, f):
            idx = rot(i, elem) * G + group
            order = jnp.argsort(idx)
            return f.at[idx[order]].add(
                rows[order], indices_are_sorted=True
            )
        return jax.lax.fori_loop(0, ITERS, body, fluxflat)

    timeit("gN", gN, elem)
    timeit("gBig", gBig, elem)
    timeit("gSplit", gSplit, elem)
    timeit("scat2", scat2, flux)
    timeit("scat1", scat1, fluxflat)
    timeit("scatSort", scatSort, fluxflat)


if __name__ == "__main__":
    main()
