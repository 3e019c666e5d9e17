"""Isolate the tally scatter-add cost and try alternative lowerings.

At 1M lanes the two scatter-adds are ~54% of walk step time
(scripts/sweep_locality.py). Candidates, measured standalone on hardware:

  pair2d   — flux[ntet, G, 2], .at[elem, group, 0].add + [.., 1].add
             (the walk's current form)
  flat1d   — flux[ntet*G, 2] with one fused index elem*G+group
  flat1d_s — flat1d with pre-sorted indices (upper bound for locality)
  seg_sum  — sort + jax.ops.segment_sum into dense bins per call

Usage: python scripts/microbench_scatter.py [n_updates] [ntet]
"""
from __future__ import annotations

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp


def bench(name, f, args, reps=20):
    f = jax.jit(f, donate_argnums=(0,))
    out = jax.block_until_ready(f(*args))
    args = (out,) + args[1:]
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
        args = (out,) + args[1:]
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    total = float(np.asarray(out).sum())  # checksum outside the clock
    n = args[1].shape[0]
    print(
        f"{name:9s} {dt*1e3:8.2f} ms  {n/dt/1e6:8.1f} Mupd/s  (sum {total:.3e})",
        flush=True,
    )


def main():
    from pumiumtally_tpu.utils.platform import require_tpu

    require_tpu()  # chip timings only: no silent CPU fallback
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_048_576
    ntet = int(sys.argv[2]) if len(sys.argv) > 2 else 998_250
    G = 8
    rng = np.random.default_rng(0)
    elem = jnp.asarray(rng.integers(0, ntet, n).astype(np.int32))
    group = jnp.asarray(rng.integers(0, G, n).astype(np.int32))
    c = jnp.asarray(rng.random(n).astype(np.float32))
    flat = elem * G + group
    flat_sorted = jnp.sort(flat)

    def pair2d(flux, elem, group, c):
        flux = flux.at[elem, group, 0].add(c, mode="drop")
        return flux.at[elem, group, 1].add(c * c, mode="drop")

    bench("pair2d", pair2d,
          (jnp.zeros((ntet, G, 2), jnp.float32), elem, group, c))

    def flat1d(flux, idx, c):
        flux = flux.at[idx, 0].add(c, mode="drop")
        return flux.at[idx, 1].add(c * c, mode="drop")

    bench("flat1d", flat1d,
          (jnp.zeros((ntet * G, 2), jnp.float32), flat, c))
    bench("flat1d_s", flat1d,
          (jnp.zeros((ntet * G, 2), jnp.float32), flat_sorted, c))

    def seg(flux, idx, c):
        order = jnp.argsort(idx)
        si, sc = idx[order], c[order]
        add0 = jax.ops.segment_sum(sc, si, num_segments=ntet * G)
        add1 = jax.ops.segment_sum(sc * sc, si, num_segments=ntet * G)
        return flux + jnp.stack([add0, add1], axis=-1)

    bench("seg_sum", seg,
          (jnp.zeros((ntet * G, 2), jnp.float32), flat, c))


if __name__ == "__main__":
    main()
