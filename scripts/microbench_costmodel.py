"""Cost-model microbench for the walk redesign (round 2).

Measures, at bench-scale lane counts on real hardware:
  gather:   table-width sweep [ntet, w] (is cost ~ a + b*w per row?),
            2-D scalar gather t2t[elem, face], tiny-table gather,
            sorted vs random indices
  scatter:  row-count scaling (does one big scatter beat R small ones?),
            pair-of-scalar vs flat-interleaved single op, drop vs clamp
  compact:  argsort(bool) vs cumsum-based stable-partition permutation,
            packed-state gather cost
All numbers feed the redesign of ops/walk.py (crossing-record flush,
packed topo, carried class, cheap compaction).
"""
from __future__ import annotations

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp


def timeit(name, fn, *args, iters=20):
    f = jax.jit(fn)
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    comp = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    print(f"{name:28s} {dt*1e3:9.3f} ms  (compile {comp:4.1f}s)", flush=True)
    return dt


def main():
    from pumiumtally_tpu.utils.platform import require_tpu

    require_tpu()  # chip timings only: no silent CPU fallback
    section = sys.argv[1] if len(sys.argv) > 1 else "all"
    ntet = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000
    n = int(sys.argv[3]) if len(sys.argv) > 3 else 1_048_576
    G = 8
    rng = np.random.default_rng(0)
    elem = jnp.asarray(rng.integers(0, ntet, n).astype(np.int32))
    elem_sorted = jnp.sort(elem)
    face = jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
    group = jnp.asarray(rng.integers(0, G, n).astype(np.int32))
    c = jnp.asarray(rng.random(n).astype(np.float32))

    if section in ("all", "gather"):
        print(f"--- gather width sweep ({n} indices, ntet={ntet}) ---")
        for w in (1, 4, 12, 16, 32):
            tbl = jnp.asarray(
                rng.standard_normal((ntet, w)).astype(np.float32)
            )
            if w == 1:
                tbl1 = tbl[:, 0]
                timeit(f"g_w1(1-D table)", lambda e: tbl1[e].sum(), elem)
            timeit(f"g_w{w}", lambda e, t=tbl: t[e].sum(), elem)

        tbl12 = jnp.asarray(
            rng.standard_normal((ntet, 4, 3)).astype(np.float32)
        )
        timeit("g_[ntet,4,3]", lambda e: tbl12[e].sum(), elem)

    if section in ("all", "gather2"):
        t2t = jnp.asarray(
            rng.integers(0, ntet, (ntet, 4)).astype(np.int32)
        )
        timeit(
            "g_2d_scalar t2t[e,f]", lambda e, f: t2t[e, f].sum(), elem, face
        )
        timeit(
            "g_row_then_take t2t[e][f]",
            lambda e, f: jnp.take_along_axis(
                t2t[e], f[:, None], axis=1
            ).sum(),
            elem, face,
        )

        tiny = jnp.asarray(rng.standard_normal(256).astype(np.float32))
        tinyidx = jnp.asarray(rng.integers(0, 256, n).astype(np.int32))
        timeit("g_tiny[256]", lambda i: tiny[i].sum(), tinyidx)

        tbl4 = jnp.asarray(rng.standard_normal((ntet, 4)).astype(np.float32))
        timeit("g_w4_sorted_idx", lambda e: tbl4[e].sum(), elem_sorted)

    if section not in ("all", "scatter", "compact", "math"):
        return
    if section in ("all", "scatter"):
        print(f"--- scatter scaling (into [ntet,{G},2] / flat) ---")
        flux = jnp.zeros((ntet, G, 2), jnp.float32)
        fluxflat = jnp.zeros(ntet * G * 2, jnp.float32)

        def pair(flux, e, g, c):
            flux = flux.at[e, g, 0].add(c, mode="drop")
            return flux.at[e, g, 1].add(c * c, mode="drop")

        timeit("scat_pair_1M", pair, flux, elem, group, c)

        for mult in (4, 8):
            eb = jnp.tile(elem, mult)
            gb = jnp.tile(group, mult)
            cb = jnp.tile(c, mult)
            dt = timeit(f"scat_pair_{mult}M", pair, flux, eb, gb, cb)
            print(f"    -> per-1M-rows: {dt/mult*1e3:.3f} ms")

        def flat_interleave(f, e, g, c):
            base = (e * G + g) * 2
            idx = jnp.concatenate([base, base + 1])
            val = jnp.concatenate([c, c * c])
            return f.at[idx].add(val, mode="drop")

        timeit(
            "scat_flat_2x1M_one_op", flat_interleave, fluxflat, elem,
            group, c,
        )

        def clampscat(flux, e, g, c):
            e2 = jnp.minimum(e, ntet - 1)
            flux = flux.at[e2, g, 0].add(c)
            return flux.at[e2, g, 1].add(c * c)

        timeit("scat_pair_clamped", clampscat, flux, elem, group, c)

        def csorted(flux, e, g, c):
            flux = flux.at[e, g, 0].add(
                c, mode="drop", indices_are_sorted=True
            )
            return flux.at[e, g, 1].add(
                c * c, mode="drop", indices_are_sorted=True
            )

        timeit("scat_pair_sortedidx", csorted, flux, elem_sorted, group, c)

    if section in ("all", "compact"):
        print("--- compaction primitives ---")
        done = jnp.asarray(rng.random(n) < 0.7)
        timeit("argsort_bool", lambda d: jnp.argsort(d), done)
        timeit("cumsum_i32", lambda d: jnp.cumsum(d.astype(jnp.int32)), done)

        def partition_perm(d):
            di = d.astype(jnp.int32)
            n_active = jnp.sum(1 - di)
            pos_active = jnp.cumsum(1 - di) - 1
            pos_done = n_active + jnp.cumsum(di) - 1
            dst = jnp.where(d, pos_done, pos_active)
            return jnp.zeros(n, jnp.int32).at[dst].set(
                jnp.arange(n, dtype=jnp.int32)
            )

        timeit("partition_perm(cumsum+scat)", partition_perm, done)

        st8 = jnp.asarray(rng.standard_normal((n, 8)).astype(np.float32))
        sub = jnp.asarray(rng.integers(0, n, n // 8).astype(np.int32))
        timeit("state_gather [n/8,8]f32", lambda i: st8[i].sum(), sub)

    if section in ("all", "math"):
        print("--- body math (no memory) ---")
        normals = jnp.asarray(
            rng.standard_normal((n, 4, 3)).astype(np.float32)
        )
        dplane = jnp.asarray(rng.standard_normal((n, 4)).astype(np.float32))
        cur = jnp.asarray(rng.standard_normal((n, 3)).astype(np.float32))
        dirv = jnp.asarray(rng.standard_normal((n, 3)).astype(np.float32))

        def body_math(normals, dplane, cur, dirv):
            denom = jnp.einsum("pfc,pc->pf", normals, dirv)
            num = dplane - jnp.einsum("pfc,pc->pf", normals, cur)
            t = jnp.where(
                denom > 0, num / jnp.where(denom > 0, denom, 1), jnp.inf
            )
            t = jnp.maximum(t, 0.0)
            return jnp.min(t, axis=-1), jnp.argmin(t, axis=-1)

        timeit("exit_face_math", body_math, normals, dplane, cur, dirv)


if __name__ == "__main__":
    main()
