"""Probe: which in-kernel gather/scatter forms does Mosaic lower here?

Decides whether the VMEM-resident Pallas walk kernel
(pumiumtally_tpu/ops/walk_pallas.py) is viable on this backend: tables
in VMEM, whole walk in one launch — no per-crossing dispatch, no HBM
gather latency. Two lowering questions, probed independently:

  GATHER — vectorized random row-gather from a VMEM table:
    take      — jnp.take(table, idx, axis=0)
    onehot    — one-hot matmul gather (MXU; the form the kernel uses)
    loop      — per-lane fori_loop of dynamic slices (scalar fallback)

  SCATTER — the matrixized tally accumulate (round 6): the kernel
  replaces the per-crossing HBM scatter-add with a one-hot OUTER
  PRODUCT into a tile-local accumulator, ``onehot(elem)^T @ V`` with
  ``V[B, 2G]`` holding (w·len, (w·len)²) pairs:
    outer     — single-pass one-hot outer-product accumulate
    peeled    — the kernel's exact-collision-peeling loop (ascending
                lane order per bin — the XLA scatter-add order), at the
                same [B, ntet] x [B, 2G] tile shapes walk_pallas uses

Each probe records OK + a rough bandwidth, or the Mosaic error. Results
print AND land in PALLAS_PROBE_r06.json (runnable pre-capture on any
backend: CPU probes run the kernels in interpret mode and answer only
"does the program agree with the reference", not "does Mosaic lower" —
the JSON records which question was asked via "interpret").
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

T, C = 4096, 16        # gather-probe table rows x cols (fits VMEM easily)
N = 2048               # lanes gathered per call

# The tally-scatter tile shapes walk_pallas.py actually runs: lane block
# B = DEFAULT_LANE_BLOCK one-hots against ntet mesh rows, accumulating
# [ntet, 2*n_groups] — probe the small/medium-mesh regime corners.
SCATTER_SHAPES = (
    (128, 384, 2),     # B, ntet, n_groups — 4x4x4 box parity mesh
    (128, 6000, 2),    # 10x10x10 box
    (128, 41154, 4),   # ~55-cell bench rung, wider group axis
)

INTERPRET = jax.default_backend() != "tpu"
RESULTS: list[dict] = []


def _record(name, shape, ok, usec=None, gbps=None, error=None):
    RESULTS.append(
        dict(
            probe=name,
            shape=list(shape),
            ok=bool(ok),
            usec_per_call=usec,
            gbps=gbps,
            error=error,
            interpret=INTERPRET,
        )
    )


def run(name, kernel, reps=50):
    tbl = jnp.asarray(np.random.default_rng(0).normal(size=(T, C)), jnp.float32)
    idx = jnp.asarray(
        np.random.default_rng(1).integers(0, T, (N,)).astype(np.int32)
    )
    try:
        f = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((N, C), jnp.float32),
            interpret=INTERPRET,
        )
        f = jax.jit(f)
        out = jax.block_until_ready(f(tbl, idx))
        expect = np.asarray(tbl)[np.asarray(idx)]
        np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = f(tbl, idx)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / reps
        gbps = N * C * 4 / dt / 1e9
        print(f"{name:10s} OK  {dt*1e6:8.1f} us/call  {gbps:7.2f} GB/s")
        _record(name, (T, C, N), True, dt * 1e6, gbps)
    except Exception as e:
        msg = str(e).split("\n")[0][:140]
        print(f"{name:10s} FAIL {type(e).__name__}: {msg}")
        _record(name, (T, C, N), False, error=f"{type(e).__name__}: {msg}")


def k_take(tbl_ref, idx_ref, out_ref):
    out_ref[:] = jnp.take(tbl_ref[:], idx_ref[:], axis=0)


def k_onehot(tbl_ref, idx_ref, out_ref):
    oh = jax.nn.one_hot(idx_ref[:], T, dtype=jnp.float32)  # [N, T]
    out_ref[:] = jnp.dot(oh, tbl_ref[:], preferred_element_type=jnp.float32)


def k_loop(tbl_ref, idx_ref, out_ref):
    def body(i, _):
        out_ref[i, :] = tbl_ref[idx_ref[i], :]
        return 0

    jax.lax.fori_loop(0, N, body, 0)


# --------------------------------------------------------------------- #
# MXU one-hot SCATTER probes (round 6): outer-product accumulate at the
# walk_pallas tally tile shapes.
# --------------------------------------------------------------------- #
def _scatter_inputs(B, ntet, G, seed=2):
    rng = np.random.default_rng(seed)
    elem = jnp.asarray(rng.integers(0, ntet, (B,)).astype(np.int32))
    group = jnp.asarray(rng.integers(0, G, (B,)).astype(np.int32))
    contrib = jnp.asarray(rng.uniform(0.1, 2.0, (B,)), jnp.float32)
    acc0 = jnp.zeros((ntet, 2 * G), jnp.float32)
    return elem, group, contrib, acc0


def _scatter_reference(elem, group, contrib, acc0):
    acc = np.asarray(acc0).copy()
    for i in range(elem.shape[0]):  # ascending lane order — XLA's order
        c = float(contrib[i])
        acc[int(elem[i]), 2 * int(group[i])] += c
        acc[int(elem[i]), 2 * int(group[i]) + 1] += c * c
    return acc


def make_k_outer(B, ntet, G):
    def k_outer(elem_ref, group_ref, contrib_ref, acc_ref, out_ref):
        elem, group, contrib = elem_ref[:], group_ref[:], contrib_ref[:]
        iota_bt = jax.lax.broadcasted_iota(jnp.int32, (B, ntet), 1)
        iota_bc = jax.lax.broadcasted_iota(jnp.int32, (B, 2 * G), 1)
        col = 2 * group
        v = jnp.where(
            iota_bc == col[:, None],
            contrib[:, None],
            jnp.where(
                iota_bc == col[:, None] + 1,
                (contrib * contrib)[:, None],
                0.0,
            ),
        )
        ohe = (elem[:, None] == iota_bt).astype(jnp.float32)
        out_ref[:] = acc_ref[:] + jax.lax.dot_general(
            ohe, v, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    return k_outer


def make_k_peeled(B, ntet, G):
    def k_peeled(elem_ref, group_ref, contrib_ref, acc_ref, out_ref):
        elem, group, contrib = elem_ref[:], group_ref[:], contrib_ref[:]
        iota_bt = jax.lax.broadcasted_iota(jnp.int32, (B, ntet), 1)
        iota_bc = jax.lax.broadcasted_iota(jnp.int32, (B, 2 * G), 1)
        i_lt = jax.lax.broadcasted_iota(
            jnp.int32, (B, B), 1
        ) < jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
        key = elem * G + group

        def body(c):
            acc, pending = c
            blocked = (
                (key[:, None] == key[None, :]) & pending[None, :] & i_lt
            )
            first = pending & ~jnp.any(blocked, axis=1)
            csel = jnp.where(first, contrib, 0.0)
            col = 2 * group
            v = jnp.where(
                iota_bc == col[:, None],
                csel[:, None],
                jnp.where(
                    iota_bc == col[:, None] + 1,
                    (csel * csel)[:, None],
                    0.0,
                ),
            )
            ohe = ((elem[:, None] == iota_bt) & first[:, None]).astype(
                jnp.float32
            )
            acc = acc + jax.lax.dot_general(
                ohe, v, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return acc, pending & ~first

        acc, _ = jax.lax.while_loop(
            lambda c: jnp.any(c[1]),
            body,
            (acc_ref[:], jnp.ones((B,), jnp.bool_)),
        )
        out_ref[:] = acc

    return k_peeled


def run_scatter(name, make_kernel, B, ntet, G, reps=20, exact=False):
    elem, group, contrib, acc0 = _scatter_inputs(B, ntet, G)
    try:
        f = pl.pallas_call(
            make_kernel(B, ntet, G),
            out_shape=jax.ShapeDtypeStruct((ntet, 2 * G), jnp.float32),
            interpret=INTERPRET,
        )
        f = jax.jit(f)
        out = jax.block_until_ready(f(elem, group, contrib, acc0))
        expect = _scatter_reference(elem, group, contrib, acc0)
        if exact:
            # The peeled form must reproduce the ascending-lane add
            # order BITWISE — that is its whole reason to exist.
            np.testing.assert_array_equal(np.asarray(out), expect)
        else:
            np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = f(elem, group, contrib, acc0)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / reps
        # Effective scatter bandwidth: the B (c, c²) pairs landed.
        gbps = B * 2 * 4 / dt / 1e9
        print(
            f"{name:10s} [{B}x{ntet}x{G}] OK  {dt*1e6:8.1f} us/call  "
            f"{gbps*1e3:7.2f} MB/s-landed"
        )
        _record(name, (B, ntet, G), True, dt * 1e6, gbps)
    except Exception as e:
        msg = str(e).split("\n")[0][:140]
        print(f"{name:10s} [{B}x{ntet}x{G}] FAIL {type(e).__name__}: {msg}")
        _record(
            name, (B, ntet, G), False, error=f"{type(e).__name__}: {msg}"
        )


def main():
    from pumiumtally_tpu.utils.platform import require_tpu

    require_tpu()  # chip timings only: no silent CPU fallback
    out_path = os.environ.get("PALLAS_PROBE_OUT", "PALLAS_PROBE_r06.json")
    print(
        f"table [{T},{C}] f32, {N} lanes, device={jax.devices()[0]}, "
        f"interpret={INTERPRET}"
    )
    run("take", k_take)
    run("onehot", k_onehot)
    run("loop", k_loop, reps=5)
    for B, ntet, G in SCATTER_SHAPES:
        run_scatter("outer", make_k_outer, B, ntet, G)
        run_scatter("peeled", make_k_peeled, B, ntet, G, exact=True)
    payload = dict(
        device=str(jax.devices()[0]),
        backend=jax.default_backend(),
        interpret=INTERPRET,
        probes=RESULTS,
    )
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=2)
    print(f"wrote {out_path} ({len(RESULTS)} probes)")


if __name__ == "__main__":
    main()
