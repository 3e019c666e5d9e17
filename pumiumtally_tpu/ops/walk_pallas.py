"""Mosaic (Pallas) walk kernel: VMEM-resident tables, matrixized tally.

The XLA walk (ops/walk.py) pays one HBM gather per crossing for the
packed ``geo20`` row and one HBM scatter-add per crossing for the tally
pair — both latency-bound on TPU because the indices are data-dependent.
This module is the Matrix-PIC / POLAR-PIC move (PAPERS.md): recast both
data-dependent accesses as dense MXU-shaped contractions against tables
that live in VMEM for the whole walk, so the entire move is ONE kernel
launch with no per-crossing HBM traffic:

  * GATHER → blocked one-hot matmul.  Each lane block's parent elements
    become a ``[B, ntet]`` one-hot matrix; one ``[B, ntet] @ [28, ntet]ᵀ``
    matmul fetches the whole decoded walk row (12 normals + 4 plane
    offsets + 4 neighbor ids + 4 material-stop bits + 4 neighbor class
    indices, every topology column stored as an exactly-representable
    small float — no bitcast NaN patterns to poison the MXU).  A one-hot
    row has exactly one nonzero, so the contraction is bitwise equal to
    ``jnp.take`` (scripts/probe_pallas_gather.py records the lowering
    probes; the one-hot form is the one Mosaic accepts).
  * SCATTER → one-hot outer product into a tile-local accumulator.  Per
    crossing the scored pair rides ``Vᵀ @ onehot(elem)`` where ``Vᵀ`` is
    the ``[2·n_groups, B]`` per-lane value matrix holding ``w·len`` at
    row ``2g`` and ``(w·len)²`` at ``2g+1`` — a ``[2·n_groups, B] @
    [B, ntet]`` contraction accumulated into a VMEM-resident
    ``[2·n_groups, ntet]`` tile that is flushed to HBM ONCE per launch
    (it aliases the flux operand), replacing the per-crossing XLA
    scatter-add entirely.  Tables and tiles keep ``ntet`` on the
    128-wide lane axis (see ``_make_kernel``), and both contractions
    run at ``Precision.HIGHEST`` so the MXU never rounds an operand.

Bitwise parity with the XLA walk
--------------------------------
The parity suites compare this kernel BIT-for-BIT against the XLA path
(tests/test_kernel_pallas.py), which constrains the design:

  * the per-lane walk arithmetic reuses the exact helpers of the XLA
    body (geometry.exit_face, chase_face_choice, end_of_flight,
    forward_bump), so per-crossing trajectories are identical;
  * the one-hot gather is exact (single nonzero per row — any reduction
    order yields the table row bitwise);
  * the outer-product scatter resolves same-(elem, group) collisions by
    EXACT PEELING: per crossing, repeated passes each select the
    lowest-indexed still-pending lane per tally bin, so every bin
    receives its contributions as a sequence of exact single adds in
    ascending lane order — precisely the order the XLA scatter-add
    applies duplicate updates.  Collision-free crossings (the common
    case) complete in one pass; a crossing with k-fold collisions costs
    k passes.  The accumulator is seeded FROM the flux operand, so the
    add association matches the per-crossing scatter chain exactly;
  * the run reductions (stats vector, integrity vector, convergence
    fold) run OUTSIDE the kernel on its per-lane outputs, through the
    same code the XLA path uses — parity by construction, and the
    packed-staging readback / fused feature tails compose unchanged.

Regime and fallback
-------------------
The kernel holds the walk table ([ntet, 28]), the flux tile
([ntet, 2·n_groups]) and all per-lane state in VMEM, so it targets the
small/medium-mesh regime where the XLA walk's per-crossing HBM gather
latency dominates.  ``select_backend`` enforces the budget: with
``kernel="auto"`` a mesh that exceeds it silently falls back to the XLA
walk; an explicit ``kernel="pallas"`` over budget is an error at
resolve time.  Straggler compaction and the ``tally_scatter`` /
``gathers`` strategy knobs are XLA-path scheduling concepts and are
ignored here (the kernel is a flat loop with a matrixized scatter);
bitwise facade parity therefore holds when the XLA path runs its flat
loop too (compaction auto-disables below 1024 lanes — the parity-suite
regime).

Off TPU the kernel runs in Pallas interpret mode (the parity suites run
it on CPU); ``kernel="auto"`` only selects it on a real TPU backend
unless ``PUMI_TPU_PALLAS_INTERPRET=1`` opts interpret mode in.  On a TPU
it is always compiled (interpret mode there is an error) and runs
float32 only: Mosaic has no 64-bit floats.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .geometry import exit_face
from .walk import (
    TraceResult,
    chase_face_choice,
    end_of_flight,
    forward_bump,
    integrity_vector,
    walk_stats_vector,
)

# Decoded walk-table layout: 12 normal components + 4 plane offsets +
# 4 neighbor ids + 4 material-stop bits + 4 neighbor class indices.
TABLE_COLS = 28
DEFAULT_LANE_BLOCK = 128
# Default VMEM budget for the whole-walk-resident working set. Mosaic
# allocates the estimate plus ~0.4 MiB (described-v5e compiles, PR 21:
# 7.29 MiB vs 6.72 estimated at 10,368 tets / 4,096 lanes; 7.42 vs 7.02
# at 8,192 lanes), so each launch gets twice the budget as its scoped
# VMEM limit: 16 MiB by default, Mosaic's own default on v5e.
DEFAULT_VMEM_MB = 8.0


def kernel_vmem_bytes(
    ntet: int,
    n_particles: int,
    n_groups: int,
    itemsize: int,
    lane_block: int = DEFAULT_LANE_BLOCK,
) -> int:
    """Estimated VMEM working set of one kernel launch: the decoded walk
    table, the flux tile (operand + accumulator), the per-lane walk
    state, and the per-block one-hot / peel temporaries.  An estimate
    with margin, not an exact Mosaic allocation — the budget knob
    (``PUMI_TPU_PALLAS_VMEM_MB``) absorbs the slack."""
    b = min(lane_block, max(n_particles, 1))
    table = ntet * TABLE_COLS * itemsize
    flux = 3 * ntet * n_groups * 2 * itemsize  # operand + acc + out
    lanes = n_particles * (10 * itemsize + 9 * 4)
    blocks = b * ntet * itemsize + b * b + b * 2 * n_groups * itemsize
    return table + flux + lanes + blocks


def _budget_bytes() -> int:
    return int(
        float(os.environ.get("PUMI_TPU_PALLAS_VMEM_MB", DEFAULT_VMEM_MB))
        * 2**20
    )


def select_backend(
    kernel: str,
    *,
    ntet: int,
    n_particles: int,
    n_groups: int,
    dtype,
    packed: bool,
    platform: str | None = None,
    strict: bool = True,
    lane_block: int | None = None,
    tuned_kernel: str | None = None,
) -> str:
    """Resolve the (already env-resolved, combo-validated) kernel knob
    against a concrete workload → ``"xla"`` or ``"pallas"``.

    ``"auto"`` is the fallback policy: Pallas only when the working set
    fits the VMEM budget, the mesh carries the packed ``geo20`` table,
    and the backend is a real TPU (or interpret mode was opted in via
    ``PUMI_TPU_PALLAS_INTERPRET=1``) — anything else silently resolves
    to the XLA walk.  An explicit ``"pallas"`` outside its regime is an
    error HERE, at resolve time, never mid-dispatch — unless
    ``strict=False``, the facades' spelling of "this 'pallas' came from
    the ``PUMI_TPU_KERNEL`` env sweep, not the config": then the kernel
    runs wherever it CAN (packed table, inside the budget, interpret
    mode off TPU is fine — the CI sweep's whole point) and silently
    falls back to the XLA walk where it structurally can't, so one env
    var can blanket a whole suite the way ``PUMI_TPU_IO_PIPELINE``
    does.

    ``lane_block`` is the RESOLVED one-hot block width (TallyConfig
    ``resolve_lane_block``; None = the kernel default) — the VMEM
    budget is checked against the block that will actually run, so a
    wide explicit block counts against ``PUMI_TPU_PALLAS_VMEM_MB``
    instead of the hardcoded default.  ``tuned_kernel`` is the tuning
    database's winner for this shape class (tuning/db.py) and steers
    ONLY the "auto" policy: a database "xla" pins the XLA walk where
    the heuristic would have picked Pallas, a database "pallas" picks
    the kernel wherever it is structurally able to run — and the
    structural gates (packed table, VMEM budget, platform/interpret)
    still apply, so a stale database can never force an infeasible
    kernel.  Explicit "xla"/"pallas" never consult it."""
    if kernel == "xla":
        return "xla"
    if kernel not in ("pallas", "auto"):
        raise ValueError(
            f"kernel must be 'xla', 'pallas' or 'auto': {kernel!r}"
        )
    itemsize = jnp.dtype(dtype).itemsize
    if platform is None:
        platform = jax.default_backend()
    if platform == "tpu" and itemsize != 4:
        # Mosaic has no 64-bit floats: the kernel runs f32 on the chip.
        if kernel == "auto" or not strict:
            return "xla"
        raise ValueError(
            f"kernel='pallas' runs float32 on a TPU; got {jnp.dtype(dtype)}"
            " — use kernel='xla' or 'auto'"
        )
    need = kernel_vmem_bytes(
        ntet, n_particles, n_groups, itemsize,
        lane_block=lane_block or DEFAULT_LANE_BLOCK,
    )
    budget = _budget_bytes()
    if kernel == "pallas":
        if not packed:
            if not strict:
                return "xla"
            raise ValueError(
                "kernel='pallas' needs the packed geo20 walk table "
                "(mesh built with packed=True and < 2^24 elements); "
                "this mesh has none — use kernel='xla' or 'auto'"
            )
        if need > budget:
            if not strict:
                return "xla"
            raise ValueError(
                f"kernel='pallas': estimated VMEM working set "
                f"{need / 2**20:.1f} MiB exceeds the "
                f"{budget / 2**20:.1f} MiB tile budget "
                f"(ntet={ntet}, n_particles={n_particles}, "
                f"n_groups={n_groups}); use kernel='auto' for the "
                "automatic XLA fallback, shrink the workload, or raise "
                "PUMI_TPU_PALLAS_VMEM_MB"
            )
        return "pallas"
    # "auto"
    interpret_ok = os.environ.get("PUMI_TPU_PALLAS_INTERPRET") == "1"
    if not packed or need > budget:
        return "xla"
    if tuned_kernel == "xla":
        # The database measured the XLA walk faster for this shape
        # class — it overrides the in-regime heuristic, not the gates.
        return "xla"
    if platform != "tpu" and not interpret_ok:
        return "xla"
    return "pallas"


def resolve_config_kernel(
    cfg,
    *,
    ntet: int,
    n_particles: int,
    n_groups: int,
    dtype,
    packed: bool,
    platform: str | None = None,
    lane_block: int | None = None,
    tuned=None,
) -> str:
    """The ONE facade-side kernel resolve: config half
    (``TallyConfig.resolve_kernel`` — combo validation, env override),
    the debug-surface pin for "auto" (record_xpoints / checkify ride
    only the XLA walk), and the workload half (``select_backend``) with
    strictness derived from whether "pallas" is written INTO the config
    (an env-forced "pallas" degrades gracefully).  PumiTally and
    StreamingTallyPipeline both call this, so the downgrade list cannot
    drift between facades.

    ``lane_block`` is the resolved block width (feeds the VMEM budget
    check); ``tuned`` is the construction-time tuning decision
    (tuning.TunedDecision or None) whose ``kernel`` winner steers the
    "auto" policy only — an explicit config/env kernel always beats the
    database."""
    kern = cfg.resolve_kernel()
    if kern == "xla":
        return "xla"
    if cfg.record_xpoints is not None or cfg.checkify_invariants:
        # "auto" over a debug surface: the surface pins the XLA walk.
        # (resolve_kernel already rejected/downgraded "pallas" here.)
        return "xla"
    return select_backend(
        kern,
        ntet=ntet,
        n_particles=n_particles,
        n_groups=n_groups,
        dtype=dtype,
        packed=packed,
        platform=platform,
        strict=cfg.kernel == "pallas",
        lane_block=lane_block,
        tuned_kernel=(
            tuned.kernel if tuned is not None and tuned.hit else None
        ),
    )


def decode_walk_table(mesh):
    """[ntet, 28] decoded walk table in the mesh float dtype: the geo20
    geometry columns verbatim, and the per-face topology codes unpacked
    into exactly-representable small floats (neighbor id < 2^24 by the
    geo20 packing precondition, stop bit 0/1, class index < 64) so the
    one-hot matmul gather can never multiply a zero against a bitcast
    NaN/inf pattern."""
    geo = mesh.geo20
    dtype = geo.dtype
    code_int = jnp.int32 if geo.dtype.itemsize == 4 else jnp.int64
    codes = jax.lax.bitcast_convert_type(
        geo[:, 16:20], code_int
    ).astype(jnp.int32)
    nbr = (codes & 0xFFFFFF) - 1
    stop = (codes >> 30) & 1
    cls = (codes >> 24) & 0x3F
    return jnp.concatenate(
        [
            geo[:, :16],
            nbr.astype(dtype),
            stop.astype(dtype),
            cls.astype(dtype),
        ],
        axis=1,
    )


def _pick4(vals, face):
    """Exact per-lane selection of one of 4 integer columns (the
    Mosaic-friendly spelling of ``take_along_axis`` on a [B, 4] int
    array): a where-reduce with a single hot column."""
    iota4 = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    # dtype pinned: under x64 jnp.sum would promote int32 → int64 and
    # poison the loop-carry dtypes.
    return jnp.sum(
        jnp.where(face[:, None] == iota4, vals, 0), axis=1,
        dtype=vals.dtype,
    )


def _col(mask):
    """[B] bool → [B, 1] bool.  Mosaic cannot relayout an i1 vector to a
    column; an int32 one it can."""
    return mask.astype(jnp.int32)[:, None] != 0


def _make_kernel(
    *,
    n_blocks: int,
    lane_block: int,
    ntet: int,
    n_groups: int,
    dtype,
    initial: bool,
    robust: bool,
    score_squares: bool,
    ledger: bool,
    unroll: int,
    max_crossings: int,
    tolerance: float,
    tol_floor: float,
):
    """Build the kernel body for one static walk configuration.

    Every VMEM array keeps its long axis on the 128-wide lane axis, so
    none is padded out from a narrow minor dim: the walk table is
    ``[28, ntet]``, the flux tile ``[2G, ntet]``, per-lane state
    ``[n_blocks, B]`` (one row per lane block) and positions
    ``[n_blocks, 3, B]``.  Each block step loads and stores its own row
    (no value-level dynamic slice reaches Mosaic) and transposes
    positions to the ``[B, 3]`` form the shared helpers take.  The
    crossing loop mirrors ops/walk.py's flat body op-for-op (same
    helpers, same masking) so trajectories are bitwise identical to the
    XLA walk."""
    B = lane_block
    G = n_groups
    # One-hot contractions must not round the gathered coordinates: the
    # MXU's default f32 path runs reduced-precision passes.
    hi = jax.lax.Precision.HIGHEST

    def kernel(
        tbl_ref, origin_ref, dest_ref, elem_ref, fly_ref, w_ref, g_ref,
        mat_ref, flux_ref,
        pos_out, elem_out, mat_out, done_out, pseg_out, ncross_out,
        nchase_out, nseg_out, nbump_out, iters_out, flux_out,
        prev_ref, stuck_ref,
    ):
        i_lt = jax.lax.broadcasted_iota(
            jnp.int32, (B, B), 1
        ) < jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)  # j < i
        iota_bt = jax.lax.broadcasted_iota(jnp.int32, (B, ntet), 1)
        iota_cb = jax.lax.broadcasted_iota(jnp.int32, (2 * G, B), 0)

        def row(ref, b):
            return ref[pl.ds(b, 1), :].reshape(B)

        def put(ref, b, v):
            ref[pl.ds(b, 1), :] = v.reshape(1, B)

        def tally_peel(elemb, groupb, contrib, pending0):
            """Matrixized tally scatter with EXACT collision peeling:
            each pass selects the lowest still-pending lane per
            (elem, group) bin and lands the whole pass as ONE
            ``onehot(elem)^T @ V`` outer product — per-bin accumulation
            order is ascending lane, the XLA scatter-add order."""
            key = elemb * G + groupb

            # The pending mask rides the loop as int32: Mosaic cannot
            # carry an i1 vector through a loop.
            def body(pending_i):
                pending = pending_i != 0
                blocked = (
                    (key[:, None] == key[None, :])
                    & pending[None, :]
                    & i_lt
                )
                first = pending & ~jnp.any(blocked, axis=1)
                csel = jnp.where(first, contrib, 0.0)
                csq = csel * csel if score_squares else csel * 0.0
                col = 2 * groupb
                # [2G, B]: w·len at row 2g, (w·len)² at row 2g+1.
                v_t = jnp.where(
                    iota_cb == col[None, :],
                    csel[None, :],
                    jnp.where(
                        iota_cb == col[None, :] + 1,
                        csq[None, :],
                        0.0,
                    ),
                )
                ohe = (
                    (elemb[:, None] == iota_bt) & _col(first)
                ).astype(dtype)
                flux_out[...] += jnp.dot(
                    v_t, ohe, precision=hi, preferred_element_type=dtype
                )
                return (pending & ~first).astype(jnp.int32)

            jax.lax.while_loop(
                lambda p: jnp.any(p != 0), body,
                pending0.astype(jnp.int32),
            )

        def block_step(b, it):
            """One boundary crossing for one lane block: blocked one-hot
            gather, the shared walk arithmetic, the matrixized tally."""
            curb = pos_out[b].T
            destb = dest_ref[b].T
            elemb = row(elem_out, b)
            doneb = row(done_out, b) != 0
            matb = row(mat_out, b)
            prevb = row(prev_ref, b)
            stuckb = row(stuck_ref, b)
            ncrossb = row(ncross_out, b)
            nchaseb = row(nchase_out, b)
            nseglb = row(nseg_out, b)
            nbumpb = row(nbump_out, b)
            flyb = row(fly_ref, b) != 0
            weightb = row(w_ref, b)
            groupb = row(g_ref, b)
            goodb = (groupb >= 0) & (groupb < G)

            active = jnp.logical_not(doneb)

            # ONE blocked one-hot matmul fetches the whole decoded row.
            oh = (elemb[:, None] == iota_bt).astype(dtype)
            tab = jax.lax.dot_general(
                oh, tbl_ref[...], (((1,), (1,)), ((), ())),
                precision=hi, preferred_element_type=dtype,
            )
            normals = jnp.stack(
                [tab[:, 3 * f:3 * f + 3] for f in range(4)], axis=1
            )
            dplane = tab[:, 12:16]
            nbrs_all = tab[:, 16:20].astype(jnp.int32)
            stop_all = tab[:, 20:24].astype(jnp.int32)
            cls_all = tab[:, 24:28].astype(jnp.int32)

            dirv = destb - curb
            if robust:
                backward = (prevb[:, None] >= 0) & (
                    nbrs_all == prevb[:, None]
                )
                t_exit, face, has_exit, plane_num = exit_face(
                    normals, dplane, curb, dirv, exclude=backward,
                    return_num=True,
                )
                sd = -plane_num
                contained = jnp.max(sd, axis=-1) <= 0.0
                chase = active & (stuckb >= 4) & ~contained
                chase_face = chase_face_choice(
                    sd, elemb, it, dtype, nbrs_all >= 0
                )
                face = jnp.where(chase, chase_face, face)
                t_exit = jnp.where(chase, 0.0, t_exit)
                has_exit = has_exit | chase
            else:
                t_exit, face, has_exit = exit_face(
                    normals, dplane, curb, dirv
                )

            dnorm = jnp.linalg.norm(dirv, axis=-1)
            reached, t_step = end_of_flight(
                t_exit, has_exit, dnorm, tolerance, tol_floor
            )
            xpoint = curb + t_step[:, None] * dirv

            crossed = active & ~reached & has_exit
            real_cross = crossed & ~chase if robust else crossed
            ncrossb = ncrossb + real_cross.astype(ncrossb.dtype)
            if robust:
                nchaseb = nchaseb + chase.astype(nchaseb.dtype)
            nbr = _pick4(nbrs_all, face)
            next_elem = jnp.where(crossed, nbr, jnp.int32(-1))

            if not initial:
                seg = t_step * dnorm
                score = active & flyb
                if robust:
                    score = score & ~chase
                contrib = jnp.where(score, seg * weightb, 0.0).astype(
                    dtype
                )
                tally_peel(elemb, groupb, contrib, score & goodb)
                nseglb = nseglb + score.astype(nseglb.dtype)
                if ledger:
                    put(pseg_out, b, row(pseg_out, b) + jnp.where(
                        score, seg, 0.0
                    ).astype(dtype))

            domain_exit = crossed & (next_elem == -1)
            if initial:
                material_stop = jnp.zeros_like(domain_exit)
            else:
                stopf = _pick4(stop_all, face)
                nbr_class = _pick4(cls_all, face)
                material_stop = crossed & (stopf == 1)
                if robust:
                    material_stop = material_stop & ~chase
            newly_done = (active & reached) | domain_exit | material_stop

            if not initial:
                matb = jnp.where(
                    material_stop,
                    nbr_class,
                    jnp.where(
                        (active & reached) | domain_exit,
                        jnp.int32(-1),
                        matb,
                    ),
                )

            hopped = crossed & (next_elem != -1)
            if robust:
                prevb = jnp.where(
                    hopped,
                    jnp.where(chase, jnp.int32(-1), elemb),
                    prevb,
                )
            elemb = jnp.where(hopped, next_elem, elemb)
            curb = jnp.where(_col(active), xpoint, curb)
            if robust:
                continuing = crossed & ~newly_done
                curb, stuckb, bumped = forward_bump(
                    stuckb, contained, continuing, t_step, tol_floor,
                    curb, dirv, destb, dnorm,
                )
                nbumpb = nbumpb + bumped.astype(nbumpb.dtype)
            doneb = doneb | newly_done

            pos_out[b] = curb.T
            put(elem_out, b, elemb)
            put(done_out, b, doneb.astype(jnp.int32))
            put(mat_out, b, matb)
            put(prev_ref, b, prevb)
            put(stuck_ref, b, stuckb)
            put(ncross_out, b, ncrossb)
            put(nchase_out, b, nchaseb)
            put(nseg_out, b, nseglb)
            put(nbump_out, b, nbumpb)
            return it

        def all_done():
            return jnp.all(done_out[...] != 0).astype(jnp.int32)

        def crossing(c):
            it, _ = c
            jax.lax.fori_loop(0, n_blocks, block_step, it)
            return it + 1, all_done()

        if unroll > 1:
            inner = crossing

            def crossing(c):  # noqa: F811 — unrolled wrapper
                for _ in range(unroll):
                    c = inner(c)
                return c

        # The loop carries "every lane done" itself: a ref read in the
        # condition is not re-evaluated under interpret mode.
        def cond(c):
            it, done = c
            return jnp.logical_and(it < max_crossings, done == 0)

        pos_out[...] = origin_ref[...]
        elem_out[...] = elem_ref[...]
        done_out[...] = (fly_ref[...] == 0).astype(jnp.int32)
        mat_out[...] = mat_ref[...]
        zeros_i = jnp.zeros((n_blocks, B), jnp.int32)
        prev_ref[...] = zeros_i - 1  # no entry face yet
        stuck_ref[...] = zeros_i
        pseg_out[...] = jnp.zeros((n_blocks, B), dtype)
        ncross_out[...] = zeros_i
        nchase_out[...] = zeros_i
        nseg_out[...] = zeros_i
        nbump_out[...] = zeros_i
        # The tile accumulator is seeded from the flux operand so the add
        # chain matches the XLA per-crossing scatter association exactly.
        flux_out[...] = flux_ref[...]
        it, _ = jax.lax.while_loop(cond, crossing, (jnp.int32(0), all_done()))
        iters_out[...] = jnp.full((1, 128), it, jnp.int32)

    return kernel


def _pad_lanes(a, n_pad, fill=0):
    n = a.shape[0]
    if n == n_pad:
        return a
    pad = jnp.full((n_pad - n,) + a.shape[1:], fill, a.dtype)
    return jnp.concatenate([a, pad], axis=0)


def trace_pallas_impl(
    mesh,
    origin,
    dest,
    elem,
    in_flight,
    weight,
    group,
    material_id,
    flux,
    *,
    initial: bool,
    max_crossings: int,
    score_squares: bool = True,
    tolerance: float = 1e-8,
    compact_after: int | None = None,
    compact_size: int | None = None,
    compact_stages: tuple | None = None,
    unroll: int = 1,
    robust: bool = True,
    tally_scatter: str = "auto",
    gathers: str = "merged",
    ledger: bool = True,
    stats: bool = True,
    integrity: bool = False,
    debug_checks: bool = False,
    record_xpoints: int | None = None,
    n_groups: int | None = None,
    conv_state: tuple | None = None,
    rel_err_target: float = 0.05,
    batch_moves: int = 1,
    lane_block: int | None = None,
    interpret: bool | None = None,
) -> TraceResult:
    """The Pallas walk with trace_impl's exact signature, so the facades
    and the packed-staging program swap it in without plumbing changes.

    ``compact_*``, ``tally_scatter`` and ``gathers`` are accepted and
    IGNORED — they are XLA-path scheduling strategies (the kernel is a
    flat loop with the matrixized scatter); ``record_xpoints`` and
    ``debug_checks`` are XLA-only debug surfaces and raise (TallyConfig
    already rejects the combinations at resolve time).  ``lane_block``
    sets the one-hot block width B (default 128, clamped to the batch);
    ``interpret`` defaults to "interpret off TPU" — the parity suites
    run the kernel interpreted on CPU."""
    del compact_after, compact_size, compact_stages  # XLA lane scheduling
    del tally_scatter, gathers  # XLA scatter/gather strategy knobs
    if record_xpoints is not None:
        raise NotImplementedError(
            "kernel='pallas' cannot record intersection points; use "
            "kernel='xla' (TallyConfig.resolve_kernel rejects the combo)"
        )
    if debug_checks:
        raise NotImplementedError(
            "kernel='pallas' does not thread checkify device asserts; "
            "use kernel='xla'"
        )
    if getattr(mesh, "geo20", None) is None:
        raise ValueError(
            "kernel='pallas' needs the packed geo20 walk table; this "
            "mesh has none (packed=False, >= 2^24 elements, or > 64 "
            "classes) — use kernel='xla'"
        )
    dtype = origin.dtype
    ntet = mesh.tet2tet.shape[0]
    n = origin.shape[0]
    if flux.ndim == 1:
        if n_groups is None:
            raise ValueError(
                "flat flux ([ntet*n_groups*2]) requires the explicit "
                "n_groups kwarg"
            )
    elif n_groups is None:
        n_groups = flux.shape[1]
    elif flux.ndim == 3 and n_groups != flux.shape[1]:
        raise ValueError(
            f"n_groups={n_groups} disagrees with flux.shape[1]="
            f"{flux.shape[1]}"
        )
    flux_shape = flux.shape
    if flux_shape not in ((ntet, n_groups, 2), (ntet * n_groups * 2,)):
        raise ValueError(
            f"flux must be [ntet, n_groups, 2] = ({ntet}, {n_groups}, 2)"
            f" or flat ({ntet * n_groups * 2},); got {flux_shape}"
        )
    if integrity and not ledger:
        raise ValueError(
            "integrity=True needs the per-particle track-length ledger "
            "(ledger=True) for the conservation invariant"
        )
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "Pallas interpret mode is the CPU rehearsal of the kernel; on "
            "a TPU the kernel is compiled (interpret=None or False)"
        )

    in_flight = in_flight.astype(bool)
    weight = weight.astype(dtype)
    group = group.astype(jnp.int32)
    flux_flat = flux.reshape(-1)
    mat0 = material_id * 0 - 2  # packed-body material-code carry
    tol_floor = 8 * float(jnp.finfo(dtype).eps)

    B = min(int(lane_block or DEFAULT_LANE_BLOCK), n)
    n_pad = -(-n // B) * B
    tbl = decode_walk_table(mesh)

    nb = n_pad // B
    kernel = _make_kernel(
        n_blocks=nb,
        lane_block=B,
        ntet=ntet,
        n_groups=n_groups,
        dtype=dtype,
        initial=initial,
        robust=robust,
        score_squares=score_squares,
        ledger=ledger,
        unroll=unroll,
        max_crossings=max_crossings,
        tolerance=tolerance,
        tol_floor=tol_floor,
    )

    def lanes(a, fill=0):
        """[n] per-lane input → the kernel's [n_blocks, B] row layout."""
        return _pad_lanes(a, n_pad, fill).reshape(nb, B)

    def points(a):
        """[n, 3] → the kernel's [n_blocks, 3, B] layout."""
        return _pad_lanes(a, n_pad).reshape(nb, B, 3).transpose(0, 2, 1)

    row_i32 = jax.ShapeDtypeStruct((nb, B), jnp.int32)
    out_shape = (
        jax.ShapeDtypeStruct((nb, 3, B), dtype),       # position
        row_i32,                                       # elem
        row_i32,                                       # material code
        row_i32,                                       # done (0/1)
        jax.ShapeDtypeStruct((nb, B), dtype),          # pseg ledger
        row_i32,                                       # real crossings
        row_i32,                                       # chase hops
        row_i32,                                       # scored segments
        row_i32,                                       # bumps
        jax.ShapeDtypeStruct((1, 128), jnp.int32),     # loop iterations
        jax.ShapeDtypeStruct((2 * n_groups, ntet), dtype),  # flux
    )
    (pos, elem_o, mat, done, pseg, ncross_l, nchase_l, nseg_l, nbump_l,
     iters, flux_out) = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((nb, B), jnp.int32),  # prev: entry-face element
            pltpu.VMEM((nb, B), jnp.int32),  # stuck: zero-progress count
        ],
        input_output_aliases={8: 10},  # flux operand → flux output
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * _budget_bytes()
        ),
        interpret=interpret,
    )(
        tbl.T,
        points(origin),
        points(dest),
        lanes(elem),
        lanes(in_flight.astype(jnp.int32)),
        lanes(weight),
        lanes(group),
        lanes(mat0, fill=-2),
        flux_flat.reshape(ntet, 2 * n_groups).T,
    )
    flux_out = flux_out.T.reshape(-1)
    pos = pos.transpose(0, 2, 1).reshape(n_pad, 3)[:n]
    elem_o, mat, pseg = (a.reshape(-1)[:n] for a in (elem_o, mat, pseg))
    done = done.reshape(-1)[:n] != 0
    ncross_l, nchase_l, nseg_l, nbump_l = (
        a.reshape(-1)[:n] for a in (ncross_l, nchase_l, nseg_l, nbump_l)
    )
    it = iters[0, 0]

    nseg_dtype = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    nseg = jnp.sum(nseg_l.astype(nseg_dtype))

    # Material codes → class values: the identical post-loop resolve of
    # the XLA packed body.
    material_id = jnp.where(
        mat == -2,
        material_id,
        jnp.where(
            mat == -1,
            jnp.int32(-1),
            mesh.class_values[jnp.maximum(mat, 0)],
        ),
    )

    # Run reductions OUTSIDE the kernel, through the same code the XLA
    # path uses — the stats / integrity / convergence tails compose with
    # packed staging unchanged and stay bitwise identical.
    stats_vec = None
    if stats:
        zero = nseg * 0
        # Every crossing sweeps every block; the batch's n lanes count
        # (not the pad), so both backends count one walk alike.
        slots = jnp.float32(n) * it.astype(jnp.float32)
        stats_vec = walk_stats_vector(
            ncross_l, nchase_l, done, zero, zero, nseg, it, slots, zero,
            jnp.sum(nbump_l),
        )
    integ_vec = None
    if integrity:
        integ_vec = integrity_vector(
            in_flight, done, weight, pseg, pos, origin, flux_out,
            dtype, initial,
        )
    conv_vec = conv_out = None
    if conv_state is not None:
        if initial:
            raise ValueError(
                "conv_state is a move-loop feature: the initial "
                "location search scores nothing and must not advance "
                "the batch cadence"
            )
        from ..obs.convergence import fold_and_reduce

        conv_out, conv_vec = fold_and_reduce(
            flux_out, *conv_state,
            batch_moves=batch_moves, rel_err_target=rel_err_target,
        )
    return TraceResult(
        position=pos,
        elem=elem_o,
        material_id=material_id,
        flux=flux_out.reshape(flux_shape),
        n_segments=nseg,
        n_crossings=it,
        done=done,
        track_length=pseg if ledger else None,
        stats=stats_vec,
        integrity=integ_vec,
        convergence=conv_vec,
        conv_state=conv_out,
    )


_STATIC_ARGNAMES = (
    "initial",
    "max_crossings",
    "score_squares",
    "tolerance",
    "compact_after",
    "compact_size",
    "compact_stages",
    "unroll",
    "robust",
    "tally_scatter",
    "gathers",
    "ledger",
    "stats",
    "integrity",
    "debug_checks",
    "record_xpoints",
    "n_groups",
    "rel_err_target",
    "batch_moves",
    "lane_block",
    "interpret",
)

_trace_pallas_jit = jax.jit(
    trace_pallas_impl,
    static_argnames=_STATIC_ARGNAMES,
    # Same donation contract as the XLA trace: the flux / convergence
    # accumulators are donated, the per-lane state is not.
    donate_argnames=("flux", "conv_state"),
)


def trace_pallas(*args, **kwargs):
    return _trace_pallas_jit(*args, **kwargs)


trace_pallas.__doc__ = trace_pallas_impl.__doc__
