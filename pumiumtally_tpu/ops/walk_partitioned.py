"""Distributed fused tracer: per-chip mesh blocks + particle migration.

The multi-chip analog of ops/walk.py for partitioned meshes
(parallel/mesh_partition.py). Each chip owns a block of elements and the
particles currently inside them; the step alternates

  1. a *walk phase* — the same per-crossing sequence as the single-chip
     kernel (score → boundary conditions → hop), except that a crossing into
     an element owned by another chip freezes the particle ("pending") with
     a decoded (target_chip, target_local_elem); and
  2. an *exchange phase* — pending particles are bucketed by destination
     chip into fixed-size per-destination blocks and exchanged with ONE
     `all_to_all` over the device axis (ICI): each chip receives only the
     rows addressed to it and adopts them into free slots,

inside one `lax.while_loop` that ends when no chip has pending particles.

The all_to_all keeps per-round traffic proportional to what actually
migrates (each chip receives n_parts·E2 rows, E2 = per-destination block
size), unlike an `all_gather` of every chip's full emigrant buffer whose
received volume grows as n_parts²·E — at pod scale a Morton-partitioned
mesh has few neighbor parts, so replicating every chip's emigrants to
every chip is almost entirely waste. Overflowing a destination block is
harmless: those emigrants simply wait a round (counted in n_rounds).

The walk phase supports the same straggler compaction as the single-chip
kernel (ops/walk.py): after ``compact_after`` crossings the still-active
lanes are compacted into ``compact_size``-lane subsets (cumsum stable
partition), so the long tail of crossing counts doesn't run every
resident slot to the bitter end.
This is the TPU-native equivalent of the reference's cross-rank particle
migration — the `migrate` flag plumbed through `search(migrate)` into
Pumi-PIC's rebuild/migrate machinery (pumipic_particle_data_structure
.cpp:256-258, 741-769) — with XLA collectives instead of MPI messages.

With a halo partition (partition_mesh(halo_layers=k) — the Pumi-PIC
"buffered picparts" model, cpp:865-876, with depth as a knob) particles
also walk and SCORE through up to k buffered layers of neighboring
parts' elements as guests; only exiting the buffered region migrates.
This collapses the one-round-per-recross ping-pong at jagged Morton cut
boundaries (round_stats showed a geometric 27-round pending tail at 1M
tets without it). Guest-scored flux lands in the host chip's halo rows
and is folded onto owner rows by ONE static all_to_all at walk end
(exact permutation-sum — results stay bit-comparable to single-chip),
after which halo rows are zeroed so callers can accumulate flux across
steps without double-folding.

Tally writes touch only the chip-local flux slab — `[max_local, g, 2]`
or flat `[max_local*g*2]`, the TPU production layout (the 3-D slab pads
its minor dim 2 → 128 under the (8,128) tile; core.tally.make_flux);
since every element is owned by exactly one chip there is no cross-chip
tally reduction at all — assembly back to global element order is a
permutation (mesh_partition.assemble_global_flux).

Capacity contract: a chip's particle buffer (`cap` slots, the per-chip
block of the global particle axis) must fit everything that migrates in.
With `cap == total particle count` no particle can ever be dropped; smaller
caps trade memory for a (counted, reported) risk of dropped immigrants —
`n_dropped` in the result is the hard failure signal. Unsent emigrants
(exchange buffer overflow) are retried next round and never lost.

Material boundaries at partition cuts: the reference hops the particle into
the far element *and* stops it there (cpp:445, 473-479). When that far
element is remote, the particle still migrates — marked done — so its
parent element (where the next move starts) lands on the owning chip; the
class_id comparison itself uses the precomputed `nbr_class` table, so the
walk never reads remote memory.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh_partition import MeshPartition
from ..parallel.particle_sharding import (
    PARTICLE_AXIS as AXIS,
    shard_map,
)
from .geometry import exit_face
from .walk import (
    add_walk_stats,
    chase_face_choice,
    end_of_flight,
    forward_bump,
    first_k_active,
    lane_slots_word,
    normalize_compact_stages,
    record_crossing,
    resolve_tally_scatter,
)


class PartitionedTraceResult(NamedTuple):
    """Per-slot outputs, sharded over the device axis ([n_parts * cap] or
    [n_parts, ...] leading layout as noted).

    position/material_id/group/weight/particle_id/elem/valid/done:
      [n_parts*cap] slot-major particle state after the step; `valid` marks
      occupied slots, `elem` is the *local* element index on the owning chip.
    flux: per-chip owned-element slabs, in the CALLER's layout —
      [n_parts, max_local, n_groups, 2], or flat
      [n_parts, max_local*n_groups*2] when the step was driven with flat
      slabs (the TPU production layout and PartitionedTally's default).
    n_segments: [n_parts] scored segment count per chip.
    n_rounds: [n_parts] walk/exchange rounds executed (replicated value).
    n_dropped: [n_parts] immigrants dropped for lack of free slots (0 unless
      cap was undersized).
    """

    position: jax.Array
    dest: jax.Array
    elem: jax.Array
    material_id: jax.Array
    weight: jax.Array
    group: jax.Array
    particle_id: jax.Array
    valid: jax.Array
    done: jax.Array
    flux: jax.Array
    n_segments: jax.Array
    n_rounds: jax.Array
    n_dropped: jax.Array
    # [n_parts*cap] per-particle scored track length (walk.py
    # track_length), migrating with its particle across cuts — the
    # conservation ledger that makes cut-boundary double-scoring visible.
    track_length: jax.Array | None = None
    # [n_parts, 6, rounds_bound] per-chip per-round cost breakdown:
    # rows are (pending before exchange, sent, received-for-me, free
    # slots before adoption, adopted, follow-up walk body iterations).
    # The round-count model in one array: rounds where sent < pending
    # are exchange-buffer overflow waits (raise exchange_size); a long
    # tail of tiny pending counts is cut ping-pong (each cut crossing
    # on a particle's path costs one round by construction). Row 5 x
    # the follow-up lane width is that round's executed walk slots —
    # the walk-vs-exchange cost split VERDICT r4 asked to expose
    # (clean-box virtual mesh, PARTITIONED_PROFILE_r05.json: the 3
    # rounds at 1M tets cost ~0.6 s of the 5.3 s step; phase 1
    # dominates, and most of it is serialized per-iteration fixed
    # cost — BENCHMARKS.md "Round-5 decomposition").
    round_stats: jax.Array | None = None
    # [n_parts*cap, K, 3] / [n_parts*cap] per-particle boundary-crossing
    # points and counts when make_partitioned_step(record_xpoints=K) was
    # requested (ops/walk.py xpoints semantics; the buffers migrate with
    # their particles across cuts, so a particle's sequence is its full
    # path order regardless of which chips walked it). None otherwise.
    xpoints: jax.Array | None = None
    n_xpoints: jax.Array | None = None
    # [n_parts, 12] per-chip telemetry vectors in the
    # obs/walk_stats.py WALK_STATS_FIELDS order. The crossing/chase
    # counters are per resident SLOT and do not migrate with particles
    # (they measure work executed on the chip, not particle identity),
    # so "max_crossings" is a per-chip per-slot maximum and "crossings"
    # sums to the global total across chips. "loop_iters" is phase-1
    # iterations plus every follow-up round's iterations (round_stats
    # row 5). obs.walk_stats.reduce_chip_stats aggregates the matrix.
    stats: jax.Array | None = None
    # [n_parts, cap*PART_RB_SLOT_COLS + tail] coalesced readback record
    # (ops/staging.py pack_partitioned_readback), present only when the
    # step was built with packed_io=True: ONE device_get carries the
    # per-slot outputs AND the per-chip stats/round-stats/counters.
    readback: jax.Array | None = None
    # [n_parts, PART_INTEGRITY_LEN] per-chip on-device integrity
    # counters (integrity/invariants.py: bad_flux / lanes_valid /
    # lanes_done), present with make_partitioned_step(integrity=True).
    # The conservation half of the partitioned invariants is evaluated
    # HOST-side by the facade from the migrating track-length ledger —
    # per-lane and cut-aware, strictly stronger than a chip-local sum.
    integrity: jax.Array | None = None
    # Statistical-convergence surface, present with
    # make_partitioned_step(convergence=True) (obs/convergence.py):
    # [n_parts, CONV_LEN] per-chip summary partials over each chip's
    # OWNED bins (halo rows return zeroed, so the partials sum exactly
    # to the global reduction), plus the updated batch accumulators —
    # per-chip snapshot/Σbatch² slabs [n_parts, max_local*n_groups] and
    # the replicated-per-chip batch/move counters [n_parts].  The
    # reductions read the flux slabs and never write them.
    convergence: jax.Array | None = None
    conv_snap: jax.Array | None = None
    conv_sumsq: jax.Array | None = None
    conv_nb: jax.Array | None = None
    conv_mv: jax.Array | None = None


def _walk_phase(
    tables, cur, dest, elem, done, target, target_elem, material_id,
    weight, group, flux, nseg, valid, prev, stuck, pseg, occ, ncross,
    nchase, *xpk,
    initial, tolerance, score_squares, max_crossings, max_local,
    unroll=1, compact_after=None, compact_size=None, compact_stages=None,
    robust=True, tally_scatter="pair", record_xpoints=None, n_groups=None,
):
    """Advance every resident particle until done or pending-migration.

    ``occ``/``ncross``/``nchase`` are the telemetry accumulators of the
    per-chip stats vector (PartitionedTraceResult.stats;
    obs/walk_stats.py): the [3] scalar accumulator (compaction
    occupancy: active lanes placed, slots swept; the forward bumps) plus per-SLOT real-crossing and chase-hop
    counters. They ride the walk carry and the compaction rounds
    exactly like ``pseg`` but do NOT migrate in the exchange — they
    measure work executed on this chip.

    ``prev`` holds the ENC-encoded element the particle last hopped out
    of (local id >= 0, remote code < -1 set by the exchange for
    immigrants, or -1 for none) so the entry-face mask works across
    partition cuts too; ``stuck`` is the zero-progress counter driving
    the chase/bump recovery (ops/walk.py).

    With ``compact_after`` set, lanes still active after that many
    crossings are compacted into ``compact_size``-lane subsets which loop
    to completion — the straggler scheme of ops/walk.py applied to the
    partitioned body (lanes that froze pending-migration drop out of
    "active" either way). ``compact_stages`` generalizes to the staged
    ladder with optional per-stage unroll, exactly as in ops/walk.py
    (entries ``(start, size[, unroll])``, strictly increasing starts;
    intermediate stages run one bounded round, the final stage loops to
    completion)."""
    normals_t, faced_t, enc_t, class_t, nbrclass_t, _ = tables
    dtype = cur.dtype
    if flux.ndim == 1:
        if n_groups is None:
            raise ValueError(
                "flat flux ([max_local*n_groups*2]) requires the explicit "
                "n_groups kwarg"
            )
    elif n_groups is None:
        n_groups = flux.shape[1]
    cap = cur.shape[0]
    tol_floor = 8 * float(jnp.finfo(dtype).eps)
    # The (c, c²) tally pair goes into the flux viewed flat under the
    # same tally_scatter strategy knob (and default) as the single-chip
    # walk — see ops/walk.py's module docstring; the stride-2 layout is
    # load-bearing either way. A flat per-shard slab
    # [max_local*n_groups*2] is the TPU production layout (the 3-D slab
    # pads its minor dim 2 → 128 under the (8,128) tile — see
    # core.tally.make_flux).
    flux_shape = flux.shape
    if flux_shape not in (
        (max_local, n_groups, 2),
        (max_local * n_groups * 2,),
    ):
        raise ValueError(
            f"flux must be [max_local, n_groups, 2] = ({max_local}, "
            f"{n_groups}, 2) or flat ({max_local * n_groups * 2},); "
            f"got {flux_shape}"
        )
    nbins = max_local * n_groups  # OOB sentinel key
    if 2 * nbins >= 2**31:
        raise NotImplementedError(
            "flat tally keys overflow int32: max_local*n_groups*2 = "
            f"{2 * nbins} >= 2^31; use more partitions"
        )
    flux = flux.reshape(-1)

    def make_body(dest_a, weight_a, group_a, valid_a):
        def body(carry):
            (cur, elem, done, target, target_elem, material_id, flux,
             nseg, occ, prev, stuck, pseg, ncross, nchase, *xpk_c,
             it) = carry
            active = valid_a & ~done & (target < 0)

            dirv = dest_a - cur
            normals = normals_t[elem]
            dplane = faced_t[elem]
            enc_row = enc_t[elem]  # [m, 4] encoded neighbors
            # Robustness trio shared with ops/walk.py (see its comments):
            # (1) never step back through the entry face — a straight ray
            # cannot re-enter a convex element it exited. prev is
            # ENC-encoded (local id >= 0 or remote code < -1), so the
            # equality also masks the face back across a partition cut
            # for freshly migrated particles.
            if robust:
                backward = (prev[:, None] != -1) & (
                    enc_row == prev[:, None]
                )
                t_exit, face, has_exit, plane_num = exit_face(
                    normals, dplane, cur, dirv, exclude=backward,
                    return_num=True,
                )
                # (2) relocation chase after 4 zero-progress crossings in
                # a non-containing element (chase_face_choice, shared
                # with walk.py): hop toward the point; resumes the normal
                # walk once contained. Remote faces count as interior
                # candidates — chasing across a partition cut correctly
                # migrates the lane to the neighbor chip.
                sd = -plane_num  # reuse the exit test's plane numerators
                contained = jnp.max(sd, axis=-1) <= 0.0
                chase = active & (stuck >= 4) & ~contained
                chase_face = chase_face_choice(
                    sd, elem, it, dtype, enc_row != -1
                )
                face = jnp.where(chase, chase_face, face)
                t_exit = jnp.where(chase, 0.0, t_exit)
                has_exit = has_exit | chase
            else:
                t_exit, face, has_exit = exit_face(
                    normals, dplane, cur, dirv
                )

            # Geometric tolerance → ray-parameter space with an ulp floor,
            # matching ops/walk.py exactly so the partitioned and
            # single-chip walks agree on borderline reached decisions.
            dnorm = jnp.linalg.norm(dirv, axis=-1)
            reached, t_step = end_of_flight(
                t_exit, has_exit, dnorm, tolerance, tol_floor
            )
            xpoint = cur + t_step[:, None] * dirv

            crossed = active & ~reached & has_exit
            enc = jnp.where(
                crossed,
                jnp.take_along_axis(enc_row, face[:, None], axis=1)[:, 0],
                jnp.int32(-1),
            )
            domain_exit = crossed & (enc == -1)
            remote = crossed & (enc < -1)
            local_hop = crossed & (enc >= 0)

            # Genuine boundary crossings only, exactly as in ops/walk.py
            # — including the crossing INTO a remote element (the cut
            # face is an interior mesh face; it is counted/recorded
            # once, on the sending chip).
            real_cross = crossed & ~chase if robust else crossed
            ncross = ncross + real_cross.astype(ncross.dtype)
            if robust:
                nchase = nchase + chase.astype(nchase.dtype)
            if record_xpoints is not None:
                xpk_c = list(
                    record_crossing(xpk_c[0], xpk_c[1], xpoint, real_cross)
                )

            if not initial:
                seg = jnp.linalg.norm(xpoint - cur, axis=-1)
                # Chase hops are bookkeeping (zero length): keep them out
                # of the tally rows and the segment count.
                score = active & ~chase if robust else active
                contrib = jnp.where(score, seg * weight_a, 0.0).astype(dtype)
                key = jnp.where(
                    score & (group_a >= 0) & (group_a < n_groups),
                    elem * n_groups + group_a,
                    nbins,
                )
                if not score_squares:
                    flux = flux.at[key * 2].add(contrib, mode="drop")
                elif tally_scatter == "interleaved":
                    kk = jnp.concatenate([key * 2, key * 2 + 1])
                    vv = jnp.concatenate([contrib, contrib * contrib])
                    flux = flux.at[kk].add(vv, mode="drop")
                else:
                    flux = flux.at[key * 2].add(contrib, mode="drop")
                    flux = flux.at[key * 2 + 1].add(
                        contrib * contrib, mode="drop"
                    )
                nseg = nseg + jnp.sum(score).astype(nseg.dtype)
                # Per-particle conservation ledger (walk.py
                # track_length); migrates with the particle so a
                # double-scored cut segment is visible in the total.
                pseg = pseg + jnp.where(score, seg, 0.0).astype(dtype)

            nclass = nbrclass_t[elem, face]
            if initial:
                material_stop = jnp.zeros_like(domain_exit)
            else:
                material_stop = (
                    crossed & (enc != -1) & (nclass != class_t[elem])
                )
                # A relocation-chase hop is bookkeeping, not a physical
                # crossing: it must not trigger a material stop.
                if robust:
                    material_stop = material_stop & ~chase
            newly_done = (active & reached) | domain_exit | material_stop
            if not initial:
                material_id = jnp.where(
                    material_stop,
                    nclass,
                    jnp.where(
                        (active & reached) | domain_exit,
                        jnp.int32(-1),
                        material_id,
                    ),
                )

            # Remote crossing → freeze + address the owner chip. A remote
            # material-stop migrates too (done on arrival) so the parent
            # element ends up on its owner.
            code = -2 - enc
            target = jnp.where(remote, code // max_local, target)
            target_elem = jnp.where(remote, code % max_local, target_elem)

            if robust:
                # Chase hops clear prev (the convexity argument behind
                # the entry-face mask applies to real crossings only,
                # walk.py).
                prev = jnp.where(
                    local_hop, jnp.where(chase, jnp.int32(-1), elem), prev
                )
            elem = jnp.where(local_hop, enc, elem)
            cur = jnp.where(active[:, None], xpoint, cur)
            if robust:
                # (3) degeneracy bump (forward_bump, shared with
                # walk.py): guaranteed forward progress per crossing,
                # landing on the destination where it would pass it.
                continuing = local_hop & ~newly_done
                cur, stuck, bumped = forward_bump(
                    stuck, contained, continuing, t_step, tol_floor,
                    cur, dirv, dest_a, dnorm,
                )
                occ = occ + jnp.concatenate([
                    jnp.zeros(2, jnp.int32),
                    jnp.sum(bumped, dtype=jnp.int32)[None],
                ])
            done = done | newly_done
            return (cur, elem, done, target, target_elem, material_id,
                    flux, nseg, occ, prev, stuck, pseg, ncross, nchase,
                    *xpk_c, it + 1)

        return body

    def run(body, valid_a, carry, bound, unroll=unroll):
        if unroll > 1:
            inner = body

            def body(c):  # noqa: F811 — dispatch-amortizing unroll
                for _ in range(unroll):
                    c = inner(c)
                return c

        def cond(carry):
            cur, elem, done, target, *_rest, it = carry
            active = valid_a & ~done & (target < 0)
            return jnp.logical_and(it < bound, jnp.any(active))

        return jax.lax.while_loop(cond, body, carry)

    # Normalize the single-stage knobs into a one-entry schedule and
    # validate — the exact rules of ops/walk.py (shared helper).
    compact_stages = normalize_compact_stages(
        compact_stages, compact_after, compact_size, cap, max(cap // 8, 64)
    )

    full_body = make_body(dest, weight, group, valid)
    phase1_bound = (
        max_crossings if compact_stages is None
        else min(compact_stages[0][0], max_crossings)
    )
    carry = (
        cur, elem, done, target, target_elem, material_id, flux, nseg,
        occ, prev, stuck, pseg, ncross, nchase, *xpk, jnp.int32(0),
    )
    # Static guard: a stage-0 schedule (the follow-up phases) must not
    # compile the dead full-width while_loop at all.
    if phase1_bound > 0:
        carry = run(full_body, valid, carry, phase1_bound)
    # Lane-slots (obs/walk_stats.py): cap per full-width iteration, S
    # per iteration of each compaction round — after each loop, float32.
    slots = jnp.float32(cap) * carry[-1].astype(jnp.float32)

    if compact_stages is not None and phase1_bound < max_crossings:
        def compact_round(state, S, bound, stage_unroll=unroll):
            """Gather the first S active lanes, advance them until done or
            pending, scatter back (first_k_active, shared with walk.py)."""
            (cur, elem, done, target, target_elem, material_id, flux,
             nseg, occ, prev, stuck, pseg, ncross, nchase, *xpk_s,
             it) = state
            active = valid & ~done & (target < 0)
            idx, n_active = first_k_active(active, S)
            sub_ok = jnp.arange(S) < n_active
            # Occupancy telemetry: active lanes placed vs slots swept.
            z = jnp.zeros_like(n_active)
            occ = occ + jnp.stack(
                [jnp.minimum(n_active, S), z + S, z]
            ).astype(jnp.int32)
            sub_body = make_body(
                dest[idx], weight[idx], group[idx], sub_ok
            )
            sub_carry = (
                cur[idx], elem[idx], jnp.logical_not(sub_ok), target[idx],
                target_elem[idx], material_id[idx], flux, nseg, occ,
                prev[idx], stuck[idx], pseg[idx], ncross[idx],
                nchase[idx], *(a[idx] for a in xpk_s), jnp.int32(0),
            )
            (scur, selem, sdone, star, stare, smat, flux, nseg, occ,
             sprev, sstuck, spseg, sncross, snchase, *sxpk, sit) = run(
                sub_body, sub_ok, sub_carry, bound, unroll=stage_unroll
            )
            idx_sb = jnp.where(sub_ok, idx, cap)
            cur = cur.at[idx_sb].set(scur, mode="drop")
            elem = elem.at[idx_sb].set(selem, mode="drop")
            done = done.at[idx_sb].set(sdone, mode="drop")
            target = target.at[idx_sb].set(star, mode="drop")
            target_elem = target_elem.at[idx_sb].set(stare, mode="drop")
            material_id = material_id.at[idx_sb].set(smat, mode="drop")
            prev = prev.at[idx_sb].set(sprev, mode="drop")
            stuck = stuck.at[idx_sb].set(sstuck, mode="drop")
            pseg = pseg.at[idx_sb].set(spseg, mode="drop")
            ncross = ncross.at[idx_sb].set(sncross, mode="drop")
            nchase = nchase.at[idx_sb].set(snchase, mode="drop")
            xpk_s = [
                a.at[idx_sb].set(v, mode="drop")
                for a, v in zip(xpk_s, sxpk)
            ]
            return (cur, elem, done, target, target_elem, material_id,
                    flux, nseg, occ, prev, stuck, pseg, ncross, nchase,
                    *xpk_s, it + sit)

        def any_active(c):
            done, target = c[2], c[3]
            return jnp.any(valid & ~done & (target < 0))

        for i, (start, size, *rest) in enumerate(compact_stages):
            S = min(cap, max(int(size), 1))
            s_unroll = int(rest[0]) if rest else unroll
            it_before = carry[-1]
            if i + 1 < len(compact_stages):
                # Intermediate stage: one bounded round; leftovers wait
                # for a later stage (the final one mops up).
                span = (
                    min(compact_stages[i + 1][0], max_crossings) - start
                )
                if span > 0:
                    carry = jax.lax.cond(
                        any_active(carry),
                        lambda c: compact_round(c, S, span, s_unroll),
                        lambda c: c,
                        carry,
                    )
            else:
                # Final stage: loop rounds to completion. Each round
                # retires >= S active lanes (to done or pending) or all
                # of them, so ceil(cap/S)+1 rounds always suffice.
                max_rounds = -(-cap // S) + 1

                def outer_body(c):
                    *st, rounds = c
                    st = compact_round(
                        tuple(st), S, max_crossings, s_unroll
                    )
                    return (*st, rounds + 1)

                def outer_cond(c):
                    rounds = c[-1]
                    return jnp.logical_and(
                        rounds < max_rounds, any_active(c[:-1])
                    )

                *carry, _ = jax.lax.while_loop(
                    outer_cond, outer_body, (*carry, jnp.int32(0))
                )
                carry = tuple(carry)
            slots = slots + jnp.float32(S) * (
                carry[-1] - it_before
            ).astype(jnp.float32)

    # prev/stuck return to the caller's carry; the loop counter comes
    # back next to last (total body iterations executed across all
    # stages — the per-round walk-cost term of round_stats) and the
    # lane-slots last. The flux rides the loop flat — restore the
    # caller's layout.
    out = carry[:-1]
    return (
        out[:6] + (out[6].reshape(flux_shape),) + out[7:]
        + (carry[-1], slots)
    )


def make_partitioned_step(
    device_mesh: Mesh,
    partition: MeshPartition,
    *,
    n_groups: int,
    initial: bool = False,
    max_crossings: int = 4096,
    max_rounds: int | None = None,
    exchange_size: int | None = None,
    tolerance: float = 1e-8,
    score_squares: bool = True,
    unroll: int = 1,
    compact_after: int | None = None,
    compact_size: int | None = None,
    compact_stages: tuple | None = None,
    followup_compact_size: int | None = None,
    robust: bool = True,
    tally_scatter: str = "auto",
    record_xpoints: int | None = None,
    packed_io: bool = False,
    integrity: bool = False,
    convergence: bool = False,
    rel_err_target: float = 0.05,
    batch_moves: int = 1,
    _jit: bool = True,
):
    """Build the jitted distributed trace step for one mesh partition.

    Args:
      device_mesh: 1-D `jax.sharding.Mesh`; its size must equal
        `partition.n_parts`.
      exchange_size: emigrant slots PER DESTINATION CHIP per round
        (default max(cap // (2·n_parts), 64)); the all_to_all moves
        n_parts·exchange_size rows per chip per round. Overflowing
        emigrants wait a round.
      max_rounds: bound on walk/exchange rounds (default 4 * n_parts + 8 —
        a particle path can re-enter parts, Morton blocks are compact so
        few passes suffice; truncation shows up as done=False).
      compact_after/compact_size: straggler compaction for the FIRST
        walk phase, as in ops/walk.py (default off).
      compact_stages: staged compaction ladder ((start, size[, unroll]),
        ...) applied to the first walk phase, as in ops/walk.py;
        overrides the two single-stage knobs.
      followup_compact_size: lane width of the walk phases AFTER the
        first exchange (default max(cap // 16, 64)). Only the particles
        adopted in the preceding exchange are active in a follow-up
        phase — usually a tiny fraction of cap — so follow-ups always
        run as compaction rounds of this width from crossing 0 instead
        of sweeping all cap slots again; per-round walk cost becomes
        O(actives), not O(cap). Pure scheduling — results unchanged.
      robust/tally_scatter: the degeneracy-recovery and tally-scatter
        strategy knobs of ops/walk.py, applied to the partitioned body
        (same semantics, same defaults).
      record_xpoints: when set to K, record each particle's first K
        boundary-crossing points (ops/walk.py semantics — cut faces are
        interior mesh faces, recorded once on the sending chip). The
        [cap, K, 3] buffer and its counter ride the walk carry, the
        compaction rounds, AND the migration exchange (payload grows by
        3K floats + 1 int per emigrant row), so a particle's recorded
        sequence is its full path order across chips.
      packed_io: move-loop I/O pipelining (ops/staging.py). When True
        the returned callable is ``step(record, flux)`` where
        ``record`` is the [n_parts*cap, PART_IN_COLS] carrier-word
        record from staging.pack_partitioned_record (donated; ONE H2D
        per move), the record unpack runs inside the compiled program,
        and the result carries a coalesced ``readback`` array packing
        every per-slot output plus the per-chip stats/round-stats/
        counters (ONE D2H per move).  Bit-identical to the unpacked
        step.  Incompatible with record_xpoints (the facade falls back
        to the legacy pipeline there).
      integrity: fold the per-chip on-device integrity counters into
        the program (PartitionedTraceResult.integrity;
        integrity/invariants.py PART_INTEGRITY_FIELDS): non-finite /
        negative flux-entry count over the owned slab plus slot
        accounting (valid and finished lanes) for the facade's
        lane-conservation check. End-of-step reductions only — the
        packed readback carries them in its existing int64 tail, so
        the one-H2D/one-D2H invariant of PR 3 is untouched.
      convergence: fold the statistical-convergence batch accumulators
        and the per-chip uncertainty reduction into the program
        (obs/convergence.py; PartitionedTraceResult.convergence +
        conv_* fields).  The step then takes FIVE extra trailing
        per-chip arrays — snapshot and Σbatch² slabs
        [n_parts, max_local*n_groups], batch and move counters
        [n_parts], and an int enable gate [n_parts] (0 suppresses the
        fold entirely: the facade passes 0 for initial-search and
        escalation re-walk dispatches so they never advance the batch
        cadence).  End-of-step elementwise passes + reductions over
        arrays already resident — the packed readback appends CONV_LEN
        carrier words per chip, so the one-H2D/one-D2H invariant still
        holds.  ``rel_err_target`` / ``batch_moves`` are the static
        knobs of the reduction.

    Returns step(cur, dest, elem, done, material, weight, group, pid, valid,
    flux[, conv]) -> PartitionedTraceResult (``conv`` is the 5-tuple
    above, required iff convergence=True), where per-particle arrays are
    [n_parts * cap] sharded over the device axis and flux is
    [n_parts, max_local, n_groups, 2] — or FLAT [n_parts,
    max_local*n_groups*2], the TPU production layout (the 3-D slab pads
    its minor dim 2 → 128 under the (8,128) tile; core.tally.make_flux) —
    sharded on its leading axis. The result keeps the caller's layout.
    The unpacked step also carries ``step.jitted`` (the program, tables
    first) and ``step.table_shapes``, so it can be lowered alone.
    """
    # One policy site for the backend split (ops/walk.py
    # resolve_tally_scatter: interleaved measured best on TPU, pair on
    # CPU — round-4 A/B), resolved against the mesh the step will
    # actually run on: the step is built once per device_mesh, so there
    # is no stale-cache hazard, and the mesh's platform beats
    # jax.default_backend() when they differ.
    if tally_scatter == "auto":
        tally_scatter = resolve_tally_scatter(
            "auto",
            platform=next(iter(device_mesh.devices.flat)).platform,
        )
    if tally_scatter not in ("interleaved", "pair"):
        raise ValueError(
            f"tally_scatter must be 'auto', 'interleaved' or 'pair': "
            f"{tally_scatter!r}"
        )
    n_parts = partition.n_parts
    if device_mesh.shape[AXIS] != n_parts:
        raise ValueError(
            f"device mesh has {device_mesh.shape[AXIS]} devices, partition "
            f"has {n_parts} parts"
        )
    max_local = partition.max_local
    rounds_bound = (
        max_rounds if max_rounds is not None else 4 * n_parts + 8
    )

    # Pin each chip's table block onto that chip once, here — partition_mesh
    # is device-mesh-agnostic, and without this the uncommitted tables would
    # be resharded on every step call (and a >HBM mesh would OOM the default
    # device before the walk ever ran).
    table_sharding = NamedSharding(device_mesh, P(AXIS))
    tables = tuple(
        jax.device_put(t, table_sharding) for t in partition.device_tables()
    )
    # Halo (buffered picparts): particles walk and score through buffered
    # neighbor elements as guests; the extra tables drive the canonical
    # back-reference on migration and the one static all_to_all that folds
    # guest-scored flux onto owner rows at walk end.
    has_halo = partition.row_owner is not None
    if has_halo:
        halo_tables = tuple(
            jax.device_put(t, table_sharding)
            for t in (
                partition.row_owner,
                partition.row_owner_local,
                partition.halo_send_rows,
                partition.halo_recv_rows,
                jnp.asarray(np.asarray(partition.counts, np.int32)[:, None]),
            )
        )
    else:
        halo_tables = ()

    def shard_body(*args):
        (normals_t, faced_t, enc_t, class_t, nbrclass_t,
         volumes_t) = args[:6]
        if has_halo:
            (row_owner_t, row_owner_local_t, halo_send_t, halo_recv_t,
             n_owned_t) = args[6:11]
        tail_args = args[6 + len(halo_tables):]
        (cur, dest, elem, done, material_id, weight, group, pid, valid,
         flux) = tail_args[:10]
        if convergence:
            (conv_snap_t, conv_sumsq_t, conv_nb_t, conv_mv_t,
             conv_en_t) = tail_args[10:]
        # Per-chip blocks arrive with a leading axis of 1; squeeze it.
        tables_l = (
            normals_t[0], faced_t[0], enc_t[0], class_t[0], nbrclass_t[0],
            volumes_t[0],
        )
        if has_halo:
            row_owner_l = row_owner_t[0]
            row_owner_local_l = row_owner_local_t[0]
            halo_send_l = halo_send_t[0]  # [n_parts, Eh] my rows by owner
            halo_recv_l = halo_recv_t[0]  # [n_parts, Eh] owner rows by src
            n_owned_l = n_owned_t[0, 0]
        flux_l = flux[0]
        cap = cur.shape[0]
        E = (
            exchange_size
            if exchange_size is not None
            else max(cap // (2 * n_parts), 64)
        )
        E = min(E, cap)
        # All loop-carried values must be device-varying from the start
        # (shard_map's vma rule) — derive them from per-particle inputs.
        vzero = valid.astype(jnp.int32)  # varying [cap]
        nseg0 = jnp.sum(vzero) * 0
        target0 = vzero * 0 - 1

        walk_kw = dict(
            initial=initial,
            tolerance=tolerance,
            score_squares=score_squares,
            max_crossings=max_crossings,
            max_local=max_local,
            unroll=unroll,
            robust=robust,
            tally_scatter=tally_scatter,
            record_xpoints=record_xpoints,
            n_groups=n_groups,
        )
        walk_first = functools.partial(
            _walk_phase,
            compact_after=compact_after,
            compact_size=compact_size,
            compact_stages=compact_stages,
            **walk_kw,
        )
        # Follow-up phases: only the just-adopted immigrants are active,
        # so skip the full-width phase entirely (stage start 0) and loop
        # narrow compaction rounds to completion.
        S_follow = (
            followup_compact_size
            if followup_compact_size is not None
            else max(cap // 16, 64)
        )
        S_follow = min(S_follow, cap)
        walk_follow = functools.partial(
            _walk_phase,
            compact_stages=((0, S_follow),),
            **walk_kw,
        )

        me = jax.lax.axis_index(AXIS)

        def exchange(carry):
            (cur, dest, elem, done, target, target_elem, material_id,
             weight, group, pid, valid, prev, stuck, pseg, flux_l, nseg,
             dropped, occ, ncross, nchase, *xpk) = carry
            emig = valid & (target >= 0)

            # Bucket emigrants by destination chip: each destination's
            # emigrants rank by a per-destination running count
            # (n_parts static cumsums — n_parts is a trace constant) and
            # address a fixed E-slot block of the send buffer. Rows
            # overflowing their destination block stay resident and
            # retry next round. This replaces a stable argsort +
            # searchsorted formulation: a bitonic sort network costs
            # O(cap·log²cap) on TPU and forced a full gather by the sort
            # order, where the cumsum ranking is O(n_parts·cap) of pure
            # elementwise/scan work and scatters rows from their
            # original lanes. (At pod scale with many parts per host the
            # sort wins asymptotically — revisit the crossover if a
            # partition ever exceeds ~32 parts per exchange group.)
            slot = jnp.full(cap, n_parts * E, jnp.int32)  # OOB rows drop
            sendable = jnp.zeros(cap, bool)
            for d in range(n_parts):
                m_d = emig & (target == d)
                rank_d = jnp.cumsum(m_d.astype(jnp.int32)) - 1
                ok_d = m_d & (rank_d < E)
                slot = jnp.where(ok_d, d * E + rank_d, slot)
                sendable = sendable | ok_d

            def fill(rows):
                buf = jnp.zeros((n_parts * E,) + rows.shape[1:], rows.dtype)
                return buf.at[slot].set(rows, mode="drop")

            K3 = 3 * record_xpoints if record_xpoints is not None else 0
            f_cols = [cur, dest, weight[:, None], pseg[:, None]]
            if record_xpoints is not None:
                # The intersection-point buffer migrates with its
                # particle (flattened [K,3] -> 3K columns).
                f_cols.append(xpk[0].reshape(cap, K3))
            pay_f = fill(jnp.concatenate(f_cols, axis=1))
            # [n_parts*E, 8(+3K)] — the track-length ledger (and the
            # xpoint buffer) migrate with the particle so cut-boundary
            # double-scoring stays visible
            # Entry-face identity for the receiver: the face by which
            # the migrated particle enters its new element points back at
            # (this chip, this element), which the receiver's adjacency
            # encodes as -2 - (me*max_local + elem) — send it so the
            # entry-face mask keeps working across the partition cut.
            # EXCEPT for lanes that froze mid-chase (stuck >= 4): a chase
            # hop is a relocation, not a real crossing, so the convexity
            # mask must not apply — send "no entry face" instead,
            # mirroring the chase prev-clear in the local bodies.
            if has_halo:
                # Canonical identity: the element being left may itself be
                # a halo row here — reference its TRUE owner's row, which
                # is how the receiver's adjacency encodes any non-local
                # neighbor. (If the receiver buffers that element locally,
                # its enc entry is a local index and the mask is simply
                # inert for that immigrant's first crossing — the
                # chase/bump recovery still covers the rare grazing cut.)
                canon = -2 - (
                    row_owner_l[elem] * max_local + row_owner_local_l[elem]
                )
            else:
                canon = -2 - (me * max_local + elem)
            back_code = jnp.where(stuck >= 4, jnp.int32(-1), canon)
            i_cols = [
                pid,
                group,
                material_id,
                target_elem,
                valid.astype(jnp.int32),  # occupied marker
                done.astype(jnp.int32),
                back_code,
            ]
            if record_xpoints is not None:
                i_cols.append(xpk[1].astype(jnp.int32))  # crossing count
            pay_i = fill(jnp.stack(i_cols, axis=1))  # [n_parts*E, 7(+1)]

            # Sent slots free up (sendable is in original lane order).
            valid = valid & ~sendable
            target = jnp.where(sendable, -1, target)

            # ONE all_to_all: block d of my send buffer goes to chip d;
            # I receive n_parts blocks of rows all addressed to me.
            FW, IW = 8 + K3, 7 + (1 if record_xpoints is not None else 0)
            g_f = jax.lax.all_to_all(
                pay_f.reshape(n_parts, E, FW), AXIS, 0, 0, tiled=False
            ).reshape(n_parts * E, FW)
            g_i = jax.lax.all_to_all(
                pay_i.reshape(n_parts, E, IW), AXIS, 0, 0, tiled=False
            ).reshape(n_parts * E, IW)
            mine = g_i[:, 4] == 1  # occupied rows (all addressed to me)

            # Place my immigrants into free slots: the i-th immigrant row
            # goes into the i-th free slot, both found with the
            # first_k_active cumsum partition (walk.py) — linear scans, no
            # argsort (a bitonic network on TPU).
            m = min(n_parts * E, cap)
            src, n_mine = first_k_active(mine, m)
            dst, n_free = first_k_active(jnp.logical_not(valid), m)
            dropped = dropped + jnp.maximum(n_mine - n_free, 0).astype(
                dropped.dtype
            )
            take = jnp.arange(m) < jnp.minimum(n_mine, n_free)
            # Slots past the adopted count must write nothing: their
            # src/dst entries are first_k_active garbage (lane 0), and a
            # duplicate-index scatter would race the real adoption of
            # slot 0 — route them out of bounds instead.
            dst_sb = jnp.where(take, dst, cap)

            def place(slot_arr, rows):
                return slot_arr.at[dst_sb].set(rows, mode="drop")

            cur = place(cur, g_f[src, 0:3].astype(cur.dtype))
            dest = place(dest, g_f[src, 3:6].astype(dest.dtype))
            weight = place(weight, g_f[src, 6].astype(weight.dtype))
            pseg = place(pseg, g_f[src, 7].astype(pseg.dtype))
            pid = place(pid, g_i[src, 0])
            group = place(group, g_i[src, 1])
            material_id = place(material_id, g_i[src, 2])
            elem = place(elem, g_i[src, 3])
            done = place(done, g_i[src, 5].astype(bool))
            prev = place(prev, g_i[src, 6])
            stuck = place(stuck, jnp.zeros_like(stuck[dst]))
            if record_xpoints is not None:
                xpk = [
                    place(
                        xpk[0],
                        g_f[src, 8:8 + K3].reshape(
                            -1, record_xpoints, 3
                        ).astype(xpk[0].dtype),
                    ),
                    place(xpk[1], g_i[src, 7].astype(xpk[1].dtype)),
                ]
            valid = place(valid, take)
            stats = jnp.stack(
                [
                    jnp.sum(emig).astype(jnp.int32),
                    jnp.sum(sendable).astype(jnp.int32),
                    n_mine.astype(jnp.int32),
                    n_free.astype(jnp.int32),
                    jnp.minimum(n_mine, n_free).astype(jnp.int32),
                ]
            )
            return (cur, dest, elem, done, target, target_elem, material_id,
                    weight, group, pid, valid, prev, stuck, pseg, flux_l,
                    nseg, dropped, occ, ncross, nchase, *xpk), stats

        def run_walk(carry, walk_fn):
            (cur, dest, elem, done, target, target_elem, material_id,
             weight, group, pid, valid, prev, stuck, pseg, flux_l, nseg,
             dropped, occ, ncross, nchase, *xpk) = carry
            (cur, elem, done, target, target_elem, material_id, flux_l,
             nseg, occ, prev, stuck, pseg, ncross, nchase, *xpk,
             w_iters, w_slots) = walk_fn(
                tables_l, cur, dest, elem, done, target, target_elem,
                material_id, weight, group, flux_l, nseg, valid, prev,
                stuck, pseg, occ, ncross, nchase, *xpk,
            )
            return (cur, dest, elem, done, target, target_elem, material_id,
                    weight, group, pid, valid, prev, stuck, pseg, flux_l,
                    nseg, dropped, occ, ncross, nchase, *xpk), (
                        w_iters, w_slots)

        # Telemetry accumulators (per-chip stats vector): [3] compaction
        # occupancy and the bumps + per-slot crossing/chase
        # counters. Resident — they never ride the exchange payload
        # (they measure THIS chip's work; an adopted slot keeps counting
        # where its last occupant left off, which is exactly the
        # per-chip total).
        occ0 = jnp.stack([vzero[0]] * 3) * 0
        carry = (
            cur, dest, elem, done, target0, vzero * 0,
            material_id, weight, group, pid, valid, target0 + 0, vzero * 0,
            weight * 0, flux_l, nseg0, nseg0 * 0, occ0, vzero * 0,
            vzero * 0,
        )
        if record_xpoints is not None:
            # Device-varying zeros (shard_map vma rule), like the other
            # loop-carried lanes.
            xp0 = (
                jnp.zeros((cap, int(record_xpoints), 3), cur.dtype)
                + cur[:, :1, None] * 0
            )
            carry = carry + (xp0, vzero * 0)
        carry, (w0_iters, w0_slots) = run_walk(carry, walk_first)

        def pending_somewhere(carry):
            target, valid = carry[4], carry[10]
            n_pend = jnp.sum(valid & (target >= 0)).astype(jnp.int32)
            return jax.lax.psum(n_pend, AXIS) > 0

        stats0 = jnp.zeros((6, rounds_bound), jnp.int32) + vzero[0] * 0

        def round_body(state):
            carry, r, stats, slots = state
            carry, ex_stats = exchange(carry)
            carry, (w_iters, w_slots) = run_walk(carry, walk_follow)
            row = jnp.concatenate(
                [ex_stats, w_iters.astype(jnp.int32)[None]]
            )
            stats = jax.lax.dynamic_update_slice(
                stats, row[:, None], (0, r)
            )
            return carry, r + 1, stats, slots + w_slots

        def round_cond(state):
            carry, r = state[:2]
            return jnp.logical_and(r < rounds_bound, pending_somewhere(carry))

        if rounds_bound > 0:
            carry, n_rounds, round_stats, round_slots = jax.lax.while_loop(
                round_cond, round_body,
                (carry, nseg0 * 0, stats0, nseg0.astype(jnp.float32)),
            )
        else:
            # max_rounds=0: walk-only step (no migration rounds) — used
            # by the phase profiler; the [6, 0] stats buffer must not
            # reach dynamic_update_slice inside a traced body.
            n_rounds, round_stats = nseg0 * 0, stats0
            round_slots = nseg0.astype(jnp.float32)
        (cur, dest, elem, done, target, target_elem, material_id,
         weight, group, pid, valid, prev, stuck, pseg, flux_l, nseg,
         dropped, occ, ncross, nchase, *xpk) = carry

        if has_halo:
            # Fold guest-scored flux back onto owner rows: ONE static
            # all_to_all over the precomputed halo row lists (pad entries
            # index max_local: masked on gather, dropped on scatter).
            # The fold runs on a 2-D [max_local, n_groups*2] view: the
            # minor dim 2G tiles the TPU (8,128) lane layout cleanly
            # (exactly 128 at g=64), where a [.., G, 2] view pads the
            # minor dim 2 up to 128 — the same transient 64x HBM blowup
            # the flat loop-carried slab exists to avoid (at the
            # 10M-tet/64-group/halo-2 target that transient is ~40 GB).
            flat_carry = flux_l.ndim == 1
            flux2 = flux_l.reshape(max_local, n_groups * 2)
            sendable_h = halo_send_l < max_local  # [n_parts, Eh]
            send_h = jnp.where(
                sendable_h[..., None],
                flux2[jnp.minimum(halo_send_l, max_local - 1)],
                0.0,
            )  # [n_parts, Eh, 2G]
            recv_h = jax.lax.all_to_all(send_h, AXIS, 0, 0, tiled=False)
            # My halo rows are folded out — zero them so a caller that
            # accumulates flux across steps cannot double-fold them.
            row_ix = jnp.arange(max_local)
            flux2 = jnp.where((row_ix < n_owned_l)[:, None], flux2, 0.0)
            flux2 = flux2.at[halo_recv_l.reshape(-1)].add(
                recv_h.reshape(-1, n_groups * 2), mode="drop"
            )
            flux_l = (
                flux2.reshape(-1)
                if flat_carry
                else flux2.reshape(max_local, n_groups, 2)
            )

        # Per-chip telemetry vector (obs/walk_stats.py field order —
        # pinned by tests/test_obs.py). loop_iters = phase-1 iterations
        # plus every follow-up round's iterations (round_stats row 5).
        sd_t = nseg.dtype
        svec = jnp.stack([
            jnp.sum(ncross).astype(sd_t),
            jnp.max(ncross).astype(sd_t),
            jnp.sum(nchase).astype(sd_t),
            jnp.sum(valid & ~done).astype(sd_t),
            occ[0].astype(sd_t),
            occ[1].astype(sd_t),
            nseg,
            (w0_iters + jnp.sum(round_stats[5])).astype(sd_t),
            lane_slots_word(w0_slots + round_slots, sd_t),
            nseg * 0,  # ladder_rounds: counted by the single-chip walk
            occ[2].astype(sd_t),
            nseg * 0,  # grid_seeded: the single-chip search's
        ])

        ivec = None
        if integrity:
            # On-device integrity counters (integrity/invariants.py
            # PART_INTEGRITY_FIELDS): corruption in the owned flux slab
            # (the additive accumulator a bit-flip poisons) plus slot
            # accounting for the facade's lane-conservation check.
            bad_flux = jnp.sum(
                jnp.logical_not(jnp.isfinite(flux_l)) | (flux_l < 0.0)
            )
            ivec = jnp.stack([
                bad_flux.astype(sd_t),
                jnp.sum(valid).astype(sd_t),
                jnp.sum(valid & done).astype(sd_t),
            ])

        cvec = cs = css = cnb = cmv = None
        if convergence:
            # Statistical-convergence fold + per-chip summary partials
            # (obs/convergence.py): runs AFTER the halo fold, so the
            # even (Σc) entries read here are the chip's complete owned
            # scores for this move (halo rows are already zeroed — they
            # never count as scored bins).  Reads the slab, never
            # writes it.
            from ..obs.convergence import fold_and_reduce

            (cs, css, cnb, cmv), cvec = fold_and_reduce(
                flux_l.reshape(-1),
                conv_snap_t[0], conv_sumsq_t[0], conv_nb_t[0],
                conv_mv_t[0],
                batch_moves=batch_moves,
                rel_err_target=rel_err_target,
                enable=conv_en_t[0],
            )

        return PartitionedTraceResult(
            position=cur,
            dest=dest,
            elem=elem,
            material_id=material_id,
            weight=weight,
            group=group,
            particle_id=pid,
            valid=valid,
            done=done,
            flux=flux_l[None],
            n_segments=nseg[None],
            n_rounds=n_rounds[None],
            n_dropped=dropped[None],
            track_length=pseg,
            round_stats=round_stats[None],
            xpoints=xpk[0] if xpk else None,
            n_xpoints=xpk[1] if xpk else None,
            stats=svec[None],
            integrity=None if ivec is None else ivec[None],
            convergence=None if cvec is None else cvec[None],
            conv_snap=None if cs is None else cs[None],
            conv_sumsq=None if css is None else css[None],
            conv_nb=None if cnb is None else cnb[None],
            conv_mv=None if cmv is None else cmv[None],
        )

    table_specs = tuple(P(AXIS) for _ in (*tables, *halo_tables))
    particle_spec = P(AXIS)
    conv_specs = (P(AXIS),) * 5 if convergence else ()
    conv_out_spec = P(AXIS) if convergence else None
    mapped = shard_map(
        shard_body,
        mesh=device_mesh,
        in_specs=table_specs + (particle_spec,) * 9 + (P(AXIS),)
        + conv_specs,
        out_specs=PartitionedTraceResult(
            position=particle_spec,
            dest=particle_spec,
            elem=particle_spec,
            material_id=particle_spec,
            weight=particle_spec,
            group=particle_spec,
            particle_id=particle_spec,
            valid=particle_spec,
            done=particle_spec,
            flux=P(AXIS),
            n_segments=P(AXIS),
            n_rounds=P(AXIS),
            n_dropped=P(AXIS),
            track_length=particle_spec,
            round_stats=P(AXIS),
            xpoints=particle_spec if record_xpoints is not None else None,
            n_xpoints=(
                particle_spec if record_xpoints is not None else None
            ),
            stats=P(AXIS),
            integrity=P(AXIS) if integrity else None,
            convergence=conv_out_spec,
            conv_snap=conv_out_spec,
            conv_sumsq=conv_out_spec,
            conv_nb=conv_out_spec,
            conv_mv=conv_out_spec,
        ),
    )
    if packed_io:
        if record_xpoints is not None:
            raise NotImplementedError(
                "packed_io does not carry the intersection-point "
                "buffers; use the unpacked step for record_xpoints"
            )
        from .staging import (
            pack_partitioned_readback,
            unpack_partitioned_record,
        )

        def packed_impl(record, flux, conv_snap=None, conv_sumsq=None,
                        conv_nb=None, conv_mv=None, conv_enable=None):
            (cur, dest, elem, done, material_id, weight, group, pid,
             valid) = unpack_partitioned_record(record)
            extra = (
                (conv_snap, conv_sumsq, conv_nb, conv_mv, conv_enable)
                if convergence
                else ()
            )
            res = mapped(
                *tables, *halo_tables, cur, dest, elem, done,
                material_id, weight, group, pid, valid, flux, *extra,
            )
            return res._replace(
                readback=pack_partitioned_readback(res, n_parts)
            )

        # Donate the flux slab exactly like the unpacked step; a
        # supervisor retry re-sees its original inputs because the
        # facade re-packs the staging record from the caller's
        # untouched host arrays (PR 2's re-arm contract).  The
        # convergence snapshot/Σbatch² slabs carry the same way (the
        # counters and the reusable enable gate are NOT donated — the
        # facade passes the same enable array every move).  The record
        # is not donated — no output shares its carrier shape.
        return jax.jit(
            packed_impl,
            donate_argnames=("flux", "conv_snap", "conv_sumsq"),
        )

    flux_ix = 6 + len(halo_tables) + 9
    if _jit:
        jitted = jax.jit(
            mapped,
            # The flux slab, plus (with convergence) the snapshot/Σbatch²
            # slabs that immediately follow it.
            donate_argnums=(flux_ix,)
            + ((flux_ix + 1, flux_ix + 2) if convergence else ()),
        )
    else:
        # Raw (unjitted) mode for callers that INLINE the step into a
        # larger compiled program (the megastep's scanned body): the
        # outer jit owns compilation and donation.
        jitted = mapped

    def step(cur, dest, elem, done, material_id, weight, group, pid, valid,
             flux, conv=None):
        extra = ()
        if convergence:
            if conv is None:
                raise ValueError(
                    "this step was built with convergence=True and "
                    "needs the (snap, sumsq, nb, mv, enable) tuple"
                )
            extra = tuple(conv)
        return jitted(
            *tables, *halo_tables, cur, dest, elem, done, material_id,
            weight, group, pid, valid, flux, *extra,
        )

    step.jitted = jitted
    step.table_shapes = tuple(
        jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=table_sharding)
        for t in (*tables, *halo_tables)
    )
    return step


# --------------------------------------------------------------------------- #
# Megastep: K device-sourced moves (walk + migration + re-source) fused
# into one compiled program.
# --------------------------------------------------------------------------- #
class PartitionedMegastepResult(NamedTuple):
    """Outputs of one partitioned megastep dispatch. Per-slot state
    ([n_parts*cap], sharded) stays device-resident between megasteps —
    the facade re-binds it; only ``readback``
    (staging.pack_partitioned_megastep_tail: per-chip stats/round/
    segment counters, integrity partials, convergence partials, and the
    replicated physics tail) is fetched, so a whole megastep is one H2D
    (the move counter) and one D2H (this tail)."""

    position: jax.Array
    dest: jax.Array
    elem: jax.Array
    material_id: jax.Array
    weight: jax.Array
    group: jax.Array
    particle_id: jax.Array
    valid: jax.Array
    alive: jax.Array
    flux: jax.Array
    readback: jax.Array
    prev_even: jax.Array | None = None
    conv_snap: jax.Array | None = None
    conv_sumsq: jax.Array | None = None
    conv_nb: jax.Array | None = None
    conv_mv: jax.Array | None = None


def make_partitioned_megastep(
    device_mesh: Mesh,
    partition: MeshPartition,
    *,
    n_moves: int,
    n_total: int,
    n_groups: int,
    sigma_local: np.ndarray,
    absorb_local: np.ndarray,
    eps_near: float,
    survival_weight: float,
    downscatter: float,
    dtype,
    max_crossings: int = 4096,
    max_rounds: int | None = None,
    exchange_size: int | None = None,
    tolerance: float = 1e-8,
    score_squares: bool = True,
    unroll: int = 1,
    compact_after: int | None = None,
    compact_size: int | None = None,
    compact_stages: tuple | None = None,
    followup_compact_size: int | None = None,
    robust: bool = True,
    tally_scatter: str = "auto",
    integrity: bool = False,
    convergence: bool = False,
    rel_err_target: float = 0.05,
    batch_moves: int = 1,
):
    """Build the jitted partitioned megastep: ``n_moves`` complete
    moves — device re-source (ops/source.py, RNG keyed by (rng_key,
    move, particle id) so sampling never depends on slot layout), the full
    walk+migration+halo-fold pipeline of ``make_partitioned_step``
    (inlined unjitted into the scanned body), and the collision/
    termination physics — as ONE compiled program.

    ``sigma_local``/``absorb_local`` are host [n_parts, max_local]
    per-LOCAL-ELEMENT Σt / absorption rows (the facade derives them
    from the region tables: sigma of a row = sigma of its class), so
    the in-loop region lookup is one sharded gather. ``n_total`` is
    the global particle count (the RNG stream width).

    The alive flag needs no migration payload: dead lanes never walk
    (their move starts done), so they never change slots, and every
    immigrant was by definition walking — post-move,
    ``alive[slot] = True where the slot's pid changed, else its prior
    value``, then the physics update applies.

    Returns ``mega(cur, elem, material_id, weight, group, pid, valid,
    alive, flux, move0, rng_key[, conv_snap, conv_sumsq, conv_nb,
    conv_mv][, prev_even]) -> PartitionedMegastepResult`` with every
    per-particle array [n_parts*cap] sharded over the device axis,
    ``move0`` a device int32 scalar (the facade's ONE H2D per
    megastep), and ``rng_key`` a device PRNG key staged once per seed
    (a runtime input — re-seeding never recompiles). Convergence folds once per fused move — the batch
    cadence counts device moves. ``prev_even`` (a runtime input —
    pass None to disable) threads the sd_mode="batch" per-chip
    snapshot.
    """
    from ..core.tally import accumulate_batch_squares
    from ..obs import IDX
    from .source import apply_physics, sample_move
    from .staging import pack_partitioned_megastep_tail

    n_parts = partition.n_parts
    max_local = partition.max_local
    step = make_partitioned_step(
        device_mesh,
        partition,
        n_groups=n_groups,
        initial=False,
        max_crossings=max_crossings,
        max_rounds=max_rounds,
        exchange_size=exchange_size,
        tolerance=tolerance,
        score_squares=score_squares,
        unroll=unroll,
        compact_after=compact_after,
        compact_size=compact_size,
        compact_stages=compact_stages,
        followup_compact_size=followup_compact_size,
        robust=robust,
        tally_scatter=tally_scatter,
        record_xpoints=None,
        packed_io=False,
        integrity=integrity,
        convergence=convergence,
        rel_err_target=rel_err_target,
        batch_moves=batch_moves,
        _jit=False,
    )
    sharding = NamedSharding(device_mesh, P(AXIS))
    sigma_dev = jax.device_put(
        jnp.asarray(np.asarray(sigma_local, np.float64).reshape(-1),
                    dtype),
        sharding,
    )
    absorb_dev = jax.device_put(
        jnp.asarray(np.asarray(absorb_local, np.float64).reshape(-1),
                    dtype),
        sharding,
    )
    conv_on = (
        jax.device_put(jnp.ones(n_parts, jnp.int32), sharding)
        if convergence
        else None
    )
    tiny = float(np.finfo(np.dtype(dtype)).tiny)
    nseg_dtype = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32

    def mega_impl(cur, elem, material_id, weight, group, pid, valid,
                  alive, flux, move0, rng_key, conv_snap=None,
                  conv_sumsq=None, conv_nb=None, conv_mv=None,
                  prev_even=None):
        N = cur.shape[0]
        cap = N // n_parts
        chip_base = (jnp.arange(N, dtype=jnp.int32) // cap) * max_local
        base_key = rng_key

        def body(k, carry):
            (cur, dest, elem, mat, weight, group, pid, valid, alive,
             flux, conv, prev_even, sacc, iacc, cvec, pacc, rounds,
             dropped, nseg) = carry
            m = move0 + k
            sig = sigma_dev[
                chip_base + jnp.clip(elem, 0, max_local - 1)
            ]
            direction, ell, coll_u, roul_u = sample_move(
                base_key, m, pid, n_total, cur.dtype
            )
            flight = direction * (ell / jnp.maximum(sig, tiny))[:, None]
            go = valid & alive
            dest = jnp.where(go[:, None], cur + flight, cur)
            res = step(
                cur, dest, elem, ~go, mat, weight, group, pid, valid,
                flux,
                (conv + (conv_on,)) if conv is not None else None,
            )
            # Dead lanes never walk, so they never change slots; every
            # immigrant was walking — a changed pid means alive.
            alive_w = res.valid & jnp.where(
                res.particle_id != pid, True, alive
            )
            ab = absorb_dev[
                chip_base + jnp.clip(res.elem, 0, max_local - 1)
            ]
            weight2, group2, alive2, phys4 = apply_physics(
                res.position, res.dest, res.done, res.material_id,
                res.weight, res.group, alive_w, ab, coll_u, roul_u,
                eps_near=eps_near,
                survival_weight=survival_weight,
                downscatter=downscatter,
                n_groups=n_groups,
            )
            flux = res.flux
            if prev_even is not None:
                flux, prev_even = accumulate_batch_squares(
                    flux, prev_even
                )
            # Per-megastep reductions of the per-chip tails: sums
            # everywhere, max of max_crossings, truncated summed over
            # the fused moves (walk.py merge_megastep_stats semantics).
            s2 = add_walk_stats(sacc, res.stats)
            sacc = s2.at[:, IDX["max_crossings"]].set(
                jnp.maximum(
                    sacc[:, IDX["max_crossings"]],
                    res.stats[:, IDX["max_crossings"]],
                )
            )
            if iacc is not None:
                # PART_INTEGRITY_FIELDS: bad_flux reflects the final
                # accumulator; the slot counts add across moves.
                iacc = jnp.concatenate(
                    [
                        res.integrity[:, :1],
                        iacc[:, 1:] + res.integrity[:, 1:],
                    ],
                    axis=1,
                )
            if cvec is not None:
                cvec = res.convergence
                conv = (res.conv_snap, res.conv_sumsq, res.conv_nb,
                        res.conv_mv)
            n_trunc = jnp.sum(alive_w & ~res.done).astype(cur.dtype)
            pacc = jnp.concatenate(
                [
                    pacc[:4] + phys4,
                    jnp.sum(alive2).astype(cur.dtype)[None],
                    pacc[5:6] + n_trunc[None],
                ]
            )
            return (res.position, res.dest, res.elem, res.material_id,
                    weight2, group2, res.particle_id, res.valid, alive2,
                    flux, conv, prev_even, sacc, iacc, cvec, pacc,
                    rounds + res.n_rounds, dropped + res.n_dropped,
                    nseg + res.n_segments)

        conv0 = (
            (conv_snap, conv_sumsq, conv_nb, conv_mv)
            if convergence
            else None
        )
        from ..integrity.invariants import PART_INTEGRITY_LEN
        from ..obs import WALK_STATS_LEN
        from .source import MEGA_PHYS_LEN

        sacc0 = jnp.zeros((n_parts, WALK_STATS_LEN), nseg_dtype)
        iacc0 = (
            jnp.zeros((n_parts, PART_INTEGRITY_LEN), nseg_dtype)
            if integrity else None
        )
        cvec0 = None
        if convergence:
            from ..obs.convergence import CONV_LEN

            cvec0 = jnp.zeros((n_parts, CONV_LEN), cur.dtype)
        pacc0 = jnp.zeros(MEGA_PHYS_LEN, cur.dtype)
        zero_pc = jnp.zeros(n_parts, nseg_dtype)
        carry = (cur, cur, elem, material_id, weight, group, pid, valid,
                 alive.astype(bool), flux, conv0, prev_even, sacc0,
                 iacc0, cvec0, pacc0, zero_pc, zero_pc, zero_pc)
        (cur, dest, elem, mat, weight, group, pid, valid, alive, flux,
         conv, prev_even, sacc, iacc, cvec, pacc, rounds, dropped,
         nseg) = jax.lax.fori_loop(0, n_moves, body, carry)
        readback = pack_partitioned_megastep_tail(
            sacc, rounds, dropped, nseg, iacc, cvec, pacc, dtype
        )
        cs, css, cnb, cmv = conv if conv is not None else (None,) * 4
        return PartitionedMegastepResult(
            position=cur,
            dest=dest,
            elem=elem,
            material_id=mat,
            weight=weight,
            group=group,
            particle_id=pid,
            valid=valid,
            alive=alive,
            flux=flux,
            readback=readback,
            prev_even=prev_even,
            conv_snap=cs,
            conv_sumsq=css,
            conv_nb=cnb,
            conv_mv=cmv,
        )

    return jax.jit(
        mega_impl,
        # Donation matches the per-move partitioned step exactly: the
        # flux / convergence / batch-sd slabs are donated, the per-slot
        # state is NOT — after a checkpoint restore those arrays can
        # zero-copy-alias the snapshot's host buffers on the CPU
        # backend, and a donated alias would let XLA scribble over the
        # retry anchor (ops/walk.py megastep has the same contract).
        donate_argnames=("flux", "conv_snap", "conv_sumsq", "prev_even"),
    )


# --------------------------------------------------------------------------- #
# Host-side helpers for placing particles onto their owner chips.
# --------------------------------------------------------------------------- #
def distribute_particles(
    partition: MeshPartition,
    device_mesh: Mesh,
    global_elem: np.ndarray,
    fields: dict,
    cap: int | None = None,
):
    """Scatter host particle arrays into per-chip slot layout.

    Args:
      global_elem: [n] global parent element per particle.
      fields: name → [n, ...] host array (must include 'origin' and 'dest';
        'weight', 'group', 'material_id' optional).
      cap: slots per chip (default: total particle count, the no-drop-safe
        capacity; use smaller to trade memory when migration is bounded).

    Returns (arrays dict with [n_parts*cap] leading axis, valid, pid) as
    device arrays sharded over the device axis.
    """
    import jax.numpy as jnp

    n = int(np.asarray(global_elem).shape[0])
    n_parts = partition.n_parts
    cap = int(cap) if cap is not None else n
    owner = partition.owner[np.asarray(global_elem)].astype(np.int64)
    counts = np.bincount(owner, minlength=n_parts)
    if counts.max(initial=0) > cap:
        raise ValueError(
            f"chip {int(counts.argmax())} needs {int(counts.max())} slots at "
            f"seed time but cap={cap}"
        )
    order = np.argsort(owner, kind="stable")
    start = np.searchsorted(owner[order], np.arange(n_parts))
    rank_in_part = np.arange(n, dtype=np.int64) - start[owner[order]]
    slot_of = np.empty(n, np.int64)
    slot_of[order] = owner[order] * cap + rank_in_part

    sharding = NamedSharding(device_mesh, P(AXIS))
    out = {}
    for name, arr in fields.items():
        arr = np.asarray(arr)
        buf = np.zeros((n_parts * cap,) + arr.shape[1:], arr.dtype)
        buf[slot_of] = arr
        out[name] = jax.device_put(jnp.asarray(buf), sharding)
    valid = np.zeros(n_parts * cap, bool)
    valid[slot_of] = True
    pid = np.full(n_parts * cap, -1, np.int32)
    pid[slot_of] = np.arange(n, dtype=np.int32)
    elem_local = np.zeros(n_parts * cap, np.int32)
    elem_local[slot_of] = partition.global2local[np.asarray(global_elem)]
    out["valid"] = jax.device_put(jnp.asarray(valid), sharding)
    out["particle_id"] = jax.device_put(jnp.asarray(pid), sharding)
    out["elem"] = jax.device_put(jnp.asarray(elem_local), sharding)
    return out


def collect_by_particle_id(
    result: PartitionedTraceResult,
    n: int,
    partition: MeshPartition | None = None,
) -> dict:
    """Gather per-particle outputs back into host pid order.

    ``elem`` is the particle's local row on the chip HOLDING it — with a
    halo a finished particle can rest as a guest in a buffered element.
    Pass ``partition`` to additionally get ``elem_global`` (resolved via
    each holding chip's local2global), the id a host driver needs to
    re-seed the next move.
    """
    pid = np.asarray(result.particle_id)
    valid = np.asarray(result.valid)
    sel = valid & (pid >= 0)
    idx = pid[sel]
    out = {}
    names = ["position", "material_id", "done", "elem", "weight",
             "group", "track_length"]
    if result.xpoints is not None:
        names += ["xpoints", "n_xpoints"]
    for name in names:
        arr = np.asarray(getattr(result, name))
        buf = np.zeros((n,) + arr.shape[1:], arr.dtype)
        buf[idx] = arr[sel]
        out[name] = buf
    if partition is not None:
        cap = pid.shape[0] // partition.n_parts
        chip = (np.arange(pid.shape[0]) // cap)[sel]
        eg = partition.local2global[
            chip, np.asarray(result.elem)[sel]
        ]
        buf = np.full(n, -1, np.int64)
        buf[idx] = eg
        out["elem_global"] = buf
    return out
