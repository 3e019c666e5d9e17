"""The fused tracer step: advance every particle to its destination,
scoring track-length flux along the way.

This is the TPU-native replacement for the reference's hot loop — the
Pumi-PIC ``ParticleTracer::search`` plus the per-crossing callback functor
``PumiParticleAtElemBoundary::operator()`` (pumipic_particle_data_structure
.cpp:537-555). Where the reference dispatches a functor at every element
boundary (evaluateFlux cpp:589-646 → updatePrevXPoint cpp:561-570 →
apply_boundary_condition cpp:452-515 → move_to_next_element cpp:440-450),
here the whole per-crossing sequence is fused into the body of one
``lax.while_loop`` over SPMD particle lanes: no callback indirection, no
host round-trips, one compiled XLA computation per (mesh, flags) signature.

Per-crossing semantics reproduced exactly:
  * segment scored into flux[elem, group, 0] (+= w·len) and [.., 1]
    (+= (w·len)^2) for in-flight, not-yet-done particles — and never during
    the *initial* location search (initial_ flag, cpp:547-550);
  * destination-reached (no exit face before t=1) → done, final position =
    destination;
  * domain-boundary hit (no neighbor across exit face) → done, destination
    clipped to the intersection point, material_id = -1 (cpp:480-482, 500-510);
  * geometry/material boundary (class_id differs across the face,
    cpp:473-479) → done, destination clipped, material_id = class_id of the
    far element, and — matching move_to_next_element, which hops regardless
    of the done flag (cpp:445) — the parent element advances to that far
    element;
  * particles whose in-flight flag is 0 are immediately done and untouched.

Atomics disappear: the per-crossing tally writes become one XLA scatter-add
over the particle axis per iteration (duplicate indices accumulate), and
race-freedom is by construction.

Kernel backends: this module is the XLA walk — the default and the only
backend that covers every mesh size and feature surface. The walk is
random-gather/-scatter bound (mesh tables indexed by data-dependent
element ids), and Mosaic on TPU has no vectorized random-gather lowering
(jnp.take / advanced indexing fail to lower inside a kernel —
scripts/probe_pallas_gather.py records the probes), so a Pallas port of
THIS body is off the table. What does lower is the one-hot-matmul form:
for meshes whose decoded walk table fits VMEM, ops/walk_pallas.py
recasts the gather as a blocked ``onehot(elem) @ table`` MXU contraction
and the per-crossing tally scatter-add as a ``onehot(elem)^T @ values``
outer-product into a tile-local accumulator flushed to HBM once per
launch — the Matrix-PIC / POLAR-PIC move (PAPERS.md), selected by
``TallyConfig(kernel="pallas"|"auto")`` and bit-identical to this body
(tests/test_kernel_pallas.py). Its regime is the small/medium mesh where
per-crossing HBM gather latency dominates; above the VMEM tile budget
(``PUMI_TPU_PALLAS_VMEM_MB``, ~16 MB/core physical) ``kernel="auto"``
falls back HERE, which is why the scattered XLA body below remains the
production path for 1M-tet meshes (~80 MB of walk tables).

Gather budget (round 3). In-loop TPU gather/scatter cost is linear in
rows (~9-11 ns/row) with width nearly free up to ~24 f32 columns
(scripts/microbench_costmodel2.py; BENCHMARKS.md round 3), so the
walk does exactly ONE gather per crossing when the mesh carries the
packed ``geo20`` table: a 20-wide row holding face normals, plane
offsets, AND the four per-face topology codes bitcast into the float
dtype (neighbor + material-boundary bit + neighbor class index, decoded
by bit masks after the exit face is known). This replaces round 2's
geo16 + topo_flat pair (two gathers) and round 1's four separate
gathers. Material ids are resolved from class *indices* with one
tiny-table gather after the loop, never per crossing.

Tally scatter: the (c, c²) pair goes into the flux viewed flat as
[ntet*n_groups*2] via a static strategy knob (``tally_scatter``):
"pair" issues two scalar scatter-adds, "interleaved" one 2m-row scatter
with keys 2k/2k+1. The round-4 hardware A/B settled the backend split:
interleaved wins on TPU (7.41 vs 7.27 Mseg/s in the real body,
consistent with the in-loop microbench's −11% scatter cost), pair wins
on CPU (the concatenate costs up to 5× there) — so the default is
"auto": interleaved on TPU, pair elsewhere, resolved at trace time.
Both are bit-identical (disjoint slots) and 3.6× cheaper than a 2-wide window
scatter; complex64 packing is unimplemented on this TPU backend
(scripts/microbench_complex_scatter.py).

Degeneracy robustness
---------------------
Grazing rays on irregular meshes hit three numerical failure modes that
per-thread CUDA walkers usually paper over with ad-hoc epsilons (and the
reference's tracer reports as "Not all particles are found",
cpp:765-768). This walk handles them structurally, at ~zero hot-path
cost (all elementwise, no extra gathers):

  * entry-face mask — a straight ray can never re-enter a convex element
    it exited, so the face leading back to `prev` is excluded from exit
    candidates (kills A↔B t=0 ping-pong where the two elements' rounded
    planes disagree about a near-parallel ray), with a fallback when the
    mask would strand the lane (exit_face);
  * relocation chase — when an element stops containing its particle
    (corner mis-hop) for 4 consecutive zero-progress crossings, the lane
    switches to a stochastic visibility walk toward the point
    (chase_face_choice), scoring and recording nothing, until
    containment is restored;
  * forward bump — continuing lanes always advance by >= ~32 ulps of
    the coordinate, so crack/edge t=0 stalls end; a bump that would
    pass the destination lands on it instead (forward_bump, with
    end_of_flight the one end-of-flight rule of every walk body).

Meshes with genuinely overlapping elements are impossible to walk and
are rejected at build time (mesh/core.py:_check_not_tangled).

Straggler compaction
--------------------
Crossing counts are long-tailed (a few particles cross 10x more elements
than the mean), and a flat SPMD while_loop runs *every* lane until the very
last particle finishes — the batch-level cost of the data-dependent walk
lengths called out in SURVEY.md §7 (hard part 1). With
``compact_after``/``compact_size`` set, the walk runs in two phases:

  1. the full batch advances for ``compact_after`` crossings (finishing the
     bulk of particles),
  2. the still-active stragglers are compacted to the front (a cumsum
     stable partition of the done mask — one n-row scatter, far cheaper
     than a sort) into a ``compact_size``-lane subset which loops to
     completion; an outer while_loop repeats the compaction while any
     particle remains active, so correctness never depends on the tail
     fitting in one subset.

Semantics (and the scored flux) are identical to the flat loop; only the
lane scheduling changes.

``compact_stages="fit"`` (the facades' default, utils/ladder.py) decides
stage boundaries from what the walk observes instead of crossing counts
fixed before it starts. The widths are static — n, n/8, n/64, ... down
to about 1,024 lanes (``fit_widths``) — and a stage loops until its live
lanes fit the next width (checked between unrolled chunks, as the flat
loop checks ``all(done)``); the last loops until every lane is done.
Every live lane therefore rides every stage: no lane waits, no stage
repeats, and each stage costs O(its width) to enter (scatter the last
stage back, ``first_k_active`` over its own done flags composed with its
index into the batch, gather). The iteration counter runs on through
the stages, so each lane walks exactly the flat loop's iterations
(``max_crossings`` included); only the scatter's summation order
differs. A short walk leaves the full width after a few chunks; a long
one (the batch-start search) stays wide while most lanes are live.
The narrower stages unroll at most twice (``fit_stage_unroll``): each
unrolled copy of the walk body is compiled code, which a TPU holds in
device memory, and a narrow stage's iterations are cheap.
"""
from __future__ import annotations

import functools
import inspect
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.ladder import FIT, fit_stage_unroll, fit_widths
from .geometry import exit_face

def first_k_active(active: jax.Array, k: int):
    """Indices of the first ``k`` active lanes, via a cumsum stable
    partition (one n-row scatter — far cheaper than argsort on TPU).

    Shared by the single-chip and partitioned walks' straggler
    compaction. Returns ``(idx[k], n_active)``; slots past ``n_active``
    gather lane 0's garbage, which callers neutralize with an
    ``arange(k) < n_active`` validity mask.
    """
    n = active.shape[0]
    n_active = jnp.sum(active.astype(jnp.int32))
    pos = jnp.cumsum(active.astype(jnp.int32)) - 1
    dst = jnp.where(active, pos, n)
    idx = (
        jnp.zeros(n, jnp.int32)
        .at[dst]
        .set(jnp.arange(n, dtype=jnp.int32), mode="drop")[:k]
    )
    return idx, n_active


def record_crossing(xp, kx, xpoint, real_cross):
    """Record one boundary-crossing point for every ``real_cross`` lane:
    non-crossing lanes row-index out of bounds (dropped), lanes past K
    recorded crossings column-index out of bounds (dropped; the count
    keeps incrementing so callers can detect truncation). Shared by the
    single-chip and partitioned walk bodies so the recording semantics
    cannot drift apart."""
    rows = jnp.where(
        real_cross, jnp.arange(xp.shape[0], dtype=jnp.int32),
        jnp.int32(xp.shape[0]),
    )
    xp = xp.at[rows, kx].set(xpoint, mode="drop")
    kx = kx + real_cross.astype(kx.dtype)
    return xp, kx


def chase_face_choice(sd, elem, it, dtype, interior):
    """Stochastic visibility-walk face choice for the relocation chase,
    shared by the single-chip and partitioned walk bodies.

    Picks the face the point violates most, scaled by pseudo-random
    per-face weights derived from (elem, iteration) so deterministic
    hop cycles break. Boundary faces are excluded while any interior
    candidate exists — a mislocated but in-domain particle must not be
    terminated as a domain exit by a chase hop (boundary planes extend
    infinitely, so an interior point can violate one numerically).
    """
    h = elem * jnp.int32(-1640531527) + it * jnp.int32(40503)
    wf = 1.0 + (
        (jnp.right_shift(h[:, None], 2 * jnp.arange(4)) & 3)
    ).astype(dtype) * 0.125
    big = jnp.asarray(jnp.finfo(dtype).max, dtype)
    any_interior = jnp.any(interior, axis=-1, keepdims=True)
    score = jnp.where(interior | ~any_interior, sd * wf, -big)
    return jnp.argmax(score, axis=-1).astype(jnp.int32)


def normalize_compact_stages(
    compact_stages, compact_after, compact_size, n, size_floor
):
    """Fold the single-stage knobs into a one-entry schedule and validate.

    Shared by the single-chip and partitioned walks: entries are
    ``(start, size)`` or ``(start, size, unroll)`` with strictly
    increasing starts; ``size_floor`` is the default subset size when
    only ``compact_after`` is given. Returns the normalized schedule (or
    None when compaction is off)."""
    if compact_stages is None and compact_after is not None:
        compact_stages = (
            (
                compact_after,
                compact_size if compact_size is not None else size_floor,
            ),
        )
    if compact_stages is not None:
        if len(compact_stages) == 0:
            raise ValueError(
                "compact_stages must be None or a non-empty schedule"
            )
        for st in compact_stages:
            if len(st) not in (2, 3):
                raise ValueError(
                    "compact_stages entries must be (start, size) or "
                    f"(start, size, unroll): {st!r}"
                )
        starts = [st[0] for st in compact_stages]
        if starts != sorted(set(starts)):
            raise ValueError(
                f"compact_stages starts must be strictly increasing: {starts}"
            )
        for st in compact_stages:
            if st[1] < 1 or (len(st) == 3 and st[2] < 1):
                raise ValueError(
                    f"compact_stages size/unroll must be >= 1: {st!r}"
                )
        # Measured cliff guard (round-4 hardware grid, BENCHMARKS.md
        # "Schedule sweep"): per-stage unroll >= 16 was perf-neutral on
        # the 7-stage dense ladder (7.62 vs 7.60 Mseg/s) but CATASTROPHIC
        # on a sparse 5-stage schedule (0.21 Mseg/s — ~35x slower, 381 s
        # compile). The mechanism is uncharacterized, so the safe rule is
        # the measured one: large per-stage unrolls only on dense-ladder-
        # shaped schedules (>= 6 stages).
        big_u = [st for st in compact_stages if len(st) == 3 and st[2] >= 16]
        if big_u and len(compact_stages) < 6:
            import warnings

            warnings.warn(
                f"compact_stages: per-stage unroll >= 16 on a sparse "
                f"{len(compact_stages)}-stage schedule measured ~35x "
                f"slower on TPU (0.21 vs 7.6 Mseg/s, round-4 grid; "
                f"BENCHMARKS.md 'Schedule sweep'); large unrolls are "
                f"only known-safe on the dense ladder (>= 6 stages). "
                f"Offending stages: {big_u}",
                RuntimeWarning,
                stacklevel=2,
            )
    return compact_stages


def walk_stats_vector(ncross_l, nchase_l, done, occ0, occ1, nseg, it,
                      slots, rounds, bumps):
    """Reduce the per-lane telemetry counters to the [12] per-move stats
    vector (obs/walk_stats.py WALK_STATS_FIELDS order — drift breaks
    tests/test_obs.py). ONE definition shared by the XLA walk body and
    the Pallas kernel path (ops/walk_pallas.py), so the schema cannot
    fork between backends. ``slots`` is the float32 lane-slot count,
    ``rounds`` the compaction rounds that ran, ``bumps`` the forward
    bumps (``forward_bump``). ``grid_seeded`` is left 0: the search's
    seeding runs before the walk (``count_seeded``)."""
    sd_t = nseg.dtype
    return jnp.stack([
        jnp.sum(ncross_l).astype(sd_t),
        jnp.max(ncross_l).astype(sd_t),
        jnp.sum(nchase_l).astype(sd_t),
        jnp.sum(jnp.logical_not(done)).astype(sd_t),
        occ0.astype(sd_t),
        occ1.astype(sd_t),
        nseg,
        it.astype(sd_t),
        lane_slots_word(slots, sd_t),
        rounds.astype(sd_t),
        bumps.astype(sd_t),
        nseg * 0,
    ])


def lane_slots_word(slots, dtype):
    """A float32 lane-slot count as its bits in an integer word of
    ``dtype`` (obs/walk_stats.py: the field's carrier)."""
    return jax.lax.bitcast_convert_type(
        slots.astype(jnp.float32), jnp.int32
    ).astype(dtype)


def _lane_slots_f32(word):
    return jax.lax.bitcast_convert_type(word.astype(jnp.int32), jnp.float32)


def add_walk_stats(a, b):
    """``a + b`` for stats vectors (or [..., LEN] per-chip matrices):
    the integer fields add, ``lane_slots`` adds as the float32 its bits
    hold. Callers override the fields that do not sum."""
    from ..obs.walk_stats import LANE_SLOTS

    slots = _lane_slots_f32(a[..., LANE_SLOTS]) + _lane_slots_f32(
        b[..., LANE_SLOTS]
    )
    out = a + b
    return out.at[..., LANE_SLOTS].set(lane_slots_word(slots, out.dtype))


def integrity_vector(
    in_flight, done, weight, pseg, cur, origin, flux, dtype, initial
):
    """End-of-walk conservation-invariant reductions → the
    [INTEGRITY_LEN] vector (integrity/invariants.py field order).
    Completed, walked lanes only: a truncated lane legitimately holds a
    partial ledger (the escalation re-walk's merge keeps the sums
    consistent across attempts — see _merge_rewalk). Shared by the XLA
    and Pallas walk paths; ``flux`` is the FLAT accumulator."""
    comp = in_flight & done
    zero = jnp.sum(weight) * 0  # device-varying scalar zero
    if initial:
        # The location search scores nothing; the conservation
        # triple is identically zero by construction.
        scored = path = resid = zero
    else:
        dist = jnp.linalg.norm(cur - origin, axis=-1)
        scored = jnp.sum(jnp.where(comp, weight * pseg, 0.0))
        path = jnp.sum(jnp.where(comp, weight * dist, 0.0))
        resid = jnp.max(jnp.where(comp, jnp.abs(pseg - dist), 0.0))
    bad_flux = jnp.sum(
        jnp.logical_not(jnp.isfinite(flux)) | (flux < 0.0)
    )
    return jnp.stack([
        scored.astype(dtype),
        path.astype(dtype),
        resid.astype(dtype),
        bad_flux.astype(dtype),
        jnp.sum(in_flight).astype(dtype),
        jnp.sum(comp).astype(dtype),
    ])


def bump_length(tol_floor, point, xp=jnp):
    """The forward bump's geometric length at ``point`` ([..., 3]): 32
    ulps of the coordinate, 4·tol_floor·(1 + max|point|)."""
    return 4.0 * tol_floor * (1.0 + xp.max(xp.abs(point), axis=-1))


def end_of_flight(t_exit, has_exit, dnorm, tolerance, tol_floor, xp=jnp):
    """The reached test: ONE definition for every walk body (this
    module, ops/walk_pallas.py, ops/walk_partitioned.py) and the host
    audit's float64 mirror (integrity/audit.py, ``xp=numpy``). A lane
    reaches its destination when no exit face lies before it
    (``t_exit >= 1 - tol_eff``, or no exit at all: a zero-length ray
    has none). Returns ``(reached, t_step)``."""
    tol_eff = xp.maximum(
        tolerance / xp.where(dnorm > 0, dnorm, 1.0), tol_floor
    )
    reached = (t_exit >= 1.0 - tol_eff) | ~has_exit
    return reached, xp.minimum(t_exit, 1.0)


def forward_bump(stuck, contained, continuing, t_step, tol_floor, cur,
                 dirv, dest, dnorm):
    """Forward bump for zero-progress crossings, shared by every walk
    body: a continuing lane (crossed, hopped, not stopped) advances at
    least ``bump_length`` (32 ulps of the coordinate, taken at the
    crossing point ``cur``) along its ray per crossing, so crack/edge
    t≈0 stalls end after at most |ray| / bump crossings. A bump that
    would carry the lane past its destination (t > 1) lands it exactly
    on ``dest`` instead; the next iteration's ray has zero length and
    the lane reaches (``end_of_flight``) in the element it hopped into.
    Without that cap a lane a few ulps from a destination on an edge
    many tets share (every tet there reports an exit at t≈0) is bumped
    past it, the next ray points back, and it oscillates to
    ``max_crossings``. The crossing itself, its hop, material stop and
    domain exit are untouched: only the bump is capped.

    ``stuck`` counts consecutive zero-progress crossings in a
    non-containing element (the relocation chase starts at 4). It
    resets as soon as the lane is genuinely contained or makes a real
    step; lanes that did not continue this iteration (done, reached, or
    frozen for migration) keep their count — the partitioned exchange
    reads stuck>=4 to know a lane froze mid-chase and must not carry an
    entry-face mask across the cut (the convexity argument covers real
    crossings only). Returns ``(cur_next, stuck_next, bumped)``:
    ``bumped`` marks the crossings that advanced by the bump, landings
    on ``dest`` included, and not by their own exit."""
    nudge0 = bump_length(tol_floor, cur) / jnp.where(dnorm > 0, dnorm, 1.0)
    bumped = continuing & (t_step < nudge0)
    stuck_next = jnp.where(
        bumped & ~contained,
        stuck + 1,
        jnp.where(continuing, jnp.int32(0), stuck),
    )
    # int32 columns: Mosaic cannot relayout an i1 vector to a column.
    landed = (bumped & (nudge0 >= 1.0)).astype(jnp.int32)[:, None] != 0
    moved = bumped.astype(jnp.int32)[:, None] != 0
    extra = jnp.where(bumped, nudge0 - t_step, 0.0)
    cur_next = jnp.where(
        landed, dest, jnp.where(moved, cur + extra[:, None] * dirv, cur)
    )
    return cur_next, stuck_next, bumped


class TraceResult(NamedTuple):
    """Outputs of one fused trace step.

    position: [n,3] final particle positions (destination, possibly clipped
      to a domain/material boundary) — the reference returns these to the
      host via copy_last_location (cpp:266-280).
    elem: [n] parent element after the walk.
    material_id: [n] updated material ids (copy_material_ids, cpp:282-294).
    flux: [ntet, n_groups, 2] accumulated (Σ w·len, Σ (w·len)^2).
    n_segments: scalar count of scored particle-segments (benchmark metric).
    n_crossings: scalar count of while-loop iterations executed.
    done: [n] bool — False where the walk was truncated by max_crossings
      (the analog of the reference's "Not all particles are found" error,
      cpp:765-768, but reported per particle instead of printed).
    xpoints: [n, K, 3] per-particle boundary-crossing points, only when
      record_xpoints=K was requested (tracer getIntersectionPoints()
      parity, reference test_pumi_tally_impl_methods.cpp:403-479);
      None otherwise — the hot path pays nothing.
    n_xpoints: [n] recorded-crossing count per particle (may exceed K,
      in which case only the first K points were kept), or None.
    track_length: [n] per-particle scored track length (Σ segment
      lengths, unweighted) — the analog used for the reference's
      cpp:618-629 consistency check, kept as a running in-walk ledger.
      NOT byte-identical to ``total_tracklength_``
      (compute_total_tracklength, cpp:721-736), which stores
      |dest − orig| of the *requested* move computed before the search:
      for particles clipped at material stops or domain exits the scored
      sum here is shorter than that pre-walk distance. Doubles as the
      conservation invariant: equals
      |position − origin| to fp accumulation (asserted under
      debug_checks, the reference's cpp:618-629 consistency print);
      zeros on initial-search traces (nothing is scored).
    stats: [12] per-move telemetry vector in the field order of
      obs/walk_stats.py WALK_STATS_FIELDS — (real crossings, max real
      crossings per particle, chase hops, truncated walks, compaction
      occupancy numerator/denominator, segments, loop iterations,
      lane-slots as float32 bits, compaction rounds, bumps, lanes the
      search started from a grid seed) —
      computed inside the jitted program so ONE scalar-vector readback
      per move carries the whole flight-recorder record (the facade's
      old per-move host scan of ``done`` goes away). None with
      stats=False.
    """

    position: jax.Array
    elem: jax.Array
    material_id: jax.Array
    flux: jax.Array
    n_segments: jax.Array
    n_crossings: jax.Array
    done: jax.Array
    xpoints: jax.Array | None = None
    n_xpoints: jax.Array | None = None
    track_length: jax.Array | None = None
    stats: jax.Array | None = None
    # [INTEGRITY_LEN] on-device conservation-invariant vector
    # (integrity/invariants.py schema: weighted scored-vs-path sums,
    # max per-lane residual, bad-flux count, lane counts), computed
    # inside the jitted program with integrity=True — a couple of
    # reductions over arrays the walk already holds, zero extra
    # dispatches or transfers (the packed pipeline appends it to the
    # readback tail). None with integrity=False.
    integrity: jax.Array | None = None
    # [CONV_LEN] convergence summary vector (obs/convergence.py
    # CONV_FIELDS: batches, scored bins, Σ/max rel-err, converged bins),
    # computed from the batch accumulators passed as ``conv_state`` —
    # the statistical-convergence analog of the integrity tail, riding
    # the same packed readback at zero extra transfers. None unless
    # conv_state was supplied.
    convergence: jax.Array | None = None
    # Updated (snapshot, Σbatch², n_batches, move counter) batch
    # accumulators (donated through; the facade re-binds them each
    # move). None unless conv_state was supplied.
    conv_state: tuple | None = None


def resolve_tally_scatter(
    tally_scatter: str, array=None, platform: str | None = None
) -> str:
    """Resolve the 'auto' tally-scatter strategy to a concrete one.

    'auto' picks by the backend that will actually run the walk: the
    platform of ``array``'s committed device when one is available
    (e.g. the flux accumulator), else ``jax.default_backend()``.
    Resolution must happen OUTSIDE jit — the knob is a static trace
    key, so resolving the literal string 'auto' inside the traced
    function would freeze the first call's backend decision into every
    later cache hit, and would mispick when arrays are explicitly
    placed off the default backend. Both strategies are bit-identical;
    the choice is perf-only (round-4 hardware A/B: interleaved on TPU,
    pair on CPU — BENCHMARKS.md).
    """
    if tally_scatter != "auto":
        return tally_scatter
    if platform is None and array is not None:
        devices = getattr(array, "devices", None)
        if callable(devices):
            try:
                platform = next(iter(devices())).platform
            except Exception:  # tracer / uncommitted / numpy input
                platform = None
    if platform is None:
        platform = jax.default_backend()
    return "interleaved" if platform == "tpu" else "pair"


def trace_impl(
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
    kernel: str = "xla",
    lane_block: int | None = None,
    seed_grid=None,
) -> TraceResult:
    """Advance all particles from origin to dest through the mesh.

    Args:
      mesh: TetMesh pytree.
      origin, dest: [n,3] ray endpoints (device dtype of the mesh).
      elem: [n] int32 current parent elements.
      in_flight: [n] bool/int — particles with 0 are parked: not walked,
        not scored, position reported as their origin.
      weight, group: [n] statistical weight and energy-group index.
      material_id: [n] int32, updated on material-boundary stops.
      flux: tally accumulator (donated). Either [ntet, n_groups, 2] or
        FLAT [ntet*n_groups*2] (stride-2 (Σc, Σc²) pairs; requires the
        explicit ``n_groups`` kwarg). Flat is the TPU production layout:
        a trailing dim of 2 pads 64× under the (8,128) tile (make_flux
        docstring); the result's flux keeps the caller's shape.
      initial: when True this is the parent-element *location* search —
        nothing is tallied and material/class boundaries do not stop the
        particle (cpp:472's !initial guard); only the domain boundary clips.
      max_crossings: static bound on boundary crossings; the loop exits as
        soon as every particle is done.
      tolerance: GEOMETRIC tolerance (reference walk tol 1e-8, cpp:123,206):
        a destination within this distance of the exit face counts as
        inside the current element. Converted to ray-parameter space per
        particle per crossing as ``tolerance / |dest - cur|`` (plane
        normals are unit, so ray-parameter × |ray| = geometric distance),
        then floored at ``8·eps(dtype)`` so the comparison
        ``t_exit >= 1 - tol`` cannot round to a no-op in float32 (under
        f32, ``1 - 1e-8 == 1`` exactly; the floor makes the effective
        tolerance a few ulps of the ray length instead of zero).
      compact_after: if set, crossings after this many full-batch iterations
        run on compacted straggler subsets (see module docstring).
      compact_size: lane count of the straggler subsets (default n // 8).
      compact_stages: generalizes the two knobs above to a schedule:
        ((start_crossing, subset_size), ...) with strictly increasing
        starts. Each intermediate stage runs ONE compaction round of its
        width until the next stage's start; the final stage loops rounds
        to completion (identical semantics to compact_after/compact_size,
        which are sugar for a single stage). Lanes that don't fit a
        stage's width simply wait for a later stage — the final stage
        guarantees completion. A stage entry may carry an optional third
        element ``(start, size, unroll)`` overriding the walk unroll for
        that stage — narrow tail stages are while-iteration-bound, so
        they often want a larger factor than the full-width phase.
        The string ``"fit"`` selects the fit ladder instead (module
        docstring): stages left when their live lanes fit the next
        width, for any walk length.
      unroll: crossings advanced per while-loop iteration. The body is a
        no-op for already-done lanes, so semantics are unchanged; unrolling
        amortizes the per-iteration dispatch overhead of a TPU while_loop
        (the measured cost driver — the loop is launch-bound, not
        bandwidth-bound) at the price of at most ``unroll - 1`` wasted
        body evaluations at the tail.
      robust: enable the degeneracy-recovery machinery (entry-face mask,
        relocation chase, forward bump — module docstring "Degeneracy
        robustness"). With False the walk has exactly the reference
        tracer's semantics: a lane a numerical degeneracy traps never
        repairs, it just fails to finish within max_crossings and is
        reported per-particle via ``done`` (the reference's "Not all
        particles are found" printf, cpp:765-768, as data instead of a
        message). On clean meshes results are identical; keep the
        default True except for A/B cost attribution or strict
        reference-parity runs.
      tally_scatter: per-crossing (Σc, Σc²) accumulation strategy.
        "pair" issues two m-row scalar scatters; "interleaved"
        concatenates both rows into ONE 2m-row scatter (c at flat slot
        2k, c² at 2k+1); "auto" (default) picks interleaved on TPU and
        pair elsewhere, per the round-4 hardware A/B. Numerically
        identical (disjoint slots). The strategies trade a concatenate
        for a second scatter dispatch and
        measure differently per backend (module docstring "Tally
        scatter") — keep both benchable; ignored when
        score_squares=False.
      gathers: packed-body table-read strategy. "merged" (default) reads
        the whole geo20 row in one 20-wide gather; "split" reads the
        geometry [.. :16] and bitcast topology [16:20] columns as two
        narrower gathers (the round-2 two-gather pattern, expressed as
        gathers from slices of the same table). Ignored by the unpacked
        fallback body.
      ledger: accumulate the per-particle scored track length
        (TraceResult.track_length — one elementwise select+add per
        crossing plus one [S] lane in compaction rounds). False skips
        the in-loop update and returns track_length=None; the
        debug_checks consistency assert requires it. Kept as a knob so
        the hardware A/B grid can price it.
      stats: fold the per-move telemetry vector (TraceResult.stats;
        obs/walk_stats.py schema) into the jitted program: two int32
        per-lane counters (real crossings, chase hops) updated
        elementwise per crossing — the same cost class as
        the ledger — plus a [4] accumulator (occupancy and rounds once
        per compaction round, the bumps per crossing), reduced to one
        [12] vector at the end. No extra
        dispatches, no extra readbacks (the caller fetches the vector
        INSTEAD of scanning ``done`` host-side). False restores the
        exact pre-telemetry carry for A/B cost attribution.
      integrity: fold the on-device conservation-invariant vector into
        the jitted program (TraceResult.integrity;
        integrity/invariants.py schema): Σ weight·scored-track vs
        Σ weight·|final − origin| over completed lanes plus the max
        per-lane residual (requires ``ledger``), a non-finite/negative
        flux-entry count, and lane-count conservation inputs. All
        end-of-walk reductions — nothing rides the crossing loop — and
        the packed pipeline carries the vector in the existing readback
        tail, so the transfer count is unchanged. The flux math is
        untouched: outputs are bit-identical with the flag on or off
        (pinned by tests/test_integrity.py).
      record_xpoints: when set to K, record each particle's first K
        boundary-crossing points into an [n, K, 3] buffer (the tracer's
        getIntersectionPoints() surface, reference test:403-479,
        561-587). Composes with compaction: the xp/kx lanes ride the
        straggler gather/scatter-back like all other per-particle state,
        so the production config can record too. The hot path pays
        nothing when the flag is off.
      conv_state: statistical-convergence batch accumulators
        ``(snapshot, Σbatch², n_batches, move_counter)``
        (obs/convergence.py; the facades own them, device-resident and
        donated).  When supplied on a non-initial trace the program
        appends the batch fold — close the current batch every
        ``batch_moves`` enabled moves — and the [CONV_LEN] rel-err
        summary reduction AFTER the walk: the reductions read the flux
        and never write it, so tally outputs are bit-identical with the
        feature on or off, and the packed pipeline carries the summary
        in the existing readback tail (zero extra transfers).  None
        (default): no convergence machinery is traced at all.
      rel_err_target: per-bin relative-error threshold for the
        converged-bin count (static; only read with conv_state).
      batch_moves: moves per statistical batch (static; only read with
        conv_state).
      debug_checks: thread `checkify` device assertions through the walk
        body — the functional analog of the reference's
        OMEGA_H_CHECK_PRINTF kernel asserts (finite intersection points
        cpp:605-608 neighborhood, element-id range, non-negative tally
        contributions cpp:618-629). Wrap the call in
        `jax.experimental.checkify.checkify` (see `checked_trace`) to
        surface the first violation; costs extra per-crossing reductions,
        debug builds only.
      kernel: walk backend. "xla" (default) is this function's scattered
        body; "pallas" routes the IDENTICAL trace contract through the
        Mosaic kernel (ops/walk_pallas.py — VMEM-resident tables,
        one-hot MXU gather, matrixized tally scatter), bit-compared
        against this path by tests/test_kernel_pallas.py. The facades
        resolve TallyConfig(kernel=...)/PUMI_TPU_KERNEL to a concrete
        backend at construction (walk_pallas.select_backend) — "auto"
        never reaches here.
      lane_block: the Mosaic kernel's one-hot block width B (first-class
        knob: TallyConfig(pallas_lane_block=...) /
        PUMI_TPU_PALLAS_LANE_BLOCK / the tuning database; every ladder
        rung is bitwise identical, so this is pure scheduling).  None =
        the kernel default (walk_pallas.DEFAULT_LANE_BLOCK).  Ignored
        by the XLA body — the facades only thread it on the Pallas
        path, so the XLA jit cache is not fragmented by a no-op key.
      seed_grid: the batch-start search's seed table
        (ops/seed_grid.py; ``initial=True`` only). Each flying lane
        whose destination's cell holds a seed starts its walk from a
        point inside the seed, before either backend runs; the stats
        vector's ``grid_seeded`` counts those lanes. None (every move,
        and the default): the walk starts at ``origin``.
    """
    seeded = None
    if seed_grid is not None:
        if not initial:
            raise ValueError(
                "seed_grid moves the start of the batch-start search "
                "(initial=True) only: a move's ray starts where its "
                "particle is"
            )
        from .seed_grid import seed_starts

        with jax.named_scope("search.seed"):
            origin, elem, seeded = seed_starts(
                seed_grid, origin, elem, dest, in_flight, tolerance
            )
    if kernel == "pallas":
        # The Mosaic path takes trace_impl's exact contract, so the
        # packed-staging program (trace_packed_impl) composes unchanged:
        # record unpack → Pallas kernel → coalesced readback is still
        # ONE compiled program with one H2D and one D2H per move.
        from .walk_pallas import trace_pallas_impl

        return count_seeded(trace_pallas_impl(
            mesh, origin, dest, elem, in_flight, weight, group,
            material_id, flux,
            initial=initial,
            max_crossings=max_crossings,
            score_squares=score_squares,
            tolerance=tolerance,
            compact_after=compact_after,
            compact_size=compact_size,
            compact_stages=compact_stages,
            unroll=unroll,
            robust=robust,
            tally_scatter=tally_scatter,
            gathers=gathers,
            ledger=ledger,
            stats=stats,
            integrity=integrity,
            debug_checks=debug_checks,
            record_xpoints=record_xpoints,
            n_groups=n_groups,
            conv_state=conv_state,
            rel_err_target=rel_err_target,
            batch_moves=batch_moves,
            lane_block=lane_block,
        ), seeded)
    if kernel != "xla":
        raise ValueError(
            f"kernel must be 'xla' or 'pallas' at trace time: {kernel!r}"
            " ('auto' is resolved by the facades via "
            "walk_pallas.select_backend before dispatch)"
        )
    del lane_block  # a Mosaic block width; no meaning for the XLA body
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

    in_flight = in_flight.astype(bool)
    weight = weight.astype(dtype)
    # Out-of-range groups contribute nothing: the scatter below drops rows
    # whose (elem, group) index is out of bounds (mode="drop"), the
    # functional analog of the reference's group-bounds device assert
    # (cpp:634-638). The facade additionally rejects them host-side.
    group = group.astype(jnp.int32)

    # One-gather packed body (see module docstring "Gather budget"); falls
    # back to the four-gather body when the mesh lacks the packed table
    # (>=2^24 elements, >64 classes, or built with packed=False).
    packed = getattr(mesh, "geo20", None) is not None

    done0 = jnp.logical_not(in_flight)
    # Derive the zero from a per-particle input so the counter carries the
    # same device-varying type as its in-loop update under shard_map.
    nseg_dtype = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    nseg0 = jnp.sum(in_flight).astype(nseg_dtype) * 0

    # In the packed body the loop-carried material lane holds a CODE,
    # resolved to real class values once after the loop: -2 = untouched
    # (keep the caller's material_id), -1 = destination reached / domain
    # exit, >=0 = index into mesh.class_values of the stopping neighbor.
    # (derived from material_id, not jnp.full, so the carry keeps the same
    # device-varying type under shard_map — see nseg0 below.)
    mat0 = material_id * 0 - 2 if packed else material_id

    # The flux rides the loop flat as [ntet*n_groups*2] so both tally
    # rows land at slots 2k / 2k+1 under either scatter strategy.
    flux_shape = flux.shape
    if flux_shape not in ((ntet, n_groups, 2), (ntet * n_groups * 2,)):
        raise ValueError(
            f"flux must be [ntet, n_groups, 2] = ({ntet}, {n_groups}, 2) "
            f"or flat ({ntet * n_groups * 2},); got {flux_shape} — the "
            "flat stride-2 tally layout carries the trailing (Σc, Σc²) pair"
        )
    flux = flux.reshape(-1)
    nbins = ntet * n_groups  # OOB sentinel key; 2·nbins is OOB in flat
    if 2 * nbins >= 2**31:
        raise NotImplementedError(
            "flat tally keys overflow int32: ntet*n_groups*2 = "
            f"{2 * nbins} >= 2^31; shard the mesh (parallel/mesh_partition)"
        )
    # Bitcast width must follow the TABLE dtype (geo20 stores int32 bits
    # for f32 meshes, int64 bits for f64), not the particle dtype — they
    # can legitimately differ under x64.
    code_int = (
        jnp.int32
        if (packed and mesh.geo20.dtype.itemsize == 4)
        else jnp.int64
    )

    # Ray-parameter tolerance floor: a few ulps so `t >= 1 - tol` survives
    # f32 rounding (1 - 1e-8 == 1 in f32). See the tolerance docstring.
    tol_floor = 8 * float(jnp.finfo(dtype).eps)

    tally_scatter = resolve_tally_scatter(tally_scatter)
    if tally_scatter not in ("interleaved", "pair"):
        raise ValueError(
            f"tally_scatter must be 'auto', 'interleaved' or 'pair': "
            f"{tally_scatter!r}"
        )
    if gathers not in ("merged", "split"):
        raise ValueError(f"gathers must be 'merged' or 'split': {gathers!r}")
    if integrity and not ledger:
        raise ValueError(
            "integrity=True needs the per-particle track-length ledger "
            "(ledger=True) for the conservation invariant"
        )

    # Carry layout — ONE definition shared by the walk body, the phase
    # runner and the compaction rounds: a fixed head (done stays at
    # index 2 for the loop conds), an optional [4] scalar accumulator
    # when stats is on (compaction: active lanes placed, slots swept,
    # rounds run; the forward bumps), then every per-lane extra in
    # static order — [ncross, nchase] when stats, [xp, kx] when
    # recording — so compaction can gather/scatter the extras uniformly,
    # and the iteration counter last.
    def unpack_carry(c):
        cur, elem, done, mat, flux, nseg = c[:6]
        rest = c[6:]
        if stats:
            occ, rest = rest[0], rest[1:]
        else:
            occ = None
        prev, stuck, pseg = rest[0], rest[1], rest[2]
        lanes = list(rest[3:-1])
        it = rest[-1]
        return (cur, elem, done, mat, flux, nseg, occ, prev, stuck,
                pseg, lanes, it)

    def pack_carry(cur, elem, done, mat, flux, nseg, occ, prev, stuck,
                   pseg, lanes, it):
        head = (cur, elem, done, mat, flux, nseg)
        if stats:
            head = head + (occ,)
        return head + (prev, stuck, pseg, *lanes, it)

    def make_body(dest_a, in_flight_a, weight_a, group_a):
        """One element-boundary crossing for every lane of a (sub)batch.

        The per-particle inputs that never change during the walk are closed
        over so the same body serves both the full batch and compacted
        straggler subsets."""
        # Out-of-range groups map to the OOB key so their rows drop.
        good_group = (group_a >= 0) & (group_a < n_groups)
        def body(carry):
            (cur, elem, done, mat, flux, nseg, occ, prev, stuck, pseg,
             lanes, it) = unpack_carry(carry)
            if record_xpoints is not None:
                xp, kx = lanes[-2], lanes[-1]
            active = jnp.logical_not(done)

            with jax.named_scope("walk.gather"):
                if packed:
                    if gathers == "merged":
                        # ONE gather: normals + offsets + bitcast topo codes.
                        geo = mesh.geo20[elem]  # [m, 20]
                        geo_g, codes_f = geo[:, :16], geo[:, 16:20]
                    else:
                        # Two narrower gathers from slices of the same table
                        # (round-2 pattern): 16-wide geometry + 4-wide topo.
                        geo_g = mesh.geo20[:, :16][elem]
                        codes_f = mesh.geo20[:, 16:20][elem]
                    normals = geo_g[:, :12].reshape(-1, 4, 3)
                    dplane = geo_g[:, 12:16]
                    codes = jax.lax.bitcast_convert_type(
                        codes_f, code_int
                    ).astype(jnp.int32)  # [m, 4]
                    nbrs_all = (codes & 0xFFFFFF) - 1
                else:
                    normals = mesh.face_normals[elem]
                    dplane = mesh.face_d[elem]
                    nbrs_all = mesh.tet2tet[elem]  # [m, 4]

            with jax.named_scope("walk.geometry"):
                dirv = dest_a - cur
                if robust:
                    # Never step back through the face we just entered: a
                    # straight ray cannot re-enter a convex element it exited,
                    # and masking that face breaks the t≈0 two-element cycles
                    # grazing rays otherwise fall into on irregular meshes
                    # (see exit_face).
                    backward = (prev[:, None] >= 0) & (
                        nbrs_all == prev[:, None]
                    )
                    t_exit, face, has_exit, plane_num = exit_face(
                        normals, dplane, cur, dirv, exclude=backward,
                        return_num=True,
                    )

                    # Relocation chase for stuck lanes. Near a grazing corner
                    # the rounded min-t exit choice can hop the particle into
                    # an element that does NOT contain the onward ray; the
                    # resulting t=0 ejection cascade can cycle instead of
                    # converging, with the position and the element assignment
                    # macroscopically diverged. After 4 consecutive
                    # zero-progress crossings in a NON-containing element,
                    # switch the lane to a stochastic visibility walk
                    # (chase_face_choice): hop toward the point without moving
                    # or scoring anything until containment is restored, then
                    # resume the normal walk (the stuck counter resets on
                    # containment). The same recovery class the reference's
                    # tracer leaves to "not all particles found" printf
                    # truncation (cpp:765-768) — here it repairs instead of
                    # giving up.
                    sd = -plane_num  # signed distance to own faces; reuse
                    # the exit test's plane numerators, not a second einsum.
                    contained = jnp.max(sd, axis=-1) <= 0.0
                    chase = active & (stuck >= 4) & ~contained
                    chase_face = chase_face_choice(
                        sd, elem, it, dtype, nbrs_all >= 0
                    )
                    face = jnp.where(chase, chase_face, face)
                    t_exit = jnp.where(chase, 0.0, t_exit)
                    has_exit = has_exit | chase
                elif debug_checks:
                    t_exit, face, has_exit, plane_num = exit_face(
                        normals, dplane, cur, dirv, return_num=True
                    )
                    sd = -plane_num
                else:
                    t_exit, face, has_exit = exit_face(
                        normals, dplane, cur, dirv
                    )

                # Geometric tolerance → ray-parameter space (normals are unit,
                # so geometric distance = t × |dirv|), floored at a few ulps;
                # the shared reached test (end_of_flight).
                dnorm = jnp.linalg.norm(dirv, axis=-1)
                reached, t_step = end_of_flight(
                    t_exit, has_exit, dnorm, tolerance, tol_floor
                )
                xpoint = cur + t_step[:, None] * dirv

                if debug_checks:
                    from jax.experimental import checkify

                    # Walk-consistency analog of the reference's
                    # tracklength device print (cpp:618-629): every active
                    # particle must actually be inside (within tolerance +
                    # rounding of) its claimed parent element — a wrong
                    # parent id, a broken hop, or degenerate geometry shows
                    # up here as an off-element position. Reuses the exit
                    # test's signed distances, so the debug cost is a couple
                    # of reductions. Also guards the tally-free initial search.
                    scale = jnp.max(jnp.abs(cur), axis=-1) + 1.0
                    bound = 10.0 * tolerance + 64.0 * tol_floor * scale
                    checkify.check(
                        jnp.all(
                            jnp.where(active, jnp.max(sd, axis=-1), 0.0)
                            <= bound
                        ),
                        "particle position outside its parent element "
                        "(corrupted walk state or degenerate geometry)",
                    )

                crossed = active & ~reached & has_exit
                # Genuine boundary crossings only (a lane that reaches its
                # destination inside the current element crosses nothing, and
                # relocation-chase hops are bookkeeping, not crossings) —
                # the convention shared by the telemetry counters and the
                # recorded intersection points.
                real_cross = crossed & ~chase if robust else crossed
                if stats:
                    ncross, nchase = lanes[0], lanes[1]
                    lanes[0] = ncross + real_cross.astype(ncross.dtype)
                    if robust:
                        lanes[1] = nchase + chase.astype(nchase.dtype)
                if record_xpoints is not None:
                    # Non-crossing lanes row-index OOB (dropped); lanes past
                    # K crossings column-index OOB (dropped).
                    xp, kx = record_crossing(xp, kx, xpoint, real_cross)
                    lanes[-2], lanes[-1] = xp, kx
                if packed:
                    # Topology came along in the geo20 row: select the exit
                    # face's code locally (no second table gather).
                    code = jnp.take_along_axis(
                        codes, face[:, None], axis=1
                    )[:, 0]
                nbr = jnp.take_along_axis(nbrs_all, face[:, None], axis=1)[:, 0]
                next_elem = jnp.where(crossed, nbr, jnp.int32(-1))

                if debug_checks:
                    from jax.experimental import checkify

                    checkify.check(
                        jnp.all(jnp.isfinite(jnp.where(active[:, None], xpoint, 0.0))),
                        "non-finite intersection point in walk",
                    )
                    checkify.check(
                        jnp.all((next_elem >= -1) & (next_elem < ntet)),
                        "element id out of range after hop",
                    )

            with jax.named_scope("walk.scatter"):
                # --- tally (skipped on the initial location search) -----------
                if not initial:
                    seg = t_step * dnorm  # |xpoint - cur|
                    # Chase hops are bookkeeping (zero length): keep them out
                    # of the segment count the benchmarks report.
                    score = active & in_flight_a
                    if robust:
                        score = score & ~chase
                    contrib = jnp.where(score, seg * weight_a, 0.0).astype(dtype)
                    # Flat (elem, group) key; non-scoring rows get the OOB
                    # sentinel and drop — the functional analog of the
                    # reference's group-bounds device assert (cpp:634-638).
                    key = jnp.where(
                        score & good_group,
                        elem * n_groups + group_a,
                        nbins,
                    )
                    if debug_checks:
                        from jax.experimental import checkify

                        checkify.check(
                            jnp.all(contrib >= 0)
                            & jnp.all(jnp.isfinite(contrib)),
                            "negative or non-finite tally contribution",
                        )
                    if not score_squares:
                        flux = flux.at[key * 2].add(contrib, mode="drop")
                    elif tally_scatter == "interleaved":
                        # Both tally rows in ONE interleaved scalar scatter:
                        # c at flat slot 2k, c² at 2k+1.
                        kk = jnp.concatenate([key * 2, key * 2 + 1])
                        vv = jnp.concatenate([contrib, contrib * contrib])
                        flux = flux.at[kk].add(vv, mode="drop")
                    else:
                        flux = flux.at[key * 2].add(contrib, mode="drop")
                        flux = flux.at[key * 2 + 1].add(
                            contrib * contrib, mode="drop"
                        )
                    nseg = nseg + jnp.sum(score).astype(nseg.dtype)
                    if ledger:
                        # Per-particle scored track length: one elementwise
                        # FMA — the walk's own conservation ledger (Σ over
                        # crossings of the scored segment = |final − origin|
                        # along the ray; checked under debug_checks,
                        # surfaced as TraceResult.track_length).
                        pseg = pseg + jnp.where(score, seg, 0.0).astype(dtype)

            with jax.named_scope("walk.geometry"):
                # --- boundary conditions (apply_boundary_condition,
                # cpp:452-515) -------------------------------------------------
                domain_exit = crossed & (next_elem == -1)
                if initial:
                    material_stop = jnp.zeros_like(domain_exit)
                else:
                    if packed:
                        # differs bit is only ever set for interior faces, so
                        # no next_elem >= 0 check is needed.
                        material_stop = crossed & (((code >> 30) & 1) == 1)
                        nbr_class = (code >> 24) & 0x3F  # class INDEX
                    else:
                        nbr_class = mesh.class_id[jnp.maximum(next_elem, 0)]
                        material_stop = (
                            crossed
                            & (next_elem >= 0)
                            & (nbr_class != mesh.class_id[elem])
                        )
                    # A relocation-chase hop is bookkeeping, not a physical
                    # crossing: it must not trigger a material stop.
                    if robust:
                        material_stop = material_stop & ~chase
                newly_done = (active & reached) | domain_exit | material_stop

                if not initial:
                    mat = jnp.where(
                        material_stop,
                        nbr_class,
                        jnp.where(
                            (active & reached) | domain_exit,
                            jnp.int32(-1),
                            mat,
                        ),
                    )

                # --- hop (move_to_next_element hops even freshly-done
                # material-stop particles, cpp:440-450) -------------------------
                hopped = crossed & (next_elem != -1)
                if robust:
                    # The entry-face mask rests on ray convexity, which only
                    # holds for REAL crossings: a chase hop must clear prev,
                    # not set it, or it could mask the ray's true exit from
                    # the new element.
                    prev = jnp.where(
                        hopped, jnp.where(chase, jnp.int32(-1), elem), prev
                    )
                elem = jnp.where(hopped, next_elem, elem)
                cur = jnp.where(active[:, None], xpoint, cur)
                if robust:
                    # Degeneracy bump (forward_bump): crack/edge t≈0 cycles
                    # the entry-face mask cannot break are escaped by
                    # guaranteed forward progress per crossing, landing on
                    # the destination where it would pass it.
                    continuing = crossed & ~newly_done
                    cur, stuck, bumped = forward_bump(
                        stuck, contained, continuing, t_step, tol_floor,
                        cur, dirv, dest_a, dnorm,
                    )
                    if stats:
                        # The bumps sum into occ's last word.
                        occ = occ + jnp.concatenate([
                            jnp.zeros(3, jnp.int32),
                            jnp.sum(bumped, dtype=jnp.int32)[None]])
                done = done | newly_done
            return pack_carry(cur, elem, done, mat, flux, nseg, occ,
                              prev, stuck, pseg, lanes, it + 1)

        return body

    def run_phase(body, carry, bound, unroll=unroll, exit_live=0):
        """Loop ``body`` in ``unroll``-iteration chunks while the
        iteration counter is under ``bound`` and more than ``exit_live``
        lanes are live (0: until every lane is done)."""
        if unroll > 1:
            inner = body

            def body(c):  # noqa: F811 — unrolled wrapper
                for _ in range(unroll):
                    c = inner(c)
                return c

        def cond(c):
            if exit_live:
                left = jnp.sum(jnp.logical_not(c[2]), dtype=jnp.int32)
                more = left > exit_live
            else:
                more = jnp.logical_not(jnp.all(c[2]))
            return jnp.logical_and(c[-1] < bound, more)

        return jax.lax.while_loop(cond, body, carry)

    fit = compact_stages == FIT
    widths = fit_widths(n) if fit else (n,)
    if not fit:
        compact_stages = normalize_compact_stages(
            compact_stages, compact_after, compact_size, n, max(n // 8, 256)
        )

    full_body = make_body(dest, in_flight, weight, group)
    phase1_bound = (
        max_crossings if fit or compact_stages is None
        else min(compact_stages[0][0], max_crossings)
    )
    prev0 = elem * 0 - 1  # device-varying -1: no entry face yet
    stuck0 = elem * 0  # consecutive zero-progress crossings per lane
    pseg0 = weight * 0  # per-lane scored track length (device-varying)
    lanes0 = []
    occ0 = None
    if stats:
        # Telemetry lanes (device-varying zeros): per-lane real-crossing
        # and chase-hop counters, plus the [4] scalar accumulator
        # (carry layout above).
        lanes0 += [elem * 0, elem * 0]
        occ0 = jnp.stack([nseg0] * 4).astype(jnp.int32)
    if record_xpoints is not None:
        xp0 = jnp.zeros((n, int(record_xpoints), 3), dtype)
        kx0 = elem * 0  # per-lane zero (device-varying under shard_map)
        lanes0 += [xp0, kx0]
    # The ``lanes`` extras (stats counters, recording buffers) ride the
    # compaction rounds like any other per-particle state, so the
    # features compose freely.
    # Static guard: a stage-0 schedule must not compile the dead
    # full-width while_loop at all.
    carry = pack_carry(origin, elem, done0, mat0, flux, nseg0, occ0,
                       prev0, stuck0, pseg0, lanes0, jnp.int32(0))
    if phase1_bound > 0:
        carry = run_phase(full_body, carry, phase1_bound,
                          exit_live=widths[1] if len(widths) > 1 else 0)
    # Lane-slots (obs/walk_stats.py "lane_slots"): n per full-width
    # iteration here, S per iteration of each compaction round below —
    # from the iteration counts after each loop, as float32.
    slots = jnp.float32(n) * carry[-1].astype(jnp.float32)

    def split_carry(c):
        """A carry's per-lane arrays other than ``done``, in one list,
        then its done flags, flux, segment count, occupancy and
        iteration counter: the shape a subset gather works on."""
        (cur, elem, done, mat, flux, nseg, occ, prev, stuck, pseg, lanes,
         it) = unpack_carry(c)
        return ([cur, elem, mat, prev, stuck, pseg, *lanes], done, flux,
                nseg, occ, it)

    def join_carry(per_lane, done, flux, nseg, occ, it):
        cur, elem, mat, prev, stuck, pseg, *lanes = per_lane
        return pack_carry(cur, elem, done, mat, flux, nseg, occ, prev,
                          stuck, pseg, lanes, it)

    def gather_subset(live, S, per_lane, batch_idx=None):
        """Gather the first ``S`` lanes flagged in ``live`` for a
        compaction round or a fit-ladder stage.

        The pick is ``first_k_active`` (cumsum stable partition) instead
        of argsort — same first-S selection, far cheaper than a 1M-lane
        sort. ``batch_idx``, an earlier subset's index into the batch,
        composes the pick, so ``live`` may be that subset's own flags.
        Slots past the live count gather lane 0 and start done, and
        their write-back index is n, so ``scatter_subset`` drops their
        rows. The per-lane extras (xp/kx buffers, crossing/chase
        counters) ride the same gather and scatter-back; a forced-done
        lane never records or counts, so the features compose with
        compaction. Returns the write-back index, the subset's done
        flags, its live count, its per-lane arrays and its walk body."""
        pick, n_live = first_k_active(live, S)
        valid = jnp.arange(S) < n_live
        if batch_idx is not None:
            pick = batch_idx[pick]
        g = jnp.where(valid, pick, 0)
        body = make_body(dest[g], jnp.ones(S, bool), weight[g], group[g])
        return (jnp.where(valid, pick, n), jnp.logical_not(valid), n_live,
                [a[g] for a in per_lane], body)

    def scatter_subset(idx, per_lane, sub):
        """Write a subset's arrays back into the batch's (see
        ``gather_subset``)."""
        return [a.at[idx].set(s, mode="drop") for a, s in zip(per_lane, sub)]

    def round_occ(n_live, S):
        """Occupancy telemetry of one round: active lanes placed, slots
        swept, and the round itself (no recovery counts)."""
        z = jnp.zeros_like(n_live)
        return jnp.stack([jnp.minimum(n_live, S), z + S, z + 1, z]
                         ).astype(jnp.int32)

    def compact_round(state, S, bound, stage_unroll=unroll):
        """One compaction round: gather the first S active lanes, advance
        them up to `bound` crossings, scatter results back."""
        per_lane, done, flux, nseg, occ, it = split_carry(state)
        with jax.named_scope("walk.compact"):
            idx, sdone, n_active, sub, sub_body = gather_subset(
                jnp.logical_not(done), S, per_lane
            )
            if stats:
                occ = occ + round_occ(n_active, S)
        sub, sdone, flux, nseg, occ, sit = split_carry(run_phase(
            sub_body, join_carry(sub, sdone, flux, nseg, occ, jnp.int32(0)),
            bound, unroll=stage_unroll,
        ))
        with jax.named_scope("walk.compact"):
            *per_lane, done = scatter_subset(
                idx, [*per_lane, done], [*sub, sdone]
            )
        return join_carry(per_lane, done, flux, nseg, occ, it + sit)

    def fit_stages(state, slots):
        """The fit ladder's narrower stages (module docstring): enter
        each with the live lanes of the one before, loop it until they
        fit the next width, and scatter it back before the next gather,
        so one subset generation is live beside the batch at a time."""
        per_lane, done, flux, nseg, occ, it = split_carry(state)
        live, idx = jnp.logical_not(done), None
        # The flat loop's last iteration: the narrower stages' chunks
        # divide ``unroll``, so they stop on it as the full width does.
        last_it = -(-max_crossings // unroll) * unroll
        for k, W in enumerate(widths[1:], start=1):
            with jax.named_scope("walk.compact"):
                if idx is not None:
                    *per_lane, done = scatter_subset(
                        idx, [*per_lane, done], [*sub, sdone]
                    )
                idx, sdone, n_live, sub, body = gather_subset(
                    live, W, per_lane, batch_idx=idx
                )
            it_before = it
            sub, sdone, flux, nseg, occ, it = split_carry(run_phase(
                body, join_carry(sub, sdone, flux, nseg, occ, it),
                last_it, unroll=fit_stage_unroll(unroll),
                exit_live=widths[k + 1] if k + 1 < len(widths) else 0,
            ))
            live = jnp.logical_not(sdone)
            ran = it - it_before
            if stats:
                # A stage that ran a chunk is a compaction round.
                occ = occ + jnp.where(ran > 0, round_occ(n_live, W), 0)
            slots = slots + jnp.float32(W) * ran.astype(jnp.float32)
        with jax.named_scope("walk.compact"):
            *per_lane, done = scatter_subset(
                idx, [*per_lane, done], [*sub, sdone]
            )
        return join_carry(per_lane, done, flux, nseg, occ, it), slots

    if len(widths) > 1:
        carry, slots = fit_stages(carry, slots)
    elif compact_stages is not None and phase1_bound < max_crossings:
        for i, (start, size, *rest) in enumerate(compact_stages):
            S = min(n, max(int(size), 1))
            s_unroll = int(rest[0]) if rest else unroll
            it_before = carry[-1]
            if i + 1 < len(compact_stages):
                # Intermediate stage: one bounded round; leftovers wait.
                # Guarded so an all-done batch skips the argsort +
                # gather/scatter entirely (the guard the final stage's
                # outer_cond provides).
                span = min(compact_stages[i + 1][0], max_crossings) - start
                if span > 0:
                    carry = jax.lax.cond(
                        jnp.all(carry[2]),
                        lambda s: s,
                        lambda s: compact_round(s, S, span, s_unroll),
                        carry,
                    )
            else:
                # Final stage: loop rounds to completion.
                max_rounds = -(-n // S) + 1  # each retires ≥S actives or all

                def outer_body(c):
                    *st, rounds = c
                    st = compact_round(tuple(st), S, max_crossings, s_unroll)
                    return (*st, rounds + 1)

                def outer_cond(c):
                    done, rounds = c[2], c[-1]
                    return jnp.logical_and(
                        rounds < max_rounds, jnp.logical_not(jnp.all(done))
                    )

                *carry, _ = jax.lax.while_loop(
                    outer_cond, outer_body, (*carry, jnp.int32(0))
                )
                carry = tuple(carry)
            # Every round of a stage sweeps the stage's S lanes.
            slots = slots + jnp.float32(S) * (
                carry[-1] - it_before
            ).astype(jnp.float32)
    (cur, elem, done, mat, flux, nseg, occ, prev, stuck, pseg, lanes,
     it) = unpack_carry(carry)

    if debug_checks and not initial and ledger:
        from jax.experimental import checkify

        # The literal analog of the reference's segment-vs-tracklength
        # consistency print (cpp:618-629): every particle's scored
        # track length must equal its net straight-line displacement —
        # all movement is along the origin→dest ray, so a mismatch means
        # a missed or double-scored segment. The bound covers fp
        # accumulation plus the robust mode's unscored ulp-scale bump
        # hops (one per crossing at worst).
        dist = jnp.linalg.norm(cur - origin, axis=-1)
        # The robust bump's unscored hop is 4·tol_floor·(1 + max|cur|)
        # per crossing (forward_bump), within the allowance's
        # coordinate term; the ray term keeps room for the rounding of
        # positions along long rays.
        raylen = jnp.linalg.norm(dest - origin, axis=-1)
        scale_d = 1.0 + jnp.maximum(
            jnp.linalg.norm(origin, axis=-1), dist
        )
        bound = (it.astype(dtype) + 1.0) * (
            tolerance + 64.0 * tol_floor * (scale_d + raylen)
        )
        checkify.check(
            jnp.all(jnp.abs(pseg - dist) <= bound),
            "scored track length disagrees with net displacement "
            "(missed or double-scored segment)",
        )

    if packed:
        # Resolve material codes to real class_id values (one tiny-table
        # gather): -2 → caller's material_id untouched, -1 → reached /
        # domain exit, >=0 → class_values[index] of the stopping neighbor.
        material_id = jnp.where(
            mat == -2,
            material_id,
            jnp.where(
                mat == -1,
                jnp.int32(-1),
                mesh.class_values[jnp.maximum(mat, 0)],
            ),
        )
    else:
        material_id = mat

    xp, kx = (
        (lanes[-2], lanes[-1]) if record_xpoints is not None
        else (None, None)
    )
    integ_vec = stats_vec = conv_vec = conv_out = None
    with jax.named_scope("obs.fold"):
        if integrity:
            integ_vec = integrity_vector(
                in_flight, done, weight, pseg, cur, origin, flux, dtype,
                initial,
            )
        if stats:
            stats_vec = walk_stats_vector(
                lanes[0], lanes[1], done, occ[0], occ[1], nseg, it, slots,
                occ[2], occ[3],
            )
        if conv_state is not None:
            # Statistical-convergence fold + summary (obs/convergence.py):
            # reads the flat flux's even (Σc) entries only, after all
            # scoring — never writes the accumulator, so the tally output
            # is bit-identical with or without it.
            if initial:
                raise ValueError(
                    "conv_state is a move-loop feature: the initial "
                    "location search scores nothing and must not advance "
                    "the batch cadence"
                )
            from ..obs.convergence import fold_and_reduce

            conv_out, conv_vec = fold_and_reduce(
                flux, *conv_state,
                batch_moves=batch_moves, rel_err_target=rel_err_target,
            )
    return count_seeded(TraceResult(
        position=cur,
        elem=elem,
        material_id=material_id,
        flux=flux.reshape(flux_shape),
        n_segments=nseg,
        n_crossings=it,
        done=done,
        xpoints=xp,
        n_xpoints=kx,
        track_length=pseg if ledger else None,
        stats=stats_vec,
        integrity=integ_vec,
        convergence=conv_vec,
        conv_state=conv_out,
    ), seeded)


def count_seeded(result, seeded):
    """Write the seeded-lane count of the batch-start search
    (``seed_grid``) into the stats vector's ``grid_seeded`` word; the
    walk bodies leave it 0."""
    if seeded is None or result.stats is None:
        return result
    from ..obs.walk_stats import IDX

    s = result.stats
    return result._replace(
        stats=s.at[IDX["grid_seeded"]].set(seeded.astype(s.dtype))
    )


@functools.lru_cache(maxsize=64)
def _checked_jit(static_kwargs: tuple):
    from jax.experimental import checkify

    fn = functools.partial(
        trace_impl, debug_checks=True, **dict(static_kwargs)
    )
    return jax.jit(checkify.checkify(fn, errors=checkify.user_checks))


# Bound from the signature so a reordered/inserted trace_impl parameter
# breaks here loudly instead of silently consulting the wrong array.
_FLUX_ARG_INDEX = list(
    inspect.signature(trace_impl).parameters
).index("flux")


def _resolve_auto_kwargs(args, kwargs):
    """Resolve 'auto' static knobs against the flux argument's device.

    Runs before the jit cache key is formed so the backend decision is
    re-made per call instead of frozen into the first trace."""
    if kwargs.get("tally_scatter", "auto") == "auto":
        flux = (
            args[_FLUX_ARG_INDEX]
            if len(args) > _FLUX_ARG_INDEX
            else kwargs.get("flux")
        )
        kwargs = dict(
            kwargs, tally_scatter=resolve_tally_scatter("auto", flux)
        )
    return kwargs


def checked_trace(*args, seed_grid=None, **kwargs):
    """Run the walk with in-kernel invariant checks (OMEGA_H_CHECK parity).

    Returns (error, TraceResult); call ``error.throw()`` to raise on the
    first violated device assertion. The checkify-transformed walk is
    jitted and cached per static-kwarg signature, so repeated calls pay
    only the extra per-crossing reductions, not retracing.
    """
    kwargs = _resolve_auto_kwargs(args, kwargs)
    return _checked_jit(tuple(sorted(kwargs.items())))(
        *args, seed_grid=seed_grid
    )


_trace_jit = jax.jit(
    trace_impl,
    static_argnames=(
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
        "kernel",
        "lane_block",
    ),
    # conv_state's batch accumulators are carried exactly like the flux:
    # donated in, fresh buffers out (None → no leaves, no donation).
    donate_argnames=("flux", "conv_state"),
)


def trace(*args, **kwargs):
    return _trace_jit(*args, **_resolve_auto_kwargs(args, kwargs))


trace.__doc__ = trace_impl.__doc__


# --------------------------------------------------------------------- #
# Packed-I/O trace (move-loop pipelining; ops/staging.py)
# --------------------------------------------------------------------- #
def trace_packed_impl(
    mesh,
    origin,
    elem,
    material_id,
    record,
    flux,
    perm=None,
    weight=None,
    group=None,
    conv_state=None,
    **kwargs,
):
    """The fused packed-I/O step: device-side record unpack (with the
    slot-permutation gather), the full walk, and the coalesced readback
    pack — ONE compiled program, so a steady-state facade move issues
    exactly one H2D transfer (the input record) and one D2H transfer
    (the readback record).

    ``record`` is a [n, MOVE_COLS] (or [n, INIT_COLS] when
    ``initial=True``) carrier-word host record (staging.pack_move_record
    / pack_init_record), donated.  ``perm`` is the device-resident slot
    permutation (``state.particle_id`` after a periodic element sort) or
    None while the layout is identity.  For the initial search,
    ``weight``/``group`` come from device state instead of the record.

    Returns ``(TraceResult, readback, dest, in_flight, weight, group)``
    — the staged device arrays ride along so the facade can update its
    state and re-arm escalation re-walks without re-staging.
    """
    from .staging import pack_trace_readback, unpack_move_record

    initial = kwargs["initial"]
    dest, in_flight, w, g = unpack_move_record(
        record, origin.dtype, perm, initial
    )
    if w is None:
        w, g = weight, group
    r = trace_impl(
        mesh, origin, dest, elem, in_flight, w, g, material_id, flux,
        conv_state=conv_state, **kwargs,
    )
    readback = pack_trace_readback(
        r.position, r.material_id, r.done, r.stats, r.n_segments, perm,
        r.integrity, r.convergence,
    )
    return r, readback, dest, in_flight, w, g


_trace_packed_jit = jax.jit(
    trace_packed_impl,
    static_argnames=(
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
        "kernel",
        "lane_block",
    ),
    # The flux carry is donated exactly like the unpacked trace — a
    # supervisor retry re-sees its original inputs because the facade
    # re-packs the staging record from the caller's untouched host
    # arrays (PR 2's re-arm contract).  The convergence batch
    # accumulators ride the same contract (None → no leaves).  The
    # record itself is NOT donated: no output shares its carrier shape,
    # so XLA would only warn.
    donate_argnames=("flux", "conv_state"),
)

_PACKED_FLUX_ARG_INDEX = list(
    inspect.signature(trace_packed_impl).parameters
).index("flux")


def trace_packed(*args, **kwargs):
    if kwargs.get("tally_scatter", "auto") == "auto":
        flux = (
            args[_PACKED_FLUX_ARG_INDEX]
            if len(args) > _PACKED_FLUX_ARG_INDEX
            else kwargs.get("flux")
        )
        kwargs = dict(
            kwargs, tally_scatter=resolve_tally_scatter("auto", flux)
        )
    return _trace_packed_jit(*args, **kwargs)


trace_packed.__doc__ = trace_packed_impl.__doc__


# --------------------------------------------------------------------- #
# Megastep: K device-sourced moves fused into one compiled program
# --------------------------------------------------------------------- #
class MegastepResult(NamedTuple):
    """Outputs of one megastep dispatch (ops/source.py module
    docstring). Per-lane state stays DEVICE-RESIDENT — the facade
    re-binds it for the next megastep; only ``readback`` (the packed
    stats/integrity/convergence/physics tail,
    staging.pack_megastep_tail) is fetched, so a whole megastep is one
    H2D (the move counter) and one D2H (this tail)."""

    position: jax.Array
    dest: jax.Array
    elem: jax.Array
    material_id: jax.Array
    weight: jax.Array
    group: jax.Array
    alive: jax.Array
    flux: jax.Array
    readback: jax.Array
    prev_even: jax.Array | None = None
    conv_state: tuple | None = None


def merge_megastep_stats(acc, stats):
    """Fold one fused move's stats vector into the megastep reduction:
    sums everywhere, max of ``max_crossings``, and ``truncated``
    SUMMED over moves (each fused move's truncation is a distinct
    would-have-warned event — unlike a re-walk merge, where attempts
    revisit the same lanes and only the final count stands)."""
    from ..obs import IDX

    out = add_walk_stats(acc, stats)
    return out.at[IDX["max_crossings"]].set(
        jnp.maximum(acc[IDX["max_crossings"]], stats[IDX["max_crossings"]])
    )


def merge_megastep_integrity(acc, integ):
    """Fold one fused move's integrity vector into the megastep
    reduction (integrity/invariants.py field order): the conservation
    sums and lane counts ADD across moves, the per-lane residual MAXES,
    and ``bad_flux`` reflects the final accumulator."""
    from ..integrity.invariants import IIDX as II

    out = acc + integ
    out = out.at[II["max_residual"]].set(
        jnp.maximum(acc[II["max_residual"]], integ[II["max_residual"]])
    )
    return out.at[II["bad_flux"]].set(integ[II["bad_flux"]])


def megastep_impl(
    mesh,
    origin,
    elem,
    material_id,
    weight,
    group,
    alive,
    pid,
    flux,
    move0,
    rng_key,
    sigma_t,
    absorb_t,
    prev_even=None,
    conv_state=None,
    *,
    n_moves: int,
    n_groups: int,
    survival_weight: float,
    downscatter: float,
    eps_near: float,
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
    rel_err_target: float = 0.05,
    batch_moves: int = 1,
) -> MegastepResult:
    """Run ``n_moves`` complete device-sourced moves as ONE program.

    Each fused move ``m = move0 + k``: re-source every alive lane with
    counter-based RNG keyed by ``(rng_key, m, pid)`` (ops/source.py —
    isotropic direction, exponential flight distance over the lane's
    region Σt from ``sigma_t[class_id[elem]]``), walk it with the
    standard fused tracer body (``trace_impl``), then apply the
    collision/termination physics of models/transport.py's inner loop
    (absorption survival weighting, downscatter, domain-escape
    termination, Russian roulette). The per-move stats/integrity
    vectors become per-megastep reductions (``merge_megastep_stats`` /
    ``merge_megastep_integrity``); the convergence batch cadence counts
    DEVICE moves (``conv_state`` folds once per fused move, exactly as
    if each were a facade move).

    ``move0`` is a device scalar (the facade's persistent move counter
    — its ONE H2D per megastep); ``rng_key`` a device PRNG key the
    facade stages once per seed (a runtime input, so re-seeding never
    recompiles); ``pid`` is the device-resident particle-id lane
    (``state.particle_id``), which keys the RNG so sampling is
    invariant to slot layout. ``prev_even`` threads the
    sd_mode="batch" snapshot (one squared per-bin delta folded per
    fused move, the bench run_fused contract). Sampling runs for every
    lane each move (dead lanes discard theirs) — the cost class of one
    elementwise pass, and the price of layout-invariant streams.
    """
    from .source import apply_physics, sample_move
    from .staging import pack_megastep_tail

    dtype = origin.dtype
    n = origin.shape[0]
    base_key = rng_key
    nclass = sigma_t.shape[0]
    tiny = jnp.asarray(np.finfo(np.dtype(dtype)).tiny, dtype)
    walk_kw = dict(
        initial=False,
        max_crossings=max_crossings,
        score_squares=score_squares,
        tolerance=tolerance,
        compact_after=compact_after,
        compact_size=compact_size,
        compact_stages=compact_stages,
        unroll=unroll,
        robust=robust,
        tally_scatter=tally_scatter,
        gathers=gathers,
        ledger=ledger,
        stats=stats,
        integrity=integrity,
        n_groups=n_groups,
        rel_err_target=rel_err_target,
        batch_moves=batch_moves,
    )
    nseg_dtype = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    zero_f = jnp.sum(weight) * 0  # device-varying scalar zero

    def body(k, carry):
        (origin, dest, elem, mat, weight, group, alive, flux, prev_even,
         conv, sacc, iacc, cvec, pacc, nseg) = carry
        m = move0 + k
        with jax.named_scope("source.sample"):
            region = mesh.class_id[jnp.clip(elem, 0, mesh.ntet - 1)]
            sig = sigma_t[jnp.clip(region, 0, nclass - 1)]
            direction, ell, coll_u, roul_u = sample_move(
                base_key, m, pid, n, dtype
            )
            flight = direction * (ell / jnp.maximum(sig, tiny))[:, None]
            dest = jnp.where(alive[:, None], origin + flight, origin)
        r = trace_impl(
            mesh, origin, dest, elem, alive, weight, group, mat, flux,
            conv_state=conv, **walk_kw,
        )
        with jax.named_scope("source.physics"):
            ab = absorb_t[
                jnp.clip(
                    mesh.class_id[jnp.clip(r.elem, 0, mesh.ntet - 1)],
                    0, nclass - 1,
                )
            ]
            weight, group, alive2, phys4 = apply_physics(
                r.position, dest, r.done, r.material_id, weight, group,
                alive, ab, coll_u, roul_u,
                eps_near=eps_near,
                survival_weight=survival_weight,
                downscatter=downscatter,
                n_groups=n_groups,
            )
        flux = r.flux
        if prev_even is not None:
            from ..core.tally import accumulate_batch_squares

            with jax.named_scope("tally.batch_sd"):
                flux, prev_even = accumulate_batch_squares(flux, prev_even)
        with jax.named_scope("obs.fold"):
            if sacc is not None:
                sacc = merge_megastep_stats(sacc, r.stats)
            if iacc is not None:
                iacc = merge_megastep_integrity(iacc, r.integrity)
            if cvec is not None:
                cvec = r.convergence
            n_trunc = jnp.sum(alive & ~r.done).astype(dtype)
            pacc = jnp.concatenate(
                [
                    pacc[:4] + phys4,
                    jnp.sum(alive2).astype(dtype)[None],
                    pacc[5:6] + n_trunc[None],
                ]
            )
        return (r.position, dest, r.elem, r.material_id, weight, group,
                alive2, flux, prev_even, r.conv_state, sacc, iacc, cvec,
                pacc, nseg + r.n_segments)

    from ..integrity.invariants import INTEGRITY_LEN
    from ..obs import WALK_STATS_LEN
    from .source import MEGA_PHYS_LEN

    sacc0 = jnp.zeros(WALK_STATS_LEN, nseg_dtype) if stats else None
    iacc0 = (
        jnp.zeros(INTEGRITY_LEN, dtype) + zero_f if integrity else None
    )
    cvec0 = None
    if conv_state is not None:
        from ..obs.convergence import CONV_LEN

        cvec0 = jnp.zeros(CONV_LEN, dtype) + zero_f
    pacc0 = jnp.zeros(MEGA_PHYS_LEN, dtype) + zero_f
    carry = (origin, origin, elem, material_id, weight, group,
             alive.astype(bool), flux, prev_even, conv_state, sacc0,
             iacc0, cvec0, pacc0, jnp.zeros((), nseg_dtype))
    (origin, dest, elem, mat, weight, group, alive, flux, prev_even,
     conv, sacc, iacc, cvec, pacc, nseg) = jax.lax.fori_loop(
        0, n_moves, body, carry
    )
    readback = pack_megastep_tail(sacc, nseg, iacc, cvec, pacc, dtype)
    return MegastepResult(
        position=origin,
        dest=dest,
        elem=elem,
        material_id=mat,
        weight=weight,
        group=group,
        alive=alive,
        flux=flux,
        readback=readback,
        prev_even=prev_even,
        conv_state=conv,
    )


_megastep_jit = jax.jit(
    megastep_impl,
    static_argnames=(
        "n_moves",
        "n_groups",
        "survival_weight",
        "downscatter",
        "eps_near",
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
        "rel_err_target",
        "batch_moves",
    ),
    # Donation matches the per-move trace exactly: the flux /
    # convergence / batch-sd accumulators are donated (always
    # device-produced chains), the per-lane STATE is not — after a
    # checkpoint/rollback restore those arrays can zero-copy-alias the
    # snapshot's host buffers on the CPU backend, and a donated alias
    # would let XLA scribble over the retry anchor.
    donate_argnames=("flux", "prev_even", "conv_state"),
)


def megastep(*args, **kwargs):
    if kwargs.get("tally_scatter", "auto") == "auto":
        kwargs = dict(
            kwargs,
            tally_scatter=resolve_tally_scatter(
                "auto", kwargs.get("flux", args[8] if len(args) > 8 else None)
            ),
        )
    return _megastep_jit(*args, **kwargs)


megastep.__doc__ = megastep_impl.__doc__


# --------------------------------------------------------------------- #
# Truncated-lane escalation (resilience)
# --------------------------------------------------------------------- #
def merge_recorded_xpoints(xa, ka, xb, kb, rows_a, rows_b) -> None:
    """Append re-walk crossing points after a prior attempt's, IN PLACE:
    for each pair (rows_a[j], rows_b[j]), ``xb``'s recorded points go
    after ``xa``'s, capped at the K-point buffer; counts keep
    incrementing past K (the caller-visible truncation signal). The ONE
    definition of the cap/overflow semantics for both the single-chip
    and partitioned escalation paths. Host-side numpy — cold path."""
    K = xa.shape[1]
    for ra, rb in zip(rows_a, rows_b):
        kept = min(int(ka[ra]), K)
        take = min(int(kb[rb]), K - kept)
        if take > 0:
            xa[ra, kept:kept + take] = xb[rb, :take]
    ka[rows_a] += kb[rows_b]


def _merge_xpoints(a, b, todo):
    """TraceResult-level wrapper over merge_recorded_xpoints for the
    single-chip re-walk (both buffers are full lane width)."""
    xa = np.asarray(a.xpoints).copy()
    ka = np.asarray(a.n_xpoints).copy()
    rows = np.nonzero(todo)[0]
    merge_recorded_xpoints(
        xa, ka, np.asarray(b.xpoints), np.asarray(b.n_xpoints),
        rows, rows,
    )
    return jnp.asarray(xa), jnp.asarray(ka)


def _merge_rewalk(a: TraceResult, b: TraceResult, todo) -> TraceResult:
    """Fold a re-walk result ``b`` (only ``todo`` lanes were in flight)
    into the prior attempt ``a``. Per-lane outputs come wholesale from
    ``b`` — parked lanes pass through trace untouched (position=origin,
    material/elem preserved) — while run totals (segments, crossings,
    stats, ledger) accumulate."""
    stats = None
    if a.stats is not None and b.stats is not None:
        stats = add_walk_stats(a.stats, b.stats)
        # max_crossings is a max, not a sum; truncated is the FINAL
        # count (b saw every still-unfinished lane as in flight).
        from ..obs import IDX

        stats = stats.at[IDX["max_crossings"]].set(
            jnp.maximum(a.stats[IDX["max_crossings"]],
                        b.stats[IDX["max_crossings"]])
        )
        stats = stats.at[IDX["truncated"]].set(b.stats[IDX["truncated"]])
    xp, kx = b.xpoints, b.n_xpoints
    if a.xpoints is not None:
        xp, kx = _merge_xpoints(a, b, todo)
    track = None
    if a.track_length is not None and b.track_length is not None:
        track = a.track_length + b.track_length
    integ = b.integrity
    if a.integrity is not None and b.integrity is not None:
        from ..integrity.invariants import IIDX as II

        # Per-attempt conservation is internally consistent (attempt b
        # walks the truncated lanes from their mid-walk positions, so
        # its scored and path sums cover exactly the continuation), so
        # the sums ADD; the residual maxes; bad_flux reflects the final
        # accumulator; lanes_flying stays the move's true in-flight
        # count (b saw only the retried subset) while lanes_done adds
        # (b's completions are lanes a left unfinished).
        integ = a.integrity + b.integrity
        integ = integ.at[II["max_residual"]].set(
            jnp.maximum(
                a.integrity[II["max_residual"]],
                b.integrity[II["max_residual"]],
            )
        )
        integ = integ.at[II["bad_flux"]].set(b.integrity[II["bad_flux"]])
        integ = integ.at[II["lanes_flying"]].set(
            a.integrity[II["lanes_flying"]]
        )
    return TraceResult(
        position=b.position,
        elem=b.elem,
        material_id=b.material_id,
        flux=b.flux,
        n_segments=a.n_segments + b.n_segments,
        n_crossings=a.n_crossings + b.n_crossings,
        done=b.done,
        xpoints=xp,
        n_xpoints=kx,
        track_length=track,
        stats=stats,
        integrity=integ,
    )


def rewalk_truncated(
    mesh,
    result: TraceResult,
    dest,
    weight,
    group,
    *,
    retries: int,
    trace_fn=None,
    **trace_kwargs,
):
    """Escalation policy for truncated walks: re-walk ONLY the truncated
    lanes with doubled ``max_crossings``, up to ``retries`` attempts,
    before declaring them lost.

    A truncated lane holds a mid-walk position and parent element, and
    flux is additive per segment, so continuing the walk from where it
    stopped scores exactly the segments the truncation dropped — no
    rescoring, no gaps. Each attempt doubles the static crossing bound
    (one extra compile per new bound, cold path only) and puts ONLY the
    still-unfinished lanes in flight; everything else rides through as
    parked.

    Args:
      result: the truncated TraceResult (``done`` has False lanes).
      dest, weight, group: the move's per-lane inputs (device order).
      retries: max re-walk attempts (bounded — this must terminate).
      trace_fn: the trace callable (default ``trace``; facades pass
        their checkify-routing ``_trace``).
      trace_kwargs: the original trace kwargs including
        ``max_crossings`` (the doubling base) and ``initial``.

    Returns ``(merged TraceResult, n_retried, n_lost)`` where
    ``n_retried`` sums lanes over attempts and ``n_lost`` counts lanes
    still unfinished after the last attempt.
    """
    if trace_fn is None:
        trace_fn = trace
    kwargs = dict(trace_kwargs)
    max_crossings = kwargs.pop("max_crossings")
    n_retried = 0
    for _ in range(retries):
        done_h = np.asarray(result.done)
        todo = np.logical_not(done_h)
        n_todo = int(todo.sum())
        if n_todo == 0:
            break
        n_retried += n_todo
        max_crossings *= 2
        r2 = trace_fn(
            mesh,
            result.position,
            dest,
            result.elem,
            jnp.asarray(todo),
            weight,
            group,
            result.material_id,
            result.flux,
            max_crossings=max_crossings,
            **kwargs,
        )
        result = _merge_rewalk(result, r2, todo)
    n_lost = int(np.sum(np.logical_not(np.asarray(result.done))))
    return result, n_retried, n_lost
