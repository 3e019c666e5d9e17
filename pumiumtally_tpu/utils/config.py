"""Runtime configuration for the tally framework.

Every hardcoded constant in the reference becomes a config field here
(reference: pumipic_particle_data_structure.cpp:123,206 tolerance 1e-8;
.cpp:256 migration period 100; .cpp:531 hardcoded 2 energy groups;
.cpp:298 output filename "fluxresult.vtk"; .cpp:789 GPU launch shape).
"""
from __future__ import annotations


def dense_ladder(n_particles: int) -> tuple:
    """The slot-planned dense compaction ladder (``compact_stages="auto"``
    and the benchmark default — one definition for both): stage widths
    track an exponential active-lane decay with mean ~15 crossings/move
    (scripts/plan_ladder.py scores it at 26.4 Mslots/step vs the
    3-stage schedule's 45.8 at bench statistics)."""
    M = n_particles
    return (
        (8, 5 * M // 8), (16, 3 * M // 8), (24, M // 4),
        (32, M // 8), (48, max(M // 16, 256)),
        (64, max(M // 32, 256)), (96, max(M // 64, 256)),
    )

import dataclasses
import os
from typing import Any

import jax.numpy as jnp

from .ladder import FIT


@dataclasses.dataclass(frozen=True)
class TallyConfig:
    """Static configuration for a :class:`~pumiumtally_tpu.api.PumiTally` run.

    Attributes:
      n_groups: number of energy groups in the flux tally. The reference
        hardcodes 2 (cpp:531, marked FIXME there); here it is configurable
        and defaults to 2 for drop-in parity.
      tolerance: geometric tolerance for the ray-tet face walk. Matches the
        reference's pumipic adjacency tolerance of 1e-8 (cpp:123, 206).
      max_crossings: static upper bound on element-boundary crossings per
        particle per move. The `lax.while_loop` exits early once every
        particle is done, so a generous bound costs nothing at runtime;
        it only guards against infinite cycling on degenerate meshes.
        ``None`` → derived from the mesh size at trace build time.
      compact_after: full-batch crossings before straggler compaction kicks
        in (ops/walk.py module docstring); None disables compaction. The
        facade disables it automatically for small particle counts.
        Setting it or ``compact_size`` away from the defaults (32, None)
        selects this single-stage schedule over the ``"fit"`` default
        (``resolve_compact_stages``); ``compact_stages=None`` selects
        it at the defaults.
      compact_size: straggler subset lane count (default n_particles // 8).
      compact_stages: ``"fit"`` (the default) for the fit ladder
        (ops/walk.py "Straggler compaction"): widths n, n/8 (at least
        256), n/64, ... down to about 1,024 lanes, each stage left as
        soon as its live lanes fit the next, for the batch-start search,
        facade moves and megastep moves alike; it yields to
        ``compact_after`` / ``compact_size`` set away from their
        defaults, and the partitioned facade keeps its single-stage
        schedule under it.
        Otherwise a multi-stage compaction schedule
        ((start_crossing, subset_size[, unroll]), ...) overriding the
        two knobs above (ops/walk.py docstring), None for those knobs
        alone, or the string
        ``"auto"`` for the slot-planned dense ladder — the best known
        schedule for walks with ~10-20 crossings per move
        (scripts/plan_ladder.py; BENCHMARKS.md "Slot-exact ladder
        planning"); ``"plan"`` for the executional planner
        (utils/ladder.plan_stages) at a mesh-density-estimated mean;
        ``"adaptive"`` (PumiTally only) to re-plan once from the
        MEASURED crossings/move after the first move. CAUTION:
        per-stage unroll >= 16 on a sparse (< 6 stage) schedule
        measured ~35x SLOWER on TPU (round-4 grid); the walk warns
        when it sees that shape.
      unroll: boundary crossings advanced per while-loop iteration
        (ops/walk.py). The TPU while_loop is dispatch-bound, so unrolling
        the body ~2x's throughput (scripts/sweep_unroll.py); done lanes
        make the extra evaluations no-ops.
      migration_period: every how many moves the particle axis is re-sorted
        by parent element for tally/gather locality (the TPU analog of the
        reference's `iter_count_ % 100` rebuild+migrate, cpp:256).
      sort_by_element: whether periodic element-sorting is enabled at all.
      dtype: floating dtype for coordinates/flux. float64 reproduces the
        reference oracle to 1e-8 on CPU; float32 (or bfloat16 mesh tables)
        is the TPU-native fast path.
      output_filename: VTK output path (reference hardcodes
        "fluxresult.vtk", cpp:298).
      score_squares: accumulate per-segment squared contributions in
        flux[..., 1] exactly like the reference (cpp:640-641). Turning it
        off halves scatter traffic when uncertainties are not needed.
      measure_time: accumulate per-phase wall-clock (TallyTimes parity,
        cpp:19-35). Device sync happens only when this is on, mirroring
        the PUMI_MEASURE_TIME fence guard (cpp:216-218).
      checkify_invariants: enable extra host-side input validation
        (finite positions/weights) on every call, the analog of the
        reference's OMEGA_H_CHECK_PRINTF device asserts (cpp:605-608).
        Group-bounds violations (cpp:634-638) are always rejected.
      record_xpoints: when set to K, every trace records each particle's
        first K boundary-crossing points, retrievable via
        PumiTally.intersection_points() (tracer getIntersectionPoints()
        parity, reference test:403-479). Composes with straggler
        compaction (the recording buffers ride the compaction rounds);
        costs one extra [n,3] store per crossing plus [S,K,3] traffic
        per compaction round. The default (None) pays nothing.
      robust: the walk's degeneracy-recovery machinery (ops/walk.py,
        "Degeneracy robustness"). False gives the reference tracer's
        truncate-on-degeneracy semantics (identical results on clean
        meshes, cheaper body); keep True unless the mesh is known
        well-behaved.
      tally_scatter / gathers: walk scheduling strategies (ops/walk.py
        docstring) — benchmark-tunable, numerically identical.
        tally_scatter "auto" resolves per backend at trace time
        (interleaved on TPU, pair elsewhere — round-4 hardware A/B).
      ledger: accumulate the per-particle track-length conservation
        ledger (TraceResult.track_length; required by the debug_checks
        consistency assert). One elementwise op per crossing — off only
        when squeezing the last percent from the hot loop.
      walk_stats: fold the per-move telemetry vector into the jitted
        walk (TraceResult.stats; obs/walk_stats.py schema — crossings,
        max crossings/particle, chase hops, truncations, compaction
        occupancy, segments, loop iters). The facade then reads ONE
        small vector per move instead of scanning the ``done`` array
        host-side, and feeds the flight recorder / ``telemetry()``.
        Cost is two int32 lanes updated elementwise per crossing (the
        ledger's cost class). False restores the pre-telemetry walk
        carry and the host-side truncation scan.

    quarantine: bad-particle quarantine (resilience/quarantine.py).
        When True, per-move inputs that would poison the additive flux
        accumulator — non-finite destinations or weights, destinations
        absurdly far outside the mesh bounding box — are MASKED out of
        the walk (the lane is parked and reports its held position,
        like flying=0) instead of raising (checkify_invariants) or
        scoring garbage (default). Quarantined lanes are counted
        per-lane and per-reason into ``telemetry()["quarantined"]`` and
        the ``pumi_quarantined_lanes_total`` counter. Off by default:
        parity runs should fail loudly on bad inputs.
    truncation_retries: escalation policy for truncated walks
        (resilience semantics; ops/walk.py rewalk_truncated). 0 (the
        default) keeps the warn-and-drop behavior. N > 0 re-walks ONLY
        the truncated lanes with doubled max_crossings, up to N
        attempts, before declaring them lost; recovered lanes score
        their remaining segments normally, lost lanes are counted in
        ``telemetry()`` (``pumi_lost_walks_total``) and still warn.
        The partitioned facade re-arms the SAME compiled step per
        attempt (additive crossing budget) instead of doubling the
        static bound — same bounded-retry contract without recompiling
        the partitioned program.

    sd_mode: standard-deviation accumulation strategy.
        "segment" (default, reference parity): the walk scatters (c, c²)
        per scored segment — slot 1 is Σc².
        "batch": the walk scatters only c (score_squares path measured
        −20% TPU step time, round-4 nosq A/B) and the facade folds ONE
        squared per-bin delta per MOVE into slot 1
        (core.tally.accumulate_batch_squares), so slot 1 is Σ(per-move
        bin totals)². The sd estimand is the same when particle scores
        are independent; the estimator has M−1 degrees of freedom
        (M = moves) instead of N·M−1, i.e. a noisier sd-of-sd by
        ~sqrt((N·M)/M) — quantified against the analytic variance
        oracle in tests/test_tally_oracle.py. Honored by PumiTally and
        PartitionedTally (per-chip elementwise fold over the owned
        slabs — halo scores are already on owner rows at step end);
        StreamingTallyPipeline rejects it (in-flight batches overlap).

    io_pipeline: move-loop I/O staging strategy (ops/staging.py).
        "packed" (default): destinations/flying/weights/groups are
        packed into ONE contiguous host record per move (one H2D
        transfer), the slot permutation is applied on device, and
        positions/material ids/done/stats come back as ONE coalesced
        device record (one D2H transfer) — bit-identical outputs to
        "legacy", structurally fewer transfers (asserted in CI via a
        jax.transfer_guard test).
        "overlap": "packed" plus double-buffered host staging records
        and deferred telemetry folding, so host-side bookkeeping of
        move k overlaps the device walk of move k+1 (flushed at every
        read surface; truncation warnings stay in-call).
        "legacy": the pre-pipeline multi-transfer path (one jnp.asarray
        per input array, per-array readbacks).
        The env var ``PUMI_TPU_IO_PIPELINE`` overrides the field (the
        CI faults step uses it to prove resilience holds under
        pipelining).  Both facades fall back to "legacy" automatically
        when record_xpoints or checkify_invariants is set (those paths
        need the un-packed result surface).

    integrity: the self-verification escalation mode
        (integrity/policy.py). "off" (default): no invariant programs,
        today's exact behavior. Any other mode folds the on-device
        conservation invariants into the walk programs (weighted
        scored-vs-path track length over completed lanes, flux
        non-negativity/finiteness, lane-count conservation — riding the
        PR 3 packed readback tail at zero extra transfers) and
        escalates violations: "warn" counts
        (``pumi_integrity_violations_total{check=...}``) and warns;
        "retry" raises a RETRYABLE ``TransientIntegrityViolation`` the
        ``ResilientRunner`` absorbs with its last-good rollback;
        "halt" raises fatally (the runner flushes a last-good
        checkpoint first). Outputs are bit-identical in every mode —
        the checks read, never write.
    integrity_tol: per-lane conservation-residual threshold (default:
        dtype- and mesh-scale-aware, integrity/invariants.py
        conservation_tolerance).
    audit_lanes: shadow-audit sample size K (integrity/audit.py). When
        > 0, every ``audit_every``-th move re-walks K randomly sampled
        completed lanes through an independent float64 host-reference
        walker and compares final positions and scored track lengths
        within ``audit_tol`` — a continuous SDC / kernel-regression
        detector. Mismatches are ``sdc_audit`` violations under the
        ``integrity`` policy; outcomes land in the flight recorder and
        ``telemetry()["integrity"]``. 0 (default) pays nothing.
    audit_every / audit_tol / audit_seed: audit cadence, comparison
        threshold (default dtype-aware) and sampling seed (the sample
        is deterministic per (seed, move), so replays audit the same
        lanes).
    move_deadline_s: dispatch-watchdog deadline around each compiled
        step + readback (integrity/watchdog.py). A hung dispatch
        surfaces as a retryable ``DispatchTimeoutError`` (counted under
        check="watchdog") instead of blocking forever, so the PR 2
        retry machinery re-arms and replays. None (default): no
        watchdog thread, zero overhead.

    convergence: statistical-convergence observability
        (obs/convergence.py). When True, both facades keep
        device-resident batch accumulators, fuse the per-bin
        relative-error reduction into the walk programs (riding the
        packed readback tail — the steady-state 1 H2D + 1 D2H
        invariant still holds), feed the ``pumi_rel_err_max`` /
        ``pumi_rel_err_mean`` / ``pumi_converged_fraction`` /
        ``pumi_fom`` gauges and per-batch flight records, and answer
        ``tally.converged()`` / ``tally.relative_error()`` /
        ``telemetry()["convergence"]``.  The reductions READ the
        accumulator and never write it: flux outputs are bit-identical
        with the flag on or off.  Works with ``score_squares=False``
        and ``sd_mode="batch"`` (only the even Σc entries are read).
        Off (default): nothing is traced, allocated, or transferred.
    rel_err_target: per-bin relative-error threshold defining a
        "converged" bin (the MCNP-style steering statistic; default
        0.05).
    batch_moves: moves per statistical batch (default: 1 — every move
        closes a batch, the finest monitoring grain). Larger values
        give fewer, better-estimated batches; ``tally.end_batch()``
        closes one explicitly regardless of cadence (and restarts it).
        Only meaningful with ``convergence=True``.
    converged_fraction: fraction of scored bins that must be at or
        below ``rel_err_target`` before ``tally.converged()`` answers
        True (default 0.95; at least 2 completed batches are always
        required — before that every scored bin reports rel-err 1).

    kernel: walk-kernel backend (ops/walk.py vs ops/walk_pallas.py).
        "xla" (default): the scattered XLA walk — every mesh size,
        every feature surface, the production default until a hardware
        window validates the Mosaic path.
        "pallas": the Mosaic kernel — VMEM-resident decoded walk table,
        blocked one-hot MXU gather, matrixized tally scatter flushed to
        HBM once per launch (ops/walk_pallas.py module docstring).
        Bitwise identical to the "xla" walk's FLAT loop
        (tests/test_kernel_pallas.py; straggler compaction is an
        XLA-path scheduling strategy the kernel ignores — with a
        compaction ladder active the backends agree numerically, not
        bit-for-bit, exactly like two different XLA schedules);
        targets the small/medium-mesh regime where the XLA walk's
        per-crossing HBM gather latency dominates. Outside its regime
        (no packed geo20 table, working set over the VMEM budget
        ``PUMI_TPU_PALLAS_VMEM_MB``) construction fails at resolve
        time; debug surfaces the kernel cannot carry (record_xpoints,
        checkify_invariants) and the fused megastep program are
        rejected at resolve time too (resolve_kernel).
        "auto": "pallas" whenever the workload fits the regime — packed
        table, VMEM budget, a real TPU backend (or
        ``PUMI_TPU_PALLAS_INTERPRET=1`` opting interpret mode in) and
        no conflicting feature — silently "xla" otherwise
        (walk_pallas.select_backend).
        The env var ``PUMI_TPU_KERNEL`` overrides the field (the CI
        kernel steps and the bench A/B drive it); an env-forced
        "pallas" degrades gracefully like PUMI_TPU_IO_PIPELINE does —
        over a config carrying a debug surface it downgrades to "xla"
        (resolve_kernel), and outside the kernel's regime (unpacked or
        over-budget mesh, the partitioned facade, the fused megastep
        program) the facades fall back to the XLA walk silently
        (select_backend(strict=False)) so one env var can blanket a
        whole suite — while the same conflict written INTO the config
        is an error.
        The partitioned facade accepts "auto" (resolving to its own
        fused per-chip program — the halo-table layout has no geo20
        packing to put in VMEM) and rejects an explicit "pallas" at
        construction.

    pallas_lane_block: the Mosaic kernel's one-hot block width B
        (ops/walk_pallas.py — the [B, ntet] blocked gather / [ntet, B]
        outer-product tally tile granularity; previously only reachable
        through the private ``lane_block=`` kwarg on the kernel entry).
        Validated at resolve time (``resolve_lane_block``): must be a
        positive power of two; clamped to the batch size; counted into
        the ``kernel_vmem_bytes`` working set that gates the VMEM
        budget (a larger block can push a mesh out of the Pallas
        regime).  Every rung of the ladder is BITWISE identical — the
        one-hot contraction is exact and the peel order is per-block
        ascending-lane (tests/test_tuning.py pins the parity) — so the
        knob is pure scheduling.  Env ``PUMI_TPU_PALLAS_LANE_BLOCK``
        beats the field.  None (default): the tuning database's winner
        for the shape class when one is active, else the kernel default
        (walk_pallas.DEFAULT_LANE_BLOCK = 128).  Ignored by the XLA
        walk.

    tuning: the autotuning database (tuning/db.py TUNING.json) the
        facades consult ONCE at construction for the knobs left at
        their defer values — kernel="auto"'s backend pick, the Pallas
        lane_block, megastep K.  A path enables it; None (default) and
        "off" disable it.  Env ``PUMI_TPU_TUNING=off|<path>`` beats the
        field.  Precedence per knob: an explicitly set knob (env
        override first, then the config field) always beats the
        database, and a database miss — no entry for the workload's
        shape class, or no database at all — falls back to today's
        defaults, so behavior without a database is byte-identical to
        a build without the tuning subsystem (every database winner is
        bitwise parity-gated by scripts/tune.py anyway).  A database
        captured under a different environment (backend / x64 / device
        count) or schema version is REFUSED at construction, exactly
        like CONTRACTS.json refuses cross-environment compares.

    megastep: moves fused per dispatch on the DEVICE-SOURCED move loop
        (``run_source_moves`` on both facades; ops/walk.py ``megastep``
        / ops/walk_partitioned.py ``make_partitioned_megastep``).  Each
        dispatch runs K complete moves — re-source (counter-based RNG
        keyed by (seed, move): isotropic direction, exponential flight
        distance from the per-region Σt table), walk (with migration
        rolled into the scanned body on the partitioned facade), and
        collision/roulette physics — as ONE compiled program, so the
        host performs 1 H2D + 1 D2H per K moves instead of per move.
        RNG streams are keyed by (seed, move, particle id), so
        megastep=K is bitwise identical to K megastep=1 dispatches
        (pinned by tests/test_megastep.py).  None (the default) means
        K=1 — per-dispatch moves, still device-sourced.  The OpenMC-
        facade ``move_to_next_location`` path is never affected: its
        destinations come from the caller, per the reference's
        per-advance-event contract.  Env override ``PUMI_TPU_MEGASTEP``
        beats the field (the CI faults step drives it).  Self-driven
        runs (models/transport.py, models/depletion.py, bench.py)
        default to megastep mode.

    Scope: ``ledger`` and ``gathers`` are honored by the single-chip and
    streaming-pipeline walks only. The partitioned walk
    (ops/walk_partitioned.py) always accumulates and migrates the ledger
    (it is the cross-cut conservation check) and always uses its own
    table layout; ``ledger=False`` / ``gathers`` are ignored there.
    ``walk_stats=False`` is likewise single-chip only: the partitioned
    walk always folds its per-chip stats vector (the counters double as
    the migration/truncation diagnostics).
    """

    n_groups: int = 2
    tolerance: float = 1e-8
    max_crossings: int | None = None
    compact_after: int | None = 32
    compact_size: int | None = None
    compact_stages: tuple | str | None = FIT
    unroll: int = 8
    migration_period: int = 100
    sort_by_element: bool = False
    dtype: Any = jnp.float32
    output_filename: str = "fluxresult.vtk"
    score_squares: bool = True
    measure_time: bool = False
    checkify_invariants: bool = False
    record_xpoints: int | None = None
    robust: bool = True
    tally_scatter: str = "auto"
    gathers: str = "merged"
    ledger: bool = True
    walk_stats: bool = True
    sd_mode: str = "segment"
    quarantine: bool = False
    truncation_retries: int = 0
    io_pipeline: str = "packed"
    integrity: str = "off"
    integrity_tol: float | None = None
    audit_lanes: int = 0
    audit_every: int = 1
    audit_tol: float | None = None
    audit_seed: int = 0
    move_deadline_s: float | None = None
    convergence: bool = False
    rel_err_target: float = 0.05
    batch_moves: int | None = None
    converged_fraction: float = 0.95
    megastep: int | None = None
    kernel: str = "xla"
    pallas_lane_block: int | None = None
    tuning: str | None = None

    def resolve_kernel(self) -> str:
        """Validate and return the walk-kernel knob ("xla" | "pallas" |
        "auto"; env ``PUMI_TPU_KERNEL`` beats the field).

        Invalid feature combos fail HERE, at resolve time, never deep
        inside dispatch: the Mosaic kernel keeps no per-crossing
        recording buffers (``record_xpoints``), cannot thread checkify
        device asserts (``checkify_invariants``), and does not ride the
        fused megastep program (``megastep``).  An env-forced "pallas"
        over a config carrying one of those debug surfaces downgrades
        to "xla" instead (the surface wins, exactly like
        ``PUMI_TPU_IO_PIPELINE`` vs record_xpoints in
        resolve_io_pipeline) so operational env sweeps never break
        debug runs; writing the conflict INTO the config is an error.
        The workload-dependent half of the decision (packed table, VMEM
        budget, backend) happens against a concrete mesh in
        ops/walk_pallas.py ``select_backend`` — also at facade
        construction, also before any dispatch."""
        env = os.environ.get("PUMI_TPU_KERNEL")
        kernel = env or self.kernel
        if kernel not in ("xla", "pallas", "auto"):
            raise ValueError(
                f"kernel must be 'xla', 'pallas' or 'auto': {kernel!r}"
            )
        if kernel == "pallas":
            from_env_sweep = bool(env) and self.kernel != "pallas"
            conflict = None
            if self.record_xpoints is not None:
                conflict = (
                    "kernel='pallas' cannot record intersection points "
                    "(the Mosaic kernel keeps no per-crossing recording "
                    "buffers); use kernel='xla' or drop record_xpoints"
                )
            elif self.checkify_invariants:
                conflict = (
                    "kernel='pallas' cannot thread checkify device "
                    "asserts through the Mosaic kernel; use "
                    "kernel='xla' or drop checkify_invariants"
                )
            elif self.megastep is not None:
                conflict = (
                    "kernel='pallas' does not compose with the fused "
                    "megastep program (megastep=K fuses source sampling "
                    "+ walk + physics into one scanned XLA body); use "
                    "kernel='xla' for device-sourced megastep runs, or "
                    "drop megastep and drive per-move dispatches"
                )
            if conflict is not None:
                if from_env_sweep:
                    return "xla"
                raise ValueError(conflict)
        return kernel

    def resolve_tuning(self) -> str | None:
        """The effective autotuning-database path (None = tuning off).
        Env ``PUMI_TPU_TUNING`` beats the field; ``"off"`` (either
        spelling) disables explicitly.  Pure knob resolution — loading,
        schema/environment validation and the shape-class lookup live
        in tuning/db.py ``resolve_tuned``."""
        env = os.environ.get("PUMI_TPU_TUNING")
        val = env if env else self.tuning
        if val in (None, "", "off"):
            return None
        return val

    def resolve_lane_block(
        self, n_particles: int | None = None, *, tuned=None
    ) -> int | None:
        """Validate and return the Pallas one-hot block width, or None
        for "kernel default" (walk_pallas.DEFAULT_LANE_BLOCK).

        Precedence: env ``PUMI_TPU_PALLAS_LANE_BLOCK`` > the
        ``pallas_lane_block`` field > the tuning database's winner for
        this shape class (``tuned``, a tuning.TunedDecision) > None.
        The value must be a positive power of two and is clamped to the
        batch size when ``n_particles`` is known (the kernel never runs
        a block wider than the batch); the caller feeds the result into
        ``select_backend``'s VMEM-budget check, so an oversized block
        is counted against ``PUMI_TPU_PALLAS_VMEM_MB`` rather than
        silently spilling."""
        env = os.environ.get("PUMI_TPU_PALLAS_LANE_BLOCK")
        if env:
            lb = int(env)
        elif self.pallas_lane_block is not None:
            lb = int(self.pallas_lane_block)
        elif tuned is not None and tuned.lane_block:
            lb = int(tuned.lane_block)
        else:
            return None
        if lb < 1 or (lb & (lb - 1)) != 0:
            raise ValueError(
                f"pallas_lane_block must be a positive power of two "
                f"(the one-hot block tiles the MXU): {lb}"
            )
        if n_particles is not None:
            lb = min(lb, max(int(n_particles), 1))
        return lb

    def resolve_megastep(self, *, tuned=None) -> int:
        """Effective moves-per-dispatch K for the device-sourced move
        loop (``run_source_moves``): the ``PUMI_TPU_MEGASTEP`` env
        override beats the field, the field beats the tuning database's
        winner (``tuned``, a tuning.TunedDecision consulted by the
        facades at construction), and with nothing set K is 1 (one
        dispatch per move).  Any K is bitwise identical to K=1 — RNG
        streams are keyed by (seed, move, particle id) — so a database
        K changes dispatch granularity, never results.

        Every ``run_source_moves`` entry point resolves the knob FIRST,
        so feature combos the fused megastep program cannot carry fail
        fast here — at resolve time, with an actionable message — for
        any K (even K=1 runs the megastep program): recorded
        intersection points and checkify device asserts are per-move
        facade surfaces."""
        env = os.environ.get("PUMI_TPU_MEGASTEP")
        if env:
            k = int(env)
        elif self.megastep is not None:
            k = int(self.megastep)
        elif tuned is not None and tuned.megastep:
            k = int(tuned.megastep)
        else:
            k = 1
        if k < 1:
            raise ValueError(f"megastep must be >= 1: {k}")
        if self.record_xpoints is not None:
            raise ValueError(
                "the device-sourced megastep program cannot record "
                "intersection points (record_xpoints); use the per-move "
                "facade path (move_to_next_location) or drop "
                "record_xpoints"
            )
        if self.checkify_invariants:
            raise ValueError(
                "the device-sourced megastep program cannot thread "
                "checkify device asserts (checkify_invariants); use the "
                "per-move facade path (move_to_next_location) or drop "
                "checkify_invariants"
            )
        return k

    def resolve_integrity(self) -> str:
        """Validate and return the self-verification mode
        (integrity/policy.py escalation ladder). Conservation invariants
        need the track-length ledger; the shadow-audit knobs must be
        coherent."""
        mode = self.integrity
        if mode not in ("off", "warn", "retry", "halt"):
            raise ValueError(
                "integrity must be 'off', 'warn', 'retry' or 'halt': "
                f"{mode!r}"
            )
        if mode != "off" and not self.ledger:
            raise ValueError(
                "integrity checks need the track-length conservation "
                "ledger: keep ledger=True (the default) or set "
                "integrity='off'"
            )
        if self.audit_lanes < 0:
            raise ValueError(
                f"audit_lanes must be >= 0: {self.audit_lanes}"
            )
        if self.audit_every < 1:
            raise ValueError(
                f"audit_every must be >= 1: {self.audit_every}"
            )
        if self.audit_lanes and not self.ledger:
            raise ValueError(
                "shadow audits compare the track-length ledger: keep "
                "ledger=True (the default) or set audit_lanes=0"
            )
        if (
            self.move_deadline_s is not None
            and self.move_deadline_s <= 0
        ):
            raise ValueError(
                f"move_deadline_s must be positive: {self.move_deadline_s}"
            )
        return mode

    def resolve_convergence(self) -> int | None:
        """Validate the convergence-observability knobs and return the
        effective moves-per-batch (None when the feature is off)."""
        if not self.convergence:
            if self.batch_moves is not None:
                raise ValueError(
                    "batch_moves only applies to convergence "
                    "observability: set convergence=True or drop it"
                )
            return None
        if not self.rel_err_target > 0:
            raise ValueError(
                f"rel_err_target must be positive: {self.rel_err_target}"
            )
        if not 0 < self.converged_fraction <= 1:
            raise ValueError(
                "converged_fraction must be in (0, 1]: "
                f"{self.converged_fraction}"
            )
        bm = 1 if self.batch_moves is None else int(self.batch_moves)
        if bm < 1:
            raise ValueError(f"batch_moves must be >= 1: {bm}")
        if self.checkify_invariants:
            # The checkify debug wrapper treats every trace kwarg as
            # static and cannot thread the device-resident batch
            # accumulators; the two debug surfaces are mutually
            # exclusive rather than silently dropping one.
            raise ValueError(
                "convergence observability does not compose with "
                "checkify_invariants (the checkified walk cannot carry "
                "the batch accumulators); disable one of them"
            )
        return bm

    def resolve_io_pipeline(self) -> str:
        """The effective move-loop I/O mode: the env override
        ``PUMI_TPU_IO_PIPELINE`` beats the field; debug surfaces that
        need the un-packed result (recorded intersection points,
        checkify invariants) force "legacy"."""
        mode = os.environ.get("PUMI_TPU_IO_PIPELINE") or self.io_pipeline
        if mode not in ("packed", "overlap", "legacy"):
            raise ValueError(
                "io_pipeline must be 'packed', 'overlap' or 'legacy': "
                f"{mode!r}"
            )
        if self.record_xpoints is not None or self.checkify_invariants:
            return "legacy"
        return mode

    def resolve_max_crossings(self, ntet: int) -> int:
        if self.max_crossings is not None:
            return self.max_crossings
        # A straight segment intersects a convex tet in a single interval, so
        # a walk can enter each element at most once: ntet (+ slack) is a
        # safe universal bound. The while_loop exits as soon as every
        # particle is done, so the generous bound costs nothing at runtime.
        return ntet + 64

    def resolve_compaction(self, n_particles: int) -> tuple[int | None, int | None]:
        """Compaction kicks in only where the straggler tail matters; tiny
        batches stay on the flat loop."""
        if self.compact_after is None or n_particles < 1024:
            return None, None
        size = self.compact_size
        if size is None:
            size = max(256, n_particles // 8)
        return self.compact_after, min(size, n_particles)

    def resolve_compact_stages(
        self, n_particles: int, ntet: int | None = None, *,
        fit_ladder: bool = True,
    ) -> tuple | str | None:
        """The schedule a facade hands its walk: a clamped stage
        schedule, ``"fit"``, or None (the single-stage knobs apply,
        through :meth:`resolve_compaction`). The one place that decides
        between the fit ladder and the other schedules.

        ``"auto"`` selects the dense ladder — the measured-best TPU
        schedule (7.60 Mseg/s vs the 3-stage schedule's 4.84, round-4
        hardware grid) — with stage STARTS scaled by mesh density when
        ``ntet`` is known: crossings/move grow with path/element-size,
        so the 55-cell-calibrated boundaries stretch by
        (ntet/998250)^(1/3), exactly the scaling bench.py applies and
        the 10M/119-cell rung validated against the DP planner.

        ``"plan"`` runs the executional ladder planner
        (utils/ladder.plan_stages) on the analytic decay at the same
        density-estimated mean — it scores ~9% under the dense ladder
        in the simulator (31.3M vs 34.2M slot-equivalents at bench
        stats) and adapts the whole shape, not just the starts, to the
        mesh; hardware A/B pending (wave-3 row staged), which is why
        "auto" still means the measured-best dense ladder.

        ``"fit"`` (the default) resolves to the fit ladder, or to None
        — the single-stage knobs — in two cases: ``fit_ladder=False``,
        for a walk that has no fit ladder (the partitioned walk), and
        ``compact_after`` / ``compact_size`` set away from their
        defaults (32, None), so that a config naming the single-stage
        knobs keeps their meaning. A config that wants the single-stage
        schedule at exactly those defaults sets ``compact_stages=None``."""
        if self.compact_stages is None or n_particles < 1024:
            return None
        if self.compact_stages == FIT:
            knobs = (self.compact_after, self.compact_size)
            defaults = (TallyConfig.compact_after, TallyConfig.compact_size)
            return FIT if fit_ladder and knobs == defaults else None
        if isinstance(self.compact_stages, str):
            density = (
                (max(ntet, 1) / 998250.0) ** (1.0 / 3.0)
                if ntet is not None
                else 1.0
            )
            if self.compact_stages == "auto":
                scale = max(1.0, density)
                return tuple(
                    (int(round(start * scale)), *rest)
                    for start, *rest in dense_ladder(n_particles)
                )
            if self.compact_stages in ("plan", "adaptive"):
                from .ladder import plan_stages

                # 14.9 = measured mean crossings/move at the bench
                # workload (55-cell unit box, mean_path 0.08).
                # "adaptive" starts from the same density estimate; the
                # PumiTally facade then RE-plans from the measured
                # crossings/move after the first move (_maybe_replan) —
                # the move-length statistics the density estimate
                # cannot see. One extra trace compile; results
                # identical up to fp summation order. Only PumiTally
                # replans — the other facades REJECT "adaptive" rather
                # than silently degrading to the static plan.
                return plan_stages(
                    n_particles, 14.9 * density, unroll=self.unroll
                ) or None
            raise ValueError(
                "unknown compact_stages string "
                f"{self.compact_stages!r}; expected 'fit', 'auto', "
                "'plan', 'adaptive' or an explicit "
                "((start, size[, unroll]), ...) schedule"
            )
        return tuple(
            (int(start), min(max(int(size), 1), n_particles),
             *(int(u) for u in rest))
            for start, size, *rest in self.compact_stages
        )
