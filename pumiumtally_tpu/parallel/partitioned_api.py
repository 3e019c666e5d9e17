"""PumiTally-shaped facade over the halo-partitioned distributed walk.

The single-chip facade (api.PumiTally) is the reference's 4-call contract
(images/public_methods_explanation.svg) on one chip's replicated mesh.
This module is the same contract for the PARTITION-MANDATORY scale
(BASELINE config 5: ~100M tets × 64 groups overflows both one chip's HBM
and the int32 flat tally key, ops/walk.py guard): the mesh is split into
Morton blocks with a buffered-picparts halo (parallel/mesh_partition.py),
each device walks its own particles with cross-chip migration
(ops/walk_partitioned.py), and the host sees the familiar surface:

    t = PartitionedTally(mesh, N, TallyConfig(...), n_parts=8)
    t.initialize_particle_location(pos, 3*N)
    t.move_to_next_location(dest, flying, w, g, mats, 3*N)   # repeat
    t.write_pumi_tally_mesh("flux.vtu")

Design notes (vs the device-resident single-chip facade):
  * Particle state lives HOST-side between calls and is redistributed to
    owner chips each move (distribute_particles). That is one host↔device
    round-trip per call — the partitioned facade optimizes for capacity
    first; a device-resident variant is the make_partitioned_step layer
    itself, which callers with a fixed batch can drive directly.
  * The global mesh object is retained for host-side duties (VTK
    coordinates, volumes for normalization); its numpy tables are the
    only full-mesh arrays touched after construction.
  * Flux accumulates in per-chip owned-element slabs across calls (halo
    rows return zeroed from every step, so the accumulation cannot
    double-fold guest scores); `raw_flux` assembles the global
    [ntet, groups, 2] view on demand.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import warnings

from ..api import _check_group_range, _out_param
from ..obs import (
    IDX,
    ConvergenceMonitor,
    TallyTelemetry,
    WALK_STATS_FIELDS,
    maybe_start_exporter,
    reduce_chip_conv,
    reduce_chip_stats,
)
from ..ops import staging
from ..ops.walk_partitioned import (
    collect_by_particle_id,
    distribute_particles,
    make_partitioned_step,
)
from ..utils.config import TallyConfig
from ..utils.profiling import annotate
from ..utils.timing import TallyTimes, phase_timer
from ..core.tally import accumulate_batch_squares
from .mesh_partition import assemble_global_flux, partition_mesh
from .particle_sharding import PARTICLE_AXIS as AXIS, make_device_mesh


def _merge_agg(a: dict, b: dict) -> dict:
    """Fold a re-walk attempt's aggregated chip stats into the move's
    running totals: sums everywhere except ``max_crossings`` (max over
    attempts) and ``truncated`` (the LATEST attempt saw every
    still-unfinished lane, so its count is the final one)."""
    out = {f: a[f] + b[f] for f in WALK_STATS_FIELDS}
    out["max_crossings"] = max(a["max_crossings"], b["max_crossings"])
    out["truncated"] = b["truncated"]
    out["occupancy"] = (
        round(out["occ_active"] / out["occ_slots"], 4)
        if out["occ_slots"]
        else None
    )
    return out


def _merge_got(got: dict, sub_trunc: np.ndarray, got2: dict) -> None:
    """Fold a re-walk attempt's collected outputs (rows = the retried
    lanes, ascending pid order — the same order ``sub_trunc`` selects)
    into the move's ``got`` dict IN PLACE."""
    for name in ("position", "material_id", "elem", "done"):
        got[name][sub_trunc] = got2[name]
    if "elem_global" in got:
        got["elem_global"][sub_trunc] = got2["elem_global"]
    if "track_length" in got:
        got["track_length"][sub_trunc] += got2["track_length"]
    if "xpoints" in got:
        from ..ops.walk import merge_recorded_xpoints

        rows_a = np.nonzero(sub_trunc)[0]
        merge_recorded_xpoints(
            got["xpoints"], got["n_xpoints"],
            got2["xpoints"], got2["n_xpoints"],
            rows_a, np.arange(rows_a.size),
        )


class PartitionedTally:
    """The 4-call tally contract over a partitioned mesh (see module
    docstring). Matches PumiTally semantics: element-0-centroid seeding,
    initial search without tallying, per-move copy-back of clipped
    positions / material ids / zeroed flying flags."""

    def __init__(
        self,
        mesh,
        num_particles: int,
        config: TallyConfig | None = None,
        *,
        n_parts: int | None = None,
        device_mesh=None,
        halo_layers: int = 1,
        cap: int | None = None,
        exchange_size: int | None = None,
        max_rounds: int | None = None,
        telemetry: TallyTelemetry | None = None,
    ):
        self.mesh = mesh
        self.num_particles = int(num_particles)
        self.config = config if config is not None else TallyConfig()
        # Telemetry + phase times: the PumiTally observability surface
        # (tally.telemetry(), TallyTimes) over the partitioned walk.
        # An elastic mesh-shrink rebuild (resilience/elastic.py) passes
        # the predecessor's telemetry in so counters, flight records
        # and the scrape registry stay one continuous history across
        # the re-partition.
        self.tally_times = TallyTimes()
        self._telemetry = (
            telemetry
            if telemetry is not None
            else TallyTelemetry("PartitionedTally")
        )
        if self.config.compact_stages == "adaptive":
            raise NotImplementedError(
                "compact_stages='adaptive' replans via PumiTally's "
                "post-move hook, which PartitionedTally does not have; "
                "use 'plan' (density-estimated) or an explicit schedule"
            )
        if self.config.sd_mode not in ("segment", "batch"):
            raise ValueError(
                f"sd_mode must be 'segment' or 'batch': "
                f"{self.config.sd_mode!r}"
            )
        if mesh.dtype != jnp.dtype(self.config.dtype):
            raise ValueError(
                f"mesh dtype {mesh.dtype} != config dtype "
                f"{self.config.dtype}"
            )
        if device_mesh is None:
            device_mesh = make_device_mesh(n_parts)
        self.device_mesh = device_mesh
        self.n_parts = int(device_mesh.shape[AXIS])
        self.partition = partition_mesh(
            mesh, self.n_parts, halo_layers=halo_layers
        )
        self.cap = int(cap) if cap is not None else self.num_particles
        if self.cap < self.num_particles:
            # The element-0 seed places EVERY particle on one chip before
            # the initial search, so any smaller cap is guaranteed to
            # fail at the first distribute; reject it up front. (A
            # sub-num_particles cap belongs to the device-resident
            # make_partitioned_step layer, where the caller controls
            # placement.)
            raise ValueError(
                f"cap={self.cap} < num_particles={self.num_particles}: "
                "the element-0 seeding of initialize_particle_location "
                "needs every particle to fit one chip"
            )
        # Straggler compaction resolves against the per-chip slot count
        # (cap), the lane width each walk phase actually sweeps. The
        # partitioned walk has no fit ladder: under the "fit" default it
        # gets the single-stage knobs.
        compact = self.config.resolve_compaction(self.cap)
        self._step_kwargs = dict(
            n_groups=self.config.n_groups,
            max_crossings=self.config.resolve_max_crossings(mesh.ntet),
            tolerance=self.config.tolerance,
            # sd_mode="batch": the walk scatters only Σc; the per-move
            # squared delta is folded in _run (same contract as
            # PumiTally / core.tally.accumulate_batch_squares).
            score_squares=(
                self.config.score_squares
                and self.config.sd_mode == "segment"
            ),
            unroll=self.config.unroll,
            robust=self.config.robust,
            tally_scatter=self.config.tally_scatter,
            record_xpoints=self.config.record_xpoints,
            compact_after=compact[0],
            compact_size=compact[1],
            compact_stages=self.config.resolve_compact_stages(
                self.cap, ntet=mesh.ntet, fit_ladder=False
            ),
            exchange_size=exchange_size,
            max_rounds=max_rounds,
            integrity=self.config.resolve_integrity() != "off",
            convergence=self.config.resolve_convergence() is not None,
            rel_err_target=self.config.rel_err_target,
            batch_moves=self.config.resolve_convergence() or 1,
        )
        self._steps: dict = {}
        # Walk-kernel backend: the partitioned walk is its own fused
        # per-chip program over halo-extended four-table layouts
        # (ops/walk_partitioned.py) — there is no geo20 packing to hold
        # in VMEM, so the Mosaic kernel's regime (ops/walk_pallas.py)
        # does not exist here. kernel="auto" therefore resolves to the
        # XLA step silently (the documented fallback policy), and so
        # does an env-forced "pallas" (the PUMI_TPU_KERNEL sweep must
        # degrade gracefully, not break partitioned suites); a
        # config-explicit kernel="pallas" is rejected NOW, at
        # construction, with the single-chip alternative named — never
        # mid-dispatch.
        # Autotuning database (tuning/): the partitioned walk never
        # rides the Mosaic kernel (no geo20 packing in the halo
        # layout, hence packed=False in the shape class), so the only
        # knob the database can steer here is megastep K — consulted
        # once, at construction, and only when neither the env nor the
        # config pinned one. Explicit knobs beat the database; a miss
        # changes nothing. NOTE: scripts/tune.py's current specs all
        # tune single-chip packed workloads, so unpacked entries only
        # exist when written deliberately (tests do; a partitioned
        # tuner rung is future work alongside the ROADMAP pod-scale
        # item) — until then this consult is armed plumbing that
        # resolves to a miss.
        from ..tuning import resolve_tuned

        self._tuned = resolve_tuned(
            self.config,
            ntet=mesh.ntet,
            n_particles=self.num_particles,
            n_groups=self.config.n_groups,
            dtype=self.config.dtype,
            packed=False,
        )
        self._kernel_policy = self.config.resolve_kernel()
        if self._kernel_policy == "pallas" and self.config.kernel == "pallas":
            raise ValueError(
                "kernel='pallas' is a single-chip walk backend "
                "(ops/walk_pallas.py: VMEM-resident geo20 table, "
                "small/medium-mesh regime); the mesh-partitioned walk "
                "runs its own fused per-chip program over halo tables "
                "with no packed layout to tile into VMEM. Use "
                "PumiTally(kernel='pallas') for meshes inside the VMEM "
                "budget, or kernel='auto'/'xla' here"
            )
        self._kernel = "xla"
        # Move-loop I/O pipelining (ops/staging.py; PumiTally mirror):
        # "packed"/"overlap" stage ONE record per walk each way through
        # the packed step; "overlap" double-buffers the host record and
        # defers telemetry folds past the next dispatch.
        self._io = self.config.resolve_io_pipeline()
        self._stager = staging.HostStager(
            depth=2 if self._io == "overlap" else 1
        )
        self._pending_folds: list = []
        # Flat per-chip slabs [n_parts, max_local*n_groups*2]: the TPU
        # production layout (3-D slabs pad their minor dim 2 → 128 under
        # the (8,128) tile; core.tally.make_flux). The 3-D view is
        # assembled host-side in raw_flux.
        self.flux_slabs = jax.device_put(
            jnp.zeros(
                (
                    self.n_parts,
                    self.partition.max_local * self.config.n_groups * 2,
                ),
                self.config.dtype,
            ),
            NamedSharding(device_mesh, P(AXIS)),
        )
        # Host-side particle state (PumiTally seeds at element 0's
        # centroid with parent element 0, api.py) — element 0's four
        # vertices only, no full-mesh centroid pass (core/state.py:53).
        c0 = np.asarray(mesh.coords, np.float64)[
            np.asarray(mesh.tet2vert[0])
        ].mean(axis=0, keepdims=True)
        self.positions = np.repeat(c0, self.num_particles, axis=0)
        self.elem_global = np.zeros(self.num_particles, np.int64)
        self.material_id = np.full(self.num_particles, -1, np.int32)
        self.iter_count = 0
        self.total_segments = 0
        self.total_rounds = 0
        self._initialized = False
        self._last_xpoints: tuple | None = None
        # Device-sourced move loop (run_source_moves): persistent host
        # physics lanes (weights/groups/alive, pid order — the per-move
        # facade takes these per call, the megastep carries them), the
        # device-resident slot-state cache, and the compiled megastep
        # program cache. The slot state lives on DEVICE between
        # megasteps — the 1 H2D + 1 D2H per K moves contract — and is
        # folded back to the host mirrors at every read surface
        # (_sync_source_state).
        self.weights = np.ones(self.num_particles)
        self.groups = np.zeros(self.num_particles, np.int32)
        self.alive = np.ones(self.num_particles, bool)
        self._src: dict | None = None
        self._mega_progs: dict = {}
        # Bad-particle quarantine (resilience/quarantine.py): same
        # contract as PumiTally — parked, counted, reported per-lane.
        self._quarantined: np.ndarray | None = None
        if self.config.quarantine:
            from ..resilience.quarantine import setup

            setup(self, mesh.coords, self.num_particles)
        # Self-verification layer (integrity/; the PumiTally contract):
        # on-device flux/lane invariants per chip, host-side
        # conservation over the migrating track ledger, shadow audits,
        # watchdog, and the facade-side fault hooks.
        self._integrity = self.config.resolve_integrity()
        self._finj = None
        self._auditor = None
        if (
            self._integrity != "off"
            or self.config.audit_lanes
            or self.config.move_deadline_s is not None
        ):
            from ..integrity import invariants
            from ..resilience.faultinject import FaultInjector

            self._finj = FaultInjector()
            scale = invariants.mesh_scale(mesh.coords)
            self._integrity_tol = invariants.conservation_tolerance(
                self.config.integrity_tol, self.config.dtype, scale,
                self.config.tolerance,
            )
            self._audit_tol = invariants.audit_tolerance(
                self.config.audit_tol, self.config.dtype, scale,
                self.config.tolerance,
            )
        if self.config.audit_lanes:
            from ..integrity.audit import HostReference

            self._auditor = HostReference(mesh)
        # sd_mode="batch": per-chip snapshot of the even (Σc) slab
        # entries as of the previous move. The halo fold has already
        # moved guest scores onto owner rows (and zeroed halo rows) by
        # the time the step returns, so the per-move owned-row delta is
        # the move's complete bin total — the fold is elementwise per
        # chip, no extra collective.
        self._prev_even = (
            jax.device_put(
                jnp.zeros(
                    (
                        self.n_parts,
                        self.partition.max_local * self.config.n_groups,
                    ),
                    self.config.dtype,
                ),
                NamedSharding(device_mesh, P(AXIS)),
            )
            if self.config.sd_mode == "batch"
            and self.config.score_squares
            else None
        )
        # Statistical-convergence observability (obs/convergence.py):
        # per-chip batch accumulators sharded like the flux slabs, the
        # replicated-per-chip counters, and the device-resident enable
        # gates (ones for main move dispatches, zeros for initial /
        # escalation dispatches — created ONCE, so steady-state moves
        # stage nothing extra).
        self._batch_moves = self.config.resolve_convergence()
        self._monitor = None
        self._conv = None
        if self._batch_moves is not None:
            sh = NamedSharding(device_mesh, P(AXIS))
            L = self.partition.max_local * self.config.n_groups
            self._conv = (
                jax.device_put(
                    jnp.zeros((self.n_parts, L), self.config.dtype), sh
                ),
                jax.device_put(
                    jnp.zeros((self.n_parts, L), self.config.dtype), sh
                ),
                jax.device_put(jnp.zeros(self.n_parts, jnp.int32), sh),
                jax.device_put(jnp.zeros(self.n_parts, jnp.int32), sh),
            )
            self._conv_on = jax.device_put(
                jnp.ones(self.n_parts, jnp.int32), sh
            )
            self._conv_off = jax.device_put(
                jnp.zeros(self.n_parts, jnp.int32), sh
            )
            self._monitor = ConvergenceMonitor(
                self._telemetry,
                rel_err_target=self.config.rel_err_target,
                converged_fraction=self.config.converged_fraction,
                batch_moves=self._batch_moves,
            )
        # Phase-boundary memory sample (tables + flux slabs are placed).
        self._telemetry.record_memory("initialization")
        # Live scrape endpoint (obs/exporter.py; PUMI_TPU_PROM_PORT).
        # Stopped by close(); the GC finalizer covers dropped tallies.
        self._exporter = maybe_start_exporter(self.metrics)
        if self._exporter is not None:
            import weakref

            weakref.finalize(self, self._exporter.stop)

    # ------------------------------------------------------------------ #
    def _check_finite(self, name: str, arr: np.ndarray) -> None:
        # Same opt-in host-side validation as PumiTally (api.py).
        if self.config.checkify_invariants and not np.isfinite(arr).all():
            raise ValueError(f"{name} contains non-finite values")

    def _quarantine(self, dest3, weights, move):
        """Bad-particle quarantine for one call — the PumiTally contract
        (api.py _quarantine) via the same shared
        resilience/quarantine.py apply(). Returns
        ``(dest3_for_staging, mask_or_None)``; never mutates the
        caller's buffers."""
        if not self.config.quarantine:
            return dest3, None
        from ..resilience import quarantine

        return quarantine.apply(self, dest3, weights, move)

    def quarantined_lanes(self) -> np.ndarray:
        """Cumulative per-lane quarantine counts, host pid order."""
        from ..resilience.quarantine import lanes

        return lanes(self)

    def _step(self, initial: bool):
        key = (bool(initial), self._io != "legacy")
        if key not in self._steps:
            self._steps[key] = make_partitioned_step(
                self.device_mesh,
                self.partition,
                initial=initial,
                packed_io=self._io != "legacy",
                **self._step_kwargs,
            )
        return self._steps[key]

    def _drain_pending(self) -> None:
        """Flush deferred telemetry folds (io_pipeline="overlap") — see
        PumiTally._drain_pending."""
        pending, self._pending_folds = self._pending_folds, []
        for fold in pending:
            fold()

    def _dispatch(self, fn, move: int, kind: str | None = None):
        """Partitioned-step dispatch + blocking readback under the
        watchdog deadline — the PumiTally._dispatch contract (the
        closure is mutation-free; a timed-out dispatch is abandoned and
        the supervisor's rollback rebuilds every donated buffer; the
        first dispatch of each kind runs un-deadlined because it
        includes XLA compilation)."""
        if self.config.move_deadline_s is None:
            return fn()
        key = kind or ("init" if move == 0 else "move")
        warm = getattr(self, "_watchdog_warm", None)
        if warm is None:
            warm = self._watchdog_warm = set()

        def body():
            if self._finj is not None and self._finj.maybe_hang(move):
                self.metrics.counter(
                    "pumi_injected_faults_total",
                    "faults injected through PUMI_TPU_FAULTS "
                    "(labeled by kind)",
                ).inc(kind="hang")
            return fn()

        if key not in warm:
            # Warm-up dispatch: un-deadlined (compilation), but still
            # through body() so a hang_at_move targeting it fires.
            warm.add(key)
            return body()
        from ..integrity.watchdog import (
            DispatchTimeoutError,
            run_with_deadline,
        )

        try:
            return run_with_deadline(
                body, self.config.move_deadline_s
            )
        except DispatchTimeoutError:
            self._telemetry.record_integrity(move, {}, ["watchdog"])
            raise

    def _self_verify(
        self, move, initial, got, moving, stats, pos_before, weights,
        n_lost,
    ) -> None:
        """Integrity evaluation over one partitioned move: the
        per-chip on-device counters (flux health, slot accounting),
        host-side per-lane conservation over the MIGRATING track
        ledger vs the facade's pre-move positions (cut-aware — a
        double-scored cut segment shows here), particle-id coverage
        (every moving pid accounted exactly once by the collect), and
        the shadow audit. Escalates per TallyConfig.integrity."""
        cfg = self.config
        if self._integrity == "off" and not cfg.audit_lanes:
            return
        from ..integrity import invariants, policy

        fields: dict = {}
        violations: list = []
        ivec = stats.pop("integrity_dev", None)
        if self._integrity != "off" and ivec is not None:
            ivec = np.asarray(ivec, np.int64)
            done = got["done"].astype(bool)
            n_moving = int(moving.sum())
            fields["bad_flux"] = int(ivec[:, 0].sum())
            fields["lanes_flying"] = n_moving
            fields["lanes_done"] = int(done.sum())
            if fields["bad_flux"] > 0:
                violations.append("flux")
            # Lane conservation: the device's occupied-slot count, the
            # collect's pid coverage (each moving pid exactly once) and
            # done + truncated == moving must all close.
            if (
                stats.get("pid_seen") != n_moving
                or stats.get("pid_unique") != n_moving
                or fields["lanes_done"] + int(n_lost) != n_moving
            ):
                violations.append("lanes")
            if not initial:
                # Host-side conservation over the migrating ledger.
                track = np.asarray(got["track_length"], np.float64)
                disp = np.linalg.norm(
                    np.asarray(got["position"], np.float64)
                    - pos_before,
                    axis=1,
                )
                resid = np.where(done, np.abs(track - disp), 0.0)
                w = np.asarray(weights, np.float64)[moving]
                fields["scored_wlen"] = float(
                    (w * np.where(done, track, 0.0)).sum()
                )
                fields["path_wlen"] = float(
                    (w * np.where(done, disp, 0.0)).sum()
                )
                fields["max_residual"] = (
                    float(resid.max()) if resid.size else 0.0
                )
                if fields["max_residual"] > self._integrity_tol:
                    violations.append("conservation")
        if (
            cfg.audit_lanes
            and self._auditor is not None
            and not initial
            and move >= 1
            and move % cfg.audit_every == 0
        ):
            out = self._run_audit(move, got, moving, pos_before)
            if out is not None:
                self._telemetry.record_audit(
                    move, out.audited, out.mismatches, out.skipped,
                    out.max_dev,
                )
                if out.mismatches:
                    violations.append("sdc_audit")
        if fields or violations:
            self._telemetry.record_integrity(move, fields, violations)
        policy.escalate(self._integrity, violations, move)

    def _run_audit(self, move, got, moving, pos_before):
        """Shadow-audit a K-lane sample of this move — entirely from
        arrays the facade already holds host-side (origins, global
        elements, collected positions and the migrated track ledger):
        zero extra transfers on the partitioned facade."""
        cfg = self.config
        done = got["done"].astype(bool)
        rows = np.nonzero(done)[0]  # rows within the moving subset
        if rows.size == 0:
            return None
        rng = np.random.default_rng([cfg.audit_seed, int(move)])
        sel = rng.choice(
            rows, size=min(cfg.audit_lanes, rows.size), replace=False
        )
        dests = self._audit_dest[sel]
        origins = pos_before[sel]
        elems = self._audit_elem_before[sel]
        prod_pos = np.asarray(got["position"], np.float64)[sel]
        track = np.asarray(got["track_length"], np.float64)[sel].copy()
        if self._finj is not None and self._finj.sdc_at(move):
            track[0] += 1e3 * self._audit_tol
            self.metrics.counter(
                "pumi_injected_faults_total",
                "faults injected through PUMI_TPU_FAULTS "
                "(labeled by kind)",
            ).inc(kind="sdc_walk")
        from ..integrity.audit import audit_sample

        return audit_sample(
            self._auditor, origins, dests, elems, prod_pos, track,
            tolerance=cfg.tolerance,
            max_crossings=self._step_kwargs["max_crossings"],
            tol=self._audit_tol,
        )

    def _maybe_inject_bitflip(self, move: int) -> None:
        """``bitflip_flux`` hook over the sharded slabs — the
        PumiTally._maybe_inject_bitflip contract."""
        if self._finj is None or not self._finj.bitflip_at(move):
            return
        flat = self.flux_slabs.reshape(-1)
        j = int(jnp.argmax(jnp.abs(flat)))
        v = flat[j]
        self.flux_slabs = (
            flat.at[j]
            .set(jnp.where(v == 0, jnp.asarray(jnp.nan, flat.dtype), -v))
            .reshape(self.flux_slabs.shape)
        )
        self.metrics.counter(
            "pumi_injected_faults_total",
            "faults injected through PUMI_TPU_FAULTS (labeled by kind)",
        ).inc(kind="bitflip_flux")

    def _run(self, dest, in_flight, weight, group, initial):
        # The per-move path owns the host-resident state contract: any
        # device-resident megastep slot state must fold back first.
        if self._src is not None:
            self._drop_source_state()
        field = (
            "initialization_time" if initial else "total_time_to_tally"
        )
        t_before = getattr(self.tally_times, field)
        with annotate(
            "PartitionedTally."
            + ("initial_search" if initial else "move")
        ), phase_timer(self.tally_times, field, True) as timer:
            got, moving, stats = self._run_inner(
                dest, in_flight, weight, group, initial
            )
            if self.config.measure_time:
                timer.sync(self.flux_slabs)
        kind = "initial_search" if initial else "move"
        move_no = self.iter_count + (0 if initial else 1)
        agg = stats.pop("agg")
        conv_dev = stats.pop("conv_dev", None)
        seconds = getattr(self.tally_times, field) - t_before
        if self._io == "overlap" and not initial:
            # Defer the fold so this move's bookkeeping overlaps the
            # next move's device walk (drained in _walk_once after the
            # step dispatch, and at every read surface).
            synced = self.config.measure_time
            self._pending_folds.append(
                lambda: self._telemetry.record_walk(
                    kind, move_no, agg, seconds=seconds, synced=synced,
                    **stats,
                )
            )
        else:
            self._telemetry.record_walk(
                kind, move_no, agg,
                seconds=seconds,
                synced=self.config.measure_time,
                **stats,
            )
        if self._monitor is not None and not initial and conv_dev is not None:
            # Reduce the per-chip convergence partials and feed the
            # monitor; deferred with the other host folds under
            # "overlap" (drained at every read surface).
            fields = reduce_chip_conv(conv_dev)
            secs_total = self.tally_times.total_time_to_tally
            if self._io == "overlap":
                self._pending_folds.append(
                    lambda: self._monitor.update(fields, secs_total)
                )
            else:
                self._monitor.update(fields, secs_total)
        return got, moving

    def _run_inner(self, dest, in_flight, weight, group, initial):
        moving = in_flight != 0
        pos_before = None
        if self._integrity != "off" or self.config.audit_lanes:
            # Pre-move positions for the host-side conservation check
            # (the walk folds positions back into self.positions in
            # place). The destination/element copies are audit-only —
            # skipped on the audit-off hot path.
            pos_before = np.asarray(
                self.positions[moving], np.float64
            ).copy()
            if self.config.audit_lanes:
                self._audit_dest = np.asarray(
                    dest[moving], np.float64
                ).copy()
                self._audit_elem_before = self.elem_global[moving].copy()
        got, stats = self._walk_once(dest, moving, weight, group, initial)
        n_lost = stats["agg"]["truncated"]
        n_re = 0
        retries = self.config.truncation_retries
        n = self.num_particles
        while n_lost and retries > 0:
            # Truncation escalation over the partitioned walk: re-walk
            # ONLY the truncated lanes. Each attempt re-arms the SAME
            # compiled step (an additive crossing/round budget) instead
            # of doubling the static bound, which would compile a fresh
            # partitioned program per attempt (TallyConfig docstring).
            # Positions/elements were already folded back mid-walk, so
            # the re-walk continues exactly where truncation stopped.
            retries -= 1
            sub_trunc = ~got["done"].astype(bool)
            trunc = np.zeros(n, bool)
            trunc[np.nonzero(moving)[0][sub_trunc]] = True
            n_re += int(trunc.sum())
            # first=False: escalation re-walks never advance the batch
            # cadence (their scores enter the next closed batch).
            got2, stats2 = self._walk_once(
                dest, trunc, weight, group, initial, first=False
            )
            _merge_got(got, sub_trunc, got2)
            stats["agg"] = _merge_agg(stats["agg"], stats2["agg"])
            if "integrity_dev" in stats2:
                # Latest attempt's on-device counters carry the FINAL
                # flux health; pid coverage keeps attempt 1's
                # full-moving-set view.
                stats["integrity_dev"] = stats2["integrity_dev"]
            for f in ("rounds", "dropped", "migrated", "adopted",
                      "h2d_bytes", "h2d_transfers", "d2h_bytes",
                      "d2h_transfers"):
                stats[f] += stats2[f]
            for f in ("per_chip_segments", "per_chip_crossings"):
                stats[f] = [
                    x + y for x, y in zip(stats[f], stats2[f])
                ]
            n_lost = stats2["agg"]["truncated"]
        if self._prev_even is not None and not initial:
            # sd_mode="batch": ONE squared per-move delta, folded after
            # any escalation re-walks so the move's full bin total (not
            # per-attempt splits) enters slot 1 — trailing-axis stride-2,
            # elementwise per chip; guest scores are already on owner
            # rows (halo rows zeroed) when each step returns.
            self.flux_slabs, self._prev_even = accumulate_batch_squares(
                self.flux_slabs, self._prev_even
            )
        if n_re or n_lost:
            self._telemetry.record_rewalk(
                self.iter_count + (0 if initial else 1), n_re, n_lost
            )
        if self.config.record_xpoints is not None:
            # Full host order; parked lanes record nothing (count 0).
            xp = np.zeros(
                (n, int(self.config.record_xpoints), 3), np.float64
            )
            counts = np.zeros(n, np.int32)  # PumiTally contract dtype
            xp[moving] = got["xpoints"]
            counts[moving] = got["n_xpoints"]
            self._last_xpoints = (xp, counts)
        if n_lost:
            warnings.warn(
                f"{n_lost} partitioned walk(s) truncated (max_crossings="
                f"{self._step_kwargs['max_crossings']} or the migration "
                "round bound); tallies for them are incomplete. Raise "
                "TallyConfig.max_crossings / max_rounds or set "
                "truncation_retries for bounded re-walk escalation.",
                RuntimeWarning,
                stacklevel=4,
            )
        # Self-verification (integrity/) + the bitflip fault hook
        # (caught by the NEXT move's on-device flux invariant).
        move = self.iter_count + (0 if initial else 1)
        self._self_verify(
            move, initial, got, moving, stats, pos_before, weight,
            n_lost,
        )
        if not initial:
            self._maybe_inject_bitflip(move)
        return got, moving, stats

    def _conv_in(self, initial: bool, first: bool):
        """The step's convergence 5-tuple (or None when the feature is
        off). The enable gate is 0 for initial-search and escalation
        re-walk dispatches: they must not advance the batch cadence —
        their scores are picked up by the next closed batch's delta."""
        if self._conv is None:
            return None
        gate = self._conv_on if (first and not initial) else self._conv_off
        return (*self._conv, gate)

    def _walk_once(self, dest, moving, weight, group, initial,
                   first=True):
        """One distribute → partitioned step → collect/fold pass over
        the ``moving`` subset (the pre-escalation ``_run_inner`` body).
        Dispatches to the packed pipeline unless io_pipeline="legacy"."""
        if self._io != "legacy":
            return self._walk_once_packed(
                dest, moving, weight, group, initial, first
            )
        placed = distribute_particles(
            self.partition,
            self.device_mesh,
            self.elem_global[moving],
            dict(
                origin=self.positions[moving],
                dest=dest[moving],
                weight=weight[moving],
                group=group[moving],
                material_id=self.material_id[moving],
            ),
            cap=self.cap,
        )
        flux_in = self.flux_slabs  # bound pre-closure: an abandoned
        # watchdog worker must consume the stale buffer, never the
        # restored live slabs (PumiTally._dispatch contract).
        conv_in = self._conv_in(initial, first)

        def _go():
            res = self._step(initial)(
                placed["origin"].astype(self.config.dtype),
                placed["dest"].astype(self.config.dtype),
                placed["elem"],
                jnp.zeros_like(placed["valid"]),
                placed["material_id"],
                placed["weight"].astype(self.config.dtype),
                placed["group"],
                placed["particle_id"],
                placed["valid"],
                flux_in,
                conv_in,
            )
            # The collect's np.asarray fetches are the blocking reads,
            # so they belong inside the watchdog-supervised closure
            # (mutation-free: state folds happen after dispatch).
            return res, collect_by_particle_id(
                res, int(moving.sum()), self.partition
            )

        res, got = self._dispatch(
            _go, self.iter_count + (0 if initial else 1)
        )
        self.flux_slabs = res.flux
        if self._conv is not None:
            self._conv = (
                res.conv_snap, res.conv_sumsq, res.conv_nb, res.conv_mv
            )
        n_dropped = int(np.asarray(res.n_dropped).sum())
        if n_dropped != 0:
            raise RuntimeError(
                "partitioned walk dropped immigrants: raise cap"
            )
        # Fold the moved particles back into full host order.
        self.positions[moving] = got["position"]
        self.elem_global[moving] = got["elem_global"]
        if not initial:
            self.material_id[moving] = got["material_id"]
        # Telemetry: the per-chip stats matrix (ONE [n_parts, 8] fetch
        # carrying segments/crossings/truncations/occupancy) plus the
        # per-shard migration counts from round_stats.
        sv = np.asarray(res.stats)
        agg = reduce_chip_stats(sv)
        rs = np.asarray(res.round_stats)  # [n_parts, 6, rounds_bound]
        n_rounds = int(np.asarray(res.n_rounds)[0])
        # Legacy-path I/O accounting: one device_put per distributed
        # field, one readback per collected/consumed result array.
        d2h_reads = [
            res.particle_id, res.valid, res.position, res.material_id,
            res.done, res.elem, res.weight, res.group, res.track_length,
            res.stats, res.round_stats, res.n_rounds, res.n_dropped,
        ] + ([res.xpoints, res.n_xpoints] if res.xpoints is not None
             else []) + (
            [res.integrity] if res.integrity is not None else []
        ) + (
            [res.convergence] if res.convergence is not None else []
        )
        stats = {
            "agg": agg,
            "rounds": n_rounds,
            "dropped": n_dropped,
            # Emigrants actually sent / immigrants adopted, summed over
            # chips and rounds (round_stats rows 1 and 4).
            "migrated": int(rs[:, 1].sum()),
            "adopted": int(rs[:, 4].sum()),
            "per_chip_segments": sv[:, IDX["segments"]].tolist(),
            "per_chip_crossings": sv[:, IDX["crossings"]].tolist(),
            "h2d_bytes": sum(int(v.nbytes) for v in placed.values()),
            "h2d_transfers": len(placed),
            "d2h_bytes": sum(int(a.nbytes) for a in d2h_reads),
            "d2h_transfers": len(d2h_reads),
        }
        if res.integrity is not None:
            stats["integrity_dev"] = np.asarray(res.integrity)
            pid_h = np.asarray(res.particle_id)
            sel = np.asarray(res.valid) & (pid_h >= 0)
            stats["pid_seen"] = int(sel.sum())
            stats["pid_unique"] = int(np.unique(pid_h[sel]).size)
        if res.convergence is not None:
            stats["conv_dev"] = np.asarray(res.convergence, np.float64)
        self.total_segments += agg["segments"]
        self.total_rounds += n_rounds
        return got, stats

    def _walk_once_packed(self, dest, moving, weight, group, initial,
                          first=True):
        """The _walk_once body over the packed pipeline (ops/staging.py):
        the slot distribution is packed into ONE carrier record and
        device_put once; the step unpacks it in-program and returns a
        coalesced readback record, so the whole pass is ONE H2D + ONE
        D2H.  Bit-identical to the legacy path (pinned by
        tests/test_io_pipeline.py)."""
        rec_h = staging.pack_partitioned_record(
            self.partition,
            self.elem_global[moving],
            dict(
                origin=self.positions[moving],
                dest=dest[moving],
                weight=weight[moving],
                group=group[moving],
                material_id=self.material_id[moving],
            ),
            self.cap,
            self.config.dtype,
            self._stager,
        )
        io = dict(
            h2d_bytes=int(rec_h.nbytes), h2d_transfers=1,
            d2h_bytes=0, d2h_transfers=0,
        )
        rec = jax.device_put(
            rec_h, NamedSharding(self.device_mesh, P(AXIS))
        )

        flux_in = self.flux_slabs  # bound pre-closure (see _walk_once)
        conv_in = self._conv_in(initial, first)

        deadline = self.config.move_deadline_s is not None

        def _go():
            res = self._step(initial)(
                rec, flux_in, *(conv_in if conv_in is not None else ())
            )
            if self._io == "overlap" and not deadline:
                # The previous move's deferred bookkeeping overlaps
                # this step's device execution. Under the watchdog the
                # closure must stay mutation-free (an abandoned worker
                # must never touch _pending_folds/telemetry), so the
                # drain moves after the dispatch.
                self._drain_pending()
            return res, jax.device_get(res.readback)

        res, host_rb = self._dispatch(
            _go, self.iter_count + (0 if initial else 1)
        )
        if self._io == "overlap" and deadline:
            self._drain_pending()
        self.flux_slabs = res.flux
        if self._conv is not None:
            self._conv = (
                res.conv_snap, res.conv_sumsq, res.conv_nb, res.conv_mv
            )
        io["d2h_bytes"] += int(host_rb.nbytes)
        io["d2h_transfers"] += 1
        parsed = staging.split_partitioned_readback(
            host_rb, self.n_parts, self.cap, self.config.dtype,
            integrity=self._integrity != "off",
            convergence=self._conv is not None,
        )
        got = staging.collect_packed(
            parsed, int(moving.sum()), self.partition
        )
        n_dropped = int(parsed["n_dropped"].sum())
        if n_dropped != 0:
            raise RuntimeError(
                "partitioned walk dropped immigrants: raise cap"
            )
        # Fold the moved particles back into full host order.
        self.positions[moving] = got["position"]
        self.elem_global[moving] = got["elem_global"]
        if not initial:
            self.material_id[moving] = got["material_id"]
        sv = parsed["stats"]
        agg = reduce_chip_stats(sv)
        rs = parsed["round_stats"]
        n_rounds = int(parsed["n_rounds"][0])
        stats = {
            "agg": agg,
            "rounds": n_rounds,
            "dropped": n_dropped,
            "migrated": int(rs[:, 1].sum()),
            "adopted": int(rs[:, 4].sum()),
            "per_chip_segments": sv[:, IDX["segments"]].tolist(),
            "per_chip_crossings": sv[:, IDX["crossings"]].tolist(),
            **io,
        }
        if "integrity" in parsed:
            stats["integrity_dev"] = parsed["integrity"]
            pid_h = parsed["particle_id"]
            sel = parsed["valid"] & (pid_h >= 0)
            stats["pid_seen"] = int(sel.sum())
            stats["pid_unique"] = int(np.unique(pid_h[sel]).size)
        if "convergence" in parsed:
            stats["conv_dev"] = parsed["convergence"]
        self.total_segments += agg["segments"]
        self.total_rounds += n_rounds
        return got, stats

    # ------------------------------------------------------------------ #
    # Megastep: device-sourced fused move loop
    # (ops/walk_partitioned.py make_partitioned_megastep)
    # ------------------------------------------------------------------ #
    def _ensure_source_state(self, weights, groups, alive) -> None:
        """Install caller-provided physics lanes (dropping any stale
        device cache) and build the device-resident slot state from the
        host mirrors when absent — ONE distribute, cold path; the
        steady-state megastep stages only the move counter."""
        n = self.num_particles
        if self._src is not None and any(
            a is not None for a in (weights, groups, alive)
        ):
            # Re-staging SOME lanes must not rewind the others: fold the
            # live device slot state back into the host mirrors first so
            # the rebuild below continues from the current positions /
            # elements and any omitted physics lane (the distributed
            # equivalent of PumiTally._stage_source_lanes, which
            # replaces only the given lanes in live device state).
            self._sync_source_state()
        if weights is not None:
            self.weights = np.asarray(
                weights, np.float64
            ).reshape(-1)[:n].copy()
            self._src = None
        if groups is not None:
            g = np.asarray(groups, np.int32).reshape(-1)[:n]
            _check_group_range(g, self.config.n_groups)
            self.groups = g.copy()
            self._src = None
        if alive is not None:
            self.alive = np.asarray(
                alive
            ).astype(bool).reshape(-1)[:n].copy()
            self._src = None
        if self._src is not None:
            return
        placed = distribute_particles(
            self.partition,
            self.device_mesh,
            self.elem_global,
            dict(
                origin=self.positions,
                dest=self.positions,
                weight=self.weights,
                group=self.groups,
                material_id=self.material_id,
            ),
            cap=self.cap,
        )
        pid_h = np.asarray(placed["particle_id"])
        alive_slot = np.zeros(pid_h.shape[0], bool)
        sel = pid_h >= 0
        alive_slot[sel] = self.alive[pid_h[sel]]
        sh = NamedSharding(self.device_mesh, P(AXIS))
        self._src = {
            "pos": placed["origin"].astype(self.config.dtype),
            "elem": placed["elem"],
            "material_id": placed["material_id"],
            "weight": placed["weight"].astype(self.config.dtype),
            "group": placed["group"],
            "pid": placed["particle_id"],
            "valid": placed["valid"],
            "alive": jax.device_put(jnp.asarray(alive_slot), sh),
        }

    def _sync_source_state(self) -> None:
        """Fold the device-resident slot state back into the host
        mirrors (positions/elem_global/material_id/weights/groups/
        alive) — the read-surface/checkpoint contract; the device cache
        stays live for the next megastep."""
        if self._src is None:
            return
        src = self._src
        pid = np.asarray(src["pid"])
        valid = np.asarray(src["valid"])
        sel = valid & (pid >= 0)
        idx = pid[sel]
        self.positions[idx] = np.asarray(src["pos"], np.float64)[sel]
        self.material_id[idx] = np.asarray(src["material_id"])[sel]
        self.weights[idx] = np.asarray(src["weight"], np.float64)[sel]
        self.groups[idx] = np.asarray(src["group"])[sel]
        alive = np.zeros(self.num_particles, bool)
        alive[idx] = np.asarray(src["alive"])[sel]
        self.alive = alive
        cap = pid.shape[0] // self.n_parts
        chip = (np.arange(pid.shape[0]) // cap)[sel]
        self.elem_global[idx] = self.partition.local2global[
            chip, np.asarray(src["elem"])[sel]
        ]

    def _drop_source_state(self) -> None:
        """Sync + invalidate the device slot cache (the per-move path
        and cross-layout restores own the host-resident contract)."""
        self._sync_source_state()
        self._src = None

    def _rng_key(self, seed: int):
        """Device PRNG key for one source seed, staged once (cold) and
        reused by every megastep dispatch of that stream. Placed
        REPLICATED across the device mesh explicitly — an uncommitted
        single-device key would be re-replicated on every dispatch,
        which jax.transfer_guard rightly flags."""
        from ..ops.source import staged_rng_key

        self._rng_key_cache = staged_rng_key(
            seed, getattr(self, "_rng_key_cache", None),
            put=lambda k: jax.device_put(
                k, NamedSharding(self.device_mesh, P())
            ),
        )
        return self._rng_key_cache[1]

    def _mega_prog(self, src, k: int):
        """Compiled megastep program for (source physics, chunk size) —
        built once per distinct pair (at most two chunk sizes per run:
        K and the remainder; the RNG seed is a runtime input and never
        forces a rebuild)."""
        key = (src.physics_key(), int(k))
        if key not in self._mega_progs:
            from ..ops.source import near_epsilon
            from ..ops.walk_partitioned import make_partitioned_megastep

            cfg = self.config
            sig, ab = src.tables(np.asarray(self.mesh.class_id))
            l2g = np.clip(
                np.asarray(self.partition.local2global), 0,
                self.mesh.ntet - 1,
            )
            cls_local = np.asarray(self.mesh.class_id)[l2g]
            cls_local = np.clip(cls_local, 0, sig.shape[0] - 1)
            kw = dict(self._step_kwargs)
            for dup in ("record_xpoints", "integrity", "convergence",
                        "n_groups"):
                kw.pop(dup, None)
            self._mega_progs[key] = make_partitioned_megastep(
                self.device_mesh,
                self.partition,
                n_moves=int(k),
                n_total=self.num_particles,
                n_groups=cfg.n_groups,
                sigma_local=sig[cls_local],
                absorb_local=ab[cls_local],
                eps_near=near_epsilon(np.asarray(self.mesh.coords)),
                survival_weight=float(src.survival_weight),
                downscatter=float(src.downscatter),
                dtype=cfg.dtype,
                integrity=self._integrity != "off",
                convergence=self._conv is not None,
                **kw,
            )
        return self._mega_progs[key]

    def run_source_moves(
        self,
        n_moves: int,
        source=None,
        weights: np.ndarray | None = None,
        groups: np.ndarray | None = None,
        alive: np.ndarray | None = None,
    ) -> dict:
        """Run ``n_moves`` DEVICE-SOURCED moves over the partitioned
        walk — the PumiTally.run_source_moves contract with migration
        rolled into the scanned body: each dispatch fuses
        ``TallyConfig(megastep=K)`` complete moves (re-source → walk →
        migrate/halo-fold → physics), so the host performs ONE H2D (the
        move counter) and ONE D2H (the per-chip tails) per K moves.
        Slot state stays device-resident between megasteps and is
        folded back to the host mirrors at every read surface; RNG
        streams are keyed by (seed, move, particle id), so results are
        bitwise identical for any K and across checkpoint restores of
        the same partition layout. Shadow audits, truncation re-walks
        and the host-side per-lane conservation check are per-move-
        facade features and do not ride the megastep (the on-device
        flux invariant still does)."""
        assert self._initialized, (
            "initialize_particle_location must run before source moves"
        )
        cfg = self.config
        # Feature combos the fused program cannot carry fail at RESOLVE
        # time (utils/config.resolve_megastep: record_xpoints /
        # checkify_invariants), before any staging or dispatch. The
        # tuning database's K applies only when neither the env nor
        # the config pinned one (bitwise identical for any K).
        K = cfg.resolve_megastep(tuned=self._tuned)
        from ..ops import staging
        from ..ops.source import SourceParams, phys_to_dict

        src = source if source is not None else SourceParams()
        rng_key = self._rng_key(src.seed)
        stage_io = dict(h2d_bytes=0, h2d_transfers=0)
        if self._src is None or any(
            a is not None for a in (weights, groups, alive)
        ):
            self._ensure_source_state(weights, groups, alive)
            stage_io = dict(
                h2d_bytes=sum(
                    # jax.Array.nbytes is metadata — np.asarray here
                    # would force a full D2H of every slot array just
                    # to read sizes.
                    int(v.nbytes) for v in self._src.values()
                ),
                h2d_transfers=len(self._src),
            )
        totals = {
            "moves": 0, "segments": 0, "collisions": 0, "escaped": 0,
            "rouletted": 0, "absorbed_weight": 0.0, "alive": 0,
            "truncated": 0,
        }
        done_moves = 0
        while done_moves < n_moves:
            k = min(K, n_moves - done_moves)
            mega = self._mega_prog(src, k)
            t_before = self.tally_times.total_time_to_tally
            with annotate("PartitionedTally.run_source_moves"), \
                    phase_timer(
                        self.tally_times, "total_time_to_tally", True
                    ) as timer:
                s = self._src
                # Replicated placement up front: the megastep's ONE H2D
                # per dispatch (an uncommitted scalar would trigger a
                # per-call device-to-device re-replication instead).
                move0 = jax.device_put(
                    np.int32(self.iter_count),
                    NamedSharding(self.device_mesh, P()),
                )
                io = dict(
                    h2d_bytes=4 + stage_io.pop("h2d_bytes", 0),
                    h2d_transfers=1 + stage_io.pop("h2d_transfers", 0),
                    d2h_bytes=0, d2h_transfers=0,
                )
                stage_io = {}
                flux_in, conv_in = self.flux_slabs, self._conv
                prev_in = self._prev_even
                conv_args = (
                    tuple(conv_in) if conv_in is not None else ()
                )

                def _go():
                    res = mega(
                        s["pos"], s["elem"], s["material_id"],
                        s["weight"], s["group"], s["pid"], s["valid"],
                        s["alive"], flux_in, move0, rng_key,
                        *conv_args, prev_even=prev_in,
                    )
                    return res, jax.device_get(res.readback)

                # Amnesty key includes k: _mega_prog caches one compiled
                # program per chunk length, so the remainder chunk's
                # compile must not run under an armed steady-state
                # deadline.
                res, host_rb = self._dispatch(
                    _go, self.iter_count + 1, kind=f"megastep:{k}"
                )
                self.flux_slabs = res.flux
                if self._conv is not None:
                    self._conv = (
                        res.conv_snap, res.conv_sumsq, res.conv_nb,
                        res.conv_mv,
                    )
                if self._prev_even is not None:
                    self._prev_even = res.prev_even
                self._src = {
                    "pos": res.position,
                    "elem": res.elem,
                    "material_id": res.material_id,
                    "weight": res.weight,
                    "group": res.group,
                    "pid": res.particle_id,
                    "valid": res.valid,
                    "alive": res.alive,
                }
                self.iter_count += k
                io["d2h_bytes"] += int(host_rb.nbytes)
                io["d2h_transfers"] += 1
                parsed = staging.split_partitioned_megastep_tail(
                    host_rb, cfg.dtype,
                    integrity=self._integrity != "off",
                    convergence=self._conv is not None,
                )
                agg = reduce_chip_stats(parsed["stats"])
                n_rounds = int(parsed["n_rounds"][0])
                n_dropped = int(parsed["n_dropped"].sum())
                if n_dropped:
                    raise RuntimeError(
                        "partitioned megastep dropped immigrants: "
                        "raise cap"
                    )
                segs = agg["segments"]
                self.total_segments += segs
                self.total_rounds += n_rounds
                p = phys_to_dict(parsed["phys"])
                if p["truncated"]:
                    warnings.warn(
                        f"{p['truncated']} fused-move walk(s) truncated "
                        "inside the megastep (max_crossings or the "
                        "round bound); the lanes stay alive and "
                        "continue next move, but their tallies for the "
                        "truncated move are incomplete.",
                        RuntimeWarning,
                        stacklevel=3,
                    )
                if "integrity" in parsed:
                    from ..integrity import policy

                    ivec = np.asarray(parsed["integrity"], np.int64)
                    fields = {
                        "bad_flux": int(ivec[:, 0].sum()),
                        "lanes_done": int(ivec[:, 2].sum()),
                    }
                    violations = (
                        ["flux"] if fields["bad_flux"] > 0 else []
                    )
                    self._telemetry.record_integrity(
                        self.iter_count, fields, violations
                    )
                    policy.escalate(
                        self._integrity, violations, self.iter_count
                    )
                self._maybe_inject_bitflip(self.iter_count)
                if cfg.measure_time:
                    timer.sync(self.flux_slabs)
            self.tally_times.n_moves += k
            seconds = self.tally_times.total_time_to_tally - t_before
            self._telemetry.record_walk(
                "megastep", self.iter_count, agg,
                seconds=seconds, synced=cfg.measure_time, moves=k,
                rounds=n_rounds, collisions=p["collisions"],
                escaped=p["escaped"], rouletted=p["rouletted"],
                alive=p["alive"], **io,
            )
            if self._monitor is not None and "convergence" in parsed:
                self._monitor.update(
                    reduce_chip_conv(parsed["convergence"]),
                    self.tally_times.total_time_to_tally,
                )
            totals["moves"] += k
            totals["segments"] += segs
            for f in ("collisions", "escaped", "rouletted", "truncated"):
                totals[f] += p[f]
            totals["absorbed_weight"] += p["absorbed_weight"]
            totals["alive"] = p["alive"]
            done_moves += k
            if p["alive"] == 0:
                break
        return totals

    # ------------------------------------------------------------------ #
    def initialize_particle_location(
        self, init_particle_positions: np.ndarray, size: int | None = None
    ) -> None:
        """Parent-element search: fly from the element-0 seed to the true
        source positions; nothing is tallied (cpp:360-385 semantics)."""
        n = self.num_particles
        pos = np.ascontiguousarray(
            init_particle_positions, np.float64
        ).reshape(-1)
        if size is None:
            size = pos.size
        assert size == n * 3
        flags = np.ones(n, np.int8)
        dest, qmask = self._quarantine(pos[:size].reshape(-1, 3), None, 0)
        if qmask is not None:
            flags[qmask] = 0  # masked lanes stay at the seed
        self._check_finite("init_particle_positions", dest)
        self._run(
            dest,
            flags,
            np.ones(n),
            np.zeros(n, np.int32),
            initial=True,
        )
        self._initialized = True

    def move_to_next_location(
        self,
        particle_destinations: np.ndarray,
        flying: np.ndarray,
        weights: np.ndarray,
        groups: np.ndarray,
        material_ids: np.ndarray,
        size: int | None = None,
    ) -> None:
        """Advance in-flight particles, tally, and copy clipped positions /
        material ids back into the caller's arrays; flying flags reset to
        0 (the cpp:221-319 call-site contract, like api.PumiTally)."""
        assert self._initialized, (
            "initialize_particle_location must run before moves"
        )
        n = self.num_particles
        dest_flat = _out_param(
            particle_destinations, "particle_destinations",
            [np.float64], n * 3,
        )
        if size is None:
            size = dest_flat.size
        assert size == n * 3
        flying_flat = _out_param(flying, "flying", [np.int8], n)
        mats_flat = _out_param(material_ids, "material_ids", [np.int32], n)
        weights_h = np.asarray(weights, np.float64).reshape(-1)[:n]
        groups_h = np.asarray(groups, np.int32).reshape(-1)[:n]
        _check_group_range(groups_h, self.config.n_groups)
        fly = flying_flat[:n]
        dest = dest_flat[: n * 3].reshape(n, 3)
        if self.config.quarantine:
            # weights_h may alias the caller's array; sanitize must not
            # write through it (and a supervisor retry must re-see the
            # original destinations, so dest is staged via a copy too).
            weights_h = weights_h.copy()
            dest, qmask = self._quarantine(
                dest, weights_h, self.iter_count + 1
            )
            if qmask is not None:
                fly = np.where(qmask, np.int8(0), fly)
        self._check_finite("particle_destinations", dest)
        self._check_finite("weights", weights_h)

        got, moving = self._run(
            dest, fly, weights_h, groups_h, initial=False
        )
        self.iter_count += 1
        self.tally_times.n_moves += 1
        # Copy-back contract, including parked lanes: a flying=0 particle
        # is not advanced and reports its HELD position and material (the
        # single-chip facade's in_flight semantics, ops/walk.py).
        out_pos = dest_flat[: n * 3].reshape(n, 3)
        out_pos[moving] = got["position"]
        out_pos[~moving] = self.positions[~moving]
        mats_flat[:n][moving] = got["material_id"]
        mats_flat[:n][~moving] = self.material_id[~moving]
        flying_flat[:n] = 0

    # ------------------------------------------------------------------ #
    @property
    def raw_flux(self) -> np.ndarray:
        """Assembled global [ntet, n_groups, 2] accumulator. The device
        slabs are flat; the 3-D view exists host-side only."""
        slabs = np.asarray(self.flux_slabs).reshape(
            self.n_parts, self.partition.max_local, self.config.n_groups, 2
        )
        return assemble_global_flux(self.partition, slabs)

    def normalized_flux(self) -> np.ndarray:
        from ..core.tally import normalize_flux_host

        return normalize_flux_host(
            self.raw_flux,
            np.asarray(self.mesh.volumes),
            self.num_particles,
            max(self.iter_count, 1),
            sd_mode=self.config.sd_mode,
        )

    def reaction_rate(self, sigma: np.ndarray) -> np.ndarray:
        from ..core.tally import reaction_rate_host

        if self.config.sd_mode != "segment":
            # Same statistic mismatch as PumiTally.reaction_rate: the
            # derived squares column assumes per-segment squares.
            raise NotImplementedError(
                "reaction_rate requires sd_mode='segment'; config has "
                f"sd_mode={self.config.sd_mode!r}"
            )
        return reaction_rate_host(
            self.raw_flux,
            np.asarray(self.mesh.class_id),
            np.asarray(sigma, self.config.dtype),
        )

    def intersection_points(self) -> tuple[np.ndarray, np.ndarray]:
        """Per-particle boundary-crossing points of the LAST call, host
        order — the PumiTally.intersection_points contract over the
        partitioned walk (the buffers migrate with their particles, so
        each sequence is the particle's full path order across chips)."""
        if self.config.record_xpoints is None:
            raise ValueError(
                "set TallyConfig.record_xpoints=K to record intersection "
                "points (off by default: the hot path pays nothing)"
            )
        if self._last_xpoints is None:
            raise RuntimeError(
                "no trace has run yet: call initialize_particle_location "
                "(and move_to_next_location) before intersection_points"
            )
        return self._last_xpoints

    def save_checkpoint(
        self, filename: str, n_shards: int | None = None
    ) -> None:
        """Persist flux (assembled — partition-layout independent) +
        particle state + counters; resumable under a different part
        count or halo depth (utils/checkpoint.py). A ``.shards``
        filename writes the sharded two-phase layout — ``n_shards``
        splits, default one per mesh part."""
        from ..utils.checkpoint import save_partitioned_checkpoint

        self._drain_pending()
        save_partitioned_checkpoint(filename, self, n_shards=n_shards)

    def restore_checkpoint(self, filename: str) -> None:
        """Inverse of save_checkpoint; validates the mesh fingerprint and
        run shape before overwriting any state."""
        from ..utils.checkpoint import restore_partitioned_checkpoint

        self._drain_pending()
        restore_partitioned_checkpoint(filename, self)
        # Recorded crossing points describe the pre-restore trace, not
        # the restored state — the "LAST call" contract must not serve
        # them up after a resume.
        self._last_xpoints = None

    # ------------------------------------------------------------------ #
    # Statistical convergence (obs/convergence.py; PumiTally contract)
    # ------------------------------------------------------------------ #
    def _require_convergence(self):
        if self._monitor is None:
            raise ValueError(
                "convergence observability is off: construct with "
                "TallyConfig(convergence=True)"
            )
        return self._monitor

    def _reset_convergence(self) -> None:
        """Re-base the batch statistics on the CURRENT slabs (checkpoint
        restore / supervisor rollback; utils/checkpoint apply hooks)."""
        if self._monitor is None:
            return
        self._drain_pending()
        self._conv = (
            self.flux_slabs[:, 0::2],
            jnp.zeros_like(self._conv[1]),
            jnp.zeros_like(self._conv[2]),
            jnp.zeros_like(self._conv[3]),
        )
        self._monitor.reset()

    def end_batch(self) -> dict:
        """Close the current statistical batch NOW (the ``batch_moves``
        cadence restarts), fold it into the per-chip accumulators on
        device, reduce the per-chip partials, and return the refreshed
        convergence summary (PumiTally.end_batch contract)."""
        self._require_convergence()
        from ..obs.convergence import end_batch_fold

        self._drain_pending()
        self._conv, vec = end_batch_fold(
            self.flux_slabs, *self._conv,
            rel_err_target=self.config.rel_err_target,
        )
        return self._monitor.update(
            reduce_chip_conv(np.asarray(vec, np.float64)),
            self.tally_times.total_time_to_tally,
        )

    def converged(self) -> bool:
        """Caller-driven early stop (PumiTally.converged contract)."""
        self._require_convergence()
        self._drain_pending()
        return self._monitor.converged

    def relative_error(self) -> np.ndarray:
        """Per-bin [ntet, n_groups] float64 relative error, assembled
        from the per-chip batch accumulators (every bin is owned by
        exactly one chip, so assembly is a permutation — the same
        contract as raw_flux)."""
        self._require_convergence()
        from ..obs.convergence import host_relative_error

        self._drain_pending()
        snap, sumsq, nb, _ = self._conv
        g = self.config.n_groups

        def _assemble(slabs):
            return assemble_global_flux(
                self.partition,
                np.asarray(slabs).reshape(
                    self.n_parts, self.partition.max_local, g, 1
                ),
            )[:, :, 0]

        return host_relative_error(
            _assemble(snap), _assemble(sumsq),
            int(np.asarray(nb)[0]),
        )

    def write_pumi_tally_mesh(
        self, filename: str | None = None, uncertainty: bool = False
    ) -> str:
        """Single-file VTK of the assembled normalized flux (PumiTally
        contract, including the phase-time report and the
        ``uncertainty=True`` rel-err cell fields); per-host PVTU pieces
        live in parallel/multihost.py."""
        from ..io.vtk import write_flux_vtk

        self._drain_pending()
        rel = self.relative_error() if uncertainty else None
        with annotate("PartitionedTally.write_pumi_tally_mesh"), \
                phase_timer(self.tally_times, "vtk_file_write_time", True):
            name = filename or self.config.output_filename
            write_flux_vtk(
                name, self.mesh, self.normalized_flux(), rel_err=rel
            )
        self._telemetry.record_memory("vtk_write")
        self.tally_times.print_times()
        return name

    # ------------------------------------------------------------------ #
    def telemetry(self) -> dict:
        """Run-wide telemetry snapshot — the PumiTally.telemetry()
        contract over the partitioned walk, with per-move migration
        extras in the flight records (rounds, emigrants sent, immigrants
        adopted, per-chip segment/crossing splits) and the convergence
        block."""
        self._drain_pending()
        out = self._telemetry.snapshot(times=self.tally_times)
        out["convergence"] = (
            self._monitor.snapshot()
            if self._monitor is not None
            else {"enabled": False}
        )
        return out

    @property
    def metrics(self):
        """This tally's MetricsRegistry (Prometheus text via
        ``tally.metrics.render_prometheus()``)."""
        return self._telemetry.registry

    def close(self) -> None:
        """Release facade-owned background resources (the PumiTally
        contract): flush deferred folds, stop the scrape endpoint."""
        self._drain_pending()
        if self._exporter is not None:
            self._exporter.stop()
            self._exporter = None
