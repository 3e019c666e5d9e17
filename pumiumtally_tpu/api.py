"""Public facade: the PumiTally class.

Drop-in TPU-native equivalent of the reference's pimpl facade
(pumipic_particle_data_structure.h:20-47) with the same four entry points and
array contracts, NumPy in/out:

  * ``PumiTally(mesh, num_particles)``            — ctor (openmc_init site)
  * ``initialize_particle_location(pos, size)``   — initial parent-element
    search, never tallied (cpp:209-219; called from initialize_batch)
  * ``move_to_next_location(dest, flying, weights, groups, material_ids,
    size)`` — the per-event workhorse (cpp:221-264): walks every in-flight
    particle to its destination, scores track-length flux, clips at
    domain/material boundaries, and writes the clipped positions and new
    material ids back into the caller's arrays (the library doubles as the
    host code's surface-crossing oracle). The caller's ``flying`` array is
    reset to 0, matching copy_and_reset_flying_flag (cpp:316-319).
  * ``write_pumi_tally_mesh()``                   — normalize + VTK output
    (cpp:296-302) and TallyTimes report.

Because positions/flying/material_ids are *out-params* (raw pointers in the
reference), they must be writable C-contiguous numpy arrays of the right
dtype; anything else raises instead of silently dropping the write-back.

Unlike the reference there is no staging-buffer dance: host arrays are
device_put once per call, state lives on device between calls, and the single
fused trace kernel replaces the copy→search→callback→copy-back pipeline.
The reference's element-bucketed rebuild/migrate-every-100-moves
(cpp:256-258) becomes an optional periodic sort of the particle axis by
parent element (config.sort_by_element / migration_period) for gather/scatter
locality; the host-side pid order of every array contract is preserved via
the particle-id permutation.
"""
from __future__ import annotations

import warnings

import numpy as np
import jax
import jax.numpy as jnp

from .core.state import ParticleState, make_particle_state, seed_at_element_centroid
from .core.tally import (
    accumulate_batch_squares,
    make_flux,
    normalize_flux_host,
)
from .io.vtk import write_flux_vtk
from .mesh.core import TetMesh
from .obs import (
    ConvergenceMonitor,
    TallyTelemetry,
    conv_to_dict,
    maybe_start_exporter,
    stats_to_dict,
)
from .ops import staging
from .ops.seed_grid import build_seed_grid
from .ops.walk import trace, trace_packed
from .utils.config import TallyConfig
from .utils.profiling import CallSpans, annotate
from .utils.timing import TallyTimes, phase_timer


def _check_group_range(group: np.ndarray, n_groups: int) -> None:
    """Host-side group-bounds rejection shared by both facades (the
    reference hard-asserts on device, cpp:634-638)."""
    if group.size and (group.min() < 0 or group.max() >= n_groups):
        bad = group[(group < 0) | (group >= n_groups)]
        raise ValueError(
            f"energy group indices out of range [0, {n_groups}): "
            f"{np.unique(bad)!r}"
        )


def _out_param(arr, name: str, expected_dtypes, min_size: int) -> np.ndarray:
    """Validate an out-param array the way the reference's raw-pointer ABI
    implies: writable, C-contiguous, correctly typed and sized. Returns a
    flat view that shares memory with the caller's array."""
    if not isinstance(arr, np.ndarray):
        raise TypeError(
            f"{name} must be a numpy.ndarray (it is written back in place); "
            f"got {type(arr).__name__}"
        )
    if arr.dtype not in [np.dtype(d) for d in expected_dtypes]:
        raise TypeError(
            f"{name} must have dtype in {expected_dtypes}, got {arr.dtype}"
        )
    if not arr.flags.writeable:
        raise ValueError(f"{name} must be writable (it is an out-param)")
    flat = arr.reshape(-1)
    if flat.size < min_size:
        raise ValueError(f"{name} must hold {min_size} entries, got {flat.size}")
    if not np.shares_memory(flat, arr):
        raise ValueError(
            f"{name} must be C-contiguous so in-place write-back reaches the "
            "caller's buffer"
        )
    return flat


class PumiTally:
    """Track-length flux tally on an unstructured tet mesh."""

    def __init__(
        self,
        mesh: TetMesh | str,
        num_particles: int,
        config: TallyConfig | None = None,
        *,
        program_bank=None,
    ):
        self.config = config or TallyConfig()
        cfg = self.config
        self.tally_times = TallyTimes()
        # Per-tally telemetry (obs/): a private registry + flight
        # recorder; every trace folds its on-device stats vector here.
        self._telemetry = TallyTelemetry("PumiTally")
        with phase_timer(
            self.tally_times, "initialization_time", True
        ) as timer:
            if isinstance(mesh, str):
                from .mesh.io import load_mesh

                mesh = load_mesh(mesh, dtype=cfg.dtype)
            if mesh.dtype != jnp.dtype(cfg.dtype):
                raise ValueError(
                    f"mesh dtype {mesh.dtype} != config dtype {cfg.dtype}"
                )
            self.mesh = mesh
            self.num_particles = int(num_particles)
            self._max_crossings = cfg.resolve_max_crossings(mesh.ntet)
            self._compact = cfg.resolve_compaction(int(num_particles))
            self._compact_stages = cfg.resolve_compact_stages(
                int(num_particles), ntet=mesh.ntet
            )
            self.state: ParticleState = seed_at_element_centroid(
                make_particle_state(self.num_particles, dtype=cfg.dtype), mesh
            )
            # Where the batch-start search starts (ops/seed_grid.py): a
            # small device table built once, an argument of the init
            # program alone.
            self._seed_grid = build_seed_grid(mesh)
            # Flat device layout: [ntet,n_groups,2] on TPU pads the minor
            # dim 2 → 128 under the (8,128) tile (64× HBM; see make_flux).
            self.flux = make_flux(
                mesh.ntet, cfg.n_groups, dtype=cfg.dtype, flat=True
            )
            if cfg.sd_mode not in ("segment", "batch"):
                raise ValueError(
                    f"sd_mode must be 'segment' or 'batch': {cfg.sd_mode!r}"
                )
            # sd_mode="batch": snapshot of the even (Σc) entries as of
            # the previous move, for the per-move squared-delta fold
            # (core.tally.accumulate_batch_squares). score_squares=False
            # still means NO squares work at all, in either mode.
            self._prev_even = (
                jnp.zeros(mesh.ntet * cfg.n_groups, cfg.dtype)
                if cfg.sd_mode == "batch" and cfg.score_squares
                else None
            )
            self.iter_count = 0
            self.total_segments = 0
            self._replanned = cfg.compact_stages != "adaptive"
            self._initialized = False
            # Host-order permutation: device slot i holds particle
            # _perm[i]; None while the layout is still identity. The
            # DEVICE-resident copy (_perm_dev) drives the packed
            # pipeline's fused gather/scatter; both are derived only
            # when the periodic sort actually fires (_resort_by_element)
            # — never per move.
            self._perm: np.ndarray | None = None
            self._perm_dev = None
            self._traces_since_sort = 0
            # Move-loop I/O pipelining (ops/staging.py): "packed" stages
            # ONE host record per move each way; "overlap" adds
            # double-buffered staging + deferred telemetry folds;
            # "legacy" is the pre-pipeline multi-transfer path.
            self._io = cfg.resolve_io_pipeline()
            # Autotuning database (tuning/): consulted ONCE, here at
            # construction, for the knobs left at their defer values —
            # kernel="auto"'s backend pick, the Pallas lane_block,
            # megastep K. Explicit config/env knobs beat it; a miss
            # (or no database — the default) changes nothing, and every
            # database winner is bitwise parity-gated by the tuner, so
            # outputs are byte-identical either way.
            from .tuning import resolve_tuned

            self._tuned = resolve_tuned(
                cfg,
                ntet=mesh.ntet,
                n_particles=self.num_particles,
                n_groups=cfg.n_groups,
                dtype=cfg.dtype,
                packed=getattr(mesh, "geo20", None) is not None,
            )
            # Shape-class key of this workload (tuning/shapes.py) —
            # the serving scheduler and the AOT bank attribute work to
            # bank entries by it, and it is useful telemetry on its
            # own, so it is computed whether or not tuning is on.
            from .tuning.shapes import classify

            self.shape_key = classify(
                mesh.ntet, self.num_particles, cfg.n_groups, cfg.dtype,
                getattr(mesh, "geo20", None) is not None,
            ).key()
            # Serving AOT program bank (serving/bank.py): when
            # attached, the packed-walk and megastep dispatches route
            # through ahead-of-time compiled executables deserialized
            # from disk — same programs, zero steady-state compile
            # cost.  None (the default) is the plain jit path.
            self._bank = program_bank
            # Pallas one-hot block width: validated here (power of two,
            # clamped to the batch) whatever the kernel resolves to, and
            # fed into select_backend's VMEM-budget check below.
            self._lane_block = cfg.resolve_lane_block(
                self.num_particles, tuned=self._tuned
            )
            # Walk-kernel backend (ops/walk_pallas.py): the config half
            # of the decision (resolve_kernel — combo validation, env
            # override) and the workload half (select_backend — packed
            # table, VMEM budget, platform) BOTH resolve here at
            # construction, never mid-dispatch. The resolved backend
            # rides every _trace call as a static jit key; "auto"
            # outside the Pallas regime (or over a debug surface the
            # kernel cannot carry) lands on "xla" silently.
            self._kernel_policy = cfg.resolve_kernel()
            if self._kernel_policy == "xla":
                self._kernel = "xla"
            else:
                from .ops.walk_pallas import resolve_config_kernel

                self._kernel = resolve_config_kernel(
                    cfg,
                    ntet=mesh.ntet,
                    n_particles=self.num_particles,
                    n_groups=cfg.n_groups,
                    dtype=cfg.dtype,
                    packed=getattr(mesh, "geo20", None) is not None,
                    lane_block=self._lane_block,
                    tuned=self._tuned,
                )
            self._stager = staging.HostStager(
                depth=2 if self._io == "overlap" else 1
            )
            self._pending_folds: list = []
            self._last_xpoints: tuple | None = None
            # Bad-particle quarantine (resilience/quarantine.py):
            # cumulative per-lane counts + the out-of-mesh threshold.
            self._quarantined: np.ndarray | None = None
            if cfg.quarantine:
                from .resilience.quarantine import setup

                setup(self, mesh.coords, self.num_particles)
            # Self-verification layer (integrity/): escalation mode,
            # invariant tolerances, the shadow-audit reference walker,
            # and the facade-side fault hooks (bitflip_flux / sdc_walk /
            # hang_at_move target the NEW detectors, so they live here,
            # not on the supervisor's injector). All None/off by
            # default — the hot path pays nothing.
            self._integrity = cfg.resolve_integrity()
            self._finj = None
            self._auditor = None
            if (
                self._integrity != "off"
                or cfg.audit_lanes
                or cfg.move_deadline_s is not None
            ):
                from .integrity import invariants
                from .resilience.faultinject import FaultInjector

                self._finj = FaultInjector()
                scale = invariants.mesh_scale(mesh.coords)
                self._integrity_tol = invariants.conservation_tolerance(
                    cfg.integrity_tol, cfg.dtype, scale, cfg.tolerance
                )
                self._audit_tol = invariants.audit_tolerance(
                    cfg.audit_tol, cfg.dtype, scale, cfg.tolerance
                )
            if cfg.audit_lanes:
                from .integrity.audit import HostReference

                self._auditor = HostReference(mesh)
            # Statistical-convergence observability (obs/convergence.py):
            # device-resident batch accumulators — the even-entry
            # snapshot Σ T_b, Σ T_b², and the batch/move counters — plus
            # the gauge-feeding monitor. All None/absent when off — the
            # hot path pays nothing and stays bit-identical.
            self._batch_moves = cfg.resolve_convergence()
            self._monitor = None
            self._conv = None
            if self._batch_moves is not None:
                nbins = mesh.ntet * cfg.n_groups
                self._conv = (
                    jnp.zeros(nbins, cfg.dtype),
                    jnp.zeros(nbins, cfg.dtype),
                    jnp.zeros((), jnp.int32),
                    jnp.zeros((), jnp.int32),
                )
                self._monitor = ConvergenceMonitor(
                    self._telemetry,
                    rel_err_target=cfg.rel_err_target,
                    converged_fraction=cfg.converged_fraction,
                    batch_moves=self._batch_moves,
                )
            timer.sync((self.state, self.flux))
        # Phase-boundary memory sample (HBM peaks where the backend
        # reports them — construction allocated the mesh tables + flux).
        self._telemetry.record_memory("initialization")
        # Live scrape endpoint (obs/exporter.py): serves this tally's
        # registry as Prometheus text when PUMI_TPU_PROM_PORT is set.
        # Stopped by close(); the GC finalizer releases the port for
        # tallies that are simply dropped (the handler closure would
        # otherwise pin the registry and the socket forever).
        self._exporter = maybe_start_exporter(self.metrics)
        if self._exporter is not None:
            import weakref

            weakref.finalize(self, self._exporter.stop)

    # ------------------------------------------------------------------ #
    def _trace(self, *args, **kwargs):
        """Dispatch to the fused walk — the facade's SINGLE walk entry
        point for every pipeline mode, so wrappers around it (the
        resilience test harness's transient-fault injection, future
        instrumentation) intercept packed and legacy moves alike.
        ``_packed=True`` routes to the packed-record program
        (ops/walk.py trace_packed); with checkify_invariants on (legacy
        mode only — resolve_io_pipeline forces it), route through the
        checkify-wrapped variant so the reference's device asserts
        (OMEGA_H_CHECK_PRINTF, cpp:605-608, 618-629) fire as Python
        exceptions."""
        kwargs.setdefault("kernel", self._kernel)
        if kwargs.get("kernel") == "pallas" and self._lane_block:
            # The resolved block width rides only the Mosaic path — the
            # XLA jit cache never sees the (no-op there) static key.
            kwargs.setdefault("lane_block", self._lane_block)
        if kwargs.pop("_packed", False):
            if self._bank is not None:
                # AOT bank dispatch: the exact (args, kwargs) the jit
                # wrapper would see, so the bank's entry key matches
                # where the jit cache would hit.
                return self._bank.dispatch(
                    "trace_packed", args, kwargs,
                    shape_key=self.shape_key,
                )
            return trace_packed(*args, **kwargs)
        if self.config.checkify_invariants:
            from .ops.walk import checked_trace

            err, result = checked_trace(*args, **kwargs)
            err.throw()
            return result
        return trace(*args, **kwargs)

    # ------------------------------------------------------------------ #
    def _dispatch(self, fn, move: int, kind: str | None = None):
        """One compiled-step dispatch + blocking readback, under the
        integrity watchdog deadline when configured
        (integrity/watchdog.py). ``fn`` must be MUTATION-FREE (pure
        dispatch + fetch): on a timeout its abandoned thread may still
        complete the device work later, and nothing must apply it —
        recovery is the supervisor's last-good rollback, which rebuilds
        every donated buffer from host copies.

        The FIRST dispatch of each kind (initial search / move /
        megastep) runs un-deadlined: it legitimately includes XLA
        compilation, which can exceed any deadline sized for
        steady-state moves (minutes on real hardware). The watchdog
        arms from the second dispatch on — the regime where a stall
        means a wedged device."""
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
            # Warm-up dispatch: un-deadlined (it includes compilation),
            # but still through body() so a hang_at_move targeting it
            # fires (inline) instead of silently never injecting.
            warm.add(key)
            return body()
        from .integrity.watchdog import (
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
        self, move, integ, stats_d, fly_h, n_lost, s_before, result,
        dest_dev, done_h, pos_out,
    ) -> None:
        """Evaluate the move's integrity surface and escalate per
        ``TallyConfig.integrity``: the fused invariant vector (device
        ↔ host lane agreement, conservation residual, flux health),
        then the shadow-audit sample. Violations are counted + recorded
        BEFORE escalation so 'warn' and 'halt' leave the same
        telemetry."""
        cfg = self.config
        if self._integrity == "off" and not cfg.audit_lanes:
            return
        from .integrity import invariants, policy

        fields: dict = {}
        violations: list = []
        if integ is not None:
            fields = invariants.integrity_to_dict(integ)
            violations += invariants.check_move(
                fields, int(fly_h.sum()), int(n_lost),
                self._integrity_tol,
            )
        if (
            cfg.audit_lanes
            and self._auditor is not None
            and move >= 1
            and move % cfg.audit_every == 0
        ):
            out = self._run_audit(
                move, s_before, result, dest_dev, fly_h, done_h, pos_out
            )
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

    def _inv_perm(self) -> np.ndarray:
        """pid → device-slot map (inverse of ``_perm``)."""
        inv = np.empty(self.num_particles, np.int64)
        inv[self._perm] = np.arange(self.num_particles)
        return inv

    def _run_audit(
        self, move, s_before, result, dest_dev, fly_h, done_h, pos_out
    ):
        """Shadow-audit one move (integrity/audit.py): sample K
        completed in-flight lanes deterministically per (seed, move),
        fetch their pre-move state + production outputs (a few tiny
        out-of-band D2H gathers — audits are opt-in and priced in
        BENCHMARKS.md), re-walk them in float64 on the host reference,
        and compare."""
        cfg = self.config
        if done_h is None:
            done_h = np.asarray(result.done)
            if self._perm is not None:  # slot order → pid order
                out = np.empty_like(done_h)
                out[self._perm] = done_h
                done_h = out
        cand = np.nonzero(fly_h & done_h)[0]
        if cand.size == 0:
            return None
        rng = np.random.default_rng([cfg.audit_seed, int(move)])
        pids = rng.choice(
            cand, size=min(cfg.audit_lanes, cand.size), replace=False
        )
        slots = pids if self._perm is None else self._inv_perm()[pids]
        sl = jnp.asarray(slots)
        origins = np.asarray(
            jax.device_get(s_before.origin[sl]), np.float64
        )
        elems = np.asarray(jax.device_get(s_before.elem[sl]))
        dests = np.asarray(jax.device_get(dest_dev[sl]), np.float64)
        track = np.asarray(
            jax.device_get(result.track_length[sl]), np.float64
        ).copy()
        prod_pos = np.asarray(pos_out[pids], np.float64)
        if self._finj is not None and self._finj.sdc_at(move):
            # Injected SDC: one mis-scored segment on the first sampled
            # lane — the float64 re-walk must flag it.
            track[0] += 1e3 * self._audit_tol
            self.metrics.counter(
                "pumi_injected_faults_total",
                "faults injected through PUMI_TPU_FAULTS "
                "(labeled by kind)",
            ).inc(kind="sdc_walk")
        from .integrity.audit import audit_sample

        return audit_sample(
            self._auditor, origins, dests, elems, prod_pos, track,
            tolerance=cfg.tolerance,
            max_crossings=self._max_crossings,
            tol=self._audit_tol,
        )

    def _maybe_inject_bitflip(self, move: int) -> None:
        """``PUMI_TPU_FAULTS=bitflip_flux:K``: flip the sign of the
        largest accumulator entry (or NaN slot 0 of an empty
        accumulator) after move K — the NEXT move's on-device flux
        invariant must catch it."""
        if self._finj is None or not self._finj.bitflip_at(move):
            return
        j = int(jnp.argmax(jnp.abs(self.flux)))
        v = self.flux[j]
        self.flux = self.flux.at[j].set(
            jnp.where(v == 0, jnp.asarray(jnp.nan, self.flux.dtype), -v)
        )
        self.metrics.counter(
            "pumi_injected_faults_total",
            "faults injected through PUMI_TPU_FAULTS (labeled by kind)",
        ).inc(kind="bitflip_flux")

    # ------------------------------------------------------------------ #
    def _gather_in(self, host: np.ndarray) -> np.ndarray:
        """Reorder per-particle host input into device slot order."""
        return host if self._perm is None else host[self._perm]

    def _refresh_perm_device(self) -> None:
        """Re-derive the device-resident slot permutation from state.
        ``state.particle_id`` after a sort IS the slot→pid map, already
        on device — no transfer.  Called by the periodic sort and by
        checkpoint restore (utils/checkpoint._apply_plain)."""
        self._perm_dev = (
            self.state.particle_id if self._perm is not None else None
        )

    def _resort_by_element(self) -> None:
        """Periodic locality sort (the migrate-every-100 analog,
        cpp:256-258).  The ``jnp.argsort(state.elem)`` and every derived
        permutation artifact (device perm for the packed pipeline's
        fused gather/scatter, host perm for cold-path un-permutes) are
        computed HERE only: moves between sorts reuse the cached
        ``_perm_dev`` unchanged, and a sort request with no trace since
        the last sort is skipped outright (the element keys cannot have
        changed)."""
        if self._traces_since_sort == 0:
            return
        order = jnp.argsort(self.state.elem)
        self.state = jax.tree_util.tree_map(
            lambda x: x[order], self.state
        )
        self._traces_since_sort = 0
        self._perm = np.asarray(jax.device_get(self.state.particle_id))
        self._refresh_perm_device()

    def _drain_pending(self) -> None:
        """Flush deferred telemetry folds (io_pipeline="overlap"): each
        entry is a zero-arg closure recorded in move order.  Called
        after the NEXT move's dispatch (so the fold work overlaps the
        device walk) and at every flush point (telemetry(), VTK write,
        checkpointing)."""
        pending, self._pending_folds = self._pending_folds, []
        for fold in pending:
            fold()

    def _check_groups(self, group: np.ndarray) -> None:
        _check_group_range(group, self.config.n_groups)

    def _check_finite(self, name: str, arr: np.ndarray) -> None:
        if self.config.checkify_invariants and not np.isfinite(arr).all():
            raise ValueError(f"{name} contains non-finite values")

    def _read_stats(self, result) -> dict | None:
        """Host view of the on-device stats vector: ONE small fetch per
        move carrying the whole flight-recorder record (crossings,
        truncations, occupancy, segments — obs/walk_stats.py). None when
        walk_stats is off."""
        if result.stats is None:
            return None
        return stats_to_dict(result.stats)

    def _n_truncated(self, result, stats_d: dict | None) -> int:
        """Truncation count from the stats vector; host-scan fallback
        (the pre-telemetry path) only when walk_stats is off."""
        if stats_d is not None:
            return stats_d["truncated"]
        return int(np.sum(~np.asarray(result.done)))

    def _warn_if_truncated(self, n_lost: int, lanes=None) -> None:
        if n_lost:
            named = ""
            if lanes:
                named = " Truncated lanes (particle id, element, " \
                    "position): " + "; ".join(
                        f"{ln['pid']}, {ln['elem']}, {tuple(ln['pos'])}"
                        for ln in lanes
                    ) + "."
            warnings.warn(
                f"{n_lost} particle walk(s) truncated at max_crossings="
                f"{self._max_crossings}; tallies for them are incomplete. "
                "Raise TallyConfig.max_crossings or set "
                f"truncation_retries for bounded re-walk escalation.{named}",
                RuntimeWarning,
                # caller → public call → its body → here
                stacklevel=4,
            )

    def _truncated_lanes(self, done_h, pos_h, elem_dev, k: int = 8):
        """Up to ``k`` truncated lanes, named by particle id, parent
        element and position, from a readback's host ``done`` and
        position columns (host pid order). Cold: the one extra transfer
        (the named lanes' elements) happens only when a lane truncated."""
        pids = np.flatnonzero(~done_h)[:k]
        slots = pids if self._perm is None else self._inv_perm()[pids]
        elems = np.asarray(jax.device_get(elem_dev[jnp.asarray(slots)]))
        return [
            {"pid": int(p), "elem": int(e),
             "pos": [float(x) for x in pos_h[p]]}
            for p, e in zip(pids, elems)
        ]

    # ------------------------------------------------------------------ #
    def _quarantine(self, dest3, weights, move):
        """Bad-particle quarantine for one call (TallyConfig.quarantine)
        — delegates to the shared resilience/quarantine.py apply() so
        both facades keep identical semantics. Returns
        ``(dest3_for_staging, mask_or_None)``: on a hit the first is a
        sanitized COPY (the caller's buffer is never mutated — a
        supervisor retry must re-see the original inputs); ``weights``
        must already be a facade copy or None."""
        if not self.config.quarantine:
            return dest3, None
        from .resilience import quarantine

        return quarantine.apply(self, dest3, weights, move)

    def quarantined_lanes(self) -> np.ndarray:
        """Cumulative per-lane quarantine counts, host pid order (the
        degraded-mode per-lane report; resilience/quarantine.py)."""
        from .resilience.quarantine import lanes

        return lanes(self)

    def _escalate_truncated(
        self, result, dest, weight, group, stats_d, tkw, move,
        done_h=None, io=None,
    ):
        """Truncation escalation (TallyConfig.truncation_retries): re-walk
        only the truncated lanes with doubled max_crossings before
        declaring them lost (ops/walk.py rewalk_truncated) — ONE policy
        for both pipelines.  The packed pipeline passes the host ``done``
        column from the readback record (``done_h``, the stats-off
        truncation count without a device scan) and its ``io`` accounting
        dict; a re-walk then refreshes the caller's host views through
        ONE cold-path coalesced readback.  Returns ``(result, stats_d,
        n_lost, parts, rewalks)`` where ``parts`` is the refreshed
        split_trace_readback tuple (packed, after a re-walk) or None and
        ``rewalks`` counts the re-walk dispatches."""
        if stats_d is not None:
            n_tr = stats_d["truncated"]
        elif done_h is not None:
            n_tr = int(np.sum(~done_h))
        else:
            n_tr = self._n_truncated(result, None)
        if not n_tr:
            return result, stats_d, 0, None, 0
        n_lost, n_retried, parts, rewalks = n_tr, 0, None, 0
        if self.config.truncation_retries > 0:
            from .ops.walk import rewalk_truncated

            def counted(*args, **kwargs):
                nonlocal rewalks
                rewalks += 1
                return self._trace(*args, **kwargs)

            result, n_retried, n_lost = rewalk_truncated(
                self.mesh, result, dest, weight, group,
                retries=self.config.truncation_retries,
                trace_fn=counted, **tkw,
            )
            if io is not None:
                host_rb = jax.device_get(
                    staging.pack_trace_readback_cold(
                        result, self._perm_dev
                    )
                )
                io["d2h_bytes"] += int(host_rb.nbytes)
                io["d2h_transfers"] += 1
                # Re-walk merges never carry a convergence tail (the
                # batch fold belongs to the move's MAIN dispatch; see
                # staging.pack_trace_readback_cold) — split accordingly
                # and let the caller keep the main readback's summary.
                parts = staging.split_trace_readback(
                    host_rb, self.num_particles, self.config.dtype,
                    integrity=self._integrity != "off",
                )
                stats_d = (
                    stats_to_dict(parts[3])
                    if self.config.walk_stats else None
                )
            else:
                stats_d = self._read_stats(result)
        if n_retried or n_lost:
            self._telemetry.record_rewalk(move, n_retried, n_lost)
        return result, stats_d, n_lost, parts, rewalks

    # ------------------------------------------------------------------ #
    def initialize_particle_location(
        self, init_particle_positions: np.ndarray, size: int | None = None
    ) -> None:
        """Find every particle's parent element at its source position;
        nothing is tallied (search_initial_elements +
        search_and_rebuild(initial=True), cpp:360-385, 741-746).

        The reference flies each particle from its current position
        (the element-0 centroid after construction, else where the last
        batch left it) to the source point. Here a flying lane's walk
        starts instead from the seed element of its source point's cell
        in the tally's seed grid (ops/seed_grid.py), a few elements
        away. The search scores nothing and only the domain boundary
        stops it, and a seed is used only where no boundary can stand
        between it and the source point, so the walk ends as the
        reference's does: in the element holding the source point, on
        the point (to an ulp). Lanes whose source point lies outside
        the mesh's bounding box, or in a cell without a seed, start
        from their current position, as in the reference."""
        with CallSpans("PumiTally.initialize_particle_location") as spans:
            spans.open("stage")
            self._initialize(init_particle_positions, size, spans)

    def _initialize(self, init_particle_positions, size, spans) -> None:
        pos = np.ascontiguousarray(
            init_particle_positions, dtype=np.float64
        ).reshape(-1)
        if size is None:
            size = pos.size
        assert size == self.num_particles * 3, (
            f"expected {self.num_particles * 3} coordinates, got {size}"
        )
        n = self.num_particles
        fly_h = np.ones(n, bool)
        pos3 = pos[:size].reshape(-1, 3)
        pos3, qmask = self._quarantine(pos3, None, 0)
        if qmask is not None:
            fly_h &= ~qmask  # masked lanes stay at the seed
        self._check_finite("init_particle_positions", pos3)
        t_before = self.tally_times.initialization_time
        lost_lanes = None
        with phase_timer(
            self.tally_times, "initialization_time", True
        ) as timer:
            s = self.state
            tkw = dict(
                initial=True,
                max_crossings=self._max_crossings,
                score_squares=self.config.score_squares,
                tolerance=self.config.tolerance,
                compact_after=self._compact[0],
                compact_size=self._compact[1],
                compact_stages=self._compact_stages,
                unroll=self.config.unroll,
                robust=self.config.robust,
                tally_scatter=self.config.tally_scatter,
                gathers=self.config.gathers,
                ledger=self.config.ledger,
                stats=self.config.walk_stats,
                integrity=self._integrity != "off",
                record_xpoints=self.config.record_xpoints,
                n_groups=self.config.n_groups,
            )
            if self._io != "legacy":
                # Packed pipeline: ONE staging record up, ONE coalesced
                # readback down (positions are unused here — only the
                # stats/done tail drives the truncation accounting).
                rec_h = staging.pack_init_record(
                    self._stager, pos3, fly_h, self.config.dtype
                )
                io = dict(
                    h2d_bytes=int(rec_h.nbytes), h2d_transfers=1,
                    d2h_bytes=0, d2h_transfers=0,
                )
                rec_dev = jax.device_put(rec_h)
                # Bind the donated flux at closure-CREATION time: an
                # abandoned watchdog worker waking after a rollback
                # must consume the stale pre-restore buffer, never the
                # restored live accumulator.
                flux_in, perm_in = self.flux, self._perm_dev

                def _step():
                    with annotate("PumiTally.dispatch"):
                        out = self._trace(
                            self.mesh, s.origin, s.elem, s.material_id,
                            rec_dev, flux_in, perm_in,
                            weight=s.weight, group=s.group, _packed=True,
                            seed_grid=self._seed_grid, **tkw,
                        )
                    with annotate("PumiTally.readback"):
                        return out, jax.device_get(out[1])

                spans.close()
                out, host_rb = self._dispatch(_step, 0)
                spans.open("fold")
                result, readback, dest, _fly, _w, _g = out
                io["d2h_bytes"] += int(host_rb.nbytes)
                io["d2h_transfers"] += 1
                pos_h, _mats, done_h, tail, integ, _conv = (
                    staging.split_trace_readback(
                        host_rb, n, self.config.dtype,
                        integrity=self._integrity != "off",
                    )
                )
                stats_d = (
                    stats_to_dict(tail) if self.config.walk_stats else None
                )
                result, stats_d, n_lost, parts, rewalks = (
                    self._escalate_truncated(
                        result, dest, s.weight, s.group, stats_d, tkw, 0,
                        done_h=done_h, io=io,
                    )
                )
                if parts is not None:
                    pos_h, _mats, done_h, _tail, integ, _ = parts
                if n_lost:
                    lost_lanes = self._truncated_lanes(
                        done_h, pos_h, result.elem
                    )
            else:
                dest_h = self._gather_in(pos3)
                dest = jnp.asarray(dest_h, dtype=self.config.dtype)
                fly_dev = jnp.asarray(self._gather_in(fly_h))
                io = dict(
                    h2d_bytes=int(dest.nbytes) + int(fly_dev.nbytes),
                    h2d_transfers=2, d2h_bytes=0, d2h_transfers=0,
                )

                flux_in = self.flux  # bound pre-closure (see above)

                def _step():
                    with annotate("PumiTally.dispatch"):
                        r = self._trace(
                            self.mesh,
                            s.origin,
                            dest,
                            s.elem,
                            fly_dev,
                            s.weight,
                            s.group,
                            s.material_id,
                            flux_in,
                            seed_grid=self._seed_grid,
                            **tkw,
                        )
                    with annotate("PumiTally.readback"):
                        return r, self._read_stats(r)

                spans.close()
                result, stats_d = self._dispatch(_step, 0)
                spans.open("fold")
                if result.stats is not None:
                    io["d2h_bytes"] += int(result.stats.nbytes)
                    io["d2h_transfers"] += 1
                result, stats_d, n_lost, _, rewalks = (
                    self._escalate_truncated(
                        result, dest, s.weight, s.group, stats_d, tkw, 0
                    )
                )
                integ = (
                    np.asarray(result.integrity, np.float64)
                    if result.integrity is not None else None
                )
                if result.integrity is not None:
                    io["d2h_bytes"] += int(result.integrity.nbytes)
                    io["d2h_transfers"] += 1
            self._traces_since_sort += 1
            self.flux = result.flux
            self.state = s._replace(
                origin=result.position, dest=dest, elem=result.elem
            )
            self._store_xpoints(result)
            self._initialized = True
            self._warn_if_truncated(n_lost, lost_lanes)
            # Integrity surface for the location search: flux must stay
            # untouched/finite and the lane accounting must close (the
            # conservation triple is identically zero here — nothing is
            # scored; the shadow audit starts with move 1).
            self._self_verify(
                0, integ, stats_d, fly_h, n_lost, s, result, dest,
                None, None,
            )
            if self.config.measure_time:
                timer.sync(self.state)
        io.update(lanes=int(fly_h.sum()), dispatches=1 + rewalks)
        if lost_lanes:
            io["truncated_lanes"] = lost_lanes
        self._telemetry.record_walk(
            "initial_search",
            0,
            stats_d,
            seconds=self.tally_times.initialization_time - t_before,
            synced=self.config.measure_time,
            **io,
        )

    def _maybe_replan(self, n_segments: int, n_moving: int) -> None:
        """compact_stages="adaptive": after the FIRST move, re-plan the
        compaction ladder from the MEASURED crossings/move instead of
        the mesh-density estimate, which cannot see the move-length
        statistics. A mover scores crossings+1 segments (the final
        destination-reach iteration scores too, walk.py), so mean
        crossings = segments/moving − 1. Later moves reuse the
        re-planned schedule (one extra trace compile total); results
        are identical up to fp summation order (schedules group the
        scatter adds differently — observed ~1e-15 in f64)."""
        if self._replanned or n_moving == 0:
            return
        self._replanned = True
        if self.num_particles < 1024:
            # Same policy as resolve_compact_stages/resolve_compaction:
            # tiny batches stay on the flat loop.
            return
        from .utils.ladder import plan_stages

        mean = max(n_segments / n_moving - 1.0, 0.25)
        planned = plan_stages(
            self.num_particles, mean, unroll=self.config.unroll
        )
        self._compact_stages = planned or None

    # ------------------------------------------------------------------ #
    def move_to_next_location(
        self,
        particle_destinations: np.ndarray,
        flying: np.ndarray,
        weights: np.ndarray,
        groups: np.ndarray,
        material_ids: np.ndarray,
        size: int | None = None,
    ) -> None:
        """Advance every in-flight particle to its destination, tally flux,
        and write the (possibly boundary-clipped) final positions and
        material ids back into the caller's arrays (cpp:221-264)."""
        with CallSpans("PumiTally.move_to_next_location") as spans:
            spans.open("stage")
            self._move(
                particle_destinations, flying, weights, groups,
                material_ids, size, spans,
            )

    def _move(
        self, particle_destinations, flying, weights, groups, material_ids,
        size, spans,
    ) -> None:
        assert self._initialized, (
            "initialize_particle_location must run before moves"
        )
        n = self.num_particles
        cfg = self.config
        dest_flat = _out_param(
            particle_destinations, "particle_destinations", [np.float64], n * 3
        )
        if size is None:
            size = dest_flat.size
        assert size == n * 3
        flying_flat = _out_param(flying, "flying", [np.int8], n)
        mats_flat = _out_param(material_ids, "material_ids", [np.int32], n)
        weights_h = np.asarray(weights, dtype=np.float64).reshape(-1)[:n]
        groups_h = np.asarray(groups, dtype=np.int32).reshape(-1)[:n]
        self._check_groups(groups_h)
        fly_h = flying_flat[:n] != 0
        dest3_h = dest_flat[: n * 3].reshape(n, 3)
        if cfg.quarantine:
            # weights_h may alias the caller's array (asarray no-copies
            # a matching dtype); sanitize must not write through it.
            weights_h = weights_h.copy()
            dest3_h, qmask = self._quarantine(
                dest3_h, weights_h, self.iter_count + 1
            )
            if qmask is not None:
                fly_h = fly_h & ~qmask  # quarantined lanes are parked
        self._check_finite("particle_destinations", dest3_h)
        self._check_finite("weights", weights_h)

        t_before = self.tally_times.total_time_to_tally
        lost_lanes = None
        with phase_timer(
            self.tally_times, "total_time_to_tally", True
        ) as timer:
            s = self.state
            # Host-side mover count for the one-shot adaptive replan —
            # counted here (before the flags are zeroed) and only while
            # a replan is still pending, so the hot path pays nothing.
            n_moving_h = (
                int(fly_h.sum()) if not self._replanned else 0
            )
            tkw = dict(
                initial=False,
                max_crossings=self._max_crossings,
                # sd_mode="batch" skips the per-segment squares rows
                # entirely (the −20% step-time share) and folds one
                # squared per-move delta below instead.
                score_squares=(
                    cfg.score_squares and cfg.sd_mode == "segment"
                ),
                tolerance=cfg.tolerance,
                compact_after=self._compact[0],
                compact_size=self._compact[1],
                compact_stages=self._compact_stages,
                unroll=cfg.unroll,
                robust=cfg.robust,
                tally_scatter=cfg.tally_scatter,
                gathers=cfg.gathers,
                ledger=cfg.ledger,
                stats=cfg.walk_stats,
                integrity=self._integrity != "off",
                record_xpoints=cfg.record_xpoints,
                n_groups=cfg.n_groups,
            )
            # Convergence observability: the batch accumulators ride the
            # move's MAIN dispatch only (escalation re-walks score into
            # the same flux, and the NEXT batch's delta picks their
            # contributions up — the batches stay an exact partition of
            # all scores). Bound pre-closure like the donated flux.
            ckw = {}
            if self._monitor is not None:
                ckw = dict(
                    conv_state=self._conv,
                    rel_err_target=cfg.rel_err_target,
                    batch_moves=self._batch_moves,
                )
            if self._io != "legacy":
                # Packed pipeline (ops/staging.py): ONE contiguous host
                # record up (dest/weight/group/flying), slot permutation
                # and unpack fused into the compiled step, ONE coalesced
                # readback down (positions/materials/done/stats already
                # scattered back into host pid order on device).
                rec_h = staging.pack_move_record(
                    self._stager, dest3_h, weights_h, groups_h, fly_h,
                    cfg.dtype,
                )
                io = dict(
                    h2d_bytes=int(rec_h.nbytes), h2d_transfers=1,
                    d2h_bytes=0, d2h_transfers=0,
                )
                rec_dev = jax.device_put(rec_h)
                # Donated-buffer binding at closure-creation time — an
                # abandoned watchdog worker must never donate the
                # restored live accumulator (see the init-path note).
                flux_in, perm_in = self.flux, self._perm_dev

                deadline = self.config.move_deadline_s is not None

                def _step():
                    with annotate("PumiTally.dispatch"):
                        out = self._trace(
                            self.mesh, s.origin, s.elem, s.material_id,
                            rec_dev, flux_in,
                            perm_in, _packed=True, **tkw, **ckw,
                        )
                    if self._io == "overlap" and not deadline:
                        # Deferred bookkeeping of the PREVIOUS move
                        # runs here, overlapping the device walk of
                        # THIS move. Under the watchdog the closure
                        # must stay mutation-free (an abandoned worker
                        # must never touch _pending_folds/telemetry),
                        # so the drain moves after the dispatch.
                        with annotate("PumiTally.fold"):
                            self._drain_pending()
                    with annotate("PumiTally.readback"):
                        return out, jax.device_get(out[1])

                spans.close()
                out, host_rb = self._dispatch(_step, self.iter_count + 1)
                spans.open("fold")
                if self._io == "overlap" and deadline:
                    self._drain_pending()
                result, readback, dest, in_flight, weight, group = out
                # Updated batch accumulators from the MAIN dispatch — an
                # escalation re-walk below replaces ``result`` with a
                # merged TraceResult that has no conv fields.
                conv_new = result.conv_state
                io["d2h_bytes"] += int(host_rb.nbytes)
                io["d2h_transfers"] += 1
                final_pos, final_mats, done_h, tail, integ, conv_h = (
                    staging.split_trace_readback(
                        host_rb, n, cfg.dtype,
                        integrity=self._integrity != "off",
                        convergence=self._monitor is not None,
                    )
                )
                stats_d = (
                    stats_to_dict(tail) if cfg.walk_stats else None
                )
                result, stats_d, n_lost, parts, rewalks = (
                    self._escalate_truncated(
                        result, dest, weight, group, stats_d, tkw,
                        self.iter_count + 1, done_h=done_h, io=io,
                    )
                )
                if parts is not None:
                    # The refreshed cold readback has no convergence
                    # tail; the main dispatch's summary stands (the
                    # re-walk's scores enter the NEXT batch's delta).
                    final_pos, final_mats, done_h, tail, integ, _ = parts
                if n_lost:
                    lost_lanes = self._truncated_lanes(
                        done_h, final_pos, result.elem
                    )
            else:
                dest = jnp.asarray(
                    self._gather_in(dest3_h), dtype=cfg.dtype
                )
                in_flight = jnp.asarray(self._gather_in(fly_h))
                weight = jnp.asarray(
                    self._gather_in(weights_h), dtype=cfg.dtype
                )
                group = jnp.asarray(
                    self._gather_in(groups_h), dtype=jnp.int32
                )
                io = dict(
                    h2d_bytes=int(
                        dest.nbytes + in_flight.nbytes + weight.nbytes
                        + group.nbytes
                    ),
                    h2d_transfers=4, d2h_bytes=0, d2h_transfers=0,
                )

                flux_in = self.flux  # bound pre-closure (see above)

                def _step():
                    with annotate("PumiTally.dispatch"):
                        r = self._trace(
                            self.mesh,
                            s.origin,
                            dest,
                            s.elem,
                            in_flight,
                            weight,
                            group,
                            s.material_id,
                            flux_in,
                            **tkw,
                            **ckw,
                        )
                    with annotate("PumiTally.readback"):
                        return r, self._read_stats(r)

                spans.close()
                result, stats_d = self._dispatch(
                    _step, self.iter_count + 1
                )
                spans.open("fold")
                conv_new = result.conv_state  # main dispatch (see above)
                conv_h = None
                if result.convergence is not None:
                    # Legacy pipeline: the summary vector is its own
                    # small fetch (this path is multi-transfer anyway).
                    conv_h = np.asarray(result.convergence, np.float64)
                    io["d2h_bytes"] += int(result.convergence.nbytes)
                    io["d2h_transfers"] += 1
                if result.stats is not None:
                    io["d2h_bytes"] += int(result.stats.nbytes)
                    io["d2h_transfers"] += 1
                result, stats_d, n_lost, _, rewalks = (
                    self._escalate_truncated(
                        result, dest, weight, group, stats_d, tkw,
                        self.iter_count + 1,
                    )
                )
                integ = (
                    np.asarray(result.integrity, np.float64)
                    if result.integrity is not None else None
                )
                if result.integrity is not None:
                    io["d2h_bytes"] += int(result.integrity.nbytes)
                    io["d2h_transfers"] += 1
                done_h = None
            self.flux = result.flux
            if self._monitor is not None:
                self._conv = conv_new
            if self._prev_even is not None:
                self.flux, self._prev_even = accumulate_batch_squares(
                    self.flux, self._prev_even
                )
            self.state = s._replace(
                origin=result.position,
                dest=dest,
                in_flight=in_flight,
                weight=weight,
                group=group,
                elem=result.elem,
                material_id=result.material_id,
            )
            self.iter_count += 1
            self._traces_since_sort += 1

            # Copy-back contract: clipped final positions and material ids
            # into the caller's arrays (copy_last_location cpp:266-280,
            # copy_material_ids cpp:282-294); host flying flags reset to 0
            # (copy_and_reset_flying_flag cpp:316-319).
            if self._io != "legacy":
                # The readback record was scattered into host pid order
                # on device; both out-params are straight copies (the
                # position assign widens walk dtype → f64).
                dest_flat[: n * 3].reshape(n, 3)[:] = final_pos
                mats_flat[:n] = final_mats
                segs = (
                    stats_d["segments"] if stats_d is not None
                    else int(tail[0])
                )
            else:
                final_pos = np.asarray(result.position, dtype=np.float64)
                final_mats = np.asarray(result.material_id, dtype=np.int32)
                io["d2h_bytes"] += int(
                    result.position.nbytes + result.material_id.nbytes
                )
                io["d2h_transfers"] += 2
                if self._perm is None:
                    dest_flat[: n * 3] = final_pos.reshape(-1)
                    mats_flat[:n] = final_mats
                else:
                    dest_flat[: n * 3].reshape(n, 3)[self._perm] = final_pos
                    mats_flat[:n][self._perm] = final_mats
                # ONE stats-vector fetch (taken above, refreshed by any
                # escalation re-walk) carries segments + truncations +
                # crossings — the pre-telemetry path read n_segments AND
                # host-scanned the whole done array here.
                segs = (
                    stats_d["segments"] if stats_d is not None
                    else int(result.n_segments)
                )
            flying_flat[:n] = 0
            self.total_segments += segs
            self._maybe_replan(segs, n_moving_h)
            self._store_xpoints(result)
            # The truncation warning is a user-facing contract and stays
            # in-call in every pipeline mode; only the telemetry fold is
            # deferred under "overlap".
            self._warn_if_truncated(n_lost, lost_lanes)

            # Self-verification (integrity/): evaluate the fused
            # invariant vector + shadow-audit sample and escalate per
            # TallyConfig.integrity; then the bitflip fault hook (its
            # corruption is caught by the NEXT move's flux invariant).
            self._self_verify(
                self.iter_count, integ, stats_d, fly_h, n_lost, s,
                result, dest, done_h,
                dest_flat[: n * 3].reshape(n, 3),
            )
            self._maybe_inject_bitflip(self.iter_count)

            # Periodic locality sort (the migrate-every-100 analog,
            # cpp:256-258) — argsort and perm artifacts cached inside
            # _resort_by_element, never recomputed per move.
            if (
                cfg.sort_by_element
                and self.iter_count % cfg.migration_period == 0
            ):
                self._resort_by_element()
            if cfg.measure_time:
                timer.sync(self.state)
        self.tally_times.n_moves += 1
        seconds = self.tally_times.total_time_to_tally - t_before
        io.update(lanes=int(fly_h.sum()), dispatches=1 + rewalks)
        if lost_lanes:
            io["truncated_lanes"] = lost_lanes
        if self._io == "overlap":
            # Defer the telemetry fold so this move's bookkeeping
            # overlaps the NEXT move's device walk; flushed by
            # _drain_pending at every read surface.
            move_no, synced = self.iter_count, cfg.measure_time
            self._pending_folds.append(
                lambda stats_d=stats_d, io=io: self._telemetry.record_walk(
                    "move", move_no, stats_d, seconds=seconds,
                    synced=synced, **io,
                )
            )
        else:
            self._telemetry.record_walk(
                "move",
                self.iter_count,
                stats_d,
                seconds=seconds,
                synced=cfg.measure_time,
                **io,
            )
        if self._monitor is not None and conv_h is not None:
            # Fold the move's on-device convergence summary into the
            # gauges / per-batch flight records; under "overlap" the
            # host fold is deferred with the telemetry fold (drained at
            # every read surface, including converged()).
            fields = conv_to_dict(conv_h)
            secs_total = self.tally_times.total_time_to_tally
            if self._io == "overlap":
                self._pending_folds.append(
                    lambda: self._monitor.update(fields, secs_total)
                )
            else:
                self._monitor.update(fields, secs_total)

    # ------------------------------------------------------------------ #
    # Megastep: device-sourced fused move loop (ops/walk.py megastep)
    # ------------------------------------------------------------------ #
    def _source_tables(self, src):
        """Device Σt/absorption tables for one SourceParams, cached by
        its identity (staged once — never on the per-megastep path)."""
        from .ops.source import staged_tables

        self._src_tables = staged_tables(
            src, self.mesh.class_id, self.config.dtype,
            getattr(self, "_src_tables", None), put=jax.device_put,
        )
        return self._src_tables[1], self._src_tables[2]

    def _rng_key(self, seed: int):
        """Device PRNG key for one source seed, staged once (cold) and
        reused by every megastep dispatch of that stream."""
        from .ops.source import staged_rng_key

        self._rng_key_cache = staged_rng_key(
            seed, getattr(self, "_rng_key_cache", None)
        )
        return self._rng_key_cache[1]

    def _megastep_statics(self, src) -> dict:
        cfg = self.config
        from .ops.source import near_epsilon

        return dict(
            n_groups=cfg.n_groups,
            survival_weight=float(src.survival_weight),
            downscatter=float(src.downscatter),
            eps_near=near_epsilon(np.asarray(self.mesh.coords)),
            max_crossings=self._max_crossings,
            score_squares=(
                cfg.score_squares and cfg.sd_mode == "segment"
            ),
            tolerance=cfg.tolerance,
            compact_after=self._compact[0],
            compact_size=self._compact[1],
            compact_stages=self._compact_stages,
            unroll=cfg.unroll,
            robust=cfg.robust,
            tally_scatter=cfg.tally_scatter,
            gathers=cfg.gathers,
            ledger=cfg.ledger,
            stats=cfg.walk_stats,
            integrity=self._integrity != "off",
            rel_err_target=cfg.rel_err_target,
            batch_moves=self._batch_moves or 1,
        )

    def _stage_source_lanes(self, weights, groups, alive, io) -> None:
        """Cold-path staging of caller-provided physics lanes into
        device state (slot order). Counted in the CALLING chunk's I/O
        accounting; the steady-state megastep stages only the move
        counter."""
        n = self.num_particles
        repl = {}
        if weights is not None:
            w = np.asarray(weights, np.float64).reshape(-1)[:n]
            repl["weight"] = jnp.asarray(
                self._gather_in(w), self.config.dtype
            )
        if groups is not None:
            g = np.asarray(groups, np.int32).reshape(-1)[:n]
            self._check_groups(g)
            repl["group"] = jnp.asarray(self._gather_in(g), jnp.int32)
        if alive is not None:
            a = np.asarray(alive).astype(bool).reshape(-1)[:n]
            repl["in_flight"] = jnp.asarray(self._gather_in(a))
        if repl:
            self.state = self.state._replace(**repl)
            io["h2d_transfers"] += len(repl)
            io["h2d_bytes"] += sum(int(v.nbytes) for v in repl.values())

    def run_source_moves(
        self,
        n_moves: int,
        source=None,
        weights: np.ndarray | None = None,
        groups: np.ndarray | None = None,
        alive: np.ndarray | None = None,
    ) -> dict:
        """Run ``n_moves`` DEVICE-SOURCED moves: per-lane flight
        sampling (counter-based RNG keyed by (seed, move, particle id)
        over the per-region Σt table), the fused walk, and the
        collision/roulette physics of models/transport.py's inner loop
        all execute on device, fused ``TallyConfig(megastep=K)`` moves
        per dispatch — the host performs ONE H2D (the move counter) and
        ONE D2H (the stats/integrity/convergence/physics tail) per K
        moves instead of per move.

        ``weights``/``groups``/``alive`` (host pid order) re-stage the
        persistent physics lanes when given (a cold-path transfer, e.g.
        at batch start); omitted, the lanes continue from device state
        — ``state.in_flight`` is the alive flag between calls, so
        consecutive calls chain bitwise-identically to one bigger call.
        Results are bitwise identical for any megastep K (pinned by
        tests/test_megastep.py), and the RNG stream is keyed by the
        persistent ``iter_count``, so checkpoint restores resume it
        exactly.

        Per-move-facade-only features do not ride the megastep: shadow
        audits and truncation-escalation re-walks are skipped (truncated
        lanes stay alive and continue next move — counted + warned),
        and the periodic element sort never fires inside a dispatch
        (sampling is layout-invariant, so it is pure scheduling either
        way). Returns the accumulated physics counters
        (ops/source.py MEGA_PHYS_FIELDS + ``moves`` + ``segments``).
        """
        with CallSpans("PumiTally.run_source_moves") as spans:
            spans.open("stage")
            return self._source_moves(
                n_moves, source, weights, groups, alive, spans
            )

    def _source_moves(self, n_moves, source, weights, groups, alive,
                      spans) -> dict:
        assert self._initialized, (
            "initialize_particle_location must run before source moves"
        )
        cfg = self.config
        # Feature combos the fused program cannot carry fail at RESOLVE
        # time (utils/config.resolve_megastep: record_xpoints /
        # checkify_invariants), before any staging or dispatch. The
        # Mosaic walk kernel likewise never rides the scanned megastep
        # body: a config-explicit kernel='pallas' is rejected here at
        # the same resolve point, while kernel='auto' — and an
        # env-forced 'pallas' (the PUMI_TPU_KERNEL sweep) — lands on
        # the XLA megastep silently (the auto fallback policy). The
        # tuning database's K applies only when neither the env nor
        # the config pinned one (bitwise identical for any K).
        K = cfg.resolve_megastep(tuned=self._tuned)
        if self._kernel_policy == "pallas" and cfg.kernel == "pallas":
            raise NotImplementedError(
                "run_source_moves fuses source sampling + walk + "
                "physics into one scanned XLA program; kernel='pallas' "
                "does not ride it (TallyConfig.resolve_kernel) — use "
                "kernel='auto' (XLA fallback) or 'xla' for "
                "device-sourced runs"
            )
        from .ops.source import SourceParams, phys_to_dict
        from .ops.walk import megastep as megastep_fn

        src = source if source is not None else SourceParams()
        sig_dev, ab_dev = self._source_tables(src)
        rng_key = self._rng_key(src.seed)
        statics = self._megastep_statics(src)
        totals = {
            "moves": 0, "segments": 0, "collisions": 0, "escaped": 0,
            "rouletted": 0, "absorbed_weight": 0.0, "alive": 0,
            "truncated": 0,
        }
        stage = dict(h2d_bytes=0, h2d_transfers=0)
        self._stage_source_lanes(weights, groups, alive, stage)
        spans.close()
        done_moves = 0
        while done_moves < n_moves:
            k = min(K, n_moves - done_moves)
            t_before = self.tally_times.total_time_to_tally
            with phase_timer(
                self.tally_times, "total_time_to_tally", True
            ) as timer:
                s = self.state
                move_no = np.int32(self.iter_count)
                io = dict(
                    h2d_bytes=4 + stage.pop("h2d_bytes", 0),
                    h2d_transfers=1 + stage.pop("h2d_transfers", 0),
                    d2h_bytes=0, d2h_transfers=0,
                )
                stage = {}
                flux_in, conv_in = self.flux, self._conv
                prev_in = self._prev_even

                def _go():
                    # The chunk's one H2D (its move counter) rides the
                    # dispatch span: the stage span is the call's.
                    with annotate("PumiTally.dispatch"):
                        move0 = jax.device_put(move_no)
                        margs = (
                            self.mesh, s.origin, s.elem, s.material_id,
                            s.weight, s.group, s.in_flight, s.particle_id,
                            flux_in, move0, rng_key, sig_dev, ab_dev,
                            prev_in, conv_in,
                        )
                        mkw = dict(n_moves=k, **statics)
                        if self._bank is not None:
                            out = self._bank.dispatch(
                                "megastep", margs, mkw,
                                shape_key=self.shape_key,
                            )
                        else:
                            out = megastep_fn(*margs, **mkw)
                    with annotate("PumiTally.readback"):
                        return out, jax.device_get(out.readback)

                # Amnesty key includes k: each distinct chunk length
                # compiles its own program (n_moves is static), and the
                # remainder chunk's compile must not run under an armed
                # steady-state deadline.
                out, host_rb = self._dispatch(
                    _go, self.iter_count + 1, kind=f"megastep:{k}"
                )
                spans.open("fold")
                self.flux = out.flux
                if self._monitor is not None:
                    self._conv = out.conv_state
                if self._prev_even is not None:
                    self._prev_even = out.prev_even
                self.state = s._replace(
                    origin=out.position,
                    dest=out.dest,
                    in_flight=out.alive,
                    weight=out.weight,
                    group=out.group,
                    elem=out.elem,
                    material_id=out.material_id,
                )
                self.iter_count += k
                self._traces_since_sort += 1
                io["d2h_bytes"] += int(host_rb.nbytes)
                io["d2h_transfers"] += 1
                tail, integ, conv_h, phys = staging.split_megastep_tail(
                    host_rb, cfg.dtype, cfg.walk_stats,
                    statics["integrity"], self._monitor is not None,
                )
                stats_d = (
                    stats_to_dict(tail) if cfg.walk_stats else None
                )
                segs = (
                    stats_d["segments"] if stats_d is not None
                    else int(tail[0])
                )
                self.total_segments += segs
                p = phys_to_dict(phys)
                self._warn_if_truncated(p["truncated"])
                if integ is not None:
                    from .integrity import invariants, policy

                    fields = invariants.integrity_to_dict(integ)
                    violations = invariants.check_megastep(
                        fields, p["truncated"], self._integrity_tol,
                        dtype=cfg.dtype, n_moves=k,
                    )
                    if fields or violations:
                        self._telemetry.record_integrity(
                            self.iter_count, fields, violations
                        )
                    policy.escalate(
                        self._integrity, violations, self.iter_count
                    )
                self._maybe_inject_bitflip(self.iter_count)
                if cfg.measure_time:
                    timer.sync(self.state)
            self.tally_times.n_moves += k
            seconds = self.tally_times.total_time_to_tally - t_before
            # Every lane rides each fused move (dead lanes parked), so
            # the walk's width is the batch.
            self._telemetry.record_walk(
                "megastep", self.iter_count, stats_d,
                seconds=seconds, synced=cfg.measure_time, moves=k,
                collisions=p["collisions"], escaped=p["escaped"],
                rouletted=p["rouletted"], alive=p["alive"],
                lanes=self.num_particles, dispatches=1, **io,
            )
            if self._monitor is not None and conv_h is not None:
                self._monitor.update(
                    conv_to_dict(conv_h),
                    self.tally_times.total_time_to_tally,
                )
            totals["moves"] += k
            totals["segments"] += segs
            for f in ("collisions", "escaped", "rouletted", "truncated"):
                totals[f] += p[f]
            totals["absorbed_weight"] += p["absorbed_weight"]
            totals["alive"] = p["alive"]
            done_moves += k
            spans.close()
            if p["alive"] == 0:
                break
        return totals

    # ------------------------------------------------------------------ #
    def _store_xpoints(self, result) -> None:
        if result.xpoints is not None:
            xp = np.asarray(result.xpoints, np.float64)
            counts = np.asarray(result.n_xpoints, np.int32)
            # Un-permute into host particle order NOW, with the perm that
            # was active for this trace — a later periodic sort replaces
            # self._perm and must not re-map an already-stored buffer.
            if self._perm is not None:
                out_xp = np.empty_like(xp)
                out_c = np.empty_like(counts)
                out_xp[self._perm] = xp
                out_c[self._perm] = counts
                xp, counts = out_xp, out_c
            self._last_xpoints = (xp, counts)

    def intersection_points(self) -> tuple[np.ndarray, np.ndarray]:
        """Per-particle boundary-crossing points of the LAST trace call —
        the tracer's getIntersectionPoints() surface (reference
        test_pumi_tally_impl_methods.cpp:403-479, 561-587).

        Requires TallyConfig.record_xpoints=K. Returns
        (xpoints [n, K, 3], counts [n]) in host particle order; counts may
        exceed K when a walk crossed more boundaries than the buffer
        holds (only the first K points are kept).
        """
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

    def normalized_flux(self) -> np.ndarray:
        """[ntet, n_groups, 3] (mean, second moment, sd) — normalizeFlux
        parity (cpp:648-683), with the sd NaN guard fix. Runs on HOST
        so the 3-D view never materializes in the TPU's padded tile
        layout (normalize_flux_host docstring)."""
        return normalize_flux_host(
            self.raw_flux,
            self.mesh.volumes,
            self.num_particles,
            max(self.iter_count, 1),
            sd_mode=self.config.sd_mode,
        )

    def reaction_rate(self, sigma: np.ndarray) -> np.ndarray:
        """Multi-tally support: a reaction-rate tally (raw Σ w·l·σ and its
        square accumulator) for a per-(region, group) response table —
        derived from the flux accumulator, see core.tally.reaction_rate.
        Host-side for the same padded-layout reason as normalized_flux."""
        from .core.tally import reaction_rate_host

        if self.config.sd_mode != "segment":
            # The derived squares column is σ²·(slot 1), which is only
            # the documented Σ(w·l·σ)² when slot 1 holds per-SEGMENT
            # squares; in batch mode slot 1 is Σ(per-move bin totals)²
            # and the product would silently be ~N× the per-segment
            # statistic.
            raise NotImplementedError(
                "reaction_rate requires sd_mode='segment' (batch mode's "
                "slot 1 holds per-move batch squares, not per-segment "
                f"squares); config has sd_mode={self.config.sd_mode!r}"
            )
        return reaction_rate_host(
            self.raw_flux,
            np.asarray(self.mesh.class_id),
            np.asarray(sigma, self.config.dtype),
        )

    # ------------------------------------------------------------------ #
    # Statistical convergence (obs/convergence.py)
    # ------------------------------------------------------------------ #
    def _require_convergence(self):
        if self._monitor is None:
            raise ValueError(
                "convergence observability is off: construct with "
                "TallyConfig(convergence=True)"
            )
        return self._monitor

    def _reset_convergence(self) -> None:
        """Re-base the batch statistics on the CURRENT accumulator
        (checkpoint restore / supervisor rollback — the persisted state
        carries no batch history, so statistics restart from here).
        Called via the utils/checkpoint apply hooks."""
        if self._monitor is None:
            return
        self._drain_pending()
        self._conv = (
            self.flux[0::2],
            jnp.zeros_like(self._conv[1]),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
        )
        self._monitor.reset()

    def end_batch(self) -> dict:
        """Close the current statistical batch NOW, regardless of the
        ``batch_moves`` cadence (which restarts from here), fold it into
        the batch accumulators on device, and return the refreshed
        convergence summary — one tiny dispatch plus one [CONV_LEN]
        fetch, an API call rather than a move-loop step."""
        self._require_convergence()
        from .obs.convergence import end_batch_fold

        self._drain_pending()
        self._conv, vec = end_batch_fold(
            self.flux, *self._conv,
            rel_err_target=self.config.rel_err_target,
        )
        return self._monitor.update(
            conv_to_dict(np.asarray(vec, np.float64)),
            self.tally_times.total_time_to_tally,
        )

    def converged(self) -> bool:
        """Caller-driven early stop: True once at least 2 batches are
        folded, and the fraction of scored bins with relative error at
        or below ``rel_err_target`` has reached
        ``converged_fraction``."""
        self._require_convergence()
        self._drain_pending()
        return self._monitor.converged

    def relative_error(self) -> np.ndarray:
        """Per-bin [ntet, n_groups] float64 relative error from the
        batch accumulators (the fused reduction's per-bin input,
        materialized host-side — a cold-path fetch for VTK export and
        analysis; unscored bins report 0, scored bins with < 2 batches
        report 1)."""
        self._require_convergence()
        from .obs.convergence import host_relative_error

        self._drain_pending()
        snap, sumsq, nb, _ = self._conv
        rel = host_relative_error(
            jax.device_get(snap), jax.device_get(sumsq),
            int(jax.device_get(nb)),
        )
        return rel.reshape(self.mesh.ntet, self.config.n_groups)

    def write_pumi_tally_mesh(
        self, filename: str | None = None, uncertainty: bool = False
    ) -> str:
        """Normalize flux, attach per-group cell fields + volume, write VTK
        (finalizeAndWritePumiFlux, cpp:685-705), print phase times.
        ``uncertainty=True`` additionally writes the per-group relative
        error next to the flux (``rel_err_group_<g>`` cell fields —
        requires convergence observability)."""
        self._drain_pending()
        rel = self.relative_error() if uncertainty else None
        with annotate("PumiTally.write_pumi_tally_mesh"), phase_timer(
            self.tally_times, "vtk_file_write_time", True
        ):
            out = filename or self.config.output_filename
            write_flux_vtk(
                out, self.mesh, self.normalized_flux(), rel_err=rel
            )
        self._telemetry.record_memory("vtk_write")
        self.tally_times.print_times()
        return out

    # ------------------------------------------------------------------ #
    def telemetry(self) -> dict:
        """Run-wide telemetry snapshot (obs/): counter totals
        (segments/crossings/truncations/chase hops), the per-move flight
        records, phase times (TallyTimes), a fresh per-device memory
        sample, the convergence block, and the full metrics-registry
        snapshot. Per-record JSONL streaming: set
        ``PUMI_TPU_METRICS=jsonl:/path``."""
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
        """Release facade-owned background resources: flush deferred
        telemetry folds and stop the metrics scrape endpoint (frees the
        port for the next tally).  Idempotent; a tally that is simply
        dropped is cleaned up by the GC finalizer instead."""
        self._drain_pending()
        if self._exporter is not None:
            self._exporter.stop()
            self._exporter = None

    # ------------------------------------------------------------------ #
    def save_checkpoint(
        self, filename: str, n_shards: int | None = None
    ) -> None:
        """Persist the resumable tally state (flux accumulator + particle
        state + iteration counter) — see utils/checkpoint.py. The reference
        has no checkpointing (SURVEY.md §5); its additive tally state makes
        this a natural extension. A ``.shards`` filename writes the
        sharded two-phase layout with ``n_shards`` splits (default 1
        on this facade)."""
        from .utils.checkpoint import save_checkpoint

        self._drain_pending()
        save_checkpoint(filename, self, n_shards=n_shards)

    def restore_checkpoint(self, filename: str) -> None:
        """Resume from a checkpoint written against the same mesh/config."""
        from .utils.checkpoint import restore_checkpoint

        self._drain_pending()
        restore_checkpoint(filename, self)

    # ------------------------------------------------------------------ #
    @property
    def raw_flux(self) -> np.ndarray:
        """Unnormalized [ntet, n_groups, 2] (Σ w·len, Σ (w·len)²). The
        device accumulator is flat (make_flux flat=True); the 3-D view
        is assembled host-side."""
        return np.asarray(self.flux).reshape(
            self.mesh.ntet, self.config.n_groups, 2
        )

    @property
    def element_ids(self) -> np.ndarray:
        """Current parent element per particle, in host pid order (tracer
        getElementIds parity, test_pumi_tally_impl_methods.cpp:153-159)."""
        elems = np.asarray(self.state.elem)
        if self._perm is None:
            return elems
        out = np.empty_like(elems)
        out[self._perm] = elems
        return out
