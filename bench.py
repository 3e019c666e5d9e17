"""Benchmark: particle-segments/sec on a ~1M-tet box mesh (single chip).

BASELINE.md config 2 analog (1M-tet mesh, tracklength flux tally). The
north-star ladder metric is particle-segments/sec/chip; the baseline target
is 1e9 segments/sec on a v5p-64 pod (BASELINE.json), i.e. 1e9/64 per chip —
``vs_baseline`` reports the ratio against that per-chip figure.

Everything stays on device: destinations are generated with jax.random and
clipped into the domain, so the timed loop measures the fused
walk+scatter kernel (plus one scalar readback per run at the end).

Knobs (env): BENCH_CELLS (default 55 → 6*55^3 = 997,500 tets),
BENCH_PARTICLES (1048576), BENCH_STEPS (10), BENCH_GROUPS (8),
BENCH_DTYPE (float32), BENCH_UNROLL (8), walk strategy A/B knobs
BENCH_ROBUST/BENCH_SCATTER/BENCH_GATHERS/BENCH_LEDGER,
BENCH_KERNEL/BENCH_LANE_BLOCK (walk kernel + Mosaic block width;
PUMI_TPU_TUNING points the run at an autotuning database and the
record's lane_block/tuning_db/tuned axes say what actually ran), and
BENCH_FUSED (default 1) runs all steps in ONE device program
(lax.fori_loop) — pure device time, free of per-dispatch latency;
BENCH_FUSED=0 launches one program per step (the gap between the modes
is the dispatch overhead). BENCH_REPEAT (default 2) times that many
measurement windows on the compiled program and reports the best
(every window lands in detail.windows). BENCH_FAULTS=<PUMI_TPU_FAULTS spec> additionally runs
a small supervised fault-mode probe and records the MTTR axes
(detail.recovery_seconds / detail.lost_moves, tagged with
detail.fault_spec — the BENCHMARKS.md recovery-overhead trajectory).
BENCH_TRACE_SPANS=1 prices the serving span tracer's per-emission
cost, enabled vs PUMI_TPU_TRACE=off (detail.trace_overhead — the
zero-cost-to-physics receipt). Prints exactly ONE JSON line on stdout.

It measures the chip and nothing else: with no TPU it exits non-zero,
names the platform JAX found, and prints no result. The compile cache
and the serving probe's program bank sit at fixed paths
(pumiumtally_tpu/utils/platform.py).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def run(
    cells: int = 55,
    n_particles: int = 1048576,
    steps: int = 10,
    n_groups: int = 8,
    dtype_name: str = "float32",
    mean_path: float = 0.08,
    seed: int = 0,
    compact_after: int | None = 32,
    compact_size: int | None = None,
    compact_stages: tuple | str | None = "default",
    unroll: int = 8,
    robust: bool = True,
    tally_scatter: str = "auto",
    gathers: str = "merged",
    ledger: bool = True,
    fused: bool = True,
    repeats: int = 2,
    flat_flux: bool = True,
    sd_mode: str = "segment",
    kernel: str = "xla",
    lane_block: int | None = None,
) -> dict:
    import contextlib

    import jax

    from pumiumtally_tpu.utils.platform import require_tpu

    require_tpu()  # also for callers that skip main()
    repeats = max(1, repeats)
    import jax.numpy as jnp

    from pumiumtally_tpu import build_box, make_flux
    from pumiumtally_tpu.core.tally import accumulate_batch_squares
    from pumiumtally_tpu.obs import (
        WALK_STATS_LEN,
        reduce_chip_stats,
        stats_to_dict,
    )
    from pumiumtally_tpu.ops.walk import resolve_tally_scatter, trace_impl
    from pumiumtally_tpu.utils.profiling import (
        annotate,
        device_memory_stats,
        profile_trace,
    )

    # BENCH_TRACE=/path captures an xprof trace of the whole measured
    # section; the annotate() spans below (and the facade-phase spans in
    # api.py) show up as named host tracks in the viewer.
    trace_dir = os.environ.get("BENCH_TRACE")
    trace_cm = (
        profile_trace(trace_dir) if trace_dir else contextlib.nullcontext()
    )

    # Resolve 'auto' here (post backend pin) so the detail record names
    # the concrete strategy that actually ran, not the literal 'auto'.
    tally_scatter = resolve_tally_scatter(tally_scatter)
    dtype = jnp.dtype(dtype_name)
    t0 = time.perf_counter()
    mesh = build_box(1.0, 1.0, 1.0, cells, cells, cells, dtype=dtype)
    build_s = time.perf_counter() - t0

    # Autotuning axes (round 7): the record carries the resolved
    # tuning-database path (PUMI_TPU_TUNING / BENCH knob semantics of
    # TallyConfig.resolve_tuning), whether THIS workload's shape class
    # hit an entry, and the Pallas lane_block that actually ran — so
    # A/B captures can be grouped by tuning decision exactly like the
    # PR 7 kernel axis.
    from pumiumtally_tpu.utils.config import TallyConfig as _TC

    tuning_db = _TC().resolve_tuning()
    tuned = None
    if tuning_db is not None:
        from pumiumtally_tpu.tuning import lookup_tuned

        tuned = lookup_tuned(
            tuning_db,
            ntet=mesh.ntet,
            n_particles=n_particles,
            n_groups=n_groups,
            dtype=dtype,
            packed=getattr(mesh, "geo20", None) is not None,
        )
    # Two resolution layers, kept separate on purpose: the EXPLICIT
    # knob (BENCH_LANE_BLOCK / the env override) goes to the facade
    # rows as a config field, while the headline trace additionally
    # falls through to the database winner for ITS shape class. The
    # event/pipeline facades consult the database themselves for their
    # own (smaller) shape classes — handing them the headline's tuned
    # winner as an "explicit" knob would override their resolve.
    # The explicit value stays UNCLAMPED (validated power of two): it
    # re-enters resolve_lane_block as a config field in the facade
    # rows, where the pow2 check runs before the batch clamp — a
    # batch-clamped (possibly non-pow2) value would be rejected there.
    lane_block_explicit = _TC(
        pallas_lane_block=lane_block
    ).resolve_lane_block()
    lane_block = (
        _TC(
            pallas_lane_block=lane_block_explicit
        ).resolve_lane_block(n_particles)
        if lane_block_explicit is not None
        else _TC().resolve_lane_block(n_particles, tuned=tuned)
    )

    # Walk-kernel axis (round 6): "pallas" routes every trace through
    # the Mosaic kernel (ops/walk_pallas.py); "auto" resolves against
    # THIS workload — steered by the tuning database's winner when one
    # is active — so the record names the backend that actually ran.
    # An explicit "pallas" outside its regime (no packed table, over
    # the VMEM budget) fails here, before any measurement.
    if kernel != "xla":
        from pumiumtally_tpu.ops.walk_pallas import select_backend

        kernel = select_backend(
            kernel,
            ntet=mesh.ntet,
            n_particles=n_particles,
            n_groups=n_groups,
            dtype=dtype,
            packed=getattr(mesh, "geo20", None) is not None,
            lane_block=lane_block,
            tuned_kernel=tuned.kernel if tuned and tuned.hit else None,
        )
    # The effective block width of the kernel that runs: the resolved
    # knob (or the kernel default clamped to the batch) on the Mosaic
    # path, null on the XLA walk.
    if kernel == "pallas":
        from pumiumtally_tpu.ops.walk_pallas import DEFAULT_LANE_BLOCK

        lane_block_eff = min(
            lane_block or DEFAULT_LANE_BLOCK, n_particles
        )
    else:
        lane_block_eff = None

    rng = np.random.default_rng(seed)
    elem = jnp.asarray(
        rng.integers(0, mesh.ntet, n_particles).astype(np.int32)
    )
    origin = jnp.asarray(
        np.asarray(mesh.centroids())[np.asarray(elem)], dtype
    )
    in_flight = jnp.ones(n_particles, bool)
    weight = jnp.ones(n_particles, dtype)
    group = jnp.asarray(
        rng.integers(0, n_groups, n_particles).astype(np.int32)
    )
    material = jnp.full(n_particles, -1, jnp.int32)
    # Flat device layout — [ntet,n_groups,2] pads its minor dim 2 → 128
    # under the TPU (8,128) tile (64× HBM; the 64-group config OOMed at
    # 32.7 GB as 3-D, round 4). See core.tally.make_flux. BENCH_FLAT=0
    # restores the 3-D layout for the A/B.
    flux = make_flux(mesh.ntet, n_groups, dtype, flat=flat_flux)

    if compact_stages in ("default", "plan"):
        # ONE definition, shared with production:
        # TallyConfig.resolve_compact_stages. "default" = the
        # density-scaled dense ladder ("auto": stage starts stretch by
        # (ntet/998250)^(1/3) = cells/55 on box meshes — measured mean
        # 14.9 crossings/move at 55 cells → 32.6 at 119); "plan" = the
        # executional ladder planner (utils/ladder.plan_stages) at the
        # density-estimated mean — the wave-3 A/B row against the dense
        # default (simulator says fewer slot-equivalents; hardware
        # arbitrates).
        from pumiumtally_tpu.utils.config import TallyConfig

        mode = "auto" if compact_stages == "default" else "plan"
        compact_stages = TallyConfig(
            compact_stages=mode, unroll=unroll
        ).resolve_compact_stages(n_particles, ntet=mesh.ntet)

    import functools

    if sd_mode not in ("segment", "batch", "none"):
        raise ValueError(f"BENCH_SD must be segment|batch|none: {sd_mode!r}")
    # "segment" scatters (c, c²) per crossing (reference parity);
    # "batch" scatters only c and folds ONE squared per-bin delta per
    # step (TallyConfig sd_mode="batch" — the −20% nosq lever with the
    # sd retained at batch statistics); "none" drops squares entirely
    # (the pure nosq A/B bound).
    if sd_mode == "batch" and not flat_flux:
        raise ValueError("BENCH_SD=batch requires the flat flux layout")

    def one_step(key, origin, elem, flux):
        kd, kl = jax.random.split(key)
        direction = jax.random.normal(kd, (n_particles, 3), dtype)
        direction = direction / jnp.linalg.norm(
            direction, axis=1, keepdims=True
        )
        length = jax.random.exponential(kl, (n_particles, 1), dtype) * mean_path
        dest = jnp.clip(origin + direction * length, 0.01, 0.99)
        r = trace_impl(
            mesh, origin, dest, elem, in_flight, weight, group, material,
            flux,
            initial=False,
            max_crossings=mesh.ntet + 64,
            score_squares=sd_mode == "segment",
            tolerance=1e-6,
            compact_after=compact_after,
            compact_size=compact_size,
            compact_stages=compact_stages,
            unroll=unroll,
            robust=robust,
            tally_scatter=tally_scatter,
            gathers=gathers,
            ledger=ledger,
            n_groups=n_groups,
            kernel=kernel,
            **(
                {"lane_block": lane_block_eff}
                if kernel == "pallas" and lane_block_eff
                else {}
            ),
        )
        return (
            r.position, r.elem, r.flux, r.n_segments, r.n_crossings,
            r.stats,
        )

    step = functools.partial(jax.jit, donate_argnums=(1, 2, 3))(one_step)

    # Fused mode (the default): all `steps` advances inside ONE device
    # program (lax.fori_loop over precomputed keys) — a single dispatch
    # and a single readback, so the number is pure device time. fused=False
    # launches one program per step (the reference's one-launch-per-move
    # shape); the gap between the two IS the dispatch overhead.
    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def run_fused(keys, origin, elem, flux):
        import jax.lax as lax

        def body(i, c):
            origin, elem, flux, prev_even, tot, _, slog = c
            pos, el, fl, nseg, ncross, sv = one_step(
                keys[i], origin, elem, flux
            )
            if sd_mode == "batch":
                # ONE definition of the fold (jit-in-jit inlines), so
                # the benchmark measures exactly the production math.
                fl, prev_even = accumulate_batch_squares(fl, prev_even)
            # Per-move telemetry log: one [8] row per step, read back
            # once after the timed window (no readback inside the loop).
            slog = lax.dynamic_update_slice(
                slog, sv.astype(slog.dtype)[None], (i, 0)
            )
            return pos, el, fl, prev_even, tot + nseg, ncross, slog

        nseg_dtype = (
            jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
        )  # matches trace_impl's n_segments carry dtype
        prev0 = jnp.zeros(
            flux.size // 2 if sd_mode == "batch" else 0, dtype
        )
        slog0 = jnp.zeros((keys.shape[0], WALK_STATS_LEN), nseg_dtype)
        out = lax.fori_loop(
            0, keys.shape[0], body,
            (origin, elem, flux, prev0, jnp.zeros((), nseg_dtype),
             jnp.int32(0), slog0),
        )
        return out[0], out[1], out[2], out[4], out[5], out[6]

    key = jax.random.key(seed)
    keys = jax.random.split(key, steps + 2)

    # Host snapshots of the initial state, taken BEFORE the warmup call
    # donates the device buffers: every measurement window restarts from
    # these (same keys + same initial state = identical workload), so
    # best-of-N bounds run-to-run interference instead of conflating it
    # with workload drift as particles evolve.
    elem_h = np.asarray(elem)
    origin_h = np.asarray(origin)

    def fresh_state():
        w_origin = jnp.asarray(origin_h, dtype)
        w_elem = jnp.asarray(elem_h)
        w_flux = make_flux(mesh.ntet, n_groups, dtype, flat=flat_flux)
        jax.block_until_ready((w_origin, w_elem, w_flux))
        return w_origin, w_elem, w_flux

    # The xprof capture (when BENCH_TRACE is set) brackets compile +
    # every measurement window; closed right after the windows so the
    # event-loop section below stays out of the trace.
    _trace_stack = contextlib.ExitStack()
    _trace_stack.enter_context(trace_cm)
    if fused:
        # Warmup/compile with a 1-step fused program shape? No — the
        # fused program's shape depends on `steps`, so warm the REAL
        # shape once (its result is discarded) and time the second call.
        t0 = time.perf_counter()
        with annotate("bench:compile"):
            pos, elem_c, flux, tot, ncross, slog = run_fused(
                keys[2:], origin, elem, flux
            )
            int(np.asarray(tot))
        compile_s = time.perf_counter() - t0
        # Repeated measurement windows on the SAME compiled program AND
        # the same initial state (restaged per window, outside the
        # clock): the headline is the best window, and every window is
        # recorded in detail.windows so the spread is visible.
        windows = []
        for w_i in range(repeats):
            w_origin, w_elem, w_flux = fresh_state()
            with annotate(f"bench:window{w_i}"):
                t0 = time.perf_counter()
                pos, elem_c, flux, tot, ncross, slog = run_fused(
                    keys[2:], w_origin, w_elem, w_flux
                )
                wseg = int(np.asarray(tot))
                windows.append((wseg, time.perf_counter() - t0))
        # Per-move stats from the last window (identical workload every
        # window), fetched AFTER the clock stopped.
        stats_rows = np.asarray(slog)
    else:
        # Warmup / compile.
        t0 = time.perf_counter()
        with annotate("bench:compile"):
            pos, elem_c, flux, nseg, _, sv = step(
                keys[0], origin, elem, flux
            )
            jax.block_until_ready(pos)
        compile_s = time.perf_counter() - t0
        pos, elem_c, flux, nseg, _, sv = step(keys[1], pos, elem_c, flux)
        jax.block_until_ready(pos)

        windows = []
        for w_i in range(repeats):
            pos, elem_c, flux = fresh_state()
            prev_even = jnp.zeros(flux.size // 2, dtype)
            total_segments = 0
            step_stats = []
            with annotate(f"bench:window{w_i}"):
                t0 = time.perf_counter()
                for i in range(steps):
                    pos, elem_c, flux, nseg, ncross, sv = step(
                        keys[2 + i], pos, elem_c, flux
                    )
                    if sd_mode == "batch":
                        flux, prev_even = accumulate_batch_squares(
                            flux, prev_even
                        )
                    total_segments += nseg  # device-side; read at end
                    step_stats.append(sv)  # device arrays — no readback
                # Host readback of a value depending on every step — a
                # stricter fence than block_until_ready on one output
                # buffer.
                total_segments = int(np.asarray(total_segments))
                windows.append(
                    (total_segments, time.perf_counter() - t0)
                )
        stats_rows = np.stack([np.asarray(s) for s in step_stats])
    _trace_stack.close()

    total_segments, elapsed = max(windows, key=lambda w: w[0] / w[1])
    segments_per_sec = total_segments / elapsed

    # ---- telemetry block (acceptance: per-move depth in BENCH JSON) ----
    # Aggregation via the ONE schema-aware reducer (obs.walk_stats
    # reduce_chip_stats — sums everywhere, max of max_crossings, derived
    # occupancy), so the bench totals and the facade telemetry cannot
    # drift when the stats schema grows.
    telemetry = {
        "per_move": [stats_to_dict(row) for row in stats_rows],
        "totals": reduce_chip_stats(stats_rows),
        "device_memory": device_memory_stats(),
    }

    # ---- event-loop benchmark (reference §3.3 per-event pattern) -------
    # Drives PumiTally.move_to_next_location with per-event HOST arrays:
    # H2D staging, fused walk, D2H position/material write-back and a
    # device sync per call — the reference's per-advance-event contract
    # (cpp:221-264) — plus the double-buffered StreamingTallyPipeline
    # variant, which keeps `depth` walks in flight and defers readbacks.
    event = {}
    if os.environ.get("BENCH_EVENT", "1") == "1":
        event = run_event_loop(
            mesh,
            n_particles=int(
                os.environ.get(
                    "BENCH_EVENT_PARTICLES",
                    str(min(262144, n_particles)),
                )
            ),
            moves=int(os.environ.get("BENCH_EVENT_MOVES", "4")),
            n_groups=n_groups,
            dtype=dtype,
            mean_path=mean_path,
            seed=seed,
            kernel=kernel,
            lane_block=lane_block_explicit,
        )

    # ---- fault-recovery benchmark (MTTR axes, BENCH_FAULTS=<spec>) -----
    fault = {}
    if os.environ.get("BENCH_FAULTS"):
        fault = run_fault_recovery(
            os.environ["BENCH_FAULTS"], n_groups=n_groups, seed=seed
        )

    # ---- serving saturation probe (BENCH_SERVE=<n_jobs>) ---------------
    serve = {}
    if os.environ.get("BENCH_SERVE"):
        serve = run_serve_saturation(
            int(os.environ["BENCH_SERVE"]), seed=seed
        )

    # ---- serving fleet probe (BENCH_FLEET=<n_members>) -----------------
    fleet = {}
    if os.environ.get("BENCH_FLEET"):
        fleet = run_fleet_bench(
            int(os.environ["BENCH_FLEET"]), seed=seed
        )

    # ---- span-tracing overhead probe (BENCH_TRACE_SPANS=1) -------------
    trace_spans = {}
    if os.environ.get("BENCH_TRACE_SPANS"):
        trace_spans = run_trace_overhead()

    per_chip_baseline = 1e9 / 64.0
    return {
        "metric": "particle_segments_per_sec_per_chip",
        "value": round(segments_per_sec, 1),
        "unit": "segments/s",
        # Which backend actually produced the number — "cpu" rows are
        # rehearsal/fallback measurements, never comparable to TPU rows.
        "backend": jax.default_backend(),
        # Which WALK KERNEL produced it (round 6 A/B axis): "xla" is
        # the scattered body, "pallas" the Mosaic matrixized-tally
        # kernel — the RESOLVED value when the caller asked for "auto".
        "kernel": kernel,
        # Autotuning axes (round 7): the EFFECTIVE Pallas one-hot block
        # width (null on the XLA walk), the tuning database consulted
        # (null = tuning off), and whether this workload's shape class
        # hit an entry — A/B captures group rows by these exactly like
        # the kernel axis.
        "lane_block": lane_block_eff,
        "tuning_db": tuning_db,
        "tuned": (
            ("hit" if tuned.hit else "miss")
            if tuned is not None else "miss"
        ),
        "vs_baseline": round(segments_per_sec / per_chip_baseline, 4),
        # Dispatch-amortization axes (the megastep tentpole's tracked
        # win): moves retired per wall-second, and how many host→device
        # program dispatches each move cost. The fused kernel loop is
        # the megastep shape (steps moves per ONE dispatch); fused=0 is
        # the per-move shape (1 dispatch per move). The event-loop /
        # megastep facade measurements carry their own copies in
        # detail.
        "moves_per_sec": round(steps / elapsed, 2),
        "dispatches_per_move": round((1.0 / steps) if fused else 1.0, 4),
        # Per-move walk depth (obs/walk_stats.py schema): crossings,
        # max crossings/particle, chase hops, truncations, compaction
        # occupancy, segments, loop iters — one row per step of the
        # measured window, folded on device (schema documented in
        # BENCHMARKS.md "Telemetry block").
        "telemetry": telemetry,
        "detail": {
            "ntet": mesh.ntet,
            "n_particles": n_particles,
            "n_groups": n_groups,
            "steps": steps,
            "dtype": str(dtype_name),
            "total_segments": total_segments,
            "elapsed_s": round(elapsed, 4),
            "mesh_build_s": round(build_s, 2),
            "compile_s": round(compile_s, 2),
            "device": str(jax.devices()[0]),
            "robust": robust,
            "tally_scatter": tally_scatter,
            "gathers": gathers,
            "kernel": kernel,
            "lane_block": lane_block_eff,
            "tuning_db": tuning_db,
            "tuned_key": tuned.key if tuned is not None else None,
            "ledger": ledger,
            "fused_steps": fused,
            "flat_flux": flat_flux,
            "sd_mode": sd_mode,
            # Per-window (segments, seconds) for every measurement
            # repeat; the headline is the best window.
            "windows": [
                [w, round(s, 4)] for w, s in windows
            ],
            # The persistent compile cache in use (not whether this
            # compile hit it). compile_s under a warm cache measures
            # deserialization, not compilation.
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "last_step_crossing_iters": int(np.asarray(ncross)),
            **event,
            **fault,
            **serve,
            **fleet,
            **trace_spans,
        },
    }


def run_trace_overhead() -> dict:
    """Span-tracing overhead probe (``BENCH_TRACE_SPANS=1``): price one
    span/event emission on the serving tracer (obs/trace.py) — the
    enabled ring+sink-less path the scheduler pays per quantum, the
    disabled (``PUMI_TPU_TRACE=off``) no-op path, and the black-box
    chrome render over a full ring.  Host-side only (no device work):
    the number that matters for the zero-cost-to-physics claim is
    nanoseconds per span against a multi-millisecond quantum.
    ``BENCH_TRACE_N`` (default 200000) sets the sample count."""
    import time as _time

    from pumiumtally_tpu.obs import SpanTracer

    n = int(os.environ.get("BENCH_TRACE_N", "200000"))
    out: dict = {"spans_n": n}
    for label, enabled in (("on", True), ("off", False)):
        tr = SpanTracer(capacity=1024, enabled=enabled)
        tid = SpanTracer.new_trace()
        with tr.bind(tid, "bench-job", SpanTracer.root_id(tid)):
            t0 = _time.perf_counter()
            for i in range(n):
                tr.span_record(
                    "quantum", 1e-3, k=4, move_start=i,
                    device_seconds=1e-3,
                )
            dt = _time.perf_counter() - t0
        out[f"span_ns_{label}"] = round(dt / n * 1e9, 1)
        if enabled:
            t0 = _time.perf_counter()
            doc = tr.chrome()
            out["chrome_render_ms_full_ring"] = round(
                (_time.perf_counter() - t0) * 1e3, 3
            )
            out["ring_records"] = len(doc["traceEvents"]) - 1
    return {"trace_overhead": out}


def run_fault_recovery(spec: str, n_groups: int, seed: int) -> dict:
    """Supervised fault-mode probe: drive a small ResilientRunner run
    under ``BENCH_FAULTS=<spec>`` (PUMI_TPU_FAULTS grammar) and record
    the MTTR axes the BENCHMARKS.md recovery-overhead trajectory
    tracks — ``recovery_seconds`` (wall-clock spent inside coordinated
    rollback / reshard / backoff) and ``lost_moves`` (moves the fault
    cost that a resume would replay) — tagged with the active spec.
    Runs the partitioned facade when the spec loses a chip and the
    backend has a mesh to shrink (the elastic path IS the measured
    recovery); knobs BENCH_FAULT_CELLS/PARTICLES/MOVES keep it small
    — this prices recovery, not throughput."""
    import shutil
    import tempfile

    import jax

    from pumiumtally_tpu import PumiTally, TallyConfig, build_box
    from pumiumtally_tpu.resilience import (
        ChipLostError,
        FaultInjector,
        InjectedFault,
        ResilientRunner,
        parse_faults,
    )

    cells = int(os.environ.get("BENCH_FAULT_CELLS", "4"))
    n = int(os.environ.get("BENCH_FAULT_PARTICLES", "64"))
    moves = int(os.environ.get("BENCH_FAULT_MOVES", "6"))
    plan = parse_faults(spec)
    mesh = build_box(1.0, 1.0, 1.0, cells, cells, cells)
    cfg = TallyConfig(n_groups=n_groups, tolerance=1e-6)
    n_dev = jax.local_device_count()
    partitioned = plan.chip_down_at_move is not None and n_dev >= 2
    if partitioned:
        from pumiumtally_tpu.parallel.partitioned_api import (
            PartitionedTally,
        )

        tally = PartitionedTally(mesh, n, cfg, n_parts=min(8, n_dev))
    else:
        tally = PumiTally(mesh, n, cfg)
    ckdir = tempfile.mkdtemp(prefix="bench_faults_")
    # backoff_base=0: recovery_seconds prices the real recovery work
    # (classify + probe + rollback + reshard/recompile), not the
    # injected exponential-backoff sleep a production run would add.
    runner = ResilientRunner(
        tally, ckdir, every_moves=2, handle_signals=False,
        backoff_base=0.0, faults=FaultInjector(plan),
    )
    rng = np.random.default_rng(seed)
    outcome = "completed"
    t0 = time.perf_counter()
    try:
        runner.initialize_particle_location(
            rng.uniform(0.1, 0.9, (n, 3)).ravel()
        )
        for i in range(1, moves + 1):
            r = np.random.default_rng(seed + i)
            runner.move_to_next_location(
                r.uniform(0.05, 0.95, (n, 3)).ravel(),
                np.ones(n, np.int8),
                r.uniform(0.5, 2.0, n),
                r.integers(0, n_groups, n).astype(np.int32),
                np.full(n, -1, np.int32),
            )
    except (InjectedFault, ChipLostError) as e:
        # Kill/preemption specs end the probe run by design, and so
        # does a chip loss with nothing to shrink onto (single-device
        # backend); the record reports what the eviction cost.
        outcome = type(e).__name__
    elapsed = time.perf_counter() - t0
    st = runner.recovery_stats
    completed = int(runner.tally.iter_count)
    runner.close(final_checkpoint=False)
    shutil.rmtree(ckdir, ignore_errors=True)
    return {
        "fault_spec": spec,
        "fault_outcome": outcome,
        "fault_facade": "partitioned" if partitioned else "single",
        "fault_n_parts": int(getattr(runner.tally, "n_parts", 1)),
        "fault_moves_completed": completed,
        "recovery_seconds": round(st["recovery_seconds"], 4),
        "lost_moves": int(st["lost_moves"] + max(0, moves - completed)),
        "fault_rollbacks": int(st["rollbacks"]),
        "fault_reshards": int(st["reshards"]),
        "fault_elapsed_s": round(elapsed, 4),
    }


def run_serve_saturation(n_jobs: int, seed: int) -> dict:
    """Serving saturation probe (``BENCH_SERVE=<n_jobs>``): drive the
    scripts/serve.py scheduler (serving/TallyScheduler through the
    shared ``run_saturation`` workload driver) in-process, three
    passes over the SAME job mix —

      aot=off    no program bank (the jit path; its first pass carries
                 the jit compiles the bank exists to eliminate),
      aot=miss   a cold bank (every entry compiled + serialized here —
                 the one-time population cost),
      aot=hit    a warm bank on the same directory in a fresh
                 ProgramBank (every entry deserialized; compile_seconds
                 must be 0 — the steady-state serving regime),

    — and record ``jobs_per_sec`` + the bank counters per pass, each
    row tagged with its ``aot`` axis.  The warm pass's flux is checked
    bitwise against the off pass (the AOT-vs-jit parity contract, also
    pinned in tests/test_serving.py).  With ``BENCH_SERVE_FAULTS=
    <spec>`` (the PUMI_TPU_FAULTS grammar, e.g.
    ``poison_job:1,transient_quantum:2``) a FOURTH pass re-runs the
    same mix over the warm bank under the fault storm, tagged
    ``aot="faults"``, recording ``jobs_per_sec`` under fire plus
    per-job retries/``recovery_seconds`` and the survivor-bitwise
    check against the off pass (the serving fault-isolation contract,
    tests/test_serving_resilience.py).  Knobs: BENCH_SERVE_CELLS (4),
    BENCH_SERVE_CLASSES ("96,192"), BENCH_SERVE_MOVES (8),
    BENCH_SERVE_QUANTUM (4), BENCH_SERVE_RESIDENT (2),
    BENCH_SERVE_BANK (default: ``.pumi_bank/bench_serve`` in the
    checkout, emptied before the cold pass)."""
    import shutil

    from pumiumtally_tpu import TallyConfig, build_box
    from pumiumtally_tpu.serving import run_saturation

    cells = int(os.environ.get("BENCH_SERVE_CELLS", "4"))
    classes = tuple(
        int(x) for x in os.environ.get(
            "BENCH_SERVE_CLASSES", "96,192"
        ).split(",")
    )
    moves = int(os.environ.get("BENCH_SERVE_MOVES", "8"))
    quantum = int(os.environ.get("BENCH_SERVE_QUANTUM", "4"))
    resident = int(os.environ.get("BENCH_SERVE_RESIDENT", "2"))
    from pumiumtally_tpu.utils.platform import DEFAULT_BANK_DIR

    bank_dir = os.environ.get("BENCH_SERVE_BANK") or os.path.join(
        DEFAULT_BANK_DIR, "bench_serve"
    )
    # The "miss" pass measures a cold bank: start from an empty one.
    shutil.rmtree(bank_dir, ignore_errors=True)
    mesh = build_box(1.0, 1.0, 1.0, cells, cells, cells)
    cfg = TallyConfig(
        n_groups=int(os.environ.get("BENCH_GROUPS", "2")),
        tolerance=1e-6,
    )

    def one_pass(tag, bank, faults=None):
        t0 = time.perf_counter()
        out = run_saturation(
            mesh, cfg, bank=bank, n_jobs=n_jobs, class_sizes=classes,
            n_moves=moves, seed=seed, max_resident=resident,
            quantum_moves=quantum, faults=faults,
        )
        aot = out["scheduler"]["aot"] or {}
        return out, {
            "aot": tag,
            "jobs_per_sec": out["jobs_per_sec"],
            "elapsed_s": out["elapsed_s"],
            "wall_s": round(time.perf_counter() - t0, 4),
            "compile_seconds": aot.get("compile_seconds", 0.0),
            "aot_hits": aot.get("hits", 0),
            "aot_misses": aot.get("misses", 0),
            "aot_rewrites": aot.get("rewrites", 0),
            "outcomes": out["scheduler"]["outcomes"],
        }

    fault_spec = os.environ.get("BENCH_SERVE_FAULTS", "")
    # The bank rides as a path: each pass gets a fresh ProgramBank
    # on the scheduler's own registry (cold = empty dir → misses,
    # warm = the populated dir → hits).
    off_out, off_row = one_pass("off", None)
    _, cold_row = one_pass("miss", bank_dir)
    warm_out, warm_row = one_pass("hit", bank_dir)
    parity = all(
        warm_out["results"][k].tobytes()
        == off_out["results"][k].tobytes()
        for k in off_out["results"]
    )
    rows = [off_row, cold_row, warm_row]
    storm = None
    if fault_spec:
        # Fault-storm pass over the warm bank: jobs_per_sec under
        # fire, per-job MTTR, and survivor-bitwise isolation vs
        # the fault-free off pass.
        from pumiumtally_tpu.resilience.faultinject import (
            FaultInjector,
            parse_faults,
        )

        fault_plan = parse_faults(fault_spec)
        if fault_plan.kill_server_at_quantum is not None:
            # The crash-model fault kills THIS process — it can
            # only be measured from outside (the chaos_serve
            # subprocess driver), never by the in-process bench.
            raise ValueError(
                "BENCH_SERVE_FAULTS: kill_server_at_quantum is "
                "the crash-model fault; the bench measures a "
                "surviving server — drive server kills through "
                "scripts/chaos_serve.py instead"
            )
        f_out, f_row = one_pass(
            "faults", bank_dir,
            faults=FaultInjector(fault_plan),
        )
        f_row["faults"] = fault_spec
        f_row["retries"] = f_out["scheduler"]["retries"]
        f_row["per_job"] = [
            {
                "job": r["job"],
                "outcome": r["outcome"],
                "retries": r["retries"],
                "recovery_seconds": r["recovery_seconds"],
            }
            for r in f_out["per_job"]
        ]
        f_row["survivors_bitwise"] = all(
            f_out["results"][k].tobytes()
            == off_out["results"][k].tobytes()
            for k in f_out["results"]
        )
        rows.append(f_row)
        storm = fault_spec
    return {
        "serve": {
            "n_jobs": n_jobs,
            "classes": list(classes),
            "n_moves": moves,
            "quantum_moves": quantum,
            "max_resident": resident,
            "aot_bitwise_vs_jit": bool(parity),
            "fault_storm": storm,
            "runs": rows,
        }
    }


def run_fleet_bench(n_members: int, seed: int) -> dict:
    """Serving-fleet probe (``BENCH_FLEET=<n_members>``): drive the
    SAME job mix as the BENCH_SERVE probe through the multi-chip
    ``FleetRouter`` (serving/fleet.py — one journaled TallyScheduler
    per member over one shared warm bank) and record fleet
    ``jobs_per_sec`` plus per-member placement counts, so the fleet
    row prices the routing + FLEET.json write-ahead overhead directly
    against the single-scheduler ``aot=hit`` row.  Jobs are submitted
    in-process (``via_http=False``) — the HTTP gateway's wire cost is
    a serving concern, not a scheduling one, and keeping it out makes
    jobs_per_sec comparable.  Reuses the BENCH_SERVE_* knobs for the
    workload shape; BENCH_FLEET_JOBS (default 8) sets the job count."""
    import shutil
    import tempfile

    from pumiumtally_tpu import TallyConfig, build_box
    from pumiumtally_tpu.serving import run_fleet_saturation

    cells = int(os.environ.get("BENCH_SERVE_CELLS", "4"))
    classes = tuple(
        int(x) for x in os.environ.get(
            "BENCH_SERVE_CLASSES", "96,192"
        ).split(",")
    )
    moves = int(os.environ.get("BENCH_SERVE_MOVES", "8"))
    quantum = int(os.environ.get("BENCH_SERVE_QUANTUM", "4"))
    resident = int(os.environ.get("BENCH_SERVE_RESIDENT", "2"))
    n_jobs = int(os.environ.get("BENCH_FLEET_JOBS", "8"))
    mesh = build_box(1.0, 1.0, 1.0, cells, cells, cells)
    cfg = TallyConfig(
        n_groups=int(os.environ.get("BENCH_GROUPS", "2")),
        tolerance=1e-6,
    )
    from pumiumtally_tpu.utils.platform import DEFAULT_BANK_DIR

    tmp = tempfile.mkdtemp(prefix="pumi_fleet_bench_")
    bank_dir = os.path.join(DEFAULT_BANK_DIR, "bench_fleet")
    try:
        # Warm the shared bank first (one single-member pass), so the
        # fleet row measures steady-state routing, not compiles.
        run_fleet_saturation(
            mesh, cfg, fleet_dir=os.path.join(tmp, "warmup"),
            n_members=1, bank=bank_dir, n_jobs=len(classes),
            class_sizes=classes, n_moves=moves, seed=seed,
            via_http=False, max_resident=resident,
            quantum_moves=quantum,
        )
        out = run_fleet_saturation(
            mesh, cfg, fleet_dir=os.path.join(tmp, "fleet"),
            n_members=n_members, bank=bank_dir, n_jobs=n_jobs,
            class_sizes=classes, n_moves=moves, seed=seed,
            via_http=False, max_resident=resident,
            quantum_moves=quantum,
        )
        # A/B the observability plane (ISSUE 20): the identical
        # workload once more with PUMI_TPU_FLEET_OBS=off — the delta
        # prices aggregation + SLO evaluation + FLEETSTATS snapshots
        # at quantum cadence.  The headline jobs_per_sec stays the
        # plane-ON number (the shipped default).
        prior = os.environ.get("PUMI_TPU_FLEET_OBS")
        os.environ["PUMI_TPU_FLEET_OBS"] = "off"
        try:
            bare = run_fleet_saturation(
                mesh, cfg, fleet_dir=os.path.join(tmp, "fleet-bare"),
                n_members=n_members, bank=bank_dir, n_jobs=n_jobs,
                class_sizes=classes, n_moves=moves, seed=seed,
                via_http=False, max_resident=resident,
                quantum_moves=quantum,
            )
        finally:
            if prior is None:
                os.environ.pop("PUMI_TPU_FLEET_OBS", None)
            else:
                os.environ["PUMI_TPU_FLEET_OBS"] = prior
        st = out["fleet"]
        return {
            "fleet": {
                "n_members": n_members,
                "n_jobs": n_jobs,
                "classes": list(classes),
                "n_moves": moves,
                "quantum_moves": quantum,
                "max_resident": resident,
                "jobs_per_sec": out["jobs_per_sec"],
                "elapsed_s": out["elapsed_s"],
                "placements": st["placements"],
                "migrations": st["migrations"],
                "outcomes": st["outcomes"],
                "aot_hits": (st["aot"] or {}).get("hits", 0),
                "aot_misses": (st["aot"] or {}).get("misses", 0),
                "obs_plane": {
                    "jobs_per_sec_on": out["jobs_per_sec"],
                    "jobs_per_sec_off": bare["jobs_per_sec"],
                    "overhead_pct": round(
                        (bare["jobs_per_sec"] - out["jobs_per_sec"])
                        / bare["jobs_per_sec"] * 100.0, 2,
                    ) if bare["jobs_per_sec"] else None,
                },
            }
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_event_loop(
    mesh, n_particles, moves, n_groups, dtype, mean_path, seed,
    kernel="xla", lane_block=None,
) -> dict:
    """Measure the full per-event host loop and the streaming pipeline.

    Returns dict entries merged into the bench detail:
      event_loop_segments_per_sec — move_to_next_location with host
        arrays in and clipped positions/materials out, one sync per call.
      event_call_overhead_ms — per-call cost above a device-resident run
        of the SAME walk configuration and batch size (so the delta is
        purely H2D+D2H staging, host prep, and the per-call sync —
        SURVEY §7 hard part 6), measured here rather than derived from
        the differently-configured headline number.
      pipeline_segments_per_sec — StreamingTallyPipeline (depth 2).
    """
    from pumiumtally_tpu.api import PumiTally, TallyConfig
    from pumiumtally_tpu.models.pipeline import StreamingTallyPipeline

    rng = np.random.default_rng(seed + 1)
    # BENCH_CONVERGENCE=1: run the event loop with the fused
    # uncertainty reduction on, so the bench JSON prices the
    # convergence-observability overhead (the transfer-count invariant
    # is pinned by tests; this prices the in-program reductions) and
    # carries the run's convergence block.
    convergence = os.environ.get("BENCH_CONVERGENCE", "0") == "1"
    cfg = TallyConfig(
        dtype=dtype, n_groups=n_groups, tolerance=1e-6, unroll=8,
        compact_stages="auto",  # same dense ladder as the kernel bench,
        # so the event-loop vs kernel gap is dispatch overhead, not a
        # scheduling difference
        convergence=convergence,
        # The resolved walk-kernel axis rides the facade loop too, so
        # the event-loop / pipeline rows A/B the same backend as the
        # headline (the megastep rows below stay XLA — the fused
        # megastep program never rides the Mosaic kernel,
        # TallyConfig.resolve_kernel). The resolved lane_block rides
        # as the explicit config knob; a PUMI_TPU_TUNING database is
        # consulted by the facade's own construction-time resolve.
        kernel=kernel,
        pallas_lane_block=lane_block,
    )
    tally = PumiTally(mesh, n_particles, cfg)
    cents = np.asarray(mesh.centroids())
    elem = rng.integers(0, mesh.ntet, n_particles).astype(np.int32)
    pos0 = cents[elem].astype(np.float64)
    tally.initialize_particle_location(pos0.reshape(-1).copy())

    def new_dest(prev):
        d = rng.normal(0, 1, (n_particles, 3))
        d /= np.linalg.norm(d, axis=1, keepdims=True)
        ln = rng.exponential(mean_path, (n_particles, 1))
        return np.clip(prev + d * ln, 0.01, 0.99)

    weights = np.ones(n_particles)
    groups = rng.integers(0, n_groups, n_particles).astype(np.int32)
    mats = np.full(n_particles, -1, np.int32)

    # Warm the move signature (compile) outside the clock.
    prev = pos0
    buf = new_dest(prev).reshape(-1).copy()
    tally.move_to_next_location(
        buf, np.ones(n_particles, np.int8), weights, groups, mats
    )
    prev = buf.reshape(n_particles, 3)
    dests = [new_dest(prev)]
    for _ in range(moves - 1):
        # Pre-generate a plausible destination chain so host RNG cost
        # stays outside the comparison where possible (the true chain
        # depends on clipped positions; the first hop uses the real one).
        dests.append(new_dest(dests[-1]))

    seg0 = tally.total_segments
    t0 = time.perf_counter()
    for i in range(moves):
        buf = dests[i].reshape(-1).copy()
        tally.move_to_next_location(
            buf, np.ones(n_particles, np.int8), weights, groups, mats
        )
    dt = time.perf_counter() - t0
    segs = tally.total_segments - seg0
    event_rate = segs / dt
    t_call = dt / moves

    # Device-resident comparator: the SAME trace configuration and batch
    # size with inputs already on device and no per-call readback — the
    # honest kernel-only baseline for the overhead number.
    import jax.numpy as jnp

    from pumiumtally_tpu.core.tally import make_flux
    from pumiumtally_tpu.ops.walk import trace

    kw = dict(
        initial=False,
        max_crossings=cfg.resolve_max_crossings(mesh.ntet),
        score_squares=cfg.score_squares,
        tolerance=cfg.tolerance,
        unroll=cfg.unroll,
        compact_stages=cfg.resolve_compact_stages(
            n_particles, ntet=mesh.ntet
        ),
    )
    ca, cs = cfg.resolve_compaction(n_particles)
    kw.update(compact_after=ca, compact_size=cs, kernel=kernel)
    dev_origin = jnp.asarray(prev, cfg.dtype)
    dev_dests = [jnp.asarray(d, cfg.dtype) for d in dests]
    dev_elem = jnp.asarray(np.asarray(tally.state.elem))
    dev_if = jnp.ones(n_particles, bool)
    dev_w = jnp.asarray(weights, cfg.dtype)
    dev_g = jnp.asarray(groups)
    dev_m = jnp.full(n_particles, -1, jnp.int32)
    kw["n_groups"] = n_groups
    kflux = make_flux(mesh.ntet, n_groups, cfg.dtype, flat=True)
    r = trace(mesh, dev_origin, dev_dests[0], dev_elem, dev_if, dev_w,
              dev_g, dev_m, kflux, **kw)  # warm (already compiled shape)
    int(np.asarray(r.n_segments))  # fence
    cur, cure, kflux = r.position, r.elem, r.flux
    ksegs = 0
    t0 = time.perf_counter()
    for i in range(moves):
        r = trace(mesh, cur, dev_dests[i % len(dev_dests)], cure, dev_if,
                  dev_w, dev_g, dev_m, kflux, **kw)
        cur, cure, kflux = r.position, r.elem, r.flux
        ksegs += r.n_segments
    ksegs = int(np.asarray(ksegs))  # readback fence
    dt_k = time.perf_counter() - t0
    overhead_ms = (t_call - dt_k / moves) * 1e3

    # Streaming pipeline variant: independent batches, depth-2 overlap.
    pipe = StreamingTallyPipeline(mesh, cfg, depth=2, want_outputs=True)
    batches = []
    for _ in range(moves + 1):
        e = rng.integers(0, mesh.ntet, n_particles).astype(np.int32)
        o = cents[e]
        batches.append((o, new_dest(o), e))
    o, d, e = batches[0]
    pipe.submit(o, d, e, weight=weights, group=groups)  # warm/compile
    pipe.finish()
    t0 = time.perf_counter()
    for o, d, e in batches[1:]:
        pipe.submit(o, d, e, weight=weights, group=groups)
    flux = pipe.finish()
    dt_p = time.perf_counter() - t0
    del flux
    # Exclude the warm/compile batch (index 0) drained before the clock.
    psegs = sum(r.n_segments for r in pipe.results() if r.index > 0)
    pipe_rate = psegs / dt_p

    out = {
        "event_loop_segments_per_sec": round(event_rate, 1),
        "event_call_overhead_ms": round(overhead_ms, 2),
        "event_particles": n_particles,
        "event_moves": moves,
        "event_kernel": kernel,
        # Autotuning axes on the facade rows (the facade's OWN resolved
        # values — the truthful record of what construction decided).
        "event_lane_block": getattr(tally, "_lane_block", None),
        "event_tuned": (
            ("hit" if tally._tuned.hit else "miss")
            if getattr(tally, "_tuned", None) is not None else "miss"
        ),
        # Per-move dispatch accounting for the facade loop (each
        # move_to_next_location is one program dispatch).
        "event_moves_per_sec": round(moves / dt, 2),
        "event_dispatches_per_move": 1.0,
        "pipeline_segments_per_sec": round(pipe_rate, 1),
    }

    # Megastep facade loop (the device-sourced fused move loop): the
    # SAME mesh and batch size driven through run_source_moves with
    # K = BENCH_MEGASTEP moves per dispatch, so the JSON tracks the
    # dispatch-amortization win against the per-move event loop above.
    mk = int(os.environ.get("BENCH_MEGASTEP", "8"))
    if mk > 0:
        from pumiumtally_tpu.ops.source import SourceParams

        mcfg = TallyConfig(
            dtype=dtype, n_groups=n_groups, tolerance=1e-6,
            unroll=8, compact_stages="auto", megastep=mk,
        )
        # PUMI_TPU_MEGASTEP beats the config field in resolve_megastep();
        # account with the EFFECTIVE chunk size so dispatches_per_move
        # and the warm-dispatch count stay truthful under the override.
        mk = mcfg.resolve_megastep()
        mt = PumiTally(mesh, n_particles, mcfg)
        mt.initialize_particle_location(pos0.reshape(-1).copy())
        msrc = SourceParams(default_sigma_t=1.0 / mean_path, seed=seed)
        ones = np.ones(n_particles)
        zer = np.zeros(n_particles, np.int32)
        # Warm/compile one full-K dispatch outside the clock.
        mt.run_source_moves(mk, msrc, weights=ones, groups=zer,
                            alive=np.ones(n_particles, bool))
        seg0 = mt.total_segments
        t0 = time.perf_counter()
        mres = mt.run_source_moves(
            mk, msrc, weights=ones, alive=np.ones(n_particles, bool)
        )
        dt_m = time.perf_counter() - t0
        out.update(
            megastep_k=mk,
            megastep_segments_per_sec=round(
                (mt.total_segments - seg0) / dt_m, 1
            ),
            megastep_moves_per_sec=round(mres["moves"] / dt_m, 2),
            megastep_dispatches_per_move=round(1.0 / mk, 4),
        )
    if convergence:
        # The run's final convergence block (rel-err / converged
        # fraction / FOM) rides the bench record, so a soak's JSON is
        # self-describing about how converged its tallies were.
        out["convergence"] = tally.telemetry()["convergence"]
    return out


def _stages_from_env() -> tuple | str | None:
    """Resolve the compaction schedule from env:
      BENCH_STAGES="16:524288,24:262144" → explicit schedule (a third
        :N on an entry overrides the unroll for that stage)
      BENCH_STAGES=none                  → no staged schedule (the
        single-stage BENCH_COMPACT_AFTER/BENCH_COMPACT_SIZE knobs apply)
      BENCH_COMPACT_AFTER/SIZE set       → same fallthrough to single-stage
      otherwise                          → the tuned default schedule
    """
    stages = os.environ.get("BENCH_STAGES", "")
    if stages == "none":
        return None
    if stages == "plan":
        return "plan"
    if stages:
        entries = []
        for p in stages.split(","):
            fields = p.split(":")
            if len(fields) not in (2, 3) or not all(
                f.strip().isdigit() for f in fields
            ):
                raise ValueError(
                    f"BENCH_STAGES entries must be start:size[:unroll], got {p!r}"
                )
            entry = tuple(int(f) for f in fields)
            if entry[1] < 1 or (len(entry) == 3 and entry[2] < 1):
                raise ValueError(
                    f"BENCH_STAGES size/unroll must be >= 1, got {p!r}"
                )
            entries.append(entry)
        return tuple(entries)
    if os.environ.get("BENCH_COMPACT_AFTER") or os.environ.get(
        "BENCH_COMPACT_SIZE"
    ):
        return None  # let the single-stage knobs take effect
    return "default"


def main() -> None:
    from pumiumtally_tpu.utils.platform import require_tpu, use_compile_cache

    require_tpu()
    use_compile_cache()
    result = run(
        cells=int(os.environ.get("BENCH_CELLS", "55")),
        n_particles=int(os.environ.get("BENCH_PARTICLES", "1048576")),
        steps=int(os.environ.get("BENCH_STEPS", "10")),
        n_groups=int(os.environ.get("BENCH_GROUPS", "8")),
        dtype_name=os.environ.get("BENCH_DTYPE", "float32"),
        compact_after=(
            None
            if os.environ.get("BENCH_COMPACT_AFTER", "32") in ("", "none")
            else int(os.environ.get("BENCH_COMPACT_AFTER", "32"))
        ),
        compact_size=(
            int(os.environ["BENCH_COMPACT_SIZE"])
            if os.environ.get("BENCH_COMPACT_SIZE")
            else None
        ),
        compact_stages=_stages_from_env(),
        unroll=int(os.environ.get("BENCH_UNROLL", "8")),
        # Robust (the library default) measured FREE on TPU in the
        # round-4 A/B (7.266 vs 7.272 Mseg/s, within noise; the 2.5×
        # CPU cost does not transfer), so the headline now runs the
        # library-default configuration. BENCH_ROBUST=0 restores the
        # reference tracer's truncate-mode semantics for attribution.
        robust=os.environ.get("BENCH_ROBUST", "1") == "1",
        # "auto" = interleaved on TPU / pair on CPU (round-4 A/B).
        tally_scatter=os.environ.get("BENCH_SCATTER", "auto"),
        gathers=os.environ.get("BENCH_GATHERS", "merged"),
        ledger=os.environ.get("BENCH_LEDGER", "1") == "1",
        # Fused is the DEFAULT: the headline is a device-resident kernel
        # measurement in one fori_loop dispatch. BENCH_FUSED=0 restores
        # one-launch-per-step
        # (the per-move launch shape; its gap to fused IS that overhead).
        fused=os.environ.get("BENCH_FUSED", "1") == "1",
        repeats=int(os.environ.get("BENCH_REPEAT", "2")),
        flat_flux=os.environ.get("BENCH_FLAT", "1") == "1",
        # segment (reference parity) | batch (cheap sd: −20% step-time
        # squares share folded into one pass per step) | none (nosq A/B)
        sd_mode=os.environ.get("BENCH_SD", "segment"),
        # xla (scattered body) | pallas (Mosaic matrixized tally) |
        # auto (pallas inside its VMEM regime) — the round-6 A/B axis.
        kernel=os.environ.get("BENCH_KERNEL", "xla"),
        # Explicit Pallas one-hot block width (the round-7 tuning axis;
        # unset = the tuning database's winner under PUMI_TPU_TUNING,
        # else the kernel default 128).
        lane_block=(
            int(os.environ["BENCH_LANE_BLOCK"])
            if os.environ.get("BENCH_LANE_BLOCK")
            else None
        ),
    )
    print(
        f"[bench] {result['detail']}", file=sys.stderr
    )
    print(json.dumps(result))


if __name__ == "__main__":
    main()
