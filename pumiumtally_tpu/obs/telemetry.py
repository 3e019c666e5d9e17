"""Facade-level telemetry: one registry + one flight recorder per tally.

Shared by PumiTally and PartitionedTally so the two facades cannot drift
on metric names or record schemas. The facade calls:

  * ``record_walk(kind, move, stats, seconds=..., **extra)`` once per
    trace (initial search or move) with the host view of the on-device
    stats vector (obs.walk_stats.stats_to_dict / reduce_chip_stats);
    the single-chip facade's extras name the ``lanes`` the call walked
    and the ``dispatches`` (compiled walk-program launches) it made;
  * ``record_memory(phase)`` at phase boundaries (construction, VTK
    write) to capture per-device HBM peaks;
  * ``snapshot(times=...)`` from ``tally.telemetry()``.

Metric families (private registry per tally by default, so concurrent
tallies don't interleave):
  pumi_moves_total, pumi_segments_total, pumi_crossings_total,
  pumi_truncated_walks_total, pumi_chase_hops_total,
  pumi_migration_rounds_total, pumi_compaction_occupancy,
  pumi_move_seconds, pumi_device_peak_bytes{device=...},
  pumi_dispatches_total

Resilience families (fed by the quarantine / truncation-escalation
paths, resilience/):
  pumi_quarantined_lanes_total (deduplicated lanes),
  pumi_quarantine_reasons_total{reason=...},
  pumi_rewalked_lanes_total, pumi_lost_walks_total
"""
from __future__ import annotations

import dataclasses

from ..utils.profiling import device_memory_stats
from .recorder import FlightRecorder
from .registry import MetricsRegistry


class TallyTelemetry:
    def __init__(
        self,
        facade: str,
        registry: MetricsRegistry | None = None,
        recorder: FlightRecorder | None = None,
    ):
        self.facade = facade
        self.registry = registry if registry is not None else MetricsRegistry()
        self.recorder = recorder if recorder is not None else FlightRecorder()
        r = self.registry
        self._moves = r.counter(
            "pumi_moves_total", "facade move_to_next_location calls"
        )
        self._segments = r.counter(
            "pumi_segments_total", "scored particle-segments"
        )
        self._crossings = r.counter(
            "pumi_crossings_total", "real element-boundary crossings"
        )
        self._truncated = r.counter(
            "pumi_truncated_walks_total",
            "walks not finished within max_crossings / the round bound",
        )
        self._chase = r.counter(
            "pumi_chase_hops_total",
            "stuck-escape (relocation chase) activations",
        )
        self._rounds = r.counter(
            "pumi_migration_rounds_total",
            "partitioned walk/exchange rounds executed",
        )
        self._occ = r.gauge(
            "pumi_compaction_occupancy",
            "mean post-compaction active occupancy of the last trace",
        )
        self._dispatches = r.counter(
            "pumi_dispatches_total",
            "compiled walk-program launches (re-walks and megastep "
            "chunks each count one)",
        )
        self._move_s = r.histogram(
            "pumi_move_seconds", "wall-clock seconds per facade move"
        )
        self._hbm = r.gauge(
            "pumi_device_peak_bytes", "peak device memory in use"
        )
        self._quarantined = r.counter(
            "pumi_quarantined_lanes_total",
            "lanes masked out of the walk by the bad-particle "
            "quarantine (each lane once per move, however many "
            "reasons it trips)",
        )
        self._quarantine_reasons = r.counter(
            "pumi_quarantine_reasons_total",
            "quarantine verdicts by reason (a lane tripping several "
            "reasons counts once per reason)",
        )
        self._rewalked = r.counter(
            "pumi_rewalked_lanes_total",
            "truncated lanes re-walked by the escalation policy",
        )
        self._lost = r.counter(
            "pumi_lost_walks_total",
            "walks declared lost after bounded re-walk retries (or "
            "immediately, with the escalation policy off)",
        )
        # Move-loop I/O accounting (ops/staging.py): bytes and transfer
        # counts the facade actually staged per trace.  Under
        # io_pipeline="packed" the steady-state invariant is ONE H2D
        # and ONE D2H per move — tests/test_io_pipeline.py asserts it
        # through these counters under a jax.transfer_guard.
        self._h2d_bytes = r.counter(
            "pumi_h2d_bytes_total",
            "host-to-device bytes staged by the move loop",
        )
        self._d2h_bytes = r.counter(
            "pumi_d2h_bytes_total",
            "device-to-host bytes read back by the move loop",
        )
        self._h2d_transfers = r.counter(
            "pumi_h2d_transfers_total",
            "host-to-device transfers issued by the move loop",
        )
        self._d2h_transfers = r.counter(
            "pumi_d2h_transfers_total",
            "device-to-host transfers issued by the move loop",
        )
        # Self-verification families (integrity/): invariant + audit +
        # watchdog violations by check, shadow-audit volume, and the
        # worst conservation residual seen this run.
        self._integ_violations = r.counter(
            "pumi_integrity_violations_total",
            "integrity-check violations (labeled by check: "
            "conservation, flux, lanes, sdc_audit, watchdog)",
        )
        self._audited = r.counter(
            "pumi_audited_lanes_total",
            "lanes re-walked by the float64 shadow audit",
        )
        self._audit_mismatch = r.counter(
            "pumi_audit_mismatches_total",
            "shadow-audit lanes disagreeing with the host reference "
            "beyond tolerance",
        )
        self._max_residual = 0.0

    # ------------------------------------------------------------------ #
    def record_walk(
        self,
        kind: str,
        move: int,
        stats: dict | None,
        seconds: float | None = None,
        **extra,
    ) -> dict:
        """Fold one trace's stats into the counters and the recorder.
        ``stats`` is the named dict from the on-device stats vector (or
        None when walk stats are disabled); ``seconds`` is the facade
        phase time for this call where measured."""
        fields = dict(extra)
        fields["move"] = int(move)
        if seconds is not None:
            fields["seconds"] = round(float(seconds), 6)
            if kind == "move":
                self._move_s.observe(float(seconds))
        if kind == "move":
            self._moves.inc()
        elif kind == "megastep":
            # One megastep record covers K fused device moves; the
            # moves counter advances by the fused count so the totals
            # stay per-MOVE comparable across loop modes.
            self._moves.inc(int(extra.get("moves", 1)))
        if stats is not None:
            fields.update(stats)
            self._segments.inc(stats["segments"])
            self._crossings.inc(stats["crossings"])
            self._truncated.inc(stats["truncated"])
            self._chase.inc(stats["chase_hops"])
            if stats.get("occupancy") is not None:
                self._occ.set(stats["occupancy"])
        if "rounds" in extra:
            self._rounds.inc(int(extra["rounds"]))
        if "dispatches" in extra:
            self._dispatches.inc(int(extra["dispatches"]))
        # I/O accounting riding the same per-trace record (the facade
        # passes what it actually staged — packed: one record each way;
        # legacy: one entry per staged array).
        for key, counter in (
            ("h2d_bytes", self._h2d_bytes),
            ("d2h_bytes", self._d2h_bytes),
            ("h2d_transfers", self._h2d_transfers),
            ("d2h_transfers", self._d2h_transfers),
        ):
            if key in extra:
                counter.inc(int(extra[key]))
        return self.recorder.record(kind, **fields)

    def record_quarantine(
        self, move: int, lanes: int, reasons: dict
    ) -> dict:
        """Fold one move's quarantine verdicts: ``lanes`` is the
        DEDUPLICATED parked-lane count (the headline number, agrees
        with ``quarantined_lanes()``); ``reasons`` maps reason name →
        verdict count (resilience/quarantine.py REASONS)."""
        self._quarantined.inc(lanes)
        for reason, count in reasons.items():
            if count:
                self._quarantine_reasons.inc(count, reason=reason)
        return self.recorder.record(
            "quarantine", move=int(move), lanes=int(lanes), **reasons
        )

    def record_rewalk(self, move: int, retried: int, lost: int) -> dict:
        """Fold one move's truncation-escalation outcome: lanes
        re-walked (summed over attempts) and lanes finally lost."""
        if retried:
            self._rewalked.inc(retried)
        if lost:
            self._lost.inc(lost)
        return self.recorder.record(
            "rewalk", move=int(move), retried=int(retried),
            lost=int(lost),
        )

    def record_integrity(
        self, move: int, fields: dict, violations: list
    ) -> dict:
        """Fold one move's integrity evaluation: the invariant scalars
        (integrity/invariants.py field names, possibly empty for
        watchdog-only events) plus the violated check names. Counting
        happens here BEFORE policy escalation so the counters are
        consistent whichever rung fires."""
        for check in violations:
            self._integ_violations.inc(check=check)
        if fields.get("max_residual") is not None:
            self._max_residual = max(
                self._max_residual, float(fields["max_residual"])
            )
        return self.recorder.record(
            "integrity",
            move=int(move),
            violations=list(violations),
            **fields,
        )

    def record_audit(
        self, move: int, audited: int, mismatches: int, skipped: int,
        max_dev: float,
    ) -> dict:
        """Fold one move's shadow-audit outcome (integrity/audit.py) —
        per-move results in the flight recorder (and any
        PUMI_TPU_METRICS=jsonl: stream)."""
        if audited:
            self._audited.inc(audited)
        if mismatches:
            self._audit_mismatch.inc(mismatches)
        return self.recorder.record(
            "audit",
            move=int(move),
            audited=int(audited),
            mismatches=int(mismatches),
            skipped=int(skipped),
            max_dev=float(max_dev),
        )

    def record_memory(self, phase: str) -> dict:
        """Sample per-device memory at a phase boundary (peak bytes where
        the backend reports them — TPU does, CPU usually returns {})."""
        mem = device_memory_stats()
        for dev, rec in mem.items():
            if "peak_bytes_in_use" in rec:
                self._hbm.set(rec["peak_bytes_in_use"], device=dev)
        return self.recorder.record("memory", phase=phase, devices=mem)

    # ------------------------------------------------------------------ #
    def snapshot(self, times=None, tail: int = 64) -> dict:
        """The ``tally.telemetry()`` payload: counter totals, the last
        ``tail`` flight records, a fresh memory sample, phase times, and
        the full registry snapshot."""
        quarantined = self._quarantined.value()
        out = {
            "facade": self.facade,
            "totals": {
                "moves": self._moves.value(),
                "segments": self._segments.value(),
                "crossings": self._crossings.value(),
                "truncated": self._truncated.value(),
                "chase_hops": self._chase.value(),
                "migration_rounds": self._rounds.value(),
                "dispatches": self._dispatches.value(),
                "quarantined": quarantined,
                "rewalked": self._rewalked.value(),
                "lost": self._lost.value(),
                "h2d_bytes": self._h2d_bytes.value(),
                "d2h_bytes": self._d2h_bytes.value(),
                "h2d_transfers": self._h2d_transfers.value(),
                "d2h_transfers": self._d2h_transfers.value(),
            },
            # Headline resilience count, also at the top level: the
            # acceptance surface is telemetry()["quarantined"].
            "quarantined": quarantined,
            # Self-verification block (integrity/): violations by
            # check, shadow-audit volume, worst conservation residual.
            "integrity": {
                "violations": {
                    s["labels"].get("check", ""): s["value"]
                    for s in self._integ_violations.snapshot()["series"]
                },
                "audited_lanes": self._audited.value(),
                "audit_mismatches": self._audit_mismatch.value(),
                "max_residual": self._max_residual,
            },
            "per_move": self.recorder.tail(tail),
            "memory": device_memory_stats(),
            "metrics": self.registry.snapshot(),
        }
        if times is not None:
            out["times"] = dataclasses.asdict(times)
        return out
