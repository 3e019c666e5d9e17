"""Schema of the on-device walk stats vector.

The reference library's only per-move observability is the host-side
"Not all particles are found" printf (pumipic_particle_data_structure
.cpp:765-768) plus four coarse [TIME] phase timers — it cannot say WHY a
run is slow. Here every fused trace folds a small vector of counters
into the jitted program itself (ops/walk.py, ops/walk_partitioned.py):
one scalar-vector readback per move carries everything the flight
recorder needs, replacing the per-move host scan of the ``done`` array
the facade used to do, with zero extra device dispatches.

The vector layout is the single source of truth for both walk kernels;
``tests/test_obs.py`` pins the field order against the kernels so a
drift breaks loudly.
"""
from __future__ import annotations

import numpy as np

WALK_STATS_FIELDS = (
    # Real element-boundary crossings summed over all lanes (relocation-
    # chase hops are bookkeeping and excluded, matching the segment
    # count's convention in ops/walk.py).
    "crossings",
    # Max real crossings by any single lane. For the partitioned walk a
    # lane is a resident SLOT, so this is a per-chip per-slot maximum
    # (counters do not migrate with particles across cuts).
    "max_crossings",
    # Stuck-escape activations: relocation-chase hops executed
    # (ops/walk.py "Degeneracy robustness"). Nonzero means grazing-ray
    # recovery is active — on a clean mesh this should be 0.
    "chase_hops",
    # In-flight walks not finished when the trace returned (truncated at
    # max_crossings / the migration round bound) — the per-particle
    # analog of the reference's cpp:765-768 error, as one scalar.
    "truncated",
    # Straggler-compaction occupancy: active lanes placed into subset
    # slots, and subset slots swept, summed over every compaction round.
    # occ_active/occ_slots is the mean post-compaction occupancy; both 0
    # when compaction never ran.
    "occ_active",
    "occ_slots",
    # Scored particle-segments (duplicates TraceResult.n_segments so ONE
    # vector fetch serves the whole flight-recorder record).
    "segments",
    # While-loop body iterations executed (TraceResult.n_crossings; for
    # the partitioned walk: phase-1 iters + all follow-up round iters).
    "loop_iters",
    # Lane-slots executed: one lane swept through one body iteration —
    # n x the full-width phase's iterations plus, for every compaction
    # round, its width S x that round's iterations (per chip for the
    # partitioned walk; the Pallas kernel sweeps its padded width every
    # iteration). Computed after each loop from the scalar iteration
    # counts, never per lane. CARRIER: a float32 whose bits ride the
    # integer vector (``LANE_SLOTS``), since the count passes int32
    # within one megastep (2^20 lanes x 1,000 iterations x 12 moves is
    # about 1.3e10); exact below 2^24, and above it to one part in 2^24.
    "lane_slots",
    # Compaction rounds that ran: for the fit ladder (ops/walk.py
    # "Straggler compaction"), the narrower stages the walk entered and
    # advanced at least one chunk — its stage exits taken; for an
    # explicit schedule, its rounds. Summed over a megastep's fused
    # moves (merge_megastep_stats). 0 in the flat loop, the Pallas
    # kernel and the partitioned walk.
    "ladder_rounds",
    # Degeneracy recovery (ops/walk.py "Degeneracy robustness"):
    # crossings (relocation-chase hops included) that advanced by the
    # forward bump and not by their own exit, landings of a bump on the
    # destination included (``forward_bump``). 0 with robust=False.
    "bumps",
    # Batch-start search (ops/seed_grid.py): flying lanes whose walk
    # started from their destination cell's grid seed instead of where
    # the lane stood. 0 on moves, megasteps and the partitioned walk.
    "grid_seeded",
)

WALK_STATS_LEN = len(WALK_STATS_FIELDS)

IDX = {name: i for i, name in enumerate(WALK_STATS_FIELDS)}

# The one field carried as float32 bits (see "lane_slots" above).
LANE_SLOTS = IDX["lane_slots"]


def lane_slots_value(word) -> int:
    """Host decode of the ``lane_slots`` word: the low 32 bits of the
    (possibly sign-widened) integer are a float32."""
    return int(np.asarray(word, np.int64).astype(np.int32).view(np.float32))


def _derived(d: dict) -> dict:
    d["occupancy"] = (
        round(d["occ_active"] / d["occ_slots"], 4) if d["occ_slots"] else None
    )
    return d


def stats_to_dict(vec) -> dict:
    """Host-side view of one stats vector: named integer fields plus the
    derived mean compaction ``occupancy`` (None when compaction never
    ran)."""
    v = np.asarray(vec)
    if v.shape != (WALK_STATS_LEN,):
        raise ValueError(
            f"expected a [{WALK_STATS_LEN}] stats vector, got {v.shape}"
        )
    d = {f: int(v[i]) for i, f in enumerate(WALK_STATS_FIELDS)}
    d["lane_slots"] = lane_slots_value(v[LANE_SLOTS])
    return _derived(d)


def reduce_chip_stats(mat) -> dict:
    """Aggregate a per-chip [n_parts, LEN] stats matrix into one run-level
    dict: sums everywhere except ``max_crossings`` (max over chips)."""
    m = np.asarray(mat)
    if m.ndim != 2 or m.shape[1] != WALK_STATS_LEN:
        raise ValueError(
            f"expected [n_parts, {WALK_STATS_LEN}] chip stats, got {m.shape}"
        )
    d = {f: int(m[:, i].sum()) for i, f in enumerate(WALK_STATS_FIELDS)}
    d["max_crossings"] = int(m[:, IDX["max_crossings"]].max(initial=0))
    d["lane_slots"] = sum(lane_slots_value(w) for w in m[:, LANE_SLOTS])
    return _derived(d)
