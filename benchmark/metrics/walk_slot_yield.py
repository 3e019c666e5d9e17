"""Scored segments per lane-slot executed over the window's ``move`` /
``megastep`` flight records, in percent: 100 means every lane swept
through a walk-loop iteration scored a segment; the rest is lanes
already done riding the full-width phase or a compaction round. Nothing
where the records carry no lane-slots.
Under ``io_pipeline="overlap"`` a move's record lands during the next
call (its fold is deferred), so the window's records shift by one call;
the cells run the default pipeline, where each call's walk record is its
last."""


def read(run):
    recs = [r for r in run.records if r.get("kind") in ("move", "megastep")]
    slots = sum(r.get("lane_slots", 0) for r in recs)
    if not slots:
        return None
    return 100.0 * sum(r.get("segments", 0) for r in recs) / slots
