"""Device time of the walk's programs inside the window's move calls
(``programs.WALK``) per lane-slot they executed: the ``lane_slots`` of
the window's ``move`` / ``megastep`` flight records (one lane swept
through one walk-loop iteration, ``obs/walk_stats.py``), in ns. Nothing
where the records carry no lane-slots.
Under ``io_pipeline="overlap"`` a move's record lands during the next
call (its fold is deferred), so the window's records shift by one call;
the cells run the default pipeline, where each call's walk record is its
last."""
from benchmark.programs import walk_seconds


def read(run):
    slots = sum(r.get("lane_slots", 0) for r in run.records
                if r.get("kind") in ("move", "megastep"))
    t = walk_seconds(run)
    if not slots or t <= 0:
        return None
    return t / slots * 1e9
