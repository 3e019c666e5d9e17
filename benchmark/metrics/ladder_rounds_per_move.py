"""Compaction rounds the walk ran per move: the ``ladder_rounds`` of the
window's ``move`` / ``megastep`` flight records (``obs/walk_stats.py``:
for the fit ladder, the narrower stages a move's walk entered) over the
moves they cover (a megastep record covers its ``moves``). Nothing where
the records carry no such counter.
Under ``io_pipeline="overlap"`` a move's record lands during the next
call (its fold is deferred), so the window's records shift by one call;
the cells run the default pipeline, where each call's walk record is its
last."""


def read(run):
    recs = [r for r in run.records
            if r.get("kind") in ("move", "megastep") and "ladder_rounds" in r]
    moves = sum(r.get("moves", 1) if r["kind"] == "megastep" else 1
                for r in recs)
    if not moves:
        return None
    return sum(r["ladder_rounds"] for r in recs) / moves
