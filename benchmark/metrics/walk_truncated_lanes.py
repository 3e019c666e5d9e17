"""Walks left unfinished at ``max_crossings``, summed over the window's
walk flight records (``initial_search``, ``move``, ``megastep``; the
on-device ``truncated`` counter, ``obs/walk_stats.py``).
Under ``io_pipeline="overlap"`` a move's record lands during the next
call (its fold is deferred), so the window's records shift by one call;
the cells run the default pipeline, where each call's walk record is its
last."""


def read(run):
    recs = [r for r in run.records
            if r.get("kind") in ("initial_search", "move", "megastep")
            and "truncated" in r]
    if not recs:
        return None
    return sum(r["truncated"] for r in recs)
