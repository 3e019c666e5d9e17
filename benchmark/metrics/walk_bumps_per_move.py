"""Forward bumps per move: the ``bumps`` counter (``obs/walk_stats.py``:
crossings that advanced by the degeneracy bump and not by their own
exit) summed over the window's walk flight records (``initial_search``,
``move``, ``megastep``), over the moves they cover (a megastep record
covers its ``moves``). Nothing where the records carry no such counter.
Under ``io_pipeline="overlap"`` a move's record lands during the next
call (its fold is deferred), so the window's records shift by one call;
the cells run the default pipeline, where each call's walk record is its
last."""


def read(run, field="bumps"):
    recs = [r for r in run.records
            if r.get("kind") in ("initial_search", "move", "megastep")
            and field in r]
    moves = sum(r.get("moves", 1) if r["kind"] == "megastep" else 1
                for r in recs if r["kind"] != "initial_search")
    if not moves:
        return None
    return sum(r[field] for r in recs) / moves
