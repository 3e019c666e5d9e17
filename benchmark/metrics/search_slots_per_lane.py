"""Lane-slots the batch-start search executed per lane it walked: the
``lane_slots`` over the ``lanes`` of the window's ``initial_search``
flight records. Nothing where the records carry neither."""


def read(run):
    recs = [r for r in run.records if r.get("kind") == "initial_search"]
    lanes = sum(r.get("lanes", 0) for r in recs)
    slots = sum(r.get("lane_slots", 0) for r in recs)
    if not lanes or not slots:
        return None
    return slots / lanes
