"""Share of the lanes the batch-start search walked that started from a
grid seed (``ops/seed_grid.py``): the ``grid_seeded`` over the ``lanes``
of the window's ``initial_search`` flight records, in percent. Nothing
where the records carry no ``grid_seeded`` (a program without the seed
grid) or no lanes."""


def read(run):
    recs = [r for r in run.records
            if r.get("kind") == "initial_search" and "grid_seeded" in r]
    lanes = sum(r.get("lanes", 0) for r in recs)
    if not lanes:
        return None
    return 100.0 * sum(r["grid_seeded"] for r in recs) / lanes
