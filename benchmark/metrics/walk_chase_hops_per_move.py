"""Relocation-chase hops per move: the ``chase_hops`` counter
(``obs/walk_stats.py``) summed over the window's walk flight records
(``initial_search``, ``move``, ``megastep``), over the moves they cover,
read as ``walk_bumps_per_move`` reads ``bumps``."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "walk_bumps_per_move",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "walk_bumps_per_move.py"))
_bumps = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_bumps)


def read(run):
    return _bumps.read(run, field="chase_hops")
