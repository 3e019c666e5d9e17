"""A CPU rehearsal of the 70-group assembly's shape (``assembly-1m-g70``):
a 3 x 3 lattice with one guide tube, on cubes as thin as the assembly's
(15:1), 70 groups, under one source and one event mix, each run through
``run.run_cell`` and judged against the float64 reference. The spec is
built here, as ``conftest.py`` builds its own, so no benchmark file
changes."""
import json
import os

import pytest

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = ["tiny-assembly.source", "tiny-assembly.event"]


@pytest.fixture(scope="module")
def assembly_bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-assembly",
        "file": "benchmark/tests/configs/tiny-assembly.json"})
    spec["workloads"] += [
        {"name": "tiny-assembly.source", "config": "tiny-assembly",
         "traffic": "source-tiny", "chips": 1},
        {"name": "tiny-assembly.event", "config": "tiny-assembly",
         "traffic": "event-tiny", "chips": 1},
    ]
    return run.Bench(spec, dirs=(HERE, run.HERE))


@pytest.mark.parametrize("workload", CELLS)
def test_tiny_assembly_correct(assembly_bench, workload):
    res = run.run_cell(assembly_bench, workload, 2**31 + 26, 0.5, False,
                       need_chip=False)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"segments_per_s", "setup_s"}


@pytest.mark.parametrize("workload", CELLS)
def test_tiny_assembly_traced_counts_recovery(assembly_bench, workload):
    """The traced run reads the walk's recovery counters and truncates
    no lane."""
    res = run.run_cell(assembly_bench, workload, 26, 0.5, True,
                       need_chip=False)
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    assert {"walk_bumps_per_move", "walk_chase_hops_per_move"} <= set(m)
    assert m["walk_truncated_lanes"]["value"] == 0
