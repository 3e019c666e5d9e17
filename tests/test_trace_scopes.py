"""scripts/trace_scopes.py: the per-scope split of a trace's device time
(innermost named scope of an op's path, self time of nested ops, the
op-name join with optimized-HLO dumps)."""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "scripts")]

import trace_scopes  # noqa: E402


@pytest.mark.parametrize("path,scope", [
    ("jit(megastep_impl)/while/body/closed_call/while/body/walk.gather/"
     "gather", "walk.gather"),
    ("jit(trace_packed_impl)/walk.compact/while/body/walk.scatter/"
     "scatter-add", "walk.scatter"),
    ("jit(megastep_impl)/while/body/source.physics/select", "source.physics"),
    ("jit(megastep_impl)/while/body/add", None),
])
def test_innermost_scope(path, scope):
    assert trace_scopes.scope_of(path) == scope


def test_self_time_subtracts_direct_children():
    # A loop (0-100) holding two ops and a nested loop (40-90) holding
    # one; a separate op after it.
    ops = [(0, 100, "%while.1"), (5, 15, "%fusion.2"), (40, 90, "%while.3"),
           (50, 70, "%fusion.4"), (120, 130, "%fusion.5"),
           (20, 30, "%gather.6")]
    own = trace_scopes.self_times(ops)
    assert own.tolist() == [100 - 10 - 50 - 10, 10, 50 - 20, 20, 10, 10]
    assert own.sum() == 110  # the union of the intervals


def test_hlo_op_names_from_dump(tmp_path):
    (tmp_path / "module_0007.jit_megastep_impl.tpu_after_optimizations.txt"
     ).write_text(
        "HloModule jit_megastep_impl, is_scheduled=true\n"
        "%fused_computation.1 (p: f32[8]) -> f32[8] {\n"
        "  ROOT %n = f32[8]{0} negate(f32[8]{0} %p), metadata={op_type=\"neg\" "
        "op_name=\"jit(megastep_impl)/walk.geometry/neg\"}\n"
        "}\n"
        "ENTRY %main {\n"
        "  %fusion.775 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, "
        "calls=%fused_computation.1, metadata={op_type=\"gather\" "
        "op_name=\"jit(megastep_impl)/while/body/walk.gather/gather\" "
        "source_file=\"walk.py\" source_line=810}\n"
        "  ROOT %copy.3 = f32[8]{0} copy(f32[8]{0} %fusion.775)\n"
        "}\n")
    names = trace_scopes.hlo_op_names(str(tmp_path))
    table = names["jit_megastep_impl"]
    assert trace_scopes.scope_of(table["%fusion.775"]) == "walk.gather"
    assert trace_scopes.scope_of(table["%n"]) == "walk.geometry"
    assert "%copy.3" not in table  # no metadata, no scope
    assert trace_scopes.op_key("%fusion.775 = f32[8]{0} fusion(...)") == \
        "%fusion.775"
    assert trace_scopes.short_op(
        "%fusion.775 = f32[1048576,3]{1,0:T(8,128)} fusion(...)") == \
        "%fusion.775 = f32[1048576,3]"


def test_split_of_a_chip_trace():
    """The committed TPU v5e trace of a `pincell.source` batch start and
    move (benchmark/tests/trace_tpu_source.xplane.pb: each op carries its
    scope path as a stat): every second of device time lands in one
    scope, and the self times add up to the union the benchmark's own
    reduction measures."""
    from benchmark import trace

    path = os.path.join(ROOT, "benchmark", "tests",
                        "trace_tpu_source.xplane.pb")
    sp = trace_scopes.split(path)
    search, mega = sp["jit_trace_packed_impl"], sp["jit_megastep_impl"]
    assert search["sources"]["hlo"] == 0 < search["sources"]["stats"]
    assert search["scopes"] == pytest.approx({
        "walk.geometry": 0.160624287, "walk.gather": 0.112306365,
        "unscoped": 0.004272907})
    assert mega["scopes"] == pytest.approx({
        "walk.geometry": 0.197837763, "walk.gather": 0.052189659,
        "source.sample": 0.008068988, "unscoped": 0.00166612,
        "walk.scatter": 0.000287034})
    total = sum(r["device_s"] for r in sp.values())
    assert total == pytest.approx(sum(
        sum(r["scopes"].values()) for r in sp.values()))
    tr = trace.load(path)
    busy = sum(float((b[:, 1] - b[:, 0]).sum()) for b in tr.busy) * 1e-9
    assert total == pytest.approx(busy)
