"""The readers of the program's own counters and spans, pinned on a
trace recorded on a TPU v5e: one `pincell.source` batch start and move
(1,048,576 lanes) under the benchmark's span names,
recorded by `scripts/trace_scopes.py --record` and cut by its `--cut`
to the program line and the host's spans whole and the op line's first
280 ms of each program (each op with its named-scope path). The two
calls' walk flight records are copied from that run's `records.json`."""
import os

import pytest

from benchmark import run, trace

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "trace_tpu_source.xplane.pb")

RECORDS = [
    {"kind": "initial_search", "lanes": 1048576, "lane_slots": 379584512,
     "segments": 0, "truncated": 0, "loop_iters": 2672},
    {"kind": "megastep", "lanes": 1048576, "lane_slots": 55574528,
     "segments": 13350842, "truncated": 0, "loop_iters": 200},
]


@pytest.fixture(scope="module")
def recorded():
    return run.Run(trace=trace.load(TRACE), counts={"init": 1, "move": 1},
                   records=RECORDS, segments=13350842, lane_moves=1048576,
                   float_bytes=4, device_kind="TPU v5 lite")


def _spans(names):
    """(start, end) of the named host spans, from the raw events."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(TRACE).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name in names]
    return out


def test_recorded_source_trace_layout(recorded):
    tr = recorded.trace
    assert tr.module_names() == ["jit_convert_element_type",
                                 "jit_megastep_impl",
                                 "jit_trace_packed_impl"]
    assert len(tr.devices[0]) == 2989
    # Each call span holds its four phases, in order.
    calls = {n: (s, e) for n, s, e in tr.spans if n in (
        "PumiTally.initialize_particle_location",
        "PumiTally.run_source_moves")}
    for c0, c1 in calls.values():
        inner = [n for n, s, e in tr.spans
                 if c0 <= s and e <= c1 and (s, e) != (c0, c1)]
        assert inner == ["PumiTally.stage", "PumiTally.dispatch",
                         "PumiTally.readback", "PumiTally.fold"]


@pytest.mark.parametrize("name,value", [
    # jit_megastep_impl's 3,232,936,792 ns in the move over its slots.
    ("walk_ns_per_slot", 3232936792 / 55574528),
    ("walk_slot_yield", 100 * 13350842 / 55574528),
    ("search_slots_per_lane", 362.0),
    ("walk_truncated_lanes", 0),
])
def test_counter_readers_on_tpu_trace(bench, recorded, name, value):
    assert bench.reader(name)(recorded) == pytest.approx(value)


def test_host_stage_reader_on_tpu_trace(bench, recorded):
    spans = _spans({"PumiTally.stage", "PumiTally.fold"})
    assert len(spans) == 4
    ms = sum(e - s for _, s, e in spans) * 1e-6 / 2
    assert ms == pytest.approx(15.6524945)
    assert bench.reader("host_stage_ms_per_call")(recorded) == \
        pytest.approx(ms)


def test_new_readers_read_nothing_without_the_counters(bench, recorded):
    """A program without the counters and spans (the program this trace
    was recorded from before they existed): the readers return nothing
    rather than raise."""
    bare = run.Run(trace=trace.load(os.path.join(
        HERE, "trace_tpu_event.xplane.pb")), counts={"init": 2, "move": 9},
        records=[{"kind": "move", "segments": 5, "truncated": 0}],
        segments=5, lane_moves=9, float_bytes=4,
        device_kind="TPU v5 lite")
    for name in ("walk_ns_per_slot", "walk_slot_yield",
                 "search_slots_per_lane", "host_stage_ms_per_call"):
        assert bench.reader(name)(bare) is None
