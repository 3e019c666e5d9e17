"""Profiler integration: a trace capture around real facade work must
produce trace artifacts, and annotations/memory stats must not throw."""
from __future__ import annotations

import glob
import os

import numpy as np
import pytest

from pumiumtally_tpu import PumiTally, TallyConfig, build_box
from pumiumtally_tpu.utils.profiling import (
    annotate,
    device_memory_stats,
    profile_trace,
)


@pytest.mark.slow
def test_profile_trace_writes_artifacts(tmp_path):
    logdir = str(tmp_path / "trace")
    mesh = build_box(1.0, 1.0, 1.0, 2, 2, 2)
    t = PumiTally(mesh, 8, TallyConfig(tolerance=1e-6))
    rng = np.random.default_rng(0)
    with profile_trace(logdir):
        with annotate("init"):
            t.initialize_particle_location(
                rng.uniform(0.1, 0.9, (8, 3)).ravel()
            )
        with annotate("move"):
            t.move_to_next_location(
                rng.uniform(0.1, 0.9, (8, 3)),
                np.ones(8, np.int8),
                np.ones(8),
                np.zeros(8, np.int32),
                np.full(8, -1, np.int32),
            )
    found = glob.glob(
        os.path.join(logdir, "**", "*.xplane.pb"), recursive=True
    ) + glob.glob(os.path.join(logdir, "**", "*.trace*"), recursive=True)
    assert found, f"no trace artifacts under {logdir}"


def test_device_memory_stats_shape():
    stats = device_memory_stats()
    for rec in stats.values():
        for v in rec.values():
            assert isinstance(v, int)


def test_call_spans_hold_their_phases_in_order(tmp_path):
    """Each public call's span covers the whole call and holds, in
    order, its stage, dispatch, readback and fold spans (a megastep
    chunk after the stage repeats the last three), reduced the way the
    benchmark reduces a trace (benchmark/trace.py)."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import trace as tracing
    from pumiumtally_tpu.ops.source import SourceParams

    mesh = build_box(1.0, 1.0, 1.0, 2, 2, 2)
    t = PumiTally(mesh, 8, TallyConfig(tolerance=1e-6, megastep=1))
    rng = np.random.default_rng(0)
    pos = rng.uniform(0.1, 0.9, (8, 3))
    move = (pos.copy(), np.ones(8, np.int8), np.ones(8),
            np.zeros(8, np.int32), np.full(8, -1, np.int32))
    # Short flights, no absorption: the lanes live through both moves.
    src = SourceParams(sigma_t={0: 20.0}, absorption={0: 0.0}, seed=2)

    def calls():
        t.initialize_particle_location(pos.ravel().copy())
        t.move_to_next_location(*move)
        t.run_source_moves(2, src, alive=np.ones(8, bool))

    calls()  # compile outside the trace
    logdir = str(tmp_path / "trace")
    with profile_trace(logdir), annotate("bench.window"):
        calls()
    spans = tracing.load(tracing.find(logdir)).spans
    phases = ("stage", "dispatch", "readback", "fold")
    for call, want in (
        ("initialize_particle_location", list(phases)),
        ("move_to_next_location", list(phases)),
        ("run_source_moves", list(phases) + list(phases[1:])),
    ):
        (c0, c1), = [(s, e) for n, s, e in spans
                     if n == "PumiTally." + call]
        inner = [(n, s, e) for n, s, e in spans
                 if c0 <= s and e <= c1 and n != "PumiTally." + call]
        assert [n.split(".", 1)[1] for n, *_ in inner] == want
        ends = [c0] + [x for _, s, e in inner for x in (s, e)] + [c1]
        assert ends == sorted(ends)  # disjoint, in order, inside
