"""Telemetry subsystem (obs/): registry semantics, flight-recorder JSONL
golden schema, and the on-device walk stats vector against a
hand-checked small-mesh oracle plus the independent intersection-point
recorder."""
from __future__ import annotations

import json

import numpy as np
import pytest

import jax.numpy as jnp

from pumiumtally_tpu import PumiTally, TallyConfig, build_box, make_flux
from pumiumtally_tpu.obs import (
    IDX,
    WALK_STATS_FIELDS,
    WALK_STATS_LEN,
    FlightRecorder,
    MetricsRegistry,
    reduce_chip_stats,
    stats_to_dict,
)
from pumiumtally_tpu.ops.walk import trace


# --------------------------------------------------------------------- #
# Registry
# --------------------------------------------------------------------- #
def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("hits", "help text")
    c.inc()
    c.inc(4)
    assert c.value() == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    # Same name returns the same family; values persist.
    assert reg.counter("hits") is c


def test_labeled_series_are_independent():
    reg = MetricsRegistry()
    c = reg.counter("reqs")
    c.inc(2, device="tpu:0")
    c.inc(3, device="tpu:1")
    c.inc(7)
    assert c.value(device="tpu:0") == 2
    assert c.value(device="tpu:1") == 3
    assert c.value() == 7
    snap = reg.snapshot()["reqs"]
    assert snap["type"] == "counter"
    assert len(snap["series"]) == 3


def test_gauge_set_and_inc():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(10)
    g.inc(5)
    g.dec(2)
    assert g.value() == 13


def test_histogram_cumulative_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    s = h.value()
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(56.05)
    # Cumulative: <=0.1 -> 1, <=1.0 -> 3, <=10.0 -> 4 (the 50.0 only in +Inf).
    assert s["buckets"] == [1, 3, 4]


def test_type_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("seg_total", "segments").inc(9, kind="move")
    reg.gauge("occ").set(0.75)
    reg.histogram("lat", buckets=(1.0,)).observe(0.5)
    text = reg.render_prometheus()
    assert "# TYPE seg_total counter" in text
    assert 'seg_total{kind="move"} 9' in text
    assert "occ 0.75" in text
    assert 'lat_bucket{le="1.0"} 1' in text
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_sum 0.5" in text
    assert "lat_count 1" in text


# --------------------------------------------------------------------- #
# Flight recorder + JSONL golden schema
# --------------------------------------------------------------------- #
def test_recorder_ring_and_seq():
    rec = FlightRecorder(capacity=3)
    for i in range(5):
        rec.record("move", move=i)
    assert len(rec) == 3
    assert rec.total_recorded == 5
    assert [r["move"] for r in rec.records()] == [2, 3, 4]
    assert [r["seq"] for r in rec.tail(2)] == [3, 4]


def test_recorder_jsonl_sink_schema(tmp_path, monkeypatch):
    """Golden schema of the JSONL record: the log-formatter envelope
    (ts/level/msg) plus the recorder fields, one JSON object per line."""
    path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("PUMI_TPU_METRICS", f"jsonl:{path}")
    rec = FlightRecorder()
    rec.record("move", move=1, segments=42, crossings=7)
    rec.record("memory", phase="vtk_write", devices={})
    lines = path.read_text().strip().split("\n")
    assert len(lines) == 2
    first = json.loads(lines[0])
    assert set(first) == {
        "ts", "level", "msg", "seq", "kind", "move", "segments",
        "crossings",
    }
    assert first["level"] == "metric"
    assert first["msg"] == "move" and first["kind"] == "move"
    assert first["segments"] == 42
    second = json.loads(lines[1])
    assert second["kind"] == "memory" and second["phase"] == "vtk_write"


def test_no_sink_is_silent(tmp_path, monkeypatch):
    monkeypatch.delenv("PUMI_TPU_METRICS", raising=False)
    FlightRecorder().record("move", move=0)  # must not raise or write


def test_unwritable_sink_never_crashes(monkeypatch, capsys):
    """Metric emission is best-effort: a typo'd PUMI_TPU_METRICS path
    must warn (once) and keep the run alive, not raise on every move."""
    monkeypatch.setenv(
        "PUMI_TPU_METRICS", "jsonl:/nonexistent_dir_pumi/m.jsonl"
    )
    rec = FlightRecorder()
    rec.record("move", move=0)
    rec.record("move", move=1)
    err = capsys.readouterr().err
    assert err.count("unwritable") == 1  # warned exactly once
    assert rec.total_recorded == 2  # ring still records


# --------------------------------------------------------------------- #
# On-device walk stats: hand-checked small-mesh oracle
# --------------------------------------------------------------------- #
N_GROUPS = 2


@pytest.fixture(scope="module")
def small_mesh():
    return build_box(1.0, 1.0, 1.0, 2, 2, 2, dtype=jnp.float64)


def _trace(mesh, origin, dest, elem, in_flight=None, **kw):
    n = origin.shape[0]
    if in_flight is None:
        in_flight = jnp.ones(n, bool)
    kw.setdefault("initial", False)
    kw.setdefault("max_crossings", mesh.ntet + 64)
    kw.setdefault("tolerance", 1e-8)
    kw.setdefault("n_groups", N_GROUPS)
    return trace(
        mesh,
        jnp.asarray(origin, jnp.float64),
        jnp.asarray(dest, jnp.float64),
        jnp.asarray(elem, jnp.int32),
        in_flight,
        jnp.ones(n, jnp.float64),
        jnp.zeros(n, jnp.int32),
        jnp.full(n, -1, jnp.int32),
        make_flux(mesh.ntet, N_GROUPS, jnp.float64),
        **kw,
    )


def test_stats_vector_schema_length(small_mesh):
    cen = np.asarray(small_mesh.centroids())
    r = _trace(small_mesh, cen[:1], cen[:1] + 1e-3, np.array([0]))
    assert WALK_STATS_FIELDS == (
        "crossings", "max_crossings", "chase_hops", "truncated",
        "occ_active", "occ_slots", "segments", "loop_iters", "lane_slots",
        "ladder_rounds", "bumps", "grid_seeded",
    )
    assert r.stats.shape == (WALK_STATS_LEN,)
    assert tuple(IDX[f] for f in WALK_STATS_FIELDS) == tuple(
        range(WALK_STATS_LEN)
    )


def test_stats_zero_crossing_walk(small_mesh):
    """Hand-checked: a destination inside the origin element crosses no
    boundary and scores exactly one segment; a parked lane contributes
    nothing at all."""
    cen = np.asarray(small_mesh.centroids())
    origin = cen[[0, 0]]
    dest = origin + np.array([[1e-4, 0, 0], [0.3, 0.3, 0.3]])
    r = _trace(
        small_mesh, origin, dest, np.zeros(2, np.int32),
        in_flight=jnp.asarray([True, False]),
    )
    d = stats_to_dict(r.stats)
    assert d["crossings"] == 0  # lane 0 stays in its element
    assert d["max_crossings"] == 0
    assert d["segments"] == 1  # destination-reach segment of lane 0 only
    assert d["truncated"] == 0  # the parked lane is done, not truncated
    assert d["chase_hops"] == 0
    assert d["occupancy"] is None  # no compaction configured


def test_stats_match_recorded_crossings(small_mesh):
    """The stats counters must agree with the independently recorded
    intersection points (record_xpoints) and result scalars, lane by
    lane aggregated: total/max crossings, segments, loop iterations."""
    rng = np.random.default_rng(11)
    n = 32
    elem = rng.integers(0, small_mesh.ntet, n).astype(np.int32)
    origin = np.asarray(small_mesh.centroids())[elem]
    dest = np.clip(origin + rng.normal(0, 0.4, (n, 3)), 0.02, 0.98)
    r = _trace(small_mesh, origin, dest, elem, record_xpoints=64)
    d = stats_to_dict(r.stats)
    counts = np.asarray(r.n_xpoints)
    assert d["crossings"] == counts.sum()
    assert d["max_crossings"] == counts.max()
    assert d["segments"] == int(r.n_segments)
    assert d["loop_iters"] == int(r.n_crossings)
    assert d["truncated"] == int(np.sum(~np.asarray(r.done))) == 0
    assert d["chase_hops"] == 0  # clean box mesh: no recovery expected


def test_stats_truncation_counter(small_mesh):
    """max_crossings=1 truncates every walk that needed more than one
    crossing; the on-device counter must equal the host scan of done."""
    rng = np.random.default_rng(5)
    n = 16
    elem = rng.integers(0, small_mesh.ntet, n).astype(np.int32)
    origin = np.asarray(small_mesh.centroids())[elem]
    dest = np.clip(origin + rng.normal(0, 0.5, (n, 3)), 0.02, 0.98)
    r = _trace(small_mesh, origin, dest, elem, max_crossings=1)
    d = stats_to_dict(r.stats)
    n_undone = int(np.sum(~np.asarray(r.done)))
    assert n_undone > 0  # the workload must actually truncate
    assert d["truncated"] == n_undone


def test_stats_compaction_occupancy_and_flux_parity(small_mesh):
    """Compaction rounds fill the occupancy accumulator; the scored flux
    (up to fp summation order — schedules group the scatter adds
    differently, ~1e-15 in f64) and every crossing counter match the
    flat loop."""
    rng = np.random.default_rng(7)
    n = 64
    elem = rng.integers(0, small_mesh.ntet, n).astype(np.int32)
    origin = np.asarray(small_mesh.centroids())[elem]
    dest = np.clip(origin + rng.normal(0, 0.4, (n, 3)), 0.02, 0.98)
    r_flat = _trace(small_mesh, origin, dest, elem)
    r_cmp = _trace(
        small_mesh, origin, dest, elem, compact_stages=((1, 16),)
    )
    d_flat = stats_to_dict(r_flat.stats)
    d_cmp = stats_to_dict(r_cmp.stats)
    np.testing.assert_allclose(
        np.asarray(r_cmp.flux), np.asarray(r_flat.flux),
        rtol=1e-13, atol=1e-15,
    )
    for f in ("crossings", "max_crossings", "segments", "truncated"):
        assert d_cmp[f] == d_flat[f]
    assert d_flat["occ_slots"] == 0
    assert d_cmp["occ_slots"] > 0
    assert 0 < d_cmp["occupancy"] <= 1


def test_stats_knob_off(small_mesh):
    cen = np.asarray(small_mesh.centroids())
    r = _trace(small_mesh, cen[:4], cen[:4] + 0.1, np.zeros(4, np.int32),
               stats=False)
    assert r.stats is None


def test_lane_slots_carrier_holds_a_megastep():
    """The lane-slot word holds 2^20 lanes x 1,000 iterations x 12 moves
    (past int32) without wrapping, summed through the megastep merge."""
    from pumiumtally_tpu.ops.walk import add_walk_stats, lane_slots_word

    per_move = jnp.zeros(WALK_STATS_LEN, jnp.int32).at[
        IDX["lane_slots"]].set(lane_slots_word(jnp.float32(2**20 * 1000),
                                               jnp.int32))
    acc = jnp.zeros(WALK_STATS_LEN, jnp.int32)
    for _ in range(12):
        acc = add_walk_stats(acc, per_move)
    assert stats_to_dict(acc)["lane_slots"] == 12 * 2**20 * 1000
    m = np.stack([np.asarray(acc), np.asarray(per_move)]).astype(np.int64)
    assert reduce_chip_stats(m)["lane_slots"] == 13 * 2**20 * 1000


def test_reduce_chip_stats():
    m = np.zeros((2, WALK_STATS_LEN), np.int64)
    m[0, IDX["crossings"]] = 5
    m[1, IDX["crossings"]] = 7
    m[0, IDX["max_crossings"]] = 4
    m[1, IDX["max_crossings"]] = 9
    m[:, IDX["occ_active"]] = 1
    m[:, IDX["occ_slots"]] = 2
    d = reduce_chip_stats(m)
    assert d["crossings"] == 12
    assert d["max_crossings"] == 9
    assert d["occupancy"] == 0.5


# --------------------------------------------------------------------- #
# Facade telemetry
# --------------------------------------------------------------------- #
def _drive_tally(n=16, moves=2, **cfg_kw):
    mesh = build_box(1.0, 1.0, 1.0, 2, 2, 2, dtype=jnp.float64)
    cfg = TallyConfig(
        dtype=jnp.float64, n_groups=N_GROUPS, tolerance=1e-8, **cfg_kw
    )
    t = PumiTally(mesh, n, cfg)
    rng = np.random.default_rng(0)
    pos = rng.uniform(0.1, 0.9, (n, 3))
    t.initialize_particle_location(pos.ravel().copy())
    for _ in range(moves):
        dest = np.clip(pos + rng.normal(0, 0.2, (n, 3)), 0.02, 0.98)
        buf = dest.ravel().copy()
        t.move_to_next_location(
            buf, np.ones(n, np.int8), np.ones(n),
            np.zeros(n, np.int32), np.full(n, -1, np.int32),
        )
        pos = buf.reshape(n, 3)
    return t


def test_pumitally_telemetry_snapshot():
    t = _drive_tally(moves=3)
    snap = t.telemetry()
    assert snap["facade"] == "PumiTally"
    assert snap["totals"]["moves"] == 3
    assert snap["totals"]["segments"] == t.total_segments > 0
    assert snap["totals"]["truncated"] == 0
    kinds = [r["kind"] for r in snap["per_move"]]
    assert kinds.count("move") == 3
    assert "initial_search" in kinds
    assert "memory" in kinds  # construction phase boundary sample
    assert snap["times"]["n_moves"] == 3
    move_recs = [r for r in snap["per_move"] if r["kind"] == "move"]
    for r in move_recs:
        assert {"move", "seconds", "crossings", "segments", "truncated",
                "occupancy"} <= set(r)
    # Registry view agrees with the counters.
    m = snap["metrics"]
    assert m["pumi_moves_total"]["series"][0]["value"] == 3
    # Prometheus exposition renders without error and carries the totals.
    text = t.metrics.render_prometheus()
    assert "pumi_segments_total" in text


def test_walk_records_count_lanes_and_dispatches():
    """Every walk record names the lanes it walked and the compiled
    launches it made: one per move and per initial search, summed into
    pumi_dispatches_total; the lane-slots cover every loop iteration."""
    t = _drive_tally(n=16, moves=3)
    snap = t.telemetry()
    walks = [r for r in snap["per_move"]
             if r["kind"] in ("initial_search", "move")]
    assert [r["dispatches"] for r in walks] == [1, 1, 1, 1]
    assert [r["lanes"] for r in walks] == [16] * 4
    assert all(r["lane_slots"] == 16 * r["loop_iters"] > 0 for r in walks)
    assert snap["totals"]["dispatches"] == 4
    assert snap["metrics"]["pumi_dispatches_total"]["series"][0][
        "value"] == 4


def test_pumitally_telemetry_jsonl_stream(tmp_path, monkeypatch):
    path = tmp_path / "run.jsonl"
    monkeypatch.setenv("PUMI_TPU_METRICS", f"jsonl:{path}")
    t = _drive_tally(moves=2)
    recs = [json.loads(ln) for ln in path.read_text().strip().split("\n")]
    moves = [r for r in recs if r["kind"] == "move"]
    assert len(moves) == 2
    assert sum(r["segments"] for r in moves) == t.total_segments


def test_pumitally_walk_stats_off_falls_back():
    t = _drive_tally(moves=2, walk_stats=False)
    snap = t.telemetry()
    # No stats vector: segment totals still flow (result scalar), the
    # stats-derived counters stay zero.
    assert t.total_segments > 0
    assert snap["totals"]["moves"] == 2
    assert snap["totals"]["crossings"] == 0


def test_tally_times_per_move_report(capsys):
    from pumiumtally_tpu.utils.timing import TallyTimes

    tt = TallyTimes(total_time_to_tally=3.0, n_moves=4)
    tt.print_times()
    err = capsys.readouterr().err
    assert "tally_per_move" in err
    assert "0.75" in err
    assert "n_moves=4" in err
