"""Straggler compaction must be a pure scheduling change: identical results
to the flat while_loop for every output, including heterogeneous ray
lengths, parked particles, boundary clips, and multi-round tails."""
import jax.numpy as jnp
import numpy as np
import pytest

from pumiumtally_tpu import build_box, make_flux, trace
from pumiumtally_tpu.ops.geometry import locate_points


@pytest.mark.parametrize(
    "compact_size",
    [pytest.param(8, marks=pytest.mark.slow), 32,
     pytest.param(None, marks=pytest.mark.slow)],
)
def test_compaction_matches_flat(compact_size):
    mesh = build_box(1, 1, 1, 4, 4, 4, dtype=jnp.float64)
    n = 128
    rng = np.random.default_rng(5)
    origin = rng.uniform(0.05, 0.95, (n, 3))
    # Mix of short hops, long diagonals (straggler tail), and out-of-domain.
    dest = origin + rng.normal(scale=0.05, size=(n, 3))
    dest[: n // 4] = rng.uniform(-0.5, 1.5, (n // 4, 3))
    in_flight = (rng.random(n) > 0.2)
    weight = rng.uniform(0.1, 3.0, n)
    group = rng.integers(0, 2, n)
    elem = np.asarray(locate_points(mesh, jnp.asarray(origin), 1e-12))
    assert (elem >= 0).all()

    args = dict(
        initial=False,
        max_crossings=mesh.ntet + 64,
        tolerance=1e-12,
    )
    common = (
        mesh,
        jnp.asarray(origin),
        jnp.asarray(dest),
        jnp.asarray(elem, jnp.int32),
        jnp.asarray(in_flight),
        jnp.asarray(weight),
        jnp.asarray(group, jnp.int32),
        jnp.full(n, -1, jnp.int32),
    )
    flat = trace(*common, make_flux(mesh.ntet, 2, jnp.float64), **args)
    compact = trace(
        *common,
        make_flux(mesh.ntet, 2, jnp.float64),
        compact_after=2,
        compact_size=compact_size,
        **args,
    )

    np.testing.assert_allclose(
        np.asarray(compact.position), np.asarray(flat.position), atol=1e-14
    )
    np.testing.assert_array_equal(
        np.asarray(compact.elem), np.asarray(flat.elem)
    )
    np.testing.assert_array_equal(
        np.asarray(compact.material_id), np.asarray(flat.material_id)
    )
    np.testing.assert_allclose(
        np.asarray(compact.flux), np.asarray(flat.flux), atol=1e-12
    )
    assert int(compact.n_segments) == int(flat.n_segments)
    assert bool(np.asarray(compact.done).all())


def test_compaction_with_truncation_reports_not_done():
    mesh = build_box(20.0, 1.0, 1.0, 20, 1, 1, dtype=jnp.float64)
    n = 4
    origin = np.tile([0.05, 0.4, 0.5], (n, 1))
    dest = np.tile([19.95, 0.4, 0.5], (n, 1))
    elem = np.asarray(locate_points(mesh, jnp.asarray(origin), 1e-12))
    r = trace(
        mesh,
        jnp.asarray(origin),
        jnp.asarray(dest),
        jnp.asarray(elem, jnp.int32),
        jnp.ones(n, bool),
        jnp.ones(n),
        jnp.zeros(n, jnp.int32),
        jnp.full(n, -1, jnp.int32),
        make_flux(mesh.ntet, 2, jnp.float64),
        initial=False,
        max_crossings=10,  # far below the ~100 crossings needed
        compact_after=2,
        compact_size=2,
    )
    assert not bool(np.asarray(r.done).any())


@pytest.mark.parametrize(
    "sched",
    [
        dict(compact_after=2, compact_size=16),
        pytest.param(
            dict(compact_stages=((2, 32), (6, 16), (10, 8))),
            marks=pytest.mark.slow,
        ),
    ],
)
def test_record_xpoints_composes_with_compaction(sched):
    """Intersection-point recording must survive the straggler
    gather/scatter-back: the compacted walk records exactly the flat
    walk's points and counts (the xp/kx lanes ride compaction rounds
    like any other per-particle state), so the production config
    (compact_stages="auto") can record too — reference tracer's
    getIntersectionPoints() is unconditional (test:403-479)."""
    mesh = build_box(1, 1, 1, 4, 4, 4, dtype=jnp.float64)
    n = 128
    rng = np.random.default_rng(11)
    origin = rng.uniform(0.05, 0.95, (n, 3))
    dest = origin + rng.normal(scale=0.05, size=(n, 3))
    dest[: n // 4] = rng.uniform(-0.5, 1.5, (n // 4, 3))
    in_flight = rng.random(n) > 0.2
    weight = rng.uniform(0.1, 3.0, n)
    group = rng.integers(0, 2, n)
    elem = np.asarray(locate_points(mesh, jnp.asarray(origin), 1e-12))
    assert (elem >= 0).all()

    args = dict(
        initial=False,
        max_crossings=mesh.ntet + 64,
        tolerance=1e-12,
        record_xpoints=6,
    )
    common = (
        mesh,
        jnp.asarray(origin),
        jnp.asarray(dest),
        jnp.asarray(elem, jnp.int32),
        jnp.asarray(in_flight),
        jnp.asarray(weight),
        jnp.asarray(group, jnp.int32),
        jnp.full(n, -1, jnp.int32),
    )
    flat = trace(*common, make_flux(mesh.ntet, 2, jnp.float64), **args)
    compact = trace(
        *common, make_flux(mesh.ntet, 2, jnp.float64), **sched, **args
    )

    assert bool(np.asarray(compact.done).all())
    np.testing.assert_array_equal(
        np.asarray(compact.n_xpoints), np.asarray(flat.n_xpoints)
    )
    # Recorded points: identical where recorded; slots past a lane's
    # count are never written in either schedule (both zero-initialized).
    np.testing.assert_allclose(
        np.asarray(compact.xpoints), np.asarray(flat.xpoints), atol=1e-14
    )
    np.testing.assert_allclose(
        np.asarray(compact.flux), np.asarray(flat.flux), atol=1e-12
    )
    assert int(np.asarray(flat.n_xpoints).max()) >= 3  # scenario non-trivial


def test_sparse_schedule_big_unroll_warns():
    """Per-stage unroll >= 16 on a sparse (<6 stage) schedule measured
    ~35x slower on TPU (round-4 grid, tail64_96_u32: 0.21 vs 7.6
    Mseg/s); normalize_compact_stages must flag the shape before a user
    burns a hardware window on it."""
    from pumiumtally_tpu.ops.walk import normalize_compact_stages

    sparse_u32 = ((16, 512), (24, 256), (40, 128), (64, 64, 16),
                  (96, 32, 32))
    with pytest.warns(RuntimeWarning, match="35x"):
        normalize_compact_stages(sparse_u32, None, None, 1024, 128)

    # The dense-ladder shape (>= 6 stages) with the same tail unrolls
    # measured neutral (dense_u32tail 7.62 vs dense 7.60) — no warning.
    dense_u = ((8, 640), (16, 384), (24, 256), (32, 128),
               (48, 64, 16), (64, 32, 16), (96, 16, 32))
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        normalize_compact_stages(dense_u, None, None, 1024, 128)

    # Small unrolls on sparse schedules stay silent too.
    with _w.catch_warnings():
        _w.simplefilter("error")
        normalize_compact_stages(((16, 512), (64, 64, 8)), None, None,
                                 1024, 128)


def test_nonpositive_stage_size_rejected():
    from pumiumtally_tpu.ops.walk import normalize_compact_stages

    with pytest.raises(ValueError, match=">= 1"):
        normalize_compact_stages(((16, 0),), None, None, 1024, 128)
    with pytest.raises(ValueError, match=">= 1"):
        normalize_compact_stages(((16, 64, 0),), None, None, 1024, 128)
    with pytest.raises(ValueError, match=">= 1"):
        # The compact_after/compact_size fold must hit the same check.
        normalize_compact_stages(None, 10, 0, 1024, 128)


# --------------------------------------------------------------------- #
# The fit ladder (compact_stages="fit", the facades' default)
# --------------------------------------------------------------------- #
FIT_N = 1 << 18  # fit_widths: 262144, 32768, 4096 — two narrower stages


@pytest.fixture(scope="module")
def two_region_box():
    """A 5^3 box whose class changes at x = 0.5, so moves stop on the
    material boundary as well as reach their destinations or leave."""
    from pumiumtally_tpu.mesh.box import build_box_arrays

    coords, t2v = build_box_arrays(1.0, 1.0, 1.0, 5, 5, 5)
    x = coords[t2v].mean(axis=1)[:, 0]
    return build_box(1, 1, 1, 5, 5, 5, class_id=np.where(x < 0.5, 1, 2),
                     dtype=jnp.float64)


def _fit_batch(mesh, kind, n=FIT_N, seed=21):
    """``short``: flights of exponential length (mean 0.08) from element
    centroids, as the megastep's moves; ``long``: uniform start and end
    points, as the batch-start search walks. A tenth parked, a few
    leaving the box."""
    rng = np.random.default_rng(seed)
    elem = rng.integers(0, mesh.ntet, n).astype(np.int32)
    if kind == "short":
        origin = np.asarray(mesh.centroids())[elem]
        u = rng.normal(size=(n, 3))
        u /= np.linalg.norm(u, axis=1, keepdims=True)
        dest = origin + u * rng.exponential(0.08, (n, 1))
    else:
        origin = rng.uniform(0.02, 0.98, (n, 3))
        elem = np.asarray(locate_points(mesh, jnp.asarray(origin), 1e-12))
        dest = rng.uniform(-0.05, 1.05, (n, 3))
    return dict(
        origin=origin, dest=dest, elem=elem,
        in_flight=rng.random(n) > 0.1,
        weight=rng.uniform(0.1, 3.0, n), group=rng.integers(0, 2, n),
    )


def _walk(mesh, b, path, **kw):
    from pumiumtally_tpu.ops import staging
    from pumiumtally_tpu.ops.walk import trace_packed

    n = len(b["elem"])
    kw = dict(max_crossings=mesh.ntet + 64, tolerance=1e-12, n_groups=2,
              unroll=4, **kw)
    flux = make_flux(mesh.ntet, 2, jnp.float64, flat=True)
    mat = jnp.full(n, -1, jnp.int32)
    if path == "trace_packed":
        rec = staging.pack_move_record(
            staging.HostStager(), b["dest"], b["weight"],
            b["group"].astype(np.int32), b["in_flight"], jnp.float64,
        )
        return trace_packed(
            mesh, jnp.asarray(b["origin"]), jnp.asarray(b["elem"]), mat,
            jnp.asarray(rec), flux, **kw,
        )[0]
    return trace(
        mesh, jnp.asarray(b["origin"]), jnp.asarray(b["dest"]),
        jnp.asarray(b["elem"], jnp.int32), jnp.asarray(b["in_flight"]),
        jnp.asarray(b["weight"]), jnp.asarray(b["group"], jnp.int32), mat,
        flux, **kw,
    )


@pytest.mark.parametrize("kind, path, initial", [
    ("short", "trace", False),
    ("long", "trace", False),
    ("long", "trace", True),
    ("short", "trace_packed", False),
])
def test_fit_ladder_matches_flat(two_region_box, kind, path, initial):
    """The fit ladder walks every lane exactly the flat loop's
    iterations: positions, elements, material ids, done flags and
    segment counts equal, the flux to summation order."""
    from pumiumtally_tpu.obs import stats_to_dict

    b = _fit_batch(two_region_box, kind)
    flat = _walk(two_region_box, b, path, initial=initial)
    fit = _walk(two_region_box, b, path, initial=initial,
                compact_stages="fit")
    for f in ("position", "elem", "material_id", "done", "n_segments"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fit, f)), np.asarray(getattr(flat, f)), f
        )
    # Loop iterations: the narrower stages' chunks divide the full
    # width's (unroll 4), so rounded up to it they are the flat loop's.
    assert -(-int(fit.n_crossings) // 4) * 4 == int(flat.n_crossings)
    np.testing.assert_allclose(np.asarray(fit.flux), np.asarray(flat.flux),
                               rtol=1e-12, atol=1e-14)
    d = stats_to_dict(fit.stats)
    assert d["ladder_rounds"] >= 1  # the ladder left the full width
    assert d["lane_slots"] < stats_to_dict(flat.stats)["lane_slots"]
    if not initial:
        assert (np.asarray(flat.material_id) >= 1).any()  # material stops


def test_source_moves_fit_matches_flat(two_region_box):
    """Device-sourced moves under the default (fit) config equal the
    flat loop's: every lane's state after the moves, and the flux to
    summation order."""
    from pumiumtally_tpu import PumiTally, TallyConfig
    from pumiumtally_tpu.ops.source import SourceParams

    n = 16384  # fit_widths: 16384, 2048
    src = SourceParams(sigma_t={1: 12.5, 2: 6.0},
                       absorption={1: 0.9, 2: 0.3}, seed=5)
    out = {}
    for name, kw in (("fit", {}),
                     ("flat", dict(compact_after=None, compact_stages=None))):
        t = PumiTally(two_region_box, n, TallyConfig(
            dtype=jnp.float64, tolerance=1e-12, megastep=3, **kw))
        assert t._compact_stages == ("fit" if name == "fit" else None)
        t.initialize_particle_location(
            np.random.default_rng(3).uniform(0.05, 0.95, (n, 3)).ravel())
        t.run_source_moves(3, src)
        out[name] = t
    fit, flat = out["fit"], out["flat"]
    for f in ("origin", "elem", "material_id", "weight", "group",
              "in_flight"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fit.state, f)),
            np.asarray(getattr(flat.state, f)), f,
        )
    assert fit.total_segments == flat.total_segments > 0
    np.testing.assert_allclose(fit.raw_flux, flat.raw_flux, rtol=1e-12,
                               atol=1e-14)
    rounds = [r["ladder_rounds"] for r in fit.telemetry()["per_move"]
              if r["kind"] in ("initial_search", "megastep")]
    assert len(rounds) == 2 and min(rounds) >= 1  # the search, 3 moves


def test_fit_truncated_lanes_counted_and_named(two_region_box):
    """A lane the fit ladder leaves at max_crossings is counted in
    ``truncated`` — as many as the flat loop leaves — and named in the
    move's record."""
    from pumiumtally_tpu import PumiTally, TallyConfig

    n = 16384  # fit_widths: 16384, 2048
    rng = np.random.default_rng(8)
    # Start at the seed element's centroid (a search of no crossings);
    # one lane in twenty flies across the box, the rest barely move, so
    # the full width is left early and the long walks hit the bound in
    # the 2,048-lane stage.
    start = np.tile(np.asarray(two_region_box.centroids())[0], (n, 1))
    far = rng.random(n) < 0.05
    dest = np.where(far[:, None], rng.uniform(0.5, 0.98, (n, 3)),
                    start + rng.uniform(-0.01, 0.01, (n, 3))).ravel()
    start = start.ravel()
    recs = {}
    for name, kw in (("fit", {}),
                     ("flat", dict(compact_after=None, compact_stages=None))):
        t = PumiTally(two_region_box, n, TallyConfig(
            dtype=jnp.float64, tolerance=1e-12, unroll=1, max_crossings=9,
            **kw))
        t.initialize_particle_location(start)
        with pytest.warns(RuntimeWarning, match="Truncated lanes"):
            t.move_to_next_location(
                dest.copy(), np.ones(n, np.int8), np.ones(n),
                np.zeros(n, np.int32), np.full(n, -1, np.int32))
        recs[name] = [r for r in t.telemetry()["per_move"]
                      if r["kind"] == "move"][-1]
    fit = recs["fit"]
    assert fit["ladder_rounds"] == 1  # the walk reached the 2,048 stage
    assert fit["truncated"] == recs["flat"]["truncated"] > 0
    named = fit["truncated_lanes"]
    assert 0 < len(named) == min(8, fit["truncated"])
    assert named == recs["flat"]["truncated_lanes"]


def test_fit_is_the_default_schedule():
    """TallyConfig() resolves to the fit ladder; explicit single-stage
    knobs, schedules and the named ladders keep their meaning, and the
    partitioned facade keeps its single-stage schedule."""
    from pumiumtally_tpu import TallyConfig
    from pumiumtally_tpu.utils.config import dense_ladder

    n = 1 << 20
    assert TallyConfig().resolve_compact_stages(n) == "fit"
    assert TallyConfig().resolve_compact_stages(512) is None
    # A walk without a fit ladder (the partitioned one) gets the knobs.
    assert TallyConfig().resolve_compact_stages(n, fit_ladder=False) is None
    for kw in (dict(compact_after=16), dict(compact_size=4096),
               dict(compact_after=None)):
        cfg = TallyConfig(**kw)
        assert cfg.resolve_compact_stages(n) is None
        assert cfg.resolve_compaction(n) == (
            (None, None) if kw.get("compact_after", 0) is None
            else (kw.get("compact_after", 32), kw.get("compact_size", n // 8))
        )
    assert TallyConfig(compact_stages="auto").resolve_compact_stages(
        n) == dense_ladder(n)
    assert TallyConfig(compact_stages=((8, 4096),)).resolve_compact_stages(
        n) == ((8, 4096),)
    assert TallyConfig(compact_stages=None).resolve_compaction(n) == (
        32, n // 8)
