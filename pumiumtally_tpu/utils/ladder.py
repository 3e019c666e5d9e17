"""Compaction-ladder planning from crossing-count statistics.

The walk's cost is executed SLOTS — Σ stage_width × stage_span — plus a
fixed cost per compaction round; both are set entirely by the
distribution of boundary crossings per move (the "decay curve") and the
schedule. This module turns a decay curve into a schedule:

  * :func:`survivors` — decay curve from measured per-particle crossing
    counts (``record_xpoints=1`` walk, or ``n_segments/n`` for just the
    mean);
  * :func:`exp_survivors` — analytic curve for a given mean
    crossings/move (exponential path lengths through a uniform mesh —
    the bench workload's measured curve matches this family);
  * :func:`simulate_ladder` — EXECUTIONAL cost model: a histogram of
    remaining iterations is advanced stage by stage exactly as
    ops/walk.py schedules lanes, so stages narrower than the live count
    price their deferred overflow honestly (the round-4 planner's
    "fake-cheap overflow" caveat is gone — and the measurement says
    moderate under-width stages are genuinely cheap: the dense ladder's
    model estimate matched hardware within 1%, BENCHMARKS.md r4 grid);
  * :func:`plan_stages` — beam search over (start, width) sequences
    under the executional model;
  * :func:`fit_widths` / :func:`simulate_fit` — the fit ladder
    (``compact_stages="fit"``, the default): static widths falling by
    ``FIT_RATIO`` from the batch size, each stage left as soon as its
    live lanes fit the next one. Every live lane rides every stage, so
    which lanes a stage picks does not matter and the model is exact on
    an integer histogram; ``simulate_ladder(..., "fit")`` uses it.

Cost calibration (round-4 hardware fit, scripts/fit_ladder_model.py):
time ≈ 81 ns/slot + 110 ms/round on the v5e bench config — a round
costs ≈ 1.3·n_particles slot-equivalents (its fixed part is the
first_k_active scans + gather/scatter over the full batch). The
round-4 DP assumed 250k (5× too cheap) and pinned widths ≥ the live
count; both biases pushed it away from the measured-best dense ladder.
Reference analog: the schedule exists to keep the GPU-resident walk of
pumipic_particle_data_structure.cpp's search loop from running every
lane to the slowest straggler; the reference has no equivalent knob.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = [
    "FIT",
    "fit_stage_unroll",
    "fit_widths",
    "survivors",
    "exp_survivors",
    "simulate_fit",
    "simulate_ladder",
    "plan_stages",
]

# The fit ladder: ``compact_stages`` value, width ratio between stages,
# the narrowest later width, the first narrower stage's floor, and the
# narrower stages' unroll (ops/walk.py "Straggler compaction").
# Each stage's loop compiles its own copies of the walk body, one per
# unrolled iteration, and on a TPU the compiled program's code sits in
# device memory beside the data: at 2^20 lanes and 998,250 tets an
# 8-times unrolled narrower stage added about 40 MB of code to the
# megastep and 24 MB to the search program (PERF.md, the fit ladder).
# A narrow stage is cheap per iteration, so its unroll buys little.
# Unrolled twice, ratio 8 walked fastest of 4, 8 and 16 on a v5e and
# left the peak below the single 131,072-lane stage's.
FIT = "fit"
FIT_RATIO = 8
FIT_FLOOR = 1024
FIT_FIRST_FLOOR = 256
FIT_STAGE_UNROLL = 2


def fit_widths(n: int) -> tuple:
    """Lane widths of the fit ladder's stages for an ``n``-lane batch:
    ``n`` itself, then ``max(n // 8, 256)`` (256 is the single-stage
    schedule's subset floor, so a batch of 1,024 to 8,191 lanes still
    leaves its full width), then ``// 8`` again while a width holds at
    least ``FIT_FLOOR`` lanes. A batch of 256 lanes or fewer gets the
    full width alone: the flat loop."""
    widths = [n]
    nxt = max(n // FIT_RATIO, FIT_FIRST_FLOOR)
    while nxt < widths[-1]:
        widths.append(nxt)
        nxt = nxt // FIT_RATIO
        if nxt < FIT_FLOOR:
            break
    return tuple(widths)


def fit_stage_unroll(unroll: int) -> int:
    """Walk iterations per loop iteration in the fit ladder's narrower
    stages: ``FIT_STAGE_UNROLL`` or less, and a divisor of the full
    width's ``unroll``, so the stages share the flat loop's last
    iteration (``max_crossings`` rounded up to ``unroll``)."""
    return math.gcd(unroll, FIT_STAGE_UNROLL)


def survivors(counts: np.ndarray, kmax: int | None = None) -> np.ndarray:
    """active[k] = lanes needing body iteration k, from measured
    per-particle crossing counts (a lane with c crossings executes c+1
    iterations; the last reaches the destination).

    active[k] = #{lanes with iterations > k} — so active[0] is every
    lane and a 0-crossing lane (1 iteration) contributes to active[0]
    only. (scripts/plan_ladder.py's variant of this shifts by one
    iteration — a 1-in-~15 bias at bench statistics; kept there
    unchanged as the round-4 historical model, fixed here.)"""
    counts = np.asarray(counts)
    iters = counts + 1
    if kmax is None:
        kmax = int(iters.max()) + 1
    hist = np.bincount(np.minimum(iters, kmax), minlength=kmax + 1)
    return (iters.size - np.cumsum(hist)).astype(float)


def exp_survivors(n: int, mean_crossings: float,
                  kmax: int | None = None) -> np.ndarray:
    """Analytic decay curve: crossings/move ~ Exponential(mean).

    Matches the measured bench curve family (mean 14.9 at the 55-cell
    mesh; crossings/move scale with move length × mesh density, so the
    mean scales ∝ cells when reusing the calibration on a denser
    mesh)."""
    m = max(float(mean_crossings), 0.25)
    if kmax is None:
        kmax = int(np.ceil(m * 12)) + 2
    k = np.arange(kmax + 1, dtype=float)
    # iterations = crossings + 1 → survivors(k) = P(crossings >= k) at
    # k-1; P(c >= x) = exp(-x/m) for the exponential family.
    return n * np.exp(-np.maximum(k - 1, 0) / m)


def _to_hist(active_or_hist) -> np.ndarray:
    """A remaining-iteration histogram (index 0 = done) from a survivors
    curve (monotone non-increasing) or from such a histogram."""
    a = np.asarray(active_or_hist, float)
    if len(a) >= 2 and np.all(np.diff(a) <= 1e-9):
        # survivors curve a[k] = #{iterations > k} → remaining-iteration
        # histogram hist[r] = #{iterations == r} = a[r-1] - a[r] for
        # r >= 1, hist[0] = 0, plus a tail bucket a[-1] for lanes
        # clipped past the curve's end.
        return np.concatenate([[0.0], -np.diff(a), [a[-1]]])
    return a.copy()


def simulate_fit(
    active_or_hist: np.ndarray,
    n: int,
    *,
    unroll: int = 8,
    max_crossings: int,
) -> tuple[float, int, int]:
    """Executed (lane-slots, rounds, loop iterations) of the fit ladder.

    Each stage of :func:`fit_widths` runs chunks of every live lane —
    ``unroll`` iterations at full width, :func:`fit_stage_unroll` in the
    narrower stages — while more lanes are live than the next width
    holds (the last stage: while any is) and the walk's iteration count
    is under ``max_crossings`` rounded up to ``unroll``; a round is a
    narrower stage that ran a chunk. Exact for an integer histogram of
    per-lane iteration counts, the expectation for a survivors curve."""
    hist = _to_hist(active_or_hist)
    widths = fit_widths(int(n))
    bound = -(-int(max_crossings) // unroll) * unroll
    slots, rounds, it = 0.0, 0, 0
    for k, width in enumerate(widths):
        exit_live = widths[k + 1] if k + 1 < len(widths) else 0
        u = unroll if k == 0 else fit_stage_unroll(unroll)
        ran = 0
        while it < bound and hist[1:].sum() > exit_live:
            # One chunk: every live lane advances `u` iterations.
            hist = np.concatenate(
                [[hist[:u + 1].sum()], hist[u + 1:],
                 np.zeros(min(u, len(hist) - 1))]
            )
            ran += u
            it += u
        slots += width * ran
        rounds += int(k > 0 and ran > 0)
    return slots, rounds, it


def _chunk(span: int, unroll: int) -> int:
    return -(-max(span, 0) // unroll) * unroll


def _advance(hist: np.ndarray, width: float, span: int, unroll: int = 1):
    """Advance min(width, active) lanes of `hist` (remaining-iteration
    histogram; index 0 = done) by `span` iterations, selecting lanes
    PROPORTIONALLY across buckets — the expectation of ops/walk.py's
    first-k-by-index pick, which is index-random w.r.t. remaining work.
    Returns (new_hist, executed_span) where executed_span <= span stops
    at the selected lanes' max remaining ROUNDED UP to an unroll chunk
    (the real while_loop's exit check runs between chunks, so a lane
    with 3 remaining still costs a full 8-iteration chunk at
    unroll=8)."""
    active = hist[1:].sum()
    if active <= 0:
        return hist, 0
    nz = np.nonzero(hist[1:])[0]
    max_rem = int(nz[-1]) + 1
    run = min(span, _chunk(max_rem, unroll))
    f = min(width / active, 1.0)
    sel = hist * f
    sel[0] = 0.0
    out = hist - sel
    # Selected lanes with remaining r move to max(r - run, 0).
    shifted = np.zeros_like(out)
    r = np.arange(len(hist))
    dst = np.maximum(r - run, 0)
    np.add.at(shifted, dst, sel)
    return out + shifted, run


def simulate_ladder(
    active_or_hist: np.ndarray,
    n: float,
    stages: tuple,
    *,
    unroll: int = 8,
    round_cost: float | None = None,
    max_crossings: int | None = None,
) -> tuple[float, int]:
    """Executed (slots, rounds) of `stages` under the executional model.

    `active_or_hist` is a survivors curve (monotone non-increasing —
    converted internally) or a remaining-iteration histogram. Every
    phase runs in `unroll`-sized chunks. Returns (slots, rounds);
    apply your own per-slot/per-round costs (fit_ladder_model.py's
    hardware fit, or plan_stages' default). ``stages="fit"`` prices the
    fit ladder (:func:`simulate_fit`) over ``max_crossings`` (default:
    past the histogram's end)."""
    hist = _to_hist(active_or_hist)
    kmax = len(hist) + 2
    if isinstance(stages, str):
        if stages != FIT:
            raise ValueError(f"unknown ladder policy {stages!r}")
        slots, rounds, _ = simulate_fit(
            hist, n, unroll=unroll,
            max_crossings=kmax + unroll if max_crossings is None
            else max_crossings,
        )
        if round_cost is not None:
            return slots + rounds * round_cost, rounds
        return slots, rounds
    slots, rounds = 0.0, 0

    stages = tuple(stages)
    first = stages[0][0] if stages else (max_crossings or kmax)
    # Phase 1: full width to the first stage start.
    h, run = _advance(hist, n, _chunk(first, unroll), unroll)
    slots += n * run
    hist = h
    for i, st in enumerate(stages):
        start, width = int(st[0]), float(st[1])
        if hist[1:].sum() <= 0:
            break
        if i + 1 < len(stages):
            span = _chunk(int(stages[i + 1][0]) - start, unroll)
            hist, run = _advance(hist, width, span, unroll)
            slots += width * run
            rounds += 1
        else:
            # Final stage: rounds of `width` to completion (bounded the
            # way the real walk bounds them: ceil(n/width)+1).
            guard = int(-(-n // max(width, 1))) + 2
            while hist[1:].sum() > 0 and guard > 0:
                hist, run = _advance(
                    hist, width, _chunk(kmax, unroll), unroll
                )
                slots += width * run
                rounds += 1
                guard -= 1
    if round_cost is not None:
        return slots + rounds * round_cost, rounds
    return slots, rounds


def plan_stages(
    n_particles: int,
    mean_crossings: float,
    *,
    counts: np.ndarray | None = None,
    unroll: int = 8,
    round_cost: float | None = None,
    width_floor: int | None = None,
    passes: int = 4,
) -> tuple:
    """Plan a compaction ladder for the given crossing statistics.

    Uses the measured decay (``counts``, per-particle crossing counts)
    when provided, else the analytic exponential family at
    ``mean_crossings``. ``round_cost`` defaults to 1.3·n_particles
    slot-equivalents — the round-4 hardware fit (110 ms/round ÷ 81
    ns/slot at n=1M; the fixed part of a round — first_k_active scans,
    gather/scatter — scales with the batch).

    Construction: seed with the HUG ladder — a stage at every survivor
    halving of the decay curve, each width the live count rounded up
    (the shape of the measured-best dense ladder, generalized to the
    curve at hand) — then hill-climb under :func:`simulate_ladder`'s
    executional score (shift starts, rescale widths, drop stages)
    until no move improves. The result is >= the hug seed by
    construction, and the seed reproduces the dense ladder's score at
    the bench statistics. (A cost-so-far beam search was tried first
    and rejected: states that under-serve lanes look locally cheap and
    crowd out the hug family.) Returns ((start, width), ...); possibly
    empty — small batches plan no ladder."""
    n = float(n_particles)
    if counts is not None:
        act = survivors(np.asarray(counts))
        act = act * (n / act[0])
    else:
        act = exp_survivors(n, mean_crossings)
    if round_cost is None:
        round_cost = 1.3 * n
    if width_floor is None:
        width_floor = max(int(n) // 128, 64)
    kmax = len(act) - 1
    gran = 4096 if n >= 65536 else 64

    def hug(a):
        w = -(-int(np.ceil(a)) // gran) * gran
        return int(min(max(w, width_floor), n))

    def score(stages):
        slots, rounds = simulate_ladder(
            act, n, stages, unroll=unroll, max_crossings=kmax + 2
        )
        return slots + rounds * round_cost

    # Seed: a stage wherever the survivor count halves, width hugging
    # the live count from above (dense-ladder shape).
    starts = []
    j = 1
    while n / 2**j >= width_floor and j < 32:
        k = int(np.searchsorted(-act, -(n / 2**j), side="left"))
        k = max(4, -(-k // 4) * 4)
        if k >= kmax:
            break
        if not starts or k > starts[-1]:
            starts.append(k)
        j += 1
    sched = tuple((k, hug(act[min(k, kmax)])) for k in starts)
    if not sched:
        return ()
    best = (score(sched), sched)

    def neighbors(stages):
        for i in range(len(stages)):
            k, w = stages[i]
            lo = stages[i - 1][0] if i else 0
            hi = stages[i + 1][0] if i + 1 < len(stages) else kmax
            for dk in (-8, -4, 4, 8):
                k2 = k + dk
                if lo < k2 < hi:
                    yield stages[:i] + ((k2, hug(act[min(k2, kmax)])),
                                        ) + stages[i + 1:]
            for f in (0.5, 0.75, 1.5):
                w2 = int(min(max(w * f, width_floor), n))
                if w2 != w:
                    yield stages[:i] + ((k, w2),) + stages[i + 1:]
            yield stages[:i] + stages[i + 1:]  # drop the stage

    for _ in range(passes):
        improved = False
        for cand in list(neighbors(best[1])):
            s = score(cand)
            if s < best[0] - 1e-6:
                best = (s, cand)
                improved = True
        if not improved:
            break
    if score(()) <= best[0]:
        return ()
    return best[1]
