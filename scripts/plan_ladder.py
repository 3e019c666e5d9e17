"""Plan the compaction ladder from the measured crossing-count decay.

The slot cost of a ladder is backend-independent: executed slots =
Σ stage_width × stage_span (+ final-stage rounds), driven entirely by
the distribution of crossings-per-move. This script measures that
distribution EXACTLY for the bench configuration (one walk with
record_xpoints=1 — n_xpoints counts every real crossing per particle;
+1 slot for each particle's terminal no-crossing iteration), evaluates
every candidate schedule's slot count, and greedily derives a
near-optimal power-of-two ladder, charging each compaction round a
configurable slot-equivalent overhead.

The absolute per-slot time differs per backend; the RANKING of ladders
(up to the round-overhead charge) does not.

CAVEAT: the model charges an intermediate stage width x span and lets
overflow lanes (active > width) "wait, unharmed" — it does NOT price
the deferred work of that overflow, so schedules whose widths sit far
below the live count at their starts (e.g. the 55-cell-tuned "dense"
ladder evaluated on a 119-cell mesh with 2x the crossings) come out
fake-cheap. Trust the ranking only among schedules whose widths are >=
the survivor count at each start; scale stage starts with
crossings/move (≈ cells) when changing mesh density.

Usage: python scripts/plan_ladder.py [cells] [particles] [round_cost_slots]
"""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def survivors(counts: np.ndarray, kmax: int) -> np.ndarray:
    """active_lanes[k] = lanes needing iteration k (0-based), k<=kmax."""
    # A lane with c recorded crossings executes c+1 body iterations
    # (the last one reaches the destination and records nothing).
    iters = counts + 1
    hist = np.bincount(np.minimum(iters, kmax), minlength=kmax + 1)
    alive = iters.size - np.cumsum(hist)  # alive after iteration k
    return np.concatenate([[iters.size], alive[:-1]])  # needing iter k


def ladder_slots(active: np.ndarray, n: int, stages, round_cost: float,
                 unroll: int = 8) -> float:
    """Executed slots for schedule `stages` given the decay curve.

    Models exactly what walk.py does: full width until stage 1's start,
    one bounded round per intermediate stage (width w, lanes beyond w
    wait), final stage loops rounds of its width to completion; every
    phase runs in unroll-sized chunks (ceil to unroll). Waiting lanes
    (active > width) stay for a LATER stage — approximated here by
    carrying the overflow forward (the real walk's final stage mops up).
    """
    kmax = len(active) - 1
    total = 0.0
    rounds = 0

    def span_slots(width, k0, k1):
        # width lanes run iterations [k0, k1) in unroll chunks
        span = k1 - k0
        span = -(-span // unroll) * unroll
        return width * span

    starts = [s[0] for s in stages] + [kmax]
    # Phase 1: full batch.
    total += span_slots(n, 0, min(starts[0], kmax))
    for i, st in enumerate(stages):
        start, width = st[0], st[1]
        if start >= kmax:
            break
        nxt = min(starts[i + 1], kmax)
        if i + 1 < len(stages):
            # One round of `width`; overflow waits (still counts later —
            # conservatively assume it joins the next stage unharmed).
            total += span_slots(width, start, nxt)
            rounds += 1
        else:
            # Final stage: delegate to the standalone model (shared with
            # optimize_ladder's DP so evaluator and optimizer can never
            # drift apart).
            total += final_loop_slots(
                active, width, start, round_cost, unroll
            )
            break
    return total + rounds * round_cost


def pinned_width(active, k, floor=8192):
    """Smallest power of two >= the live count at crossing k (never below
    the live count, so the fake-cheap overflow caveat cannot apply),
    floored. Shared by the DP optimizer and the candidate builders."""
    kmax = len(active) - 1
    a = active[min(k, kmax)]
    return int(max(2 ** int(np.ceil(np.log2(max(a, 1)))), floor))


def final_loop_slots(active, width, start, round_cost, unroll=8):
    """Slot cost of ENDING the ladder at `start` with a looping final
    stage of `width`: rounds of `width` until the tail is done, each
    round's span read off the decay curve by longest-first service
    (consistent across candidates, slightly optimistic vs the real
    first-k-by-index pick). Shared by ladder_slots and the DP."""
    kmax = len(active) - 1
    alive = active[min(start, kmax)]
    total, served, rounds = 0.0, 0, 0
    while alive - served > 0:
        nd = int(
            np.searchsorted(-np.asarray(active), -served, side="left")
        ) - 1
        nd = max(nd, start)
        span = min(nd, kmax) - start
        span = -(-span // unroll) * unroll
        total += width * span
        rounds += 1
        served += width
    return total + rounds * round_cost


def optimize_ladder(active, n, round_cost, unroll=8, grid_step=4,
                    width_floor=8192):
    """Optimum of the slot model over stage starts on a grid (shortest
    path; exact over starts in range(grid_step, min(kmax, 512),
    grid_step) — off-grid starts are not searched).

    With each stage's width pinned to the smallest power of two >= the
    survivor count at its start (pinned_width — never below the live
    count, so the fake-cheap overflow caveat cannot apply), the model's
    cost decomposes per stage: intermediate stage [a, b) costs
    width(a) x span_unroll(a, b) + round_cost, and ending at `a` costs
    the final-stage loop. That is a DAG shortest path over candidate
    starts — solved by DP, no hand-listing.
    """
    kmax = len(active) - 1

    def w_of(k):
        return pinned_width(active, k, width_floor)

    starts = list(range(grid_step, min(kmax, 512), grid_step))
    # best[i] = (cost from start_i to completion, schedule tuple)
    best: dict[int, tuple[float, tuple]] = {}
    for a in reversed(starts):
        wa = w_of(a)
        # Option 1: a is the FINAL stage.
        c_end = final_loop_slots(active, wa, a, round_cost, unroll)
        best_here = (c_end, ((a, wa),))
        # Option 2: one bounded round until a later start b.
        for b in starts:
            if b <= a:
                continue
            span = -(-(b - a) // unroll) * unroll
            c = wa * span + round_cost + best[b][0]
            if c < best_here[0]:
                best_here = (c, ((a, wa),) + best[b][1])
        best[a] = best_here
    # Phase 1 (full width) to the first start; also allow "no ladder".
    flat = ladder_slots(active, n, (), round_cost, unroll)
    opt = (flat, ())
    for a in starts:
        span = -(-a // unroll) * unroll
        c = n * span + best[a][0]
        if c < opt[0]:
            opt = (c, best[a][1])
    return opt


def main():
    import jax.numpy as jnp

    from pumiumtally_tpu import build_box, make_flux
    from pumiumtally_tpu.ops.walk import trace_impl

    cells = int(sys.argv[1]) if len(sys.argv) > 1 else 55
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 65536
    round_cost = float(sys.argv[3]) if len(sys.argv) > 3 else 2e6
    dtype = jnp.float32
    mean_path = 0.08

    mesh = build_box(1.0, 1.0, 1.0, cells, cells, cells, dtype=dtype)
    rng = np.random.default_rng(0)
    elem = jnp.asarray(rng.integers(0, mesh.ntet, n).astype(np.int32))
    origin = jnp.asarray(np.asarray(mesh.centroids())[np.asarray(elem)], dtype)
    d = rng.normal(0, 1, (n, 3))
    d /= np.linalg.norm(d, axis=1, keepdims=True)
    ln = rng.exponential(mean_path, (n, 1))
    dest = jnp.asarray(np.clip(np.asarray(origin) + d * ln, 0.01, 0.99), dtype)
    r = trace_impl(
        mesh, origin, dest, elem, jnp.ones(n, bool), jnp.ones(n, dtype),
        jnp.zeros(n, jnp.int32), jnp.full(n, -1, jnp.int32),
        make_flux(mesh.ntet, 1, dtype),
        initial=False, max_crossings=mesh.ntet + 64, tolerance=1e-6,
        record_xpoints=1,
    )
    counts = np.asarray(r.n_xpoints)
    kmax = int(counts.max()) + 2
    active = survivors(counts, kmax)
    print(f"crossings/move: mean {counts.mean():.1f}, p50 "
          f"{np.median(counts):.0f}, p99 {np.percentile(counts, 99):.0f}, "
          f"max {counts.max()}", flush=True)

    M = 1048576  # evaluate at bench scale (curve is per-lane, rescale)
    scale = M / n
    act = active * scale

    def pow2_ladder(first, last, width_of):
        ks, k = [], first
        while k < min(last, kmax):
            ks.append(k)
            k = int(k * 1.5) if k * 1.5 - k >= 4 else k + 4
        return tuple((k, width_of(k)) for k in ks)

    def w_of(k):
        return pinned_width(act, k)

    candidates = {
        "default_r2": ((16, M // 2), (24, M // 4), (40, M // 8)),
        "tail64_96": ((16, M // 2), (24, M // 4), (40, M // 8),
                      (64, M // 32), (96, M // 64)),
        "dense": ((8, 5 * M // 8), (16, 3 * M // 8), (24, M // 4),
                  (32, M // 8), (48, M // 16), (64, M // 32),
                  (96, M // 64)),
        "auto_pow2": pow2_ladder(8, 160, w_of),
        "dense_x2": tuple(
            (2 * st, w) for st, w in (
                (8, 5 * M // 8), (16, 3 * M // 8), (24, M // 4),
                (32, M // 8), (48, M // 16), (64, M // 32), (96, M // 64))
        ),
        "every8": tuple(
            (k, pinned_width(act, k, 4096)) for k in range(8, 128, 8)
        ),
        "none": (),
    }
    base = ladder_slots(act, M, (), round_cost)
    for name, stages in candidates.items():
        s = ladder_slots(act, M, stages, round_cost)
        print(f"{name:12s} {s/1e6:9.1f} Mslots  ({base/s:4.2f}x vs flat)  "
              f"{stages if len(str(stages)) < 90 else str(stages)[:88]}",
              flush=True)
    c_opt, sched_opt = optimize_ladder(act, M, round_cost)
    print(f"{'OPTIMAL_DP':12s} {c_opt/1e6:9.1f} Mslots  "
          f"({base/c_opt:4.2f}x vs flat)  {sched_opt}", flush=True)


if __name__ == "__main__":
    main()
