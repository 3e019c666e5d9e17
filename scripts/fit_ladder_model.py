"""Fit the ladder slot model's cost coefficients to the round-4 grid.

Round-4 review, weak item 5: the DP-planned dp_r250k schedule (6.93 Mseg/s)
lost to the hand-built dense ladder (7.60) even though the DP is exact
under the slot model. Either the model misprices something or its
round-cost assumption (250k slot-equivalents per compaction round) is
off. This script reconciles model and measurement:

  1. re-measures the crossing-count decay curve exactly as
     scripts/plan_ladder.py does (record_xpoints walk, CPU),
  2. computes each round-4 grid schedule's (slots, rounds) under the
     model,
  3. least-squares fits   time_ms = c_slot*slots + c_round*rounds + c0
     to the measured ms/step rows (sweep_stages.out, wave-1 hardware),
  4. prints per-schedule residuals — a schedule whose residual is large
     is the one the model misprices — and the implied round cost in
     slot-equivalents (c_round / c_slot),
  5. re-runs the DP with the FITTED round cost and prints the new
     optimal schedule for hardware re-validation.

Usage: python scripts/fit_ladder_model.py [cells] [particles]
"""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts.plan_ladder import (  # noqa: E402
    optimize_ladder,
    survivors,
)

# Measured ms/step, round-4 wave-1 hardware grid (BENCH_GRID_r04.md,
# sweep_stages; 1M particles, 55-cell mesh, unroll 8). The
# tail64_96_u32 catastrophe is excluded — its 77 s/step is a different
# regime (compile/codegen pathology), not slot-model territory.
MEASURED_MS = {
    "default_r2": 3437.9,
    "tail64": 2433.1,
    "tail64_96": 2438.1,
    "early8": 2393.7,
    "dense": 2188.8,
    "dp_r250k": 2400.1,
}

M = 1048576

SCHEDULES = {
    "default_r2": ((16, M // 2), (24, M // 4), (40, M // 8)),
    "tail64": ((16, M // 2), (24, M // 4), (40, M // 8), (64, M // 32)),
    "tail64_96": ((16, M // 2), (24, M // 4), (40, M // 8),
                  (64, M // 32), (96, M // 64)),
    "early8": ((8, 5 * M // 8), (16, 3 * M // 8), (24, M // 4),
               (40, M // 8), (64, M // 32)),
    "dense": ((8, 5 * M // 8), (16, 3 * M // 8), (24, M // 4),
              (32, M // 8), (48, M // 16), (64, M // 32), (96, M // 64)),
    "dp_r250k": ((16, M // 2), (24, M // 4), (40, M // 8),
                 (48, M // 16), (56, M // 32), (76, 8192)),
}


def ladder_slots_rounds(active, n, stages, unroll=8):
    """(slots, rounds) under the model of plan_ladder.ladder_slots, but
    with the round count returned instead of folded into the cost, and
    the final stage's rounds counted the same way."""
    kmax = len(active) - 1
    total, rounds = 0.0, 0

    def span_slots(width, k0, k1):
        span = -(-(k1 - k0) // unroll) * unroll
        return width * span

    starts = [s[0] for s in stages] + [kmax]
    total += span_slots(n, 0, min(starts[0], kmax))
    for i, st in enumerate(stages):
        start, width = st[0], st[1]
        if start >= kmax:
            break
        nxt = min(starts[i + 1], kmax)
        if i + 1 < len(stages):
            total += span_slots(width, start, nxt)
            rounds += 1
        else:
            # Final stage loop: replicate final_loop_slots but count
            # rounds (round_cost=0 so the return is pure slots).
            alive = active[min(start, kmax)]
            served = 0
            while alive - served > 0:
                nd = int(np.searchsorted(
                    -np.asarray(active), -served, side="left")) - 1
                nd = max(nd, start)
                span = -(-(min(nd, kmax) - start) // unroll) * unroll
                total += width * span
                rounds += 1
                served += width
            break
    return total, rounds


def main():
    import jax.numpy as jnp

    from pumiumtally_tpu import build_box, make_flux
    from pumiumtally_tpu.ops.walk import trace_impl

    cells = int(sys.argv[1]) if len(sys.argv) > 1 else 55
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 65536
    dtype = jnp.float32
    mean_path = 0.08

    mesh = build_box(1.0, 1.0, 1.0, cells, cells, cells, dtype=dtype)
    rng = np.random.default_rng(0)
    elem = jnp.asarray(rng.integers(0, mesh.ntet, n).astype(np.int32))
    origin = jnp.asarray(
        np.asarray(mesh.centroids())[np.asarray(elem)], dtype
    )
    d = rng.normal(0, 1, (n, 3))
    d /= np.linalg.norm(d, axis=1, keepdims=True)
    ln = rng.exponential(mean_path, (n, 1))
    dest = jnp.asarray(
        np.clip(np.asarray(origin) + d * ln, 0.01, 0.99), dtype
    )
    r = trace_impl(
        mesh, origin, dest, elem, jnp.ones(n, bool), jnp.ones(n, dtype),
        jnp.zeros(n, jnp.int32), jnp.full(n, -1, jnp.int32),
        make_flux(mesh.ntet, 1, dtype),
        initial=False, max_crossings=mesh.ntet + 64, tolerance=1e-6,
        record_xpoints=1,
    )
    counts = np.asarray(r.n_xpoints)
    kmax = int(counts.max()) + 2
    act = survivors(counts, kmax) * (M / n)

    names = list(MEASURED_MS)
    rows = np.array([
        ladder_slots_rounds(act, M, SCHEDULES[name]) for name in names
    ])
    slots, rounds = rows[:, 0], rows[:, 1]
    y = np.array([MEASURED_MS[name] for name in names])

    # time_ms = c_slot*slots + c_round*rounds + c0
    A = np.stack([slots, rounds, np.ones_like(slots)], axis=1)
    coef, *_ = np.linalg.lstsq(A, y, rcond=None)
    c_slot, c_round, c0 = coef
    pred = A @ coef
    print(f"decay: mean {counts.mean():.1f} crossings/move, kmax {kmax}")
    print(f"fit: c_slot {c_slot*1e6:.2f} ns/slot, c_round "
          f"{c_round:.1f} ms/round, c0 {c0:.0f} ms  "
          f"(round cost = {c_round/c_slot/1e3:.0f} kslot-equivalents)")
    print(f"{'schedule':12s} {'slots(M)':>9s} {'rounds':>6s} "
          f"{'meas':>7s} {'pred':>7s} {'resid':>7s}")
    for i, name in enumerate(names):
        print(f"{name:12s} {slots[i]/1e6:9.1f} {rounds[i]:6.0f} "
              f"{y[i]:7.1f} {pred[i]:7.1f} {y[i]-pred[i]:+7.1f}")

    # Re-plan with the fitted round cost (in slot units).
    rc_fit = max(c_round / c_slot, 0.0)
    for rc in (250e3, rc_fit):
        c_opt, sched = optimize_ladder(act, M, rc)
        s_o, r_o = ladder_slots_rounds(act, M, sched)
        t_pred = c_slot * s_o + c_round * r_o + c0
        print(f"DP(rc={rc/1e3:.0f}k): pred {t_pred:.1f} ms  "
              f"slots {s_o/1e6:.1f}M rounds {r_o}  {sched}")
    # Dense's prediction under the fit, for reference.
    i = names.index("dense")
    print(f"dense pred {pred[i]:.1f} ms (meas {y[i]:.1f})")


if __name__ == "__main__":
    main()
