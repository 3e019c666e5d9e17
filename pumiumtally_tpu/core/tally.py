"""Flux tally accumulator: allocation, normalization, finalization.

Replaces PumiParticleAtElemBoundary's flux bookkeeping
(pumipic_particle_data_structure.cpp:517-524 allocation,
cpp:648-683 normalizeFlux). The accumulator is [ntet, n_groups, 2]
holding (Σ w·len, Σ (w·len)^2); the standard-deviation slot the reference
stores at index 2 is derived at finalization time instead of carried.

The reference's sd formula is flagged incorrect in its own source
("FIXME this is not correct, needs number of iterations", cpp:673-677) and
can produce sqrt of a negative value; here it is guarded and divided by the
move/batch count when provided (the fix the in-code FIXME asks for).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def make_flux(
    ntet: int, n_groups: int, dtype=jnp.float32, flat: bool = False
) -> jax.Array:
    """Zero tally accumulator.

    flat=False: [ntet, n_groups, 2] — the host/reference-parity shape.
    flat=True: [ntet*n_groups*2] — the DEVICE shape for the hot path.
      On TPU a trailing dimension of 2 forces the (8,128) tile layout to
      pad the minor dim 2 → 128, a 64× HBM blowup (measured: the 1M-tet
      64-group flux allocates 32.7 GB as [ntet,64,2] vs 511 MB flat,
      round-4 builder capture, BENCHMARKS.md). The walk scatters into the flat
      stride-2 layout either way; keep device-resident accumulators flat
      and reshape host-side.
    """
    if flat:
        return jnp.zeros(ntet * n_groups * 2, dtype=dtype)
    return jnp.zeros((ntet, n_groups, 2), dtype=dtype)


def _normalize_flux_impl(
    xp, flux, volumes, n_particles, n_iterations, sd_mode="segment"
):
    vol = volumes[:, None]
    n = xp.asarray(n_particles, flux.dtype)
    m = xp.maximum(xp.asarray(n_iterations, flux.dtype), 1.0)
    m1 = flux[..., 0] / (vol * n)
    m2 = flux[..., 1] / (vol * vol * n)
    if sd_mode == "segment":
        h = n * m  # total samples: per-(particle, move) scores
        var_y = xp.maximum(
            flux[..., 1] - flux[..., 0] * flux[..., 0] / h, 0.0
        ) / xp.maximum(h - 1.0, 1.0)
        sd = xp.sqrt(m * var_y / n) / vol
    elif sd_mode == "batch":
        # Slot 1 holds Σ T² of per-MOVE bin totals T (TallyConfig
        # sd_mode="batch": the walk skips per-segment squares and the
        # facade squares each move's bin delta once — one elementwise
        # pass over the accumulator per move instead of doubling the
        # per-crossing scatter rows). Samples are the M move totals:
        #   s²_T  = (ΣT² − (ΣT)²/M) / (M − 1)
        #   flux  = ΣT/(vol·N);  Var(flux) = M·s²_T/(vol²·N²)
        #   sd    = sqrt(M·s²_T)/(vol·N)
        # Same estimand as the segment form when particle scores are
        # independent; the estimator itself is noisier (M−1 degrees of
        # freedom instead of N·M−1 — relative sd-of-sd ~ 1/sqrt(2(M−1))).
        var_t = xp.maximum(
            flux[..., 1] - flux[..., 0] * flux[..., 0] / m, 0.0
        ) / xp.maximum(m - 1.0, 1.0)
        sd = xp.sqrt(m * var_t) / (vol * n)
    else:
        raise ValueError(
            f"sd_mode must be 'segment' or 'batch': {sd_mode!r}"
        )
    return xp.stack([m1, m2, sd], axis=-1)


@functools.partial(jax.jit, static_argnames=("sd_mode",))
def normalize_flux(flux, volumes, n_particles, n_iterations=1,
                   sd_mode="segment"):
    """Normalize raw tallies by element volume and particle count, with a
    statistically correct standard deviation of the flux estimate.

    Mean and second moment keep reference parity (normalizeFlux,
    cpp:660-666): slot 0 = Σc/(vol·N), slot 1 = Σc²/(vol²·N), where
    c = w·len per scored segment.

    The sd replaces the reference's in-code-flagged-broken
    ``sqrt(m2 − m1²)`` (cpp:673-677, "FIXME ... needs number of
    iterations"). Derivation — the accumulator's per-segment squares are
    per-(particle, move) samples because a straight ray scores at most
    one segment per tet per move, so with N particles over M moves there
    are H = N·M independent samples y of the per-move element score:

        s²_y   = (Σc² − (Σc)²/H) / (H − 1)        unbiased Var(y)
        flux   = Σc / (vol·N)                      = M · mean(y) / vol
        Var(f) = M² · Var(mean y) / vol²
               = M² · s²_y / (H·vol²) = M·s²_y / (N·vol²)
        sd     = sqrt(M · s²_y / N) / vol

    i.e. the iteration count enters MULTIPLICATIVELY through the M-move
    accumulation, not as the reference FIXME's flat divide — pinned
    against an analytic known-variance oracle in
    tests/test_tally_oracle.py::test_sd_matches_analytic_variance.

    ``sd_mode="batch"`` reads slot 1 as Σ(per-move bin totals)² instead
    of per-segment squares (see _normalize_flux_impl) — the cheap-tally
    mode's estimator, pinned against the same analytic oracle.

    Returns [ntet, n_groups, 3]: (mean flux, second moment, sd).
    """
    return _normalize_flux_impl(
        jnp, flux, volumes, n_particles, n_iterations, sd_mode
    )


def normalize_flux_host(flux, volumes, n_particles, n_iterations=1,
                        sd_mode="segment"):
    """normalize_flux on HOST numpy arrays — identical math, no device
    round-trip. The write path uses this so the one-shot [ntet,n_groups,2]
    view never materializes in the TPU's padded tile layout (see
    make_flux). Pinned equal to normalize_flux in tests/test_flat_flux.py.
    """
    return _normalize_flux_impl(
        np, np.asarray(flux), np.asarray(volumes), n_particles,
        n_iterations, sd_mode,
    )


@functools.partial(jax.jit, donate_argnums=(0, 1))
def accumulate_batch_squares(flux, prev_even):
    """Fold one move's batch-level squared contribution into the tally
    (TallyConfig ``sd_mode="batch"``).

    ``flux`` is the FLAT stride-2 accumulator whose even entries hold
    Σc INCLUDING the move just walked (with ``score_squares=False`` the
    walk writes only even keys); ``prev_even`` is the even-entry
    snapshot from before it. Adds the squared per-bin delta (this
    move's bin total T, squared) into the odd entries and returns the
    updated (flux, new snapshot): two elementwise passes over the
    accumulator per MOVE in place of doubling every per-crossing
    scatter row — the squares rows measured ~20% of TPU step time
    (round-4 nosq A/B; BENCHMARKS.md "v5e ceiling").

    The stride-2 split runs on the TRAILING axis, so the same fold
    serves the 1-D single-chip accumulator and PartitionedTally's 2-D
    per-chip slabs [n_parts, max_local*n_groups*2] (elementwise per
    chip — sharding preserved, no collective)."""
    even = flux[..., 0::2]
    delta = even - prev_even
    return flux.at[..., 1::2].add(delta * delta), even


@jax.jit
def reaction_rate(flux, class_id, sigma):
    """Track-length reaction-rate tally derived from the flux accumulator.

    The track-length estimator of a reaction rate is Σᵢ wᵢ·lᵢ·σ(eᵢ,gᵢ) =
    σ(e,g)·Σᵢ wᵢ·lᵢ, because the response σ depends only on the element's
    material region and the energy group — so every response tally is a
    cheap post-hoc product of the single in-loop flux accumulator instead
    of an extra in-loop scatter (the reference would need a second atomic
    accumulator per response; the multi-tally of BASELINE.md config 5).

    Args:
      flux: [ntet, n_groups, 2] raw accumulator (Σ w·l, Σ (w·l)²).
      class_id: [ntet] material region per element.
      sigma: [n_regions, n_groups] response coefficient (e.g. macroscopic
        reaction cross-section) per region and group. Region ids outside
        [0, n_regions) contribute 0.

    Returns [ntet, n_groups, 2]: (Σ w·l·σ, Σ (w·l)²·σ²).
    """
    return _reaction_rate_impl(jnp, flux, class_id, sigma)


def _reaction_rate_impl(xp, flux, class_id, sigma):
    n_regions = sigma.shape[0]
    safe = xp.clip(class_id, 0, n_regions - 1)
    s = sigma[safe]  # [ntet, n_groups]
    valid = (class_id >= 0) & (class_id < n_regions)
    s = xp.where(valid[:, None], s, 0.0).astype(flux.dtype)
    return xp.stack(
        [flux[..., 0] * s, flux[..., 1] * s * s], axis=-1
    )


def reaction_rate_host(flux, class_id, sigma):
    """reaction_rate on HOST numpy arrays — identical math, no device
    round-trip (same padded-tile-layout rationale as normalize_flux_host)."""
    return _reaction_rate_impl(
        np, np.asarray(flux), np.asarray(class_id), np.asarray(sigma)
    )
