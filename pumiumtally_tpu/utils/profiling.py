"""Profiler integration (jax.profiler / xprof).

The reference has only coarse phase timers and defers per-kernel profiling
to external tools (SURVEY.md §5: nsys / Kokkos-tools). On TPU the native
story is jax.profiler: ``profile_trace`` captures an xprof trace viewable
in TensorBoard/xprof (device kernels, HLO names, host dispatch), and
``annotate`` scopes host-side phases so facade calls show up as named
spans alongside the device work.

Usage::

    from pumiumtally_tpu.utils.profiling import profile_trace, annotate

    with profile_trace("/tmp/tally_trace"):
        with annotate("init"):
            tally.initialize_particle_location(pos)
        with annotate("moves"):
            for _ in range(100):
                tally.move_to_next_location(...)
"""
from __future__ import annotations

import contextlib
import os

import jax


@contextlib.contextmanager
def profile_trace(logdir: str):
    """Capture a jax.profiler trace for the duration of the block."""
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(
        logdir,
        create_perfetto_link=False,
        create_perfetto_trace=False,
    )
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named host span that brackets device dispatches (xprof
    TraceAnnotation; shows up in the trace viewer's host track)."""
    return jax.profiler.TraceAnnotation(name)


class CallSpans:
    """A public call's span, with its phases as child spans opened one
    after another on the calling thread (``open`` closes the phase
    still open), so a phase may end inside a block the next one
    outlives. Phases named ``p`` record as ``<facade>.<p>``."""

    def __init__(self, name: str):
        self._facade = name.split(".", 1)[0]
        self._call = annotate(name)
        self._phase = None

    def open(self, phase: str) -> None:
        self.close()
        self._phase = annotate(f"{self._facade}.{phase}")
        self._phase.__enter__()

    def close(self) -> None:
        if self._phase is not None:
            self._phase.__exit__(None, None, None)
            self._phase = None

    def __enter__(self):
        self._call.__enter__()
        return self

    def __exit__(self, *exc):
        self.close()
        return self._call.__exit__(*exc)


def device_memory_stats() -> dict:
    """Per-device memory stats where the backend reports them (bytes in
    use / peak / limit) — the observability hook for HBM-capacity work
    (BASELINE.md config 5)."""
    out = {}
    for d in jax.local_devices():
        stats = getattr(d, "memory_stats", None)
        if callable(stats):
            try:
                s = stats() or {}
            except Exception:
                continue
            out[str(d)] = {
                k: s[k]
                for k in (
                    "bytes_in_use",
                    "peak_bytes_in_use",
                    "bytes_limit",
                )
                if k in s
            }
    return out
