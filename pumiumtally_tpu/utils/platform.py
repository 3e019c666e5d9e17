"""Where the program keeps what it compiles, and which device it runs on.

The entry points (chip_smoke.py, bench.py, scripts/serve.py) call
``use_compile_cache()`` once, right after importing JAX. The cache and
the AOT program bank live at fixed paths: a path is part of the cache
key, so a directory that moves from run to run never hits.
"""
from __future__ import annotations

import os

#: The checkout root (this file is ``<root>/pumiumtally_tpu/utils/``).
CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
#: Default AOT program-bank root of serve.py / bench.py.
DEFAULT_BANK_DIR = os.path.join(CHECKOUT, ".pumi_bank")


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory and
    return it. ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads
    it itself and nothing is set here. Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (gitignored)."""
    import jax

    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_tpu():
    """The device list, or ``SystemExit`` naming the platform JAX found
    when it is not a TPU. A measurement that finds no chip fails; it
    never falls back to the CPU."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"no TPU: JAX found platform {platform!r} "
            f"({devices[0].device_kind}); this entry point measures the "
            "chip and does not run elsewhere"
        )
    return devices
