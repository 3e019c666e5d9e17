"""Test harness setup: force the CPU platform with 8 virtual devices (the
multi-chip sharding tests run on a fake mesh, SURVEY.md §5 distributed notes)
and enable float64 so the reference's 1e-8 analytic oracles port literally
(test_pumi_tally_impl_methods.cpp:22)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    )

import jax

jax.config.update("jax_enable_x64", True)

# Persistent compile cache: the suite's cost is dominated by XLA compiles
# of the walk programs (one per static-config signature). Caching them on
# disk makes every re-run after the first (the common case: the driver's
# per-round gate, local red-green loops) skip the compiles entirely.
# Threshold 0 caches even sub-second entries — hit rate matters more than
# per-entry size here, and the cache lives in gitignored scratch.
jax.config.update(
    "jax_compilation_cache_dir",
    os.path.join(os.path.dirname(__file__), os.pardir, ".jax_cache_tests"),
)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
