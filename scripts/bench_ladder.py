"""The BASELINE.md benchmark ladder (configs 1-4).

One JSON line per config on stdout:

  1. 10k-tet unit cube, single-group tally, 1 chip — correctness-scale.
  2. ~1M-tet mesh, 8 groups, 1 chip — single-chip kernel throughput
     (bench.py's configuration).
  3. ~1M-tet mesh partitioned across 8 devices with ghost halos, cross-chip
     particle migration, and a final tally reduce — collective path. Runs on
     the real chips when >=8 are present, otherwise re-executes itself on a
     virtual 8-device CPU mesh (functional validation; the absolute number
     is not TPU-comparable and is flagged "virtual").
  4. Multi-group (64 energy bins) on the 1M-tet mesh — scatter/atomic
     contention stress (the reference's per-element atomics analog).

Config 5 (full-core ~100M tets on a v5p-64 pod) needs hardware this
environment does not have; its code path is config 3's at larger ntet.

Usage: python scripts/bench_ladder.py [--configs 1,2,3,4]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def run_single_chip(name, cells, n_particles, n_groups, steps=5):
    import bench

    r = bench.run(
        cells=cells,
        n_particles=n_particles,
        steps=steps,
        n_groups=n_groups,
    )
    _emit(
        {
            "config": name,
            "metric": r["metric"],
            "value": r["value"],
            "unit": r["unit"],
            "detail": r["detail"],
        }
    )


def run_partitioned(n_devices=8, cells=32, n_particles=65536, steps=3):
    import jax

    virtual = os.environ.get("PUMI_LADDER_VIRTUAL") == "1"
    if virtual:
        # Functional validation scale: the virtual CPU mesh measures
        # nothing TPU-comparable, so keep compile time in check. Scale is
        # overridable for the large partitioned dryruns (BENCH task 2).
        cells = int(os.environ.get("PUMI_LADDER_CELLS", "12"))
        n_particles = int(os.environ.get("PUMI_LADDER_PARTICLES", "8192"))
        steps = int(os.environ.get("PUMI_LADDER_STEPS", "2"))

    if len(jax.devices()) < n_devices:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_devices}"
        )
        env["PUMI_LADDER_VIRTUAL"] = "1"
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--configs", "3"],
            env=env,
            capture_output=True,
            text=True,
        )
        sys.stderr.write(out.stderr[-2000:])
        for line in out.stdout.splitlines():
            if line.startswith("{"):
                print(line, flush=True)
        if out.returncode != 0:
            raise RuntimeError("virtual-mesh config 3 failed")
        return

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pumiumtally_tpu import build_box
    from pumiumtally_tpu.ops.walk_partitioned import (
        distribute_particles,
        make_partitioned_step,
    )
    from pumiumtally_tpu.parallel.mesh_partition import (
        assemble_global_flux,
        partition_mesh,
    )
    from pumiumtally_tpu.parallel.particle_sharding import make_device_mesh

    dtype = jnp.float32
    n_groups = 8
    mesh = build_box(1.0, 1.0, 1.0, cells, cells, cells, dtype=dtype)
    print(
        f"[ladder-3] mesh {mesh.ntet} tets, {n_devices} devices, "
        f"{n_particles} particles (virtual={virtual})",
        file=sys.stderr, flush=True,
    )
    # 2-layer buffered-picparts halo: measured at 1M tets it cuts the
    # migration rounds 27 -> 3 (cut ping-pong; BENCHMARKS.md round-4
    # section) at +9% table memory, exact results.
    part = partition_mesh(mesh, n_devices, halo_layers=2)
    dmesh = make_device_mesh(n_devices)
    # unroll/compact_after are TPU dispatch-amortization knobs; on the
    # virtual CPU mesh they only add wasted body evaluations (measured
    # 184k vs 283k seg/s), so the ladder leaves them off.
    step = make_partitioned_step(
        dmesh, part, n_groups=n_groups, max_crossings=mesh.ntet + 64,
        tolerance=1e-6,
        # robust=True since round 4: the recovery machinery measured FREE
        # on TPU (wave-1 A/B, 7.266 vs 7.272 Mseg/s) and the headline
        # bench now runs the library-default configuration too.
        robust=True,
    )

    rng = np.random.default_rng(0)
    elem = rng.integers(0, mesh.ntet, n_particles).astype(np.int32)
    origin = np.asarray(mesh.centroids())[elem]

    def place(dest):
        return distribute_particles(
            part, dmesh, elem,
            dict(
                origin=origin.astype(np.float32),
                dest=dest.astype(np.float32),
                weight=np.ones(n_particles, np.float32),
                group=rng.integers(0, n_groups, n_particles).astype(np.int32),
                material_id=np.full(n_particles, -1, np.int32),
            ),
        )

    # Flat per-chip slabs — the TPU production layout (3-D slabs pad
    # their minor dim 2 → 128 under the (8,128) tile; core.tally.make_flux).
    flux = jax.device_put(
        jnp.zeros((n_devices, part.max_local * n_groups * 2), dtype),
        NamedSharding(dmesh, P("p")),
    )

    def one(dest, flux):
        placed = place(dest)
        return step(
            placed["origin"], placed["dest"], placed["elem"],
            jnp.zeros_like(placed["valid"]), placed["material_id"],
            placed["weight"], placed["group"], placed["particle_id"],
            placed["valid"], flux,
        )

    def new_dest():
        d = origin + rng.normal(0, 0.15, (n_particles, 3))
        return np.clip(d, 0.01, 0.99)

    t0 = time.perf_counter()
    res = one(new_dest(), flux)
    jax.block_until_ready(res.flux)
    compile_s = time.perf_counter() - t0
    print(f"[ladder-3] compiled in {compile_s:.0f}s", file=sys.stderr,
          flush=True)

    total = 0
    t0 = time.perf_counter()
    for _ in range(steps):
        res = one(new_dest(), res.flux)
        total += int(np.asarray(res.n_segments).sum())
    t1 = time.perf_counter()
    # Tally reduce: assemble the global flux from per-chip partitions (the
    # MPI tally-reduce analog).
    tr0 = time.perf_counter()
    flux_np = assemble_global_flux(
        part,
        np.asarray(res.flux).reshape(
            n_devices, part.max_local, n_groups, 2
        ),
    )
    tr1 = time.perf_counter()
    nbytes = flux_np.nbytes
    _emit(
        {
            "config": "3_partitioned_8dev" + ("_virtual" if virtual else ""),
            "metric": "particle_segments_per_sec",
            "value": round(total / (t1 - t0), 1),
            "unit": "segments/s",
            "detail": {
                "n_devices": n_devices,
                "ntet": mesh.ntet,
                "n_particles": n_particles,
                "halo_layers": part.halo_layers,
                "steps": steps,
                "compile_s": round(compile_s, 1),
                "tally_reduce_gbps": round(nbytes / (tr1 - tr0) / 1e9, 3),
                "virtual_cpu_mesh": virtual,
            },
        }
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="1,2,3,4")
    args = ap.parse_args()
    configs = {c.strip() for c in args.configs.split(",")}

    if os.environ.get("PUMI_LADDER_VIRTUAL") == "1":
        # The baked TPU plugin overrides the JAX_PLATFORMS env var; only
        # the config update reliably selects the virtual CPU mesh.
        import jax

        jax.config.update("jax_platforms", "cpu")

    if "1" in configs:
        run_single_chip("1_correctness_10k", cells=12, n_particles=65536,
                        n_groups=1)
    if "2" in configs:
        run_single_chip("2_throughput_1m", cells=55, n_particles=1048576,
                        n_groups=8)
    if "3" in configs:
        run_partitioned()
    if "4" in configs:
        run_single_chip("4_multigroup_64", cells=55, n_particles=1048576,
                        n_groups=64)


if __name__ == "__main__":
    main()
