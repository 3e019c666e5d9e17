"""End-to-end example: flux tally on a two-region 'pincell'.

A unit-box mesh whose elements are classified by centroid radius into a
fuel pin (region 1, strong absorber) and moderator (region 0): the shape
of BASELINE.md config 2 at laptop scale. Synthetic event-based transport
(models/transport.py) stands in for OpenMC and drives the facade exactly
like the real host: init → move per advance event → write.

Run:  python examples/pincell_flux.py [out.vtu]
(CPU-friendly; set JAX_PLATFORMS=cpu to pin the platform.)
"""
from __future__ import annotations

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import numpy as np

from pumiumtally_tpu import Material, PumiTally, SyntheticTransport, TallyConfig
from pumiumtally_tpu.mesh.box import build_box_arrays
from pumiumtally_tpu.mesh.core import TetMesh


def pincell_mesh(cells: int = 8, pin_radius: float = 0.25) -> TetMesh:
    coords, tets = build_box_arrays(1.0, 1.0, 1.0, cells, cells, cells)
    centroids = coords[tets].mean(axis=1)
    r = np.linalg.norm(centroids[:, :2] - 0.5, axis=1)
    class_id = (r < pin_radius).astype(np.int32)  # 1 = fuel pin
    return TetMesh.from_numpy(coords, tets, class_id)


def main() -> None:
    out = sys.argv[1] if len(sys.argv) > 1 else "out/pincell_flux.vtu"
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    mesh = pincell_mesh()
    n_fuel = int(np.asarray(mesh.class_id).sum())
    print(f"mesh: {mesh.ntet} tets ({n_fuel} fuel, {mesh.ntet - n_fuel} moderator)")

    tally = PumiTally(
        mesh, num_particles=512,
        config=TallyConfig(n_groups=2, tolerance=1e-6, measure_time=True),
    )
    driver = SyntheticTransport(
        tally,
        materials={
            0: Material(sigma_t=2.0, absorption=0.15),   # moderator
            1: Material(sigma_t=12.0, absorption=0.65),  # fuel
        },
        seed=0,
    )
    stats = driver.run(batches=4, output=out)
    print(f"transport: {stats}")

    flux = tally.normalized_flux()
    cid = np.asarray(mesh.class_id)
    for rid, name in ((1, "fuel"), (0, "moderator")):
        mean = flux[cid == rid, :, 0].mean(axis=0)
        print(f"{name:9s} mean flux per group: {np.array2string(mean, precision=4)}")
    # Absorber depresses the in-pin flux.
    assert flux[cid == 1, :, 0].mean() < flux[cid == 0, :, 0].mean()

    rates = tally.reaction_rate(
        np.array([[0.3, 0.3], [7.8, 7.8]])  # Σ_abs per region/group
    )
    print(f"absorption rate: fuel {rates[cid == 1, :, 0].sum():.4f}, "
          f"moderator {rates[cid == 0, :, 0].sum():.4f}")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
