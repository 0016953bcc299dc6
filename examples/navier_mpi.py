"""Mesh-sharded Navier–Stokes runs — the MPI examples' counterpart.

One script covers /root/reference/examples/{navier_mpi, navier_periodic_mpi,
navier_periodic_hc_mpi}.rs: the same ``Navier2D`` model pencil-sharded over a
``jax.sharding.Mesh`` of all visible devices (physical y-pencils / spectral
x-pencils with XLA all-to-all pencil flips — the GSPMD form of the
reference's Decomp2d transposes; a periodic model rests the other way round,
``Space2.rest``).  On one real chip this degenerates to a
1-device mesh; run under a virtual CPU mesh to exercise the collectives:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/navier_mpi.py --quick
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--periodic", action="store_true")
    ap.add_argument("--bc", default="rbc", choices=["rbc", "hc"])
    ap.add_argument("--nx", type=int, default=None)
    ap.add_argument("--ny", type=int, default=None)
    ap.add_argument("--ra", type=float, default=1e5)
    ap.add_argument("--dt", type=float, default=0.01)
    ap.add_argument("--max-time", type=float, default=None)
    args = ap.parse_args()

    import jax
    from jax.sharding import Mesh

    from rustpde_mpi_tpu import Navier2D, integrate
    from rustpde_mpi_tpu.parallel.mesh import AXIS

    devices = jax.devices()
    mesh = Mesh(np.array(devices), (AXIS,))
    print(f"pencil mesh over {len(devices)} {devices[0].platform} device(s)")

    if args.quick:
        nx, ny, max_time, save = 33, 33, 1.0, 0.5
    else:
        nx, ny, max_time, save = 128, 129, 10.0, 5.0
    nx = args.nx or nx
    ny = args.ny or ny
    max_time = args.max_time or max_time

    ctor = Navier2D.new_periodic if args.periodic else Navier2D.new_confined
    navier = ctor(nx, ny, args.ra, 1.0, args.dt, 1.0, args.bc, mesh=mesh)
    navier.set_velocity(0.2, 1.0, 1.0)
    navier.set_temperature(0.2, 1.0, 1.0)
    t0 = time.perf_counter()
    integrate(navier, max_time, save)
    wall = time.perf_counter() - t0
    steps = round(navier.get_time() / navier.get_dt())
    nu, nuv, re, div = navier.get_observables()
    ok = nu == nu and div == div
    print(
        f"done: {steps} steps in {wall:.1f}s ({steps / wall:.1f} steps/s), "
        f"Nu={nu:.4f} Re={re:.3f} |div|={div:.2e}  {'OK' if ok else 'FAILED'}"
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
