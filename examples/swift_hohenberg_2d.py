"""2-D Swift–Hohenberg pattern formation: du/dt = [r - (lap+1)^2] u - u^3.

TPU rebuild of the reference's user-level "bring your own PDE" demo
(/root/reference/examples/swift_hohenberg_2d.rs: 512^2, length=20, r=0.35,
dt=0.02, integrate to t=1000 saving every 10; --nx 2048 runs it at
2048^2).  The IMEX step is diagonal in Fourier space;
on the TPU chip the transforms run as real MXU matmuls over the split Re/Im
representation.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from rustpde_mpi_tpu import SwiftHohenberg2D, integrate


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small fast config")
    ap.add_argument("--nx", type=int, default=None)
    ap.add_argument("--r", type=float, default=0.35)
    ap.add_argument("--dt", type=float, default=0.02)
    ap.add_argument("--length", type=float, default=20.0)
    ap.add_argument("--max-time", type=float, default=None)
    ap.add_argument("--save", type=float, default=None)
    args = ap.parse_args()

    if args.quick:
        nx, max_time, save = 64, 20.0, 10.0
    else:
        nx, max_time, save = 512, 1000.0, 10.0
    if args.nx is not None:
        nx = args.nx
    if args.max_time is not None:
        max_time = args.max_time
    if args.save is not None:
        save = args.save

    pde = SwiftHohenberg2D(nx, nx, args.r, args.dt, args.length)
    print(f"SwiftHohenberg2D {nx}x{nx}, r={args.r}, dt={args.dt}, length={args.length}")
    pde.callback()
    t0 = time.perf_counter()
    integrate(pde, max_time, save)
    wall = time.perf_counter() - t0
    steps = round(pde.get_time() / pde.get_dt())
    print(
        f"done: t={pde.get_time():.2f} ({steps} steps) in {wall:.1f}s "
        f"({steps / wall:.1f} steps/s), pattern energy={pde.pattern_energy():.4e}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
