"""Standalone Helmholtz solves with analytic verification (assert-style).

Port of the reference's MPI solver-check examples
(/root/reference/examples/hholtz_mpi.rs: 257^2 cheb_dirichlet^2, alpha=1e-5,
f = cos(pi/2 x) cos(pi/2 y) -> u = f / (1 + 2 alpha (pi/2)^2);
hholtz_periodic_mpi.rs: the Fourier x Chebyshev variant).  ``--mesh`` runs
the same solves GSPMD-sharded over all visible devices — the reference runs
these under ``cargo mpirun -np 2`` and panics on mismatch; here a failed
allclose exits nonzero.
"""

import argparse
import contextlib
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from rustpde_mpi_tpu import Space2, cheb_dirichlet, fourier_r2c
from rustpde_mpi_tpu.solver import HholtzAdi

ALPHA = 1e-5


def check(space, note: str, f, lam: float, mesh=None, tol: float = 1e-6) -> None:
    """Solve (I - ALPHA*lap) u = f where lap f = -lam * f, so u = f/(1+ALPHA*lam)."""
    import jax.numpy as jnp

    from rustpde_mpi_tpu.parallel.mesh import use_mesh

    scope = use_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with scope:
        solver = HholtzAdi(space, (ALPHA, ALPHA))
        expected = f / (1.0 + ALPHA * lam)
        rhs = space.to_ortho(space.forward(jnp.asarray(f)))
        out = np.asarray(space.backward(solver.solve(rhs)))
    err = float(np.abs(out - expected).max())
    status = "OK" if err < tol else "FAILED"
    print(f"  {note}: max |err| = {err:.3e}  {status}")
    if err >= tol:
        raise SystemExit(1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=257)
    ap.add_argument("--mesh", action="store_true", help="shard over all devices")
    args = ap.parse_args()

    mesh = None
    if args.mesh:
        import jax
        from jax.sharding import Mesh

        from rustpde_mpi_tpu.parallel.mesh import AXIS

        mesh = Mesh(np.array(jax.devices()), (AXIS,))
        print(f"pencil mesh over {len(jax.devices())} devices")

    n = args.n
    hn = np.pi / 2.0
    print(f"Helmholtz ADI checks at {n}x{n} (alpha={ALPHA:g}):")

    # confined: f = cos(pi/2 x) cos(pi/2 y), lap f = -2 (pi/2)^2 f
    sp = Space2(cheb_dirichlet(n), cheb_dirichlet(n))
    xs, ys = (b.points for b in sp.bases)
    f = np.cos(hn * xs)[:, None] * np.cos(hn * ys)[None, :]
    check(sp, "cheb x cheb   ", f, 2.0 * hn * hn, mesh)

    # periodic x: f = cos(2x) cos(pi/2 y), lap f = -(4 + (pi/2)^2) f
    sp = Space2(fourier_r2c(n - 1), cheb_dirichlet(n))
    xs, ys = (b.points for b in sp.bases)
    f = np.cos(2.0 * xs)[:, None] * np.cos(hn * ys)[None, :]
    check(sp, "fourier x cheb", f, 4.0 + hn * hn, mesh)
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
