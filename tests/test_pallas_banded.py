"""Pallas banded-substitution kernel: exactness vs the scan path.

Runs in Pallas interpreter mode on the CPU CI mesh; on a real TPU the same
kernel compiles natively (verified on-chip, 2026-07: max diff 0.0 vs the
scan path; PR 21's compile table is in CHANGES.md)."""

import numpy as np
import pytest

import jax.numpy as jnp

from rustpde_mpi_tpu.ops.banded import BandedSolver, banded_lu_factor
from rustpde_mpi_tpu.ops.pallas_banded import (
    PallasBandedSolver,
    banded_solve_pallas,
)


def _system(n, p=2, q=4, seed=0):
    rng = np.random.default_rng(seed)
    dense = np.eye(n) * 4.0
    for d in range(1, p + 1):
        dense += np.diag(rng.uniform(0.2, 0.6, n - d), k=-d)
    for d in range(1, q + 1):
        dense += np.diag(rng.uniform(0.2, 0.6, n - d), k=d)
    return dense


@pytest.mark.parametrize("n,lanes", [(16, 8), (33, 130), (64, 128)])
def test_pallas_matches_scan(n, lanes):
    p, q = 2, 4
    dense = _system(n, p, q)
    rng = np.random.default_rng(1)
    b = jnp.asarray(rng.standard_normal((n, lanes)))
    ref = BandedSolver(dense, p, q).solve(b, 0)
    out = PallasBandedSolver(dense, p, q, interpret=True).solve(b, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-10)


def test_pallas_reconstructs_solution():
    """A x = b round-trip (the reference's kernel test pattern,
    /root/reference/src/solver/fdma.rs:277-337)."""
    n, p, q = 24, 2, 4
    dense = _system(n, p, q, seed=3)
    rng = np.random.default_rng(4)
    b = rng.standard_normal((n, 4))
    lower, upper = banded_lu_factor(dense, p, q)
    x = banded_solve_pallas(
        jnp.asarray(lower), jnp.asarray(upper), jnp.asarray(b), p, q,
        interpret=True,
    )
    np.testing.assert_allclose(dense @ np.asarray(x), b, atol=1e-9)


def test_pallas_solver_axis1_and_batch():
    """solve() moves an arbitrary axis into the lane position."""
    n, p, q = 16, 2, 4
    dense = _system(n, p, q, seed=5)
    rng = np.random.default_rng(6)
    b = jnp.asarray(rng.standard_normal((7, n)))
    ref = BandedSolver(dense, p, q).solve(b, 1)
    out = PallasBandedSolver(dense, p, q, interpret=True).solve(b, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-10)


def test_pallas_via_axis_solver_dispatch():
    """method="pallas" is selectable through the solver layer."""
    from rustpde_mpi_tpu import Space2, cheb_dirichlet
    from rustpde_mpi_tpu.solver import HholtzAdi

    space = Space2(cheb_dirichlet(24), cheb_dirichlet(24))
    # interpret-mode pallas on CPU: patch the auto-detection via solver attr
    adi_pallas = HholtzAdi(space, (1e-3, 1e-3), method="pallas")
    for ax in adi_pallas.solvers:
        if hasattr(ax.solver, "interpret"):
            ax.solver.interpret = True
    adi_ref = HholtzAdi(space, (1e-3, 1e-3), method="banded")
    rng = np.random.default_rng(7)
    f = jnp.asarray(rng.standard_normal((24, 24)))
    rhs = space.to_ortho(space.forward(f))
    np.testing.assert_allclose(
        np.asarray(adi_pallas.solve(rhs)), np.asarray(adi_ref.solve(rhs)), atol=1e-9
    )
