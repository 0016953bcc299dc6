"""Pallas TPU kernel: batched banded forward/backward substitution.

The SURVEY (S7 "hard parts") flags the banded solve as the make-or-break TPU
kernel: the reference's rayon lane-parallel Thomas sweeps
(/root/reference/src/solver/fdma.rs:177-191) have no free parallel axis on a
TPU core except the 128-wide vector lanes.  This kernel keeps the transverse
lanes on the VPU lane dimension and marches the banded LU recurrence over
rows in VMEM:

    forward:   y_i = b_i - sum_{d=1..p} L[i, i-d] * y_{i-d}
    backward:  x_i = (y_i - sum_{d=1..q} U[i, i+d] * x_{i+d}) / U[i, i]

**Measured role** (bench_banded_paths, last recorded 2026-07 on a v5e:
1379 us Pallas vs 1098 us dense inverse at the ADI shapes): the f32 model
path solves these systems faster through the precomputed dense-inverse
GEMM (ops/banded.DenseSolver) — the MXU beats a sequential n-step VMEM
recurrence despite doing O(n/(p+q)) times more flops.  The
Pallas path wins where matmuls are weak: emulated f64, and very large n
where the O(n^2) dense-inverse memory becomes the constraint.  Solver
selection (solver.default_method) stays measurement-driven; this kernel is
the validated alternative, exact to the banded scan path on both backends
(tests/test_pallas_banded.py runs it in interpreter mode on CPU; on a TPU it
compiles natively or construction raises ``PallasCompileRefused``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .pallas_common import LANE, require_native_compile, resolve_interpret


def _kernel(low_ref, upp_ref, b_ref, o_ref, *, p: int, q: int, n: int):
    # factor refs live in SMEM — the recurrence coefficients are true
    # scalars with a dynamically-indexed row, which VMEM vector loads
    # cannot express
    from jax.experimental import pallas as pl

    # forward substitution into o_ref.  Out-of-range neighbor reads are
    # clamped and masked with a select (not a multiply: the clamped row is
    # uninitialized memory, and 0 * NaN would poison the result)
    def fwd(i, carry):
        acc = b_ref[pl.ds(i, 1), :]
        for d in range(1, p + 1):
            prev = o_ref[pl.ds(jnp.maximum(i - d, 0), 1), :]
            coef = (low_ref[d - 1, i]).astype(acc.dtype)
            acc = acc - jnp.where(i >= d, coef * prev, 0.0)
        o_ref[pl.ds(i, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, n, fwd, 0)

    # backward substitution in place
    def bwd(k, carry):
        i = n - 1 - k
        acc = o_ref[pl.ds(i, 1), :]
        for d in range(1, q + 1):
            nxt = o_ref[pl.ds(jnp.minimum(i + d, n - 1), 1), :]
            coef = (upp_ref[d, i]).astype(acc.dtype)
            acc = acc - jnp.where(i + d <= n - 1, coef * nxt, 0.0)
        o_ref[pl.ds(i, 1), :] = acc / upp_ref[0, i]
        return carry

    jax.lax.fori_loop(0, n, bwd, 0)


@functools.partial(jax.jit, static_argnames=("p", "q", "interpret"))
def banded_solve_pallas(lower, upper, b, p: int, q: int, interpret: bool = False):
    """Solve the banded LU system along axis 0 of ``b`` (n, lanes).

    ``lower`` (p, n) / ``upper`` (q+1, n) are the factors of
    ops.banded.banded_lu_factor (single factor set, broadcast over lanes).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, lanes = b.shape
    pad = (-lanes) % LANE
    bb = jnp.pad(b, ((0, 0), (0, pad))) if pad else b
    grid = (bb.shape[1] // LANE,)
    out = pl.pallas_call(
        functools.partial(_kernel, p=p, q=q, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, n), lambda j: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((q + 1, n), lambda j: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((n, LANE), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((n, LANE), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct(bb.shape, bb.dtype),
        interpret=interpret,
        name="banded_solve",
    )(lower, upper, bb)
    return out[:, :lanes] if pad else out


class PallasBandedSolver:
    """Drop-in ``solve(b, axis)`` wrapper around the Pallas kernel (single
    factor set; the ADI-solver use case)."""

    def __init__(self, dense: np.ndarray, p: int, q: int, dtype=None,
                 interpret: bool | None = None):
        from .banded import banded_lu_factor

        if np.asarray(dense).ndim != 2:
            raise ValueError("PallasBandedSolver takes a single (n, n) matrix")
        lower, upper = banded_lu_factor(dense, p, q)
        dt = dtype or jnp.zeros(0).dtype
        self.p, self.q = p, q
        self.n = dense.shape[-1]
        self.lower = jnp.asarray(lower, dtype=dt)
        self.upper = jnp.asarray(upper, dtype=dt)
        self.interpret = resolve_interpret(interpret)
        if not self.interpret:
            # one lane tile is the whole kernel body (the grid only repeats
            # it), so a (n, LANE) probe decides for every lane count
            require_native_compile(
                'solver method="pallas"',
                "banded_solve",
                functools.partial(banded_solve_pallas, p=p, q=q),
                self.lower,
                self.upper,
                jax.ShapeDtypeStruct((self.n, LANE), self.lower.dtype),
            )

    def solve(self, b, axis: int):
        moved = jnp.moveaxis(b, axis, 0)
        shape = moved.shape
        flat = moved.reshape(shape[0], -1)
        out = banded_solve_pallas(
            self.lower, self.upper, flat, self.p, self.q, interpret=self.interpret
        )
        return jnp.moveaxis(out.reshape(shape), 0, axis)


def bench_banded_paths(n: int = 1023, lanes: int = 1025, repeats: int = 50):
    """Microbenchmark: Pallas recurrence vs dense-inverse GEMM vs lax.scan
    on this backend at the ADI solver's real shapes.  Returns seconds per
    solve for each path — the measurement behind solver.default_method."""
    import time

    from .banded import BandedSolver, DenseSolver

    rng = np.random.default_rng(0)
    p, q = 2, 4
    dense = np.eye(n) * 4.0
    for d, off in ((-2, 0.5), (2, 0.7), (4, 0.3)):
        dense += np.diag(np.full(n - abs(d), off), k=d)
    b = jnp.asarray(rng.standard_normal((n, lanes)), dtype=jnp.zeros(0).dtype)

    solvers = {
        "pallas": PallasBandedSolver(dense, p, q),
        "dense_gemm": DenseSolver(dense),
        "banded_scan": BandedSolver(dense, p, q),
    }
    results = {}
    for name, s in solvers.items():
        out = s.solve(b, 0)
        np.asarray(out[:1, :1])  # warm + sync
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = s.solve(b, 0)
        np.asarray(out[:1, :1])
        results[name] = (time.perf_counter() - t0) / repeats
    return results
