"""Composite Helmholtz / Poisson solvers over Galerkin spectral spaces.

TPU rebuild of the reference solver layer (/root/reference/src/solver/):

* :class:`HholtzAdi` — ``(I - c*D2) u = f`` by alternating-direction-implicit
  1-D solves per axis (same O(dt*c) splitting as the reference,
  /root/reference/src/solver/hholtz_adi.rs:12-16).
* :class:`TensorSolver` — the `FdmaTensor` analog: eigen-diagonalize axis 0
  through the B2-preconditioned pencil ``(pinv S)^-1 (peye S)``, leaving a
  banded family along axis 1
  (/root/reference/src/solver/fdma_tensor.rs:36-71 documents the math).
  One deliberate departure from the reference: the per-eigenvalue banded
  factorizations are computed ONCE at build time (host numpy) instead of per
  solve call (poisson.rs:226-228 re-sweeps every step).
* :class:`FastDiag` — both axes eigen-diagonalized through the same pencils;
  solves the *identical* discrete system as :class:`TensorSolver` (tested),
  but as pure GEMMs + one elementwise divide — the MXU-native path.
* :class:`Poisson` / :class:`Hholtz` — pressure Poisson (alpha=0, singular
  mode regularized) and exact Helmholtz (alpha=1).

The discretization is reference-exact: the truncated quasi-inverse
(ops/chebyshev.quasi_inverse_b2) reproduces the reference's embedded pypde
golden solutions (tests/test_golden.py) and makes the pencil spectrum exactly
real for every composite base — the imaginary parts the reference's
utils::eig silently drops (/root/reference/src/solver/utils.rs:84-86) are
structurally zero under this convention.

All device work is GEMMs (MXU) + one batched banded substitution scan.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from . import config
from .bases import Base, BaseKind, Space2  # noqa: F401
from .ops.banded import BandedSolver, DenseSolver, DiagSolver
from .ops.folded import FoldedMatrix
from .telemetry import tracing as _tr

_P, _Q = 2, 4  # lower/upper bandwidth of every preconditioned Chebyshev operator


def ingredients_for_hholtz(space: Space2, axis: int):
    """(mat_a, mat_b, precond) per axis — the contract of
    /root/reference/src/field.rs:195-216:

    Chebyshev axes: precondition with the restricted quasi-inverse so the
    Helmholtz operator ``mat_a - c*mat_b`` becomes banded; Fourier axes are
    already diagonal."""
    base = space.bases[axis]
    if base.kind.is_chebyshev:
        peye = base.laplace_inv_eye()
        pinv = peye @ base.laplace_inv()
        S = base.mass()
        if base.kind == BaseKind.CHEBYSHEV:
            S = S[:, 2:]
        return pinv @ S, peye @ S, pinv
    mass = np.eye(base.m)
    lap = base.laplace()
    return mass, lap, None


def hholtz_axis_solve_matrix(space: Space2, axis: int, ci: float) -> np.ndarray:
    """Dense equivalent of ONE :class:`HholtzAdi` axis factor, in natural
    (split-form for periodic) order: ``A = (mat_a - ci*mat_b)^-1 @ precond``
    — the full 2-D ADI solve is ``A0 @ rhs @ A1^T``.  This is the public
    modal contract the fused step kernels (ops/pallas_step.py) build their
    stage matrices from: the banded recurrence, the precomputed dense
    inverse, and this explicit inverse factor all solve the identical 1-D
    system (machine-precision agreement in f64).

    Periodic axes return the diagonal ``1/(1 + ci*k^2)`` in the split Re/Im
    convention over ``2*(n//2+1)`` rows (each eigenvalue twice — complex r2c
    bases get the duplication here, split bases already carry it), matching
    ``Base.axis_operator``'s split-matrix form."""
    base = space.bases[axis]
    mat_a, mat_b, precond = ingredients_for_hholtz(space, axis)
    mat = mat_a - ci * mat_b
    if base.kind.is_periodic:
        d = 1.0 / np.diag(mat)
        if not base.kind.is_split:
            d = np.concatenate([d, d])
        return np.diag(d)
    return np.linalg.solve(mat, precond)


def modal_data_split(space: Space2, axis: int, ci: float, sign: float = 1.0):
    """Public :func:`_axis_modal_data` in the split-real convention of the
    fused step kernels: ``(lam, fwd, bwd)`` with periodic-axis eigenvalues
    duplicated over the Re/Im blocks (complex r2c bases carry each
    eigenvalue once; split bases already twice).  ``fwd``/``bwd`` are None
    for periodic axes (already modal); eigenvalues come back in natural
    order — sep-storage callers apply ``parity_perm`` themselves, exactly
    like :class:`FastDiag`."""
    lam, fwd, bwd = _axis_modal_data(space, axis, ci, sign)
    base = space.bases[axis]
    if base.kind.is_periodic and not base.kind.is_split:
        lam = np.concatenate([lam, lam])
    return lam, fwd, bwd


def _checker_shift(m: np.ndarray) -> int | None:
    """Shift s in {0, 1} such that ``m[i, j] == 0`` (exactly) whenever
    ``(i + j + s)`` is odd; None if neither holds.  The pure-Chebyshev solver
    ingredients are products of even-offset banded matrices, so their
    checkerboard zeros are *exact* floating-point zeros — no tolerance."""
    r, c = m.shape
    i = np.arange(r)[:, None]
    j = np.arange(c)[None, :]
    for s in (0, 1):
        if not np.any(m[(i + j + s) % 2 == 1]):
            return s
    return None


def _real_eig_desc(x: np.ndarray):
    """Real eigendecomposition sorted by descending eigenvalue."""
    lam, q = np.linalg.eig(x)
    if np.abs(lam.imag).max(initial=0.0) > 1e-8 * max(np.abs(lam.real).max(), 1.0):
        raise ValueError("tensor-solver eigenvalues are significantly complex")
    lam = lam.real
    q = q.real if np.iscomplexobj(q) else q
    order = np.argsort(lam)[::-1]
    return lam[order], q[:, order]


# bump when the modal decomposition code or the stored (lam, fwd, q)
# semantics change — the disk-cache key hashes only the ingredient matrices
_MODAL_CACHE_VERSION = "v1"


def _axis_modal_data(space: Space2, axis: int, ci: float, sign: float):
    """Modal diagonalization of one axis of the preconditioned operator.

    Returns ``(lam, fwd, bwd)``: ``lam`` scaled by ``sign * ci``; ``fwd``
    maps the axis's *ortho-space* rhs into eigenspace (it folds the B2
    preconditioner in: ``Q^-1 C^-1 pinv``), ``bwd = Q`` maps the eigenspace
    solution back to composite coefficients.  Fourier axes are already modal:
    ``lam = sign*ci*(-k^2)``, no maps.  This is the pencil the reference's
    FdmaTensor diagonalizes (/root/reference/src/solver/fdma_tensor.rs:106-154);
    under the truncated quasi-inverse its spectrum is exactly real.

    On the span that is open (``solver.build``): ``eigs`` counts the axes
    decomposed here, ``eig_cached`` those the host-eig disk cache served."""
    base = space.bases[axis]
    if base.kind.is_periodic:
        return sign * ci * (-(base.wavenumbers**2)), None, None
    mat_c, mat_a, precond = ingredients_for_hholtz(space, axis)
    # host-eig disk cache (SURVEY S7 "cache to disk for big N"): the
    # nonsymmetric parity-block eigendecompositions dominate build time at
    # the flagship sizes (~tens of seconds at 2049); exact f64 npz
    # round-trips, keyed by the INGREDIENT CONTENT (cheap O(n^2) hash of the
    # matrices actually decomposed) plus ci/sign.  The content hash does NOT
    # see this function's code: the _MODAL_CACHE_VERSION salt below must be
    # bumped whenever the decomposition algorithm or the stored (lam, fwd,
    # q) semantics change (ADVICE r4).  Gated to n >= 512: below that the
    # eig costs less than the IO.
    cache_path = None
    if base.n >= 512:
        import hashlib

        h = hashlib.blake2b(digest_size=12)
        for m in (mat_c, mat_a, precond):
            h.update(np.ascontiguousarray(m).tobytes())
        cache_path = os.path.join(
            config.host_cache_dir(),
            f"modal_{_MODAL_CACHE_VERSION}_{base.kind.value}_{base.n}_"
            f"{float(ci):.17g}_{sign:g}_{h.hexdigest()}.npz",
        )
        try:
            with np.load(cache_path) as z:
                modal = z["lam"], z["fwd"], z["q"]
            _tr.count(eigs=1, eig_cached=1)
            return modal
        except Exception:  # missing/corrupt/format-drift: recompute
            pass
    _tr.count(eigs=1)
    if (
        _checker_shift(mat_c) == 0
        and _checker_shift(mat_a) == 0
        and _checker_shift(precond) == 0
    ):
        # Parity-blocked eigendecomposition: the pencil preserves parity, so
        # solve the even and odd subproblems independently and assemble with
        # eigen indices interleaved (evens at even positions).  The modal
        # maps are then checkerboard with *exact* zeros — a full-matrix eig
        # leaves O(1e-7)-relative off-parity noise at n >= 1025, which
        # silently defeated fold detection (and the noise is itself error:
        # the true eigenvectors have definite parity).
        m = mat_c.shape[0]
        n_cols = precond.shape[1]
        lam = np.empty(m)
        q = np.zeros((m, m))
        fwd = np.zeros((m, n_cols))
        for par in (0, 1):
            sl = slice(par, None, 2)
            c_b = mat_c[sl, sl]
            lam_b, q_b = _real_eig_desc(np.linalg.solve(c_b, mat_a[sl, sl]))
            fwd_b = np.linalg.solve(q_b, np.linalg.solve(c_b, precond[sl, sl]))
            lam[sl] = lam_b
            q[sl, sl] = q_b
            fwd[sl, sl] = fwd_b
        return _modal_cache_store(cache_path, sign * ci * lam, fwd, q)
    # non-parity-preserving pencils (mixed Dirichlet-Neumann base): plain
    # descending eigen order, as in the reference (solver/utils.rs:88-95)
    lam, q = _real_eig_desc(np.linalg.solve(mat_c, mat_a))
    fwd = np.linalg.solve(q, np.linalg.solve(mat_c, precond))
    return _modal_cache_store(cache_path, sign * ci * lam, fwd, q)


def _modal_cache_store(path, lam, fwd, q):
    if path is not None:
        config.host_cache_store(path, lambda tmp: np.savez(tmp, lam=lam, fwd=fwd, q=q))
    return lam, fwd, q


class _AxisSolver:
    """1-D solver for one axis: banded/dense/pallas (Chebyshev) or diagonal
    (Fourier).  ``sep``: the axis uses the parity-separated spectral layout —
    the dense inverse handles it natively (block GEMMs, ops/folded.py); the
    sequential banded/Pallas recurrences are wrapped with explicit
    permutations (ops/banded.SepWrapped, the CPU correctness fallback)."""

    def __init__(self, mat: np.ndarray, kind: BaseKind, method: str, sep: bool = False):
        from .ops.banded import SepWrapped

        if kind.is_periodic:
            assert not sep, "sep layout is not defined for Fourier axes"
            self.solver = DiagSolver(np.diag(mat))
        elif method == "dense":
            self.solver = DenseSolver(mat, sep=sep)
        elif method == "pallas":
            from .ops.pallas_banded import PallasBandedSolver

            self.solver = PallasBandedSolver(mat, _P, _Q)
            if sep:
                self.solver = SepWrapped(self.solver, mat.shape[-1])
        else:
            self.solver = BandedSolver(mat, _P, _Q)
            if sep:
                self.solver = SepWrapped(self.solver, mat.shape[-1])

    def solve(self, b, axis: int):
        return self.solver.solve(b, axis)


def default_method() -> str:
    """Execution path for the 1-D axis solves.  Measured on v5e at the
    1025^2 shapes (ops/pallas_banded.bench_banded_paths, 2026-07): the
    precomputed dense-inverse GEMM (~1.10 ms/solve fused) beats both the
    Pallas VMEM recurrence (~1.38 ms) and by 3 orders of magnitude the
    lax.scan substitution — the MXU wins despite O(n/(p+q)) more flops.  The
    same holds in emulated f64 (129^2 ADI: dense 1.6 ms vs scan 2.5 ms;
    Pallas has no Mosaic f64 support).  On CPU the O(n) banded scan wins.
    Override per-solver with ``method="banded"|"dense"|"pallas"``."""
    return "dense" if config.is_tpu_like() else "banded"


class HholtzAdi:
    """ADI Helmholtz: ``(I - c*D2) vhat = A f`` solved axis-by-axis.

    ``method``: "banded" (scan substitution, exact O(n)) or "dense"
    (precomputed inverse GEMMs; fastest on TPU).  Default auto-selects.
    """

    def __init__(self, space: Space2, c, method: str | None = None):
        with _tr.span(
            "solver.build", layer="operators and kernels",
            kind="hholtz_adi", shape=space.shape_physical,
        ):
            self._build(space, c, method)

    def _build(self, space: Space2, c, method) -> None:
        method = method or default_method()
        self.space = space
        self.rest = space.rest  # the pencil its spectral arrays rest in
        sep = getattr(space, "sep", (False, False))
        self.matvec = []
        self.solvers = []
        for axis, ci in enumerate(c):
            mat_a, mat_b, precond = ingredients_for_hholtz(space, axis)
            mat = mat_a - ci * mat_b
            kind = space.base_kind(axis)
            self.solvers.append(_AxisSolver(mat, kind, method, sep=sep[axis]))
            # the B2 precond is checkerboard parity-foldable like every
            # pure-Chebyshev operator (ops/folded.py) -> two half GEMMs
            self.matvec.append(
                FoldedMatrix(
                    precond,
                    lambda m: jnp.asarray(m, dtype=config.real_dtype()),
                    sep_in=sep[axis],
                    sep_out=sep[axis],
                )
                if precond is not None
                else None
            )

    def solve(self, rhs):
        """rhs in ortho space -> solution in composite space.  Extra leading
        dims are batch (identical-operator fields solved in one dispatch).

        Under a parallel mesh the axis solves run on the pencil whose solve
        axis is local (the reference's HholtzAdiMpi transpose pattern,
        /root/reference/src/solver_mpi/hholtz_adi.rs:105-145); the pencil
        flips are sharding constraints, XLA inserts the all-to-alls.  The
        x-factor runs in the space's resting layout: x is local there, or it
        is a diagonal (a Fourier axis) and asks for no layout of its own, so
        such a solve enters, works and returns on the y-pencil."""
        from .parallel.mesh import LOCAL, constrain, flip

        if rhs.ndim < 2:
            raise ValueError(
                f"2-D tensor solver needs rhs.ndim >= 2, got {rhs.ndim} "
                "(a rank-1 rhs would silently solve both axes over the same "
                "axis; batch dims go in front)"
            )
        with jax.named_scope("helmholtz"):
            ax = rhs.ndim - 2
            rest = self.rest
            out = constrain(rhs, rest)
            if self.matvec[0] is not None:
                out = self.matvec[0].apply(out, ax)
            out = flip(out, LOCAL[1], rest)
            if self.matvec[1] is not None:
                out = self.matvec[1].apply(out, ax + 1)
            out = self.solvers[1].solve(out, ax + 1)  # axis-1 recurrence
            out = flip(out, rest, LOCAL[1])
            out = self.solvers[0].solve(out, ax)  # axis-0 recurrence
            return constrain(out, rest)


class TensorSolver:
    """2-D tensor-product solver: ``[(A_x x C_y) + (C_x x A_y) + alpha (C_x x
    C_y)] u = B2 f``; axis 0 diagonalized through the preconditioned pencil
    (or already-diagonal Fourier), axis 1 a batch of banded systems factored
    at build time (the reference re-sweeps per solve,
    /root/reference/src/solver/poisson.rs:226-228).

    ``modal0 = (lam0, fwd0, bwd0)`` from :func:`_axis_modal_data` — ``fwd0``
    maps the axis-0 *ortho-space* rhs into eigenspace (preconditioner folded
    in), so no separate axis-0 matvec is applied."""

    def __init__(
        self, modal0, a1, c1, precond1, alpha: float, fix_singular=False,
        sep=(False, False), *, rest,
    ):
        from .ops.banded import SepWrapped
        from .ops.folded import parity_perm

        self.rest = rest  # the pencil the space's spectral arrays rest in (Space2.rest)

        dt = config.real_dtype()
        lam, fwd0, bwd0 = modal0
        s0 = sep[0] and fwd0 is not None  # Fourier axes are never sep
        to_dev = lambda m: jnp.asarray(m, dtype=dt)  # noqa: E731
        self.fwd = (
            FoldedMatrix(fwd0, to_dev, sep_in=s0, sep_out=s0)
            if fwd0 is not None
            else None
        )
        self.bwd = (
            FoldedMatrix(bwd0, to_dev, sep_in=s0, sep_out=s0)
            if bwd0 is not None
            else None
        )
        if fix_singular and abs(lam[0]) < 1e-10:
            # pure-Neumann problems: nudge the zero mode so the banded
            # factorization exists (/root/reference/src/solver/poisson.rs:84-87)
            lam = lam.copy()
            lam -= 1e-10
        if s0:
            # eigenvalue lanes live on the sep-ordered axis 0
            lam = lam[parity_perm(len(lam))]
        self.lam = lam
        self.alpha = alpha
        self.matvec1 = (
            FoldedMatrix(precond1, to_dev, sep_in=sep[1], sep_out=sep[1])
            if precond1 is not None
            else None
        )
        # (A_y + (lam_i + alpha) C_y) factored for every eigenvalue lane i
        mats = a1[None, :, :] + (lam[:, None, None] + alpha) * c1[None, :, :]
        self.banded = BandedSolver(mats, _P, _Q)
        if sep[1]:
            # the banded recurrence runs in natural axis-1 order
            self.banded = SepWrapped(self.banded, a1.shape[-1])

    def solve(self, rhs):
        """Under a parallel mesh: GEMMs run on the x-pencil (axis 0 local),
        the per-eigenvalue banded solves on the y-pencil where the eigenvalue
        lanes (axis 0) are sharded — the reference's PoissonMpi lam-slicing
        (/root/reference/src/solver_mpi/poisson.rs:139-187).  A Fourier x-axis
        has no GEMM (its modes are the eigenvalue lanes already): the solve
        stays on the y-pencil its space rests in.  Extra leading dims are
        batch (the per-eigenvalue factors broadcast against them)."""
        from .parallel.mesh import LOCAL, constrain, flip

        if rhs.ndim < 2:
            raise ValueError(
                f"2-D tensor solver needs rhs.ndim >= 2, got {rhs.ndim} "
                "(a rank-1 rhs would silently solve both axes over the same "
                "axis; batch dims go in front)"
            )
        with jax.named_scope("tensor_solve"):
            ax = rhs.ndim - 2
            rest = self.rest
            out = constrain(rhs, rest)
            at = rest
            if self.matvec1 is not None:
                out, at = self.matvec1.apply(flip(out, LOCAL[1], rest), ax + 1), LOCAL[1]
            out = flip(out, rest, at)
            if self.fwd is not None:
                out = self.fwd.apply(out, ax)
            out = self.banded.solve(flip(out, LOCAL[1], rest), ax + 1)
            out = flip(out, rest, LOCAL[1])
            if self.bwd is not None:
                out = self.bwd.apply(out, ax)
            return constrain(out, rest)


class FastDiag:
    """Fast-diagonalisation 2-D solver: BOTH axes eigendecomposed through the
    preconditioned pencils, so the device solve is 4 GEMMs + 1 elementwise
    divide — pure MXU work, no sequential recurrence.  This is the TPU-native
    answer to the reference's FdmaTensor (eig axis 0 + per-eigenvalue banded
    sweeps along axis 1, /root/reference/src/solver/fdma_tensor.rs:36-71):
    the *identical* discrete solution (same pencils, tested against
    :class:`TensorSolver`), but the O(n) Thomas recurrence the reference
    parallelises with rayon lanes would serialise a TPU, while matmuls
    saturate it.

    Fourier axes are already modal (diagonal), so their fwd/bwd maps are
    identity and their eigenvalues are -k^2.
    """

    def __init__(
        self, modal0, modal1, alpha: float, fix_singular=False, sep=(False, False),
        *, rest,
    ):
        from .ops.folded import parity_perm

        self.rest = rest  # the pencil the space's spectral arrays rest in (Space2.rest)
        dt = config.real_dtype()
        lams, self.fwd, self.bwd = [], [], []
        to_dev = lambda m: jnp.asarray(m, dtype=dt)  # noqa: E731
        for si, (lam, fwd, bwd) in zip(sep, (modal0, modal1)):
            si = si and fwd is not None  # Fourier axes are never sep
            self.fwd.append(
                FoldedMatrix(fwd, to_dev, sep_in=si, sep_out=si)
                if fwd is not None
                else None
            )
            self.bwd.append(
                FoldedMatrix(bwd, to_dev, sep_in=si, sep_out=si)
                if bwd is not None
                else None
            )
            lams.append(lam[parity_perm(len(lam))] if si else lam)
        if fix_singular and abs(lams[0][0]) < 1e-10:
            # pure-Neumann zero mode: same nudge as the reference
            # (/root/reference/src/solver/poisson.rs:84-87)
            lams[0] = lams[0].copy()
            lams[0] -= 1e-10
        denom = lams[0][:, None] + lams[1][None, :] + alpha
        self.denom = jnp.asarray(denom, dtype=dt)

    def solve(self, rhs):
        """rhs in ortho space -> solution in composite space (extra leading
        dims are batch).  Pencil flips sit between the two contractions;
        where the x-maps are None (a Fourier axis, modal already) there is no
        contraction along x and no flip: all of the work is on the y-pencil
        the space rests in."""
        from .parallel.mesh import LOCAL, constrain, flip

        if rhs.ndim < 2:
            raise ValueError(
                f"2-D tensor solver needs rhs.ndim >= 2, got {rhs.ndim} "
                "(a rank-1 rhs would silently solve both axes over the same "
                "axis; batch dims go in front)"
            )
        with jax.named_scope("fastdiag"):
            ax = rhs.ndim - 2
            rest = self.rest
            out = constrain(rhs, rest)
            if self.fwd[0] is not None:
                out = self.fwd[0].apply(out, ax)
            out = flip(out, LOCAL[1], rest)
            if self.fwd[1] is not None:
                out = self.fwd[1].apply(out, ax + 1)
            out = out / self.denom.astype(out.dtype)
            if self.bwd[1] is not None:
                out = self.bwd[1].apply(out, ax + 1)
            out = flip(out, rest, LOCAL[1])
            if self.bwd[0] is not None:
                out = self.bwd[0].apply(out, ax)
            return constrain(out, rest)


class _TensorBased:
    """Shared assembly for Poisson/Hholtz: fast-diagonalisation on TPU,
    eig-axis0 + banded-axis1 tensor solver elsewhere.  Both backends
    diagonalize the same preconditioned pencils, so they solve the same
    discrete system (tests/test_golden.py asserts equality to machine
    precision)."""

    def __init__(
        self,
        space: Space2,
        c,
        alpha: float,
        negate_lap: bool,
        fix_singular=False,
        method: str | None = None,
    ):
        with _tr.span(
            "solver.build", layer="operators and kernels",
            kind=type(self).__name__.lower(), shape=space.shape_physical,
            eigs=0, eig_cached=0,
        ):
            self._build(space, c, alpha, negate_lap, fix_singular, method)

    def _build(self, space, c, alpha, negate_lap, fix_singular, method) -> None:
        method = method or ("fd" if config.is_tpu_like() else "banded")
        sign = -1.0 if negate_lap else 1.0
        sep = getattr(space, "sep", (False, False))
        modal0 = _axis_modal_data(space, 0, c[0], sign)
        if method == "fd":
            modal1 = _axis_modal_data(space, 1, c[1], sign)
            self._solver = FastDiag(
                modal0, modal1, alpha, fix_singular, sep=sep, rest=space.rest
            )
        else:
            # mat_c1 = preconditioned mass (pinv S, or I for Fourier),
            # mat_a1 = preconditioned laplacian (peye S, or diag(-k^2))
            mat_c1, mat_a1, precond1 = ingredients_for_hholtz(space, 1)
            self._solver = TensorSolver(
                modal0,
                sign * c[1] * mat_a1,
                mat_c1,
                precond1,
                alpha,
                fix_singular=fix_singular,
                sep=sep,
                rest=space.rest,
            )

    def solve(self, rhs):
        return self._solver.solve(rhs)


class Poisson(_TensorBased):
    """Pressure Poisson ``c * D2 u = A f`` with singular-mode regularization
    (lam -= 1e-10, /root/reference/src/solver/poisson.rs:84-87)."""

    def __init__(self, space: Space2, c, **kw):
        super().__init__(space, c, alpha=0.0, negate_lap=False, fix_singular=True, **kw)


class Hholtz(_TensorBased):
    """Exact (non-ADI) Helmholtz ``(I - c*D2) u = A f`` via the tensor solver
    with alpha=1 (/root/reference/src/solver/hholtz.rs:63-100)."""

    def __init__(self, space: Space2, c, **kw):
        super().__init__(space, c, alpha=1.0, negate_lap=True, **kw)
