"""Parity-folded matrix application (ops/folded.py).

The folded path must be numerically interchangeable with the plain GEMM on
every matrix family the framework builds, on even and odd sizes, along both
axes — and the fold must actually engage (flops_factor 0.5) wherever the
parity structure exists."""

import numpy as np
import pytest

import jax.numpy as jnp

from rustpde_mpi_tpu.bases import (
    cheb_dirichlet,
    cheb_dirichlet_neumann,
    cheb_neumann,
    chebyshev,
)
from rustpde_mpi_tpu.ops import chebyshev as chb
from rustpde_mpi_tpu.ops.folded import FoldedMatrix


def _dev(m):
    return jnp.asarray(m)


def _check(mat, expect_kind=None, batch=5, atol=1e-12):
    fm = FoldedMatrix(mat, _dev)
    if expect_kind is not None:
        assert fm.kind == expect_kind, (fm.kind, expect_kind)
    rng = np.random.default_rng(0)
    x0 = jnp.asarray(rng.standard_normal((mat.shape[1], batch)))
    ref0 = np.asarray(mat) @ np.asarray(x0)
    np.testing.assert_allclose(np.asarray(fm.apply(x0, 0)), ref0, atol=atol)
    x1 = jnp.asarray(rng.standard_normal((batch, mat.shape[1])))
    ref1 = np.asarray(x1) @ np.asarray(mat).T
    np.testing.assert_allclose(np.asarray(fm.apply(x1, 1)), ref1, atol=atol)
    return fm


@pytest.mark.parametrize("n", [16, 17])
@pytest.mark.parametrize("base_fn", [chebyshev, cheb_dirichlet, cheb_neumann])
def test_transform_matrices_fold(base_fn, n):
    """Both transform directions fold (for even n both reflection symmetries
    hold simultaneously and either fold type is valid)."""
    base = base_fn(n)
    fwd = base.projection @ chb.analysis_matrix(n)
    bwd = chb.synthesis_matrix(n) @ base.stencil
    for mat in (fwd, bwd, chb.synthesis_matrix(n)):
        fm = _check(mat)
        assert fm.kind in ("analysis", "synthesis"), fm.kind
        assert fm.flops_factor == 0.5


@pytest.mark.parametrize("n", [16, 17])
def test_spectral_operators_fold_checkerboard(n):
    base = cheb_dirichlet(n)
    # the stencil's two diagonals run as shifted adds; the dense projection
    # and gradient matrices fold checkerboard
    s = _check(base.stencil, "banded")
    assert s.flops_factor < 0.5
    _check(base.projection, "checker")
    _check(base.gradient_matrix(1), "checker")
    _check(base.gradient_matrix(2), "checker")
    # a parity-preserving implicit-solve inverse
    peye = base.laplace_inv_eye()
    pinv = peye @ base.laplace_inv()
    op = pinv @ base.stencil - 0.1 * (peye @ base.stencil)
    _check(np.linalg.inv(op), "checker", atol=1e-10)


def test_mixed_bc_base_falls_back_to_plain():
    base = cheb_dirichlet_neumann(17)
    fwd = base.projection @ chb.analysis_matrix(17)
    fm = _check(fwd, "plain")
    assert fm.flops_factor == 1.0


def test_unstructured_matrix_is_plain():
    rng = np.random.default_rng(1)
    _check(rng.standard_normal((12, 14)), "plain")


def test_folded_accepts_complex_input():
    fm = FoldedMatrix(chb.synthesis_matrix(16), _dev)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((16, 3)) + 1j * rng.standard_normal((16, 3)))
    ref = chb.synthesis_matrix(16) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(fm.apply(x, 0)), ref, atol=1e-12)


def test_disable_env(monkeypatch):
    monkeypatch.setenv("RUSTPDE_FOLDED", "0")
    fm = FoldedMatrix(chb.synthesis_matrix(16), _dev)
    assert fm.kind == "plain"


@pytest.mark.slow
def test_space_transform_equivalence_folded_vs_plain(monkeypatch):
    """End-to-end: Space2 matmul transforms with folding on vs off."""
    import subprocess
    import sys
    import os

    code = r"""
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %r)
import jax
import numpy as np, jax.numpy as jnp
from rustpde_mpi_tpu import Space2, cheb_dirichlet, cheb_neumann
space = Space2(cheb_dirichlet(17), cheb_neumann(16), method="matmul")
rng = np.random.default_rng(5)
vhat = jnp.asarray(rng.standard_normal(space.shape_spectral))
v = space.backward(vhat)
out = {
    "v": np.asarray(v).tolist(),
    "rt": np.asarray(space.forward(v)).tolist(),
    "grad": np.asarray(space.gradient(vhat, (1, 1))).tolist(),
}
print("OUT:" + json.dumps(out))
"""
    import json

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    results = {}
    for flag in ("1", "0"):
        env = dict(os.environ, RUSTPDE_FOLDED=flag, RUSTPDE_X64="1")
        res = subprocess.run(
            [sys.executable, "-c", code % repo],
            capture_output=True, text=True, env=env, timeout=300,
        )
        line = [l for l in res.stdout.splitlines() if l.startswith("OUT:")]
        assert line, res.stderr[-500:]
        results[flag] = json.loads(line[0][4:])
    for key in ("v", "rt", "grad"):
        np.testing.assert_allclose(
            np.asarray(results["1"][key]), np.asarray(results["0"][key]),
            atol=1e-12, err_msg=key,
        )


def test_modal_maps_fold_with_parity_interleaved_eig():
    """The parity-interleaved eigen ordering makes the fast-diag modal maps
    checkerboard, so they fold; the singular mode still sits at index 0."""
    from rustpde_mpi_tpu import Space2, cheb_neumann
    from rustpde_mpi_tpu.solver import FastDiag, Poisson, _axis_modal_data

    space = Space2(cheb_neumann(16), cheb_neumann(17))
    lam, fwd, bwd = _axis_modal_data(space, 0, 1.0, 1.0)
    assert FoldedMatrix(fwd, _dev).kind == "checker"
    assert FoldedMatrix(bwd, _dev).kind == "checker"
    assert abs(lam[0]) < 1e-9  # pure-Neumann singular mode at index 0
    solver = Poisson(space, (1.0, 1.0))
    impl = solver._solver
    if isinstance(impl, FastDiag):
        assert impl.fwd[0].flops_factor == 0.5


def test_circular_folds_on_fourier_matrices(monkeypatch):
    """Split-Fourier and DFT cos/sin matrices fold under the circular
    reflection j -> (n-j) mod n, for even and odd n (gate lowered so the
    small unit sizes exercise the fold math)."""
    from rustpde_mpi_tpu.ops import folded, fourier as fou

    monkeypatch.setattr(folded, "_CIRC_MIN_DIM", 4)
    for n in (16, 17):
        fwd = _check(fou.split_forward_matrix(n), "circ_analysis")
        assert fwd.flops_factor == 0.5
        bwd = _check(fou.split_backward_matrix(n), "circ_synthesis")
        assert bwd.flops_factor == 0.5


def test_circ_both_quarter_fold_on_dft_matrices(monkeypatch):
    """DFT cos/sin matrices carry both circular symmetries with one output
    sign -> quarter-flops fold."""
    from rustpde_mpi_tpu.ops import folded

    from rustpde_mpi_tpu.ops import fourier as fou

    monkeypatch.setattr(folded, "_CIRC_MIN_DIM", 4)
    for n in (16, 17):
        cos = _check(fou.dft_cos_matrix(n), "circ_both")
        sin = _check(fou.dft_sin_matrix(n), "circ_both")
        assert cos.flops_factor == 0.25
        assert sin.flops_factor == 0.25


def test_circular_fold_size_gate():
    """Below the size gate the circular families stay plain (their gathers
    cost more than the saved flops on dispatch-bound small GEMMs); at
    transform scale they engage."""
    from rustpde_mpi_tpu.ops import folded, fourier as fou

    gate = folded._CIRC_MIN_DIM
    small = FoldedMatrix(fou.split_forward_matrix(gate // 2), _dev)
    assert small.kind == "plain"
    big = FoldedMatrix(fou.split_forward_matrix(2 * gate), _dev)
    assert big.kind == "circ_analysis"
    assert FoldedMatrix(fou.dft_cos_matrix(gate), _dev).kind == "circ_both"


def test_banded_apply_families():
    """Exactly-banded operators (stencils, B2 quasi-inverse, restricted eye)
    run as shifted adds, matching the dense product to machine epsilon."""
    for mat in (
        chb.stencil_dirichlet(33),
        chb.stencil_neumann(32),
        chb.stencil_dirichlet_neumann(33),
        chb.quasi_inverse_b2(32),
        chb.restricted_eye(33),
        chb.restricted_eye(32) @ chb.quasi_inverse_b2(32),
    ):
        fm = _check(mat, "banded", atol=1e-13)
        assert fm.flops_factor < 0.25


def test_hybrid_cast_rejects_complex_input():
    # the hybrid cast path (f64 state through f32 device transforms) is only
    # defined real->real: astype(float32) on a complex operand would silently
    # drop the imaginary part, so it must raise instead
    rng = np.random.default_rng(0)
    fm = FoldedMatrix(rng.standard_normal((8, 8)), _dev, cast=np.float32)
    ok = fm.apply(jnp.asarray(rng.standard_normal((8, 5))), 0)
    assert ok.dtype == jnp.float64  # output cast back to the input dtype
    bad = jnp.asarray(rng.standard_normal((8, 5)) + 1j)
    with pytest.raises(TypeError, match="imaginary"):
        fm.apply(bad, 0)
