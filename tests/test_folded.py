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
def test_transform_matrices_fold(base_fn, n, fold_gate):
    """Both transform directions fold (for even n both reflection symmetries
    hold simultaneously and either fold type is valid), from the gate up: it
    is pinned to this size."""
    fold_gate(n - 2)
    base = base_fn(n)
    fwd = base.projection @ chb.analysis_matrix(n)
    bwd = chb.synthesis_matrix(n) @ base.stencil
    for mat in (fwd, bwd, chb.synthesis_matrix(n)):
        fm = _check(mat)
        assert fm.kind in ("analysis", "synthesis"), fm.kind
        assert fm.flops_factor == 0.5
    fold_gate(n + 1)  # below the gate each is one plain product that keeps the hook
    for mat in (fwd, bwd, chb.synthesis_matrix(n)):
        fm = _check(mat, "plain")
        assert fm.flops_factor == 1.0 and fm.set_precision("high")


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
    fm = _check(rng.standard_normal((12, 14)), "plain")
    assert not fm.set_precision("high")  # only a transform's forms take the fast key's


@pytest.mark.parametrize("kind", ["synthesis", "plain"])
def test_folded_accepts_complex_input(kind, fold_gate):
    fold_gate(16 if kind == "synthesis" else 17)
    fm = FoldedMatrix(chb.synthesis_matrix(16), _dev)
    assert fm.kind == kind
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((16, 3)) + 1j * rng.standard_normal((16, 3)))
    ref = chb.synthesis_matrix(16) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(fm.apply(x, 0)), ref, atol=1e-12)


def test_disable_env(monkeypatch, fold_gate):
    fold_gate(16)
    assert FoldedMatrix(chb.synthesis_matrix(16), _dev).kind == "synthesis"
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


# -- the fold gate -----------------------------------------------------------------

GATE = 31


def _gated(case, n):
    """``(host matrix, FoldedMatrix keywords, folded kind)`` of one reflection
    structure on ``cheb_dirichlet(n)``, whose smaller extent is ``n - 2``."""
    base = cheb_dirichlet(n)
    fwd = base.projection @ chb.analysis_matrix(n)
    if case == "analysis":
        return fwd, dict(sep_out=True), "analysis_sep"
    if case == "analysis_cut":
        return fwd, dict(sep_out=True, keep_rows=base.m * 2 // 3), "analysis_sep_cut"
    order = 0 if case == "synthesis_plus" else 1  # the odd derivative flips the sign
    return chb.synthesis_matrix(n) @ base.gradient_matrix(order), dict(sep_in=True), "synthesis_sep"


@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("case", ["analysis", "analysis_cut", "synthesis_plus", "synthesis_minus"])
@pytest.mark.parametrize("side", ["below", "above"])
def test_reflection_folds_engage_from_the_gate_up(side, case, axis, fold_gate):
    """Just below ``_FOLD_MIN_DIM`` a reflection-symmetric transform is one
    plain product and from it up the fold; at either size the two forms are
    the same operator to machine epsilon, a cut analysis leaves exact zeros in
    its dead rows in both, and both carry the precision the fast key sets."""
    import jax

    from rustpde_mpi_tpu.ops.folded import dense_operator, kept_storage_rows
    from rustpde_mpi_tpu.utils.jit import equations

    n = GATE + 1 if side == "below" else GATE + 2  # smaller extent 30 | 31
    mat, kw, folded_kind = _gated(case, n)
    fold_gate(GATE)
    here = FoldedMatrix(mat, _dev, **kw)
    assert here.kind == ("plain" if side == "below" else folded_kind)
    fold_gate(n if side == "above" else 4)
    other = FoldedMatrix(mat, _dev, **kw)
    assert other.kind == (folded_kind if side == "below" else "plain")
    assert {here.flops_factor, other.flops_factor} == (
        {0.5 * kw["keep_rows"] / mat.shape[0], kw["keep_rows"] / mat.shape[0]}
        if "keep_rows" in kw else {0.5, 1.0}
    )

    dense = dense_operator(mat, **kw)  # the storage-layout operator both must equal
    rng = np.random.default_rng(3)
    shape = (mat.shape[1], 5) if axis == 0 else (5, mat.shape[1])
    x = jnp.asarray(rng.standard_normal(shape))
    want = np.moveaxis(np.tensordot(dense, np.asarray(x), axes=([1], [axis])), 0, axis)
    got_here, got_other = np.asarray(here.apply(x, axis)), np.asarray(other.apply(x, axis))
    eps = 50 * np.finfo(got_here.dtype).eps * np.abs(want).max()
    np.testing.assert_allclose(got_here, want, atol=eps)
    np.testing.assert_allclose(got_other, got_here, atol=eps)
    if "keep_rows" in kw:
        kept = kept_storage_rows(mat.shape[0], kw["keep_rows"], True)
        dead = np.setdiff1d(np.arange(mat.shape[0]), kept)
        assert dead.size
        for got in (got_here, got_other):
            assert not np.take(got, dead, axis=axis).any()  # exact zeros
            assert np.take(got, kept, axis=axis).all()

    def precisions(fm):
        jaxpr = jax.make_jaxpr(lambda v: fm.apply(v, axis))(x).jaxpr
        return [e.params["precision"] for e in equations(jaxpr) if e.primitive.name == "dot_general"]

    for fm, products in ((here, 1 if side == "below" else 2), (other, 2 if side == "below" else 1)):
        high, highest = jax.lax.Precision.HIGH, jax.lax.Precision.HIGHEST
        assert precisions(fm) == [(highest, highest)] * products  # the session's default
        assert fm.set_precision("high")
        assert precisions(fm) == [(high, high)] * products


def test_fold_gate_reads_the_shape_and_the_products_itemsize():
    """The module's own gate: in float32 a reflection fold engages from a
    smaller extent of 255 (the 257-point grid's: a parity block fills a whole
    128 tile), in float64, which the chip emulates, at every size; the
    hybrid's float32 operators of a float64 session take float32's."""
    from rustpde_mpi_tpu import config
    from rustpde_mpi_tpu.ops import folded

    small, below, at = (chb.synthesis_matrix(n) for n in (33, 254, 255))
    assert folded._detect(small, itemsize=4).kind == "plain"
    assert folded._detect(small, itemsize=8).kind == "synthesis"
    assert folded._detect(below, itemsize=4).kind == "plain"
    assert folded._detect(at, itemsize=4).kind == "synthesis"
    for n, kind in ((257, "analysis_sep"), (256, "plain")):  # 255 x 257 | 254 x 256
        fwd = cheb_dirichlet(n).projection @ chb.analysis_matrix(n)
        assert folded._detect(fwd, sep_out=True, itemsize=4).kind == kind
        assert folded._detect(fwd, sep_out=True, itemsize=8).kind == "analysis_sep"
    # the dense checkerboard blocks of the sep layout: two products from a
    # smaller extent of 191 up, one below it (float64: two at every size)
    for n, kind in ((193, "sep_preserve[plain,plain]"), (192, "plain")):  # 191 x 193 | 190 x 192
        proj = cheb_dirichlet(n).projection
        assert folded._detect(proj, sep_in=True, sep_out=True, itemsize=4).kind == kind
        assert folded._detect(proj, True, True, itemsize=8).kind == "sep_preserve[plain,plain]"
    stencil = cheb_dirichlet(65).stencil  # banded blocks are not gated
    assert folded._detect(stencil, True, True, itemsize=4).kind == "sep_preserve[banded,banded]"
    session = "synthesis" if config.X64 else "plain"
    assert FoldedMatrix(small, _dev).kind == session
    assert FoldedMatrix(small, _dev, cast=np.float32).kind == "plain"


@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("case", ["preserve", "flip"])
def test_dense_checkerboard_blocks_engage_from_their_gate_up(case, axis, fold_gate):
    """A spectral->spectral operator of the sep layout whose two parity
    blocks are dense: below ``_SEP_MIN_DIM`` one product over the whole
    matrix, its exact zeros included, from it up the two blocks; the same
    operator either way."""
    from rustpde_mpi_tpu.ops.folded import dense_operator

    base = cheb_dirichlet(33)
    mat = base.projection if case == "preserve" else base.gradient_matrix(1)
    fold_gate(4)
    blocks = FoldedMatrix(mat, _dev, sep_in=True, sep_out=True)
    assert blocks.kind == f"sep_{case}[plain,plain]" and abs(blocks.flops_factor - 0.5) < 0.01
    fold_gate(fold_gate.NEVER)
    plain = FoldedMatrix(mat, _dev, sep_in=True, sep_out=True)
    assert plain.kind == "plain" and plain.flops_factor == 1.0
    dense = dense_operator(mat, sep_in=True, sep_out=True)
    x = jnp.asarray(np.random.default_rng(5).standard_normal((mat.shape[1], 4) if axis == 0 else (4, mat.shape[1])))
    want = np.moveaxis(np.tensordot(dense, np.asarray(x), axes=([1], [axis])), 0, axis)
    eps = 50 * np.finfo(want.dtype).eps * np.abs(want).max()
    np.testing.assert_allclose(np.asarray(blocks.apply(x, axis)), want, atol=eps)
    np.testing.assert_allclose(np.asarray(plain.apply(x, axis)), want, atol=eps)
