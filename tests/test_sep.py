"""Parity tests for the sep-layout kernels at sizes where every production
impl actually engages (the small-n suite never reaches _StripTrapezoid's
192-row minimum or the fused conv paths — round-4 review finding)."""

import numpy as np
import pytest

import rustpde_mpi_tpu as rp
from rustpde_mpi_tpu.ops import chebyshev as chb
from rustpde_mpi_tpu.ops import transforms as tr
from rustpde_mpi_tpu.ops.folded import FoldedMatrix, parity_perm, parity_perm_inv

import jax.numpy as jnp

_dev = lambda m: jnp.asarray(m)  # noqa: E731


@pytest.fixture(params=["folded", "plain"])
def transform_form(request, fold_gate):
    """Both forms of a reflection-symmetric transform at a small size: its
    parity fold (the gate of ops/folded.py pinned below the size) and the one
    plain product every grid below the gate runs."""
    fold_gate(4 if request.param == "folded" else fold_gate.NEVER)
    return request.param


@pytest.mark.parametrize("n", [513, 512])
def test_trapezoid_strips_engage_and_match(n):
    S = chb.stencil_dirichlet(n)
    for order in (1, 2):
        G = chb.diff_matrix(n, order) @ S
        fm = FoldedMatrix(G, _dev, sep_in=True, sep_out=True)
        assert "trapezoid" in fm.kind, fm.kind  # the production impl runs
        assert fm.flops_factor < 0.45
        rng = np.random.default_rng(order)
        x = rng.standard_normal((G.shape[1], 3))
        got = np.asarray(fm.apply(jnp.asarray(x[parity_perm(G.shape[1])]), 0))
        want = (G @ x)[parity_perm(G.shape[0])]
        np.testing.assert_allclose(got, want, atol=1e-11 * np.abs(want).max())


@pytest.mark.parametrize("n", [17, 16, 33])
def test_fwd_cut_matches_masked_forward(n, transform_form):
    """forward_dealiased (dead GEMM rows dropped) == forward * 2/3-mask."""
    sep = rp.Space2(rp.cheb_dirichlet(n), rp.cheb_neumann(n + 1), sep=True, method="matmul")
    assert all(sep.sep)
    kind = "analysis_sep_cut" if transform_form == "folded" else "plain"
    assert sep.bases[0]._sep_dev("fwd_cut").kind == kind
    rng = np.random.default_rng(0)
    v = rng.standard_normal(sep.shape_physical)
    got = np.asarray(sep.forward_dealiased(v))
    want = np.asarray(sep.forward(v)) * sep.dealias_mask()
    np.testing.assert_allclose(got, want, atol=1e-13)


@pytest.mark.parametrize("deriv", [(1, 0), (0, 1), (2, 0), (1, 1)])
def test_backward_gradient_fusion_matches(deriv, transform_form):
    """Syn @ D @ S fusion (incl. the sign=-1 odd-order synthesis symmetry)
    == backward_ortho(gradient(.))."""
    sep = rp.Space2(rp.cheb_dirichlet(33), rp.cheb_neumann(32), sep=True, method="matmul")
    assert all(sep.sep)
    kind = "synthesis_sep" if transform_form == "folded" else "plain"
    assert sep.bases[0]._sep_dev(("bwd_grad", 1)).kind == kind
    rng = np.random.default_rng(1)
    vhat = sep.forward(rng.standard_normal(sep.shape_physical))
    got = np.asarray(sep.backward_gradient(vhat, deriv, (1.0, 2.0)))
    want = np.asarray(sep.backward_ortho(sep.gradient(vhat, deriv, (1.0, 2.0))))
    np.testing.assert_allclose(got, want, atol=1e-10)


@pytest.mark.parametrize("n,order", [(33, 1), (32, 2), (17, 3)])
def test_cheb_derivative_sep_matches(n, order):
    rng = np.random.default_rng(2)
    c = rng.standard_normal((n, 4))
    want = np.asarray(tr.cheb_derivative(jnp.asarray(c), order, 0))
    got = np.asarray(
        tr.cheb_derivative_sep(jnp.asarray(c[parity_perm(n)]), order, 0)
    )[parity_perm_inv(n)]
    np.testing.assert_allclose(got, want, atol=1e-11 * max(1.0, np.abs(want).max()))


def test_sep_layout_roundtrip_io_boundary():
    """spectral_to_natural/from_natural invert each other and match the
    natural-space coefficients."""
    nat = rp.Space2(rp.cheb_dirichlet(19), rp.cheb_dirichlet(18), sep=False, method="matmul")
    sep = rp.Space2(rp.cheb_dirichlet(19), rp.cheb_dirichlet(18), sep=True, method="matmul")
    rng = np.random.default_rng(3)
    v = rng.standard_normal(nat.shape_physical)
    a = np.asarray(nat.forward(v))
    b = sep.forward(v)
    np.testing.assert_allclose(sep.spectral_to_natural(b), a, atol=1e-13)
    np.testing.assert_allclose(
        np.asarray(sep.spectral_from_natural(sep.spectral_to_natural(b))),
        np.asarray(b),
        atol=0,
    )


@pytest.mark.parametrize("deriv", [(0, 0), (1, 0), (0, 1)])
def test_backward_gradient_fast_matches(deriv, monkeypatch):
    """The fast-key plumbing (('bwd','fast') / ('bwd_grad',o,'fast')): under
    X64 (the CI default) no downgrade happens, so fast == exact bitwise; the
    key construction and base_key slicing are exercised either way."""
    sep = rp.Space2(rp.cheb_dirichlet(33), rp.cheb_neumann(32), sep=True, method="matmul")
    assert all(sep.sep)
    rng = np.random.default_rng(7)
    vhat = sep.forward(rng.standard_normal(sep.shape_physical))
    fast = np.asarray(sep.backward_gradient(vhat, deriv, (1.0, 2.0), fast=True))
    exact = np.asarray(sep.backward_gradient(vhat, deriv, (1.0, 2.0), fast=False))
    np.testing.assert_array_equal(fast, exact)
    # the alias path: fast keys must map to the SAME cached FoldedMatrix
    base = sep.bases[0]
    key = ("bwd_grad", 1) if deriv[0] else "bwd"
    fkey = key + ("fast",) if isinstance(key, tuple) else (key, "fast")
    assert base._sep_dev(fkey) is base._sep_dev(key)


def test_backward_fast_matches_backward():
    sep = rp.Space2(rp.cheb_dirichlet(17), rp.cheb_dirichlet(16), sep=True, method="matmul")
    rng = np.random.default_rng(8)
    vhat = sep.forward(rng.standard_normal(sep.shape_physical))
    np.testing.assert_array_equal(
        np.asarray(sep.backward_fast(vhat)), np.asarray(sep.backward(vhat))
    )


def test_fused_projection_gradient_helper():
    """bases.fused_projection_gradient: matmul-only gating, periodic -> None,
    value-keyed dedup (square grids share operators), and numerical equality
    with the unfused from_ortho(gradient(.)) chain."""
    from rustpde_mpi_tpu.bases import fused_projection_gradient

    q = rp.Space2(rp.cheb_neumann(33), rp.cheb_neumann(33), method="matmul")
    u = rp.Space2(rp.cheb_dirichlet(33), rp.cheb_dirichlet(33), method="matmul")
    gx = fused_projection_gradient(u, q, (1, 0))
    gy = fused_projection_gradient(u, q, (0, 1))
    assert gx and gy
    # square grid: the order-0 cast of gy and gx share one cached operator
    assert gx[1] is gy[0]
    rng = np.random.default_rng(11)
    vhat = q.forward(rng.standard_normal(q.shape_physical))
    ax = vhat.ndim - 2
    got = np.asarray(gx[1].apply(gx[0].apply(vhat, ax), ax + 1))
    want = np.asarray(u.from_ortho(q.gradient(vhat, (1, 0), None)))
    np.testing.assert_allclose(got, want, atol=1e-11)
    # fft-method spaces (the recurrence path) are not fused
    q_fft = rp.Space2(rp.cheb_neumann(17), rp.cheb_neumann(17), method="fft")
    u_fft = rp.Space2(rp.cheb_dirichlet(17), rp.cheb_dirichlet(17), method="fft")
    assert fused_projection_gradient(u_fft, q_fft, (1, 0)) is None
    # periodic axes (diagonal Fourier gradient) are not fused either
    q_per = rp.Space2(rp.fourier_r2c(16), rp.cheb_neumann(17))
    u_per = rp.Space2(rp.fourier_r2c(16), rp.cheb_dirichlet(17))
    assert fused_projection_gradient(u_per, q_per, (1, 0)) is None


def test_fwd_cut_fast_key_plumbing(monkeypatch):
    """("fwd_cut","fast"): aliases the exact entry when RUSTPDE_FWD_PRECISION
    is unset/highest (default OFF until measured on-chip), and builds a
    distinct impl carrying the precision override when set to high."""
    from rustpde_mpi_tpu import config as cfg

    sep = rp.Space2(rp.cheb_dirichlet(33), rp.cheb_neumann(33), sep=True, method="matmul")
    b = sep.bases[0]
    monkeypatch.delenv("RUSTPDE_FWD_PRECISION", raising=False)
    assert b._sep_dev(("fwd_cut", "fast")) is b._sep_dev("fwd_cut")
    if cfg.X64:
        return  # f64 never downgrades; alias behavior above is the contract
    b2 = rp.cheb_dirichlet(35)
    monkeypatch.setenv("RUSTPDE_FWD_PRECISION", "high")
    fast = b2._sep_dev(("fwd_cut", "fast"))
    assert fast is not b2._sep_dev("fwd_cut")
    assert fast._impl.precision == "high"
    # fast forward == exact forward on CPU (precision hint is a no-op there)
    rng = np.random.default_rng(1)
    v = rng.standard_normal(sep.shape_physical)
    got = np.asarray(sep.forward_dealiased(v, fast=False))
    want = np.asarray(sep.forward(v)) * sep.dealias_mask()
    np.testing.assert_allclose(got, want, atol=1e-13)


def test_mixed_sep_periodic_space(monkeypatch, transform_form):
    """Periodic (split-Fourier x, Chebyshev y) space with the Chebyshev axis
    sep: the per-axis fused paths — forward_dealiased with a vector cut on
    the Fourier axis, backward_gradient with the fused chain on the sep axis
    only — match the unfused forms exactly."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    sp = rp.Space2(rp.fourier_r2c(16), rp.cheb_dirichlet(17), method="matmul", sep=True)
    assert sp.sep == (False, True)
    rng = np.random.default_rng(3)
    v = rng.standard_normal(sp.shape_physical)
    got = np.asarray(sp.forward_dealiased(v))
    want = np.asarray(sp.forward(v)) * sp.dealias_mask()
    np.testing.assert_allclose(got, want, atol=1e-12)
    vhat = sp.forward(jnp.asarray(v))
    for deriv in [(0, 0), (1, 0), (0, 1), (1, 1), (0, 2)]:
        got = np.asarray(sp.backward_gradient(vhat, deriv, None))
        want = np.asarray(sp.backward_ortho(sp.gradient(vhat, deriv, None)))
        np.testing.assert_allclose(
            got, want, atol=1e-10 * max(1.0, np.abs(want).max()), err_msg=str(deriv)
        )


@pytest.mark.slow
def test_periodic_model_forced_sep_matches_default():
    """A periodic Navier model with the Chebyshev axis forced sep
    (RUSTPDE_SEP=1) reproduces the default-layout trajectory to roundoff —
    the at-scale periodic layout candidate (VERDICT r4 next #2)."""
    import json
    import os
    import subprocess
    import sys

    code = (
        "import jax, json\n"
        "from rustpde_mpi_tpu import Navier2D\n"
        "m = Navier2D.new_periodic(16, 17, 1e4, 1.0, 1e-2, 1.0, 'rbc')\n"
        "import sys; print('sep', m.temp_space.sep, file=sys.stderr)\n"
        "m.set_velocity(0.1, 2.0, 2.0); m.set_temperature(0.1, 2.0, 2.0)\n"
        "m.update_n(60)\n"
        "print(json.dumps(list(m.get_observables())))\n"
    )
    obs = {}
    for sep in ("0", "1"):
        env = dict(
            os.environ,
            RUSTPDE_FORCE_TPU_PATH="1",
            RUSTPDE_SEP=sep,
            JAX_PLATFORMS="cpu",
        )
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, env=env, timeout=600,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        obs[sep] = json.loads(out.stdout.strip().splitlines()[-1])
    for a, b in zip(obs["0"], obs["1"]):
        assert abs(a - b) <= 1e-9 * max(1.0, abs(a)), (obs["0"], obs["1"])
