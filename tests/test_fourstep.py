"""Four-step (Bailey) MXU FFT/DCT factorization (ops/fourstep.py).

The factored transforms must be numerically interchangeable with the dense
transform matrices (1e-12 absolute in f64 — same reductions, reassociated)
on even and odd lengths, prime-free and not, along both axes, and through
the Base/Space wrappers that auto-select them.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from rustpde_mpi_tpu.ops import chebyshev as chb
from rustpde_mpi_tpu.ops import fourier as fou
from rustpde_mpi_tpu.ops import fourstep


def _dev(m):
    return jnp.asarray(m)


@pytest.mark.parametrize("n", [16, 24, 36, 128, 510])
def test_rfft_plans_match_numpy(n):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, 3))
    m = n // 2 + 1
    c = np.fft.rfft(x, axis=0)
    plan = fourstep.RfftPlan(n, _dev)
    got = np.asarray(plan.split(jnp.asarray(x)))
    np.testing.assert_allclose(got[:m], c.real, atol=1e-12)
    np.testing.assert_allclose(got[m:], c.imag, atol=1e-12)
    np.testing.assert_allclose(np.asarray(plan.re(jnp.asarray(x))), c.real, atol=1e-12)
    # inverse: split coefficients in the amplitude convention c/n
    s = jnp.asarray(np.concatenate([c.real, c.imag], axis=0) / n)
    v = np.asarray(fourstep.IrfftPlan(n, _dev).apply(s))
    np.testing.assert_allclose(v, x, atol=1e-12)


@pytest.mark.parametrize("n", [16, 36, 128])
def test_c2c_plans_match_numpy(n):
    rng = np.random.default_rng(1)
    z = rng.standard_normal((n, 4)) + 1j * rng.standard_normal((n, 4))
    fwd = fourstep.C2cPlan(n, _dev, sign=-1.0)
    re, im = fwd.apply(jnp.asarray(z.real), jnp.asarray(z.imag))
    zf = np.fft.fft(z, axis=0)
    np.testing.assert_allclose(np.asarray(re), zf.real, atol=1e-11)
    np.testing.assert_allclose(np.asarray(im), zf.imag, atol=1e-11)
    bwd = fourstep.C2cPlan(n, _dev, sign=+1.0)
    re, im = bwd.apply(jnp.asarray(z.real), jnp.asarray(z.imag))
    zi = np.fft.ifft(z, axis=0) * n
    np.testing.assert_allclose(np.asarray(re), zi.real, atol=1e-11)
    np.testing.assert_allclose(np.asarray(im), zi.imag, atol=1e-11)


def test_dense_vs_fourstep_equality_dense_sizes():
    """VERDICT r2 'done' criterion: factored == dense transform at 1e-12
    (f64) on representative transform sizes, both matrix families."""
    rng = np.random.default_rng(2)
    for n in (64, 96, 256):
        x = rng.standard_normal((n, 2))
        dense = fou.split_forward_matrix(n) @ x
        got = np.asarray(fourstep.RfftPlan(n, _dev).split(jnp.asarray(x))) / n
        np.testing.assert_allclose(got, dense, atol=1e-12)
        s = rng.standard_normal((2 * (n // 2 + 1), 2))
        dense_b = fou.split_backward_matrix(n) @ s
        got_b = np.asarray(fourstep.IrfftPlan(n, _dev).apply(jnp.asarray(s)))
        np.testing.assert_allclose(got_b, dense_b, atol=1e-11)


def test_f32_accuracy():
    """f32 factored transform tracks the f64 dense one to ~1e-5 relative
    (better than the dense f32 GEMM's own roundoff profile)."""
    rng = np.random.default_rng(3)
    n = 256
    x64 = rng.standard_normal((n, 4))
    ref = fou.split_forward_matrix(n) @ x64
    to_f32 = lambda m: jnp.asarray(np.asarray(m, dtype=np.float32))  # noqa: E731
    got = np.asarray(fourstep.RfftPlan(n, to_f32).split(to_f32(x64))) / n
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() / scale < 2e-5


@pytest.fixture
def force_fourstep(monkeypatch):
    monkeypatch.setattr(fourstep, "_MODE", "1")


@pytest.mark.parametrize("n", [33, 34, 37])
def test_base_fast_cheb_matches_dense(force_fourstep, n):
    """Base-level matmul transforms ride the fast DCT when enabled and match
    the dense operator matrices exactly."""
    from rustpde_mpi_tpu import bases

    rng = np.random.default_rng(4)
    for ctor in (bases.chebyshev, bases.cheb_dirichlet, bases.cheb_neumann):
        base = ctor(n)
        assert base._dct_plan is not None
        v = rng.standard_normal((n, 5))
        if base.kind == bases.BaseKind.CHEBYSHEV:
            F = base.projection @ chb.analysis_matrix(n)
            got = np.asarray(base.forward(jnp.asarray(v), 0, "matmul"))
            np.testing.assert_allclose(got, F @ v, atol=1e-12)
        S = chb.synthesis_matrix(n) @ base.stencil
        c = rng.standard_normal((base.m, 5))
        got = np.asarray(base.backward(jnp.asarray(c), 0, "matmul"))
        np.testing.assert_allclose(got, S @ c, atol=1e-12)
        # axis-1 application through the moveaxis wrapper
        got1 = np.asarray(base.backward(jnp.asarray(c.T), 1, "matmul"))
        np.testing.assert_allclose(got1, (S @ c).T, atol=1e-12)
        o = rng.standard_normal((n, 5))
        got_o = np.asarray(base.backward_ortho(jnp.asarray(o), 0, "matmul"))
        np.testing.assert_allclose(got_o, chb.synthesis_matrix(n) @ o, atol=1e-12)


def test_split_base_fast_matches_matrices(force_fourstep):
    from rustpde_mpi_tpu import bases

    n = 36
    base = bases.fourier_r2c_split(n)
    assert base._rfft_plan is not None
    rng = np.random.default_rng(5)
    v = rng.standard_normal((n, 3))
    np.testing.assert_allclose(
        np.asarray(base.forward(jnp.asarray(v), 0)),
        fou.split_forward_matrix(n) @ v,
        atol=1e-13,
    )
    s = rng.standard_normal((base.m, 3))
    np.testing.assert_allclose(
        np.asarray(base.backward(jnp.asarray(s), 0)),
        fou.split_backward_matrix(n) @ s,
        atol=1e-12,
    )
    # round trip through a Space1-style use
    np.testing.assert_allclose(
        np.asarray(base.backward(base.forward(jnp.asarray(v), 0), 0)), v, atol=1e-12
    )


def test_biperiodic_fast_matches_fft(force_fourstep):
    from rustpde_mpi_tpu.bases import BiPeriodicSpace2

    sp = BiPeriodicSpace2(32, 36, method="matmul")
    spf = BiPeriodicSpace2(32, 36, method="fft")
    assert sp._x_c2c_fwd is not None and sp._y_rfft_plan is not None
    rng = np.random.default_rng(6)
    v = rng.standard_normal((32, 36))
    a = np.asarray(sp.forward(jnp.asarray(v)))
    b = np.asarray(spf.forward(jnp.asarray(v)))
    np.testing.assert_allclose(a, b, atol=1e-13)
    np.testing.assert_allclose(np.asarray(sp.backward(jnp.asarray(a))), v, atol=1e-12)


@pytest.mark.slow
def test_navier_step_fast_vs_dense_transforms():
    """One full confined Navier2D step with the four-step transforms forced on
    matches the dense-transform step to near machine epsilon (the grid is
    below the auto gate, so default stays dense)."""
    import subprocess
    import sys
    import os
    import json

    code = r"""
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %r)
import jax
import numpy as np
from rustpde_mpi_tpu import Navier2D
m = Navier2D.new_confined(33, 33, 1e6, 1.0, 1e-3, 1.0, "rbc")
m.update_n(5)
print("OUT:" + json.dumps({
    "nu": m.eval_nu(), "t": np.asarray(m.state.temp).tolist()}))
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    results = {}
    for mode in ("1", "0"):
        env = dict(
            os.environ,
            RUSTPDE_X64="1",
            RUSTPDE_FOURSTEP=mode,
            RUSTPDE_FORCE_TPU_PATH="1",
            RUSTPDE_FAST_DERIV="1" if mode == "1" else "0",
        )
        res = subprocess.run(
            [sys.executable, "-c", code % repo],
            capture_output=True,
            text=True,
            env=env,
            timeout=600,
        )
        line = [ln for ln in res.stdout.splitlines() if ln.startswith("OUT:")]
        assert line, res.stderr[-2000:]
        results[mode] = json.loads(line[0][4:])
    np.testing.assert_allclose(
        np.asarray(results["1"]["t"]), np.asarray(results["0"]["t"]), atol=1e-11
    )
    assert abs(results["1"]["nu"] - results["0"]["nu"]) < 1e-9
