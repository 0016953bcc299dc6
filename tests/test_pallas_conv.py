"""Pallas fused convection chain: interpreter-mode parity suite.

Mirrors tests/test_pallas_banded.py's role: the kernel runs in Pallas
interpreter mode on the CPU CI mesh (natively on an attached TPU), so tier-1
exercises the fused chain on every layout without a chip.  Documented
tolerances: the kernel computes the same linear chain with one reassociation
(dense GEMMs vs folded half-GEMMs / FFT paths), so parity is fp-epsilon in
f64 and ~1e-5 relative in f32 / f64-hybrid.

Also covers the stable ``Base.axis_operator`` accessor (the fold-structure
source of truth the kernel builders consume) and the explicit ring-transpose
path beside ``jax.lax.all_to_all`` (parallel/decomp.py).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import rustpde_mpi_tpu as rp
from rustpde_mpi_tpu.bases import (
    Space2,
    cheb_dirichlet,
    cheb_dirichlet_neumann,
    chebyshev,
    fourier_r2c,
    fourier_r2c_split,
)
from rustpde_mpi_tpu.ops.pallas_conv import FusedConv


def _data(sp, seed=0):
    rng = np.random.default_rng(seed)
    nx, ny = sp.shape_physical
    ux = jnp.asarray(rng.standard_normal((nx, ny)))
    uy = jnp.asarray(rng.standard_normal((nx, ny)))
    vhat = sp.forward(jnp.asarray(rng.standard_normal((nx, ny))))
    return ux, uy, vhat


def _check(fc, ux, uy, vhat, atol, with_bc=False, seed=5):
    if with_bc:
        rng = np.random.default_rng(seed)
        nx, ny = fc.space_in.shape_physical
        bcx = jnp.asarray(rng.standard_normal((nx, ny)))
        bcy = jnp.asarray(rng.standard_normal((nx, ny)))
        args = (ux, uy, vhat, bcx, bcy)
    else:
        args = (ux, uy, vhat)
    ref = np.asarray(fc.reference(*args))
    out = np.asarray(fc.apply(*args))
    np.testing.assert_allclose(out, ref, atol=atol * max(1.0, np.abs(ref).max()))
    return out, ref


def test_confined_sep_layout(monkeypatch):
    """The TPU layout: sep Chebyshev x sep Chebyshev, matmul transforms."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    sp = Space2(cheb_dirichlet(33), cheb_dirichlet(33), method="matmul", sep=True)
    fs = Space2(chebyshev(33), chebyshev(33), method="matmul", sep=True)
    assert all(sp.sep) and all(fs.sep)
    fc = FusedConv(sp, fs, (1.0, 1.0))
    ux, uy, vhat = _data(sp)
    _check(fc, ux, uy, vhat, 1e-12)
    _check(fc, ux, uy, vhat, 1e-12, with_bc=True)


def test_confined_natural_layout_fft_reference():
    """Non-sep CPU-default layout (fft method): same linear operator, so
    the kernel still matches — the cross-method parity case."""
    sp = Space2(cheb_dirichlet(17), cheb_dirichlet(17))
    fs = Space2(chebyshev(17), chebyshev(17))
    assert not any(sp.sep)
    fc = FusedConv(sp, fs, (1.0, 2.0))
    ux, uy, vhat = _data(sp)
    _check(fc, ux, uy, vhat, 1e-12)


def test_periodic_complex_layout():
    """Complex r2c Fourier x Chebyshev (the CPU periodic layout): the
    kernel converts to split Re/Im planes at the chain boundary."""
    sp = Space2(fourier_r2c(16), cheb_dirichlet(17))
    fs = Space2(fourier_r2c(16), chebyshev(17))
    assert sp.spectral_is_complex
    fc = FusedConv(sp, fs, (1.0, 1.0))
    ux, uy, vhat = _data(sp)
    out, _ = _check(fc, ux, uy, vhat, 1e-12, with_bc=True)
    assert np.iscomplexobj(out)


def test_split_sep_layout(monkeypatch):
    """Split Re/Im Fourier x sep Chebyshev — the real multichip periodic
    layout (and the hc mixed-BC temp space rides the same path)."""
    monkeypatch.setenv("RUSTPDE_SEP", "1")
    sp = Space2(fourier_r2c_split(16), cheb_dirichlet(17), method="matmul", sep=True)
    fs = Space2(fourier_r2c_split(16), chebyshev(17), method="matmul", sep=True)
    assert sp.sep == (False, True)
    fc = FusedConv(sp, fs, (1.0, 1.0))
    ux, uy, vhat = _data(sp)
    _check(fc, ux, uy, vhat, 1e-12, with_bc=True)
    # mixed-BC y base (no parity structure -> conjugated dense operators)
    sp2 = Space2(fourier_r2c_split(16), cheb_dirichlet_neumann(17), method="matmul", sep=True)
    fc2 = FusedConv(sp2, fs, (1.0, 1.0))
    ux, uy, vhat = _data(sp2, seed=3)
    _check(fc2, ux, uy, vhat, 1e-12)


def test_dealias_mask_equivalence(monkeypatch):
    """The kernel's row-drop epilogue reproduces the 2/3-rule mask exactly:
    dead rows are hard zeros, live rows match the dense masked forward."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    sp = Space2(cheb_dirichlet(33), cheb_dirichlet(33), method="matmul", sep=True)
    fs = Space2(chebyshev(33), chebyshev(33), method="matmul", sep=True)
    fc = FusedConv(sp, fs, (1.0, 1.0))
    ux, uy, vhat = _data(sp)
    out = np.asarray(fc.apply(ux, uy, vhat))
    mask = fs.dealias_mask()
    assert np.all(out[mask == 0.0] == 0.0)
    assert np.any(out[mask == 1.0] != 0.0)
    np.testing.assert_array_equal(out * mask, out)


def test_f32_dtype():
    sp = Space2(cheb_dirichlet(17), cheb_dirichlet(17))
    fs = Space2(chebyshev(17), chebyshev(17))
    fc32 = FusedConv(sp, fs, (1.0, 1.0), cast=np.float32)
    ux, uy, vhat = _data(sp)
    ref = np.asarray(fc32.reference(ux, uy, vhat))
    out = np.asarray(fc32.apply(ux.astype(np.float32), uy.astype(np.float32),
                                vhat.astype(np.float32)))
    assert out.dtype == np.float32
    np.testing.assert_allclose(out, ref, atol=2e-5 * max(1.0, np.abs(ref).max()))


def test_f64_hybrid_cast(monkeypatch):
    """RUSTPDE_F64_HYBRID=1 convention: f32-stored matrices, f64 state cast
    through the chain — f64 in/out dtype, f32-level agreement."""
    from rustpde_mpi_tpu.ops.pallas_conv import hybrid_cast

    monkeypatch.setenv("RUSTPDE_F64_HYBRID", "1")
    assert hybrid_cast() == np.float32
    sp = Space2(cheb_dirichlet(17), cheb_dirichlet(17))
    fs = Space2(chebyshev(17), chebyshev(17))
    fc = FusedConv(sp, fs, (1.0, 1.0), cast=hybrid_cast())
    ux, uy, vhat = _data(sp)
    ref = np.asarray(fc.reference(ux, uy, vhat, fast=False))
    out = np.asarray(fc.apply(ux, uy, vhat))
    assert out.dtype == np.float64
    np.testing.assert_allclose(out, ref, atol=2e-5 * max(1.0, np.abs(ref).max()))


def test_vmapped_ensemble_batching():
    """vmap over the kernel == per-member applies (the ensemble engine's
    batched dispatch re-vmaps the step jaxpr through the pallas_call)."""
    sp = Space2(cheb_dirichlet(17), cheb_dirichlet(17))
    fs = Space2(chebyshev(17), chebyshev(17))
    fc = FusedConv(sp, fs, (1.0, 1.0))
    rng = np.random.default_rng(0)
    K = 3
    ux = jnp.asarray(rng.standard_normal((K, 17, 17)))
    uy = jnp.asarray(rng.standard_normal((K, 17, 17)))
    vhat = jnp.stack(
        [sp.forward(jnp.asarray(rng.standard_normal((17, 17)))) for _ in range(K)]
    )
    batched = np.asarray(jax.vmap(fc.apply)(ux, uy, vhat))
    solo = np.stack(
        [np.asarray(fc.apply(ux[k], uy[k], vhat[k])) for k in range(K)]
    )
    np.testing.assert_array_equal(batched, solo)


# -- model integration (RUSTPDE_CONV_KERNEL knob) -----------------------------


def _build_navier(periodic, **kw):
    nx, ny = (16, 17) if periodic else (17, 17)
    m = rp.Navier2D(nx, ny, 1e4, 1.0, 5e-3, 1.0, "rbc", periodic=periodic, **kw)
    m.set_velocity(0.1, 1.0, 1.0)
    m.set_temperature(0.1, 1.0, 1.0)
    return m


@pytest.mark.parametrize("periodic", [False, True])
def test_navier_step_knob_parity(monkeypatch, periodic):
    """RUSTPDE_CONV_KERNEL=pallas: 5-step trajectories match the dense
    chain at fp-epsilon (documented tolerance 1e-13 absolute, f64)."""
    dense = _build_navier(periodic)
    dense.update_n(5)
    monkeypatch.setenv("RUSTPDE_CONV_KERNEL", "pallas")
    pal = _build_navier(periodic)
    assert pal._conv_impl is not None
    pal.update_n(5)
    for attr in ("temp", "velx", "vely", "pres", "pseu"):
        np.testing.assert_allclose(
            np.asarray(getattr(pal.state, attr)),
            np.asarray(getattr(dense.state, attr)),
            atol=1e-13,
            err_msg=attr,
        )
    assert pal.eval_nu() == pytest.approx(dense.eval_nu(), abs=1e-12)


def test_navier_ensemble_knob_parity(monkeypatch):
    """The vmapped ensemble dispatch rides the kernel path unchanged."""
    monkeypatch.setenv("RUSTPDE_CONV_KERNEL", "pallas")
    model = _build_navier(False)
    ens = rp.NavierEnsemble.from_seeds(model, seeds=range(2))
    ens.update_n(3)
    assert ens.alive().all()
    solo = _build_navier(False)
    solo.init_random(0.1, seed=0)
    solo.update_n(3)
    np.testing.assert_allclose(
        np.asarray(ens.state.temp[0]), np.asarray(solo.state.temp), atol=1e-13
    )


def test_recompile_flat_across_knob_flips(monkeypatch):
    """The knob binds at model build: flipping RUSTPDE_CONV_KERNEL under a
    LIVE model must not leak rebuilds (recompile_count stays flat) and must
    not change which path the live model runs."""
    dense = _build_navier(False)
    monkeypatch.setenv("RUSTPDE_CONV_KERNEL", "pallas")
    pal = _build_navier(False)
    before = (dense.recompile_count, pal.recompile_count)
    monkeypatch.setenv("RUSTPDE_CONV_KERNEL", "dense")
    pal.update_n(4)
    monkeypatch.setenv("RUSTPDE_CONV_KERNEL", "pallas")
    dense.update_n(4)
    assert (dense.recompile_count, pal.recompile_count) == before
    assert dense._conv_impl is None and pal._conv_impl is not None


def test_axis_operator_accessor():
    """The stable (matrix, parity, dealias_rows) accessor reproduces the
    private folded device applies exactly — one source of truth for the
    fold structure."""
    rng = np.random.default_rng(0)
    b = cheb_dirichlet(17)
    for sep in (False, True):
        for key in ("fwd", "bwd", "synthesis", ("bwd_grad", 1)):
            op = b.axis_operator(key, sep=sep)
            assert op.parity in ((False, False), (False, True), (True, False))
            x = rng.standard_normal((op.matrix.shape[1], 3))
            if sep:
                fm = b._sep_dev(key)
                ref = np.asarray(fm.apply(jnp.asarray(x), 0))
            else:
                if key == "fwd":
                    ref = np.asarray(b.forward(jnp.asarray(x), 0, "matmul"))
                elif key == "bwd":
                    ref = np.asarray(b.backward(jnp.asarray(x), 0, "matmul"))
                elif key == "synthesis":
                    ref = np.asarray(b.backward_ortho(jnp.asarray(x), 0, "matmul"))
                else:
                    ref = np.asarray(
                        b.backward_ortho(b.gradient(jnp.asarray(x), 1, 0), 0, "matmul")
                    )
            np.testing.assert_allclose(op.matrix @ x, ref, atol=1e-11)
    # dealias cut bookkeeping
    op = b.axis_operator("fwd_cut", sep=True)
    assert op.dealias_rows == b.m * 2 // 3
    kept = op.kept_rows
    from rustpde_mpi_tpu.ops.folded import parity_perm

    assert np.array_equal(np.sort(parity_perm(b.m)[kept]), np.arange(op.dealias_rows))


# -- explicit ring transpose (parallel/decomp.py) -----------------------------


def test_ring_transpose_matches_all_to_all():
    """The shift-permute ring body is value-identical to the tiled
    all_to_all on the virtual mesh, both directions, odd extents included."""
    from rustpde_mpi_tpu.parallel import make_mesh
    from rustpde_mpi_tpu.parallel.decomp import Decomp2d

    mesh = make_mesh()
    for shape in [(16, 16), (33, 17)]:
        d = Decomp2d(shape, mesh)
        a = jnp.asarray(np.random.default_rng(1).standard_normal(shape))
        for x2y in (True, False):
            go = d.transpose_x_to_y if x2y else d.transpose_y_to_x
            ref = np.asarray(go(a, method="alltoall"))
            ring = np.asarray(go(a, method="ring"))
            np.testing.assert_array_equal(ref, np.asarray(a))
            np.testing.assert_array_equal(ring, ref)


def test_ring_transpose_knob_roundtrip(monkeypatch):
    """RUSTPDE_TRANSPOSE=ring routes the default path; x2y∘y2x == id."""
    from rustpde_mpi_tpu.parallel import make_mesh
    from rustpde_mpi_tpu.parallel.decomp import Decomp2d, transpose_method

    monkeypatch.setenv("RUSTPDE_TRANSPOSE", "ring")
    assert transpose_method() == "ring"
    d = Decomp2d((24, 16), make_mesh())
    a = jnp.asarray(np.random.default_rng(2).standard_normal((24, 16)))
    np.testing.assert_array_equal(
        np.asarray(d.transpose_y_to_x(d.transpose_x_to_y(a))), np.asarray(a)
    )


def test_manual_conv_region_matches_dense(monkeypatch):
    """parallel/decomp.ShardedConv (the manual split-sep region) == the
    serial dense chain, under both transpose methods."""
    from rustpde_mpi_tpu.parallel import make_mesh, use_mesh
    from rustpde_mpi_tpu.parallel.decomp import ShardedConv

    monkeypatch.setenv("RUSTPDE_SEP", "1")
    sp = Space2(fourier_r2c_split(16), cheb_dirichlet(17), method="matmul")
    fs = Space2(fourier_r2c_split(16), chebyshev(17), method="matmul")
    fc = FusedConv(sp, fs, (1.0, 1.0))  # serial reference chain
    ux, uy, vhat = _data(sp)
    ref = np.asarray(fc.reference(ux, uy, vhat))
    mesh = make_mesh()
    for method in ("alltoall", "ring"):
        monkeypatch.setenv("RUSTPDE_TRANSPOSE", method)
        sc = ShardedConv(sp, fs, (1.0, 1.0), mesh)
        with use_mesh(mesh):
            out = np.asarray(jax.jit(sc.apply)(ux, uy, vhat))
        np.testing.assert_allclose(out, ref, atol=1e-13, err_msg=method)
