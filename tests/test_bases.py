"""Basis-layer tests: transform round-trips, differentiation of known
functions, Galerkin stencil identities, quasi-inverse identities.

Models the reference's inline solver tests + doc-tests (SURVEY.md S4), plus
the boundary conditions each composite base must satisfy by construction.
"""

import numpy as np
import pytest

import rustpde_mpi_tpu as rp
from rustpde_mpi_tpu.ops import chebyshev as chb


# ---------------------------------------------------------------------------
# transforms
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [8, 9, 33])
@pytest.mark.parametrize("method", ["fft", "matmul"])
def test_chebyshev_roundtrip(n, method):
    base = rp.chebyshev(n)
    rng = np.random.default_rng(0)
    u = rng.standard_normal(n)
    uh = base.forward(u, 0, method)
    back = base.backward(uh, 0, method)
    np.testing.assert_allclose(np.asarray(back), u, atol=1e-12)


def test_chebyshev_fft_matches_matmul():
    n = 17
    base = rp.chebyshev(n)
    rng = np.random.default_rng(1)
    u = rng.standard_normal((n, 5))
    a = np.asarray(base.forward(u, 0, "fft"))
    b = np.asarray(base.forward(u, 0, "matmul"))
    np.testing.assert_allclose(a, b, atol=1e-12)


def test_chebyshev_coefficients_of_polynomial():
    # u(x) = T_0 + 2 T_1 + 3 T_3  ->  exact coefficient recovery
    n = 9
    base = rp.chebyshev(n)
    x = base.points
    u = 1.0 + 2.0 * x + 3.0 * (4 * x**3 - 3 * x)
    uh = np.asarray(base.forward(u, 0, "fft"))
    expect = np.zeros(n)
    expect[0], expect[1], expect[3] = 1.0, 2.0, 3.0
    np.testing.assert_allclose(uh, expect, atol=1e-12)


@pytest.mark.parametrize("n", [8, 16])
def test_fourier_r2c_roundtrip(n):
    base = rp.fourier_r2c(n)
    rng = np.random.default_rng(2)
    u = rng.standard_normal(n)
    uh = base.forward(u, 0)
    back = np.asarray(base.backward(uh, 0))
    np.testing.assert_allclose(back, u, atol=1e-12)


def test_fourier_c2c_roundtrip():
    n = 12
    base = rp.fourier_c2c(n)
    rng = np.random.default_rng(3)
    u = rng.standard_normal(n) + 1j * rng.standard_normal(n)
    uh = base.forward(u, 0)
    back = np.asarray(base.backward(uh, 0))
    np.testing.assert_allclose(back, u, atol=1e-12)


# ---------------------------------------------------------------------------
# differentiation
# ---------------------------------------------------------------------------


def test_chebyshev_derivative_of_sin():
    n = 32
    base = rp.chebyshev(n)
    x = base.points
    u = np.sin(np.pi * x)
    uh = base.forward(u, 0, "fft")
    du = np.asarray(base.backward(base.gradient(uh, 1, 0), 0, "fft"))
    np.testing.assert_allclose(du, np.pi * np.cos(np.pi * x), atol=1e-8)
    d2u = np.asarray(base.backward(base.gradient(uh, 2, 0), 0, "fft"))
    np.testing.assert_allclose(d2u, -np.pi**2 * np.sin(np.pi * x), atol=1e-6)


def test_fourier_derivative_of_wave():
    n = 32
    base = rp.fourier_r2c(n)
    x = base.points
    u = np.cos(3 * x)
    uh = base.forward(u, 0)
    du = np.asarray(base.backward(base.gradient(uh, 1, 0), 0))
    np.testing.assert_allclose(du, -3 * np.sin(3 * x), atol=1e-10)


def test_space2_mixed_gradient_with_scale():
    nx, ny = 32, 33
    space = rp.Space2(rp.fourier_r2c(nx), rp.chebyshev(ny))
    scale = [2.0, 1.0]
    x = space.base_x.points * scale[0]
    y = space.base_y.points
    X, Y = np.meshgrid(x, y, indexing="ij")
    u = np.cos(2 * X / scale[0]) * np.sin(np.pi * Y)
    vhat = space.forward(u)
    dudx = np.asarray(space.backward(space.gradient(vhat, [1, 0], scale)))
    expect = -(2 / scale[0]) * np.sin(2 * X / scale[0]) * np.sin(np.pi * Y)
    np.testing.assert_allclose(dudx, expect, atol=1e-8)


def _space(layout: str):
    if layout == "periodic_fft":
        return rp.Space2(rp.fourier_r2c(16), rp.cheb_dirichlet(17), method="fft")
    if layout == "confined_fft":
        return rp.Space2(rp.cheb_dirichlet(17), rp.cheb_neumann(16), method="fft")
    if layout == "pure_fft":  # the pressure's space: both casts are the identity
        return rp.Space2(rp.chebyshev(17), rp.chebyshev(16), method="fft")
    return rp.Space2(rp.cheb_dirichlet(17), rp.cheb_neumann(16), method="matmul", sep=False)


@pytest.mark.parametrize("layout", ["periodic_fft", "confined_fft", "confined_matmul"])
def test_backward_gradient_of_a_space_with_no_sep_axis(layout):
    """No sep axis: the per-axis loop (derivative and synthesis along x, then
    along y) gives ``backward_ortho(gradient(.))``; the operators of the two
    axes commute."""
    space = _space(layout)
    assert not any(space.sep)
    vhat = space.forward(np.random.default_rng(4).standard_normal(space.shape_physical))
    for deriv in [(0, 0), (1, 0), (0, 1), (1, 1), (0, 2)]:
        got = np.asarray(space.backward_gradient(vhat, deriv, (1.0, 2.0)))
        want = np.asarray(space.backward_ortho(space.gradient(vhat, deriv, (1.0, 2.0))))
        np.testing.assert_allclose(got, want, atol=1e-11 * max(1.0, np.abs(want).max()))


@pytest.mark.parametrize("layout", ["periodic_fft", "confined_fft", "confined_matmul", "pure_fft"])
def test_gradient_into_a_space_is_its_from_ortho(layout):
    """``gradient(..., into=space)`` is ``space.from_ortho(gradient(...))``:
    the projection's velocity correction, cast inside the derivative's own
    visit to the layout in which y is local."""
    src = _space(layout)
    x_base = src.base_x if src.base_x.is_periodic else rp.cheb_dirichlet(src.base_x.n)
    dst = rp.Space2(x_base, rp.cheb_dirichlet(src.base_y.n), method=src.method, sep=False)
    vhat = src.forward(np.random.default_rng(5).standard_normal(src.shape_physical))
    for deriv in [(1, 0), (0, 1)]:
        got = np.asarray(src.gradient(vhat, deriv, (2.0, 0.5), into=dst))
        want = np.asarray(dst.from_ortho(src.gradient(vhat, deriv, (2.0, 0.5))))
        assert got.shape == dst.shape_spectral
        np.testing.assert_allclose(got, want, atol=1e-12 * max(1.0, np.abs(want).max()))


def _two_step_space(layout: str):
    """The three layouts a chip runs: confined (both axes sep), Fourier x
    Chebyshev (split Re/Im along x, no sep axis) and the mixed one (split
    along x, sep along y)."""
    if layout == "confined":
        return rp.Space2(rp.cheb_dirichlet(17), rp.cheb_neumann(16), method="matmul", sep=True)
    return rp.Space2(
        rp.fourier_r2c_split(16), rp.cheb_dirichlet(17), method="matmul", sep=layout == "mixed_sep"
    )


def _axis_by_axis(space, vhat, how, order=0):
    """The synthesis from the one-axis operators of the two bases, x then y
    (they commute): ``how`` names a base's ``backward`` or ``backward_ortho``,
    and a derivative is taken axis by axis before an ortho synthesis."""
    out = vhat
    for axis, base in enumerate(space.bases):
        a = out.ndim - 2 + axis
        if how == "gradient":
            out = base.gradient(out, order[axis], a, sep=space.sep[axis])
            out = base.backward_ortho(out, a, "matmul", sep=space.sep[axis])
        else:
            out = getattr(base, how)(out, a, "matmul", sep=space.sep[axis])
    return np.asarray(out)


@pytest.mark.parametrize("devices", [0, 4], ids=["one_device", "four_devices"])
@pytest.mark.parametrize("batch", [False, True], ids=["plain", "batched"])
@pytest.mark.parametrize("layout", ["confined", "periodic", "mixed_sep"])
def test_two_step_synthesis_is_every_synthesis(layout, batch, devices):
    """``synthesis_finish(synthesis_first(.))`` is ``backward``,
    ``backward_ortho`` and ``backward_gradient`` (orders 0 and 1 on either
    axis), each against the bases' one-axis operators; and ONE first-axis
    partial finished with order 0 and with order 1 along the second axis is
    the plain synthesis and that derivative's, which is what a velocity and
    its own convection chain share (models/navier.py).  On four devices a
    Fourier x Chebyshev space takes y first and flips once (x first on one
    device and on a confined space), so both orders are gone through."""
    import jax

    from rustpde_mpi_tpu.parallel.mesh import make_mesh, use_mesh

    space = _two_step_space(layout)
    scale = (2.0, 0.5)
    rng = np.random.default_rng(11)
    shape = ((3,) if batch else ()) + space.shape_spectral
    vhat = rng.standard_normal(shape)
    ortho = np.asarray(space.to_ortho(vhat))  # n coefficients an axis, not m

    def close(got, want):
        np.testing.assert_allclose(
            np.asarray(got), want, atol=1e-11 * max(1.0, np.abs(want).max())
        )

    with use_mesh(make_mesh(jax.devices()[:devices]) if devices else None):
        y_first = bool(devices) and layout != "confined"
        assert space.synthesis_axes == ((1, 0) if y_first else (0, 1))
        second = space.synthesis_axes[1]

        def two_step(deriv=None, scale=None, ortho=False, of=vhat):
            return jax.jit(
                lambda v: space.synthesis_finish(
                    space.synthesis_first(v, deriv, ortho=ortho), deriv, scale, ortho=ortho
                )
            )(of)

        close(two_step(), _axis_by_axis(space, vhat, "backward"))
        close(two_step(), np.asarray(jax.jit(space.backward)(vhat)))
        close(two_step(ortho=True, of=ortho), _axis_by_axis(space, ortho, "backward_ortho"))
        close(two_step(ortho=True, of=ortho), np.asarray(jax.jit(space.backward_ortho)(ortho)))
        for deriv in [(0, 0), (1, 0), (0, 1)]:
            want = _axis_by_axis(space, vhat, "gradient", deriv)
            want = want / (scale[0] ** deriv[0] * scale[1] ** deriv[1])
            close(two_step(deriv, scale), want)
            close(jax.jit(lambda v, d=deriv: space.backward_gradient(v, d, scale))(vhat), want)

        # one partial, two finishes
        unit = (int(second == 0), int(second == 1))

        @jax.jit
        def shared(v):
            partial = space.synthesis_first(v)
            return space.synthesis_finish(partial), space.synthesis_finish(partial, unit, scale)

        plain, derivative = shared(vhat)
        close(plain, _axis_by_axis(space, vhat, "backward"))
        close(derivative, _axis_by_axis(space, vhat, "gradient", unit) / scale[second])


# ---------------------------------------------------------------------------
# composite bases: boundary conditions + ortho casts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "factory", [rp.cheb_dirichlet, rp.cheb_neumann, rp.cheb_dirichlet_neumann]
)
def test_composite_roundtrip_via_ortho(factory):
    n = 16
    base = factory(n)
    rng = np.random.default_rng(4)
    comp = rng.standard_normal(base.m)
    ortho = base.to_ortho(comp, 0)
    back = np.asarray(base.from_ortho(ortho, 0))
    np.testing.assert_allclose(back, comp, atol=1e-10)


def test_dirichlet_basis_satisfies_bc():
    n = 12
    S = rp.cheb_dirichlet(n).stencil
    Tm1 = np.array([(-1.0) ** k for k in range(n)])  # T_k(-1)
    Tp1 = np.ones(n)  # T_k(1)
    np.testing.assert_allclose(Tm1 @ S, 0.0, atol=1e-12)
    np.testing.assert_allclose(Tp1 @ S, 0.0, atol=1e-12)


def test_neumann_basis_satisfies_bc():
    n = 12
    S = rp.cheb_neumann(n).stencil
    dTm1 = np.array([(-1.0) ** (k + 1) * k**2 for k in range(n)])  # T_k'(-1)
    dTp1 = np.array([float(k**2) for k in range(n)])  # T_k'(1)
    np.testing.assert_allclose(dTm1 @ S, 0.0, atol=1e-12)
    np.testing.assert_allclose(dTp1 @ S, 0.0, atol=1e-12)


def test_dirichlet_neumann_basis_satisfies_bc():
    n = 12
    S = rp.cheb_dirichlet_neumann(n).stencil
    Tm1 = np.array([(-1.0) ** k for k in range(n)])
    dTp1 = np.array([float(k**2) for k in range(n)])
    np.testing.assert_allclose(Tm1 @ S, 0.0, atol=1e-12)
    np.testing.assert_allclose(dTp1 @ S, 0.0, atol=1e-12)


def test_composite_forward_reproduces_bc_function():
    # a function that already satisfies dirichlet BCs is reproduced exactly
    n = 24
    base = rp.cheb_dirichlet(n)
    x = base.points
    u = np.sin(np.pi * x)
    uh = base.forward(u, 0, "fft")
    back = np.asarray(base.backward(uh, 0, "fft"))
    np.testing.assert_allclose(back, u, atol=1e-10)


# ---------------------------------------------------------------------------
# quasi-inverse identities (the contract the solver layer builds on)
# ---------------------------------------------------------------------------


def test_b2_is_quasi_inverse_of_d2():
    n = 16
    D2 = chb.diff_matrix(n, 2)
    B2 = chb.quasi_inverse_b2(n)
    prod = B2 @ D2
    np.testing.assert_allclose(prod[2:, :], np.eye(n)[2:, :], atol=1e-10)
    np.testing.assert_allclose(prod[:2, :], 0.0, atol=1e-12)


def test_helmholtz_operator_is_banded():
    # pinv @ S must be 4-banded with offsets (-2, 0, 2, 4) — the structure the
    # reference's Fdma kernel exploits (/root/reference/src/solver/fdma.rs).
    n = 16
    base = rp.cheb_dirichlet(n)
    S = base.mass()
    pinv = base.laplace_inv_eye() @ base.laplace_inv()
    A = pinv @ S
    m = A.shape[0]
    for i in range(m):
        for j in range(m):
            if j - i not in (-2, 0, 2, 4):
                assert abs(A[i, j]) < 1e-12, (i, j, A[i, j])


def test_dirichlet_neumann_operator_is_seven_banded():
    n = 16
    base = rp.cheb_dirichlet_neumann(n)
    S = base.mass()
    pinv = base.laplace_inv_eye() @ base.laplace_inv()
    A = pinv @ S
    m = A.shape[0]
    for i in range(m):
        for j in range(m):
            if j - i not in (-2, -1, 0, 1, 2, 3, 4):
                assert abs(A[i, j]) < 1e-12, (i, j, A[i, j])


@pytest.mark.slow
def test_space2_leading_batch_dims():
    """Space transforms/gradients/solvers are polymorphic over extra leading
    batch dims (stacked same-space fields) and match per-field application."""
    import jax.numpy as jnp

    from rustpde_mpi_tpu.solver import HholtzAdi, Poisson

    space = rp.Space2(rp.cheb_dirichlet(17), rp.cheb_dirichlet(16))
    rng = np.random.default_rng(7)
    a, b = rng.standard_normal((2, 17, 16))
    stacked_phys = jnp.stack([jnp.asarray(a), jnp.asarray(b)])
    fw = space.forward(stacked_phys)
    np.testing.assert_allclose(np.asarray(fw[0]), np.asarray(space.forward(a)), atol=1e-13)
    np.testing.assert_allclose(np.asarray(fw[1]), np.asarray(space.forward(b)), atol=1e-13)
    bw = space.backward(fw)
    np.testing.assert_allclose(np.asarray(bw[0]), np.asarray(space.backward(space.forward(a))), atol=1e-13)
    g = space.gradient(fw, (1, 1), (1.0, 1.0))
    np.testing.assert_allclose(
        np.asarray(g[1]), np.asarray(space.gradient(space.forward(b), (1, 1), (1.0, 1.0))), atol=1e-12
    )
    # identical-operator implicit solves, batched
    adi = HholtzAdi(space, (0.1, 0.1))
    rhs = jnp.stack([space.to_ortho(space.forward(a)), space.to_ortho(space.forward(b))])
    out = adi.solve(rhs)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(adi.solve(rhs[0])), atol=1e-12)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(adi.solve(rhs[1])), atol=1e-12)
    poi_space = rp.Space2(rp.cheb_neumann(17), rp.cheb_neumann(16))
    poi = Poisson(poi_space, (1.0, 1.0))
    rhs_n = jnp.stack([jnp.asarray(rng.standard_normal((17, 16))) for _ in range(2)])
    out = poi.solve(rhs_n)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(poi.solve(rhs_n[0])), atol=1e-11)
