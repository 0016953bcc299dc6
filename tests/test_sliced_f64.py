"""The float64 product of the TPU path as exact int8 slice products
(``ops/folded.py``):

* accuracy against an exact reference (``numpy.longdouble`` row sums) on the
  operator kinds of the 513 x 513 float64 cell, for physical values and for
  spectral coefficients decaying from 1 to 1e-16 along the contraction: never
  more than twice the error of numpy's own float64 product;
* the number of slices, from float64's 53 significand bits and the guard bits;
* whole models on the forced TPU path, sliced against XLA's own float64 dots;
* that a float32 program traces to the equations it traced before the sliced
  product existed (digests read at that tree), and what the 513 x 513 step
  counts.
"""

import json
import os
import subprocess
import sys
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rustpde_mpi_tpu import Navier2D, bases, config, solver
from rustpde_mpi_tpu.models.swift_hohenberg import SwiftHohenberg2D
from rustpde_mpi_tpu.ops import chebyshev as chb
from rustpde_mpi_tpu.ops import folded

pytestmark = pytest.mark.skipif(not config.X64, reason="float64 products")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 513  # the float64 cell's grid


# -- accuracy ---------------------------------------------------------------------


def _cell_operators():
    """Host matrices ``(mat, sep_in, sep_out, keep_rows)`` of the kinds the
    513 x 513 float64 step multiplies by, in the step's own sep layout."""
    vel, neu = bases.cheb_dirichlet(N), bases.cheb_neumann(N)
    _, fwd, bwd = solver._axis_modal_data(bases.Space2(neu, neu), 0, 1.0, 1.0)
    return {
        "synthesis": (chb.synthesis_matrix(N) @ vel.stencil, True, False, None),
        "analysis": (vel.projection @ chb.analysis_matrix(N), False, True, (2 * N) // 3),
        "helmholtz": (solver.hholtz_axis_solve_matrix(bases.Space2(vel, vel), 0, 0.01), True, True, None),
        "fastdiag_fwd": (fwd, True, True, None),
        "fastdiag_bwd": (bwd, True, True, None),
        "projection_gradient": (vel.projection @ neu.gradient_matrix(1), True, True, None),
        "trapezoid": (neu.gradient_matrix(1), True, True, None),
    }


def _product_groups(impl):
    """The host matrices of an impl's products, as ``place`` takes them: a
    fold's two halves (or two dense sep blocks) together, any other alone."""
    blocks = getattr(impl, "blocks", None)
    if blocks is not None:
        if all(b.kind == "plain" for b in blocks):
            return [[b.mat for b in blocks]]
        return [g for b in blocks for g in _product_groups(b)]
    if getattr(impl, "m_e", None) is not None:
        return [[impl.m_e, impl.m_o]]
    if getattr(impl, "mats", None) is not None:
        return [[m] for m in impl.mats]
    return [[impl.mat]] if getattr(impl, "mat", None) is not None else []


def _field(kind: str, k: int, rng) -> np.ndarray:
    if kind == "physical":
        return rng.uniform(-1.0, 1.0, (k, 6))
    decay = 10.0 ** (-16.0 * np.arange(k) / max(k - 1, 1))
    return rng.standard_normal((k, 6)) * decay[:, None]


def _rel(y, exact) -> float:
    return float(np.linalg.norm(np.asarray(y, np.longdouble) - exact) / np.linalg.norm(exact))


@pytest.mark.parametrize("field", ["physical", "spectral"])
@pytest.mark.parametrize(
    "kind",
    ["synthesis", "analysis", "helmholtz", "fastdiag_fwd", "fastdiag_bwd",
     "projection_gradient", "trapezoid"],
)
def test_sliced_product_is_as_accurate_as_a_float64_dot(kind, field):
    mat, sep_in, sep_out, keep = _cell_operators()[kind]
    impl = folded._detect(np.asarray(mat), sep_in, sep_out, keep, 8)
    if kind == "trapezoid":
        assert "trapezoid" in impl.kind, impl.kind
    groups = _product_groups(impl)
    assert groups, impl.kind
    rng = np.random.default_rng(abs(hash((kind, field))) % 2**32)
    place = folded._Place(jnp.asarray, sliced=True)
    for mats in groups:
        xs = [_field(field, m.shape[1], rng) for m in mats]
        op = place.group(*mats)
        got = folded._products(op, [jnp.asarray(x) for x in xs])
        for m, x, y in zip(mats, xs, got):
            exact = m.astype(np.longdouble) @ x.astype(np.longdouble)
            sliced, native = _rel(y, exact), _rel(m @ x, exact)
            assert sliced <= 2 * native, (kind, field, m.shape, sliced, native)


@pytest.mark.parametrize("kind", ["fastdiag_bwd", "projection_gradient", "trapezoid"])
def test_a_null_mode_ten_decades_up_sets_no_scale(kind):
    """The Neumann Poisson solve nudges its null mode (``lam - 1e-10``): the
    pseudo-pressure's constant mode comes out some ten decades above the
    others.  The modal map passes it through a column with one nonzero, and a
    gradient annihilates it (a column of zeros); neither may let it set the
    scale the other modes are cut to (``SlicedOperator.lone``, ``balance``):
    every row it does not land on is as accurate as a float64 dot's."""
    mat, sep_in, sep_out, keep = _cell_operators()[kind]
    impl = folded._detect(np.asarray(mat), sep_in, sep_out, keep, 8)
    rng = np.random.default_rng(11)
    place = folded._Place(jnp.asarray, sliced=True)
    spiked = 0
    for mats in _product_groups(impl):
        xs = [_field("spectral", m.shape[1], rng) for m in mats]
        for m, x in zip(mats, xs):
            if np.count_nonzero(m[:, 0]) <= 1:  # the mode lands on one row, or none
                x[0] *= 1e10
                spiked += 1
        got = folded._products(place.group(*mats), [jnp.asarray(x) for x in xs])
        for m, x, y in zip(mats, xs, got):
            rows = m[:, 0] == 0
            if not rows.any():  # a dense first column: the mode lands everywhere
                continue
            exact = m[rows].astype(np.longdouble) @ x.astype(np.longdouble)
            sliced, native = _rel(np.asarray(y)[rows], exact), _rel(m[rows] @ x, exact)
            assert sliced <= 2 * native, (kind, m.shape, sliced, native)
    assert spiked  # the cell's operator of this kind has such a column


def test_slices_carry_53_bits_and_the_guard_bits():
    """``SLICES`` digits of 7 bits hold float64's 53-bit significand and the
    10 guard bits the module's docstring derives, and no fewer would; a digit
    is an integer in [-64, 64] and the digits give back the value to
    2^-63; the int32 partials stay exact at the cell's widest contraction."""
    assert folded._GUARD_BITS == 10
    assert folded.SLICES == 9
    assert 7 * (folded.SLICES - 1) < 53 + folded._GUARD_BITS <= 7 * folded.SLICES
    x = np.random.default_rng(3).uniform(-0.5, 0.5, 4096)
    digits = folded._digits(x[None], np)
    assert digits.shape == (folded.SLICES, 4096)
    assert np.all(digits == np.round(digits)) and np.abs(digits).max() <= 64
    weights = np.ldexp(1.0, -7 * np.arange(1, folded.SLICES + 1))
    assert np.abs((weights[:, None] * digits).sum(axis=0) - x).max() <= 2.0**-64
    widest = (N + 1) // 2  # a fold's half of the 513-point axis
    assert folded.SLICES * widest * 64 * 96 < 2**31


# -- whole models on the forced TPU path ----------------------------------------------


def _confined(nx, ny):
    return lambda: Navier2D.new_confined(nx, ny, 1e5, 1.0, 0.01, 1.0, "rbc")


MODELS = {
    "confined 17 x 17": _confined(17, 17),
    "confined 24 x 21": _confined(24, 21),
    "periodic 16 x 17": lambda: Navier2D.new_periodic(16, 17, 1e5, 1.0, 0.01, 1.0, "rbc"),
    "swift-hohenberg 16 x 16": lambda: SwiftHohenberg2D(16, 16, 0.35, 0.02, 4.0),
}


def _ten_steps(build, monkeypatch):
    monkeypatch.setattr(bases, "_BASE_CACHE", weakref.WeakValueDictionary())
    model = build()
    model.init_random(0.1, seed=0)
    model.update_n(10)
    return model


@pytest.mark.parametrize("name", list(MODELS))
def test_sliced_step_agrees_with_float64_dots(name, monkeypatch):
    """Ten steps with every float64 product sliced against the same ten with
    XLA's own float64 dots (the forced TPU path either way): the same state
    to 1e-11."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    sliced = _ten_steps(MODELS[name], monkeypatch)
    assert sliced._step_products["sliced_products"] > 0
    assert sliced._step_products["f64_products"] == 0
    monkeypatch.setattr(folded, "_sliced", lambda itemsize: False)
    native = _ten_steps(MODELS[name], monkeypatch)
    assert native._step_products["sliced_products"] == 0
    assert native._step_products["f64_products"] == sliced._step_products["sliced_products"]
    for a, b in zip(jax.tree.leaves(sliced.state), jax.tree.leaves(native.state)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= 1e-11 * np.linalg.norm(b), name


# -- float32 programs, and the counts of the cell's step --------------------------------

FLOAT32 = """
import hashlib, json
import jax, jax.numpy as jnp
from rustpde_mpi_tpu import Navier2D, bases, solver
from rustpde_mpi_tpu.models.swift_hohenberg import SwiftHohenberg2D
from rustpde_mpi_tpu.ops import chebyshev as chb, folded, fourier as fou

def digest(jaxpr):
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]

out = {}
physics = (1e5, 1.0, 0.01, 1.0, "rbc")
out["confined 17 x 17"] = digest(Navier2D.new_confined(17, 17, *physics)._step_cc.jaxpr)
out["periodic 16 x 17"] = digest(Navier2D.new_periodic(16, 17, *physics)._step_cc.jaxpr)
out["swift-hohenberg 16 x 16"] = digest(SwiftHohenberg2D(16, 16, 0.35, 0.02, 4.0)._step_cc.jaxpr)
n = 257
vel = bases.cheb_dirichlet(n)
operators = {
    "synthesis 257": (chb.synthesis_matrix(n) @ vel.stencil, True, False, None),
    "analysis 257": (vel.projection @ chb.analysis_matrix(n), False, True, (2 * n) // 3),
    "helmholtz 257": (solver.hholtz_axis_solve_matrix(bases.Space2(vel, vel), 0, 0.01), True, True, None),
    "circular 256": (fou.dft_cos_matrix(256), False, False, None),
}
for name, (mat, sep_in, sep_out, keep) in operators.items():
    fm = folded.FoldedMatrix(mat, lambda m: jnp.asarray(m, jnp.float32), sep_in=sep_in,
                             sep_out=sep_out, keep_rows=keep)
    x = jax.ShapeDtypeStruct((mat.shape[1], 8), jnp.float32)
    out[name] = fm.kind + " " + digest(jax.make_jaxpr(lambda a: fm.apply(a, 0))(x).jaxpr)
out["products 513"] = Navier2D.new_confined(513, 513, *physics)._step_products
print(json.dumps(out))
"""

#: read at the tree before the sliced product (the parent of the change that
#: brought it), with this jax: a float32 program's equations are theirs
FLOAT32_DIGESTS = {
    "confined 17 x 17": "3f3f0a8d22ea13db",
    "periodic 16 x 17": "2c1de627c1424cfd",
    "swift-hohenberg 16 x 16": "76019cac13df189e",
    "synthesis 257": "synthesis_sep 12da5ad0cbbec1fc",
    "analysis 257": "analysis_sep_cut a4763e00a263f7ea",
    "helmholtz 257": "sep_preserve[plain,plain] db7ed0cb2cd2467b",
    "circular 256": "circ_both 704069ac0f274ee7",
}


@pytest.fixture(scope="module")
def float32_programs():
    env = dict(os.environ, JAX_PLATFORMS="cpu", RUSTPDE_FORCE_TPU_PATH="1", RUSTPDE_X64="0",
               PYTHONPATH=REPO)
    done = subprocess.run([sys.executable, "-c", FLOAT32], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_float32_programs_trace_as_before(float32_programs):
    got = {k: v for k, v in float32_programs.items() if k != "products 513"}
    assert got == FLOAT32_DIGESTS


def test_cell_step_states_its_float64_products_as_sliced_products(monkeypatch, float32_programs):
    """At 513 x 513 (a CPU count, nothing runs): the float64 step states as
    sliced products the products the float32 step states as float32 dots,
    leaves none to XLA's float64 dot, and needs at most ``SLICES`` int8
    products for each."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    monkeypatch.setattr(bases, "_BASE_CACHE", weakref.WeakValueDictionary())
    counts = Navier2D.new_confined(N, N, 1e5, 1.0, 0.01, 1.0, "rbc")._step_products
    f32 = float32_programs["products 513"]
    assert f32["f64_products"] == 0 and f32["f32_products"] == 84
    assert f32["sliced_products"] == f32["int8_products"] == 0
    assert counts["sliced_products"] == f32["f32_products"]
    assert counts["f64_products"] == counts["f32_products"] == 0
    assert 0 < counts["int8_products"] <= folded.SLICES * counts["sliced_products"]
    assert counts["reverses"] == f32["reverses"]

