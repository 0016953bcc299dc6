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
import types
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rustpde_mpi_tpu import Navier2D, bases, config, solver
from rustpde_mpi_tpu.models.swift_hohenberg import SwiftHohenberg2D
from rustpde_mpi_tpu.ops import chebyshev as chb
from rustpde_mpi_tpu.ops import folded
from rustpde_mpi_tpu.utils.jit import dot_generals_by_operand, equations

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


def _field(kind: str, k: int, rng, columns=None) -> np.ndarray:
    """Six columns of ``k`` values: physical, or spectral coefficients
    decaying from 1 to 1e-16 along the contraction; ``columns`` multiplies
    column j by ``columns[j]``."""
    if kind == "physical":
        x = rng.uniform(-1.0, 1.0, (k, 6))
    else:
        decay = 10.0 ** (-16.0 * np.arange(k) / max(k - 1, 1))
        x = rng.standard_normal((k, 6)) * decay[:, None]
    return x if columns is None else x * np.asarray(columns)[None, :]


def _rel(y, exact) -> float:
    return float(np.linalg.norm(np.asarray(y, np.longdouble) - exact) / np.linalg.norm(exact))


#: the operator kinds of the 513 x 513 float64 step (:func:`_cell_operators`)
KINDS = ["synthesis", "analysis", "helmholtz", "fastdiag_fwd", "fastdiag_bwd",
         "projection_gradient", "trapezoid"]


@pytest.mark.parametrize("field", ["physical", "spectral"])
@pytest.mark.parametrize("kind", KINDS)
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


# -- the scalings on the float32 pieces -------------------------------------------------


def _multiplied_digits(x, balance):
    """The field side with its scaling as a float64 product: the field scaled
    by ``balance * scale``, then cut into its pieces."""
    balance, scale = folded._column_scale(x, balance)
    x = x * jax.lax.convert_element_type(balance * scale, x.dtype)
    digits = sum(folded._digits(jnp.expand_dims(p, 1), jnp, axis=1) for p in folded._pieces(x))
    return digits.astype(jnp.int8), 1 / scale


def _multiplied_sum(partials, scale, column, k):
    """The output side with its scalings as float64 products: the float64
    sum of the pieces made first, then times the row's and column's scale."""
    whole, pieces = folded._carried(partials)
    total = whole.astype(jnp.float64)
    for piece in pieces:
        total = total + piece.astype(jnp.float64)
    rows = jnp.expand_dims(scale, tuple(range(2, total.ndim)))
    return total * (rows * column.astype(jnp.float64))


def _retraced(monkeypatch, **names):
    """:func:`folded.sliced_product` traced afresh: a new function object of
    the same code and name (jit's trace cache keys on the function), whose
    module names ``names`` replace while ``monkeypatch`` holds them."""
    for name, value in names.items():
        monkeypatch.setattr(folded, name, value)
    f = folded.sliced_product.__wrapped__
    fresh = types.FunctionType(f.__code__, f.__globals__, f.__name__, f.__defaults__, f.__closure__)
    return jax.jit(fresh, static_argnames=("lone", "rows", "row_major"))


def _multiplied_product(monkeypatch):
    """:func:`folded.sliced_product` with its scalings as float64 products
    (the two functions above)."""
    return _retraced(monkeypatch, _column_digits=_multiplied_digits, _recombine=_multiplied_sum)


def _both_forms(op, xs, monkeypatch):
    """The sliced product of ``op`` on ``xs`` as it is and with its
    scalings as float64 products (the oracle), the lone columns left out of
    both: their one-row update is the same code in either, and the CPU's
    compiler contracts it into a fused multiply-add with whichever product
    it meets."""
    args = (op.digits, op.scale, op.balance, tuple(jnp.asarray(x) for x in xs))
    got = folded.sliced_product(*args, lone=(), rows=op.rows)
    with monkeypatch.context() as m:
        want = _multiplied_product(m)(*args, lone=(), rows=op.rows)
    return [np.asarray(y) for y in got], [np.asarray(y) for y in want]


def _stacked(op, xs):
    """The fields ``xs`` stacked as the sliced product stacks them, padded
    with zeros to the operators' contraction."""
    k = op.digits.shape[-1]
    return np.stack([np.pad(v, [(0, k - v.shape[0])] + [(0, 0)] * (v.ndim - 1)) for v in xs])


def _normal_pieces(op, xs):
    """``(B, r, ...)``: where every scaled piece of the product is a normal
    float32.  On the field side a piece that has digits is at least 2^-64
    after scaling, so it is normal before it wherever the column's scale is
    at most 2^62; on the output side the smallest piece is 2^-56 of the
    row's and column's scales (the last run's weight), normal wherever they
    multiply to at least 2^-70."""
    x = _stacked(op, xs)
    _, scale = folded._column_scale(jnp.asarray(x), jnp.asarray(op.balance))
    column = 1 / np.asarray(scale, np.float64)
    rows = np.asarray(op.scale).reshape(op.scale.shape + (1,) * (x.ndim - 2))
    return (column >= 2.0**-62) & (rows * column >= 2.0**-70)


def _flushed(m):
    """What flushed pieces can move each output row of ``m``'s product: under
    2^-124 on the output side (four pieces, each under 2^-126), and on the
    field side under 2^-125 an element (two pieces) times the row's
    absolute sum."""
    return 2.0**-124 * (1 + np.abs(m).sum(axis=1))


def _bits(a):
    return np.asarray(a, np.float64).view(np.int64)


@pytest.mark.parametrize("field", ["physical", "spectral", "columns 1e-20 to 1e20"])
@pytest.mark.parametrize("kind", KINDS)
def test_scalings_on_the_pieces_are_bitwise_the_float64_products(kind, field, monkeypatch):
    """Applying the powers of two to the float32 pieces in float32, in place
    of float64 products on the field and on the sum, changes no bit of the
    sliced product wherever every scaled piece is a normal float32: a power
    of two scales a normal float32 exactly, the cut into pieces and each
    float64 sum's rounding with it.  That holds for every element of
    physical and decaying fields; of columns scaled from 1e-20 to 1e20 it
    leaves out the column under 2^-64 and the outputs whose row and column
    scales multiply to under 2^-70 (:func:`_normal_pieces`), where a scaled
    piece under 2^-126 may be flushed (the CPU flushes float32 subnormals,
    and the chip's float64 holds no word below 2^-126), which moves such an
    output by no more than :func:`_flushed`."""
    mat, sep_in, sep_out, keep = _cell_operators()[kind]
    impl = folded._detect(np.asarray(mat), sep_in, sep_out, keep, 8)
    rng = np.random.default_rng(abs(hash((kind, field))) % 2**32)
    columns = np.logspace(-20, 20, 6) if field.startswith("columns") else None
    place = folded._Place(jnp.asarray, sliced=True)
    normal = []
    for mats in _product_groups(impl):
        xs = [_field("physical" if field == "physical" else "spectral", m.shape[1], rng, columns)
              for m in mats]
        op = place.group(*mats)
        got, want = _both_forms(op, xs, monkeypatch)
        mask = _normal_pieces(op, xs)
        for b, (m, y, oracle) in enumerate(zip(mats, got, want)):
            inside = np.broadcast_to(mask[b, : y.shape[0]], y.shape)
            assert np.array_equal(_bits(y)[inside], _bits(oracle)[inside]), (kind, field, b)
            flushed = _flushed(m)[:, None] + 0 * y
            assert np.all(np.abs(y - oracle)[~inside] <= flushed[~inside]), (kind, field, b)
            normal.append(inside)
    normal = np.concatenate(normal)
    if columns is None:
        assert normal.all()
    else:  # only outputs of the columns at 1e-20 and 1e-12 fall outside
        assert normal[:, columns >= 1e-4].all() and not normal.all()


@pytest.mark.parametrize("kind", KINDS)
def test_columns_far_from_one_are_as_accurate_as_a_float64_dot(kind):
    """Field columns from 1e-30 up to 1e22, the top of the fields' range
    (the exponent read-off holds a field under 2^75, as it did before the
    scalings moved to the pieces): where a scaled piece falls below 2^-126
    it is flushed, which a column at 1e-30 meets on both sides.  The product
    stays within twice numpy's float64 product's error, normwise, and each
    column within that plus what the flushed pieces can move its outputs
    (:func:`_flushed`)."""
    mat, sep_in, sep_out, keep = _cell_operators()[kind]
    impl = folded._detect(np.asarray(mat), sep_in, sep_out, keep, 8)
    rng = np.random.default_rng(abs(hash(kind)) % 2**32)
    columns = np.logspace(-30, 22, 6)
    place = folded._Place(jnp.asarray, sliced=True)
    for mats in _product_groups(impl):
        xs = [_field("spectral", m.shape[1], rng, columns) for m in mats]
        got = folded._products(place.group(*mats), [jnp.asarray(x) for x in xs])
        for m, x, y in zip(mats, xs, got):
            exact = m.astype(np.longdouble) @ x.astype(np.longdouble)
            sliced, native = _rel(y, exact), _rel(m @ x, exact)
            assert sliced <= 2 * native, (kind, m.shape, sliced, native)
            err = np.linalg.norm(np.asarray(y, np.longdouble) - exact, axis=0)
            numpy_err = np.linalg.norm((m @ x).astype(np.longdouble) - exact, axis=0)
            flushed = np.linalg.norm(_flushed(m))
            assert np.all(err <= 2 * numpy_err + flushed), (kind, m.shape, err, numpy_err, flushed)


def test_a_sliced_product_states_no_float64_multiply(monkeypatch):
    """One sliced product's program holds no float64 multiply but the lone
    columns' one-row updates (which ``sliced_f64_multiplies`` leaves out),
    where the form with float64 products held three, each over a whole
    field; the integer ``P_0 + carry`` is exact in one float32 at the widest
    contraction of the float64 cells (a 1025-point Chebyshev axis), and a
    wider one splits it into two words, still bitwise the float64 sum."""
    mat, sep_in, sep_out, keep = _cell_operators()["fastdiag_bwd"]
    impl = folded._detect(np.asarray(mat), sep_in, sep_out, keep, 8)
    (mats, *_) = _product_groups(impl)
    op = folded._Place(jnp.asarray, sliced=True).group(*mats)
    assert op.lone  # the null mode's column
    xs = tuple(jax.ShapeDtypeStruct((m.shape[1], 6), jnp.float64) for m in mats)
    jaxpr = jax.make_jaxpr(lambda *xs: folded._products(op, xs))(*xs).jaxpr
    assert folded.sliced_products(jaxpr) == len(mats)
    assert folded.sliced_f64_multiplies(jaxpr) == 0
    muls = [e for e in equations(jaxpr) if e.primitive.name == "mul"
            and e.outvars[0].aval.dtype == np.float64]
    assert len(muls) == len(op.lone) and all(e.outvars[0].aval.shape[0] == 1 for e in muls)
    with monkeypatch.context() as m:
        fresh = _multiplied_product(m)
        old = jax.make_jaxpr(lambda *xs: fresh(op.digits, op.scale, op.balance, xs,
                                               lone=op.lone, rows=op.rows))(*xs).jaxpr
    assert folded.sliced_f64_multiplies(old) == 3
    assert folded._whole_bound(2 * N - 1) < 2**24
    # a contraction past ~2600 terms: the integer in two exact float32 words
    wide = np.random.default_rng(5).standard_normal((7, 3000))
    assert folded._whole_bound(wide.shape[1]) >= 2**24
    op = folded._Place(jnp.asarray, sliced=True).group(wide)
    x = np.random.default_rng(6).uniform(-1.0, 1.0, (3000, 4))
    (got,), (want,) = _both_forms(op, [x], monkeypatch)
    assert np.array_equal(_bits(got), _bits(want))


# -- one int8 product per slice pair ---------------------------------------------------

#: the runs of the field's slices in the block-Toeplitz form, the reference
RUNS = ((0, 5), (5, folded.SLICES))


def _toeplitz_partials(digits, field, row_major=False):
    """The partials as the block-Toeplitz form made them (in the layout the
    compiler chose: ``row_major`` is not read): for each run of
    the field's slices (``RUNS``) the operator's slices laid out as a block
    Toeplitz matrix (block (d, t) holds slice d - t, zero above the diagonal)
    and ONE int8 product over the rows the run reaches, whose stacked int32
    outputs are summed; 45 + 16 of the 81 blocks streamed."""
    zero = jnp.zeros_like(digits[:, 0])
    partials = [None] * folded.SLICES
    for t0, t1 in RUNS:
        blocks = jnp.stack([jnp.stack([digits[:, d - t] if t <= d else zero for t in range(t0, t1)], 2)
                            for d in range(t0, folded.SLICES)], 1)  # (B, SLICES - t0, r, t1 - t0, K)
        out = jax.lax.dot_general(blocks, field[:, t0:t1], (((3, 4), (1, 2)), ((0,), (0,))),
                                  preferred_element_type=jnp.int32)
        for d in range(t0, folded.SLICES):
            partials[d] = out[:, d - t0] if partials[d] is None else partials[d] + out[:, d - t0]
    return partials


def _sliced_groups(impl):
    """The host matrices of each sliced product an impl states, as ``place``
    takes them: :func:`_product_groups`, but a trapezoid block's strips
    together, one sliced product as in the step."""
    blocks = getattr(impl, "blocks", None)
    if blocks is not None and not all(b.kind == "plain" for b in blocks):
        return [g for b in blocks for g in _sliced_groups(b)]
    if getattr(impl, "mats", None) is not None:
        return [list(impl.mats)]
    return _product_groups(impl)


#: the operator kinds by the fields they meet, and one contraction past the
#: split of ``_whole_bound`` (3000 terms: the integer in two float32 words)
PAIR_CASES = [(kind, field) for kind in KINDS
              for field in ("physical", "spectral", "columns 1e-30 to 1e22")] + [("wide", "physical")]


@pytest.mark.parametrize("kind, field", PAIR_CASES)
def test_pair_products_are_bitwise_the_block_toeplitz_runs(kind, field, monkeypatch):
    """One int8 product for each slice pair, each partial summed where its
    pairs' products are made, gives the block-Toeplitz runs' float64 result
    bit for bit (int32 sums are exact in any order), lone columns included,
    on a parity fold's pair, a trapezoid's strips in one product, columns
    from 1e-30 to 1e22 and a contraction past the integer's split, with its
    partials held row-major or left to the compiler (``row_major``); and each
    partial is the int64 sum of ``A_s X_t`` over ``s + t = d``."""
    if kind == "wide":
        groups = [[np.random.default_rng(5).standard_normal((7, 3000))]]
        assert folded._whole_bound(3000) >= 2**24
    else:
        mat, sep_in, sep_out, keep = _cell_operators()[kind]
        impl = folded._detect(np.asarray(mat), sep_in, sep_out, keep, 8)
        assert ("trapezoid" in impl.kind) == (kind == "trapezoid"), impl.kind
        groups = _sliced_groups(impl)  # a trapezoid's strips in one product
    rng = np.random.default_rng(abs(hash((kind, field, "pairs"))) % 2**32)
    columns = np.logspace(-30, 22, 6) if field.startswith("columns") else None
    place = folded._Place(jnp.asarray, sliced=True)
    lone = 0
    for mats in groups:
        op = place.group(*mats)
        lone += len(op.lone)
        xs = tuple(jnp.asarray(_field(field if field == "physical" else "spectral", m.shape[1], rng, columns))
                   for m in mats)
        args = (op.digits, op.scale, op.balance, xs)
        with monkeypatch.context() as m:
            want = _retraced(m, _partials=_toeplitz_partials)(*args, lone=op.lone, rows=op.rows)
        field_digits, _ = folded._column_digits(jnp.asarray(_stacked(op, xs)), op.balance)
        a, x = np.asarray(op.digits, np.int64), np.asarray(field_digits, np.int64)
        for row_major in (False, True):
            got = folded.sliced_product(*args, lone=op.lone, rows=op.rows, row_major=row_major)
            for y, oracle in zip(got, want):
                assert np.array_equal(_bits(y), _bits(oracle)), (kind, field, row_major)
            partials = jax.jit(folded._partials, static_argnums=2)(op.digits, field_digits, row_major)
            for d, p in enumerate(partials):
                exact = sum(np.einsum("brk,bkn->brn", a[:, d - t], x[:, t]) for t in range(d + 1))
                assert p.dtype == jnp.int32 and np.array_equal(np.asarray(p), exact), (kind, field, d)
    assert lone > 0 if kind in ("fastdiag_bwd", "projection_gradient") else True


def test_one_call_states_an_int8_product_per_slice_pair(monkeypatch):
    """A sliced product states one int8 ``dot_general`` for each of the 45
    slice pairs ``s + t < SLICES`` and streams 45 operator slices for each of
    its operators (``int8_blocks``); the block-Toeplitz runs stated two and
    streamed 61."""
    assert len(folded.PAIRS) == 45 == len(set(folded.PAIRS))
    assert all(s + t < folded.SLICES for s, t in folded.PAIRS)
    mat, sep_in, sep_out, keep = _cell_operators()["helmholtz"]
    (mats,) = _product_groups(folded._detect(np.asarray(mat), sep_in, sep_out, keep, 8))
    op = folded._Place(jnp.asarray, sliced=True).group(*mats)
    xs = tuple(jax.ShapeDtypeStruct((m.shape[1], N), jnp.float64) for m in mats)
    jaxpr = jax.make_jaxpr(lambda *xs: folded._products(op, xs))(*xs).jaxpr
    assert dot_generals_by_operand(jaxpr) == {"int8": 45}
    assert folded.sliced_products(jaxpr) == len(mats) == 2
    assert folded.int8_blocks(jaxpr) == 45 * len(mats)
    with monkeypatch.context() as m:
        fresh = _retraced(m, _partials=_toeplitz_partials)
        runs = jax.make_jaxpr(lambda *xs: fresh(op.digits, op.scale, op.balance, xs,
                                                lone=op.lone, rows=op.rows))(*xs).jaxpr
    assert dot_generals_by_operand(runs) == {"int8": len(RUNS)}
    assert folded.int8_blocks(runs) == 61 * len(mats)


@pytest.mark.parametrize("kind", KINDS)
def test_an_operator_is_kept_as_its_nine_slices(kind):
    """``slice_operator`` keeps each operator as its ``SLICES`` int8 digit
    matrices, one byte an element a slice: 9 slices where the block-Toeplitz
    runs held 61 blocks of the same size."""
    mat, sep_in, sep_out, keep = _cell_operators()[kind]
    for mats in _sliced_groups(folded._detect(np.asarray(mat), sep_in, sep_out, keep, 8)):
        op = folded.slice_operator(mats)
        r, k = max(m.shape[0] for m in mats), max(m.shape[1] for m in mats)
        assert op.digits.dtype == np.int8 and op.digits.shape == (len(mats), folded.SLICES, r, k)
        assert op.digits.nbytes == len(mats) * 9 * r * k
        assert np.abs(op.digits).max() <= 64


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
    leaves none to XLA's float64 dot, states one int8 product for each slice
    pair of each of its 42 calls and streams 45 operator slices for each
    sliced operator (61 in the block-Toeplitz runs before); its sliced
    products state no float64 multiply, where with their scalings as float64
    products they stated three a call."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    monkeypatch.setattr(bases, "_BASE_CACHE", weakref.WeakValueDictionary())
    build = lambda: Navier2D.new_confined(N, N, 1e5, 1.0, 0.01, 1.0, "rbc")._step_products  # noqa: E731
    counts = build()
    f32 = float32_programs["products 513"]
    assert f32["f64_products"] == 0 and f32["f32_products"] == 84
    assert f32["sliced_products"] == f32["int8_products"] == f32["sliced_f64_multiplies"] == 0
    assert f32["int8_blocks"] == 0
    assert counts["sliced_products"] == f32["f32_products"]
    assert counts["f64_products"] == counts["f32_products"] == 0
    calls = counts["int8_products"] // len(folded.PAIRS)
    assert counts["int8_products"] == len(folded.PAIRS) * calls == 45 * 42
    assert counts["int8_blocks"] == len(folded.PAIRS) * counts["sliced_products"] == 45 * 84
    assert counts["reverses"] == f32["reverses"]
    assert counts["sliced_f64_multiplies"] == 0
    monkeypatch.setattr(folded, "sliced_product", _multiplied_product(monkeypatch))
    assert build()["sliced_f64_multiplies"] == 3 * calls == 126

