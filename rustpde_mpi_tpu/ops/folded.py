"""Parity-folded matrix application: two half-size GEMMs instead of one.

Every Chebyshev operator in this framework inherits the even/odd symmetry of
the basis — the same structure the reference exploits with its stride-2
banded solvers (/root/reference/src/solver/tdma.rs:49-82, offsets (-2,0,2)).
On TPU the equivalent trick halves the MXU flops of the dense transforms:

* physical<->spectral matrices satisfy a reflection symmetry
  (``M[j, n-1-i] = (-1)^j M[j, i]`` for analysis-type, transposed for
  synthesis-type), so folding the physical side into symmetric/antisymmetric
  halves turns one (r x n) GEMM into an (r_e x ~n/2) + (r_o x ~n/2) pair;
* spectral->spectral operators (derivative matrices, implicit-solve
  inverses) are checkerboard-sparse (``M[j, k] = 0`` unless ``j + k + s``
  is even), foldable the same way by index parity.

When a fold engages.  Detection is numerical at build time; matrices without
the structure (e.g. the mixed Dirichlet-Neumann base's operators) fall back
to the plain GEMM.  A matrix WITH a reflection structure (Chebyshev analysis /
synthesis) is folded only where its smaller extent reaches ``_FOLD_MIN_DIM``
(the circular Fourier folds have a gate of their own, ``_CIRC_MIN_DIM``): the
MXU's cost counts in 128-wide tiles, so while the two parity blocks are a
half-empty tile each they stream as many rows through the MXU as the one
plain product does, and the fold's full-array reverse, slice pair, add,
subtract and concatenate stay.  Measured on a v5e (PERF.md section 6, PR 34):
the 8-member 129^2 ensemble step spent 56 of its 248 us in 20 stand-alone
reverses on folds that saved no MXU pass, and runs 23.6 % shorter plain; at
193^2 plain is still 19 % shorter; at 257^2, where a block fills a whole
tile, the fold wins by 11 % (1 % for a single field); at 513^2 the blocks
are two to three tiles and the fold halves real work.  Below the gate the
operator is ONE plain product with any sep permutation baked into the host
matrix, the dealias-dead rows still dropped from it, and the fold's
precision hook kept.  In float64 the fold engages at every size: measured
while XLA emulated each float64 dot, a product's cost was cutting its field
operand into float32 pieces and the plain form ran 6-26 % slower from 129^2
to 257^2 (same section).  The gate reads the operator's shape and the
itemsize of the arithmetic its products run in, and nothing else.  The
checkerboard operators of the sep layout have the same kind of gate, lower
(``_SEP_MIN_DIM``): two DENSE parity blocks are one product over the whole
matrix, exact zeros included, below the 193-point grid (129^2 and 128 x 57:
13 % and 22 % of the step, same section); banded and trapezoid blocks are
not touched.

Float64 products on the TPU path.  The chip has no float64 unit, and a
float64 ``dot_general`` left to XLA is emulated by cutting both operands into
float32 pieces once for every product.  Where an operator's products run in
float64 (itemsize 8) on the TPU path (``config.is_tpu_like()``), each is
instead a sliced product (section below): the operator cut on the host at
build into int8 slices, the field cut on the device in one pass, exact
int8 x int8 -> int32 products on the MXU, one float64 recombination.  The
fold gates still read itemsize 8: a sliced product's cost is the field's
slicing and the int8 work, both of which the fold halves as it halved the
emulation's, and a fold's two halves are one sliced product; the gates were
not measured again.  ``SLICES`` (9 slices of 7 bits) comes from float64's 53
significand bits and 10 guard bits for the exponent spread that the
power-of-two scaling of rows and columns leaves (tests/test_sliced_f64.py).
Float32 products, and float64 products off the TPU path (the CPU's native
float64), are XLA's own dots.

Folded and plain paths agree to machine epsilon (tests/test_folded.py) —
each output element is the same reduction, reassociated only across the
explicitly-zero half of the terms (reflection folds: across the two parity
halves).

Enable/disable with RUSTPDE_FOLDED (default on).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from .. import config

# Structure detection tolerance.  Every foldable matrix in this framework is
# built with its symmetry *exact* (mirror-constructed transform matrices,
# parity-blocked eigendecompositions, analytically banded operators), so the
# tolerance only needs to absorb true floating-point zeros that are written
# as ~1-ulp garbage (e.g. sin(pi*k) at a Nyquist column).  At 1e-11 a
# near-symmetric matrix could be folded and silently perturbed; 1e-14 keeps
# the folded/plain agreement at genuine machine epsilon.
_ATOL = 1e-14
# Reflection folds (Chebyshev analysis / synthesis) engage where the
# operator's smaller extent reaches this, by the itemsize of the arithmetic
# its products run in.  Each gate stands at the smallest extent at which the
# split form measured faster on a v5e; the plain form measured faster at
# every smaller size tried (module docstring; PERF.md section 6, PR 34).
# float32: 255, the 257-point grid's, whose plain product needs a third
# 128-wide tile (8 members: plain 19 % faster at 191, fold 11 % faster at
# 255).  float64, which the chip emulates: always, because there a product's
# cost is cutting its field operand into float32 pieces, and the plain form
# measured 6-26 % slower at every size tried (127, 191, 255).
_FOLD_MIN_DIM = {4: 255, 8: 4}
# The same for the two dense parity blocks of a spectral->spectral operator
# (`_SepBoth` over two `_Plain` blocks: the Helmholtz inverses, the fast-diag
# modal maps, dense derivative and projection operators).  float32: 191, the
# 193-point grid's (8 members: one product over the whole checkerboard
# matrix, exact zeros included, 13 % of the step faster at the 129-point
# grid and 22 % at 128 x 57; the blocks 10 % faster at 191).  float64:
# always (the plain form 25 % slower at the 129-point grid).
_SEP_MIN_DIM = {4: 191, 8: 4}
_CIRC_MIN_DIM = 256  # circular folds engage only for large transforms
_MAX_BAND_OFFSETS = 8  # banded shift-apply engages up to this many diagonals


def folding_enabled() -> bool:
    return config.env_get("RUSTPDE_FOLDED", "1") != "0"


# ---------------------------------------------------------------------------
# Parity-separated ("sep") spectral layout
# ---------------------------------------------------------------------------
#
# The folded applies above still pay strided gathers (``x[0::2]``), full-array
# reverses and interleave scatters around every GEMM.  In the sep layout a
# spectral axis of length m is stored parity-permuted — ``[0,2,4,...,1,3,...]``
# (evens then odds) — so every parity-structured operator acts on *contiguous
# slices* and reassembles with a concat (which XLA fuses into the output
# buffers): zero data-movement passes.  The physical side keeps natural order
# (elementwise products, masks, observables unchanged); analysis-type applies
# produce sep output directly (concat instead of interleave), synthesis-type
# consume it directly (slices instead of strided gathers).  This is the
# layout-level completion of the reference's stride-2 structure
# (/root/reference/src/solver/tdma.rs:49-82).


def parity_perm(m: int) -> np.ndarray:
    """Natural -> sep order: position p holds natural index perm[p]."""
    return np.concatenate([np.arange(0, m, 2), np.arange(1, m, 2)])


class AxisOperator(NamedTuple):
    """One per-axis transform operator in its *storage layout* — the stable
    accessor contract the fused-kernel builders consume (ops/pallas_conv.py,
    the manual-sharding conv region in parallel/decomp.py) instead of
    reaching into the private folding internals above.

    * ``matrix`` — dense host matrix equal, element for element, to what the
      folded/sep device applies compute: sep permutations baked into the
      rows/columns, dealias-dead output rows zeroed.  Applying it with one
      plain GEMM reproduces the folded apply exactly up to floating-point
      reassociation (the folds are lossless).
    * ``parity`` — ``(sep_in, sep_out)``: which sides are stored in the
      parity-separated order (ops/folded.py sep layout).
    * ``dealias_rows`` — number of kept NATURAL-order output rows under the
      2/3-rule cut (None: no cut baked in).
    * ``kept_rows`` — storage-layout indices of the rows that stay nonzero
      under the cut (None: all rows); the contiguous-run structure a kernel
      epilogue uses to drop the dead rows from its GEMM and zero-fill the
      output."""

    matrix: np.ndarray
    parity: tuple
    dealias_rows: int | None
    kept_rows: np.ndarray | None


def dense_operator(
    mat: np.ndarray,
    sep_in: bool = False,
    sep_out: bool = False,
    keep_rows: int | None = None,
) -> np.ndarray:
    """The dense storage-layout matrix equivalent to
    ``FoldedMatrix(mat, sep_in=…, sep_out=…, keep_rows=…)`` — THE single
    source of truth for how the sep layout permutes operator matrices (the
    same conjugation `_detect_sep` applies to unstructured fallbacks).
    Dead dealias rows are zeroed in NATURAL order before any permutation,
    exactly like the ``keep_rows`` row-drop of `_AnalysisSep`."""
    mat = np.asarray(mat)
    r, c = mat.shape
    if keep_rows is not None and keep_rows < r:
        mat = np.where(np.arange(r)[:, None] < max(0, keep_rows), mat, 0.0)
    if sep_out:
        mat = mat[parity_perm(r), :]
    if sep_in:
        mat = mat[:, parity_perm(c)]
    return mat


def pad_dense(mat: np.ndarray, rows: int, cols: int) -> np.ndarray:
    """Zero-pad a host operator matrix to ``(rows, cols)`` — the one shared
    tile-padding helper of the fused-kernel builders (zero rows/columns are
    mathematically inert through the linear chains)."""
    mat = np.asarray(mat)
    out = np.zeros((rows, cols), dtype=mat.dtype)
    out[: mat.shape[0], : mat.shape[1]] = mat
    return out


def kept_storage_rows(r: int, keep_rows: int, sep_out: bool) -> np.ndarray:
    """Storage-layout row indices that survive a ``keep_rows`` natural-order
    prefix cut: ``arange(keep_rows)`` in natural order; under the sep
    permutation the kept rows form one contiguous run per parity block."""
    if not sep_out:
        return np.arange(max(0, min(keep_rows, r)))
    return np.where(parity_perm(r) < keep_rows)[0]


def parity_perm_inv(m: int) -> np.ndarray:
    """Sep -> natural: position i holds sep position of natural index i."""
    return np.argsort(parity_perm(m))


def _move(a, axis):
    return jnp.moveaxis(a, axis, 0)


def _unmove(a, axis):
    return jnp.moveaxis(a, 0, axis)


# ---------------------------------------------------------------------------
# Float64 products as exact int8 slice products
# ---------------------------------------------------------------------------
#
# The chip has no float64 unit: XLA stores a float64 as a pair of float32
# (high, low) and emulates a float64 dot by cutting both operands into
# float32 pieces, once for every product that reads them.  On the TPU path a
# float64 product is instead stated here, error-free on the MXU's int8 unit
# (Ozaki's splitting).  Each operand is scaled by powers of two (the operator
# per output row, the field per output column, both along the contraction;
# the operator's columns balanced against the field's rows first) to
# magnitudes under 1/2 and cut into ``SLICES`` balanced base-128 digits,
# integers in [-64, 64]: ``x = sum_s d_s 2^(-7(s+1))`` up to 2^(-7 SLICES).
# The operator is cut once, on the host, at build; a field once per product,
# in one vectorised pass over its float32 pieces, which its powers of two
# scale in float32.
#
# The slice pairs (s, t) with s + t = d make the partial ``P_d``, exact in
# int32 ((d + 1) K terms of at most 64 x 96).  The pairs with
# s + t >= SLICES are left out: each is below 2^(-7 SLICES) of the row's and
# the column's scale, as is what the cut leaves of either operand.  Each of
# the 45 pairs that are left is one int8 product of an operator slice and a
# field slice, and each partial is the int32 sum of its pairs' products,
# carried (:func:`_carried`) where it is made: the compiler sums and carries
# in the products' own output fusions, and no stack of partials is written
# out and read back.  The carried partials are turned into float64 in four
# exact float32 pieces, each scaled by the row's and the column's powers
# of two in float32, and three float64 additions.  A sliced product states no
# float64 multiply (the lone columns' one-row updates aside): the chip would
# emulate each as a general two-word product over a whole field, where a
# power of two scales a normal float32 exactly.  So the result is bitwise that
# of scaling the operand and the sum in float64 wherever every scaled piece is
# a normal float32; a piece under 2^-126 is flushed, as the chip's float64
# flushes its low word there (``_recombine``).
#
# A parity fold's two halves (and a trapezoid block's strips) are one sliced
# product over a batch (the smaller operands padded with zeros), so a fold
# states one program where it states two dots.

#: bits of one slice: a balanced digit in [-64, 64] (int8)
_SLICE_BITS = 7
#: guard bits beyond float64's 53-bit significand: the exponent spread that
#: the scaling leaves.  An operand's element below its row's (column's)
#: largest keeps its bits down to 2^-63 of that largest, and the K terms that
#: the cut and the dropped slice pairs perturb at that level sum to under
#: 2^-53 of the product's scale for every K up to 2^10 = 1024 (at random
#: signs, far beyond)
_GUARD_BITS = 10
#: slices an operand is cut into, set by accuracy alone: 53 + 10 bits, 9 x 7
SLICES = -(-(53 + _GUARD_BITS) // _SLICE_BITS)
#: the slice pairs ``(s, t)``, operator slice s and field slice t, that make
#: the partials: ``s + t < SLICES``, each one int8 product (see above)
PAIRS = tuple((d - t, t) for d in range(SLICES) for t in range(d + 1))
#: the ``jax.jit`` name of the sliced product: one traced program for each
#: signature, and what the step's ``sliced_products`` count reads
SLICED_PRODUCT = "sliced_product"


class SlicedOperator:
    """One float64 operator ``(r, K)``, or several applied side by side (a
    parity fold's two halves, a trapezoid block's strips), as the sliced
    product reads them: ``digits`` holds the ``SLICES`` int8 digit
    matrices ``(B, SLICES, r, K)`` of the operators, most significant first,
    ``scale`` the float64 ``(B, r)`` power of two of each row times 2^-14
    (the weight of the partial ``P_0``), ``balance`` the float32 ``(B, K)``
    power of two of each column relative to the largest, in [2^-48, 1] (0
    for a column of zeros), which multiplies the field's row instead;
    ``rows`` and ``cols`` the shape of each of the ``B`` operators, before
    the padding that stacks them.  ``lone`` names
    the columns with a single nonzero, ``(b, row, col, value)`` each, which
    the sliced product leaves out (their ``balance`` is 0) and adds back as
    ``y[b, row] += value x[b, col]`` in float64: a field's mode that
    such a column passes through, the nudged null mode of a Neumann solve,
    may be ten decades above the rest and would otherwise set the scale
    every other mode is cut to."""

    __slots__ = ("digits", "scale", "balance", "lone", "rows", "cols")

    def __init__(self, digits, scale, balance, lone, rows, cols):
        self.digits, self.scale, self.balance = digits, scale, balance
        self.lone, self.rows, self.cols = lone, rows, cols


def _digits(x, xp, axis: int = 0):
    """The ``SLICES`` balanced base-128 digits of ``x`` (|x| < 1/2, any real
    dtype whose products by 2^(7 s) are exact) along ``axis``, where ``x``
    has an axis of extent 1, most significant first:
    ``d_s = round(x 2^(7(s+1))) - 128 round(x 2^(7s))``, each an integer in
    [-64, 64], exactly, and ``sum_s d_s 2^(-7(s+1))`` over all ``SLICES`` is
    ``x`` rounded at 2^(-7 SLICES)."""
    exponents = _SLICE_BITS * np.arange(1, SLICES + 1)
    shape = [1] * x.ndim
    shape[axis] = exponents.size
    above = np.ldexp(1.0, exponents).astype(x.dtype).reshape(shape)
    below = np.ldexp(1.0, exponents - _SLICE_BITS).astype(x.dtype).reshape(shape)
    return xp.round(x * above) - 128 * xp.round(x * below)


def slice_operator(mats):
    """Host float64 matrices (one, or several applied side by side) ->
    :class:`SlicedOperator` as numpy arrays.  Each column is first divided by
    the power of two of its largest element relative to the largest column's
    (the field's row is multiplied by it instead, and a column of zeros takes
    the field's row out: the constant mode a derivative annihilates), so
    that an operator whose columns grow along the spectrum meets a field that
    decays along it on even terms, and a column with one nonzero is left to
    ``lone``; then each
    row is scaled by the power of two that brings its largest element into
    [1/4, 1/2), exactly, and cut into ``SLICES`` digit matrices."""
    mats = [np.asarray(m, dtype=np.float64) for m in mats]
    rows = tuple(m.shape[0] for m in mats)
    cols = tuple(m.shape[1] for m in mats)
    r, c = max(rows), max(cols)
    digits = np.zeros((len(mats), SLICES, r, c), np.int8)
    scale = np.ones((len(mats), r))
    balance = np.zeros((len(mats), c))
    lone = []
    for i, m in enumerate(mats):
        for k in np.flatnonzero(np.count_nonzero(m, axis=0) == 1):
            row = int(np.flatnonzero(m[:, k])[0])
            lone.append((i, row, int(k), float(m[row, k])))
            m = np.where(np.arange(m.shape[1]) == k, 0.0, m)
        largest = np.abs(m).max(axis=0, initial=0.0)
        _, e = np.frexp(largest)
        # relative to the largest column: at most 1, at least 2^-48
        e = np.clip(e - e.max(initial=0), -48, 0)
        column = np.where(largest > 0, np.ldexp(1.0, e), 0.0)
        balance[i, : m.shape[1]] = column
        m = m / np.where(column > 0, column, 1.0)
        _, e = np.frexp(np.abs(m).max(axis=1, initial=0.0))
        scale[i, : m.shape[0]] = np.ldexp(1.0, e + 1 - 2 * _SLICE_BITS)
        row = np.ldexp(1.0, e + 1)[:, None]
        digits[i, :, : m.shape[0], : m.shape[1]] = _digits((m / row)[None], np)
    # the rows' scales multiply float32 pieces (``_recombine``)
    if not np.all((scale >= 2.0**-126) & (scale < 2.0**127)):
        raise ValueError("an operator row's scale is outside float32's normal range")
    return SlicedOperator(digits, scale, balance, tuple(lone), rows, cols)


def _column_scale(x, balance):
    """Fields ``(B, K, ...)`` float64 and the operators' ``balance``
    ``(B, K)`` (float32: :func:`slice_operator`) -> ``balance`` expanded to
    the fields' rank and each column's scale ``(B, 1, ...)`` (float32): the
    power of two that brings the column's largest balanced element into
    [1/4, 1/2).

    It is read off the exponent field E of that element in float32: a scale
    2^(125 - E), held to [2^-76, 2^124] (E in [1, 201]: a column of zeros, or
    of values float32 flushes, gets 2^124 and stays under 1/4, as
    balance <= 1), so that ``balance * scale`` (balance >= 2^-48) is a
    normal float32.  Fields are held under 2^75."""
    lax, f32 = jax.lax, jnp.float32
    balance = lax.expand_dims(balance, tuple(range(2, x.ndim)))
    largest = lax.reduce_max(lax.abs(lax.convert_element_type(x, f32)) * balance, (1,))
    biased = lax.shift_right_logical(lax.bitcast_convert_type(largest, jnp.int32), np.int32(23))
    biased = lax.clamp(np.int32(1), biased, np.int32(201))
    scale = lax.bitcast_convert_type(lax.shift_left(np.int32(252) - biased, np.int32(23)), f32)
    return balance, lax.expand_dims(scale, (1,))


def _pieces(x):
    """Float64 ``x`` -> three float32 pieces, ``x = hi + mid + lo`` exactly
    (72 bits for 53, each piece under half an ulp of the one before) wherever
    they are normal floats; on the chip a float64 IS the pair hi + mid."""
    lax, f32 = jax.lax, jnp.float32
    hi = lax.convert_element_type(x, f32)
    rest = lax.sub(x, lax.convert_element_type(hi, x.dtype))
    mid = lax.convert_element_type(rest, f32)
    lo = lax.convert_element_type(lax.sub(rest, lax.convert_element_type(mid, x.dtype)), f32)
    return hi, mid, lo


def _column_digits(x, balance):
    """Fields ``(B, K, ...)`` float64 and the operators' ``balance``
    ``(B, K)`` -> the digits ``(B, SLICES, K, ...)`` int8 of the balanced
    fields, most significant first, and each column's scale ``(B, 1, ...)``
    (float32) inverted (:func:`_column_scale`).

    Two float64 subtractions cut a field into its three float32 pieces
    (:func:`_pieces`); each piece is multiplied by the float32 power of two
    ``balance * scale``, and the rest is float32 arithmetic on the pieces,
    their digits summed: no float64 multiply.  A piece is under half an ulp
    of the one before, so in one slot at most two pieces have digits, of at
    most 64 and 32: a slot's digit stays within [-96, 96].  A piece has
    digits only where it is 2^-64 or more once scaled, so it is a normal
    float32 before the scaling, and the digits are bitwise those of the
    field scaled in float64, wherever ``scale`` is at most 2^62 (the
    column's largest balanced element 2^-64 or more)."""
    lax = jax.lax
    balance, scale = _column_scale(x, balance)
    factor = balance * scale
    digits = [_digits(lax.expand_dims(piece * factor, (1,)), jnp, axis=1) for piece in _pieces(x)]
    digits = lax.add(lax.add(*digits[:2]), digits[2])
    return lax.convert_element_type(digits, jnp.int8), 1 / scale


def _whole_bound(k: int) -> int:
    """A bound on ``|P_0 + carry|`` (:func:`_carried`) for ``k`` contracted
    terms: ``P_d`` sums ``(d + 1) k`` products of an operator digit (at most
    64) and a field digit (at most 96), and the carry into ``P_d`` is the
    arithmetic shift of the sum below it by 7 bits."""
    carry = 0
    for d in range(SLICES - 1, 0, -1):
        carry = -(-((d + 1) * k * 64 * 96 + carry) // 2**_SLICE_BITS)
    return k * 64 * 96 + carry


def _carried(partials):
    """The int32 partials summed with carries: one integer ``P_0 + carry``
    (int32) and ``SLICES - 1`` digits of 7 bits, packed three to a float32
    (21 bits: exact) and weighted by their powers of two (exact), so that
    ``sum_d 2^(-7 d) P_d`` is the integer plus the pieces."""
    lax = jax.lax
    carry = None
    rest = [None] * SLICES
    for d in range(SLICES - 1, 0, -1):
        v = partials[d] if carry is None else lax.add(partials[d], carry)
        carry = lax.shift_right_arithmetic(v, np.int32(_SLICE_BITS))
        rest[d] = lax.sub(v, lax.shift_left(carry, np.int32(_SLICE_BITS)))  # in [0, 128)
    pieces = []
    for first in range(1, SLICES, 3):
        run = range(first, min(first + 3, SLICES))
        packed = rest[run[0]]
        for d in run[1:]:
            packed = lax.add(lax.shift_left(packed, np.int32(_SLICE_BITS)), rest[d])
        weight = np.float32(2.0 ** (-_SLICE_BITS * run[-1]))
        pieces.append(lax.mul(lax.convert_element_type(packed, jnp.float32), weight))
    return lax.add(partials[0], carry), pieces


#: low bits of the integer ``P_0 + carry`` split off into a word of its own
#: where it may not be exact in float32 (:func:`_recombine`)
_WHOLE_SPLIT = 12


def _recombine(partials, scale, column, k: int):
    """``sum_d 2^(-7 d) P_d`` (:func:`_carried`) times each row's and
    column's scale, in float64, for ``k`` contracted terms.

    The two scales are one float32 power of two an element, ``factor``, and
    multiply the float32 pieces before each is turned into float64: the
    integer whole (exact in float32 while |P_0 + carry| < 2^24, which
    :func:`_whole_bound` holds for K up to ~2600; a wider contraction splits
    it into two exact float32 words, whose float64 sum is exact) and the
    three packed runs of digits.  The float64 additions are the only float64
    arithmetic, in the order the pieces come.  A power of two scales a
    normal float32 exactly and a float64 sum's rounding with it, so the
    result is bitwise that of scaling the float64 sum, wherever every scaled
    piece is a normal float32: the smallest is 2^-56 of ``factor``, normal
    while ``factor`` is at least 2^-70.  Below that (an operator row and a
    field column whose largest elements multiply to under ~2^-60) a piece
    under 2^-126 is flushed, which moves an output by under 2^-124: a loss
    of relative precision only in outputs under ~2^-70, and under ~2^-102
    none that the chip's two-word float64 holds (its low word is flushed
    there too)."""
    lax, f32, f64 = jax.lax, jnp.float32, jnp.float64
    whole, pieces = _carried(partials)
    rows = lax.expand_dims(lax.convert_element_type(scale, f32), tuple(range(2, whole.ndim)))
    factor = rows * column
    if _whole_bound(k) < 2**24:
        total = lax.convert_element_type(lax.convert_element_type(whole, f32) * factor, f64)
    else:
        split = np.int32(_WHOLE_SPLIT)
        high = lax.shift_left(lax.shift_right_arithmetic(whole, split), split)
        words = [lax.convert_element_type(w, f32) * factor for w in (high, lax.sub(whole, high))]
        total = lax.add(*(lax.convert_element_type(w, f64) for w in words))
    for piece in pieces:
        total = lax.add(total, lax.convert_element_type(piece * factor, f64))
    return total


def _partials(digits, field, row_major: bool = False):
    """The int32 partials ``P_d = sum_(s + t = d) A_s X_t``, ``d <`` ``SLICES``,
    of the operators' digits ``(B, SLICES, r, K)`` and the fields' ``(B,
    SLICES, K, ...)``: one int8 product for each slice pair of ``PAIRS``,
    summed in int32 (exact: any order).  ``row_major``: each partial is held
    in the row-major layout its products write best.  Left free, the
    compiler gives the partials, and so the products, the layout of a caller
    that reads the output transposed (a product along a field's second
    axis): the products then write their int32 outputs with the rows minor,
    at 1.7 to 4 times the cycles of the same product written row-major.
    Held, the transposition falls to the recombination (on a v5e at 513²
    the step is 7 % shorter: PERF.md section 6)."""
    lax = jax.lax
    partials = [None] * SLICES
    for s, t in PAIRS:
        a = lax.index_in_dim(digits, s, axis=1, keepdims=False)
        x = lax.index_in_dim(field, t, axis=1, keepdims=False)
        p = lax.dot_general(a, x, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.int32)
        partials[s + t] = p if partials[s + t] is None else lax.add(partials[s + t], p)
    if not row_major:
        return partials
    layout = Layout(tuple(range(partials[0].ndim)))
    return [with_layout_constraint(p, layout) for p in partials]


@partial(jax.jit, static_argnames=("lone", "rows", "row_major"))
def sliced_product(digits, scale, balance, xs, lone, rows, row_major=False):
    """``[tensordot(A_b, x_b, axes=([1], [0])) for b]`` for the ``B``
    float64 operators of a :class:`SlicedOperator` (``digits``, ``scale``,
    ``balance``, ``lone``, ``rows``) and the float64 fields
    ``xs`` ``(K_b, ...)``: stacked (padded with zeros to one length),
    balanced, sliced, multiplied pair by pair, recombined, the lone columns
    added; ``row_major``: :func:`_partials`, the result the same bit for
    bit."""
    lax = jax.lax
    k = digits.shape[-1]
    x = jnp.stack([lax.pad(v, 0.0, [(0, k - v.shape[0], 0)] + [(0, 0, 0)] * (v.ndim - 1)) for v in xs])
    field, column = _column_digits(x, balance)
    y = _recombine(_partials(digits, field, row_major), scale, column, k)
    out = [lax.slice_in_dim(y[b], 0, r, axis=0) for b, r in enumerate(rows)]
    for b, row, col, value in lone:  # static: slices, no gather
        update = out[b][row : row + 1] + np.float64(value) * xs[b][col : col + 1]
        out[b] = lax.concatenate([out[b][:row], update, out[b][row + 1 :]], 0)
    return tuple(out)


def _sliced_calls(jaxpr):
    """The :func:`sliced_product` calls of ``jaxpr``, sub-programs included
    (``utils/jit.equations``): their invars are the operators' ``digits``,
    ``scale``, ``balance``, then the fields."""
    from ..utils.jit import equations

    return [eqn for eqn in equations(jaxpr) if eqn.params.get("name") == SLICED_PRODUCT]


def sliced_products(jaxpr) -> int:
    """The float64 products ``jaxpr`` states as sliced products: each call of
    :func:`sliced_product` counts its operators (the leading extent of
    ``scale``, which no ``vmap`` batches: it is a constant)."""
    return sum(eqn.invars[1].aval.shape[0] for eqn in _sliced_calls(jaxpr))


def int8_blocks(jaxpr) -> int:
    """The int8 operator slices the sliced products of ``jaxpr`` stream, in
    units of one slice ``(r, K)`` of one operator: each int8 ``dot_general``
    of a :func:`sliced_product` call counts its operand made of the call's
    ``digits`` (whichever side it is on) over the rows of ``scale`` times the
    contraction of ``balance``, so that a call reads ``len(PAIRS)`` for each
    of its operators."""
    from jax.extend.core import Var

    count = 0
    for eqn in _sliced_calls(jaxpr):
        (_, r), (_, k) = eqn.invars[1].aval.shape, eqn.invars[2].aval.shape
        inner = eqn.params["jaxpr"].jaxpr
        operator = {inner.invars[0]}  # the digits, and what is cut from them
        for e in inner.eqns:
            mine = [v for v in e.invars if isinstance(v, Var) and v in operator]
            if mine and e.primitive.name == "dot_general":
                count += sum(math.prod(v.aval.shape) // (r * k) for v in mine)
            elif mine:
                operator.update(e.outvars)
    return count


def sliced_f64_multiplies(jaxpr) -> int:
    """The float64 ``mul`` equations inside the sliced products that
    ``jaxpr`` states (:func:`sliced_products`), the lone columns' one-row
    updates left out: those are of a field's rank, where the stacked fields
    and everything made of them have one axis more."""
    from ..utils.jit import equations

    count = 0
    for eqn in _sliced_calls(jaxpr):
        rank = eqn.invars[3].aval.ndim  # a field
        count += sum(
            inner.primitive.name == "mul"
            and inner.outvars[0].aval.dtype == np.float64
            and inner.outvars[0].aval.ndim > rank
            for inner in equations(eqn.params["jaxpr"].jaxpr)
        )
    return count


def _products(ops, xs, precision=None):
    """``[tensordot(m, x, axes=([1], [0])) for m, x in zip(ops, xs)]``, where
    every dense product of this module is stated: ``ops`` is what ``place``
    or ``place.group`` made.  A :class:`SlicedOperator` is one sliced product
    over its operators, anything else XLA's own dot in ``precision``, one
    each."""
    if not isinstance(ops, SlicedOperator):
        return [jnp.tensordot(m, x, axes=([1], [0]), precision=precision) for m, x in zip(ops, xs)]
    if any(jnp.iscomplexobj(x) for x in xs):
        # a complex spectrum (a space off the TPU path that shares this base's
        # operators) is its real and imaginary parts, each a float64 field
        parts = [_products(ops, [x.real for x in xs]), _products(ops, [x.imag for x in xs])]
        return [jax.lax.complex(re, im) for re, im in zip(*parts)]
    from ..parallel.mesh import LOCAL, active_mesh, constrain

    # under a mesh the contracted axis of a field is whole on a device and
    # the other is cut: stated, so that the digits and partials keep that
    # layout (left to propagation they are gathered); inside a hand-
    # partitioned region (``shard_map``) a field is its device's block, and a
    # vector has no pencil: neither states anything
    manual = any(getattr(jax.typeof(x), "vma", None) for x in xs)
    pin = lambda v: v if manual or v.ndim < 2 else constrain(v, LOCAL[0])  # noqa: E731
    xs = tuple(pin(x.astype(jnp.float64)) for x in xs)
    # the partials are held row-major (:func:`_partials`) where no mesh is
    # active: the partitioner does not split a layout constraint, and under
    # a mesh it gathers every held partial
    out = sliced_product(ops.digits, ops.scale, ops.balance, xs, lone=ops.lone, rows=ops.rows,
                         row_major=active_mesh() is None and not manual)
    return [pin(y) for y in out]


def _product(m, x, precision=None):
    """One ``tensordot(m, x, axes=([1], [0]))`` (:func:`_products`)."""
    return _products(m if isinstance(m, SlicedOperator) else (m,), (x,), precision)[0]


def _halves(dev, first, second, precision=None):
    """A parity fold's two products ``(m_e first(), m_o second())`` for its
    halves ``dev`` (``place.group``): one sliced product for both where they
    are sliced, else two dots, each operand made just before its own dot (the
    equations, in order, of two plain products)."""
    if isinstance(dev, SlicedOperator):
        return tuple(_products(dev, (first(), second())))
    m_e, m_o = dev
    y_e = _product(m_e, first(), precision)
    return y_e, _product(m_o, second(), precision)


def _sliced(itemsize: int) -> bool:
    """Whether an operator's products, of ``itemsize`` bytes an element, are
    sliced products: float64 on the TPU path."""
    return itemsize == 8 and config.is_tpu_like()


class _Place:
    """The host -> device placement an impl's ``device_parts`` is handed:
    ``place(m)`` for the operator of one product, ``place.group(*mats)`` for
    operators applied side by side, a fold's two halves or a trapezoid's
    strips (each cut into int8 slices, the group as one, where the products
    are sliced), ``place.plain(m)`` for any other constant."""

    def __init__(self, plain, sliced: bool):
        self.plain = plain
        self.sliced = sliced

    def _slice(self, mats):
        op = slice_operator(mats)
        with jax.ensure_compile_time_eval():
            op.digits = jnp.asarray(op.digits)
            op.scale = self.plain(op.scale)
            op.balance = jnp.asarray(op.balance, jnp.float32)
        return op

    def __call__(self, m):
        return self._slice([m]) if self.sliced else self.plain(m)

    def group(self, *mats):
        if self.sliced:
            return self._slice(mats)
        return tuple(self.plain(m) for m in mats)


# even/odd row interleave shared with the cumsum-derivative kernel
from .transforms import _interleave0 as _interleave  # noqa: E402


class _BandedApply:
    """Matrix with few nonzero diagonals applied as diagonal-scaled shifted
    adds: ``out[i] = sum_d w_d[i] * x[i+d]`` — O(#offsets * n) per lane
    instead of the O(n^2) GEMM.  This is how the exactly-banded operator
    family (stencils S, the B2 quasi-inverse preconditioner, restricted
    eyes) should hit the TPU: a handful of fused VPU multiply-adds streaming
    HBM once, leaving the MXU to the genuinely dense work.  (The reference
    gets the same effect from explicit banded storage in its Tdma/Fdma
    kernels, /root/reference/src/solver/tdma.rs.)"""

    kind = "banded"

    def __init__(self, mat: np.ndarray, offsets: np.ndarray):
        r, c = mat.shape
        self.r, self.c = r, c
        self.offsets = [int(d) for d in offsets]
        if self.offsets:
            ws = np.zeros((len(self.offsets), r))
            for t, d in enumerate(self.offsets):
                i0, i1 = max(0, -d), min(r, c - d)
                idx = np.arange(i0, i1)
                ws[t, i0:i1] = mat[idx, idx + d]
            self.weights = ws
            self.flops_factor = len(self.offsets) / c
        else:  # structurally zero matrix
            self.weights = np.zeros((0, r))
            self.flops_factor = 0.0

    def device_parts(self, to_dev):
        return (to_dev.plain(self.weights),)

    def apply(self, dev, a, axis: int):
        (w,) = dev
        x = _move(a, axis)
        r = self.r
        batch = x.shape[1:]
        if not self.offsets:
            return _unmove(jnp.zeros((r,) + batch, dtype=x.dtype), axis)
        lo = max(0, -min(self.offsets))
        hi = max(0, max(self.offsets) + r - self.c)
        xp = jnp.pad(x, [(lo, hi)] + [(0, 0)] * len(batch))
        bshape = (r,) + (1,) * len(batch)
        out = None
        for t, d in enumerate(self.offsets):
            term = w[t].reshape(bshape) * jax.lax.slice_in_dim(xp, lo + d, lo + d + r, axis=0)
            out = term if out is None else out + term
        return _unmove(out, axis)


class _Plain:
    kind = "plain"

    def __init__(self, mat: np.ndarray):
        self.mat = mat
        self.flops_factor = 1.0

    def apply(self, dev, a, axis: int):
        from .transforms import apply_matrix

        (m,) = dev
        if isinstance(m, SlicedOperator):
            return _unmove(_product(m, _move(a, axis)), axis)
        return apply_matrix(m, a, axis)

    def device_parts(self, to_dev):
        return (to_dev(self.mat),)


class _PlainReflect(_Plain):
    """A reflection-symmetric transform below ``_FOLD_MIN_DIM``: one product
    in place of the fold, with what the fold carries besides: the
    ``precision`` hook (the fast syntheses stay three-pass,
    RUSTPDE_FWD_PRECISION keeps its meaning) and, for a dealiased analysis,
    the dead rows dropped from the GEMM and zero-filled after it.

    ``zero_fill``: ``((kept, dead), ...)`` runs of the OUTPUT rows in storage
    order; ``mat`` then holds the kept rows only."""

    #: optional matmul precision override (None = session default), the same
    #: hook as _SynthesisSep.precision
    precision = None

    def __init__(self, mat: np.ndarray, zero_fill=None):
        super().__init__(mat)
        self.zero_fill = zero_fill

    @classmethod
    def analysis_sep(cls, mat: np.ndarray, keep_rows=None):
        """Sep-layout output with the ``keep_rows`` prefix cut of
        `_AnalysisSep`: the kept rows of each parity block are one run."""
        r = mat.shape[0]
        if keep_rows is None or keep_rows >= r:
            return cls(mat[parity_perm(r), :])
        k = max(0, keep_rows)
        re, ke, ko = (r + 1) // 2, (k + 1) // 2, k // 2
        impl = cls(
            mat[:k][parity_perm(k), :],
            zero_fill=((ke, re - ke), (ko, r - re - ko)),
        )
        impl.flops_factor = k / r if r else 0.0
        return impl

    def apply(self, dev, a, axis: int):
        (m,) = dev
        y = _product(m, _move(a, axis), self.precision)
        if self.zero_fill is not None:
            parts, row = [], 0
            for kept, dead in self.zero_fill:
                parts.append(y[row : row + kept])
                parts.append(jnp.zeros((dead,) + y.shape[1:], dtype=y.dtype))
                row += kept
            y = jnp.concatenate(parts, axis=0)
        return _unmove(y, axis)


class _AnalysisFold:
    """M[j, n-1-i] = (-1)^j M[j, i]: fold the (physical) input side."""

    kind = "analysis"

    #: optional per-impl matmul precision override (None = session default);
    #: set by FoldedMatrix for the dealiased-forward 3-pass mode
    #: (RUSTPDE_FWD_PRECISION) — same hook as _SynthesisSep.precision
    precision = None

    def __init__(self, mat: np.ndarray):
        r, n = mat.shape
        h = n // 2
        self.n = n
        self.h = h
        even = mat[0::2, :]
        odd = mat[1::2, :]
        m_e = even[:, :h]
        if n % 2 == 1:
            m_e = np.concatenate([m_e, even[:, h : h + 1]], axis=1)
        self.m_e = m_e  # (r_e, h [+1])
        self.m_o = odd[:, :h]  # (r_o, h)
        self.r = r
        self.flops_factor = 0.5

    def device_parts(self, to_dev):
        return to_dev.group(self.m_e, self.m_o)

    def _combine(self, y_e, y_o):
        return _interleave(y_e, y_o, self.r)

    def apply(self, dev, a, axis: int):
        x = _move(a, axis)
        h, n = self.h, self.n
        xr = x[::-1]
        u = x[:h] + xr[:h]
        v = x[:h] - xr[:h]
        if n % 2 == 1:
            u = jnp.concatenate([u, x[h : h + 1]], axis=0)
        y_e, y_o = _halves(dev, lambda: u, lambda: v, self.precision)
        return _unmove(self._combine(y_e, y_o), axis)


class _SynthesisFold:
    """M[n-1-i, k] = (-1)^k M[i, k]: fold the (physical) output side."""

    kind = "synthesis"

    def __init__(self, mat: np.ndarray):
        n, c = mat.shape
        ceil = (n + 1) // 2
        self.n = n
        self.ceil = ceil
        self.m_e = mat[:ceil, 0::2]  # couples even spectral modes
        self.m_o = mat[:ceil, 1::2]
        self.flops_factor = 0.5

    def device_parts(self, to_dev):
        return to_dev.group(self.m_e, self.m_o)

    def apply(self, dev, a, axis: int):
        x = _move(a, axis)
        A, B = _halves(dev, lambda: x[0::2], lambda: x[1::2])
        top = A + B
        floor = self.n // 2
        bottom = (A - B)[:floor][::-1]
        return _unmove(jnp.concatenate([top, bottom], axis=0), axis)


class _CheckerFold:
    """M[j, k] = 0 unless (j + k + shift) even: fold both spectral sides."""

    kind = "checker"

    def __init__(self, mat: np.ndarray, shift: int):
        r, c = mat.shape
        self.r = r
        self.shift = shift
        # output row j couples inputs of parity (j + shift) % 2
        self.m_e = mat[0::2, shift % 2 :: 2]
        self.m_o = mat[1::2, (1 + shift) % 2 :: 2]
        self.flops_factor = 0.5

    def device_parts(self, to_dev):
        return to_dev.group(self.m_e, self.m_o)

    def apply(self, dev, a, axis: int):
        x = _move(a, axis)
        s = self.shift % 2
        y_e, y_o = _halves(dev, lambda: x[s::2], lambda: x[(1 + s) % 2 :: 2])
        return _unmove(_interleave(y_e, y_o, self.r), axis)


class _AnalysisSep(_AnalysisFold):
    """Analysis-type apply with sep-layout output: the even/odd half-GEMM
    results concatenate contiguously instead of interleaving.

    ``keep_rows``: only the first ``keep_rows`` NATURAL output modes are
    nonzero (a prefix dealias cut); the GEMMs drop the dead rows and the
    output is zero-padded — the 2/3-rule forward costs 2/3 of the flops and
    needs no separate mask multiply."""

    kind = "analysis_sep"

    def __init__(self, mat: np.ndarray, keep_rows: int | None = None):
        super().__init__(mat)
        r = self.r
        self.re = (r + 1) // 2  # even-block size of the sep output
        if keep_rows is None or keep_rows >= r:
            self.keep = None
        else:
            k = max(0, keep_rows)
            self.keep = ((k + 1) // 2, k // 2)  # kept rows per parity block
            self.m_e = self.m_e[: self.keep[0]]
            self.m_o = self.m_o[: self.keep[1]]
            self.flops_factor = 0.5 * k / r if r else 0.0
            self.kind = "analysis_sep_cut"

    def _combine(self, y_e, y_o):
        if self.keep is None:
            return jnp.concatenate([y_e, y_o], axis=0)
        ke, ko = self.keep
        batch = y_e.shape[1:]
        z_e = jnp.zeros((self.re - ke,) + batch, dtype=y_e.dtype)
        z_o = jnp.zeros((self.r - self.re - ko,) + batch, dtype=y_o.dtype)
        return jnp.concatenate([y_e, z_e, y_o, z_o], axis=0)


class _SynthesisSep(_SynthesisFold):
    """Synthesis-type apply with sep-layout input: contiguous slices instead
    of strided gathers.

    ``sign``: +1 for the plain synthesis symmetry ``M[n-1-i,k] =
    (-1)^k M[i,k]``; -1 for the sign-shifted variant ``(-1)^(k+1)`` that
    synthesis-of-odd-derivative fusions (``Syn @ D @ S``) carry."""

    kind = "synthesis_sep"

    #: optional per-impl matmul precision override (None = session default);
    #: set by FoldedMatrix for experiments like the synthesis-only 3-pass
    #: mode (RUSTPDE_SYNTH_PRECISION)
    precision = None

    def __init__(self, mat: np.ndarray, sign: float = 1.0):
        super().__init__(mat)
        self.ce = (mat.shape[1] + 1) // 2  # even-block size of the sep input
        self.sign = sign

    def apply(self, dev, a, axis: int):
        x = _move(a, axis)
        A, B = _halves(dev, lambda: x[: self.ce], lambda: x[self.ce :], self.precision)
        top = A + B
        floor = self.n // 2
        bottom = (self.sign * (A - B))[:floor][::-1]
        return _unmove(jnp.concatenate([top, bottom], axis=0), axis)


class _StripTrapezoid:
    """Upper-trapezoidal dense block (the Chebyshev derivative factors
    ``D^o @ S``: row k couples only columns ``>= k - bandwidth``): split the
    output rows into strips, each strip's GEMM starting at its first nonzero
    column — the zero lower-left triangle the full dense GEMM pays for is
    skipped.  4 strips recover ~37% of a perfectly triangular block's flops;
    the strips stay MXU-sized (>=256 rows at the production grids)."""

    kind = "trapezoid"

    def __init__(self, mat: np.ndarray, row_starts, col_starts):
        self.bounds = []
        mats = []
        r, c = mat.shape
        for i, (r0, c0) in enumerate(zip(row_starts, col_starts)):
            r1 = row_starts[i + 1] if i + 1 < len(row_starts) else r
            self.bounds.append((r0, r1, c0))
            mats.append(np.ascontiguousarray(mat[r0:r1, c0:]))
        self.mats = mats  # host copies; dropped by FoldedMatrix cleanup
        self.flops_factor = (
            sum((r1 - r0) * (c - c0) for r0, r1, c0 in self.bounds) / (r * c)
            if r * c
            else 0.0
        )

    def device_parts(self, to_dev):
        if to_dev.sliced:
            return to_dev.group(*self.mats)  # one sliced product for all strips
        return tuple(to_dev(m) for m in self.mats)

    def apply(self, dev, a, axis: int):
        x = _move(a, axis)
        if isinstance(dev, SlicedOperator):
            parts = _products(dev, [x[c0:] for _, _, c0 in self.bounds])
        else:
            parts = [
                _product(m, x[c0:])
                for m, (_, _, c0) in zip(dev, self.bounds)
            ]
        return _unmove(jnp.concatenate(parts, axis=0), axis)


_TRAP_MIN_DIM = 192  # strips below this lose more to GEMM granularity than
_TRAP_MAX_FACTOR = 0.85  # ... the skipped flops save; engage only when the
#                          trapezoid actually removes >=15% of the block


def _detect_trapezoid(mat: np.ndarray):
    """Strip decomposition when the block has a zero lower-left triangle
    (exact zeros — the derivative/stencil products are constructed so)."""
    r, c = mat.shape
    if min(r, c) < _TRAP_MIN_DIM:
        return None
    nz = mat != 0.0
    if not nz.any():
        return None
    # first nonzero column of each row (c for all-zero rows)
    first = np.where(nz.any(axis=1), nz.argmax(axis=1), c)
    strips = max(2, min(8, r // _TRAP_MIN_DIM))
    row_starts = [(r * i) // strips for i in range(strips)]
    col_starts = []
    for i, r0 in enumerate(row_starts):
        r1 = row_starts[i + 1] if i + 1 < len(row_starts) else r
        col_starts.append(int(first[r0:r1].min(initial=c)))
    trap = _StripTrapezoid(mat, row_starts, col_starts)
    if trap.flops_factor > _TRAP_MAX_FACTOR:
        return None
    return trap


def _detect_block(mat: np.ndarray):
    """Banded / trapezoid / plain detection for the parity blocks of a sep
    operator."""
    r, c = mat.shape
    if min(r, c) >= 4:
        scale = np.abs(mat).max() or 1.0
        mask = np.abs(mat) > _ATOL * scale
        if np.count_nonzero(mask) <= _MAX_BAND_OFFSETS * max(r, c):
            rows, cols = np.nonzero(mask)
            offs = np.unique(cols - rows)
            if offs.size <= _MAX_BAND_OFFSETS and offs.size * 4 <= c:
                kept = np.isin(np.arange(c)[None, :] - np.arange(r)[:, None], offs)
                # same lossless-only acceptance as _detect: the banded apply
                # drops off-band entries, so they must be exact zeros
                if not np.any(np.where(kept, 0.0, mat)):
                    return _BandedApply(mat, offs)
        trap = _detect_trapezoid(mat)
        if trap is not None:
            return trap
    return _Plain(mat)


class _SepBoth:
    """Spectral->spectral operator between sep-layout axes: parity-preserving
    (shift 0, e->e/o->o) or parity-flipping (shift 1, e->o/o->e) applies on
    the contiguous parity blocks — no gathers, no interleaves; banded blocks
    keep their shifted-add form with halved offsets."""

    def __init__(self, mat: np.ndarray, shift: int):
        r, c = mat.shape
        self.r = r
        self.ce = (c + 1) // 2
        self.shift = shift
        if shift == 0:
            subs = (mat[0::2, 0::2], mat[1::2, 1::2])
        else:  # even OUT rows couple odd IN cols and vice versa
            subs = (mat[0::2, 1::2], mat[1::2, 0::2])
        self.blocks = tuple(_detect_block(np.ascontiguousarray(s)) for s in subs)
        tot = sum(b.flops_factor * s.size for b, s in zip(self.blocks, subs))
        self.flops_factor = tot / (r * c) if r * c else 0.0
        self.kind = (
            f"sep_{'preserve' if shift == 0 else 'flip'}"
            f"[{self.blocks[0].kind},{self.blocks[1].kind}]"
        )

    def device_parts(self, to_dev):
        if to_dev.sliced and all(b.kind == "plain" for b in self.blocks):
            return to_dev.group(*(b.mat for b in self.blocks))  # one sliced product
        return tuple(b.device_parts(to_dev) for b in self.blocks)

    def apply(self, dev, a, axis: int):
        x = _move(a, axis)
        x_e, x_o = x[: self.ce], x[self.ce :]
        b_e, b_o = self.blocks
        if isinstance(dev, SlicedOperator):
            y_e, y_o = _products(dev, (x_e, x_o) if self.shift == 0 else (x_o, x_e))
        elif self.shift == 0:
            y_e = b_e.apply(dev[0], x_e, 0)
            y_o = b_o.apply(dev[1], x_o, 0)
        else:
            y_e = b_e.apply(dev[0], x_o, 0)
            y_o = b_o.apply(dev[1], x_e, 0)
        return _unmove(jnp.concatenate([y_e, y_o], axis=0), axis)


def _detect_sep(mat: np.ndarray, sep_in: bool, sep_out: bool, keep_rows=None, itemsize=None):
    """Impl selection for sep-layout sides.  Unstructured matrices absorb the
    permutation into the dense operator (conjugation on the host — zero
    runtime cost); structured ones get the gather-free block applies, the
    reflection folds from ``_FOLD_MIN_DIM`` up (below it: one product, the
    permutation absorbed the same way)."""
    if np.iscomplexobj(mat) or mat.ndim != 2:
        raise ValueError("sep layout requires real 2-D operator matrices")
    r, c = mat.shape
    scale = np.abs(mat).max() or 1.0
    structured = folding_enabled() and min(r, c) >= 4
    fold = min(r, c) >= _gate(_FOLD_MIN_DIM, itemsize)
    if sep_in and sep_out:
        if structured:
            j = np.arange(r)[:, None]
            k = np.arange(c)[None, :]
            for shift in (0, 1):
                zero_part = mat[(j + k + shift) % 2 == 1]
                if np.abs(zero_part).max(initial=0.0) < _ATOL * scale:
                    both = _SepBoth(mat, shift)
                    if min(r, c) >= _gate(_SEP_MIN_DIM, itemsize) or any(
                        b.kind != "plain" for b in both.blocks
                    ):
                        return both
                    break  # two dense blocks below their gate: one product
        return _Plain(mat[np.ix_(parity_perm(r), parity_perm(c))])
    if sep_out:  # physical/natural input -> sep output (analysis position)
        if structured:
            sgn_r = (-1.0) ** np.arange(r)[:, None]
            if np.abs(mat[:, ::-1] - sgn_r * mat).max() < _ATOL * scale:
                if fold:
                    return _AnalysisSep(mat, keep_rows=keep_rows)
                return _PlainReflect.analysis_sep(mat, keep_rows)
        if keep_rows is not None and keep_rows < r:
            mat = np.where(np.arange(r)[:, None] < keep_rows, mat, 0.0)
        return _Plain(mat[parity_perm(r), :])
    # sep input -> physical/natural output (synthesis position)
    if structured:
        sgn_c = (-1.0) ** np.arange(c)[None, :]
        for sign in (1.0, -1.0):
            if np.abs(mat[::-1, :] - sign * sgn_c * mat).max() < _ATOL * scale:
                if fold:
                    return _SynthesisSep(mat, sign)
                return _PlainReflect(mat[:, parity_perm(c)])
    return _Plain(mat[:, parity_perm(c)])


def _gate(min_dim: dict, itemsize=None) -> int:
    """A size gate's value (``_FOLD_MIN_DIM``, ``_SEP_MIN_DIM``) for
    products of ``itemsize`` bytes an element (None: the session's real
    dtype)."""
    if itemsize is None:
        itemsize = np.dtype(config.real_dtype()).itemsize
    return min_dim[itemsize]


def _detect(
    mat: np.ndarray, sep_in: bool = False, sep_out: bool = False, keep_rows=None, itemsize=None
):
    """``itemsize``: bytes an element of the arithmetic the products will
    run in (`_gate`)."""
    if sep_in or sep_out:
        return _detect_sep(np.asarray(mat), sep_in, sep_out, keep_rows, itemsize)
    if not folding_enabled():
        return _Plain(mat)
    if np.iscomplexobj(mat) or mat.ndim != 2 or min(mat.shape) < 4:
        return _Plain(mat)
    r, c = mat.shape
    scale = np.abs(mat).max() or 1.0
    # small-bandwidth matrices: shifted adds beat any GEMM fold.  Cheap
    # nnz pre-check first so dense matrices skip the O(nnz) index
    # materialization (np.nonzero on a 2049^2 transform is ~67 MB transient)
    mask = np.abs(mat) > _ATOL * scale
    if np.count_nonzero(mask) <= _MAX_BAND_OFFSETS * max(r, c):
        rows, cols = np.nonzero(mask)
        offs = np.unique(cols - rows)
        if offs.size <= _MAX_BAND_OFFSETS and offs.size * 4 <= c:
            # the banded apply DROPS everything off the kept diagonals, so
            # it is only taken when the dropped entries are exact zeros —
            # every current banded operator (stencils, B2, restricted eyes)
            # is constructed that way.  A near-banded matrix with nonzero
            # sub-tolerance off-band entries falls through to the lossless
            # folds/dense applies instead of being silently truncated.
            kept = np.isin(np.arange(c)[None, :] - np.arange(r)[:, None], offs)
            if not np.any(np.where(kept, 0.0, mat)):
                return _BandedApply(mat, offs)
    # synthesis-type first: pure transform matrices of even N carry BOTH
    # reflection structures (quarter-constructed, ops/chebyshev.py) and the
    # output-side fold is measured cheaper on TPU — its flip/concat touches
    # the half-size result, while the input-side (analysis) fold streams a
    # full-array reverse before the GEMM
    # Either engages from _FOLD_MIN_DIM up; below it the transform is one
    # plain product (module docstring)
    fold = min(r, c) >= _gate(_FOLD_MIN_DIM, itemsize)
    sgn_c = (-1.0) ** np.arange(c)[None, :]
    if np.abs(mat[::-1, :] - sgn_c * mat).max() < _ATOL * scale:
        return _SynthesisFold(mat) if fold else _PlainReflect(mat)
    # analysis-type: input reflection <-> output index parity
    sgn_r = (-1.0) ** np.arange(r)[:, None]
    if np.abs(mat[:, ::-1] - sgn_r * mat).max() < _ATOL * scale:
        return _AnalysisFold(mat) if fold else _PlainReflect(mat)
    # checkerboard
    j = np.arange(r)[:, None]
    k = np.arange(c)[None, :]
    for shift in (0, 1):
        mask = (j + k + shift) % 2 == 1
        if np.abs(mat[mask]).max(initial=0.0) < _ATOL * scale:
            return _CheckerFold(mat, shift)
    # circular (Fourier) reflection folds.  Size-gated: the index gathers
    # they add are pure overhead on dispatch-bound small GEMMs (measured:
    # the 128x65 periodic config runs faster plain), while at SH-2048-class
    # sizes the flop saving dominates.
    if min(r, c) < _CIRC_MIN_DIM:
        return _Plain(mat)
    cls_in = _classify_circular(mat, on_rows=True)
    # the column classification is only needed for the square quarter-fold
    # candidates and the synthesis fallback — skip the O(r*c) pass otherwise
    cls_out = (
        _classify_circular(mat, on_rows=False)
        if (r == c and cls_in is not None) or cls_in is None
        else None
    )
    if cls_in is not None and cls_out is not None and r == c:
        # single global output class -> rows mirror with one sign: quarter fold
        cols_s, cols_a = cls_out
        if cols_a.size == 0:
            return _CircBothFold(mat, +1.0)
        if cols_s.size == 0 or np.abs(mat[:, cols_s]).max(initial=0.0) < _ATOL * scale:
            return _CircBothFold(mat, -1.0)
    if cls_in is not None:
        return _CircAnalysisFold(mat, *cls_in)
    if cls_out is not None:
        return _CircSynthesisFold(mat, *cls_out)
    return _Plain(mat)


class FoldedMatrix:
    """Device-resident matrix application with automatic parity folding.

    Drop-in for the ``tr.apply_matrix(dev_matrix, a, axis)`` pattern:
    ``FoldedMatrix(host_matrix, to_dev).apply(a, axis)``.  ``to_dev`` is the
    host->device constant placement (bases._dev)."""

    def __init__(
        self, mat: np.ndarray, to_dev, sep_in: bool = False, sep_out: bool = False,
        keep_rows=None, cast=None,
    ):
        """``cast``: store the device parts in this dtype and run apply()
        through it (input cast in, output cast back to the input dtype) —
        the f64-hybrid mode's f32 convection transforms (Base._sep_dev)."""
        self._cast = np.dtype(cast) if cast is not None else None
        itemsize = (self._cast or np.dtype(config.real_dtype())).itemsize
        self._impl = _detect(np.asarray(mat), sep_in, sep_out, keep_rows, itemsize)
        if self._cast is None:
            place = to_dev
        else:
            def place(m, _c=self._cast):
                import jax

                # cast on the HOST and place directly (bypassing to_dev,
                # whose astype(config.real_dtype()) would undo the cast):
                # half the bytes over the wire and no transient f64 device
                # buffer; ensure_compile_time_eval keeps the constant
                # concrete under lazy in-trace materialization, like
                # bases._dev itself
                with jax.ensure_compile_time_eval():
                    return jnp.asarray(np.asarray(m).astype(_c))
        self._dev = self._impl.device_parts(_Place(place, _sliced(itemsize)))
        # drop the host copies — apply() reads only the device parts and the
        # scalar shape metadata (at 2049^2 f64 a retained inverse is ~33 MB);
        # recurse into wrapped impls (_CircBothFold holds an inner fold,
        # _SepBoth holds per-parity blocks)
        stack = [self._impl]
        while stack:
            impl = stack.pop()
            for attr in ("mat", "m_e", "m_o", "mats"):
                if hasattr(impl, attr):
                    setattr(impl, attr, None)
            inner = getattr(impl, "_inner", None)
            if inner is not None:
                stack.append(inner)
            stack.extend(getattr(impl, "blocks", ()))

    @property
    def kind(self) -> str:
        return self._impl.kind

    @property
    def flops_factor(self) -> float:
        return self._impl.flops_factor

    def set_precision(self, precision: str | None) -> bool:
        """Override the matmul precision of the underlying apply, where the
        impl supports one (the ``_SynthesisSep`` family and its form below
        the fold gate, ``_PlainReflect``).  Returns whether the override
        took — callers must not assume it did: unstructured ``_Plain``
        fallbacks stay at session precision.  The public face of what
        bases.py used to do by reaching into ``_impl``."""
        if precision and hasattr(type(self._impl), "precision"):
            self._impl.precision = precision
            return True
        return False

    def apply(self, a, axis: int):
        if self._cast is not None and a.dtype != self._cast:
            if jnp.iscomplexobj(a) and not jnp.issubdtype(
                self._cast, jnp.complexfloating
            ):
                # astype(real) silently DROPS the imaginary part; the hybrid
                # cast is only defined real->real (f64 state through f32
                # transforms).  Complex spectral data must stay complex —
                # split-Fourier layouts reach here as real re/im planes.
                raise TypeError(
                    f"FoldedMatrix hybrid cast: complex operand ({a.dtype}) "
                    f"cannot be cast to real {self._cast} without losing the "
                    "imaginary part"
                )
            out = self._impl.apply(self._dev, a.astype(self._cast), axis)
            return out.astype(a.dtype)
        return self._impl.apply(self._dev, a, axis)


class _CircAnalysisFold:
    """Circular input fold: columns pair under j -> (n-j) mod n and every
    output row is symmetric (+) or antisymmetric (-) across that pairing —
    the structure of the split-Fourier forward matrices (cos rows +, sin
    rows -; fixed points j=0 and, for even n, j=n/2)."""

    kind = "circ_analysis"

    def __init__(self, mat: np.ndarray, rows_s: np.ndarray, rows_a: np.ndarray):
        r, n = mat.shape
        self.r = r
        fixed = [0] + ([n // 2] if n % 2 == 0 else [])
        pair = np.arange(1, (n - 1) // 2 + 1)
        self._fixed = np.asarray(fixed)
        self._pair = pair
        self._partner = n - pair
        # inverse permutation scattering concat(y_s, y_a) back to row order
        perm = np.concatenate([rows_s, rows_a])
        self._inv = np.argsort(perm)
        self.m_e = mat[np.ix_(rows_s, np.concatenate([self._fixed, pair]))]
        self.m_o = mat[np.ix_(rows_a, pair)] if rows_a.size else None
        self.flops_factor = 0.5

    def device_parts(self, to_dev):
        if self.m_o is None:
            return (to_dev(self.m_e), None)
        return to_dev.group(self.m_e, self.m_o)

    def apply(self, dev, a, axis: int):
        x = _move(a, axis)
        u = jnp.concatenate([x[self._fixed], x[self._pair] + x[self._partner]])
        if isinstance(dev, SlicedOperator) or dev[1] is not None:
            v = lambda: x[self._pair] - x[self._partner]  # noqa: E731
            parts = list(_halves(dev, lambda: u, v))
        else:
            parts = [_product(dev[0], u)]
        out = jnp.concatenate(parts, axis=0)[self._inv]
        return _unmove(out, axis)


class _CircSynthesisFold:
    """Circular output fold: rows pair under i -> (n-i) mod n, each input
    column symmetric (+) or antisymmetric (-) — the split-Fourier backward
    matrices (cos columns +, sin columns -)."""

    kind = "circ_synthesis"

    def __init__(self, mat: np.ndarray, cols_s: np.ndarray, cols_a: np.ndarray):
        n, c = mat.shape
        self.n = n
        keep = n // 2 + 1  # rows 0..n//2 inclusive
        self._cols_s = cols_s
        self._cols_a = cols_a
        self.m_e = mat[np.ix_(np.arange(keep), cols_s)]
        self.m_o = mat[np.ix_(np.arange(keep), cols_a)] if cols_a.size else None
        # bottom rows n-1..n//2+1 mirror i = 1..ceil(n/2)-1
        self._mirror = np.arange(1, (n + 1) // 2)[::-1]
        self.flops_factor = 0.5

    def device_parts(self, to_dev):
        if self.m_o is None:
            return (to_dev(self.m_e), None)
        return to_dev.group(self.m_e, self.m_o)

    def apply(self, dev, a, axis: int):
        x = _move(a, axis)
        if isinstance(dev, SlicedOperator) or dev[1] is not None:
            A, B = _halves(dev, lambda: x[self._cols_s], lambda: x[self._cols_a])
            top, bottom = A + B, A - B
        else:
            top = bottom = _product(dev[0], x[self._cols_s])
        out = jnp.concatenate([top, bottom[self._mirror]], axis=0)
        return _unmove(out, axis)


def _classify_circular(mat: np.ndarray, on_rows: bool):
    """Partition rows (on_rows=False: columns) into symmetric/antisymmetric
    classes under the circular reflection of the other index; None if any
    vector is neither."""
    m = mat if on_rows else mat.T  # classify rows of m under column pairing
    r, n = m.shape
    idx = (-np.arange(n)) % n
    refl = m[:, idx]
    scale = np.abs(m).max() or 1.0
    sym = np.abs(refl - m).max(axis=1) < _ATOL * scale
    asym = np.abs(refl + m).max(axis=1) < _ATOL * scale
    if not np.all(sym | asym):
        return None
    # ambiguous (zero) vectors count as symmetric
    rows_s = np.where(sym)[0]
    rows_a = np.where(~sym & asym)[0]
    return rows_s, rows_a


class _CircBothFold:
    """Quarter-flops circular fold for matrices with BOTH circular
    symmetries and a single output class: input columns pair under
    j -> (n-j) mod n (per-row sym/antisym), and every output row mirrors as
    ``M[(n-i) mod n, :] = t * M[i, :]`` with one global sign t — the DFT
    cos (t=+1) and sin (t=-1) matrices.  Computes the kept rows 0..n//2 via
    the half-input fold, then mirrors the bottom rows."""

    kind = "circ_both"

    def __init__(self, mat: np.ndarray, sign: float):
        n = mat.shape[0]
        keep = n // 2 + 1
        kept = mat[:keep]
        cls = _classify_circular(kept, on_rows=True)
        self._inner = _CircAnalysisFold(kept, *cls)
        self._sign = sign
        self._mirror = np.arange(1, (n + 1) // 2)[::-1]
        self.flops_factor = 0.25
        # host copies live on self._inner; FoldedMatrix's cleanup recurses

    def device_parts(self, to_dev):
        return self._inner.device_parts(to_dev)

    def apply(self, dev, a, axis: int):
        x = _move(a, axis)
        top = self._inner.apply(dev, x, 0)
        bottom = self._sign * top[self._mirror]
        return _unmove(jnp.concatenate([top, bottom], axis=0), axis)
