"""Pallas TPU kernels: the implicit half of the Navier step as fused stages.

Every stage of the implicit half of the step (the Helmholtz
velocity/temperature solves, the pressure Poisson solve, and the
synthesis/projection glue between them) round-trips HBM between ~4-8
separate GEMM dispatches per stage on the dense path (the last recorded
2049^2 f64 row, 2026-07-31, ran at 4.00 steps/s).  This module fuses each
stage into ONE
``pl.pallas_call`` with the modal intermediates resident in VMEM:

    rhs assembly      sum_t  L_t @ x_t @ R_t^T      (stage-1 GEMMs, tiled)
    [+ BC-lift]       + const                        (host-precomputed)
    [modal solve]     * (1 / (lam0_i + lam1_j))      (fast-diag scaling)
    [modal backward]  B0 @ . @ B1^T                  (composite coefficients)
    [singular pin]    * mask                          (pressure zero mode)

The per-stage term lists are composed host-side (numpy f64) from the stable
``Base.axis_operator`` accessor plus the ``solver`` module's public modal
data (``hholtz_axis_solve_matrix`` / ``modal_data_split``) — no private
folding internals — so one generalized kernel covers all eight step stages:

* ``velx``/``vely``/``temp``/``scal`` — convection RHS + pressure-gradient +
  buoyancy/Coriolis terms with the ADI Helmholtz inverse folded into every
  term's axis matrices (solve == A0 @ rhs @ A1^T; the dense path's banded
  recurrences and the precomputed dense inverse solve the identical system).
* ``div`` — the divergence RHS (two gradient terms) in scratch-ortho space.
* ``poisson`` — fast-diagonalisation pressure solve (modal forward GEMM ->
  per-eigenvalue scaling -> modal backward GEMM) with the singular-mode pin
  folded as an output mask.  The same discrete system as solver.TensorSolver
  / the ``pallas_banded`` recurrence (tests/test_golden.py); the fast-diag
  scaling form is the MXU-native choice
  (``pallas_banded.bench_banded_paths`` times the recurrence against it).
* ``projx``/``projy`` — the pressure-gradient velocity correction
  (projection x gradient cross-space GEMMs), subtracted outside the kernel.

Layouts: confined sep Chebyshev, split-sep periodic, and complex periodic
(complex arrays convert to stacked ``[Re; Im]`` planes at the kernel
boundary, exactly the ``FusedConv`` convention).  Interpreter mode runs the
same kernels on CPU (tests/test_pallas_step.py); on a TPU they compile
natively or the model build
raises ``PallasCompileRefused`` (ops/pallas_common.py) — never interpreted
there, never silently dense.  vmap/ensemble batching rides the standard
``pallas_call`` batching rule.

Selection mirrors ``RUSTPDE_CONV_KERNEL``: ``RUSTPDE_STEP_KERNEL=dense|
pallas`` (default ``dense`` until an on-chip A/B against the dense chain
lands: ROADMAP Queue 1).  VMEM: each stage holds its whole-width right-side
operand ``R_t^T`` and the output block resident across grid steps and asks Mosaic
for that much (``pallas_common.compiler_params``); a grid whose residency
exceeds the core's VMEM is refused at build (output-column tiling would
lift that).

``RUSTPDE_F64_HYBRID`` convention: ``build_model_step`` keeps the solve
stages in full f64 (cast=None) — matching the dense path, whose hybrid cast
covers only the convection transforms while the implicit solves stay f64.
Mosaic does not lower f64, so under ``RUSTPDE_X64=1`` the knob is refused
at build on a TPU (the CPU interpreter still runs the f64 parity suite).
The ``cast`` parameter exists for direct A/B of an all-f32 solve chain.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import config
from .pallas_common import (
    LANE,
    SUBLANE,
    compiler_params,
    require_native_compile,
    resolve_interpret,
)


def step_kernel_choice() -> str:
    """The ``RUSTPDE_STEP_KERNEL`` knob: ``"dense"`` (default — the unfused
    solver chain) or ``"pallas"`` (the fused stage kernels).  Read at model
    compile time, like ``conv_kernel_choice``."""
    return config.env_get("RUSTPDE_STEP_KERNEL", "dense")


def _ceil_to(x: int, m: int) -> int:
    return -(-int(x) // m) * m


class StageTerm(NamedTuple):
    """One ``L @ x @ R^T`` term of a fused stage, in storage layout.

    ``l`` may be None for single-term stages whose input is already in the
    stage-1 row space (the periodic Poisson forward: Fourier modes are
    already modal).  ``complex_in``: the input array is complex and converts
    to stacked ``[Re; Im]`` planes at the kernel boundary."""

    l: np.ndarray | None
    r: np.ndarray
    complex_in: bool


def _stage_kernel(*refs, nt, nj, ni, has_l, has_const, has_dinv, has_b1,
                  has_b0, has_mask):
    """Grid (i over stage-1 row tiles, j over contraction tiles; j
    innermost).  Stage 1 accumulates each term's ``L_t @ x_t`` into VMEM
    scratch; the j-final epilogue contracts with ``R_t^T``, sums the terms,
    applies const/modal-scaling/backward maps, and writes (or, with a modal
    backward ``B0``, accumulates over i) the output block."""
    from jax.experimental import pallas as pl

    pos = 0
    ls = refs[pos:pos + nt] if has_l else ()
    pos += nt if has_l else 0
    xs = refs[pos:pos + nt]
    pos += nt
    rts = refs[pos:pos + nt]
    pos += nt
    const = refs[pos] if has_const else None
    pos += 1 if has_const else 0
    dinv = refs[pos] if has_dinv else None
    pos += 1 if has_dinv else 0
    b1t = refs[pos] if has_b1 else None
    pos += 1 if has_b1 else 0
    b0 = refs[pos] if has_b0 else None
    pos += 1 if has_b0 else 0
    mask = refs[pos] if has_mask else None
    pos += 1 if has_mask else 0
    o = refs[pos]
    accs = refs[pos + 1:]

    i = pl.program_id(0)
    j = pl.program_id(1)
    acc_t = o.dtype
    prec = jax.lax.Precision.HIGHEST

    if has_l:
        for t in range(nt):
            part = jnp.dot(ls[t][...], xs[t][...], precision=prec,
                           preferred_element_type=acc_t)

            @pl.when(j == 0)
            def _init(acc=accs[t], part=part):
                acc[...] = part

            @pl.when(j > 0)
            def _accum(acc=accs[t], part=part):
                acc[...] = acc[...] + part

    @pl.when(j == nj - 1)
    def _epilogue():
        m = None
        for t in range(nt):
            src = accs[t][...] if has_l else xs[t][...]
            part = jnp.dot(src, rts[t][...], precision=prec,
                           preferred_element_type=acc_t)
            m = part if m is None else m + part
        if has_dinv:
            m = m * dinv[...]
        if has_b1:
            m = jnp.dot(m, b1t[...], precision=prec,
                        preferred_element_type=acc_t)
        if has_const:
            m = m + const[...]
        if has_b0:
            part = jnp.dot(b0[...], m, precision=prec,
                           preferred_element_type=acc_t)

            @pl.when(i == 0)
            def _first():
                o[...] = part

            @pl.when(i > 0)
            def _rest():
                o[...] = o[...] + part

            if has_mask:
                @pl.when(i == ni - 1)
                def _pin():
                    o[...] = o[...] * mask[...]
        else:
            if has_mask:
                m = m * mask[...]
            o[...] = m


class FusedStage:
    """One fused step stage: ``apply(*xs) == sum_t L_t @ xs[t] @ R_t^T
    [+ const] [-> modal scale -> backward] [* mask]`` in ONE Pallas kernel,
    the per-term matrices given in storage layout (conjugated with the
    spaces' sep/split permutations by the builder).

    ``modal=(dinv, b0, b1)``: the fast-diag solve stage — elementwise
    ``1/(lam0_i + lam1_j)`` scaling between the term contraction and the
    backward maps (either of ``b0``/``b1`` may be None for periodic axes).
    ``mask``: multiplicative output mask (the pressure singular-mode pin).
    ``cast`` mirrors the FusedConv convention (store matrices in that dtype,
    run the chain through it); ``interpret`` defaults to True on the CPU and
    False on a TPU.
    ``reference()`` is the same chain unfused (plain XLA dots over the same
    padded constants) — the kernel-plumbing A/B; the model-level dense A/B
    lives in tests/test_pallas_step.py."""

    def __init__(self, name, terms, complex_out, const=None, modal=None,
                 mask=None, cast=None, interpret: bool | None = None,
                 block_rows: int | None = None, block_k: int | None = None):
        self.terms = list(terms)
        nt = len(self.terms)
        if nt == 0:
            raise ValueError("a fused stage needs at least one term")
        self.complex_out = bool(complex_out)
        self.has_l = self.terms[0].l is not None
        if any((t.l is None) != (not self.has_l) for t in self.terms):
            raise ValueError("terms must uniformly carry or omit L matrices")
        if not self.has_l and nt != 1:
            raise ValueError("L-less stages are single-term only")

        dinv = b0 = b1 = None
        if modal is not None:
            dinv, b0, b1 = modal
        if const is not None and (modal is not None or b0 is not None):
            raise ValueError("const is a post-solve fold; modal stages "
                             "carry their lift in the rhs terms instead")

        # true (unpadded) dims
        self.q1 = int(self.terms[0].r.shape[0])
        if any(int(t.r.shape[0]) != self.q1 for t in self.terms):
            raise ValueError("stage terms must share the output column space")
        if self.has_l:
            self.r0 = int(self.terms[0].l.shape[0])
            if any(int(t.l.shape[0]) != self.r0 for t in self.terms):
                raise ValueError("stage terms must share the stage-1 row space")
            self._k0 = [int(t.l.shape[1]) for t in self.terms]
        else:
            self.r0 = int(dinv.shape[0]) if dinv is not None else None
            if self.r0 is None:
                raise ValueError("L-less stages need modal data to fix rows")
            self._k0 = [self.r0]
        self._k1 = [int(t.r.shape[1]) for t in self.terms]
        self.p0 = int(b0.shape[0]) if b0 is not None else self.r0
        self.p1 = int(b1.shape[0]) if b1 is not None else self.q1

        # padded dims + tiles (FusedConv sizing: row tiles from block_rows,
        # common contraction padded to the largest term, LANE-quantized)
        br = int(block_rows or config.env_get("RUSTPDE_PALLAS_CONV_BLOCK", 256))
        br = max(LANE, _ceil_to(br, LANE))
        self._r0p = _ceil_to(self.r0, br)
        self._bi = min(br, self._r0p)
        self._k0p = _ceil_to(max(self._k0), LANE)
        bj = int(block_k or config.env_get("RUSTPDE_PALLAS_CONV_BLOCK_K", 512))
        bj = max(LANE, (bj // LANE) * LANE)
        if self.has_l:
            while self._k0p % bj:
                bj -= LANE
        else:
            bj = self._k0p
        self._bj = bj
        self._k1p = [_ceil_to(k, LANE) for k in self._k1]
        self._q1p = _ceil_to(self.q1, LANE)
        self._p1p = _ceil_to(self.p1, LANE)
        self._p0p = _ceil_to(self.p0, SUBLANE) if b0 is not None else self._r0p

        self.name = name
        self.kernel_name = f"fused_step_{name}_{self.p0}x{self.p1}_t{nt}"
        self._cast = np.dtype(cast) if cast is not None else None
        dt = self._cast or config.real_dtype()
        from .folded import pad_dense

        with jax.ensure_compile_time_eval():

            def place(m, rows, cols):
                return jnp.asarray(pad_dense(np.asarray(m), rows, cols).astype(dt))

            self._ls = (
                [place(t.l, self._r0p, self._k0p) for t in self.terms]
                if self.has_l else None
            )
            self._rts = [
                place(t.r.T, k1p, self._q1p)
                for t, k1p in zip(self.terms, self._k1p)
            ]
            self._const = (
                place(const, self._r0p, self._q1p) if const is not None else None
            )
            # modal denominators are built at TRUE shape, then zero-padded:
            # the pad region multiplies zero-padded data, so exact zeros
            # (not 1/0 = inf) keep the padding mathematically inert
            self._dinv = (
                place(dinv, self._r0p, self._q1p) if dinv is not None else None
            )
            self._b1t = place(b1.T, self._q1p, self._p1p) if b1 is not None else None
            self._b0 = place(b0, self._p0p, self._r0p) if b0 is not None else None
            mrows = self._p0p if b0 is not None else self._r0p
            self._mask = place(mask, mrows, self._p1p) if mask is not None else None
        self.interpret = resolve_interpret(interpret)

    def check_compiles(self) -> None:
        """On a TPU, compile the stage now, so a Mosaic refusal (f64 under
        X64, VMEM residency at large grids) is one typed error at model
        build (:class:`~.pallas_common.PallasCompileRefused`); nothing to
        check under the CPU interpreter."""
        if self.interpret:
            return
        rdt, cdt = config.real_dtype(), config.complex_dtype()
        rows = self._k0 if self.has_l else [self.r0]
        example = [
            jax.ShapeDtypeStruct(
                (r // 2, k1) if t.complex_in else (r, k1),
                cdt if t.complex_in else rdt,
            )
            for t, r, k1 in zip(self.terms, rows, self._k1)
        ]
        require_native_compile(
            "RUSTPDE_STEP_KERNEL=pallas", self.kernel_name, self.apply, *example
        )

    # -- traffic accounting ---------------------------------------------------

    @property
    def hbm_bytes(self) -> float:
        """HBM bytes ONE fused invocation moves: every operand (padded
        operator constants + padded inputs) read once, the output written
        once — the megakernel side of the step traffic estimate."""
        item = np.dtype(self._cast or config.real_dtype()).itemsize
        n = sum(m.size for m in (self._ls or []))
        n += sum(m.size for m in self._rts)
        for extra in (self._const, self._dinv, self._b1t, self._b0, self._mask):
            if extra is not None:
                n += extra.size
        rows = self._k0p if self.has_l else self._r0p
        n += sum(rows * k1p for k1p in self._k1p)  # inputs
        if self._b0 is not None:
            n += self._p0p * self._p1p
        else:
            n += self._r0p * self._p1p
        return float(n) * item

    @property
    def dense_hbm_bytes(self) -> float:
        """Analytic HBM bytes of the UNFUSED chain computing the same stage:
        each per-axis apply / elementwise op reads and writes a full array
        (the intermediates this kernel keeps in VMEM), plus the same
        operator constants read once.  Coarse by design — a dispatch-count
        model, not a cache simulation — but it is the dense side of the
        traffic ratio ``step_traffic_estimate`` reports and makes the fusion
        win quantitative."""
        item = np.dtype(self._cast or config.real_dtype()).itemsize
        s = float(self.r0 * self.q1) * item  # working array size
        ops = 0
        for _ in self.terms:
            ops += 2 if self.has_l else 1  # one apply per side
        ops += len(self.terms) - 1  # rhs adds
        if self._const is not None:
            ops += 1
        if self._dinv is not None:
            ops += 1  # elementwise divide
        if self._b1t is not None:
            ops += 1
        if self._b0 is not None:
            ops += 1
        if self._mask is not None:
            ops += 1
        mats = sum(float(np.prod(t.l.shape)) for t in self.terms if t.l is not None)
        mats += sum(float(np.prod(t.r.shape)) for t in self.terms)
        return 2.0 * ops * s + mats * item

    # -- the fused stage ------------------------------------------------------

    def _pallas_call(self):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        gi = self._r0p // self._bi
        gj = (self._k0p // self._bj) if self.has_l else 1
        bi, bj = self._bi, self._bj
        in_specs = []
        if self.has_l:
            in_specs += [
                pl.BlockSpec((bi, bj), lambda i, j: (i, j))
                for _ in self.terms
            ]
            in_specs += [
                pl.BlockSpec((bj, k1p), lambda i, j: (j, 0))
                for k1p in self._k1p
            ]
        else:
            in_specs += [
                pl.BlockSpec((bi, k1p), lambda i, j: (i, 0))
                for k1p in self._k1p
            ]
        in_specs += [
            pl.BlockSpec((k1p, self._q1p), lambda i, j: (0, 0))
            for k1p in self._k1p
        ]
        if self._const is not None:
            in_specs.append(pl.BlockSpec((bi, self._q1p), lambda i, j: (i, 0)))
        if self._dinv is not None:
            in_specs.append(pl.BlockSpec((bi, self._q1p), lambda i, j: (i, 0)))
        if self._b1t is not None:
            in_specs.append(pl.BlockSpec((self._q1p, self._p1p), lambda i, j: (0, 0)))
        has_b0 = self._b0 is not None
        if has_b0:
            in_specs.append(pl.BlockSpec((self._p0p, bi), lambda i, j: (0, i)))
            out_spec = pl.BlockSpec((self._p0p, self._p1p), lambda i, j: (0, 0))
            out_shape = (self._p0p, self._p1p)
        else:
            out_spec = pl.BlockSpec((bi, self._p1p), lambda i, j: (i, 0))
            out_shape = (self._r0p, self._p1p)
        if self._mask is not None:
            mrows = self._p0p if has_b0 else bi
            midx = (lambda i, j: (0, 0)) if has_b0 else (lambda i, j: (i, 0))
            in_specs.append(pl.BlockSpec((mrows, self._p1p), midx))
        dt = self._rts[0].dtype
        scratch = (
            [pltpu.VMEM((bi, k1p), dt) for k1p in self._k1p]
            if self.has_l else []
        )
        params = None
        if not self.interpret:
            params = compiler_params(
                (*in_specs, out_spec),
                sum(bi * k1p for k1p in self._k1p) if self.has_l else 0,
                dt,
            )
        return pl.pallas_call(
            functools.partial(
                _stage_kernel,
                nt=len(self.terms), nj=gj, ni=gi,
                has_l=self.has_l,
                has_const=self._const is not None,
                has_dinv=self._dinv is not None,
                has_b1=self._b1t is not None,
                has_b0=has_b0,
                has_mask=self._mask is not None,
            ),
            grid=(gi, gj),
            in_specs=in_specs,
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct(out_shape, dt),
            scratch_shapes=scratch,
            compiler_params=params,
            interpret=self.interpret,
            name=self.kernel_name,
        )

    def _prep(self, x, t):
        if self.terms[t].complex_in:
            x = jnp.concatenate([x.real, x.imag], axis=0)
        dt = self._rts[0].dtype
        rows = self._k0p if self.has_l else self._r0p
        return jnp.pad(
            x.astype(dt),
            ((0, rows - x.shape[0]), (0, self._k1p[t] - x.shape[1])),
        )

    def _finish(self, out, out_dtype):
        out = out[: self.p0, : self.p1]
        if self.complex_out:
            mc = self.p0 // 2
            rdt = np.zeros(0, dtype=out_dtype).real.dtype
            return (out[:mc].astype(rdt) + 1j * out[mc:].astype(rdt)).astype(out_dtype)
        return out.astype(out_dtype)

    def apply(self, *xs):
        """The fused stage; output in the stage's composite/ortho storage
        layout — drop-in for the dense chain's result."""
        if len(xs) != len(self.terms):
            raise ValueError(
                f"stage {self.name!r} takes {len(self.terms)} inputs, got {len(xs)}"
            )
        out_dtype = xs[0].dtype
        args = [self._prep(x, t) for t, x in enumerate(xs)]
        if self.has_l:
            args = self._ls + args
        args += self._rts
        for extra in (self._const, self._dinv, self._b1t, self._b0, self._mask):
            if extra is not None:
                args.append(extra)
        return self._finish(self._pallas_call()(*args), out_dtype)

    def reference(self, *xs):
        """The same chain as plain unfused XLA dots over the same padded
        constants — the kernel-plumbing A/B denominator (the model-level
        dense A/B compares whole steps instead)."""
        out_dtype = xs[0].dtype
        prec = jax.lax.Precision.HIGHEST
        m = None
        for t, x in enumerate(xs):
            y = self._prep(x, t)
            if self.has_l:
                y = jnp.dot(self._ls[t], y, precision=prec)
            y = jnp.dot(y, self._rts[t], precision=prec)
            m = y if m is None else m + y
        if self._dinv is not None:
            m = m * self._dinv
        if self._b1t is not None:
            m = jnp.dot(m, self._b1t, precision=prec)
        if self._const is not None:
            m = m + self._const
        if self._b0 is not None:
            m = jnp.dot(self._b0, m, precision=prec)
        if self._mask is not None:
            m = m * self._mask
        return self._finish(m, out_dtype)


# -- model builders -----------------------------------------------------------


def _storage(mat, sep_in: bool, sep_out: bool) -> np.ndarray:
    """Conjugate a natural/split-form axis matrix into storage layout (the
    per-axis parity permutations of sep spaces; identity otherwise)."""
    from .folded import dense_operator

    return dense_operator(np.asarray(mat, dtype=np.float64),
                          sep_in=sep_in, sep_out=sep_out)


def _nat(space, axis: int, key):
    """Natural-order (split-form for periodic) per-axis operator matrix."""
    return space.bases[axis].axis_operator(key, sep=False).matrix


def _stack_host(arr) -> np.ndarray:
    a = np.asarray(arr)
    if np.iscomplexobj(a):
        a = np.concatenate([a.real, a.imag], axis=0)
    return a


def build_model_step(model, interpret: bool | None = None) -> dict:
    """Fused stage kernels for a Navier2D model's implicit half, keyed by
    stage tag: ``velx``/``vely`` (inputs: state field, pres, [temp,] conv
    output[, cross-velocity when Coriolis is active]), ``temp``/``scal``
    (state field, conv output), ``div`` (velx_n, vely_n), ``poisson``
    (div), ``projx``/``projy`` (pseu_n).  Raises on layouts the fused step
    does not cover (an active mesh routes around this builder)."""
    from .. import solver as slv

    sp_u, sp_t = model.velx_space, model.temp_space
    sp_p, sp_q, sp_f = model.pres_space, model.pseu_space, model.field_space
    spaces = (sp_u, sp_t, sp_p, sp_q, sp_f)
    sep = sp_u.sep
    if any(s.sep != sep for s in spaces):
        raise ValueError("fused step stages need uniform sep flags across spaces")
    cplx = sp_u.spectral_is_complex
    if any(s.spectral_is_complex != cplx for s in spaces):
        raise ValueError("fused step stages need a uniform complex flag")

    dt = model.dt
    nu, ka = model.params["nu"], model.params["ka"]
    scale = model.scale
    sx2, sy2 = scale[0] ** 2, scale[1] ** 2
    coriolis = model._coriolis()
    has_scal = model._scalar_active()

    # Helmholtz dense-equivalent axis factors (solve == A0 @ rhs @ A1^T)
    A0u = slv.hholtz_axis_solve_matrix(sp_u, 0, dt * nu / sx2)
    A1u = slv.hholtz_axis_solve_matrix(sp_u, 1, dt * nu / sy2)
    A0t = slv.hholtz_axis_solve_matrix(sp_t, 0, dt * ka / sx2)
    A1t = slv.hholtz_axis_solve_matrix(sp_t, 1, dt * ka / sy2)

    st0u, st1u = _nat(sp_u, 0, "stencil"), _nat(sp_u, 1, "stencil")
    st0p, st1p = _nat(sp_p, 0, "stencil"), _nat(sp_p, 1, "stencil")
    st0t, st1t = _nat(sp_t, 0, "stencil"), _nat(sp_t, 1, "stencil")
    st0q, st1q = _nat(sp_q, 0, "stencil"), _nat(sp_q, 1, "stencil")
    g1p0, g1p1 = _nat(sp_p, 0, ("grad", 1)), _nat(sp_p, 1, ("grad", 1))
    g1u0, g1u1 = _nat(sp_u, 0, ("grad", 1)), _nat(sp_u, 1, ("grad", 1))
    g1q0, g1q1 = _nat(sp_q, 0, ("grad", 1)), _nat(sp_q, 1, ("grad", 1))
    p0u, p1u = _nat(sp_u, 0, "proj"), _nat(sp_u, 1, "proj")

    def term(lnat, rnat, space_in, sep_out):
        return StageTerm(
            _storage(lnat, space_in.sep[0], sep_out[0]),
            _storage(rnat, space_in.sep[1], sep_out[1]),
            space_in.spectral_is_complex,
        )

    def lift_const(L, R, arr, factor):
        """Post-solve BC-lift fold: conjugate the solve factors from the
        lift field's (field-space) storage flags into the output space's
        and bake the product (``A (rhs + c*lift) == A rhs + c * A lift A^T``)."""
        if arr is None:
            return None
        Lc = _storage(L, sp_f.sep[0], sep[0])
        Rc = _storage(R, sp_f.sep[1], sep[1])
        return factor * (Lc @ _stack_host(arr) @ Rc.T)

    cast = None  # solves stay f64 under RUSTPDE_F64_HYBRID (see module doc)
    kw = dict(cast=cast, interpret=interpret)
    nx, ny = model.nx, model.ny

    # velocity stages: state + pressure-gradient + convection (+ buoyancy,
    # +/- Coriolis cross-coupling); the Helmholtz inverse folded into L/R
    terms_vx = [
        term(A0u @ st0u, A1u @ st1u, sp_u, sep),
        term((-dt / scale[0]) * (A0u @ g1p0), A1u @ st1p, sp_p, sep),
        term(-dt * A0u, A1u, sp_f, sep),
    ]
    terms_vy = [
        term(A0u @ st0u, A1u @ st1u, sp_u, sep),
        term((-dt / scale[1]) * (A0u @ st0p), A1u @ g1p1, sp_p, sep),
        term(dt * (A0u @ st0t), A1u @ st1t, sp_t, sep),
        term(-dt * A0u, A1u, sp_f, sep),
    ]
    if coriolis:
        terms_vx.append(term(dt * coriolis * (A0u @ st0u), A1u @ st1u, sp_u, sep))
        terms_vy.append(term(-dt * coriolis * (A0u @ st0u), A1u @ st1u, sp_u, sep))
    # buoyancy lift: A (rhs + dt*that) == A rhs + dt * A @ tb @ A^T
    const_vy = lift_const(A0u, A1u, model.tempbc_ortho, dt)

    stages = {
        "velx": FusedStage(f"velx_{nx}x{ny}", terms_vx, cplx, **kw),
        "vely": FusedStage(f"vely_{nx}x{ny}", terms_vy, cplx,
                           const=const_vy, **kw),
    }

    # temperature / passive scalar: state + convection + diffusion lift
    terms_t = [
        term(A0t @ st0t, A1t @ st1t, sp_t, sep),
        term(-dt * A0t, A1t, sp_f, sep),
    ]
    const_t = lift_const(A0t, A1t, model._tempbc_diff, 1.0)
    stages["temp"] = FusedStage(f"temp_{nx}x{ny}", terms_t, cplx,
                                const=const_t, **kw)
    if has_scal:
        kc = model._scalar_kappa()
        A0c = slv.hholtz_axis_solve_matrix(sp_t, 0, dt * kc / sx2)
        A1c = slv.hholtz_axis_solve_matrix(sp_t, 1, dt * kc / sy2)
        terms_c = [
            term(A0c @ st0t, A1c @ st1t, sp_t, sep),
            term(-dt * A0c, A1c, sp_f, sep),
        ]
        const_c = lift_const(A0c, A1c, model._tempbc_diff, kc / ka)
        stages["scal"] = FusedStage(f"scal_{nx}x{ny}", terms_c, cplx,
                                    const=const_c, **kw)

    # divergence RHS in scratch-ortho space (the projection solve input and
    # the pressure-update/div-norm array)
    terms_div = [
        term(g1u0 / scale[0], st1u, sp_u, sep),
        term(st0u, g1u1 / scale[1], sp_u, sep),
    ]
    stages["div"] = FusedStage(f"div_{nx}x{ny}", terms_div, cplx, **kw)

    # pressure Poisson: fast-diag modal solve with the singular pin folded
    # as an output mask (the step still calls pin_zero_mode — idempotent)
    from .folded import parity_perm

    lam0, f0, b0m = slv.modal_data_split(sp_q, 0, 1.0 / sx2, 1.0)
    lam1, f1, b1m = slv.modal_data_split(sp_q, 1, 1.0 / sy2, 1.0)
    s0 = sep[0] and f0 is not None
    s1 = sep[1] and f1 is not None
    if s0:
        lam0 = lam0[parity_perm(len(lam0))]
    if s1:
        lam1 = lam1[parity_perm(len(lam1))]
    if abs(lam0[0]) < 1e-10:
        # singular-mode nudge, exactly solver.FastDiag's fix_singular
        lam0 = lam0 - 1e-10
    dinv = 1.0 / (lam0[:, None] + lam1[None, :])
    pin = np.ones((len(lam0), b1m.shape[0] if b1m is not None else len(lam1)))
    pin[0, 0] = 0.0
    if sp_q.bases[0].kind.is_periodic:
        pin[len(lam0) // 2, 0] = 0.0  # the Im row of the k=0 mode
    if f0 is not None:
        tpo = StageTerm(_storage(f0, sep[0], s0), _storage(f1, sep[1], s1), cplx)
    else:
        tpo = StageTerm(None, _storage(f1, sep[1], s1), cplx)
    modal = (
        dinv,
        _storage(b0m, s0, sep[0]) if b0m is not None else None,
        _storage(b1m, s1, sep[1]) if b1m is not None else None,
    )
    stages["poisson"] = FusedStage(f"poisson_{nx}x{ny}", [tpo], cplx,
                                   modal=modal, mask=pin, **kw)

    # pressure-gradient projection (subtracted from the velocities outside)
    stages["projx"] = FusedStage(
        f"projx_{nx}x{ny}",
        [term((p0u @ g1q0) / scale[0], p1u @ st1q, sp_q, sep)], cplx, **kw)
    stages["projy"] = FusedStage(
        f"projy_{nx}x{ny}",
        [term(p0u @ st0q, (p1u @ g1q1) / scale[1], sp_q, sep)], cplx, **kw)

    return stages


def step_traffic_estimate(model) -> dict:
    """Analytic HBM bytes/step of the implicit (solve) half: the unfused
    dense chain vs the fused stage kernels — the quantity the megakernel
    exists to shrink (dense/fused 0.83 at 129^2, 1.41 at 257^2, 1.67 at
    513^2, f64).  Uses the model's live fused stages when present,
    else builds a throwaway set."""
    stages = getattr(model, "_step_impl", None)
    if stages is None:
        stages = build_model_step(model)  # byte accounting only: never run
    dense = sum(s.dense_hbm_bytes for s in stages.values())
    fused = sum(s.hbm_bytes for s in stages.values())
    return {
        "dense_bytes_per_step": dense,
        "pallas_bytes_per_step": fused,
        "traffic_ratio": dense / fused if fused else float("nan"),
    }
