"""Pallas TPU kernel: the fused convection-transform chain.

The convection family is a third of the confined 513^2 step's device time
and over half of the periodic 1024 x 1025 step's (PERF.md section 5),
dispatched as ~22 separate XLA ops per step with full HBM round-trips
between the derivative syntheses, the pointwise product, and the dealiased
forward.
This kernel fuses the whole chain

    dvdx = synthesis-of-d/dx(vhat)        (one GEMM per axis)
    dvdy = synthesis-of-d/dy(vhat)
    total = ux*dvdx + uy*dvdy [+ BC-lift terms]
    out  = dealiased forward(total)       (dead 2/3-rule rows DROPPED)

into one ``pl.pallas_call``: the transform GEMMs are tiled through VMEM over
physical-x blocks (grid axis 0) with the spectral-y contraction split over
grid axis 1 (VMEM scratch accumulators), so the physical-space intermediates
``dvdx``/``dvdy``/``total`` never touch HBM, and the 2/3-rule row-drop plus
dealias mask are folded into the kernel epilogue (the forward matrices carry
only the kept rows; dead rows are zero-filled outside).

The per-axis operator matrices come from the stable
``Base.axis_operator(key)`` accessor (ops/folded.py ``AxisOperator`` — sep
permutations and the dealias cut baked in), so the kernel is exact to the
dense unfused path up to floating-point reassociation on every layout:
confined (sep Chebyshev x sep Chebyshev), periodic (complex r2c converted to
the split Re/Im planes at the chain boundary), and split-sep (the TPU
layout).  Interpreter mode runs the same kernel on CPU
(tests/test_pallas_conv.py); on a TPU it compiles natively or the model
build raises ``PallasCompileRefused`` (ops/pallas_common.py).

Selection stays measurement-driven like ``solver.default_method``:
``RUSTPDE_CONV_KERNEL=dense|pallas`` (default dense until an on-chip A/B
against the dense chain lands: ROADMAP Queue 1).  VMEM: the whole-width
operands (``fyt``, the output block, the y-synthesis columns) are resident
across grid steps and the kernel asks
Mosaic for that much (``pallas_common.compiler_params``); a grid whose
residency exceeds the core's VMEM is refused at build (output-column
tiling would lift that).
"""

from __future__ import annotations

import functools

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


def conv_kernel_choice() -> str:
    """The ``RUSTPDE_CONV_KERNEL`` knob: ``"dense"`` (default — the unfused
    per-GEMM chain) or ``"pallas"`` (this kernel).  Read at model
    compile time, like the solver-method selection."""
    return config.env_get("RUSTPDE_CONV_KERNEL", "dense")


def _ceil_to(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def _conv_kernel(*refs, with_bc: bool, nj: int):
    """Grid (i over physical-x tiles, j over spectral-y contraction tiles;
    j innermost).  Stage 1 accumulates the two derivative syntheses into
    VMEM scratch; the j-final epilogue forms the pointwise product and the
    dealiased forward, accumulating the output block over the i tiles."""
    from jax.experimental import pallas as pl

    if with_bc:
        (gx1, gx0, v, gy0t, gy1t, ux, uy, bcdx, bcdy, fx, fyt, o, adx, ady) = refs
    else:
        (gx1, gx0, v, gy0t, gy1t, ux, uy, fx, fyt, o, adx, ady) = refs
        bcdx = bcdy = None
    i = pl.program_id(0)
    j = pl.program_id(1)
    acc_t = o.dtype
    prec = jax.lax.Precision.HIGHEST
    # stage 1: this (i, j) tile's contribution to the two half-transforms —
    # a1/a0 are (bx, bj) column slices, their y-syntheses accumulate over j
    a1 = jnp.dot(gx1[...], v[...], precision=prec, preferred_element_type=acc_t)
    a0 = jnp.dot(gx0[...], v[...], precision=prec, preferred_element_type=acc_t)
    pdx = jnp.dot(a1, gy0t[...], precision=prec, preferred_element_type=acc_t)
    pdy = jnp.dot(a0, gy1t[...], precision=prec, preferred_element_type=acc_t)

    @pl.when(j == 0)
    def _init():
        adx[...] = pdx
        ady[...] = pdy

    @pl.when(j > 0)
    def _accum():
        adx[...] = adx[...] + pdx
        ady[...] = ady[...] + pdy

    @pl.when(j == nj - 1)
    def _epilogue():
        dvdx = adx[...]
        dvdy = ady[...]
        if with_bc:
            # ux*tb_dx + uy*tb_dy folded as a shift of the derivatives
            dvdx = dvdx + bcdx[...]
            dvdy = dvdy + bcdy[...]
        total = ux[...] * dvdx + uy[...] * dvdy
        part = jnp.dot(total, fyt[...], precision=prec, preferred_element_type=acc_t)
        part = jnp.dot(fx[...], part, precision=prec, preferred_element_type=acc_t)

        @pl.when(i == 0)
        def _first():
            o[...] = part

        @pl.when(i > 0)
        def _rest():
            o[...] = o[...] + part


class FusedConv:
    """The fused convection chain for one (input space, scratch space) pair:
    ``apply(ux, uy, vhat[, bc_dx, bc_dy])`` == the unfused
    ``forward_dealiased(ux*d(vhat)/dx + uy*d(vhat)/dy [+ bc])`` of
    models/navier.py's ``conv``, computed in one Pallas kernel.

    ``cast`` mirrors the f64-hybrid convention of ``Base._sep_dev``: store
    the operator matrices in that dtype and run the chain through it, casting
    the f64 inputs in and the output back (the hybrid keeps ONE round-trip
    where the per-GEMM dense path casts around every apply — strictly fewer
    roundings).  ``interpret`` defaults to True on the CPU (the CI parity
    suite) and False on a TPU; ``reference()`` is the unfused chain for A/B
    and tests."""

    def __init__(
        self,
        space_in,
        field_space,
        scale,
        cast=None,
        interpret: bool | None = None,
        block_x: int | None = None,
        block_k: int | None = None,
    ):
        self.space_in = space_in
        self.field_space = field_space
        self.scale = tuple(scale)
        if space_in.shape_physical != field_space.shape_physical:
            raise ValueError("conv spaces must share the physical grid")
        bx_in, by_in = space_in.bases
        fx_b, fy_b = field_space.bases
        self.complex_in = bx_in.spectral_is_complex
        self.complex_out = fx_b.spectral_is_complex
        if self.complex_in != self.complex_out:
            raise ValueError("mixed complex/real x-axes are unsupported")

        gx1 = bx_in.axis_operator(("bwd_grad", 1), sep=space_in.sep[0]).matrix
        gx0 = bx_in.axis_operator("bwd", sep=space_in.sep[0]).matrix
        gy1 = by_in.axis_operator(("bwd_grad", 1), sep=space_in.sep[1]).matrix
        gy0 = by_in.axis_operator("bwd", sep=space_in.sep[1]).matrix
        op_fx = fx_b.axis_operator("fwd_cut", sep=field_space.sep[0])
        op_fy = fy_b.axis_operator("fwd_cut", sep=field_space.sep[1])
        gx1 = gx1 / self.scale[0]
        gy1 = gy1 / self.scale[1]
        kept_x = (
            op_fx.kept_rows
            if op_fx.kept_rows is not None
            else np.arange(op_fx.matrix.shape[0])
        )
        kept_y = (
            op_fy.kept_rows
            if op_fy.kept_rows is not None
            else np.arange(op_fy.matrix.shape[0])
        )
        fxm = op_fx.matrix[kept_x]
        fym = op_fy.matrix[kept_y]
        self._kept_x = kept_x
        self._kept_y = kept_y

        nx, ny = space_in.shape_physical
        mx, my = gx0.shape[1], gy0.shape[1]
        kx, ky = fxm.shape[0], fym.shape[0]
        self.nx, self.ny, self.mx, self.my, self.kx, self.ky = nx, ny, mx, my, kx, ky

        bx = int(block_x or config.env_get("RUSTPDE_PALLAS_CONV_BLOCK", 256))
        bx = max(LANE, _ceil_to(bx, LANE))
        self.nxp = _ceil_to(nx, bx)
        self.bx = min(bx, self.nxp)
        self.mxp = _ceil_to(mx, LANE)
        self.myp = _ceil_to(my, LANE)
        bj = int(block_k or config.env_get("RUSTPDE_PALLAS_CONV_BLOCK_K", 512))
        bj = max(LANE, (bj // LANE) * LANE)
        while self.myp % bj:
            bj -= LANE
        self.bj = bj
        self.nyp = _ceil_to(ny, LANE)
        self.kxp = _ceil_to(kx, SUBLANE)
        self.kyp = _ceil_to(ky, LANE)

        # shape-keyed kernel name: the flop registry prices pallas_call eqns
        # BY NAME, so two chains with different shapes must not collide
        # (equal shapes share the entry harmlessly)
        self.kernel_name = (
            f"fused_conv_{nx}x{ny}_{mx}x{my}_{kx}x{ky}"
        )
        self._cast = np.dtype(cast) if cast is not None else None
        dt = self._cast or config.real_dtype()
        from .folded import pad_dense

        with jax.ensure_compile_time_eval():

            def place(m, rows, cols):
                return jnp.asarray(pad_dense(np.asarray(m), rows, cols).astype(dt))

            self._gx1 = place(gx1, self.nxp, self.mxp)
            self._gx0 = place(gx0, self.nxp, self.mxp)
            self._gy0t = place(gy0.T, self.myp, self.nyp)
            self._gy1t = place(gy1.T, self.myp, self.nyp)
            self._fx = place(fxm, self.kxp, self.nxp)
            self._fyt = place(fym.T, self.nyp, self.kyp)
        self.interpret = resolve_interpret(interpret)

    def check_compiles(self) -> None:
        """On a TPU, compile both call forms (with and without the BC-lift
        terms) now, so a Mosaic refusal is one typed error at model build
        (:class:`~.pallas_common.PallasCompileRefused`); nothing to check
        under the CPU interpreter."""
        if self.interpret:
            return
        sp = self.space_in
        phys = jax.ShapeDtypeStruct(sp.shape_physical, config.real_dtype())
        vhat = jax.ShapeDtypeStruct(sp.shape_spectral, sp.spectral_dtype())
        for args in ((phys, phys, vhat), (phys, phys, vhat, phys, phys)):
            require_native_compile(
                "RUSTPDE_CONV_KERNEL=pallas", self.kernel_name, self.apply, *args
            )

    # -- the fused chain ------------------------------------------------------

    def _pallas_call(self, with_bc: bool, batch: bool = False):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        del batch
        gi = self.nxp // self.bx
        gj = self.myp // self.bj
        in_specs = [
            pl.BlockSpec((self.bx, self.mxp), lambda i, j: (i, 0)),  # gx1
            pl.BlockSpec((self.bx, self.mxp), lambda i, j: (i, 0)),  # gx0
            pl.BlockSpec((self.mxp, self.bj), lambda i, j: (0, j)),  # vhat
            pl.BlockSpec((self.bj, self.nyp), lambda i, j: (j, 0)),  # gy0t
            pl.BlockSpec((self.bj, self.nyp), lambda i, j: (j, 0)),  # gy1t
            pl.BlockSpec((self.bx, self.nyp), lambda i, j: (i, 0)),  # ux
            pl.BlockSpec((self.bx, self.nyp), lambda i, j: (i, 0)),  # uy
        ]
        if with_bc:
            in_specs += [
                pl.BlockSpec((self.bx, self.nyp), lambda i, j: (i, 0)),  # bc dx
                pl.BlockSpec((self.bx, self.nyp), lambda i, j: (i, 0)),  # bc dy
            ]
        in_specs += [
            pl.BlockSpec((self.kxp, self.bx), lambda i, j: (0, i)),  # fx
            pl.BlockSpec((self.nyp, self.kyp), lambda i, j: (0, 0)),  # fyt
        ]
        dt = self._gx1.dtype
        out_spec = pl.BlockSpec((self.kxp, self.kyp), lambda i, j: (0, 0))
        params = None
        if not self.interpret:
            params = compiler_params(
                (*in_specs, out_spec), 2 * self.bx * self.nyp, dt
            )
        return pl.pallas_call(
            functools.partial(_conv_kernel, with_bc=with_bc, nj=gj),
            grid=(gi, gj),
            in_specs=in_specs,
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((self.kxp, self.kyp), dt),
            scratch_shapes=[
                pltpu.VMEM((self.bx, self.nyp), dt),
                pltpu.VMEM((self.bx, self.nyp), dt),
            ],
            compiler_params=params,
            interpret=self.interpret,
            name=self.kernel_name,
        )

    def _pad_phys(self, a, dt):
        return jnp.pad(
            a.astype(dt), ((0, self.nxp - self.nx), (0, self.nyp - self.ny))
        )

    def apply(self, ux, uy, vhat, bc_dx=None, bc_dy=None):
        """The fused chain; output in the scratch space's spectral storage
        layout with the dealias-dead rows zero-filled — drop-in for the
        dense ``forward_dealiased(...)`` result."""
        out_dtype = vhat.dtype
        if self.complex_in:
            v = jnp.concatenate([vhat.real, vhat.imag], axis=0)
        else:
            v = vhat
        dt = self._gx1.dtype
        v = jnp.pad(
            v.astype(dt), ((0, self.mxp - self.mx), (0, self.myp - self.my))
        )
        args = [self._gx1, self._gx0, v, self._gy0t, self._gy1t,
                self._pad_phys(ux, dt), self._pad_phys(uy, dt)]
        with_bc = bc_dx is not None
        if with_bc:
            args += [self._pad_phys(bc_dx, dt), self._pad_phys(bc_dy, dt)]
        args += [self._fx, self._fyt]
        out = self._pallas_call(with_bc)(*args)[: self.kx, : self.ky]
        shape = self.field_space.shape_spectral
        if self.complex_out:
            # split kept rows are [0:kc] (Re) and [mc:mc+kc] (Im), compacted
            # by the kernel to [0:kc]+[kc:2kc]: reassemble the complex modes
            kc = self.kx // 2
            rdt = np.zeros(0, dtype=out_dtype).real.dtype
            res = (out[:kc].astype(rdt) + 1j * out[kc:].astype(rdt)).astype(out_dtype)
            full = jnp.zeros(shape, dtype=out_dtype)
            return full.at[np.ix_(np.arange(kc), self._kept_y)].set(res)
        full = jnp.zeros(shape, dtype=out_dtype)
        return full.at[np.ix_(self._kept_x, self._kept_y)].set(
            out.astype(out_dtype)
        )

    def reference(self, ux, uy, vhat, bc_dx=None, bc_dy=None, fast=True):
        """The unfused dense chain (exactly models/navier.py's ``conv``):
        the A/B denominator of the parity tests."""
        sp, fs = self.space_in, self.field_space
        dvdx = sp.backward_gradient(vhat, (1, 0), self.scale, fast=fast)
        dvdy = sp.backward_gradient(vhat, (0, 1), self.scale, fast=fast)
        total = ux * dvdx + uy * dvdy
        if bc_dx is not None:
            total = total + ux * bc_dx + uy * bc_dy
        if any(fs.sep):
            return fs.forward_dealiased(total, fast=fast)
        mask = jnp.asarray(fs.dealias_mask(), dtype=config.real_dtype())
        return fs.forward(total) * mask


def hybrid_cast():
    """The f64-hybrid cast the model convection path runs under
    ``RUSTPDE_F64_HYBRID=1`` (same convention as ``Base._sep_dev``):
    operator matrices stored f32, f64 state cast through the chain."""
    if config.X64 and config.env_get("RUSTPDE_F64_HYBRID") == "1":
        return np.float32
    return None


def build_model_convs(model, interpret: bool | None = None) -> dict:
    """``{id(space): FusedConv}`` for a Navier-family model's convection
    spaces (velx/vely share one space object; temp has its own), keyed so
    the step's ``conv(ux, uy, space, vhat)`` can route by identity."""
    cast = hybrid_cast()
    specs: dict[int, FusedConv] = {}
    for space in (model.velx_space, model.temp_space):
        if id(space) in specs:
            continue
        fc = FusedConv(space, model.field_space, model.scale, cast=cast,
                       interpret=interpret)
        specs[id(space)] = fc
    return specs


def bench_conv_paths(n: int = 129, repeats: int = 20):
    """Microbenchmark: fused Pallas chain vs the unfused dense chain on this
    backend at a confined grid — the measurement behind the
    RUSTPDE_CONV_KERNEL default (the CPU interpreter measures only
    correctness plumbing, not speed; the A/B needs a chip)."""
    import time

    from ..bases import Space2, cheb_dirichlet, chebyshev

    sp = Space2(cheb_dirichlet(n), cheb_dirichlet(n))
    fs = Space2(chebyshev(n), chebyshev(n))
    fc = FusedConv(sp, fs, (1.0, 1.0))
    rng = np.random.default_rng(0)
    rdt = config.real_dtype()
    ux = jnp.asarray(rng.standard_normal((n, n)), dtype=rdt)
    uy = jnp.asarray(rng.standard_normal((n, n)), dtype=rdt)
    vhat = sp.forward(jnp.asarray(rng.standard_normal((n, n)), dtype=rdt))
    results = {}
    for name, fn in (
        ("pallas", jax.jit(fc.apply)),
        ("dense", jax.jit(fc.reference)),
    ):
        out = fn(ux, uy, vhat)
        np.asarray(out.real[:1, :1])
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(ux, uy, vhat)
        np.asarray(out.real[:1, :1])
        results[name] = (time.perf_counter() - t0) / repeats
    return results
