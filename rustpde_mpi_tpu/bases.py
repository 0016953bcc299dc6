"""Spectral bases and 2-D tensor-product spaces.

TPU-native rebuild of the basis layer the reference re-exports from the
external ``funspace`` crate (/root/reference/src/bases.rs:11-19; full contract
reconstructed in SURVEY.md S2.2).  Public vocabulary matches the reference:

    chebyshev(n), cheb_dirichlet(n), cheb_neumann(n),
    cheb_dirichlet_neumann(n), fourier_r2c(n), fourier_c2c(n), Space2

Design (idiomatic JAX, not a port): every base precomputes small dense/banded
operator matrices on the host in numpy f64 — stencil S (composite -> ortho),
Galerkin projection P (ortho -> composite), coefficient-space derivatives,
the Chebyshev quasi-inverse B2 — and the device work is FFTs/DCTs or batched
matmuls over those constants.  No in-place mutation anywhere; fields are
plain arrays.
"""

from __future__ import annotations

import enum
from functools import cached_property

import jax
import jax.numpy as jnp
import numpy as np

from . import config
from .ops import chebyshev as chb
from .ops import fourier as fou
from .ops import fourstep
from .ops import transforms as tr
from .ops.folded import FoldedMatrix
from .telemetry import tracing as _tr


class BaseKind(enum.Enum):
    CHEBYSHEV = "chebyshev"
    CHEB_DIRICHLET = "cheb_dirichlet"
    CHEB_NEUMANN = "cheb_neumann"
    CHEB_DIRICHLET_NEUMANN = "cheb_dirichlet_neumann"
    FOURIER_R2C = "fourier_r2c"
    FOURIER_C2C = "fourier_c2c"
    FOURIER_R2C_SPLIT = "fourier_r2c_split"

    @property
    def is_chebyshev(self) -> bool:
        return self in (
            BaseKind.CHEBYSHEV,
            BaseKind.CHEB_DIRICHLET,
            BaseKind.CHEB_NEUMANN,
            BaseKind.CHEB_DIRICHLET_NEUMANN,
        )

    @property
    def is_periodic(self) -> bool:
        return self in (
            BaseKind.FOURIER_R2C,
            BaseKind.FOURIER_C2C,
            BaseKind.FOURIER_R2C_SPLIT,
        )

    @property
    def is_split(self) -> bool:
        return self == BaseKind.FOURIER_R2C_SPLIT


_FAST_DERIV = config.env_get("RUSTPDE_FAST_DERIV", "auto")
_FAST_DERIV_MIN = int(config.env_get("RUSTPDE_FAST_DERIV_MIN", "2048"))


def _fast_deriv_enabled(n: int, sep: bool = False) -> bool:
    """Chebyshev derivatives via the parity-cumsum recurrence
    (ops/transforms.cheb_derivative) instead of dense triangular GEMMs.
    ``RUSTPDE_FAST_DERIV``: "auto" (default), "1" (always), "0" (never).
    Auto is measured on the v5e (building blocks timed in isolation,
    round 3): f32 cumsum 0.22 vs GEMM 0.46 ms at 2049 but 0.11 vs 0.07 at
    1025 (dispatch/bandwidth bound), and in *emulated f64* the cumsum's scan
    ops are 2-5x slower than the MXU GEMM at every tested size — so the
    recurrence engages only for f32 at n >= 2048.  Under the parity-
    separated layout the GEMM gradient is gather-free block MXU work and
    auto never engages: measured at the 2049^2 step (round 4), cumsum
    18.7 ms vs GEMM 16.4 ms."""
    if _FAST_DERIV == "0":
        return False
    if _FAST_DERIV == "1":
        return True
    return n >= _FAST_DERIV_MIN and not config.X64 and not sep


def _dev(mat: np.ndarray):
    """Host f64 matrix -> device constant in the configured precision.

    ``ensure_compile_time_eval`` keeps the constant concrete even when the
    first (lazy) materialization happens inside a jit trace — otherwise the
    cached value would be a leaked tracer."""
    import jax

    with jax.ensure_compile_time_eval():
        if np.iscomplexobj(mat):
            return jnp.asarray(mat.astype(config.complex_dtype()))
        return jnp.asarray(mat.astype(config.real_dtype()))


class Base:
    """One spectral base along one axis.

    ``n``: physical grid size; ``m``: number of spectral modes
    (n-2 for composite Galerkin bases, n//2+1 for r2c, else n).
    """

    def __init__(self, kind: BaseKind, n: int):
        self.kind = kind
        self.n = n
        self._diff_cache: dict = {}
        self._grad_cache: dict = {}
        self._grad_dev_cache: dict = {}
        # fused projection-gradient device operators (fused_projection_gradient):
        # living on the instance ties their lifetime to the weak _BASE_CACHE
        self._proj_grad_cache: dict = {}
        if kind in (BaseKind.CHEBYSHEV, BaseKind.FOURIER_C2C):
            self.m = n
        elif kind == BaseKind.FOURIER_R2C:
            self.m = n // 2 + 1
        else:
            self.m = n - 2

    def __repr__(self):
        return f"Base({self.kind.value}, n={self.n})"

    # -- grid ---------------------------------------------------------------

    @cached_property
    def points(self) -> np.ndarray:
        if self.kind.is_chebyshev:
            return chb.cgl_points(self.n)
        return fou.fourier_points(self.n)

    @property
    def is_periodic(self) -> bool:
        return self.kind.is_periodic

    @property
    def spectral_is_complex(self) -> bool:
        return self.kind.is_periodic

    # -- host operator matrices (funspace contract, SURVEY.md S2.2) ---------

    @cached_property
    def stencil(self) -> np.ndarray:
        """S, (n x m): composite coefficients -> orthogonal coefficients."""
        if self.kind == BaseKind.CHEBYSHEV:
            return chb.stencil_chebyshev(self.n)
        if self.kind == BaseKind.CHEB_DIRICHLET:
            return chb.stencil_dirichlet(self.n)
        if self.kind == BaseKind.CHEB_NEUMANN:
            return chb.stencil_neumann(self.n)
        if self.kind == BaseKind.CHEB_DIRICHLET_NEUMANN:
            return chb.stencil_dirichlet_neumann(self.n)
        return np.eye(self.m)

    @cached_property
    def projection(self) -> np.ndarray:
        """P, (m x n): weighted Galerkin projection ortho -> composite
        (funspace `from_ortho`)."""
        if self.kind.is_chebyshev:
            return chb.projection_matrix(self.stencil)
        return np.eye(self.m)

    @cached_property
    def wavenumbers(self) -> np.ndarray:
        if self.kind == BaseKind.FOURIER_R2C:
            return fou.wavenumbers_r2c(self.n)
        if self.kind == BaseKind.FOURIER_C2C:
            return fou.wavenumbers_c2c(self.n)
        raise ValueError("wavenumbers only defined for Fourier bases")

    def diff_ortho(self, order: int) -> np.ndarray:
        """Derivative operator in the *orthogonal* coefficient space.

        Chebyshev: dense (n x n) upper-triangular recurrence matrix.
        Fourier: returned as a diagonal (1-D array) of (i k)^order.
        """
        if order not in self._diff_cache:
            if self.kind.is_chebyshev:
                self._diff_cache[order] = chb.diff_matrix(self.n, order)
            else:
                self._diff_cache[order] = fou.diff_diag(
                    self.wavenumbers, order, self.n, self.kind == BaseKind.FOURIER_R2C
                )
        return self._diff_cache[order]

    def gradient_matrix(self, order: int) -> np.ndarray:
        """D^order @ S: composite coefficients -> ortho derivative coeffs.

        For Fourier bases this is diagonal and returned 1-D.
        """
        if order not in self._grad_cache:
            if self.kind.is_chebyshev:
                self._grad_cache[order] = self.diff_ortho(order) @ self.stencil
            else:
                self._grad_cache[order] = self.diff_ortho(order)
        return self._grad_cache[order]

    # funspace operator-matrix contract used by the solver layer
    # (/root/reference/src/field.rs:195-249)

    def mass(self) -> np.ndarray:
        """The stencil S (identity for orthogonal/Fourier bases)."""
        return self.stencil

    def laplace(self) -> np.ndarray:
        """D2 in ortho coefficient space (dense for Chebyshev, diag for Fourier)."""
        if self.kind.is_chebyshev:
            return self.diff_ortho(2)
        return np.diag(-(self.wavenumbers**2))

    def laplace_inv(self) -> np.ndarray:
        """Chebyshev quasi-inverse B2 of D2 (rows 0,1 zero)."""
        if not self.kind.is_chebyshev:
            raise ValueError("laplace_inv only defined for Chebyshev bases")
        return chb.quasi_inverse_b2(self.n)

    def laplace_inv_eye(self) -> np.ndarray:
        """(n-2) x n restriction selecting rows 2.. (B2 @ D2 restricted = I)."""
        if not self.kind.is_chebyshev:
            raise ValueError("laplace_inv_eye only defined for Chebyshev bases")
        return chb.restricted_eye(self.n)

    # -- device transforms --------------------------------------------------

    # transform/operator matrices are wrapped in FoldedMatrix: the even/odd
    # parity every pure-Chebyshev operator carries (the reference's stride-2
    # structure, solver/tdma.rs:49-82) halves the GEMM flops; matrices
    # without the structure (mixed-BC bases) automatically run the plain GEMM

    @cached_property
    def _sep_cache(self) -> dict:
        """Device matrices for the parity-separated spectral layout
        (ops/folded.py sep classes), cached per shared Base instance."""
        return {}

    def _sep_dev(self, key) -> FoldedMatrix:
        """Sep-layout counterpart of the folded device matrices.  ``key``:
        "fwd" | "bwd" | "stencil" | "proj" | "synthesis" | "fwd_cut" |
        ("grad", order) | ("bwd_grad", order); appending "fast" to a
        synthesis-type key — ("bwd", "fast") / ("bwd_grad", order, "fast") —
        selects the 3-pass variant below.

        "fast" synthesis variants: the DNS step's convection syntheses
        (spectral -> physical values feeding the dealiased products) run the
        3-pass bf16 MXU mode in f32: measured on the v5e at Ra=1e9, step
        rate +17-18% (1025^2 -> ~667 steps/s, 2049^2 -> ~93), shadow drift
        vs f64 1.6e-5 (gate 1e-2), and a 4096-step random-IC trajectory
        statistically indistinguishable from "highest" (Re to 4 digits, same
        div decay).  ONLY the explicit fast keys downgrade — general
        backward()/get_field/observables/IO keep full precision (a global
        default corrupted the standalone-Poisson MMS readback to 3.7e-2).
        The round-2 NaN came from GLOBAL "high"; solves and analysis
        forwards always stay "highest".  RUSTPDE_SYNTH_PRECISION=highest
        disables (build-time gate); f64 never downgrades."""
        if not self.kind.is_chebyshev:
            raise ValueError("sep layout is defined for Chebyshev-family bases only")
        cache = self._sep_cache
        fast = isinstance(key, tuple) and key[-1] == "fast"
        base_key = (key[0] if len(key) == 2 else key[:-1]) if fast else key
        if key in cache:
            return cache[key]
        synth_prec = None
        cast = None
        if fast and not config.X64:
            if base_key == "fwd_cut":
                # the dealiased convection FORWARD has its own knob, default
                # OFF (highest): unlike the syntheses it writes the solve
                # rhs directly, so the downgrade ships only once measured
                # on-chip + shadow-gated (RUSTPDE_FWD_PRECISION=high to A/B)
                env = config.env_get("RUSTPDE_FWD_PRECISION", "highest")
            else:
                env = config.env_get("RUSTPDE_SYNTH_PRECISION", "high")
            synth_prec = None if env in ("", "highest") else env
        elif fast and config.X64 and config.env_get("RUSTPDE_F64_HYBRID") == "1":
            # f64-hybrid (SURVEY S7 / VERDICT r4 next #3b): the convection
            # transforms — the step's fast keys, nothing else — run as f32
            # GEMMs (device matrices stored f32, inputs cast in, outputs cast
            # back to f64), dodging the f64 emulation on the dominant
            # transform flops (on a v5e a float64 513^2 step takes 22.0
            # times the float32 step's device time and a convection chain
            # 22-28 times: PERF.md section 5, chip run of PR 33) while every
            # solve, analysis forward, observable and IO stays full f64.
            # Opt-in; judged in the cell rbc513_f64.solo under its limits
            # before any default flip.
            cast = np.float32
        if fast and synth_prec is None and cast is None:
            # no downgrade requested (f64 without hybrid, or
            # RUSTPDE_*_PRECISION=highest): the fast key is byte-identical to
            # the base entry — alias it instead of re-detecting and
            # double-placing the device matrix
            cache[key] = self._sep_dev(base_key)
            return cache[key]
        if base_key == "fwd":
            fm = FoldedMatrix(
                self.projection @ chb.analysis_matrix(self.n), _dev, sep_out=True, cast=cast
            )
        elif base_key == "bwd":
            fm = FoldedMatrix(
                chb.synthesis_matrix(self.n) @ self.stencil, _dev, sep_in=True, cast=cast
            )
        elif base_key == "stencil":
            fm = FoldedMatrix(self.stencil, _dev, sep_in=True, sep_out=True, cast=cast)
        elif base_key == "proj":
            fm = FoldedMatrix(self.projection, _dev, sep_in=True, sep_out=True, cast=cast)
        elif base_key == "synthesis":
            fm = FoldedMatrix(chb.synthesis_matrix(self.n), _dev, sep_in=True, cast=cast)
        elif base_key == "fwd_cut":
            # forward with the 2/3-rule dealias folded in: the zeroed output
            # modes are dropped from the GEMM (keep_rows), so the dealiased
            # forward costs 2/3 flops and no mask multiply
            fm = FoldedMatrix(
                self.projection @ chb.analysis_matrix(self.n),
                _dev,
                sep_out=True,
                keep_rows=self.m * 2 // 3,
                cast=cast,
            )
        elif isinstance(base_key, tuple) and base_key[0] == "bwd_grad":
            # synthesis-of-derivative fusion: physical values of the order-th
            # derivative straight from composite coefficients — one GEMM
            # instead of gradient + synthesis (the odd-order product carries
            # the sign-shifted synthesis symmetry, _SynthesisSep sign=-1)
            fm = FoldedMatrix(
                chb.synthesis_matrix(self.n) @ self.gradient_matrix(base_key[1]),
                _dev,
                sep_in=True,
                cast=cast,
            )
        else:  # ("grad", order)
            fm = FoldedMatrix(
                self.gradient_matrix(base_key[1]),
                _dev,
                sep_in=True,
                sep_out=True,
                cast=cast,
            )
        # a transform's forms honor the override (the _SynthesisSep family
        # and, below the fold gate of ops/folded.py, the one plain product);
        # unstructured _Plain fallbacks stay at session precision
        fm.set_precision(synth_prec)
        cache[key] = fm
        return cache[key]

    @cached_property
    def _fwd_matrix(self) -> FoldedMatrix:
        if self.kind.is_chebyshev:
            return FoldedMatrix(self.projection @ chb.analysis_matrix(self.n), _dev)
        raise ValueError("matmul transform only for Chebyshev bases")

    @cached_property
    def _bwd_matrix(self) -> FoldedMatrix:
        if self.kind.is_chebyshev:
            return FoldedMatrix(chb.synthesis_matrix(self.n) @ self.stencil, _dev)
        raise ValueError("matmul transform only for Chebyshev bases")

    @cached_property
    def _stencil_dev(self) -> FoldedMatrix:
        return FoldedMatrix(self.stencil, _dev)

    @cached_property
    def _proj_dev(self) -> FoldedMatrix:
        return FoldedMatrix(self.projection, _dev)

    @cached_property
    def _synthesis_dev(self) -> FoldedMatrix:
        return FoldedMatrix(chb.synthesis_matrix(self.n), _dev)

    # -- four-step fast DCT path (ops/fourstep.py) ---------------------------
    #
    # Both Chebyshev transform directions are diagonal scalings around the
    # size-(N+1) cosine kernel, which factors through a length-2N four-step
    # real DFT: O(n^1.5) MXU flops instead of the O(n^2) dense matrices the
    # funspace reference pays rustdct to avoid (SURVEY.md S2.2).

    @cached_property
    def _dct_plan(self):
        N = self.n - 1
        if N < 2 or not fourstep.enabled(2 * N, "dct"):
            return None
        return fourstep.Dct1Plan(self.n, _dev)

    @cached_property
    def _dct_diags(self):
        """(sigma*(-1)^k analysis row scale, (-1)^k signs) device constants;
        reshaped for axis-0 broadcasting at the call sites."""
        n = self.n
        N = n - 1
        sigma = np.full(n, 1.0 / N)
        sigma[0] = sigma[-1] = 1.0 / (2.0 * N)
        signs = (-1.0) ** np.arange(n)
        return _dev(sigma * signs), _dev(signs)

    def _fast_analysis(self, v, axis: int):
        """uhat = analysis_matrix @ u == sigma*(-1)^k * Re(rfft(ext(u)))."""
        x = jnp.moveaxis(v, axis, 0)
        row_scale, _ = self._dct_diags
        out = self._dct_plan.apply(x)
        out = out * row_scale.reshape((self.n,) + (1,) * (out.ndim - 1)).astype(
            out.real.dtype
        )
        return jnp.moveaxis(out, 0, axis)

    def _fast_synthesis(self, c, axis: int):
        """u = synthesis_matrix @ c via the same cosine core:
        with g = (-1)^k * c,  u_j = 0.5*core(g)_j + 0.5*(g_0 + (-1)^j g_N)."""
        x = jnp.moveaxis(c, axis, 0)
        _, signs = self._dct_diags
        sg = signs.reshape((self.n,) + (1,) * (x.ndim - 1)).astype(x.real.dtype)
        g = x * sg
        out = 0.5 * self._dct_plan.apply(g) + 0.5 * (g[0][None] + sg * g[-1][None])
        return jnp.moveaxis(out, 0, axis)

    def _gradient_dev(self, order: int):
        """Chebyshev: FoldedMatrix; Fourier: cached device diagonal."""
        if order not in self._grad_dev_cache:
            mat = self.gradient_matrix(order)
            self._grad_dev_cache[order] = (
                FoldedMatrix(mat, _dev) if self.kind.is_chebyshev else _dev(mat)
            )
        return self._grad_dev_cache[order]

    def forward(self, v, axis: int, method: str = "fft", sep: bool = False):
        """Physical -> (composite) spectral along ``axis``."""
        if self.kind.is_chebyshev:
            if sep:
                # sep layout: matmul only (the fast DCT/FFT cores produce the
                # natural interleaved order)
                return self._sep_dev("fwd").apply(v, axis)
            if method == "matmul":
                if self.kind == BaseKind.CHEBYSHEV and self._dct_plan is not None:
                    # pure base: projection is the identity, so the whole
                    # forward is the fast DCT core (composite bases keep the
                    # fused dense P @ F GEMM — P is dense-checkerboard, so
                    # splitting it out would not reduce flops)
                    return self._fast_analysis(v, axis)
                return self._fwd_matrix.apply(v, axis)
            c = tr.cheb_forward_fft(v, axis)
            return self.from_ortho(c, axis)
        if self.kind == BaseKind.FOURIER_R2C:
            return tr.fourier_r2c_forward_fft(v, axis)
        return tr.fourier_c2c_forward_fft(v, axis)

    def backward(self, vhat, axis: int, method: str = "fft", sep: bool = False):
        """(Composite) spectral -> physical along ``axis``."""
        if self.kind.is_chebyshev:
            if sep:
                return self._sep_dev("bwd").apply(vhat, axis)
            if method == "matmul":
                if self._dct_plan is not None:
                    # banded stencil + fast DCT synthesis — cheaper than the
                    # fused dense synthesis @ S GEMM in every composite case
                    return self._fast_synthesis(self.to_ortho(vhat, axis), axis)
                return self._bwd_matrix.apply(vhat, axis)
            return tr.cheb_backward_fft(self.to_ortho(vhat, axis), axis)
        if self.kind == BaseKind.FOURIER_R2C:
            return tr.fourier_r2c_backward_fft(vhat, axis, self.n)
        return tr.fourier_c2c_backward_fft(vhat, axis, self.n)

    def backward_ortho(self, c, axis: int, method: str = "fft", sep: bool = False):
        """Synthesize physical values from *orthogonal* coefficients along
        ``axis`` (no composite cast — gradients already live in ortho space)."""
        if self.kind.is_chebyshev:
            if sep:
                return self._sep_dev("synthesis").apply(c, axis)
            if method == "matmul":
                if self._dct_plan is not None:
                    return self._fast_synthesis(c, axis)
                return self._synthesis_dev.apply(c, axis)
            return tr.cheb_backward_fft(c, axis)
        if self.kind == BaseKind.FOURIER_R2C:
            return tr.fourier_r2c_backward_fft(c, axis, self.n)
        return tr.fourier_c2c_backward_fft(c, axis, self.n)

    @property
    def is_orthogonal(self) -> bool:
        """The base is its own orthogonal space (no composite cast):
        ``to_ortho`` and ``from_ortho`` are the identity."""
        return not self.kind.is_chebyshev or self.kind == BaseKind.CHEBYSHEV

    def to_ortho(self, vhat, axis: int, sep: bool = False):
        if self.is_orthogonal:
            return vhat
        if sep:
            return self._sep_dev("stencil").apply(vhat, axis)
        return self._stencil_dev.apply(vhat, axis)

    def from_ortho(self, c, axis: int, sep: bool = False):
        if self.is_orthogonal:
            return c
        if sep:
            return self._sep_dev("proj").apply(c, axis)
        return self._proj_dev.apply(c, axis)

    def gradient(self, vhat, order: int, axis: int, sep: bool = False):
        """Composite spectral -> ortho-space derivative coefficients."""
        if order == 0:
            return self.to_ortho(vhat, axis, sep)
        if self.kind.is_chebyshev:
            if sep:
                if _fast_deriv_enabled(self.n, sep=True):
                    # the recurrence's parity split IS the sep storage order
                    return tr.cheb_derivative_sep(
                        self.to_ortho(vhat, axis, sep=True), order, axis
                    )
                return self._sep_dev(("grad", order)).apply(vhat, axis)
            if _fast_deriv_enabled(self.n):
                # banded stencil + parity-cumsum recurrence: O(n) per lane
                # instead of the dense upper-triangular D^order @ S GEMM
                return tr.cheb_derivative(self.to_ortho(vhat, axis), order, axis)
            return self._gradient_dev(order).apply(vhat, axis)
        return tr.apply_diag(self._gradient_dev(order), vhat, axis)

    def dealias_cut(self) -> np.ndarray:
        """1-D 2/3-rule mask over this base's spectral rows
        (/root/reference/src/navier_stokes/functions.rs:72-82); the single
        home of the cutoff convention for every space class."""
        cut = np.ones(self.m)
        cut[self.m * 2 // 3 :] = 0.0
        return cut

    def axis_operator(self, key, sep: bool = False):
        """Stable public accessor for the dense per-axis operator matrix in
        this base's *storage layout* — what the fused-kernel builders
        (ops/pallas_conv.py, the manual-sharding conv region) consume
        instead of reaching into the private folding internals.  ``key``
        uses the `_sep_dev` vocabulary: ``"fwd" | "fwd_cut" | "bwd" |
        "synthesis" | "stencil" | "proj" | ("bwd_grad", order) |
        ("grad", order)``.  Returns an
        :class:`~rustpde_mpi_tpu.ops.folded.AxisOperator`; applying its
        ``matrix`` with one plain GEMM reproduces the folded/sep device
        apply exactly up to floating-point reassociation.

        Periodic r2c bases return the SPLIT Re/Im real-matrix form (the only
        dense-matrix form of the r2c transform); for the complex
        representation the caller converts at the boundary
        (``[Re(c); Im(c)]`` stacking, bases.SplitFourierBase.to_complex)."""
        from .ops.folded import AxisOperator, dense_operator, kept_storage_rows

        if self.kind.is_periodic:
            if self.kind == BaseKind.FOURIER_C2C:
                raise ValueError("axis_operator is not defined for c2c bases")
            if sep:
                raise ValueError("sep layout is not defined for Fourier axes")
            m2 = 2 * (self.n // 2 + 1)
            if key == "fwd":
                return AxisOperator(fou.split_forward_matrix(self.n), (False, False), None, None)
            if key == "fwd_cut":
                # per-complex-mode 2/3 cut applied to the Re and Im blocks
                # alike (SplitFourierBase.dealias_cut — also the convention
                # the complex base's dealias_mask follows per mode)
                mc = self.n // 2 + 1
                cut = np.ones(m2)
                cut[mc * 2 // 3 : mc] = 0.0
                cut[mc + mc * 2 // 3 :] = 0.0
                mat = fou.split_forward_matrix(self.n) * cut[:, None]
                return AxisOperator(mat, (False, False), mc * 2 // 3, np.where(cut > 0)[0])
            if key in ("bwd", "synthesis"):
                return AxisOperator(fou.split_backward_matrix(self.n), (False, False), None, None)
            if isinstance(key, tuple) and key[0] == "bwd_grad":
                mat = fou.split_backward_matrix(self.n) @ fou.split_diff_matrix(self.n, key[1])
                return AxisOperator(mat, (False, False), None, None)
            if isinstance(key, tuple) and key[0] == "grad":
                return AxisOperator(fou.split_diff_matrix(self.n, key[1]), (False, False), None, None)
            if key in ("stencil", "proj"):
                return AxisOperator(np.eye(m2), (False, False), None, None)
            raise ValueError(f"unknown axis_operator key {key!r}")
        if not self.kind.is_chebyshev:  # pragma: no cover - no other kinds
            raise ValueError(f"axis_operator undefined for {self.kind}")
        keep = None
        if key == "fwd":
            mat, sin, sout = self.projection @ chb.analysis_matrix(self.n), False, sep
        elif key == "fwd_cut":
            mat, sin, sout = self.projection @ chb.analysis_matrix(self.n), False, sep
            keep = self.m * 2 // 3
        elif key == "bwd":
            mat, sin, sout = chb.synthesis_matrix(self.n) @ self.stencil, sep, False
        elif key == "synthesis":
            mat, sin, sout = chb.synthesis_matrix(self.n), sep, False
        elif key == "stencil":
            mat, sin, sout = self.stencil, sep, sep
        elif key == "proj":
            mat, sin, sout = self.projection, sep, sep
        elif isinstance(key, tuple) and key[0] == "bwd_grad":
            mat = chb.synthesis_matrix(self.n) @ self.gradient_matrix(key[1])
            sin, sout = sep, False
        elif isinstance(key, tuple) and key[0] == "grad":
            mat, sin, sout = self.gradient_matrix(key[1]), sep, sep
        else:
            raise ValueError(f"unknown axis_operator key {key!r}")
        kept = None if keep is None else kept_storage_rows(mat.shape[0], keep, sout)
        return AxisOperator(
            dense_operator(mat, sep_in=sin, sep_out=sout, keep_rows=keep),
            (sin, sout),
            keep,
            kept,
        )


class SplitFourierBase(Base):
    """Real r2c Fourier base in the split Re/Im representation: spectral
    arrays are real with 2m rows, ``[Re(c_0..c_{m-1}); Im(c_0..c_{m-1})]``,
    m = n//2+1.

    This is the TPU form of ``fourier_r2c`` (dense transforms on the MXU are
    the design there, and complex128 does not exist on a TPU —
    ``config.supports_complex``): transforms are single real MXU matmuls,
    the (ik)^order spectral derivative becomes a block rotation of the Re/Im
    halves, and diagonal solver ingredients carry each eigenvalue twice.
    Numerically identical to the complex base — tested block-for-block
    (tests/test_split.py)."""

    def __init__(self, n: int):
        super().__init__(BaseKind.FOURIER_R2C_SPLIT, n)
        self.m_complex = n // 2 + 1
        self.m = 2 * self.m_complex

    @cached_property
    def wavenumbers(self) -> np.ndarray:  # type: ignore[override]
        """Each mode's k, duplicated across the Re and Im blocks — so the
        diagonal operator algebra (-k^2 laplacians, modal solves) applies to
        the split representation unchanged."""
        k = fou.wavenumbers_r2c(self.n)
        return np.concatenate([k, k])

    @property
    def spectral_is_complex(self) -> bool:  # type: ignore[override]
        return False

    # (operator matrices — mass/laplace/stencil/projection — inherit from
    # Base: its non-Chebyshev branches already use the overridden duplicated
    # wavenumbers and identity stencils)

    # -- transforms ----------------------------------------------------------

    @cached_property
    def _fwd_dev(self) -> FoldedMatrix:
        # circular-reflection fold (cos rows symmetric / sin rows antisym
        # under j -> n-j) halves the split-transform GEMM (ops/folded.py)
        return FoldedMatrix(fou.split_forward_matrix(self.n), _dev)

    @cached_property
    def _bwd_dev(self) -> FoldedMatrix:
        return FoldedMatrix(fou.split_backward_matrix(self.n), _dev)

    @cached_property
    def _rfft_plan(self):
        if not fourstep.enabled(self.n, "dft"):
            return None
        return fourstep.RfftPlan(self.n, _dev)

    @cached_property
    def _irfft_plan(self):
        if not fourstep.enabled(self.n, "dft"):
            return None
        return fourstep.IrfftPlan(self.n, _dev)

    def forward(self, v, axis: int, method: str = "matmul", sep: bool = False):
        del method  # matmul is the only (and native) path
        assert not sep, "sep layout is not defined for split-Fourier axes"
        if self._rfft_plan is not None:
            x = jnp.moveaxis(v, axis, 0)
            out = self._rfft_plan.split(x) / self.n
            return jnp.moveaxis(out, 0, axis)
        return self._fwd_dev.apply(v, axis)

    def backward(self, vhat, axis: int, method: str = "matmul", sep: bool = False):
        del method
        assert not sep, "sep layout is not defined for split-Fourier axes"
        if self._irfft_plan is not None:
            x = jnp.moveaxis(vhat, axis, 0)
            return jnp.moveaxis(self._irfft_plan.apply(x), 0, axis)
        return self._bwd_dev.apply(vhat, axis)

    def backward_ortho(self, c, axis: int, method: str = "matmul", sep: bool = False):
        return self.backward(c, axis)

    def to_ortho(self, vhat, axis: int, sep: bool = False):
        return vhat

    def from_ortho(self, c, axis: int, sep: bool = False):
        return c

    def gradient(self, vhat, order: int, axis: int, sep: bool = False):
        """(ik)^order on the split blocks: i^order cycles through
        (1, i, -1, -i), i.e. (re, im) -> (re, im), (-k im, k re),
        -(re, im), (k im, -k re) times k^order."""
        if order == 0:
            return vhat
        mc = self.m_complex
        k = fou.wavenumbers_r2c(self.n) ** order
        if order % 2 == 1 and self.n % 2 == 0:
            k = k.copy()
            k[-1] = 0.0  # Nyquist of odd derivatives (see fourier.diff_diag)
        shape = [1] * vhat.ndim
        quadrant = order % 4
        if quadrant % 2 == 0:
            # an even derivative keeps the halves where they are: a diagonal,
            # which needs no slice of the axis (nor, on a mesh, the axis whole)
            shape[axis] = 2 * mc
            k = np.concatenate([k, k]) * (1.0 if quadrant == 0 else -1.0)
            return vhat * jnp.asarray(k, dtype=vhat.dtype).reshape(shape)
        shape[axis] = mc
        kd = jnp.asarray(k, dtype=vhat.dtype).reshape(shape)
        re = jax.lax.slice_in_dim(vhat, 0, mc, axis=axis)
        im = jax.lax.slice_in_dim(vhat, mc, 2 * mc, axis=axis)
        if quadrant == 1:
            re_n, im_n = -kd * im, kd * re
        else:
            re_n, im_n = kd * im, -kd * re
        return jnp.concatenate([re_n, im_n], axis=axis)

    def dealias_cut(self) -> np.ndarray:
        """2/3-rule applied per complex mode — the Re and Im blocks get the
        same cutoff."""
        mc = self.m_complex
        cut = np.ones(self.m)
        cut[mc * 2 // 3 : mc] = 0.0
        cut[mc + mc * 2 // 3 :] = 0.0
        return cut

    # -- complex interop (checkpoint IO keeps the reference layout) ----------

    def to_complex(self, vhat_split: np.ndarray, axis: int = 0) -> np.ndarray:
        a = np.moveaxis(np.asarray(vhat_split), axis, 0)
        out = a[: self.m_complex] + 1j * a[self.m_complex :]
        return np.moveaxis(out, 0, axis)

    def from_complex(self, vhat_c: np.ndarray, axis: int = 0) -> np.ndarray:
        a = np.moveaxis(np.asarray(vhat_c), axis, 0)
        out = np.concatenate([a.real, a.imag], axis=0)
        return np.moveaxis(out, 0, axis)


import weakref

_BASE_CACHE: "weakref.WeakValueDictionary[tuple[BaseKind, int], Base]" = (
    weakref.WeakValueDictionary()
)


def _cached_base(kind: BaseKind, n: int) -> Base:
    """Bases are immutable operator factories — share one instance per
    (kind, n) so repeated constructions (e.g. the velx and vely spaces of a
    model) reuse the same device-resident transform matrices.  Weak values:
    once no space references a base, its O(n^2) device matrices are freed."""
    key = (kind, n)
    base = _BASE_CACHE.get(key)
    if base is None:
        base = (
            SplitFourierBase(n) if kind == BaseKind.FOURIER_R2C_SPLIT else Base(kind, n)
        )
        _BASE_CACHE[key] = base
    return base


def chebyshev(n: int) -> Base:
    return _cached_base(BaseKind.CHEBYSHEV, n)


def cheb_dirichlet(n: int) -> Base:
    return _cached_base(BaseKind.CHEB_DIRICHLET, n)


def cheb_neumann(n: int) -> Base:
    return _cached_base(BaseKind.CHEB_NEUMANN, n)


def cheb_dirichlet_neumann(n: int) -> Base:
    return _cached_base(BaseKind.CHEB_DIRICHLET_NEUMANN, n)


def fourier_r2c(n: int) -> Base:
    """Real-to-complex Fourier base.  On backends without complex dtypes
    (the TPU chip) this transparently returns the split Re/Im representation
    (:class:`SplitFourierBase`), so periodic models run unchanged there."""
    if not config.supports_complex():
        return fourier_r2c_split(n)
    return _cached_base(BaseKind.FOURIER_R2C, n)


def fourier_r2c_split(n: int) -> Base:
    """Explicitly request the split Re/Im r2c base (any backend)."""
    return _cached_base(BaseKind.FOURIER_R2C_SPLIT, n)


def fourier_c2c(n: int) -> Base:
    return _cached_base(BaseKind.FOURIER_C2C, n)


class Space2:
    """Tensor product of two bases (axis 0 = x, axis 1 = y).

    Equivalent of funspace's ``Space2`` as used by the reference field layer
    (/root/reference/src/field.rs:59-129).  ``method`` picks the transform
    execution path: "fft" or "matmul" (Chebyshev axes only), default
    auto-selected: FFT everywhere except f64-on-TPU, where the emulated FFT
    path is unavailable and dense MXU transforms are used instead.
    """

    def __init__(
        self, base_x: Base, base_y: Base, method: str | None = None, sep=None
    ):
        # ``space.build`` (operators and kernels): the layout decisions below.
        # The one-axis operators themselves are built on the shared ``Base``
        # objects at first use, inside whichever span first applies them
        with _tr.span(
            "space.build", layer="operators and kernels",
            shape=(base_x.n, base_y.n), bases=(base_x.kind.value, base_y.kind.value),
        ):
            self._build(base_x, base_y, method, sep)

    def _build(self, base_x: Base, base_y: Base, method, sep) -> None:
        if base_y.kind.is_periodic and not base_x.kind.is_periodic:
            raise ValueError("periodic y-axis under non-periodic x is unsupported")
        self.bases = (base_x, base_y)
        if base_y.kind.is_split:
            raise NotImplementedError(
                "the split Re/Im representation is implemented for the "
                "x-axis only (the IO/pinning helpers assume a split axis 0); "
                "doubly-periodic split spaces are unsupported"
            )
        if any(b.spectral_is_complex for b in self.bases) and not config.supports_complex():
            raise NotImplementedError(
                "complex Fourier bases are unsupported on this backend "
                "(no complex dtypes); use fourier_r2c_split / the "
                "fourier_r2c factory, which auto-selects the split "
                "representation."
            )
        if method is None:
            # TPU path: dense MXU transforms by design (config.supports_complex).
            method = "matmul" if config.is_tpu_like() else "fft"
        self.method = method
        # Parity-separated spectral layout (ops/folded.py): spectral axes are
        # stored parity-permuted ([evens..., odds...]) so every structured
        # operator runs on contiguous slices — no gathers/interleaves around
        # the GEMMs.  ``sep``: None -> RUSTPDE_SEP env ("auto" default: on
        # for all-Chebyshev matmul spaces, where the layout is defined and
        # measured to win); True/False force.  Per-axis: only Chebyshev-
        # family axes separate (split-Fourier axes keep their layout).
        if sep is None:
            env = config.env_get("RUSTPDE_SEP", "auto")
            if env == "auto":
                sep = method == "matmul" and all(
                    b.kind.is_chebyshev for b in self.bases
                )
            else:
                sep = env == "1"
        self.sep = (
            bool(sep) and base_x.kind.is_chebyshev and method == "matmul",
            bool(sep) and base_y.kind.is_chebyshev and method == "matmul",
        )
        # Under a mesh the spectral operators (gradient, to_ortho, from_ortho)
        # state the pencil layout they run in, as the transforms always have.
        # A model whose mesh program is not GSPMD's to place clears this at its
        # build (models/navier.py), before it builds its solvers; the
        # transforms' flips are part of that program, and stay the x-pencil
        # ones it was written for.
        self.states_layout = True

    @property
    def base_x(self) -> Base:
        return self.bases[0]

    @property
    def base_y(self) -> Base:
        return self.bases[1]

    @property
    def shape_physical(self) -> tuple[int, int]:
        return (self.bases[0].n, self.bases[1].n)

    @property
    def shape_spectral(self) -> tuple[int, int]:
        return (self.bases[0].m, self.bases[1].m)

    @property
    def spectral_is_complex(self) -> bool:
        return any(b.spectral_is_complex for b in self.bases)

    def spectral_dtype(self):
        return config.complex_dtype() if self.spectral_is_complex else config.real_dtype()

    def base_kind(self, axis: int) -> BaseKind:
        return self.bases[axis].kind

    def coords(self) -> list[np.ndarray]:
        return [b.points for b in self.bases]

    def ndarray_physical(self):
        return jnp.zeros(self.shape_physical, dtype=config.real_dtype())

    def ndarray_spectral(self):
        return jnp.zeros(self.shape_spectral, dtype=self.spectral_dtype())

    # -- transforms ---------------------------------------------------------
    #
    # Pencil discipline (active only under a parallel mesh): each 2-D
    # transform works on its local axis and flips pencils in between, the
    # all-to-all left to XLA GSPMD.  Spectral arrays rest in the pencil of the
    # axis a synthesis takes first, physical ones in that of the axis it takes
    # last.  A confined space: x first, so spectral data is an x-pencil (axis
    # 1 sharded), where its dense x-operators run in place, and physical data
    # a y-pencil (axis 0 sharded) — exactly funspace's forward_inplace_mpi =
    # [transform y][transpose y->x][transform x]
    # (/root/reference/src/field_mpi.rs:324-333).  A Fourier x Chebyshev
    # space: spectral data is a y-pencil, where every solve, y-derivative,
    # stencil and cast runs in place, because every spectral x-operator
    # between two transforms is a diagonal there but one (the odd derivative
    # of the split layout, ``gradient``); so a synthesis takes y first and
    # leaves physical data an x-pencil.  One flip a transform either way.

    @property
    def rest(self) -> tuple:
        """The pencil layout spectral arrays of this space rest in under a
        mesh: decided by what the x-base is."""
        from .parallel.mesh import LOCAL

        y_local = (
            self.bases[0].kind.is_periodic
            and self.bases[1].kind.is_chebyshev
            and self.states_layout
        )
        return LOCAL[1 if y_local else 0]

    @property
    def synthesis_axes(self) -> tuple[int, int]:
        """The order in which a synthesis takes the axes (an analysis takes
        the reverse).  Where arrays are distributed, the axis that is local
        at rest first: any other order costs a second flip.  Where nothing is
        distributed every order is free of flips, and x goes first: a
        synthesis shrinks x (1026 split rows to 1024 points) and widens y
        (1023 composite modes to 1025 points), so x first keeps the free
        extent of both products within eight MXU tiles of 128 where y first
        takes nine (measured on one chip at 1024 x 1025: the step 4 % slower,
        PERF.md section 6, PR 31)."""
        from .parallel.mesh import LOCAL, active_mesh

        if active_mesh() is not None and self.rest == LOCAL[1]:
            return (1, 0)
        return (0, 1)

    @property
    def physical(self) -> tuple:
        """The pencil layout a synthesis leaves physical arrays in."""
        from .parallel.mesh import LOCAL

        return LOCAL[self.synthesis_axes[1]]

    def _axis_method(self, axis: int) -> str:
        """Per-axis transform path; under an active mesh Chebyshev axes use
        the (identical) matmul form — GSPMD shards GEMMs cleanly, while the
        XLA CPU FFT rejects the padded layouts non-divisible shardings
        produce."""
        from .parallel.mesh import active_mesh

        if active_mesh() is not None and self.bases[axis].kind.is_chebyshev:
            return "matmul"
        return self.method

    # All transforms are polymorphic over extra *leading* batch dims: the
    # tensor axes are the trailing two (models stack same-space fields and
    # transform them in one batched GEMM; mesh constraints replicate the
    # leading dims).

    @staticmethod
    def _batch_ax(arr) -> int:
        """Index of the first tensor axis; loud failure below rank 2 (a 1-D
        slice would otherwise transform one axis twice and return garbage)."""
        if arr.ndim < 2:
            raise ValueError(f"Space2 expects a (..., nx, ny) array, got rank {arr.ndim}")
        return arr.ndim - 2

    def forward(self, v):
        """Physical (..., n_x, n_y) -> spectral (..., m_x, m_y)."""
        from .parallel.mesh import LOCAL, constrain, flip

        ax = self._batch_ax(v)
        out, at = v, self.physical
        for axis in reversed(self.synthesis_axes):
            out = self.bases[axis].forward(
                flip(out, LOCAL[axis], at), ax + axis, self._axis_method(axis),
                sep=self.sep[axis],
            )
            at = LOCAL[axis]
        return constrain(out, self.rest)

    # A synthesis in two steps: the first axis of ``synthesis_axes`` alone,
    # then a finish along the second.  The four public syntheses below are
    # compositions of the two, so an axis is synthesised in one place
    # (``_synthesise``); a caller that needs two syntheses of one array that
    # differ only along the second axis (a velocity and the derivative of its
    # own convection chain, models/navier.py) takes the first step once and
    # finishes it twice: one product, and under a mesh one flip, for both.

    def _synthesise(self, out, axis: int, order, fast: bool, ortho: bool):
        """One axis of a synthesis, on an array in which ``axis`` is local.
        ``ortho``: from orthogonal coefficients (the base's
        ``backward_ortho``).  ``order`` None: the base's plain ``backward``
        of composite coefficients; an int: the synthesis of that derivative,
        ONE synthesis-of-derivative GEMM on a sep axis (key
        ("bwd_grad", order); order 0 is the plain fused backward), gradient
        then synthesis on any other (e.g. the split-Fourier axis of a periodic
        space).  ``fast`` selects the 3-pass variants of the sep keys (DNS
        convection path only, see Base._sep_dev); a plain synthesis takes
        them only where both axes are sep, and is the exact ``backward`` off
        it."""
        b = self.bases[axis]
        a = self._batch_ax(out) + axis
        method = self._axis_method(axis)
        if ortho:
            return b.backward_ortho(out, a, method, sep=self.sep[axis])
        if order is None and not (fast and all(self.sep)):
            return b.backward(out, a, method, sep=self.sep[axis])
        if self.sep[axis]:
            key = ("bwd_grad", order) if order else "bwd"
            if fast:
                key = (key, "fast") if isinstance(key, str) else key + ("fast",)
            return b._sep_dev(key).apply(out, a)
        return b.backward_ortho(b.gradient(out, order, a, sep=False), a, method)

    def synthesis_first(self, vhat, deriv=None, fast=False, ortho=False):
        """The first step of a synthesis: the first axis of
        ``synthesis_axes`` alone, where it is local, handed on in the pencil
        of the second (under a mesh the flip is here, so whoever shares the
        partial shares the flip).  ``deriv``: the derivative orders of
        ``backward_gradient``, of which this step takes its own axis's (None:
        the plain ``backward``); ``fast`` and ``ortho`` as in
        ``_synthesise``."""
        from .parallel.mesh import LOCAL, constrain, flip

        first, second = self.synthesis_axes
        out = self._synthesise(
            constrain(vhat, LOCAL[first]), first,
            None if deriv is None else deriv[first], fast, ortho,
        )
        return flip(out, LOCAL[second], LOCAL[first])

    def synthesis_finish(self, partial, deriv=None, scale=None, fast=False, ortho=False):
        """The second step: derivative and synthesis along the second axis of
        ``synthesis_axes`` on a ``synthesis_first`` partial, the division by
        scale^deriv, and the ``physical`` pin.  ``deriv`` names both orders
        (the first axis's is the one the partial was taken with); one partial
        may be finished with several orders along the second axis."""
        from .parallel.mesh import constrain

        second = self.synthesis_axes[1]
        out = self._synthesise(
            partial, second, None if deriv is None else deriv[second], fast, ortho
        )
        out = constrain(out, self.physical)
        if scale is not None and deriv is not None:
            factor = (scale[0] ** deriv[0]) * (scale[1] ** deriv[1])
            if factor != 1.0:
                out = out / factor
        return out

    def backward(self, vhat):
        """Spectral (..., m_x, m_y) -> physical (..., n_x, n_y)."""
        return self.synthesis_finish(self.synthesis_first(vhat))

    def backward_ortho(self, c):
        """Physical values from orthogonal-space coefficients (the space the
        reference's scratch ``field`` provides, /root/reference/src/navier_stokes/navier.rs:256)."""
        return self.synthesis_finish(self.synthesis_first(c, ortho=True), ortho=True)

    def forward_dealiased(self, v, fast: bool = False):
        """Physical -> spectral with the 2/3-rule mask applied, in one fused
        form: sep axes drop the dead rows from their forward GEMMs (2/3
        flops, no mask pass); non-sep axes (e.g. the split-Fourier axis of a
        periodic space) run their plain forward and get their 1-D cut as a
        vector multiply.  Callers keep a ``forward() * mask`` fallback for
        fully non-sep spaces.  ``fast=True`` selects the 3-pass variant
        gated by RUSTPDE_FWD_PRECISION (default off — see Base._sep_dev)."""
        from .parallel.mesh import LOCAL, constrain, flip

        if not any(self.sep):
            raise ValueError("forward_dealiased requires at least one sep axis")
        ax = self._batch_ax(v)
        key = ("fwd_cut", "fast") if fast else "fwd_cut"
        out, at = v, self.physical
        for axis in reversed(self.synthesis_axes):
            out, at = flip(out, LOCAL[axis], at), LOCAL[axis]
            if self.sep[axis]:
                out = self.bases[axis]._sep_dev(key).apply(out, ax + axis)
            else:
                out = self.bases[axis].forward(out, ax + axis, self._axis_method(axis))
        for axis in (0, 1):
            if not self.sep[axis]:
                cut = self.bases[axis].dealias_cut()
                shape = [1] * out.ndim
                shape[ax + axis] = cut.shape[0]
                out = out * jnp.asarray(
                    cut.reshape(shape), dtype=config.real_dtype()
                )
        return constrain(out, self.rest)

    def backward_gradient(self, vhat, deriv, scale=None, fast=False):
        """Physical values of d^deriv[0]/dx d^deriv[1]/dy — the fused
        ``backward_ortho(gradient(...))``: each sep axis is ONE
        synthesis-of-derivative GEMM, saving the separate gradient apply;
        non-sep axes run gradient-then-synthesis on that axis, so mixed
        spaces still fuse their Chebyshev axis, and under a mesh each axis's
        derivative runs with its synthesis, where that axis is local (the odd
        x-derivative of a split base too).  ``fast=True`` selects the 3-pass
        synthesis variants (DNS convection path only — see Base._sep_dev)."""
        return self.synthesis_finish(
            self.synthesis_first(vhat, deriv, fast), deriv, scale, fast
        )

    def backward_fast(self, vhat):
        """``backward`` via the fast synthesis variants (DNS convection
        velocities only); the exact backward off-sep (``_synthesise``)."""
        return self.synthesis_finish(self.synthesis_first(vhat, fast=True), fast=True)

    def to_ortho(self, vhat):
        ax = self._batch_ax(vhat)
        out = self.bases[0].to_ortho(self._pin(vhat, self.rest), ax, sep=self.sep[0])
        return self._pin(self.bases[1].to_ortho(out, ax + 1, sep=self.sep[1]), self.rest)

    # Spectral operators under a mesh: an operator that needs a whole axis
    # runs where that axis is local, and says so itself; arrays rest where
    # most operators need no flip (``rest``).  On a confined space that is the
    # x-pencil: the dense x-operators run in place, and a y-operator that
    # needs the whole y extent at once (a Chebyshev derivative, the dense
    # composite cast of from_ortho) is taken between a pair of flips to the
    # y-pencil layout; the banded to_ortho stencil costs two halo rows where
    # it is and stays.  On a Fourier x Chebyshev space it is the y-pencil:
    # every y-operator runs in place, the x-operators are diagonals that need
    # no layout, and the one that does need the whole x extent (the odd
    # derivative of the split layout, which swaps the Re and Im halves of the
    # x extent) is the one taken between a pair of flips.  Left to
    # propagation, GSPMD runs the parity interleave or the half swap of such
    # an operator ALONG the sharded axis: every device scatters its rows into
    # a zero field and the fields are summed, a whole-field all-reduce each.
    # These operators map rest to rest and flip in pairs.  A synthesis leaves
    # rest for good and flips once, inside ``synthesis_first``: its partial
    # (first axis done, second still spectral) lives in the pencil of the
    # second axis, so two syntheses of one array that differ only along the
    # second axis are two ``synthesis_finish`` of one partial and pay one flip
    # between them (a velocity and its own chain's derivative: 15 flips a
    # periodic step where each stating its own made 17).

    def _pin(self, a, spec, at=None):
        """``constrain`` for the spectral operators below, unless the model
        that owns the space cleared ``states_layout`` at its build.  ``at``:
        the pencil ``a`` is stated in, where it is another: the pin is then a
        flip, and counted as one (``parallel.mesh.flip``)."""
        from .parallel.mesh import flip

        return flip(a, spec, at) if self.states_layout else a

    def _where_local(self, axis: int, apply, c):
        """``apply`` (operators that need the whole extent of ``axis``) on a
        spectral array at rest: in place where the resting pencil has that
        axis local, else between a stated pair of flips."""
        from .parallel.mesh import LOCAL

        there = LOCAL[axis]
        return self._pin(apply(self._pin(c, there, self.rest)), self.rest, there)

    def _whole(self, axis: int, order: int) -> bool:
        """Whether the ``order``-th derivative along ``axis`` needs the whole
        extent at once: a Chebyshev recurrence or product does, a Fourier
        diagonal does not, the odd derivative of the split layout (Re and Im
        halves swapped) does."""
        kind = self.bases[axis].kind
        if kind.is_chebyshev:
            return order >= 1
        return kind.is_split and order % 2 == 1

    def from_ortho(self, c):
        ax = self._batch_ax(c)
        out = c
        if not self.bases[0].is_orthogonal:
            out = self.bases[0].from_ortho(self._pin(out, self.rest), ax, sep=self.sep[0])
        if self.bases[1].is_orthogonal:
            return out
        return self._where_local(
            1, lambda a: self.bases[1].from_ortho(a, ax + 1, sep=self.sep[1]), out
        )

    def gradient(self, vhat, deriv, scale=None, into: "Space2 | None" = None):
        """d^deriv[0]/dx d^deriv[1]/dy in ortho space; divides by
        scale^deriv like the reference (/root/reference/src/field.rs:127).
        ``into``: the space whose composite coefficients the derivative is
        cast to, ``into.from_ortho(gradient(.))`` (the projection's velocity
        correction), so that a y-derivative and the cast share one visit to
        the layout in which y is local."""
        from .parallel.mesh import LOCAL

        ax = self._batch_ax(vhat)
        rest = self.rest
        factor = 1.0
        if scale is not None:
            factor = (scale[0] ** deriv[0]) * (scale[1] ** deriv[1])

        def along_y(a):
            a = self.bases[1].gradient(a, deriv[1], ax + 1, sep=self.sep[1])
            return a / factor if factor != 1.0 else a

        def cast_y(a):
            if into is None:
                return a
            return into.bases[1].from_ortho(a, ax + 1, sep=into.sep[1])

        def along_x(a):
            return self.bases[0].gradient(a, deriv[0], ax, sep=self.sep[0])

        out = vhat
        if self._whole(0, deriv[0]) and rest != LOCAL[0]:
            out = self._where_local(0, along_x, out)
        elif deriv[0] or not self.bases[0].is_orthogonal:
            out = along_x(self._pin(out, rest))
        if into is not None and not into.bases[0].is_orthogonal:
            out = into.bases[0].from_ortho(self._pin(out, rest), ax, sep=into.sep[0])
        if self._whole(1, deriv[1]):
            return self._where_local(1, lambda a: cast_y(along_y(a)), out)
        # the banded stencil, a diagonal or nothing: where it is
        out = self._pin(along_y(self._pin(out, rest)), rest)
        if into is None or into.bases[1].is_orthogonal:
            return out
        return self._where_local(1, cast_y, out)

    # -- representation-aware helpers ---------------------------------------

    def dealias_mask(self) -> np.ndarray:
        """2/3-rule mask over this space's spectral shape
        (/root/reference/src/navier_stokes/functions.rs:72-82); for a split
        Fourier axis the cutoff applies per complex mode, i.e. to the Re and
        Im blocks alike (Base.dealias_cut); sep axes get the mask in their
        parity-permuted order."""
        from .ops.folded import parity_perm

        cuts = [base.dealias_cut() for base in self.bases]
        cuts = [
            c[parity_perm(len(c))] if s else c for c, s in zip(cuts, self.sep)
        ]
        return cuts[0][:, None] * cuts[1][None, :]

    # -- sep-layout boundary (host side) -------------------------------------

    def spectral_to_natural(self, vhat: np.ndarray) -> np.ndarray:
        """Host copy of spectral coefficients in the natural index order
        (identity for non-sep spaces) — the IO/parity boundary."""
        from .ops.folded import parity_perm_inv

        a = np.asarray(vhat)
        for axis, s in enumerate(self.sep):
            if s:
                a = np.take(a, parity_perm_inv(a.shape[axis - 2]), axis=axis - 2)
        return a

    def spectral_from_natural(self, vhat: np.ndarray) -> np.ndarray:
        from .ops.folded import parity_perm

        a = np.asarray(vhat)
        for axis, s in enumerate(self.sep):
            if s:
                a = np.take(a, parity_perm(a.shape[axis - 2]), axis=axis - 2)
        return a

    def pin_zero_mode(self, vhat):
        """Zero the constant mode (the pressure singularity pin,
        /root/reference/src/navier_stokes/navier_eq.rs:158-162); a split
        x-axis pins both the Re and the Im row of k=0."""
        out = vhat.at[0, 0].set(0.0)
        if self.bases[0].kind.is_split:
            out = out.at[self.bases[0].m_complex, 0].set(0.0)
        return out

    def vhat_as_complex(self, vhat) -> np.ndarray:
        """Host view of the coefficients in the complex convention (identity
        for non-split spaces) — keeps checkpoint files layout-identical
        across backends."""
        if self.bases[0].kind.is_split:
            # a forced-sep y-axis still needs its unpermute (different axes,
            # order-independent)
            return self.bases[0].to_complex(self.spectral_to_natural(vhat), axis=0)
        return self.spectral_to_natural(vhat)

    def vhat_from_complex(self, vhat_c: np.ndarray):
        if self.bases[0].kind.is_split:
            return self.spectral_from_natural(
                self.bases[0].from_complex(vhat_c, axis=0)
            )
        return self.spectral_from_natural(vhat_c)


def fused_projection_gradient(space_out: "Space2", space_in: "Space2", deriv):
    """Per-axis cross-space operators applying
    ``space_out.from_ortho(space_in.gradient(., deriv))`` as ONE GEMM per
    axis: ``P_out @ D^order @ S_in`` (the pressure-projection velocity
    correction in the Navier/LNSE/adjoint steps).  Returns a FoldedMatrix
    pair, or None when the fusion does not apply (periodic axes — the
    Fourier gradient is diagonal logic — or non-matmul transform methods,
    where the unfused path uses the O(n) recurrences the fusion was never
    benchmarked against).

    Deduplicated by VALUE key (base kinds + sizes + order + sep flags —
    operator matrices depend on nothing else), so e.g. the d/dx and d/dy
    corrections of a square grid share their device constants.  The cache
    dict lives ON the output-axis Base instance (which _BASE_CACHE holds
    only weakly), so the device matrices are freed with their bases instead
    of accumulating module-globally across many model sizes (ADVICE r4)."""
    bases_all = tuple(space_in.bases) + tuple(space_out.bases)
    if any(b.kind.is_periodic for b in bases_all):
        return None
    if space_out.method != "matmul" or space_in.method != "matmul":
        return None
    mats = []
    for ax, order in enumerate(deriv):
        b_out, b_in = space_out.bases[ax], space_in.bases[ax]
        cache = b_out._proj_grad_cache
        key = (b_in.kind, b_in.n, order, space_in.sep[ax], space_out.sep[ax])
        fm = cache.get(key)
        if fm is None:
            fm = FoldedMatrix(
                b_out.projection @ b_in.gradient_matrix(order),
                _dev,
                sep_in=space_in.sep[ax],
                sep_out=space_out.sep[ax],
            )
            cache[key] = fm
        mats.append(fm)
    return tuple(mats)


class Space1:
    """One-dimensional spectral space — the funspace ``Space1`` analog the
    reference's 1-D fields are built on (/root/reference/src/field.rs:59-72;
    consumed by examples/swift_hohenberg_1d.rs and the 1-D demos).

    Same execution-path selection as :class:`Space2`: FFT transforms except
    on the TPU backend, where dense MXU matmuls are used.  ``fourier_r2c``
    transparently becomes the split Re/Im representation there, so 1-D
    periodic models run on-chip unchanged.
    """

    def __init__(self, base: Base, method: str | None = None):
        if base.spectral_is_complex and not config.supports_complex():
            raise NotImplementedError(
                "complex Fourier bases are unsupported on this backend; "
                "use the fourier_r2c factory (auto-selects the split "
                "representation)"
            )
        self.base = base
        self.bases = (base,)
        if method is None:
            method = "matmul" if config.is_tpu_like() else "fft"
        self.method = method

    @property
    def shape_physical(self) -> tuple[int]:
        return (self.base.n,)

    @property
    def shape_spectral(self) -> tuple[int]:
        return (self.base.m,)

    @property
    def spectral_is_complex(self) -> bool:
        return self.base.spectral_is_complex

    def spectral_dtype(self):
        return config.complex_dtype() if self.spectral_is_complex else config.real_dtype()

    def base_kind(self, axis: int = 0) -> BaseKind:
        return self.base.kind

    def coords(self) -> list[np.ndarray]:
        return [self.base.points]

    def ndarray_physical(self):
        return jnp.zeros(self.shape_physical, dtype=config.real_dtype())

    def ndarray_spectral(self):
        return jnp.zeros(self.shape_spectral, dtype=self.spectral_dtype())

    def forward(self, v):
        return self.base.forward(v, 0, self.method)

    def backward(self, vhat):
        return self.base.backward(vhat, 0, self.method)

    def backward_ortho(self, c):
        return self.base.backward_ortho(c, 0, self.method)

    def to_ortho(self, vhat):
        return self.base.to_ortho(vhat, 0)

    def from_ortho(self, c):
        return self.base.from_ortho(c, 0)

    def gradient(self, vhat, deriv, scale=None):
        """d^deriv/dx in ortho space, divided by scale^deriv like the
        reference (/root/reference/src/field.rs:127).  ``deriv`` may be an
        int or a 1-element sequence."""
        order = deriv if isinstance(deriv, int) else deriv[0]
        out = self.base.gradient(vhat, order, 0)
        if scale is not None:
            s = scale if isinstance(scale, (int, float)) else scale[0]
            factor = float(s) ** order
            if factor != 1.0:
                out = out / factor
        return out

    def dealias_mask(self) -> np.ndarray:
        """2/3-rule mask (the 1-D form of Space2.dealias_mask; matches the
        reference's 1-D cutoff, examples/swift_hohenberg_1d.rs dealias)."""
        return self.base.dealias_cut()

    def pin_zero_mode(self, vhat):
        out = vhat.at[0].set(0.0)
        if self.base.kind.is_split:
            out = out.at[self.base.m_complex].set(0.0)
        return out

    def vhat_as_complex(self, vhat) -> np.ndarray:
        if self.base.kind.is_split:
            return self.base.to_complex(np.asarray(vhat), axis=0)
        return np.asarray(vhat)

    def vhat_from_complex(self, vhat_c: np.ndarray):
        if self.base.kind.is_split:
            return self.base.from_complex(vhat_c, axis=0)
        return vhat_c


class BiPeriodicSpace2:
    """Doubly-periodic real 2-D space (Fourier x Fourier), split Re/Im layout.

    The reference's Swift–Hohenberg demo runs on ``fourier_c2c x fourier_r2c``
    with complex coefficients (/root/reference/examples/swift_hohenberg_2d.rs).
    A complex c2c axis cannot ride the per-axis split trick of
    :class:`SplitFourierBase` (a c2c transform mixes Re and Im across the
    *other* axis's blocks), so the doubly-periodic case gets its own space:
    spectral data is a real ``(2, nx, my)`` array — plane 0 = Re, plane 1 =
    Im of the c2c x r2c coefficients, ``my = ny//2+1`` — and the transforms
    run either as XLA FFTs (CPU) or as real MXU matmuls handling the Re/Im
    mixing explicitly (TPU: no FFT, no complex dtypes).  Normalization is
    amplitude (fft/n per axis), matching ops/fourier.
    """

    def __init__(self, nx: int, ny: int, method: str | None = None):
        self.nx, self.ny = nx, ny
        self.my = ny // 2 + 1
        if method is None:
            method = "matmul" if config.is_tpu_like() else "fft"
        self.method = method
        self.kx = fou.wavenumbers_c2c(nx)
        self.ky = fou.wavenumbers_r2c(ny)
        # ``space.build`` (operators and kernels).  The space owns its
        # operators (no shared ``Base`` objects), so the matmul path's are
        # built here, inside the span: a transform's first trace builds the
        # device constants it applies (four-step plan or folded matrix)
        with _tr.span(
            "space.build", layer="operators and kernels",
            shape=(nx, ny), bases=("fourier_c2c", "fourier_r2c"),
        ):
            if method == "matmul":
                rdt = config.real_dtype()
                jax.eval_shape(self.forward, jax.ShapeDtypeStruct(self.shape_physical, rdt))
                jax.eval_shape(self.backward, jax.ShapeDtypeStruct(self.shape_spectral, rdt))

    # -- geometry -----------------------------------------------------------

    @property
    def shape_physical(self) -> tuple[int, int]:
        return (self.nx, self.ny)

    @property
    def shape_spectral(self) -> tuple[int, int, int]:
        return (2, self.nx, self.my)

    def coords(self) -> list[np.ndarray]:
        return [fou.fourier_points(self.nx), fou.fourier_points(self.ny)]

    def ndarray_physical(self):
        return jnp.zeros(self.shape_physical, dtype=config.real_dtype())

    def ndarray_spectral(self):
        return jnp.zeros(self.shape_spectral, dtype=config.real_dtype())

    # -- transform matrices (host, lazily built) ----------------------------

    @cached_property
    def _y_fwd(self) -> FoldedMatrix:
        return FoldedMatrix(fou.split_forward_matrix(self.ny), _dev)  # (2my, ny)

    @cached_property
    def _y_bwd(self) -> FoldedMatrix:
        return FoldedMatrix(fou.split_backward_matrix(self.ny), _dev)  # (ny, 2my)

    @cached_property
    def _x_cos(self) -> FoldedMatrix:
        return FoldedMatrix(fou.dft_cos_matrix(self.nx), _dev)

    @cached_property
    def _x_sin(self) -> FoldedMatrix:
        return FoldedMatrix(fou.dft_sin_matrix(self.nx), _dev)

    # four-step plans (ops/fourstep.py); None below the size gate
    @cached_property
    def _y_rfft_plan(self):
        return fourstep.RfftPlan(self.ny, _dev) if fourstep.enabled(self.ny, "dft") else None

    @cached_property
    def _y_irfft_plan(self):
        return fourstep.IrfftPlan(self.ny, _dev) if fourstep.enabled(self.ny, "dft") else None

    @cached_property
    def _x_c2c_fwd(self):
        return (
            fourstep.C2cPlan(self.nx, _dev, sign=-1.0)
            if fourstep.enabled(self.nx, "c2c")
            else None
        )

    @cached_property
    def _x_c2c_bwd(self):
        return (
            fourstep.C2cPlan(self.nx, _dev, sign=+1.0)
            if fourstep.enabled(self.nx, "c2c")
            else None
        )

    # -- transforms ----------------------------------------------------------

    def forward(self, v):
        """Real physical (nx, ny) -> split spectral (2, nx, my)."""
        if self.method == "fft":
            c = jnp.fft.fft(jnp.fft.rfft(v, axis=1) / self.ny, axis=0) / self.nx
            return jnp.stack([c.real, c.imag]).astype(v.dtype)
        if self._y_rfft_plan is not None:
            w = jnp.moveaxis(
                self._y_rfft_plan.split(jnp.moveaxis(v, 1, 0)) / self.ny, 0, 1
            )
        else:
            w = self._y_fwd.apply(v, 1)  # (nx, 2my): [Re | Im] of the y-r2c
        re1, im1 = w[:, : self.my], w[:, self.my :]
        # x-axis c2c forward F = C - iS applied to re1 + i*im1
        if self._x_c2c_fwd is not None:
            re, im = self._x_c2c_fwd.apply(re1, im1)
            return jnp.stack([re / self.nx, im / self.nx])
        # forward c2c matrices are the backward pair scaled by 1/nx — share
        # the device constants and fold the scalar in here
        cos, sin = self._x_cos, self._x_sin
        re = (cos.apply(re1, 0) + sin.apply(im1, 0)) / self.nx
        im = (cos.apply(im1, 0) - sin.apply(re1, 0)) / self.nx
        return jnp.stack([re, im])

    def backward(self, s):
        """Split spectral (2, nx, my) -> real physical (nx, ny)."""
        if self.method == "fft":
            c = (s[0] + 1j * s[1]).astype(config.complex_dtype())
            mid = jnp.fft.ifft(c * self.nx, axis=0)
            return jnp.fft.irfft(mid * self.ny, n=self.ny, axis=1).astype(s.dtype)
        # x-axis inverse c2c B = C + iS
        if self._x_c2c_bwd is not None:
            mid_re, mid_im = self._x_c2c_bwd.apply(s[0], s[1])
        else:
            cos, sin = self._x_cos, self._x_sin
            mid_re = cos.apply(s[0], 0) - sin.apply(s[1], 0)
            mid_im = cos.apply(s[1], 0) + sin.apply(s[0], 0)
        # y-axis r2c synthesis on the [Re | Im] blocks (imag part of the
        # physical signal is structurally zero and never materialized)
        mid = jnp.concatenate([mid_re, mid_im], axis=1)
        if self._y_irfft_plan is not None:
            return jnp.moveaxis(
                self._y_irfft_plan.apply(jnp.moveaxis(mid, 1, 0)), 0, 1
            )
        return self._y_bwd.apply(mid, 1)

    # -- spectral operators --------------------------------------------------

    def _grad_factor(self, deriv) -> np.ndarray:
        """(i kx)^dx (i ky)^dy over the (nx, my) mode grid (complex host
        array), odd-order Nyquist modes zeroed (see ops/fourier.diff_diag)."""
        fx = fou.diff_diag(self.kx, deriv[0], self.nx, r2c=False)
        fy = fou.diff_diag(self.ky, deriv[1], self.ny, r2c=True)
        return fx[:, None] * fy[None, :]

    def gradient(self, s, deriv, scale=None):
        """Mixed derivative in spectral space on the split layout."""
        f = self._grad_factor(deriv)
        if scale is not None:
            f = f / ((scale[0] ** deriv[0]) * (scale[1] ** deriv[1]))
        fre = jnp.asarray(f.real, dtype=s.dtype)
        fim = jnp.asarray(f.imag, dtype=s.dtype)
        return jnp.stack(
            [fre * s[0] - fim * s[1], fre * s[1] + fim * s[0]]
        )

    def dealias_mask(self) -> np.ndarray:
        """2/3-rule over both axes, shape (nx, my).  Same integer-floor
        cutoff convention as Base.dealias_cut (keep |k| < floor(2m/3)); the
        c2c x-axis is cut by wavenumber magnitude."""
        mx = self.nx // 2 + 1
        cx = (np.abs(self.kx) < (mx * 2) // 3).astype(np.float64)
        cy = np.ones(self.my)
        cy[(self.my * 2) // 3 :] = 0.0
        return cx[:, None] * cy[None, :]

    def pin_zero_mode(self, s):
        return s.at[:, 0, 0].set(0.0)

    def enforce_hermitian_x(self, s):
        """Make the self-conjugate ky columns conjugate-symmetric in kx — a
        real physical field demands c(-kx, ky) = conj(c(kx, ky)) at ky = 0
        and, for even ny, at the ky-Nyquist column (both map to themselves
        under ky -> -ky); anti-Hermitian roundoff there is amplified without
        bound by the diagonal implicit update wherever the mode is linearly
        unstable.  The reference's helper notes the Nyquist case but fixes
        only ky=0 (/root/reference/examples/swift_hohenberg_2d.rs
        enforce_hermitian_symmetry); both columns are projected here."""
        # conjugate pairing index: k -> (nx - k) % nx
        idx = (-jnp.arange(self.nx)) % self.nx
        cols = [0] + ([self.my - 1] if self.ny % 2 == 0 else [])
        for c in cols:
            sym_re = 0.5 * (s[0, :, c] + s[0, idx, c])
            sym_im = 0.5 * (s[1, :, c] - s[1, idx, c])
            s = s.at[0, :, c].set(sym_re).at[1, :, c].set(sym_im)
        return s

    # -- complex interop (checkpoint IO keeps the reference layout) ----------

    def vhat_as_complex(self, s) -> np.ndarray:
        a = np.asarray(s)
        return a[0] + 1j * a[1]

    def vhat_from_complex(self, c: np.ndarray) -> np.ndarray:
        c = np.asarray(c)
        return np.stack([c.real, c.imag])
