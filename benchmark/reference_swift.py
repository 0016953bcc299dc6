"""Plain reference for the doubly periodic Swift-Hohenberg step (Fourier x
Fourier) the ``swift512_f32`` cell times.

Same rules as ``reference.py`` and ``reference_periodic.py``: it imports
nothing of ``rustpde_mpi_tpu`` and takes nothing the program has made.  The two
discrete Fourier transforms are written out here from ``numpy.fft``'s
conventions as float64 numpy matrices, cast once to float32, and applied as
unfolded dense matrix products at ``Precision.HIGHEST``; a spectrum is a pair
of real arrays (Re, Im) and the complex arithmetic is written out.  No split
layout, no circular fold, no fast transform, no solver, no cache.

The axes, by ``numpy.fft``'s conventions (the source's bases are
``fourier_c2c(nx)`` x ``fourier_r2c(ny)``): n uniform points x_j = 2 pi j / n
on [0, 2 pi); along y the real-to-complex transform with wavenumbers
ky = 0..ny//2, along x the complex one with kx in ``fftfreq`` order
(0, 1, .., -2, -1); amplitude-normalised coefficients

    c[kx, ky] = 1/(nx ny) sum_jl v[j, l] exp(-i kx x_j) exp(-i ky y_l)
    v[j, l]   = sum_ky w_ky Re( exp(i ky y_l) sum_kx c[kx, ky] exp(i kx x_j) )

with w = 1 for ky = 0 and for the Nyquist mode of an even ny, else 2 (what
``numpy.fft.irfft`` computes: it ignores the imaginary part of those two).

Semantics (upstream ``examples/swift_hohenberg_2d.rs``; one IMEX Euler step of
d theta/dt = [r - (lap + 1)^2] theta - theta^3 on a square of side
2 pi length, the linear operator implicit and diagonal, the cubic term
explicit and not dealiased):

    v       = synthesis(theta)
    theta*  = ( theta - dt analysis(v^3) ) / ( 1 + dt ((1 - K^2)^2 - r) ),
              K^2 = (kx^2 + ky^2) / length^2
    theta*[0, 0] = 0                                   (the mean mode pinned)
    theta*[:, ky] = ( theta*[kx, ky] + conj(theta*[-kx, ky]) ) / 2
              for ky = 0                   (the column a real field makes Hermitian)

Departure from upstream, noted: the same projection is applied to the
ky-Nyquist column of an even ny, which is self-conjugate too.  The source's
helper says so and projects ky = 0 alone; the program projects both (an
anti-Hermitian rounding residue there grows without bound wherever the mode is
linearly unstable), and the reference follows the program so that the two run
the same semantics.  From a real initial field the column's anti-Hermitian
part is rounding, so the departure is without effect at float32.

``mode`` is ``reference.py``'s: ``"f32"`` (``Precision.HIGHEST``, the
reference), ``"bf16_3x"`` (three bfloat16 passes, the nearest precision below:
the control of ``tests/``), ``"bf16"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .reference import _mm

# ---------------------------------------------------------------------------
# the two axes, float64, host
# ---------------------------------------------------------------------------


def points(n: int) -> np.ndarray:
    return 2.0 * np.pi * np.arange(n) / n


def wavenumbers_r2c(n: int) -> np.ndarray:
    return np.arange(n // 2 + 1, dtype=np.float64)


def wavenumbers_c2c(n: int) -> np.ndarray:
    """``numpy.fft.fftfreq`` order: 0, 1, .., then the negative ones."""
    k = np.arange(n, dtype=np.float64)
    k[k > (n - 1) // 2] -= n
    return k


def analysis(k: np.ndarray, n: int) -> tuple:
    """(cos, -sin) / n, each len(k) x n: ``c = (C + i S) v``."""
    ang = np.outer(k, points(n))
    return np.cos(ang) / n, -np.sin(ang) / n


def synthesis_c2c(n: int) -> tuple:
    """(cos, sin), each n x n: ``v = (C + i S) c``, the inverse complex
    transform."""
    ang = np.outer(points(n), wavenumbers_c2c(n))
    return np.cos(ang), np.sin(ang)


def synthesis_r2c(n: int) -> tuple:
    """(w cos, -w sin), each n x (n//2+1): ``v = C Re(c) + S Im(c)``."""
    w = np.full(n // 2 + 1, 2.0)
    w[0] = 1.0
    if n % 2 == 0:
        w[-1] = 1.0
    ang = np.outer(points(n), wavenumbers_r2c(n))
    return w * np.cos(ang), -w * np.sin(ang)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class Reference:
    """Swift-Hohenberg at (nx, ny, r, dt, length) on the doubly periodic
    square."""

    def __init__(self, nx, ny, r, dt, length, dtype=np.float32):
        # dtype: float32 as the cell runs; float64 (needs jax_enable_x64) only
        # in tests/, to pin these semantics to the program's f64 CPU path
        self.nx, self.ny, self.my = int(nx), int(ny), int(ny) // 2 + 1
        self.r, self.dt, self.length = float(r), float(dt), float(length)
        self.dtype = dtype
        kx = wavenumbers_c2c(self.nx) / self.length
        ky = wavenumbers_r2c(self.ny) / self.length
        k2 = kx[:, None] ** 2 + ky[None, :] ** 2
        xa = analysis(wavenumbers_c2c(self.nx), self.nx)
        ya = analysis(wavenumbers_r2c(self.ny), self.ny)
        xs, ys = synthesis_c2c(self.nx), synthesis_r2c(self.ny)
        self._host = {
            "xa_c": xa[0], "xa_s": xa[1], "xs_c": xs[0], "xs_s": xs[1],
            # along y a field is multiplied from the right: transposed here
            "ya_cT": ya[0].T, "ya_sT": ya[1].T, "ys_cT": ys[0].T, "ys_sT": ys[1].T,
            "inv_matl": 1.0 / (1.0 + self.dt * ((1.0 - k2) ** 2 - self.r)),
        }
        self._dev = None

    # -- host-side transforms (float64) -------------------------------------

    def forward(self, values: np.ndarray) -> tuple:
        """Physical values -> (Re, Im) of the coefficients."""
        h = self._host
        v = np.asarray(values, np.float64)
        a, b = v @ h["ya_cT"], v @ h["ya_sT"]
        return h["xa_c"] @ a - h["xa_s"] @ b, h["xa_c"] @ b + h["xa_s"] @ a

    def backward(self, state) -> np.ndarray:
        """(Re, Im) of the coefficients -> physical values (float64)."""
        h = self._host
        re, im = (np.asarray(a, np.float64) for a in state)
        mid_re = h["xs_c"] @ re - h["xs_s"] @ im
        mid_im = h["xs_c"] @ im + h["xs_s"] @ re
        return mid_re @ h["ys_cT"] + mid_im @ h["ys_sT"]

    def initial_state(self, values: np.ndarray) -> tuple:
        return tuple(a.astype(self.dtype) for a in self.forward(values))

    def norm(self, state) -> float:
        """The source's |F|: the coefficients' L2 norm over their number."""
        re, im = (np.asarray(a, np.float64) for a in state)
        return float(np.sqrt(np.sum(re * re + im * im)) / (self.nx * self.my))

    # -- the step, on the device ----------------------------------------------

    def run(self, state, steps: int, mode: str = "f32") -> tuple:
        """``steps`` steps from ``state`` (Re, Im); the new state as numpy
        arrays."""
        if self._dev is None:
            self._dev = jax.tree.map(lambda a: jnp.asarray(a, self.dtype), self._host)
        out = _run(self._dev, tuple(jnp.asarray(a) for a in state), jnp.int32(steps),
                   (self.dt, self.ny % 2 == 0), mode)
        return tuple(np.asarray(a) for a in out)


def hermitian(re, im):
    """One ky column made conjugate-symmetric in kx: the partner of row k is
    row (nx - k) % nx, the column reversed and turned by one."""
    return 0.5 * (re + jnp.roll(re[::-1], 1)), 0.5 * (im - jnp.roll(im[::-1], 1))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _run(c, state, steps, scal, mode):
    dt, even_ny = scal

    def to_physical(re, im):
        mid_re = _mm(c["xs_c"], re, mode) - _mm(c["xs_s"], im, mode)
        mid_im = _mm(c["xs_c"], im, mode) + _mm(c["xs_s"], re, mode)
        return _mm(mid_re, c["ys_cT"], mode) + _mm(mid_im, c["ys_sT"], mode)

    def to_spectral(v):
        a, b = _mm(v, c["ya_cT"], mode), _mm(v, c["ya_sT"], mode)
        return (_mm(c["xa_c"], a, mode) - _mm(c["xa_s"], b, mode),
                _mm(c["xa_c"], b, mode) + _mm(c["xa_s"], a, mode))

    def step(_, s):
        re, im = s
        v = to_physical(re, im)
        cub_re, cub_im = to_spectral(v * v * v)
        re = (re - dt * cub_re) * c["inv_matl"]
        im = (im - dt * cub_im) * c["inv_matl"]
        re, im = re.at[0, 0].set(0.0), im.at[0, 0].set(0.0)
        for col in (0, -1) if even_ny else (0,):
            col_re, col_im = hermitian(re[:, col], im[:, col])
            re, im = re.at[:, col].set(col_re), im.at[:, col].set(col_im)
        return re, im

    return lax.fori_loop(0, steps, step, state)
