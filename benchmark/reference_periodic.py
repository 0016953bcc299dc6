"""Plain reference for the horizontally periodic Rayleigh-Benard step
(Fourier x Chebyshev) the ``periodic1024_f32`` cells time.

Same rules as ``reference.py``: it imports nothing of ``rustpde_mpi_tpu`` and
takes nothing the program has made.  Operators are built here in float64 numpy
from their definitions, cast once to float32, and applied as unfolded dense
matrix products in natural coefficient order; spectra are complex arrays and
the complex arithmetic is written out (a real matrix applied to the real and to
the imaginary part; a cosine and a sine matrix for the transform).  No split
Re/Im layout, no folding, no ``shard_map``, no cache.  The Chebyshev axis uses
``reference.py``'s one-axis builders.

The Fourier axis, by ``numpy.fft.rfft``'s conventions written out: n uniform
points x_j = 2 pi j / n on [0, 2 pi), integer wavenumbers k = 0..n//2,
amplitude-normalised coefficients

    c_k = (1/n) sum_j v_j exp(-i k x_j),   v_j = sum_k w_k Re(c_k exp(i k x_j))

with w = 1 for k = 0 and for the Nyquist mode of an even n, else 2.  The
aspect ratio enters through the gradient's scale alone (upstream
``navier.rs:225``): d/dx is ``i k / sx`` and the base knows nothing of it.

Semantics (upstream ``src/navier_stokes/navier_eq.rs`` on the spaces of
``Navier2D::new_periodic``, ``navier.rs:336-428``; one IMEX Euler step), with
``reference.py``'s names:

    ux, uy      = synthesis(velx), synthesis(vely)               (old level)
    conv(f)     = dealias_2/3( analysis( ux df/dx + uy df/dy ) )   both axes
    velx*       = Hu[ velx - dt dp/dx - dt conv(velx) ]
    vely*       = Hu[ vely - dt dp/dy + dt (T + T_bc) - dt conv(vely) ]
    div         = d velx*/dx + d vely*/dy
    pseu        = Poisson^-1 div, the k = 0 constant mode pinned to 0
    velx, vely  = vel* - grad pseu
    pres       += -nu div + pseu / dt
    temp        = Ht[ temp + dt ka lap(T_bc) - dt conv(temp + T_bc) ]

``Hu``/``Ht`` are the ADI Helmholtz solves ``(1 + c_x k^2)(I - c_y D2)``: the
Fourier factor is a division per wavenumber, the Chebyshev factor the
quasi-inverse preconditioned solve along y.  The Poisson solve is diagonal in
k along x and diagonalised along y (``-k^2/sx^2 + lambda_y``).  Spaces along y:
velx, vely, temp Dirichlet; pres Chebyshev; pseu Neumann.  ``rbc`` lift:
T_bc = -y/2 (+0.5 on the bottom plate).

Departures from upstream, each without effect at float32:

* upstream nudges every Poisson eigenvalue of the x axis by -1e-10 so that its
  banded factorisation of the singular k = 0 system exists
  (``solver/poisson.rs:84-87``) and pins the constant mode afterwards; here
  the singular mode is dropped from the division and pinned, nothing nudged;
* upstream solves the y systems by banded sweeps; here their dense inverses
  (eigen-decomposed for Poisson) are applied as products, the same systems;
* the Nyquist mode of an even n carries no odd derivative (its ``i k`` is 0),
  as in the program and in any real-to-complex code.

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

from .reference import (
    _mm,
    analysis,
    cgl_points,
    diff,
    helmholtz_inverse,
    poisson_modes,
    projection,
    stencil,
    synthesis,
)

# ---------------------------------------------------------------------------
# the Fourier axis, float64, host
# ---------------------------------------------------------------------------


def fourier_points(n: int) -> np.ndarray:
    return 2.0 * np.pi * np.arange(n) / n


def wavenumbers(n: int) -> np.ndarray:
    return np.arange(n // 2 + 1, dtype=np.float64)


def fourier_analysis(n: int) -> tuple:
    """(cos, -sin) / n, each (n//2+1) x n: ``c = (C + i S) v``."""
    ang = np.outer(wavenumbers(n), fourier_points(n))
    return np.cos(ang) / n, -np.sin(ang) / n


def fourier_synthesis(n: int) -> tuple:
    """(w cos, -w sin), each n x (n//2+1): ``v = C Re(c) + S Im(c)``."""
    w = np.full(n // 2 + 1, 2.0)
    w[0] = 1.0
    if n % 2 == 0:
        w[-1] = 1.0
    ang = np.outer(fourier_points(n), wavenumbers(n))
    return w * np.cos(ang), -w * np.sin(ang)


def first_derivative(n: int) -> np.ndarray:
    """k of ``d/dx = i k``; 0 for the Nyquist mode of an even n."""
    k = wavenumbers(n)
    if n % 2 == 0:
        k[-1] = 0.0
    return k


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

STATE = ("temp", "velx", "vely", "pres", "pseu")
_YBASE = {"temp": "D", "velx": "D", "vely": "D", "pres": "C", "pseu": "N"}


class Reference:
    """Periodic RBC at (nx, ny, Ra, Pr, dt, aspect), bc "rbc"."""

    def __init__(self, nx, ny, ra, pr, dt, aspect=1.0, dtype=np.float32):
        # dtype: float32 as the cells run; float64 (needs jax_enable_x64) only
        # in tests/, to pin these semantics to the program's f64 CPU path
        self.nx, self.ny, self.dt = int(nx), int(ny), float(dt)
        self.mx = self.nx // 2 + 1
        self.dtype = dtype
        self.cdtype = np.complex64 if dtype == np.float32 else np.complex128
        sx, sy = float(aspect), 1.0
        height = 2.0 * sy
        self.nu = float(np.sqrt(pr / (ra / height**3)))
        self.ka = float(np.sqrt(1.0 / ((ra / height**3) * pr)))
        n = self.ny
        bsyn, fana, dmat = synthesis(n), analysis(n), diff(n)
        st = {k: stencil(k, n) for k in "DNC"}
        self._y = {"B": bsyn, "F": fana, "S": st}
        self._xa, self._xs = fourier_analysis(self.nx), fourier_synthesis(self.nx)
        k = wavenumbers(self.nx)
        k1 = first_derivative(self.nx) / sx

        # boundary lift T_bc = -y/2: constant in x, so only k = 0 carries it
        tb_y = fana @ (-0.5 * cgl_points(n))
        tb = np.zeros((self.mx, n))
        tb[0] = tb_y
        tb_dy = np.broadcast_to((bsyn @ (dmat @ tb_y) / sy)[None, :], (self.nx, n))
        tb_diff = np.zeros((self.mx, n))
        tb_diff[0] = dt * self.ka * (dmat @ dmat @ tb_y) / sy**2

        cut_x = np.where(np.arange(self.mx) < self.mx * 2 // 3, 1.0, 0.0)
        cut_y = np.where(np.arange(n) < n * 2 // 3, 1.0, 0.0)
        pd = projection(st["D"])
        lam_y, pois_f, pois_b = poisson_modes("N", n, 1.0 / sy**2)
        denom = -(k**2)[:, None] / sx**2 + lam_y[None, :]
        keep = np.ones_like(denom, dtype=bool)
        keep[0, int(np.argmin(np.abs(lam_y)))] = False  # the constant pressure mode
        inv_denom = np.zeros_like(denom)
        inv_denom[keep] = 1.0 / denom[keep]

        host = {
            "xa": self._xa, "xs": self._xs, "k1": k1,
            "tb": tb, "tb_dy": tb_dy, "tb_diff": tb_diff,
            "cut_x": cut_x, "fwd_yT": (cut_y[:, None] * fana).T,
            "inv_denom": inv_denom, "pois_fT": pois_f.T, "pois_bT": pois_b.T,
            "gp_yT": dmat.T / sy,
            "q_orthoT": st["N"].T,
            "proj_xT": (pd @ st["N"]).T,
            "proj_yT": (pd @ dmat @ st["N"]).T / sy,
            "ortho_T": st["D"].T,
            "div_yT": (dmat @ st["D"]).T / sy,
            "syn_T": (bsyn @ st["D"]).T,
            "dsyn_T": (bsyn @ dmat @ st["D"]).T / sy,
        }
        for name, c in (("u", self.nu), ("t", self.ka)):
            host[f"helm_x_{name}"] = 1.0 / (1.0 + dt * c / sx**2 * k**2)
            host[f"helm_yT_{name}"] = helmholtz_inverse("D", n, dt * c / sy**2).T
        self._host = host
        self._dev = None

    # -- host-side transforms (float64) -------------------------------------

    def forward(self, name: str, values: np.ndarray) -> np.ndarray:
        """Physical values -> composite coefficients of variable ``name``."""
        y = self._y
        kind = _YBASE[name]
        py = projection(y["S"][kind]) if kind != "C" else np.eye(self.ny)
        v = np.asarray(values, np.float64) @ (py @ y["F"]).T
        return self._xa[0] @ v + 1j * (self._xa[1] @ v)

    def backward(self, name: str, coeffs) -> np.ndarray:
        """Composite coefficients -> physical values (float64)."""
        y = self._y
        c = np.asarray(coeffs, np.complex128) @ (y["B"] @ y["S"][_YBASE[name]]).T
        return self._xs[0] @ c.real + self._xs[1] @ c.imag

    def initial_state(self, fields: dict) -> tuple:
        """State from physical values of temp, velx, vely (pres = pseu = 0)."""
        width = {"pres": self.ny, "pseu": self.ny - 2}
        return tuple(
            self.forward(n, fields[n]).astype(self.cdtype)
            if n in fields
            else np.zeros((self.mx, width[n]), self.cdtype)
            for n in STATE
        )

    # -- the step, on the device ----------------------------------------------

    def run(self, state, steps: int, mode: str = "f32"):
        """``steps`` steps from ``state`` (complex composite coefficients);
        returns the new state as numpy arrays."""
        if self._dev is None:
            self._dev = jax.tree.map(lambda a: jnp.asarray(a, self.dtype), self._host)
        scal = (self.dt, self.nu)
        out = _run(self._dev, tuple(jnp.asarray(a) for a in state), jnp.int32(steps), scal, mode)
        return tuple(np.asarray(a) for a in out)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _run(c, state, steps, scal, mode):
    dt, nu = scal

    def along_y(v, mat_t):
        """A real y-operator (given transposed) applied to a complex field."""
        return lax.complex(_mm(v.real, mat_t, mode), _mm(v.imag, mat_t, mode))

    def to_physical(v):
        return _mm(c["xs"][0], v.real, mode) + _mm(c["xs"][1], v.imag, mode)

    def to_spectral(v):
        return lax.complex(_mm(c["xa"][0], v, mode), _mm(c["xa"][1], v, mode))

    def ddx(v):
        return lax.complex(-c["k1"][:, None] * v.imag, c["k1"][:, None] * v.real)

    def helmholtz(rhs, tag):
        return c[f"helm_x_{tag}"][:, None] * along_y(rhs, c[f"helm_yT_{tag}"])

    def step(_, s):
        temp, velx, vely, pres, _pseu = s
        that = along_y(temp, c["ortho_T"]) + c["tb"]
        ux = to_physical(along_y(velx, c["syn_T"]))
        uy = to_physical(along_y(vely, c["syn_T"]))

        def conv(v, with_bc=False):
            dvdx = to_physical(along_y(ddx(v), c["syn_T"]))
            dvdy = to_physical(along_y(v, c["dsyn_T"]))
            if with_bc:
                dvdy = dvdy + c["tb_dy"]
            return c["cut_x"][:, None] * along_y(to_spectral(ux * dvdx + uy * dvdy), c["fwd_yT"])

        rhs = along_y(velx, c["ortho_T"]) - dt * ddx(pres) - dt * conv(velx)
        velx_n = helmholtz(rhs, "u")
        rhs = (
            along_y(vely, c["ortho_T"])
            - dt * along_y(pres, c["gp_yT"])
            + dt * that
            - dt * conv(vely)
        )
        vely_n = helmholtz(rhs, "u")
        div = ddx(along_y(velx_n, c["ortho_T"])) + along_y(vely_n, c["div_yT"])
        pseu_n = along_y(along_y(div, c["pois_fT"]) * c["inv_denom"], c["pois_bT"])
        pseu_n = pseu_n.at[0, 0].set(0.0)
        velx_n = velx_n - along_y(ddx(pseu_n), c["proj_xT"])
        vely_n = vely_n - along_y(pseu_n, c["proj_yT"])
        pres_n = pres - nu * div + along_y(pseu_n, c["q_orthoT"]) / dt
        rhs = along_y(temp, c["ortho_T"]) + c["tb_diff"] - dt * conv(temp, with_bc=True)
        temp_n = helmholtz(rhs, "t")
        return temp_n, velx_n, vely_n, pres_n, pseu_n

    return lax.fori_loop(0, steps, step, state)
