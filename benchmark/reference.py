"""Plain reference for the confined Rayleigh-Benard step the cells time.

It imports nothing of ``rustpde_mpi_tpu`` and takes nothing that the program
has made: operators are built here in float64 numpy from their definitions
(Chebyshev-Gauss-Lobatto collocation, Galerkin stencils, the quasi-inverse
preconditioned Helmholtz/Poisson pencils of the upstream ``rustpde-mpi``),
cast once to float32, and applied as unfolded dense matrix products in natural
coefficient order.  No parity folding, no separated layout, no fused kernels,
no cache.

Semantics (upstream ``src/navier_stokes/navier_eq.rs``; one IMEX Euler step):

    ux, uy      = synthesis(velx), synthesis(vely)               (old level)
    conv(f)     = dealias_2/3( analysis( ux df/dx + uy df/dy ) )
    velx*       = Hu[ velx - dt dp/dx - dt conv(velx) ]
    vely*       = Hu[ vely - dt dp/dy + dt (T + T_bc) - dt conv(vely) ]
    div         = d velx*/dx + d vely*/dy
    pseu        = Poisson^-1 div, constant mode pinned to 0
    velx, vely  = vel* - grad pseu
    pres       += -nu div + pseu / dt
    temp        = Ht[ temp + dt ka lap(T_bc) - dt conv(temp + T_bc) ]

``Hu``/``Ht`` are the ADI Helmholtz solves ``(I - c_x D2)(I - c_y D2)``.
Spaces: velx, vely Dirichlet x Dirichlet; temp Neumann(x) x Dirichlet(y);
pres Chebyshev x Chebyshev; pseu Neumann x Neumann.

``mode`` selects the arithmetic of every matrix product:

* ``"f32"``     float32 operands, ``Precision.HIGHEST`` (the reference);
* ``"bf16_3x"`` the three leading partial products of the hi/lo bfloat16 split
  of both operands -- what ``Precision.HIGH`` runs on a TPU, written out so
  that it is the same arithmetic on the CPU;
* ``"bf16"``    one bfloat16 pass (``Precision.DEFAULT`` on a TPU).

The last two are the lower-precision controls of ``tests/``; the benchmark's
own runs never use them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# ---------------------------------------------------------------------------
# one axis, float64, host
# ---------------------------------------------------------------------------


def cgl_points(n: int) -> np.ndarray:
    """Ascending Chebyshev-Gauss-Lobatto points, x[0] = -1 (bottom/left)."""
    return -np.cos(np.pi * np.arange(n) / (n - 1))


def synthesis(n: int) -> np.ndarray:
    """B[j, k] = T_k(x_j)."""
    j = np.arange(n)[:, None]
    k = np.arange(n)[None, :]
    return (-1.0) ** k * np.cos(np.pi * k * j / (n - 1))


def analysis(n: int) -> np.ndarray:
    """Inverse of :func:`synthesis` (DCT-I orthogonality)."""
    big = n - 1
    c = np.ones(n)
    c[0] = c[-1] = 2.0
    return (2.0 / big) * synthesis(n).T / (c[:, None] * c[None, :])


def diff(n: int) -> np.ndarray:
    """d/dx on Chebyshev coefficients: T_p' = 2p sum_{k<p, p-k odd} T_k / c_k."""
    d = np.zeros((n, n))
    for p in range(1, n):
        d[p - 1 :: -2, p] = 2.0 * p
    d[0] *= 0.5
    return d


def stencil(kind: str, n: int) -> np.ndarray:
    """Composite -> orthogonal coefficients, n x m.

    ``"D"``: phi_k = T_k - T_{k+2} (u = 0 at both walls);
    ``"N"``: phi_k = T_k - (k/(k+2))^2 T_{k+2} (u' = 0 at both walls);
    ``"C"``: the orthogonal base itself."""
    if kind == "C":
        return np.eye(n)
    s = np.zeros((n, n - 2))
    k = np.arange(n - 2)
    s[k, k] = 1.0
    s[k + 2, k] = -1.0 if kind == "D" else -((k / (k + 2.0)) ** 2)
    return s


def projection(s: np.ndarray) -> np.ndarray:
    """Chebyshev-weighted Galerkin projection, orthogonal -> composite."""
    w = np.ones(s.shape[0])
    w[0] = 2.0
    return np.linalg.solve(s.T @ (w[:, None] * s), s.T * w[None, :])


def quasi_inverse(n: int) -> np.ndarray:
    """Rows 2.. of the banded quasi-inverse B2 of D2 (B2 D2 = I on rows
    2..), last two columns dropped as in pypde/funspace: (n-2) x n."""
    b = np.zeros((n, n))
    for k in range(2, n):
        b[k, k - 2] = (2.0 if k == 2 else 1.0) / (4.0 * k * (k - 1.0))
        b[k, k] = -1.0 / (2.0 * (k * k - 1.0))
        if k + 2 < n:
            b[k, k + 2] = 1.0 / (4.0 * k * (k + 1.0))
    b[:, n - 2 :] = 0.0
    return b[2:]


def helmholtz_inverse(kind: str, n: int, c: float) -> np.ndarray:
    """One ADI factor: orthogonal-space rhs -> composite solution of
    ``(I - c D2) u = f``, preconditioned by the quasi-inverse: m x n."""
    s = stencil(kind, n)
    b2 = quasi_inverse(n)
    return np.linalg.solve(b2 @ s - c * s[2:], b2)


def poisson_modes(kind: str, n: int, c: float):
    """Diagonalise one axis of ``c D2 u = f`` in its preconditioned form
    ``A u = B2 f`` with ``A = I_r S``, ``C = B2 S``: ``C^-1 A = Q L Q^-1``.
    Returns ``(c L, Q^-1 C^-1 B2, Q)``.  Even and odd modes decouple, so the
    two halves are decomposed apart (the eigenvectors then have a parity)."""
    s = stencil(kind, n)
    b2 = quasi_inverse(n)
    a_mat, c_mat = s[2:], b2 @ s
    m = n - 2
    lam = np.empty(m)
    q = np.zeros((m, m))
    fwd = np.zeros((m, n))
    for par in (0, 1):
        sl = slice(par, None, 2)
        off = (np.add.outer(np.arange(m), np.arange(m)) % 2) == 1
        if np.abs(a_mat[off]).max() > 0 or np.abs(c_mat[off]).max() > 0:
            raise ValueError("pencil does not preserve parity")
        w, v = np.linalg.eig(np.linalg.solve(c_mat[sl, sl], a_mat[sl, sl]))
        if np.abs(w.imag).max() > 1e-8 * np.abs(w.real).max():
            raise ValueError("pencil has complex eigenvalues")
        lam[sl] = w.real
        q[sl, sl] = v.real
        fwd[sl, sl] = np.linalg.solve(v.real, np.linalg.solve(c_mat[sl, sl], b2[sl, sl]))
    return c * lam, fwd, q


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

STATE = ("temp", "velx", "vely", "pres", "pseu")
_BASES = {"temp": ("N", "D"), "velx": ("D", "D"), "vely": ("D", "D"),
          "pres": ("C", "C"), "pseu": ("N", "N")}


class Reference:
    """Confined RBC at (nx, ny, Ra, Pr, dt, aspect), bc "rbc"."""

    def __init__(self, nx, ny, ra, pr, dt, aspect=1.0, dtype=np.float32):
        # dtype: float32 as the cells run; float64 (needs jax_enable_x64) only
        # in tests/, to pin these semantics to the program's f64 CPU path
        self.nx, self.ny, self.dt = int(nx), int(ny), float(dt)
        self.dtype = dtype
        sx, sy = float(aspect), 1.0
        self.scale = (sx, sy)
        height = 2.0 * sy
        self.nu = float(np.sqrt(pr / (ra / height**3)))
        self.ka = float(np.sqrt(1.0 / ((ra / height**3) * pr)))
        ns = (self.nx, self.ny)
        sc = (sx, sy)
        bsyn = [synthesis(n) for n in ns]
        fana = [analysis(n) for n in ns]
        dmat = [diff(n) for n in ns]
        st = {k: [stencil(k, n) for n in ns] for k in "DNC"}
        self._h = {"B": bsyn, "F": fana, "D": dmat, "S": st}

        # boundary lift: T = +0.5 on the bottom plate, -0.5 on the top
        y = cgl_points(self.ny)
        lift = np.broadcast_to((-0.5 * y)[None, :], ns)
        tb = fana[0] @ lift @ fana[1].T
        tb_dx = bsyn[0] @ (dmat[0] @ tb) @ bsyn[1].T / sx
        tb_dy = bsyn[0] @ (tb @ dmat[1].T) @ bsyn[1].T / sy
        tb_diff = dt * self.ka * (
            dmat[0] @ dmat[0] @ tb / sx**2 + tb @ (dmat[1] @ dmat[1]).T / sy**2
        )

        cut = [np.where(np.arange(n) < n * 2 // 3, 1.0, 0.0) for n in ns]
        pd = [projection(st["D"][a]) for a in (0, 1)]
        pm = [poisson_modes("N", ns[a], 1.0 / sc[a] ** 2) for a in (0, 1)]
        denom = pm[0][0][:, None] + pm[1][0][None, :]
        zero = (int(np.argmin(np.abs(pm[0][0]))), int(np.argmin(np.abs(pm[1][0]))))
        inv_denom = np.zeros_like(denom)
        keep = np.ones_like(denom, dtype=bool)
        keep[zero] = False  # the constant pressure mode: dropped, then pinned
        inv_denom[keep] = 1.0 / denom[keep]

        def two(kx, ky, fx, fy):
            """(left, right-transposed) pair for ``L @ v @ R^T``."""
            return fx(kx, 0), fy(ky, 1).T

        def syn(k, a):
            return bsyn[a] @ st[k][a]

        def dsyn(k, a):
            return bsyn[a] @ dmat[a] @ st[k][a] / sc[a]

        def sten(k, a):
            return st[k][a]

        def dsten(k, a):
            return dmat[a] @ st[k][a] / sc[a]

        host = {
            "tb": tb, "tb_dx": tb_dx, "tb_dy": tb_dy, "tb_diff": tb_diff,
            "fwd": (cut[0][:, None] * fana[0], (cut[1][:, None] * fana[1]).T),
            "inv_denom": inv_denom,
            "pois_f": (pm[0][1], pm[1][1].T),
            "pois_b": (pm[0][2], pm[1][2].T),
            "gp_x": dmat[0] / sx, "gp_yT": dmat[1].T / sy,
            "q_ortho": two("N", "N", sten, sten),
            "proj_x": (pd[0] @ dsten("N", 0), (pd[1] @ st["N"][1]).T),
            "proj_y": (pd[0] @ st["N"][0], (pd[1] @ dsten("N", 1)).T),
            "div_x": two("D", "D", dsten, sten),
            "div_y": two("D", "D", sten, dsten),
        }
        for name, c in (("u", self.nu), ("t", self.ka)):
            kx = "D" if name == "u" else "N"
            host[f"syn_{name}"] = two(kx, "D", syn, syn)
            host[f"dx_{name}"] = two(kx, "D", dsyn, syn)
            host[f"dy_{name}"] = two(kx, "D", syn, dsyn)
            host[f"ortho_{name}"] = two(kx, "D", sten, sten)
            host[f"helm_{name}"] = (
                helmholtz_inverse(kx, self.nx, dt * c / sx**2),
                helmholtz_inverse("D", self.ny, dt * c / sy**2).T,
            )
        self._host = host
        self._dev = None

    # -- host-side transforms (float64) -------------------------------------

    def forward(self, name: str, values: np.ndarray) -> np.ndarray:
        """Physical values -> composite coefficients of variable ``name``."""
        h = self._h
        kx, ky = _BASES[name]
        px = projection(h["S"][kx][0]) if kx != "C" else np.eye(self.nx)
        py = projection(h["S"][ky][1]) if ky != "C" else np.eye(self.ny)
        return (px @ h["F"][0]) @ np.asarray(values, np.float64) @ (py @ h["F"][1]).T

    def backward(self, name: str, coeffs) -> np.ndarray:
        """Composite coefficients -> physical values (float64)."""
        h = self._h
        kx, ky = _BASES[name]
        return (h["B"][0] @ h["S"][kx][0]) @ np.asarray(coeffs, np.float64) @ (
            h["B"][1] @ h["S"][ky][1]
        ).T

    def initial_state(self, fields: dict) -> tuple:
        """State from physical values of temp, velx, vely (pres = pseu = 0)."""
        zero = {"pres": (self.nx, self.ny), "pseu": (self.nx - 2, self.ny - 2)}
        return tuple(
            self.forward(n, fields[n]).astype(self.dtype)
            if n in fields
            else np.zeros(zero[n], self.dtype)
            for n in STATE
        )

    # -- the step, on the device ----------------------------------------------

    def run(self, state, steps: int, mode: str = "f32"):
        """``steps`` steps from ``state`` (composite float32 coefficients);
        returns the new state as numpy arrays."""
        if self._dev is None:
            self._dev = jax.tree.map(lambda a: jnp.asarray(a, self.dtype), self._host)
        scal = (self.dt, self.nu)
        out = _run(self._dev, tuple(jnp.asarray(a) for a in state), jnp.int32(steps), scal, mode)
        return tuple(np.asarray(a) for a in out)


def _mm(a, b, mode):
    if mode == "f32":
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    bf, f32 = jnp.bfloat16, jnp.float32
    a0, b0 = a.astype(bf), b.astype(bf)
    if mode == "bf16":
        return jnp.matmul(a0, b0, preferred_element_type=f32)
    if mode != "bf16_3x":
        raise ValueError(f"unknown arithmetic {mode!r}")
    a1 = (a - a0.astype(f32)).astype(bf)
    b1 = (b - b0.astype(f32)).astype(bf)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=f32)

    return dot(a0, b0) + (dot(a0, b1) + dot(a1, b0))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _run(c, state, steps, scal, mode):
    dt, nu = scal

    def lr(pair, v):
        return _mm(_mm(pair[0], v, mode), pair[1], mode)

    def step(_, s):
        temp, velx, vely, pres, _pseu = s
        that = lr(c["ortho_t"], temp) + c["tb"]
        ux = lr(c["syn_u"], velx)
        uy = lr(c["syn_u"], vely)

        def conv(v, tag, with_bc=False):
            dvdx = lr(c[f"dx_{tag}"], v)
            dvdy = lr(c[f"dy_{tag}"], v)
            if with_bc:
                dvdx = dvdx + c["tb_dx"]
                dvdy = dvdy + c["tb_dy"]
            return lr(c["fwd"], ux * dvdx + uy * dvdy)

        rhs = lr(c["ortho_u"], velx) - dt * _mm(c["gp_x"], pres, mode) - dt * conv(velx, "u")
        velx_n = lr(c["helm_u"], rhs)
        rhs = (
            lr(c["ortho_u"], vely)
            - dt * _mm(pres, c["gp_yT"], mode)
            + dt * that
            - dt * conv(vely, "u")
        )
        vely_n = lr(c["helm_u"], rhs)
        div = lr(c["div_x"], velx_n) + lr(c["div_y"], vely_n)
        pseu_n = lr(c["pois_b"], lr(c["pois_f"], div) * c["inv_denom"])
        pseu_n = pseu_n.at[0, 0].set(0.0)
        velx_n = velx_n - lr(c["proj_x"], pseu_n)
        vely_n = vely_n - lr(c["proj_y"], pseu_n)
        pres_n = pres - nu * div + lr(c["q_ortho"], pseu_n) / dt
        rhs = lr(c["ortho_t"], temp) + c["tb_diff"] - dt * conv(temp, "t", with_bc=True)
        temp_n = lr(c["helm_t"], rhs)
        return temp_n, velx_n, vely_n, pres_n, pseu_n

    return lax.fori_loop(0, steps, step, state)


def random_fields(shape, amp: float, seed: int) -> dict:
    """The program's random initial condition as its public contract states
    it (``init_random``, the served request's too): uniform noise in
    [-amp, amp] from ``numpy.random.default_rng(seed)``, drawn for temp, velx,
    vely in that order."""
    rng = np.random.default_rng(int(seed))
    return {n: rng.uniform(-amp, amp, size=shape) for n in ("temp", "velx", "vely")}
