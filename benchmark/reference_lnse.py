"""Plain reference for one iteration of the optimal-perturbation campaign the
``lnse_opt128_f32`` cell times: the nonlinear forward sweep about a base
state with its trajectory stored, the functional, the hand-adjoint sweep
backward through that trajectory, the gradient and the energy-constrained
descent (upstream ``examples/navier_lnse_opt_reversals.rs`` over
``src/navier_stokes_lnse/nonlin_eq.rs``, ``nonlin_adj_eq.rs``,
``nonlin_adj_grad.rs``, ``functions.rs`` and ``opt_routines.rs``).

Same rules as ``reference.py``: it imports nothing of ``rustpde_mpi_tpu`` and
takes nothing the program has made.  Operators are built here in float64 numpy
from their definitions with ``reference.py``'s one-axis builders, cast once to
float32 and applied as unfolded dense matrix products in natural coefficient
order at ``Precision.HIGHEST``.  No parity folding, no separated layout, no
hoisting, no buckets: each sweep is one scan.

The base state ``(U, V, T)`` is handed in as physical values on the grid, T
the **total** temperature (conduction profile included).  With ``u, v, t`` the
perturbation, ``reference.py``'s names for the operators, and
``conv(f) = dealias_2/3(analysis(f))``:

forward step (``nonlin_eq.rs``; one IMEX Euler step of the full equations
stated about the base state):

    N(f, M)     = u dM/dx + v dM/dy + U df/dx + V df/dy + u df/dx + v df/dy
    velx*       = Hu[ velx - dt dp/dx - dt (conv N(u, U) + conv(U.grad U)) + dt nu lap U ]
    vely*       = Hu[ vely - dt dp/dy + dt (t + T) - dt (conv N(v, V) + conv(U.grad V)) + dt nu lap V ]
    projection, pres: as in ``reference.py``
    temp        = Ht[ temp - dt (conv N(t, T) + conv(U.grad T)) + dt ka lap T ]

and the post-step ``(velx, vely, temp)`` in the orthogonal base is the
trajectory's entry for that step (``nonlin_adj_grad.rs:66-76``).

functional (``functions.rs:32-57``), with ``(tu, tv, tt)`` the target as a
perturbation (the x-mirrored base state less the base state):

    J = 0.5 sum_grid [ b1 ((u - tu)^2 + (v - tv)^2) + b2 (t - tt)^2 ]

terminal condition (``nonlin_adj_grad.rs:155-168``): velx, vely scaled by b1
and temp by b2 after the target is taken off; pres and pseu kept.

adjoint step (``nonlin_adj_eq.rs``), with ``(uh, vh, th)`` the trajectory's
entry, entries consumed last to first:

    A(f)        = (U + uh) df/dx + (V + vh) df/dy
    velx*       = Hu[ velx - dt dp/dx + dt conv( A(u) - u d(U+uh)/dx - v d(V+vh)/dx - t d(T+th)/dx ) ]
    vely*       = Hu[ vely - dt dp/dy + dt conv( A(v) - u d(U+uh)/dy - v d(V+vh)/dy - t d(T+th)/dy ) ]
    projection, pres: as forward
    temp        = Ht[ temp + dt conv A(t) + dt vely_old ]

gradient: ``-(u, v, t)`` of the adjoint state after the sweep, in physical
space (``MAXIMIZE = false``).  Descent: ``steepest_descent_energy_constrained``
in numpy, below.

Departures from upstream, each without effect at float32:

* upstream solves its Helmholtz and Poisson systems by banded sweeps; here
  their dense inverses (eigen-decomposed for Poisson) are applied as products,
  and the singular pressure mode is dropped and pinned, as in ``reference.py``;
* upstream keeps the trajectory as a vector of field triples and pops it; here
  it is three stacked arrays read from their ends;
* the base state's constants (its gradients, its own convection and its
  Laplacians) are worked out once in float64 and cast, where upstream
  recomputes the base state's convection every step.

``mode`` is ``reference.py``'s: ``"f32"`` (the reference), ``"bf16_3x"`` (three
bfloat16 passes, the nearest precision below: the control), ``"bf16"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .reference import (
    _mm,
    analysis,
    diff,
    helmholtz_inverse,
    poisson_modes,
    projection,
    stencil,
    synthesis,
)

STATE = ("temp", "velx", "vely", "pres", "pseu")
FIELDS = ("velx", "vely", "temp")
_BASES = {"temp": ("N", "D"), "velx": ("D", "D"), "vely": ("D", "D"),
          "pres": ("C", "C"), "pseu": ("N", "N")}


def mirrored_target(base: dict) -> dict:
    """The x-mirrored base state as a perturbation about the base state
    (``navier_lnse_opt_reversals.rs:7-13``: velx and vely change sign, velx
    and temp are flipped along x)."""
    return {
        "velx": -base["velx"][::-1, :] - base["velx"],
        "vely": -base["vely"] - base["vely"],
        "temp": base["temp"][::-1, :] - base["temp"],
    }


def energy(velx, vely, temp, beta1: float, beta2: float) -> float:
    """``functions.rs:32-57`` of a field with itself: a plain sum over the
    grid's points."""
    return float(0.5 * np.sum(beta1 * (velx * velx + vely * vely) + beta2 * temp * temp))


def steepest_descent_energy_constrained(old: dict, grad: dict, beta1, beta2, alpha) -> dict:
    """``opt_routines.rs:15-56``: the gradient made perpendicular to the old
    fields in the energy's inner product, then old and gradient combined on
    the sphere of the old fields' energy at the angle ``alpha``."""
    def inner(a, b):
        return 0.5 * np.sum(
            beta1 * (a["velx"] * b["velx"] + a["vely"] * b["vely"]) + beta2 * a["temp"] * b["temp"]
        )

    e0 = inner(old, old)
    perp = {k: grad[k] - (inner(grad, old) / e0) * old[k] for k in FIELDS}
    fac = np.sqrt(e0 / inner(perp, perp))
    return {k: old[k] * np.cos(alpha) + perp[k] * (fac * np.sin(alpha)) for k in FIELDS}


class Reference:
    """The perturbation form of confined RBC at (nx, ny, Ra, Pr, dt, aspect),
    bc "rbc", about ``base`` (physical velx, vely and total temp)."""

    def __init__(self, nx, ny, ra, pr, dt, aspect, base: dict, dtype=np.float32):
        # dtype: float32 as the cell runs; float64 (needs jax_enable_x64) only
        # in tests/, to pin these semantics to the program's f64 CPU path
        self.nx, self.ny, self.dt = int(nx), int(ny), float(dt)
        self.dtype = dtype
        sc = (float(aspect), 1.0)
        height = 2.0 * sc[1]
        self.nu = float(np.sqrt(pr / (ra / height**3)))
        self.ka = float(np.sqrt(1.0 / ((ra / height**3) * pr)))
        ns = (self.nx, self.ny)
        bsyn = [synthesis(n) for n in ns]
        fana = [analysis(n) for n in ns]
        dmat = [diff(n) / sc[a] for a, n in enumerate(ns)]
        st = {k: [stencil(k, n) for n in ns] for k in "DNC"}
        self._h = {"B": bsyn, "F": fana, "S": st}

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
            return bsyn[a] @ dmat[a] @ st[k][a]

        def sten(k, a):
            return st[k][a]

        def dsten(k, a):
            return dmat[a] @ st[k][a]

        host = {
            "fwd": (cut[0][:, None] * fana[0], (cut[1][:, None] * fana[1]).T),
            "inv_denom": inv_denom,
            "pois_f": (pm[0][1], pm[1][1].T),
            "pois_b": (pm[0][2], pm[1][2].T),
            "gp_x": dmat[0], "gp_yT": dmat[1].T,
            "q_ortho": two("N", "N", sten, sten),
            "proj_x": (pd[0] @ dsten("N", 0), (pd[1] @ st["N"][1]).T),
            "proj_y": (pd[0] @ st["N"][0], (pd[1] @ dsten("N", 1)).T),
            "div_x": two("D", "D", dsten, sten),
            "div_y": two("D", "D", sten, dsten),
            # the orthogonal base's own synthesis, for the stored trajectory
            "syn_f": two("C", "C", syn, syn),
            "dx_f": two("C", "C", dsyn, syn),
            "dy_f": two("C", "C", syn, dsyn),
        }
        for name, c in (("u", self.nu), ("t", self.ka)):
            kx = "D" if name == "u" else "N"
            host[f"syn_{name}"] = two(kx, "D", syn, syn)
            host[f"dx_{name}"] = two(kx, "D", dsyn, syn)
            host[f"dy_{name}"] = two(kx, "D", syn, dsyn)
            host[f"ortho_{name}"] = two(kx, "D", sten, sten)
            host[f"helm_{name}"] = (
                helmholtz_inverse(kx, self.nx, dt * c / sc[0] ** 2),
                helmholtz_inverse("D", self.ny, dt * c / sc[1] ** 2).T,
            )

        # the base state: physical values, gradients, its own convection and
        # its Laplacians, all from its orthogonal coefficients, in float64
        def both(pair, v):
            return pair[0] @ v @ pair[1]

        hat = {k: fana[0] @ np.asarray(base[k], np.float64) @ fana[1].T for k in FIELDS}
        phys = {k: both(host["syn_f"], hat[k]) for k in FIELDS}
        ddx = {k: both(host["dx_f"], hat[k]) for k in FIELDS}
        ddy = {k: both(host["dy_f"], hat[k]) for k in FIELDS}
        host["mean"] = {"U": phys["velx"], "V": phys["vely"], "that": hat["temp"]}
        for k, tag in zip(FIELDS, "UVT"):
            host["mean"][f"d{tag}dx"], host["mean"][f"d{tag}dy"] = ddx[k], ddy[k]
            host["mean"][f"conv_{tag}"] = both(
                host["fwd"], phys["velx"] * ddx[k] + phys["vely"] * ddy[k]
            )
            host["mean"][f"lap_{tag}"] = (
                dmat[0] @ dmat[0] @ hat[k] + hat[k] @ (dmat[1] @ dmat[1]).T
            )

        # the target: a perturbation in physical space (the functional) and
        # in each variable's own base (the terminal condition)
        self.target = mirrored_target(base)
        host["target"] = {k: self.forward(k, self.target[k]) for k in FIELDS}
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

    # -- the sweeps, on the device --------------------------------------------

    def _consts(self):
        if self._dev is None:
            self._dev = jax.tree.map(lambda a: jnp.asarray(a, self.dtype), self._host)
        return self._dev

    def sweep_forward(self, state, steps: int, mode: str = "f32") -> tuple:
        """``steps`` forward steps from ``state``; returns the new state and
        the trajectory ``(uh, vh, th)``, each ``(steps, nx, ny)``, entry ``i``
        the orthogonal coefficients after step ``i + 1``."""
        scal = (self.dt, self.nu, self.ka)
        out, hist = _forward(
            self._consts(), tuple(jnp.asarray(a) for a in state), int(steps), scal, mode
        )
        return tuple(np.asarray(a) for a in out), hist

    def functional(self, state, beta1: float, beta2: float) -> float:
        gap = {k: self.backward(k, state[STATE.index(k)]) - self.target[k] for k in FIELDS}
        return energy(gap["velx"], gap["vely"], gap["temp"], beta1, beta2)

    def terminal(self, state, beta1: float, beta2: float) -> tuple:
        weight = {"velx": beta1, "vely": beta1, "temp": beta2}
        return tuple(
            ((np.asarray(a, np.float64) - self._host["target"][n]) * weight[n]).astype(self.dtype)
            if n in weight
            else a
            for n, a in zip(STATE, state)
        )

    def sweep_adjoint(self, state, history, mode: str = "f32") -> tuple:
        """One adjoint step per entry of ``history``, last entry first."""
        scal = (self.dt, self.nu, self.ka)
        out = _adjoint(self._consts(), tuple(jnp.asarray(a) for a in state), history, scal, mode)
        return tuple(np.asarray(a) for a in out)

    def iteration(self, fields: dict, steps: int, beta1, beta2, alpha, mode: str = "f32") -> dict:
        """One iteration of the campaign from the physical initial condition
        ``fields``: ``fun_val``, the gradient ``grad_<field>``, the new initial
        condition ``new_<field>`` (physical values, float64) and the state
        and trajectory after the forward sweep."""
        after, history = self.sweep_forward(self.initial_state(fields), steps, mode)
        fun_val = self.functional(after, beta1, beta2)
        adj = self.sweep_adjoint(self.terminal(after, beta1, beta2), history, mode)
        # MAXIMIZE = false: the descent direction
        grad = {k: -self.backward(k, adj[STATE.index(k)]) for k in FIELDS}
        old = {k: np.asarray(fields[k], np.float64) for k in FIELDS}
        new = steepest_descent_energy_constrained(old, grad, beta1, beta2, alpha)
        out = {"fun_val": fun_val, "state": after, "history": history}
        out.update({f"grad_{k}": grad[k] for k in FIELDS})
        out.update({f"new_{k}": new[k] for k in FIELDS})
        return out


def _products(mode: str):
    """``mm(a, b)``, one matrix product in ``mode``'s arithmetic, and
    ``lr(pair, v) = L @ v @ R^T`` of an operator pair."""
    def mm(a, b):
        return _mm(a, b, mode)

    def lr(pair, v):
        return mm(mm(pair[0], v), pair[1])

    return mm, lr


def _projection_half(c, lr, velx_n, vely_n, pres, dt, nu):
    div = lr(c["div_x"], velx_n) + lr(c["div_y"], vely_n)
    pseu_n = lr(c["pois_b"], lr(c["pois_f"], div) * c["inv_denom"])
    pseu_n = pseu_n.at[0, 0].set(0.0)
    velx_n = velx_n - lr(c["proj_x"], pseu_n)
    vely_n = vely_n - lr(c["proj_y"], pseu_n)
    pres_n = pres - nu * div + lr(c["q_ortho"], pseu_n) / dt
    return velx_n, vely_n, pres_n, pseu_n


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _forward(c, state, steps, scal, mode):
    dt, nu, ka = scal
    m = c["mean"]
    mm, lr = _products(mode)

    def step(s, _):
        temp, velx, vely, pres, _pseu = s
        ux = lr(c["syn_u"], velx)
        uy = lr(c["syn_u"], vely)

        def conv(v, tag, dmdx, dmdy):
            dvdx = lr(c[f"dx_{tag}"], v)
            dvdy = lr(c[f"dy_{tag}"], v)
            total = ux * dmdx + uy * dmdy + m["U"] * dvdx + m["V"] * dvdy + ux * dvdx + uy * dvdy
            return lr(c["fwd"], total)

        rhs = (
            lr(c["ortho_u"], velx)
            - dt * mm(c["gp_x"], pres)
            - dt * (conv(velx, "u", m["dUdx"], m["dUdy"]) + m["conv_U"])
            + dt * nu * m["lap_U"]
        )
        velx_n = lr(c["helm_u"], rhs)
        rhs = (
            lr(c["ortho_u"], vely)
            - dt * mm(pres, c["gp_yT"])
            + dt * (lr(c["ortho_t"], temp) + m["that"])
            - dt * (conv(vely, "u", m["dVdx"], m["dVdy"]) + m["conv_V"])
            + dt * nu * m["lap_V"]
        )
        vely_n = lr(c["helm_u"], rhs)
        velx_n, vely_n, pres_n, pseu_n = _projection_half(c, lr, velx_n, vely_n, pres, dt, nu)
        rhs = (
            lr(c["ortho_t"], temp)
            - dt * (conv(temp, "t", m["dTdx"], m["dTdy"]) + m["conv_T"])
            + dt * ka * m["lap_T"]
        )
        temp_n = lr(c["helm_t"], rhs)
        stored = (lr(c["ortho_u"], velx_n), lr(c["ortho_u"], vely_n), lr(c["ortho_t"], temp_n))
        return (temp_n, velx_n, vely_n, pres_n, pseu_n), stored

    return jax.lax.scan(step, state, None, length=steps)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _adjoint(c, state, history, scal, mode):
    dt, nu, _ka = scal
    m = c["mean"]
    mm, lr = _products(mode)

    def step(s, entry):
        temp, velx, vely, pres, _pseu = s
        uh, vh, th = entry
        us = lr(c["syn_u"], velx)
        vs = lr(c["syn_u"], vely)
        ts = lr(c["syn_t"], temp)
        # base state plus trajectory: the flow the adjoint is carried by, and
        # the gradients it is turned by
        adv_x = m["U"] + lr(c["syn_f"], uh)
        adv_y = m["V"] + lr(c["syn_f"], vh)

        def turned(axis):
            return (
                us * (m[f"dUd{axis}"] + lr(c[f"d{axis}_f"], uh))
                + vs * (m[f"dVd{axis}"] + lr(c[f"d{axis}_f"], vh))
                + ts * (m[f"dTd{axis}"] + lr(c[f"d{axis}_f"], th))
            )

        def carried(v, tag):
            return adv_x * lr(c[f"dx_{tag}"], v) + adv_y * lr(c[f"dy_{tag}"], v)

        rhs = (
            lr(c["ortho_u"], velx)
            - dt * mm(c["gp_x"], pres)
            + dt * lr(c["fwd"], carried(velx, "u") - turned("x"))
        )
        velx_n = lr(c["helm_u"], rhs)
        rhs = (
            lr(c["ortho_u"], vely)
            - dt * mm(pres, c["gp_yT"])
            + dt * lr(c["fwd"], carried(vely, "u") - turned("y"))
        )
        vely_n = lr(c["helm_u"], rhs)
        velx_n, vely_n, pres_n, pseu_n = _projection_half(c, lr, velx_n, vely_n, pres, dt, nu)
        rhs = (
            lr(c["ortho_t"], temp)
            + dt * lr(c["fwd"], carried(temp, "t"))
            + dt * lr(c["ortho_u"], vely)
        )
        temp_n = lr(c["helm_t"], rhs)
        return (temp_n, velx_n, vely_n, pres_n, pseu_n), None

    return jax.lax.scan(step, state, history, reverse=True)[0]
