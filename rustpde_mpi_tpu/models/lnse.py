"""Navier2DLnse / Navier2DNonLin — linearized & perturbation-form NSE with
adjoint-based sensitivity, TPU-native.

Rebuild of /root/reference/src/navier_stokes_lnse/ (lnse.rs, lnse_eq.rs,
lnse_adj_eq.rs, lnse_adj_grad.rs, lnse_fd_grad.rs, nonlin*.rs):

* :class:`Navier2DLnse` — NSE linearized about a :class:`MeanFields` base
  state; convection ``u . grad(U) + U . grad(u)`` (lnse_eq.rs:59-110), same
  implicit-diffusion / pressure-projection scheme as Navier2D.
* :class:`Navier2DNonLin` — full nonlinear equations stated as a perturbation
  about the base state (adds ``u.grad(u)`` and the mean-balance terms,
  nonlin_eq.rs), recording the forward trajectory for the adjoint loop.
* ``grad_adjoint`` — the reference's discrete hand-adjoint: forward loop to
  ``max_time``, energy functional, backward adjoint loop, gradient w.r.t.
  the initial condition (lnse_adj_grad.rs:105-205).  Kept for parity with
  the reference's validation tolerance (~30%: it is a continuous-adjoint
  approximation).
* ``grad_autodiff`` — the TPU-native alternative: ``jax.grad`` through the
  scanned forward loop, giving the *exact* gradient of the discrete
  objective (matches finite differences to ~1e-6 instead of ~30%).
* ``grad_fd`` — brute-force finite differences (lnse_fd_grad.rs:32-58),
  vmapped over perturbation batches instead of the reference's sequential
  per-grid-point loop.

The whole forward/adjoint loops run as ``lax.scan`` on device; a host
round-trip happens only at the energy evaluation between them.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from .. import config
from ..field import norm_l2
from ..utils.integrate import Integrate
from .campaign import CampaignModelBase
from .meanfield import MeanFields
from .navier import Navier2D, NavierState

#: Solve maximization problem instead of minimization (lnse_adj_grad.rs:16)
MAXIMIZE = False


def l2_norm(a1, a2, b1, b2, c1, c2, beta1: float, beta2: float):
    """0.5 * sum(beta1*(a1*a2 + b1*b2) + beta2*c1*c2) over grid points
    (/root/reference/src/navier_stokes_lnse/functions.rs:32-57)."""
    return 0.5 * jnp.sum(beta1 * (a1 * a2 + b1 * b2) + beta2 * (c1 * c2))


class Navier2DLnse(CampaignModelBase, Integrate):
    """Linearized NSE about a mean field; Navier2D parameter vocabulary plus
    ``mean`` (defaults to the analytic bc profile).

    A full campaign model (models/campaign.py): the direct step is hoisted
    into ``_step_cc`` so eigenmode sweeps run as vmapped
    :class:`~rustpde_mpi_tpu.models.ensemble.NavierEnsemble` batches under
    ``ResilientRunner`` and the serve scheduler — observables are the
    perturbation energies ``(energy, ke, te, div)``, whose chunk-boundary
    trajectory the eigenmode workload fits growth rates from
    (workloads/eigenmodes.py)."""

    MODEL_KIND = "lnse"
    observable_names = ("energy", "ke", "te", "div")

    #: include the perturbation self-convection + mean-balance terms
    NONLINEAR = False

    def __init__(
        self,
        nx: int,
        ny: int,
        ra: float,
        pr: float,
        dt: float,
        aspect: float,
        bc: str,
        periodic: bool = False,
        mean: MeanFields | None = None,
        mesh=None,
    ):
        self.navier = Navier2D(nx, ny, ra, pr, dt, aspect, bc, periodic, mesh=mesh)
        if mean is None:
            mean = MeanFields.read_from(nx, ny, "mean.h5", bc=bc, periodic=periodic)
        if mean.space.shape_physical != self.navier.field_space.shape_physical:
            raise ValueError(
                f"mean field grid {mean.space.shape_physical} != model grid "
                f"{self.navier.field_space.shape_physical}"
            )
        self.mean = mean
        self.mesh = mesh
        self.dt = dt
        self.params = self.navier.params
        self.scale = self.navier.scale
        self.write_intervall: float | None = None
        self.statistics = None
        self._init_campaign()
        self._compile_entry_points()
        self.state = NavierState(*self.navier.state)

    @property
    def nx(self) -> int:
        return self.navier.nx

    @property
    def ny(self) -> int:
        return self.navier.ny

    # space delegates (checkpoint layer vocabulary)
    @property
    def temp_space(self):
        return self.navier.temp_space

    @property
    def velx_space(self):
        return self.navier.velx_space

    @property
    def vely_space(self):
        return self.navier.vely_space

    @property
    def pres_space(self):
        return self.navier.pres_space

    @property
    def pseu_space(self):
        return self.navier.pseu_space

    @property
    def field_space(self):
        return self.navier.field_space

    @property
    def x(self):
        return self.navier.x

    def _compat_fields(self) -> tuple:
        return (
            int(self.navier.nx),
            int(self.navier.ny),
            float(self.params["ra"]),
            float(self.params["pr"]),
            float(self.dt),
            float(self.scale[0]),
            str(self.navier.bc),
            bool(self.navier.periodic),
            (),  # scenario slot (modifiers are a DNS axis)
        )

    def _gspmd_split_sep_fallback(self) -> bool:
        # the DNS step routes this layout through manual shard_map regions
        # (ShardedConv/ShardedPoisson); the LNSE step has no manual
        # counterpart yet — shared eager-guard policy
        return self.navier._split_sep_eager_unless_forced()

    def _state_example(self):
        nav = self.navier
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            NavierState(*nav.state[:5]),
        )

    @classmethod
    def new_confined(cls, nx, ny, ra, pr, dt, aspect, bc, mean=None, mesh=None):
        return cls(nx, ny, ra, pr, dt, aspect, bc, periodic=False, mean=mean, mesh=mesh)

    @classmethod
    def new_periodic(cls, nx, ny, ra, pr, dt, aspect, bc, mean=None, mesh=None):
        return cls(nx, ny, ra, pr, dt, aspect, bc, periodic=True, mean=mean, mesh=mesh)

    # -- mean-field device constants -----------------------------------------

    def _mean_constants(self):
        """Physical values + physical gradients of the base state, as device
        constants closed over by the jitted steps."""
        sp = self.navier.field_space
        scale = self.scale

        def phys(vhat, deriv=(0, 0)):
            if deriv == (0, 0):
                return sp.backward_ortho(vhat)
            return sp.backward_ortho(sp.gradient(vhat, deriv, scale))

        m = self.mean
        return {
            "U": phys(m.velx),
            "V": phys(m.vely),
            "T": phys(m.temp),
            "dUdx": phys(m.velx, (1, 0)),
            "dUdy": phys(m.velx, (0, 1)),
            "dVdx": phys(m.vely, (1, 0)),
            "dVdy": phys(m.vely, (0, 1)),
            "dTdx": phys(m.temp, (1, 0)),
            "dTdy": phys(m.temp, (0, 1)),
        }

    # -- direct (forward) step ------------------------------------------------

    def _make_step(self, with_sentinels: bool = False):
        """The linearized step; ``with_sentinels=True`` additionally returns
        ``(cfl, ke, |div|)`` — the advective CFL uses the TOTAL velocity
        (mean + perturbation: the mean advects the perturbation, so it
        bounds the explicit convection's stability), ke is the perturbation
        kinetic energy, |div| the pre-projection residual."""
        nav = self.navier
        dt = self.dt
        scale = self.scale
        nu, ka = self.params["nu"], self.params["ka"]
        inv_dx, inv_dy = nav._inv_dx, nav._inv_dy
        w0s, w1s = nav._w0, nav._w1
        sp_t, sp_u, sp_v = nav.temp_space, nav.velx_space, nav.vely_space
        sp_p, sp_q, sp_f = nav.pres_space, nav.pseu_space, nav.field_space
        from ..bases import fused_projection_gradient

        _gx = fused_projection_gradient(sp_u, sp_q, (1, 0))
        _gy = fused_projection_gradient(sp_v, sp_q, (0, 1))
        proj_grad = (*_gx, *_gy) if _gx and _gy else None
        mask = nav._dealias
        mc = self._mean_constants()
        sol_u, sol_v, sol_t, sol_p = (
            nav.solver_velx, nav.solver_vely, nav.solver_temp, nav.solver_pres,
        )
        nonlinear = self.NONLINEAR
        mean = self.mean

        def gphys(space, vhat, deriv):
            return sp_f.backward_ortho(space.gradient(vhat, deriv, scale))

        def conv(total):
            if any(sp_f.sep):
                return sp_f.forward_dealiased(total)
            return sp_f.forward(total) * mask

        # mean-balance constants of the perturbation form (nonlin_eq.rs):
        # mean-mean convection and mean diffusion enter the rhs every step
        if nonlinear:
            conv_mm_x = np.asarray(
                conv(mc["U"] * mc["dUdx"] + mc["V"] * mc["dUdy"])
            )
            conv_mm_y = np.asarray(
                conv(mc["U"] * mc["dVdx"] + mc["V"] * mc["dVdy"])
            )
            conv_mm_t = np.asarray(
                conv(mc["U"] * mc["dTdx"] + mc["V"] * mc["dTdy"])
            )
            lap_u_m = np.asarray(
                sp_f.gradient(mean.velx, (2, 0), scale)
                + sp_f.gradient(mean.velx, (0, 2), scale)
            )
            lap_v_m = np.asarray(
                sp_f.gradient(mean.vely, (2, 0), scale)
                + sp_f.gradient(mean.vely, (0, 2), scale)
            )
            lap_t_m = np.asarray(
                sp_f.gradient(mean.temp, (2, 0), scale)
                + sp_f.gradient(mean.temp, (0, 2), scale)
            )
            that_mean = np.asarray(mean.temp)

        def step(state: NavierState) -> NavierState:
            temp, velx, vely, pres, pseu = state
            that = sp_t.to_ortho(temp)
            if nonlinear:
                that = that + that_mean  # buoyancy incl. base state
            ux = sp_u.backward(velx)
            uy = sp_v.backward(vely)

            if with_sentinels:
                # advective CFL of the TOTAL velocity (mean + perturbation)
                # + perturbation KE, from arrays the step needs anyway
                cfl = dt * jnp.max(
                    jnp.abs(mc["U"] + ux) * inv_dx[:, None]
                    + jnp.abs(mc["V"] + uy) * inv_dy[None, :]
                )
                ke = 0.5 * jnp.sum(
                    (ux**2 + uy**2) * w0s[:, None] * w1s[None, :]
                )

            # linearized convection: u.grad(U) + U.grad(u) (lnse_eq.rs:59-110)
            du_dx = gphys(sp_u, velx, (1, 0))
            du_dy = gphys(sp_u, velx, (0, 1))
            dv_dx = gphys(sp_v, vely, (1, 0))
            dv_dy = gphys(sp_v, vely, (0, 1))
            dT_dx = gphys(sp_t, temp, (1, 0))
            dT_dy = gphys(sp_t, temp, (0, 1))
            cx = ux * mc["dUdx"] + uy * mc["dUdy"] + mc["U"] * du_dx + mc["V"] * du_dy
            cy = ux * mc["dVdx"] + uy * mc["dVdy"] + mc["U"] * dv_dx + mc["V"] * dv_dy
            ct = ux * mc["dTdx"] + uy * mc["dTdy"] + mc["U"] * dT_dx + mc["V"] * dT_dy
            if nonlinear:
                # + u.grad(u) and + U.grad(U) (nonlin_eq.rs:59-120)
                cx = cx + ux * du_dx + uy * du_dy
                cy = cy + ux * dv_dx + uy * dv_dy
                ct = ct + ux * dT_dx + uy * dT_dy
            conv_x, conv_y, conv_t = conv(cx), conv(cy), conv(ct)
            if nonlinear:
                conv_x = conv_x + conv_mm_x
                conv_y = conv_y + conv_mm_y
                conv_t = conv_t + conv_mm_t

            rhs = sp_u.to_ortho(velx)
            rhs = rhs - dt * sp_p.gradient(pres, (1, 0), scale)
            rhs = rhs - dt * conv_x
            if nonlinear:
                rhs = rhs + dt * nu * lap_u_m
            velx_n = sol_u.solve(rhs)

            rhs = sp_v.to_ortho(vely)
            rhs = rhs - dt * sp_p.gradient(pres, (0, 1), scale)
            rhs = rhs + dt * that
            rhs = rhs - dt * conv_y
            if nonlinear:
                rhs = rhs + dt * nu * lap_v_m
            vely_n = sol_v.solve(rhs)

            div = sp_u.gradient(velx_n, (1, 0), scale) + sp_v.gradient(
                vely_n, (0, 1), scale
            )
            pseu_n = sol_p.solve(div)
            pseu_n = sp_q.pin_zero_mode(pseu_n)
            if proj_grad is not None:
                gx0, gx1, gy0, gy1 = proj_grad
                pax = pseu_n.ndim - 2
                velx_n = velx_n - gx1.apply(gx0.apply(pseu_n, pax), pax + 1) / scale[0]
                vely_n = vely_n - gy1.apply(gy0.apply(pseu_n, pax), pax + 1) / scale[1]
            else:
                velx_n = velx_n - sp_q.gradient(pseu_n, (1, 0), scale, into=sp_u)
                vely_n = vely_n - sp_q.gradient(pseu_n, (0, 1), scale, into=sp_v)
            pres_n = pres - nu * div + sp_q.to_ortho(pseu_n) / dt

            rhs = sp_t.to_ortho(temp)
            rhs = rhs - dt * conv_t
            if nonlinear:
                rhs = rhs + dt * ka * lap_t_m
            temp_n = sol_t.solve(rhs)

            state_n = NavierState(temp_n, velx_n, vely_n, pres_n, pseu_n)
            if with_sentinels:
                return state_n, (cfl, ke, norm_l2(div))
            return state_n

        return step

    def _make_observables(self):
        """Fused perturbation diagnostics ``(energy, ke, te, |div|)``:
        the same plain grid-point sums :meth:`energy` uses (``energy`` ==
        ``energy(0.5, 0.5)``), so growth-rate fits over the observable
        trajectory and the optimization objective agree; |div| is the
        NaN detector (observable_names index 3 by convention)."""
        nav = self.navier
        sp_t, sp_u, sp_v = nav.temp_space, nav.velx_space, nav.vely_space
        scale = self.scale

        def observables(state: NavierState):
            u = sp_u.backward(state.velx)
            v = sp_v.backward(state.vely)
            t = sp_t.backward(state.temp)
            ke = 0.5 * jnp.sum(u * u + v * v)
            te = 0.5 * jnp.sum(t * t)
            div = norm_l2(
                sp_u.gradient(state.velx, (1, 0), scale)
                + sp_v.gradient(state.vely, (0, 1), scale)
            )
            return 0.5 * (ke + te), ke, te, div

        return observables

    # -- adjoint step ----------------------------------------------------------

    def _make_adjoint_step(self):
        """One backward (adjoint) step; with history ``h = (uh, vh, th)``
        vhats from the forward loop for the nonlinear variant
        (lnse_adj_eq.rs / nonlin_adj_eq.rs)."""
        nav = self.navier
        dt = self.dt
        scale = self.scale
        nu = self.params["nu"]
        sp_t, sp_u, sp_v = nav.temp_space, nav.velx_space, nav.vely_space
        sp_p, sp_q, sp_f = nav.pres_space, nav.pseu_space, nav.field_space
        from ..bases import fused_projection_gradient

        _gx = fused_projection_gradient(sp_u, sp_q, (1, 0))
        _gy = fused_projection_gradient(sp_v, sp_q, (0, 1))
        proj_grad = (*_gx, *_gy) if _gx and _gy else None
        mask = nav._dealias
        mc = self._mean_constants()
        sol_u, sol_v, sol_t, sol_p = (
            nav.solver_velx, nav.solver_vely, nav.solver_temp, nav.solver_pres,
        )
        nonlinear = self.NONLINEAR

        def gphys(space, vhat, deriv):
            return sp_f.backward_ortho(space.gradient(vhat, deriv, scale))

        def conv(total):
            if any(sp_f.sep):
                return sp_f.forward_dealiased(total)
            return sp_f.forward(total) * mask

        def step(state: NavierState, history=None) -> NavierState:
            temp, velx, vely, pres, pseu = state
            uyhat = sp_v.to_ortho(vely)  # adjoint buoyancy source (pre-update)
            us = sp_u.backward(velx)
            vs = sp_v.backward(vely)
            ts = sp_t.backward(temp)

            U, V = mc["U"], mc["V"]
            dUdx, dVdx, dTdx = mc["dUdx"], mc["dVdx"], mc["dTdx"]
            dUdy, dVdy, dTdy = mc["dUdy"], mc["dVdy"], mc["dTdy"]
            # adjoint convection (lnse_adj_eq.rs:21-92):
            # + U.grad(u*) - (u* dUdx + v* dVdx + T* dTdx) etc.
            cx = (
                U * gphys(sp_u, velx, (1, 0))
                + V * gphys(sp_u, velx, (0, 1))
                - us * dUdx - vs * dVdx - ts * dTdx
            )
            cy = (
                U * gphys(sp_v, vely, (1, 0))
                + V * gphys(sp_v, vely, (0, 1))
                - us * dUdy - vs * dVdy - ts * dTdy
            )
            ct = U * gphys(sp_t, temp, (1, 0)) + V * gphys(sp_t, temp, (0, 1))
            if nonlinear:
                # history contributions (nonlin_adj_eq.rs:21-125)
                uh, vh, th = history
                Uh = sp_f.backward_ortho(uh)
                Vh = sp_f.backward_ortho(vh)
                cx = cx + (
                    Uh * gphys(sp_u, velx, (1, 0))
                    + Vh * gphys(sp_u, velx, (0, 1))
                    - us * sp_f.backward_ortho(sp_f.gradient(uh, (1, 0), scale))
                    - vs * sp_f.backward_ortho(sp_f.gradient(vh, (1, 0), scale))
                    - ts * sp_f.backward_ortho(sp_f.gradient(th, (1, 0), scale))
                )
                cy = cy + (
                    Uh * gphys(sp_v, vely, (1, 0))
                    + Vh * gphys(sp_v, vely, (0, 1))
                    - us * sp_f.backward_ortho(sp_f.gradient(uh, (0, 1), scale))
                    - vs * sp_f.backward_ortho(sp_f.gradient(vh, (0, 1), scale))
                    - ts * sp_f.backward_ortho(sp_f.gradient(th, (0, 1), scale))
                )
                ct = ct + Uh * gphys(sp_t, temp, (1, 0)) + Vh * gphys(sp_t, temp, (0, 1))
            conv_x, conv_y, conv_t = conv(cx), conv(cy), conv(ct)

            rhs = sp_u.to_ortho(velx)
            rhs = rhs - dt * sp_p.gradient(pres, (1, 0), scale)
            rhs = rhs + dt * conv_x
            velx_n = sol_u.solve(rhs)

            rhs = sp_v.to_ortho(vely)
            rhs = rhs - dt * sp_p.gradient(pres, (0, 1), scale)
            rhs = rhs + dt * conv_y
            vely_n = sol_v.solve(rhs)

            div = sp_u.gradient(velx_n, (1, 0), scale) + sp_v.gradient(
                vely_n, (0, 1), scale
            )
            pseu_n = sol_p.solve(div)
            pseu_n = sp_q.pin_zero_mode(pseu_n)
            if proj_grad is not None:
                gx0, gx1, gy0, gy1 = proj_grad
                pax = pseu_n.ndim - 2
                velx_n = velx_n - gx1.apply(gx0.apply(pseu_n, pax), pax + 1) / scale[0]
                vely_n = vely_n - gy1.apply(gy0.apply(pseu_n, pax), pax + 1) / scale[1]
            else:
                velx_n = velx_n - sp_q.gradient(pseu_n, (1, 0), scale, into=sp_u)
                vely_n = vely_n - sp_q.gradient(pseu_n, (0, 1), scale, into=sp_v)
            pres_n = pres - nu * div + sp_q.to_ortho(pseu_n) / dt

            rhs = sp_t.to_ortho(temp)
            rhs = rhs + dt * conv_t
            rhs = rhs + dt * uyhat  # adjoint buoyancy
            temp_n = sol_t.solve(rhs)

            return NavierState(temp_n, velx_n, vely_n, pres_n, pseu_n)

        return step

    # -- compiled entry points -------------------------------------------------

    # dt-baked artifacts (campaign rung cache) include the adjoint entries
    _DT_ARTIFACTS = ("_adj_n", "_adj_consts") + CampaignModelBase._DT_ARTIFACTS

    def _dt_changed(self, dt: float) -> None:
        """Propagate a campaign dt change into the embedded Navier2D (whose
        implicit solvers the linearized step shares) — its own rung cache
        bounds the rebuild cost."""
        self.navier.set_dt(dt)

    def _compile_entry_points_impl(self) -> None:
        """The campaign entry points (hoisted ``_step_cc``/``_obs_cc``,
        chunked scans, sentinels — CampaignModelBase) plus the lnse-specific
        ADJOINT loop entries of the linearized model.  Overrides the IMPL
        hook (not the timed wrapper), so the per-kind compile attribution
        covers the adjoint-loop hoist+jit too."""
        super()._compile_entry_points_impl()
        if self.NONLINEAR:
            return
        nav = self.navier
        example = self._state_example()
        adj = self._make_adjoint_step()
        adj_cc, adj_consts = nav._hoist(lambda s: adj(s), example)
        self._adj_consts = adj_consts

        def adj_n(consts, state, n: int):
            return jax.lax.scan(
                lambda c, _: (adj_cc(consts, c), None), state, None, length=n
            )[0]

        adj_n_jit = jax.jit(adj_n, static_argnames=("n",))
        self._adj_n = lambda s, n: adj_n_jit(self._adj_consts, s, n=n)

    # -- Integrate protocol ----------------------------------------------------
    # update/update_n/update_n_pending, sentinels, set_dt, observable
    # futures and exit/exit_future come from CampaignModelBase

    def update_direct(self) -> None:
        self.update()

    def _sync_navier(self) -> None:
        self.navier.state = NavierState(*self.state)
        self.navier.time = self.time
        self.navier._obs_cache = None

    def eval_nu(self) -> float:
        """DNS-vocabulary Nu of the perturbation state (legacy IO paths);
        the campaign observables are the perturbation energies."""
        self._sync_navier()
        return self.navier.get_observables()[0]

    def callback(self) -> None:
        from ..utils import navier_io

        self._sync_navier()
        self.navier.write_intervall = self.write_intervall
        self.navier.statistics = self.statistics
        navier_io.callback(self.navier)

    # -- field access ----------------------------------------------------------

    def init_random(self, amp: float, seed: int = 0) -> None:
        self.navier.init_random(amp, seed)
        self.state = NavierState(*self.navier.state)
        self._obs_cache = None

    def set_velocity(self, amp: float, m: float, n: float) -> None:
        """Seed one velocity eigenmode shape (the eigenmode-sweep IC)."""
        self._sync_navier()
        self.navier.set_velocity(amp, m, n)
        self.state = NavierState(*self.navier.state)
        self._obs_cache = None

    def set_temperature(self, amp: float, m: float, n: float) -> None:
        self._sync_navier()
        self.navier.set_temperature(amp, m, n)
        self.state = NavierState(*self.navier.state)
        self._obs_cache = None

    def set_field(self, name: str, values) -> None:
        self._sync_navier()
        self.navier.set_field(name, values)
        self.state = NavierState(*self.navier.state)
        self._obs_cache = None

    def get_field(self, name: str):
        self._sync_navier()
        return self.navier.get_field(name)

    def write(self, filename: str) -> None:
        self._sync_navier()
        self.navier.write(filename)

    def read(self, filename: str) -> None:
        from ..utils import checkpoint

        if checkpoint.is_sharded_checkpoint(filename):
            # topology-elastic manifest restore targets THIS model's
            # snapshot surface (state/... names), not the embedded DNS's
            checkpoint.read_sharded_snapshot(self, filename)
            return
        self.navier.read(filename)
        self.state = NavierState(*self.navier.state)
        self.time = self.navier.time
        self._obs_cache = None

    # -- energy / gradient machinery -------------------------------------------

    def _phys(self, state: NavierState):
        nav = self.navier
        return (
            nav.velx_space.backward(state.velx),
            nav.vely_space.backward(state.vely),
            nav.temp_space.backward(state.temp),
        )

    def energy(self, beta1: float, beta2: float, target: MeanFields | None = None):
        """l2_norm of the current (optionally target-shifted) state."""
        u, v, t = self._phys(self.state)
        if target is not None:
            tu, tv, tt = target.physical()
            u, v, t = u - tu, v - tv, t - tt
        return float(l2_norm(u, u, v, v, t, t, beta1, beta2))

    def _zero_state(self) -> NavierState:
        return NavierState(
            temp=jnp.zeros_like(self.state.temp),
            velx=jnp.zeros_like(self.state.velx),
            vely=jnp.zeros_like(self.state.vely),
            pres=jnp.zeros_like(self.state.pres),
            pseu=jnp.zeros_like(self.state.pseu),
        )

    def _adjoint_ic(self, state, beta1, beta2, target):
        """Terminal condition of the adjoint loop: fields scaled by the norm
        weights (minus target) with pressure kept (lnse_adj_grad.rs:155-168)."""
        nav = self.navier
        velx, vely, temp = state.velx, state.vely, state.temp
        if target is not None:
            velx = velx - nav.velx_space.from_ortho(target.velx)
            vely = vely - nav.vely_space.from_ortho(target.vely)
            temp = temp - nav.temp_space.from_ortho(target.temp)
        return state._replace(
            velx=velx * beta1, vely=vely * beta1, temp=temp * beta2
        )

    def grad_adjoint(
        self,
        max_time: float,
        save_intervall: float | None = None,
        beta1: float = 0.5,
        beta2: float = 0.5,
        target: MeanFields | None = None,
        outfile: str | None = None,
    ):
        """Hand-adjoint gradient of the final energy w.r.t. the initial
        condition (lnse_adj_grad.rs:105-205).

        Returns ``(fun_val, (grad_u, grad_v, grad_t))`` with gradients as
        physical-space numpy arrays.  MAXIMIZE flips the sign.
        """
        del save_intervall  # device loop; intermediate snapshots not written
        n = max(1, round(max_time / self.dt))
        self.update_n(n)
        fun_val = self.energy(beta1, beta2, target)

        with self.navier._scope():
            self.state = self._adjoint_ic(self.state, beta1, beta2, target)
            from ..utils.jit import run_scanned

            self.state = run_scanned(self._adj_n, self.state, n)
        self.reset_time()

        fac = 1.0 if MAXIMIZE else -1.0
        u, v, t = self._phys(self.state)
        grads = (fac * np.asarray(u), fac * np.asarray(v), fac * np.asarray(t))
        if outfile:
            self._write_grad(outfile, grads)
        return fun_val, grads

    def _write_grad(self, filename, grads):
        import os

        import h5py

        from ..field import grid_deltas
        from ..utils.checkpoint import write_field

        nav = self.navier
        os.makedirs(os.path.dirname(filename) or ".", exist_ok=True)
        xs, dxs = (
            [b.points * s for b, s in zip(nav.field_space.bases, self.scale)],
            [
                grid_deltas(b.points, b.is_periodic) * s
                for b, s in zip(nav.field_space.bases, self.scale)
            ],
        )
        names = ("ux", "uy", "temp")
        spaces = (nav.velx_space, nav.vely_space, nav.temp_space)
        with h5py.File(filename, "a") as h5:
            for name, space, g in zip(names, spaces, grads):
                vhat = space.forward(jnp.asarray(g, dtype=config.real_dtype()))
                write_field(h5, name, space, vhat, xs, dxs)

    # -- exact discrete gradient via JAX autodiff ------------------------------

    def _objective_fn(self, n: int, beta1, beta2, target: MeanFields | None):
        """J(u0, v0, T0 physical) = energy after n forward steps."""
        nav = self.navier
        step = self._make_step()
        if target is not None:
            tu, tv, tt = target.physical()

        def objective(u0, v0, t0):
            state = self._zero_state()._replace(
                velx=nav.velx_space.forward(u0),
                vely=nav.vely_space.forward(v0),
                temp=nav.temp_space.forward(t0),
            )
            ckpt_step = jax.checkpoint(step)
            state = jax.lax.scan(
                lambda c, _: (ckpt_step(c), None), state, None, length=n
            )[0]
            u, v, t = self._phys(state)
            if target is not None:
                u, v, t = u - tu, v - tv, t - tt
            return l2_norm(u, u, v, v, t, t, beta1, beta2)

        return objective

    def grad_autodiff(
        self,
        max_time: float,
        beta1: float = 0.5,
        beta2: float = 0.5,
        target: MeanFields | None = None,
    ):
        """Exact gradient of the discrete objective w.r.t. the physical
        initial condition, by reverse-mode autodiff through the scanned
        forward loop (``jax.checkpoint`` bounds the memory).  The TPU-native
        answer to the reference's continuous hand-adjoint — exact to
        roundoff instead of O(30%).

        Starts from the CURRENT state (like grad_adjoint); does not advance
        the model.  MAXIMIZE flips the sign to match grad_adjoint's
        descent/ascent convention.
        """
        n = max(1, round(max_time / self.dt))
        u0, v0, t0 = self._phys(self.state)
        objective = self._objective_fn(n, beta1, beta2, target)
        with self.navier._scope():
            val, grads = jax.jit(jax.value_and_grad(objective, argnums=(0, 1, 2)))(
                u0, v0, t0
            )
        # grad_adjoint returns the descent direction -dJ/du0 under
        # MAXIMIZE=False (+dJ/du0 under MAXIMIZE); mirror that convention
        fac = 1.0 if MAXIMIZE else -1.0
        return float(val), tuple(fac * np.asarray(g) for g in grads)

    def grad_fd(
        self,
        max_time: float,
        beta1: float = 0.5,
        beta2: float = 0.5,
        eps: float = 1e-5,
        batch: int = 64,
    ):
        """Finite-difference gradient (lnse_fd_grad.rs:32-58): perturb every
        physical grid point of every field.  The reference integrates one
        perturbation at a time; here perturbations run vmapped in batches —
        the same O(N^2) work as a single batched scan per chunk.

        Returns physical-space FD gradients (forward differences, matching
        the reference's (E(x+eps)-E(x))/eps).
        """
        n = max(1, round(max_time / self.dt))
        u0, v0, t0 = (np.asarray(a) for a in self._phys(self.state))
        objective = self._objective_fn(n, beta1, beta2, None)
        obj_jit = jax.jit(objective)
        e_base = float(obj_jit(u0, v0, t0))

        obj_batch = jax.jit(jax.vmap(objective, in_axes=(0, 0, 0)))
        grads = []
        for idx, base in enumerate((u0, v0, t0)):
            flat = base.size
            grad = np.zeros(flat)
            for start in range(0, flat, batch):
                count = min(batch, flat - start)
                pert = np.tile(base.ravel(), (count, 1))
                pert[np.arange(count), start + np.arange(count)] += eps
                pert = pert.reshape((count,) + base.shape)
                args = [
                    np.broadcast_to(a, (count,) + a.shape) for a in (u0, v0, t0)
                ]
                args[idx] = pert
                energies = np.asarray(obj_batch(*args))
                grad[start : start + count] = (energies - e_base) / eps
            grads.append(grad.reshape(base.shape))
        return tuple(grads)


class Navier2DNonLin(Navier2DLnse):
    """Full nonlinear equations as a perturbation about the base state
    (nonlin.rs:23-57); the forward loop records the trajectory history the
    adjoint convection terms need (nonlin_adj_grad.rs:186-190)."""

    NONLINEAR = True

    def _compile_entry_points_impl(self) -> None:
        # impl-hook override (see the linear model's note): the nonlinear
        # trajectory-recording entries stay inside the timed attribution
        super()._compile_entry_points_impl()
        nav = self.navier
        example = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), NavierState(*nav.state)
        )
        step = self._make_step()
        sp_u, sp_v, sp_t = nav.velx_space, nav.vely_space, nav.temp_space

        def fwd_with_history(state):
            new = step(state)
            # ortho-space history of the *new* fields (the reference stores
            # the post-step state, nonlin_adj_grad.rs:66-76)
            hist = (
                sp_u.to_ortho(new.velx),
                sp_v.to_ortho(new.vely),
                sp_t.to_ortho(new.temp),
            )
            return new, hist

        fwd_cc, fwd_consts = nav._hoist(fwd_with_history, example)
        adj = self._make_adjoint_step()
        sds = jax.ShapeDtypeStruct(
            nav.field_space.shape_spectral, nav.field_space.spectral_dtype()
        )
        hist_sds = (sds, sds, sds)
        adj_cc, adj_consts = nav._hoist(
            lambda s, h: adj(s, history=h), example, hist_sds
        )
        self._fwd_consts = fwd_consts
        self._nl_adj_consts = adj_consts

        def fwd_scan(consts, state, n: int):
            return jax.lax.scan(
                lambda c, _: fwd_cc(consts, c), state, None, length=n
            )

        def adj_scan(consts, state, history):
            return jax.lax.scan(
                lambda c, h: (adj_cc(consts, c, h), None),
                state,
                jax.tree.map(lambda x: x[::-1], history),
            )[0]

        self._fwd_scan = jax.jit(fwd_scan, static_argnames=("n",))
        self._adj_scan = jax.jit(adj_scan)

    def grad_adjoint(
        self,
        max_time: float,
        save_intervall: float | None = None,
        beta1: float = 0.5,
        beta2: float = 0.5,
        target: MeanFields | None = None,
        outfile: str | None = None,
    ):
        """Nonlinear variant: the adjoint loop consumes the recorded forward
        trajectory backward (nonlin_adj_grad.rs:120-223)."""
        del save_intervall
        n = max(1, round(max_time / self.dt))
        with self.navier._scope():
            self.state, history = self._fwd_scan(self._fwd_consts, self.state, n=n)
        self.time += n * self.dt
        fun_val = self.energy(beta1, beta2, target)

        with self.navier._scope():
            self.state = self._adjoint_ic(self.state, beta1, beta2, target)
            self.state = self._adj_scan(self._nl_adj_consts, self.state, history)
        self.reset_time()

        fac = 1.0 if MAXIMIZE else -1.0
        u, v, t = self._phys(self.state)
        grads = (fac * np.asarray(u), fac * np.asarray(v), fac * np.asarray(t))
        if outfile:
            self._write_grad(outfile, grads)
        return fun_val, grads
