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
from ..telemetry import tracing as _tr
from ..utils.integrate import Integrate
from ..utils.jit import scan_buckets
from .campaign import _LAYER, CampaignModelBase
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
        with self._build_span(nx, ny, mesh):
            self._build(nx, ny, ra, pr, dt, aspect, bc, periodic, mean, mesh)

    def _build(self, nx, ny, ra, pr, dt, aspect, bc, periodic, mean, mesh) -> None:
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
        self._host_seams = None
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

    def _make_projection(self):
        """``(velx, vely, pseu) -> (velx, vely)`` less the gradient of the
        pressure increment: the projection both steps end their momentum
        half with."""
        nav = self.navier
        scale = self.scale
        sp_u, sp_v, sp_q = nav.velx_space, nav.vely_space, nav.pseu_space
        from ..bases import fused_projection_gradient

        _gx = fused_projection_gradient(sp_u, sp_q, (1, 0))
        _gy = fused_projection_gradient(sp_v, sp_q, (0, 1))
        proj_grad = (*_gx, *_gy) if _gx and _gy else None

        def project(velx, vely, pseu):
            if proj_grad is not None:
                gx0, gx1, gy0, gy1 = proj_grad
                pax = pseu.ndim - 2
                velx = velx - gx1.apply(gx0.apply(pseu, pax), pax + 1) / scale[0]
                vely = vely - gy1.apply(gy0.apply(pseu, pax), pax + 1) / scale[1]
            else:
                velx = velx - sp_q.gradient(pseu, (1, 0), scale, into=sp_u)
                vely = vely - sp_q.gradient(pseu, (0, 1), scale, into=sp_v)
            return velx, vely

        return project

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
        project = self._make_projection()
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
        conv_mm_x = conv_mm_y = conv_mm_t = None
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

        # Navier2D._make_step's stage names on every device operation the
        # step lowers to (instruction metadata only), so a device trace of a
        # sweep reads by stage: scripts/stage_times.py
        stage = jax.named_scope

        def step(state: NavierState) -> NavierState:
            temp, velx, vely, pres, pseu = state
            with stage("buoyancy"):
                that = sp_t.to_ortho(temp)
                if nonlinear:
                    that = that + that_mean  # buoyancy incl. base state
            with stage("synthesis"):
                ux = sp_u.backward(velx)
                uy = sp_v.backward(vely)

            if with_sentinels:
                # advective CFL of the TOTAL velocity (mean + perturbation)
                # + perturbation KE, from arrays the step needs anyway
                with stage("sentinels"):
                    cfl = dt * jnp.max(
                        jnp.abs(mc["U"] + ux) * inv_dx[:, None]
                        + jnp.abs(mc["V"] + uy) * inv_dy[None, :]
                    )
                    ke = 0.5 * jnp.sum(
                        (ux**2 + uy**2) * w0s[:, None] * w1s[None, :]
                    )

            @stage("convection")  # named under each caller's stage
            def convection(space, vhat, dMdx, dMdy, mean_mean):
                """Linearized convection ``u.grad(M) + U.grad(f)`` of one
                field ``f`` about its base field ``M`` (lnse_eq.rs:59-110);
                the perturbation form adds ``u.grad(f)`` and the base
                state's own ``U.grad(M)`` (nonlin_eq.rs:59-120)."""
                df_dx = gphys(space, vhat, (1, 0))
                df_dy = gphys(space, vhat, (0, 1))
                total = ux * dMdx + uy * dMdy + mc["U"] * df_dx + mc["V"] * df_dy
                if nonlinear:
                    total = total + ux * df_dx + uy * df_dy
                out = conv(total)
                return out + mean_mean if nonlinear else out

            with stage("momentum_x"):
                rhs = sp_u.to_ortho(velx)
                rhs = rhs - dt * sp_p.gradient(pres, (1, 0), scale)
                rhs = rhs - dt * convection(sp_u, velx, mc["dUdx"], mc["dUdy"], conv_mm_x)
                if nonlinear:
                    rhs = rhs + dt * nu * lap_u_m
                velx_n = sol_u.solve(rhs)

            with stage("momentum_y"):
                rhs = sp_v.to_ortho(vely)
                rhs = rhs - dt * sp_p.gradient(pres, (0, 1), scale)
                rhs = rhs + dt * that
                rhs = rhs - dt * convection(sp_v, vely, mc["dVdx"], mc["dVdy"], conv_mm_y)
                if nonlinear:
                    rhs = rhs + dt * nu * lap_v_m
                vely_n = sol_v.solve(rhs)

            with stage("divergence"):
                div = sp_u.gradient(velx_n, (1, 0), scale) + sp_v.gradient(
                    vely_n, (0, 1), scale
                )
            with stage("poisson"):
                pseu_n = sol_p.solve(div)
                pseu_n = sp_q.pin_zero_mode(pseu_n)
            with stage("projection"):
                velx_n, vely_n = project(velx_n, vely_n, pseu_n)
            with stage("pressure"):
                pres_n = pres - nu * div + sp_q.to_ortho(pseu_n) / dt

            with stage("temperature"):
                rhs = sp_t.to_ortho(temp)
                rhs = rhs - dt * convection(sp_t, temp, mc["dTdx"], mc["dTdy"], conv_mm_t)
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
        project = self._make_projection()
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

        stage = jax.named_scope  # metadata only, as in _make_step

        def step(state: NavierState, history=None) -> NavierState:
            temp, velx, vely, pres, pseu = state
            with stage("buoyancy"):
                uyhat = sp_v.to_ortho(vely)  # adjoint buoyancy source (pre-update)
            with stage("synthesis"):
                us = sp_u.backward(velx)
                vs = sp_v.backward(vely)
                ts = sp_t.backward(temp)

            U, V = mc["U"], mc["V"]
            if nonlinear:
                # the forward trajectory's fields and their derivatives at
                # this step, in physical space (nonlin_adj_eq.rs:21-125)
                with stage("history_terms"):
                    uh, vh, th = history
                    Uh = sp_f.backward_ortho(uh)
                    Vh = sp_f.backward_ortho(vh)
                    hist_dx = [gphys(sp_f, h, (1, 0)) for h in (uh, vh, th)]
                    hist_dy = [gphys(sp_f, h, (0, 1)) for h in (uh, vh, th)]

            @stage("convection")  # named under each caller's stage
            def convection(space, vhat, mean_d, hist_d):
                """Adjoint convection of one field (lnse_adj_eq.rs:21-92):
                ``+ U.grad(f*) - (u* dU + v* dV + T* dT)`` along the field's
                own direction; the perturbation form adds the same with the
                stored trajectory in the base state's place.  ``mean_d`` and
                ``hist_d`` are ``None`` for the temperature, which no base
                gradient couples back."""
                df_dx = gphys(space, vhat, (1, 0))
                df_dy = gphys(space, vhat, (0, 1))
                total = U * df_dx + V * df_dy
                if mean_d is not None:
                    total = total - us * mean_d[0] - vs * mean_d[1] - ts * mean_d[2]
                if nonlinear:
                    extra = Uh * df_dx + Vh * df_dy
                    if hist_d is not None:
                        extra = extra - us * hist_d[0] - vs * hist_d[1] - ts * hist_d[2]
                    total = total + extra
                return conv(total)

            with stage("momentum_x"):
                rhs = sp_u.to_ortho(velx)
                rhs = rhs - dt * sp_p.gradient(pres, (1, 0), scale)
                rhs = rhs + dt * convection(
                    sp_u, velx, (mc["dUdx"], mc["dVdx"], mc["dTdx"]),
                    hist_dx if nonlinear else None,
                )
                velx_n = sol_u.solve(rhs)

            with stage("momentum_y"):
                rhs = sp_v.to_ortho(vely)
                rhs = rhs - dt * sp_p.gradient(pres, (0, 1), scale)
                rhs = rhs + dt * convection(
                    sp_v, vely, (mc["dUdy"], mc["dVdy"], mc["dTdy"]),
                    hist_dy if nonlinear else None,
                )
                vely_n = sol_v.solve(rhs)

            with stage("divergence"):
                div = sp_u.gradient(velx_n, (1, 0), scale) + sp_v.gradient(
                    vely_n, (0, 1), scale
                )
            with stage("poisson"):
                pseu_n = sol_p.solve(div)
                pseu_n = sp_q.pin_zero_mode(pseu_n)
            with stage("projection"):
                velx_n, vely_n = project(velx_n, vely_n, pseu_n)
            with stage("pressure"):
                pres_n = pres - nu * div + sp_q.to_ortho(pseu_n) / dt

            with stage("temperature"):
                rhs = sp_t.to_ortho(temp)
                rhs = rhs + dt * convection(sp_t, temp, None, None)
                rhs = rhs + dt * uyhat  # adjoint buoyancy
                temp_n = sol_t.solve(rhs)

            return NavierState(temp_n, velx_n, vely_n, pres_n, pseu_n)

        return step

    # -- compiled entry points -------------------------------------------------

    # dt-baked artifacts (campaign rung cache) include the sweeps' entries
    _DT_ARTIFACTS = (
        "_adj_n", "_adj_n_jit", "_adj_consts", "_fwd_n", "_fwd_n_jit", "_fwd_consts",
    ) + CampaignModelBase._DT_ARTIFACTS

    def _dt_changed(self, dt: float) -> None:
        """Propagate a campaign dt change into the embedded Navier2D (whose
        implicit solvers the linearized step shares) — its own rung cache
        bounds the rebuild cost."""
        self.navier.set_dt(dt)

    def _compile_entry_points(self) -> None:
        """The compile seam, with the sweeps' constants counted beside the
        chunks' for the spans' ``unplaced_args``."""
        from ..parallel.mesh import unplaced

        super()._compile_entry_points()
        self._unplaced_consts += unplaced(
            (self._adj_consts, self._fwd_consts), getattr(self, "mesh", None)
        )

    def _compile_entry_points_impl(self) -> None:
        """The campaign entry points (hoisted ``_step_cc``/``_obs_cc``,
        chunked scans, sentinels — CampaignModelBase) plus the two sweeps of
        ``grad_adjoint``, through the same hoisting seam.  Overrides the IMPL
        hook (not the timed wrapper), so the per-kind compile attribution
        covers the sweeps' hoist+jit too."""
        super()._compile_entry_points_impl()
        self._compile_sweep_entry_points(self._state_example())

    def _compile_sweep_entry_points(self, example) -> None:
        """``_adj_n(state, history, n)``: n adjoint steps.  The linear
        model's forward sweep is ``update_n`` and keeps no history."""
        adj = self._make_adjoint_step()
        adj_cc, self._adj_consts = self._hoist(lambda s: adj(s), example)
        self._fwd_n, self._fwd_consts = None, None

        def adj_n(consts, state, n: int):
            return jax.lax.scan(
                lambda c, _: (adj_cc(consts, c), None), state, None, length=n
            )[0]

        # the jit objects are retained, as ``_step_n_jit`` is: a stage table
        # lowers them to read the scopes (scripts/stage_times.py)
        self._fwd_n_jit = None
        self._adj_n_jit = adj_n_jit = jax.jit(adj_n, static_argnames=("n",))
        self._adj_n = lambda s, history, n: adj_n_jit(self._adj_consts, s, n=n)

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

    def _host_seam(self, name: str):
        """The iteration's small device programs between the sweeps, each one
        hoisted jit (one dispatch where the eager form made dozens):
        ``physical(state)``, ``energy(state, target, beta1, beta2)``,
        ``terminal(state, target, beta1, beta2)`` and ``forward(velx, vely,
        temp)``; ``target`` is the target's three ortho-space fields.  None
        depends on dt; built at first use."""
        if self._host_seams is None:
            nav = self.navier
            sp_t, sp_u, sp_v, sp_f = nav.temp_space, nav.velx_space, nav.vely_space, nav.field_space
            rdt = config.real_dtype()
            state = self._state_example()
            vhat = jax.ShapeDtypeStruct(sp_f.shape_spectral, sp_f.spectral_dtype())
            phys = jax.ShapeDtypeStruct(sp_f.shape_physical, rdt)
            beta = jax.ShapeDtypeStruct((), rdt)

            def energy(st, target, beta1, beta2):
                u, v, t = self._phys(st)
                tu, tv, tt = (sp_f.backward_ortho(x) for x in target)
                u, v, t = u - tu, v - tv, t - tt
                return l2_norm(u, u, v, v, t, t, beta1, beta2)

            def terminal(st, target, beta1, beta2):
                return st._replace(
                    velx=(st.velx - sp_u.from_ortho(target[0])) * beta1,
                    vely=(st.vely - sp_v.from_ortho(target[1])) * beta1,
                    temp=(st.temp - sp_t.from_ortho(target[2])) * beta2,
                )

            def forward(velx, vely, temp):
                return sp_u.forward(velx), sp_v.forward(vely), sp_t.forward(temp)

            def hoisted(fn, *example):
                cc, consts = self._hoist(fn, *example)
                jitted = jax.jit(cc)
                return lambda *args: jitted(consts, *args)

            self._host_seams = {
                "physical": hoisted(self._phys, state),
                "energy": hoisted(energy, state, (vhat,) * 3, beta, beta),
                "terminal": hoisted(terminal, state, (vhat,) * 3, beta, beta),
                "forward": hoisted(forward, phys, phys, phys),
            }
        return self._host_seams[name]

    def _target_fields(self, target: MeanFields | None):
        """The target's ortho-space fields; zeros without a target."""
        if target is None:
            return (self.navier.field_space.ndarray_spectral(),) * 3
        return target.velx, target.vely, target.temp

    def physical(self):
        """Physical values ``(velx, vely, temp)`` of the state, one dispatch."""
        with self.navier._scope():
            return self._host_seam("physical")(self.state)

    def set_fields(self, velx, vely, temp) -> None:
        """``set_field`` of the three prognostic fields from physical values,
        as one dispatch (pres and pseu untouched)."""
        rdt = config.real_dtype()
        nav = self.navier
        fields = ("velx", "vely", "temp")
        with _tr.span("model.set_field", layer=_LAYER, fields=fields), nav._scope():
            vhats = self._host_seam("forward")(*(jnp.asarray(a, dtype=rdt) for a in (velx, vely, temp)))
            self.state = self.state._replace(
                **{k: nav._place(v) for k, v in zip(fields, vhats)}
            )
        self._obs_cache = None

    def energy(self, beta1: float, beta2: float, target: MeanFields | None = None):
        """l2_norm of the current (optionally target-shifted) state."""
        with self.navier._scope():
            return float(
                self._host_seam("energy")(self.state, self._target_fields(target), beta1, beta2)
            )

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
        return self._host_seam("terminal")(state, self._target_fields(target), beta1, beta2)

    def _launch_sweep(self, step_k, carry, lengths):
        """``carry = step_k(carry, k)`` for each program length of a sweep,
        each under a ``model.launch`` span as ``update_n``'s buckets are (a
        length dispatched for the first time leaves its ``lowerings`` and
        ``backend_compiles`` on that span); returns the carry and the
        launches made."""
        for k in lengths:
            with _tr.span("model.launch", layer=_LAYER, steps=int(k), aot=False):
                carry = step_k(carry, int(k))
        return carry, len(lengths)

    def _forward_sweep(self, n: int):
        """n forward steps from ``self.state``; returns ``(history,
        launches)``.  The linear adjoint reads no trajectory, so the sweep is
        ``update_n`` itself and the history is empty."""
        self.update_n(n)
        return (), len(scan_buckets(n))

    def _adjoint_sweep(self, n: int, history) -> int:
        """n adjoint steps from ``self.state`` (the terminal condition);
        returns the launches.  The linear adjoint carries nothing but its
        state, so it runs in ``run_scanned``'s power-of-two buckets, as
        ``update_n`` does."""
        self.state, launches = self._launch_sweep(
            lambda s, k: self._adj_n(s, history, k), self.state, scan_buckets(n)
        )
        return launches

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
        condition (lnse_adj_grad.rs:105-205; the nonlinear variant's adjoint
        loop consumes the recorded forward trajectory backward,
        nonlin_adj_grad.rs:120-223).

        Returns ``(fun_val, (grad_u, grad_v, grad_t))`` with gradients as
        physical-space numpy arrays.  MAXIMIZE flips the sign.

        Both sweeps go through the hoisting compile seam and their programs
        are kept with the model, so a horizon seen before builds nothing.
        Spans (model step): ``lnse.grad_adjoint`` (``steps``, ``launches``,
        ``history_bytes`` of the stacked trajectory) > ``lnse.forward_sweep``
        (to the moment J is on the host) and ``lnse.adjoint_sweep`` (terminal
        condition to the gradient on the host) > ``model.launch``, which
        carries ``backend_compiles`` where a sweep program was dispatched for
        the first time.
        """
        del save_intervall  # device loop; intermediate snapshots not written
        n = max(1, round(max_time / self.dt))
        with _tr.span("lnse.grad_adjoint", layer=_LAYER, steps=2 * n) as whole:
            with _tr.span("lnse.forward_sweep", layer=_LAYER, steps=n):
                history, launches = self._forward_sweep(n)
                fun_val = self.energy(beta1, beta2, target)
            with _tr.span("lnse.adjoint_sweep", layer=_LAYER, steps=n):
                with self.navier._scope():
                    self.state = self._adjoint_ic(self.state, beta1, beta2, target)
                    more = self._adjoint_sweep(n, history)
                self.reset_time()
                fac = 1.0 if MAXIMIZE else -1.0
                grads = tuple(fac * a for a in jax.device_get(self.physical()))
            whole.set(
                launches=launches + more,
                history_bytes=sum(
                    leaf.nbytes for leaf in jax.tree.leaves(history)
                ),
            )
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
    adjoint convection terms need (nonlin_adj_grad.rs:186-190).

    Each sweep is ONE program per horizon, kept in the model's jit cache: the
    forward scan stacks the trajectory ``(n, nx, ny)`` per field and the
    adjoint scan reads it from its end.  ``update_n``'s power-of-two buckets
    were tried for them (one stacked chunk a bucket, never joined) and cost
    more than they save: a sweep's step lowers in 1-2 s of host time per
    program at first use, so the five buckets of a 2500-step horizon made a
    model's first ``grad_adjoint`` 22 s where the compile cache was warm, and
    the upstream's campaign visits five horizons, fewer than the dozen bucket
    lengths they break into (PERF.md section 6, PR 30)."""

    NONLINEAR = True

    def _compile_sweep_entry_points(self, example) -> None:
        """``_fwd_n(state, n) -> (state, history)`` and
        ``_adj_n(state, history, n)``, ``history`` the three stacked
        ``(n, nx, ny)`` arrays of the trajectory."""
        nav = self.navier
        step = self._make_step()
        sp_u, sp_v, sp_t = nav.velx_space, nav.vely_space, nav.temp_space

        def fwd_with_history(state):
            new = step(state)
            # ortho-space history of the *new* fields (the reference stores
            # the post-step state, nonlin_adj_grad.rs:66-76)
            with jax.named_scope("history"):
                hist = (
                    sp_u.to_ortho(new.velx),
                    sp_v.to_ortho(new.vely),
                    sp_t.to_ortho(new.temp),
                )
            return new, hist

        fwd_cc, self._fwd_consts = self._hoist(fwd_with_history, example)
        adj = self._make_adjoint_step()
        sds = jax.ShapeDtypeStruct(
            nav.field_space.shape_spectral, nav.field_space.spectral_dtype()
        )
        adj_cc, self._adj_consts = self._hoist(
            lambda s, h: adj(s, history=h), example, (sds, sds, sds)
        )

        def fwd_n(consts, state, n: int):
            return jax.lax.scan(
                lambda c, _: fwd_cc(consts, c), state, None, length=n
            )

        def adj_n(consts, state, history):
            return jax.lax.scan(
                lambda c, h: (adj_cc(consts, c, h), None),
                state,
                jax.tree.map(lambda x: x[::-1], history),
            )[0]

        self._fwd_n_jit = fwd_n_jit = jax.jit(fwd_n, static_argnames=("n",))
        self._adj_n_jit = adj_n_jit = jax.jit(adj_n)
        self._fwd_n = lambda s, n: fwd_n_jit(self._fwd_consts, s, n=n)
        self._adj_n = lambda s, history, n: adj_n_jit(self._adj_consts, s, history)

    def _forward_sweep(self, n: int):
        with self.navier._scope():
            (self.state, history), launches = self._launch_sweep(
                self._fwd_n, self.state, [n]
            )
        self.time += n * self.dt
        return history, launches

    def _adjoint_sweep(self, n: int, history) -> int:
        self.state, launches = self._launch_sweep(
            lambda s, k: self._adj_n(s, history, k), self.state, [n]
        )
        return launches
