"""Navier2DAdjoint — steady-state finder by adjoint descent, TPU-native.

Rebuild of /root/reference/src/navier_stokes/steady_adjoint{,_eq,_io}.rs
(Farazmand 2016 JFM 795; Reiter et al. 2022): each ``update()`` performs

1. one forward Navier-Stokes step at the fixed inner timestep
   ``DT_NAVIER = 1e-3`` (steady_adjoint.rs:64, 541-581),
2. the residual ``res_q = (q_new - q_old) / DT_NAVIER`` per evolved variable,
3. a smoothing-norm solve ``q_adj = -(I - 0.1*D2)^-1 res_q`` (the
   ``WEIGHT_LAPLACIAN`` Hholtz norm, steady_adjoint.rs:62, 316-338), and
4. one explicit adjoint-descent step of pseudo-time ``dt`` that drives the
   *physical* fields toward the steady state using the adjoint convection
   terms, explicit adjoint diffusion and a pressure projection
   (steady_adjoint_eq.rs:355-437).

Converged when the mean smoothed-residual norm drops below
``RES_TOL = 1e-7`` (steady_adjoint.rs:624-638).

Functional JAX design: the whole iteration (forward step + residual + norm
solves + adjoint step) is ONE jitted function scanned on device via
``update_n``; residual norms ride along in the carry so the convergence test
costs no extra dispatch.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import config
from ..field import norm_l2
from ..solver import Hholtz
from ..utils.integrate import Integrate
from .campaign import CampaignModelBase
from .navier import Navier2D, NavierState

RES_TOL = 1e-7  # steady_adjoint.rs:60
WEIGHT_LAPLACIAN = 1e-1  # steady_adjoint.rs:62
DT_NAVIER = 1e-3  # steady_adjoint.rs:64


class AdjointState(NamedTuple):
    """Physical fields + adjoint pressure + last residual norms."""

    temp: jax.Array
    velx: jax.Array
    vely: jax.Array
    pres: jax.Array
    pseu: jax.Array
    pres_adj: jax.Array
    res_norms: jax.Array  # (3,): |velx_adj|, |vely_adj|, |temp_adj|


class Navier2DAdjoint(CampaignModelBase, Integrate):
    """Steady-state RBC solver; same parameter vocabulary as Navier2D.

    A full campaign model (models/campaign.py): the whole adjoint-descent
    iteration is hoisted into ``_step_cc``, so steady-state finds run as
    vmapped K-member ensembles under ``ResilientRunner`` — and since the
    residual norms ride the state, RESIDUAL CONVERGENCE is compiled into
    the scanned chunk's early-exit (:meth:`_scan_ok`): a member whose mean
    smoothed residual drops below ``res_tol`` freezes at its converged
    state mid-chunk, costing no further GEMMs — the residual-based exit
    sentinel of the steady-find workload (workloads/steady.py)."""

    MODEL_KIND = "adjoint"
    observable_names = ("res", "res_u", "res_t", "div")

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
        mesh=None,
        res_tol: float = RES_TOL,
    ):
        with self._build_span(nx, ny, mesh):
            self._build(nx, ny, ra, pr, dt, aspect, bc, periodic, mesh, res_tol)

    def _build(self, nx, ny, ra, pr, dt, aspect, bc, periodic, mesh, res_tol) -> None:
        # the embedded forward model is built at DT_NAVIER so its implicit
        # Helmholtz solvers carry the correct dt (steady_adjoint.rs:286-300)
        self.navier = Navier2D(nx, ny, ra, pr, DT_NAVIER, aspect, bc, periodic, mesh=mesh)
        self.mesh = mesh
        self.dt = dt
        self.res_tol = float(res_tol)
        self.params = self.navier.params
        self.scale = self.navier.scale
        self.write_intervall: float | None = None
        self.statistics = None
        self._init_campaign()

        nav = self.navier
        sx2, sy2 = self.scale[0] ** 2, self.scale[1] ** 2
        c_norm = (WEIGHT_LAPLACIAN / sx2, WEIGHT_LAPLACIAN / sy2)
        # smoothing norms (1 - 0.1*D2)^-1 per variable space
        # (steady_adjoint.rs:316-338); velx/vely share a space -> one solver
        self._norm_vel = Hholtz(nav.velx_space, c_norm)
        self._norm_temp = Hholtz(nav.temp_space, c_norm)

        self._compile_entry_points()
        with nav._scope():
            zero = nav._place(nav.pres_space.ndarray_spectral())
            self.state = AdjointState(
                temp=nav.state.temp,
                velx=nav.state.velx,
                vely=nav.state.vely,
                pres=nav.state.pres,
                pseu=nav.state.pseu,
                pres_adj=zero,
                res_norms=jnp.full((3,), np.inf, dtype=config.real_dtype()),
            )

    @property
    def nx(self) -> int:
        return self.navier.nx

    @property
    def ny(self) -> int:
        return self.navier.ny

    def _compat_fields(self) -> tuple:
        # self.dt is the DESCENT pseudo-step (the inner forward model runs
        # at the fixed DT_NAVIER); res_tol is compiled into the chunk's
        # convergence early-exit, so it buckets too
        return (
            int(self.navier.nx),
            int(self.navier.ny),
            float(self.params["ra"]),
            float(self.params["pr"]),
            float(self.dt),
            float(self.scale[0]),
            str(self.navier.bc),
            bool(self.navier.periodic),
            # variant slot: only a NON-default tolerance buckets separately
            # (so registry-built default models match kind-prefixed request
            # keys, which cannot express a custom tolerance)
            () if self.res_tol == RES_TOL else (("res_tol", float(self.res_tol)),),
        )

    def _gspmd_split_sep_fallback(self) -> bool:
        # like Navier2DLnse: no manual shard_map counterpart for the
        # adjoint step yet — shared eager-guard policy
        return self.navier._split_sep_eager_unless_forced()

    def restart_fill(self, name: str, like):
        """Gathered-restore fill: residual norms restart at +inf (unknown —
        zero would read as instantly converged), everything else at zero."""
        if name == "res_norms":
            return jnp.full_like(like, np.inf)
        return jnp.zeros_like(like)

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

    def _scan_ok(self, state):
        """Continue while finite AND unconverged: the mean smoothed-residual
        convergence test (steady_adjoint.rs:624-638) compiled into the
        scanned chunk — a converged state freezes mid-chunk (identity
        steps), which is the workload's exit sentinel."""
        finite = jnp.isfinite(jnp.sum(state.temp))
        return finite & (jnp.mean(state.res_norms) >= self.res_tol)

    def _scan_done_ok(self, state):
        """A member that stopped advancing CONVERGED (rather than died)
        when its residual is finite and below tolerance."""
        res = jnp.mean(state.res_norms)
        return jnp.isfinite(res) & (res < self.res_tol)

    def _scan_commit_ok(self, state):
        """Commit any FINITE candidate: convergence stops the member (via
        ``_scan_ok``) but the converged state is the answer and must land
        in the carry before the freeze."""
        return jnp.isfinite(jnp.sum(state.temp))

    # -- construction ---------------------------------------------------------

    @classmethod
    def new_confined(cls, nx, ny, ra, pr, dt, aspect, bc, mesh=None) -> "Navier2DAdjoint":
        return cls(nx, ny, ra, pr, dt, aspect, bc, periodic=False, mesh=mesh)

    @classmethod
    def new_periodic(cls, nx, ny, ra, pr, dt, aspect, bc, mesh=None) -> "Navier2DAdjoint":
        return cls(nx, ny, ra, pr, dt, aspect, bc, periodic=True, mesh=mesh)

    @classmethod
    def from_config(cls, cfg, mesh=None) -> "Navier2DAdjoint":
        """Construct from a :class:`~rustpde_mpi_tpu.config.NavierConfig`
        (same field handling as Navier2D.from_config)."""
        model = cls(*cfg.ctor_args(), periodic=cfg.periodic, mesh=mesh)
        if cfg.init_random_amp:
            model.init_random(cfg.init_random_amp)
        model.write_intervall = cfg.write_intervall
        model.navier.params.update(cfg.params)
        return model

    # -- the adjoint iteration ------------------------------------------------

    def _make_step(self, with_sentinels: bool = False):
        nav = self.navier
        dt = self.dt
        scale = nav.scale
        nu, ka = nav.params["nu"], nav.params["ka"]
        inv_dx, inv_dy = nav._inv_dx, nav._inv_dy
        w0s, w1s = nav._w0, nav._w1
        sp_t, sp_u, sp_v = nav.temp_space, nav.velx_space, nav.vely_space
        sp_p, sp_q, sp_f = nav.pres_space, nav.pseu_space, nav.field_space
        from ..bases import fused_projection_gradient

        _gx = fused_projection_gradient(sp_u, sp_q, (1, 0))
        _gy = fused_projection_gradient(sp_v, sp_q, (0, 1))
        proj_grad = (*_gx, *_gy) if _gx and _gy else None
        mask = nav._dealias
        tb_ortho = nav.tempbc_ortho
        nav_step = nav._make_step()
        sol_p = nav.solver_pres
        norm_u, norm_t = self._norm_vel, self._norm_temp

        def grad_phys(space, vhat, deriv):
            return sp_f.backward_ortho(space.gradient(vhat, deriv, scale))

        def lap(space, vhat):
            return space.gradient(vhat, (2, 0), scale) + space.gradient(vhat, (0, 2), scale)

        def step(state: AdjointState) -> AdjointState:
            ns_old = NavierState(state.temp, state.velx, state.vely, state.pres, state.pseu)

            # *** forward Navier step at DT_NAVIER (steady_adjoint.rs:541-567)
            ns = nav_step(ns_old)

            # *** residual + smoothing norm (steady_adjoint.rs:568-581)
            res_u = (sp_u.to_ortho(ns.velx) - sp_u.to_ortho(ns_old.velx)) / DT_NAVIER
            res_v = (sp_v.to_ortho(ns.vely) - sp_v.to_ortho(ns_old.vely)) / DT_NAVIER
            res_t = (sp_t.to_ortho(ns.temp) - sp_t.to_ortho(ns_old.temp)) / DT_NAVIER
            velx_adj = -norm_u.solve(res_u)
            vely_adj = -norm_u.solve(res_v)
            temp_adj = -norm_t.solve(res_t)
            res_norms = jnp.stack(
                [norm_l2(velx_adj), norm_l2(vely_adj), norm_l2(temp_adj)]
            )

            # *** adjoint descent step (steady_adjoint.rs:584-605)
            ux = sp_u.backward(ns.velx)
            uy = sp_v.backward(ns.vely)

            if with_sentinels:
                # advective CFL of the embedded FORWARD step (the stiff,
                # explicitly-convected part of the iteration) + flow KE
                cfl = DT_NAVIER * jnp.max(
                    jnp.abs(ux) * inv_dx[:, None] + jnp.abs(uy) * inv_dy[None, :]
                )
                ke = 0.5 * jnp.sum((ux**2 + uy**2) * w0s[:, None] * w1s[None, :])
            ta = sp_t.backward(temp_adj)

            # physical gradients of the evolved + adjoint fields
            that_full = sp_t.to_ortho(ns.temp) + tb_ortho

            def conv(total):
                if any(sp_f.sep):
                    return sp_f.forward_dealiased(total)
                return sp_f.forward(total) * mask

            # x-momentum adjoint convection (steady_adjoint_eq.rs:258-289):
            # U.grad(u*_x) + U.(d_x u*) - theta* d_x(T + Tbc)
            conv_x = conv(
                ux * grad_phys(sp_u, velx_adj, (1, 0))
                + uy * grad_phys(sp_u, velx_adj, (0, 1))
                + ux * grad_phys(sp_u, velx_adj, (1, 0))
                + uy * grad_phys(sp_v, vely_adj, (1, 0))
                - ta * grad_phys(sp_f, that_full, (1, 0))
            )
            # y-momentum (steady_adjoint_eq.rs:292-321)
            conv_y = conv(
                ux * grad_phys(sp_v, vely_adj, (1, 0))
                + uy * grad_phys(sp_v, vely_adj, (0, 1))
                + ux * grad_phys(sp_u, velx_adj, (0, 1))
                + uy * grad_phys(sp_v, vely_adj, (0, 1))
                - ta * grad_phys(sp_f, that_full, (0, 1))
            )
            # temperature (steady_adjoint_eq.rs:324-341): U.grad(theta*)
            conv_t = conv(
                ux * grad_phys(sp_t, temp_adj, (1, 0))
                + uy * grad_phys(sp_t, temp_adj, (0, 1))
            )

            # explicit updates (steady_adjoint_eq.rs:355-437): the *physical*
            # fields descend along the adjoint direction
            rhs = sp_u.to_ortho(ns.velx)
            rhs = rhs - dt * sp_p.gradient(state.pres_adj, (1, 0), scale)
            rhs = rhs + dt * conv_x
            rhs = rhs + dt * nu * lap(sp_u, velx_adj)
            velx_n = sp_u.from_ortho(rhs)

            rhs = sp_v.to_ortho(ns.vely)
            rhs = rhs - dt * sp_p.gradient(state.pres_adj, (0, 1), scale)
            rhs = rhs + dt * conv_y
            rhs = rhs + dt * nu * lap(sp_v, vely_adj)
            vely_n = sp_v.from_ortho(rhs)

            # projection (steady_adjoint.rs:597-600)
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
            # adjoint pressure update: pres_adj += pseu/dt
            # (steady_adjoint_eq.rs:226-236)
            pres_adj_n = state.pres_adj + sp_q.to_ortho(pseu_n) / dt

            # temperature descent (steady_adjoint_eq.rs:408-437)
            rhs = sp_t.to_ortho(ns.temp)
            rhs = rhs + dt * conv_t
            rhs = rhs + dt * sp_v.to_ortho(vely_adj)  # adjoint buoyancy
            rhs = rhs + dt * ka * lap(sp_t, temp_adj)
            temp_n = sp_t.from_ortho(rhs)

            state_n = AdjointState(
                temp_n, velx_n, vely_n, ns.pres, pseu_n, pres_adj_n, res_norms
            )
            if with_sentinels:
                return state_n, (cfl, ke, norm_l2(div))
            return state_n

        return step

    def _make_observables(self):
        """Fused convergence diagnostics ``(res, res_u, res_t, |div|)``:
        the mean smoothed-residual norm (the convergence measure,
        steady_adjoint.rs:633) plus its velocity/temperature components —
        all riding the state carry, so the per-chunk convergence check
        costs no extra dispatch — and the velocity divergence norm as the
        NaN detector."""
        nav = self.navier
        sp_u, sp_v = nav.velx_space, nav.vely_space
        scale = nav.scale

        def observables(state: AdjointState):
            res = jnp.mean(state.res_norms)
            div = norm_l2(
                sp_u.gradient(state.velx, (1, 0), scale)
                + sp_v.gradient(state.vely, (0, 1), scale)
            )
            return res, state.res_norms[0], state.res_norms[2], div

        return observables

    def _state_example(self):
        nav = self.navier
        rdt = config.real_dtype()

        def sds(space):
            return jax.ShapeDtypeStruct(space.shape_spectral, space.spectral_dtype())

        return AdjointState(
            temp=sds(nav.temp_space),
            velx=sds(nav.velx_space),
            vely=sds(nav.vely_space),
            pres=sds(nav.pres_space),
            pseu=sds(nav.pseu_space),
            pres_adj=sds(nav.pres_space),
            res_norms=jax.ShapeDtypeStruct((3,), rdt),
        )

    # -- field access (delegates keep the Navier2D vocabulary) ---------------

    def _sync_navier(self) -> None:
        """Mirror the physical fields into the embedded model (for
        observables/IO, which read navier.state)."""
        self.navier.state = NavierState(
            self.state.temp, self.state.velx, self.state.vely,
            self.state.pres, self.state.pseu,
        )
        self.navier.time = self.time
        self.navier._obs_cache = None

    def _pull_navier(self) -> None:
        """Adopt navier.state (after set_field/read) into the adjoint state
        (residual norms reset — they describe the previous iterate)."""
        ns = self.navier.state
        self.state = self.state._replace(
            temp=ns.temp, velx=ns.velx, vely=ns.vely, pres=ns.pres, pseu=ns.pseu,
            res_norms=jnp.full((3,), np.inf, dtype=config.real_dtype()),
        )
        self._obs_cache = None

    def set_velocity(self, amp, m, n):
        self.navier.set_velocity(amp, m, n)
        self._pull_navier()

    def set_temperature(self, amp, m, n):
        self.navier.set_temperature(amp, m, n)
        self._pull_navier()

    def init_random(self, amp, seed: int = 0):
        self.navier.init_random(amp, seed)
        self._pull_navier()

    def get_field(self, name):
        self._sync_navier()
        return self.navier.get_field(name)

    def read(self, filename: str) -> None:
        from ..utils import checkpoint

        if checkpoint.is_sharded_checkpoint(filename):
            # manifest restore targets THIS model's snapshot surface (every
            # AdjointState leaf incl. pres_adj/res_norms — bit-exact resume)
            checkpoint.read_sharded_snapshot(self, filename)
            return
        self.navier.read(filename)
        self._pull_navier()
        self.time = self.navier.time

    def write(self, filename: str) -> None:
        self._sync_navier()
        self.navier.write(filename)

    # -- Integrate protocol ---------------------------------------------------
    # update/update_n/update_n_pending, sentinels, set_dt (rung-cached; the
    # descent dt only lives in the compiled step — _rebuild_dt_artifacts is
    # the base recompile) and observable futures come from CampaignModelBase

    def norm_residual(self) -> tuple[float, float, float]:
        """Smoothed-residual norms (|u*_x|, |u*_y|, |theta*|)
        (steady_adjoint_eq.rs:44-51)."""
        return tuple(float(v) for v in np.asarray(self.state.res_norms))

    def residual(self) -> float:
        """Mean residual — the convergence measure (steady_adjoint.rs:633)."""
        return float(np.mean(np.asarray(self.state.res_norms)))

    def eval_nu(self):
        """Nusselt of the current iterate (DNS vocabulary, via the embedded
        model; the campaign observables are the residual norms)."""
        self._sync_navier()
        return self.navier.get_observables()[0]

    def eval_nuvol(self):
        self._sync_navier()
        return self.navier.get_observables()[1]

    def eval_re(self):
        self._sync_navier()
        return self.navier.get_observables()[2]

    def callback(self) -> None:
        from ..utils import navier_io

        self._sync_navier()
        # propagate the adjoint's own IO throttles onto the embedded model
        # navier_io reads (the reference passes self.write_intervall,
        # steady_adjoint.rs:621)
        self.navier.write_intervall = self.write_intervall
        self.navier.statistics = self.statistics
        res = self.residual()
        navier_io.callback(
            self.navier,
            flowname=f"data/adjoint{self.time:08.2f}.h5",
            io_name="data/info_adjoint.txt",
            extra=f"res = {res:5.3e}",
        )

    def exit(self) -> bool:
        """NaN divergence (or a latched sentinel catch), or converged: mean
        residual < ``res_tol`` (steady_adjoint.rs:624-638).  A converged
        exit is a SUCCESS — :meth:`state_healthy` (the checkpoint guard)
        deliberately keeps reporting True for it."""
        if super().exit():
            return True
        if self.residual() < self.res_tol:
            print("Steady state converged!")
            return True
        return False
