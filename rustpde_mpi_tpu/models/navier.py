"""Navier2D — 2-D Boussinesq Rayleigh–Bénard DNS, TPU-native.

Rebuild of the reference's physics layer
(/root/reference/src/navier_stokes/{navier,navier_eq}.rs) as a *functional*
JAX model: the simulation state is an immutable pytree of spectral
coefficients, one time step is a pure jitted function, and many steps run per
host round-trip through ``lax.scan``.  One model class covers both the
fully-confined (Chebyshev x Chebyshev) and horizontally-periodic
(Fourier x Chebyshev) configurations — the reference's serial/MPI module
duplication is intentionally not reproduced; sharding is layered on top in
``parallel/`` without touching the physics.

Numerical scheme (identical to the reference, navier_eq.rs):

* implicit Euler diffusion via ADI Helmholtz solves,
* explicit convection with 2/3-rule dealiasing,
* pressure projection: Poisson solve for a pseudo-pressure, velocity
  correction, pressure update ``pres += -nu*div + pseu/dt``,
* inhomogeneous BCs through constant lift fields (boundary_conditions.py).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import config
from ..bases import (
    Space2,
    cheb_dirichlet,
    cheb_dirichlet_neumann,
    cheb_neumann,
    chebyshev,
    fourier_r2c,
)
from ..field import average_weights, norm_l2
from ..solver import HholtzAdi, Poisson
from ..telemetry import tracing as _tr
from ..utils.integrate import Integrate
from . import boundary_conditions as bcs
from . import functions as fns
from .campaign import _LAYER, CampaignModelBase


class NavierState(NamedTuple):
    """Spectral-coefficient pytree threaded through the jitted step."""

    temp: jax.Array
    velx: jax.Array
    vely: jax.Array
    pres: jax.Array
    pseu: jax.Array


class NavierScalarState(NamedTuple):
    """NavierState plus a passive scalar (the ``passive_scalar`` scenario
    modifier): ``scal`` is advected by the flow and diffused at the scalar
    diffusivity, with the temperature BC lift as its boundary forcing — so a
    scalar released equal to the temperature with matched diffusivity stays
    identically equal (the scenario's exact validation case)."""

    temp: jax.Array
    velx: jax.Array
    vely: jax.Array
    pres: jax.Array
    pseu: jax.Array
    scal: jax.Array


def scenario_signature(scenario) -> tuple:
    """Canonical compat-key signature of a scenario-modifier config (any
    object carrying ``coriolis`` / ``passive_scalar`` / ``scalar_kappa``,
    e.g. :class:`~rustpde_mpi_tpu.workloads.modifiers.ScenarioConfig`, or a
    plain dict as carried by a :class:`~rustpde_mpi_tpu.serve.SimRequest`).
    Modifier terms are baked into the compiled step, so they MUST flow
    through ``compat_key`` — an empty/default scenario signs as ``()``,
    equal to no scenario at all."""
    if scenario is None:
        return ()
    get = (
        scenario.get
        if isinstance(scenario, dict)
        else lambda k, d=None: getattr(scenario, k, d)
    )
    items = []
    f = float(get("coriolis", 0.0) or 0.0)
    if f:
        items.append(("coriolis", f))
    if get("passive_scalar", False):
        kappa = get("scalar_kappa", None)
        if kappa is not None and float(kappa) <= 0.0:
            # 0.0 would collide with the thermal-default sentinel below
            # (and a non-diffusive implicit solve is not supported)
            raise ValueError(
                f"scalar_kappa must be positive (got {kappa}); omit it for "
                "the thermal diffusivity"
            )
        items.append(
            ("passive_scalar", float(kappa) if kappa is not None else 0.0)
        )
    return tuple(items)


def brinkman_factors(model, mask, value=None, eta: float | None = None):
    """The pointwise implicit-Brinkman penalization factors
    ``(fac, temp_add)`` for one obstacle on ``model``'s grid — THE single
    implementation shared by :meth:`Navier2D.set_solid` (which bakes them
    into the step) and the vmapped geometry sweep
    (workloads/modifiers.py, which feeds them as per-member runtime
    inputs); the sweep's bit-match-solo guarantee rests on this sharing.

    ``fac = 1 / (1 + (dt/eta) mask)``; ``temp_add`` relaxes the
    temperature toward ``value`` minus the BC lift (the temp state
    excludes the lift field)."""
    rdt = config.real_dtype()
    mask = np.asarray(mask, dtype=np.float64)
    if value is None:
        value = np.zeros_like(mask)
    if eta is None:
        eta = model.dt / 10.0
    a = (model.dt / float(eta)) * mask
    fac = 1.0 / (1.0 + a)
    # temp state excludes the BC lift field: target = value - tempbc
    sp = model.field_space
    with model._scope():
        tempbc_phys = np.asarray(sp.backward_ortho(model.tempbc_ortho))
    temp_add = a * (value - tempbc_phys) * fac
    return jnp.asarray(fac, dtype=rdt), jnp.asarray(temp_add, dtype=rdt)


class Navier2D(CampaignModelBase, Integrate):
    """2-D Rayleigh–Bénard convection solver.

    Construct via :meth:`new_confined` (Chebyshev x Chebyshev) or
    :meth:`new_periodic` (Fourier x Chebyshev); parameter vocabulary matches
    the reference (nx, ny, ra, pr, dt, aspect, bc in {"rbc", "hc"}).

    The campaign-model machinery (chunked scans, sentinels, dt rung cache,
    observable futures, snapshot surface — everything the ensemble engine,
    governor, checkpoints and serve scheduler ride on) lives in
    :class:`~rustpde_mpi_tpu.models.campaign.CampaignModelBase`; this class
    supplies the physics: spaces, solvers, the step, the observables, and
    the config-carried scenario modifiers (rotating-frame Coriolis term,
    passive-scalar transport)."""

    MODEL_KIND = "dns"

    @property
    def observable_names(self) -> tuple:
        """The fused-observables vocabulary.  A passive-scalar scenario
        appends ``sherwood`` (the scalar-transfer analog of the plate-flux
        Nusselt number) AFTER the conventional four — index 3 stays the
        NaN-detector |div| every consumer keys on."""
        base = ("nu", "nuvol", "re", "div")
        if self._scalar_active():
            return base + ("sherwood",)
        return base

    def __init__(
        self,
        nx: int,
        ny: int,
        ra: float,
        pr: float,
        dt: float,
        aspect: float,
        bc: str,
        periodic: bool,
        mesh=None,
        scenario=None,
    ):
        with self._build_span(nx, ny, mesh):
            self._build(nx, ny, ra, pr, dt, aspect, bc, periodic, mesh, scenario)

    def _build(self, nx, ny, ra, pr, dt, aspect, bc, periodic, mesh, scenario) -> None:
        if bc not in ("rbc", "hc"):
            raise ValueError(f"boundary condition type {bc!r} not recognized")
        # pencil-sharding mesh (None = single device); one model serves both —
        # the reference's navier_stokes vs navier_stokes_mpi duplication is
        # deliberately not reproduced (SURVEY.md S1 note)
        self.mesh = mesh
        self.nx, self.ny = nx, ny
        self.dt = dt
        self.periodic = periodic
        self.bc = bc
        self.scale = (float(aspect), 1.0)
        nu = fns.get_nu(ra, pr, self.scale[1] * 2.0)
        ka = fns.get_ka(ra, pr, self.scale[1] * 2.0)
        self.params = {"ra": ra, "pr": pr, "nu": nu, "ka": ka}
        self.write_intervall: float | None = None
        self.statistics = None
        self._init_campaign()  # obs cache, sentinels, dt rung cache
        self._solid = None  # (penalization factors) set via set_solid()
        # config-carried scenario step modifiers (rotating-frame Coriolis,
        # passive scalar — see workloads/modifiers.ScenarioConfig); baked
        # into the compiled step, signed into compat_key
        self._scenario = scenario
        # diagnostics history appended by the IO callback — the map the
        # reference allocates but never writes (navier.rs:81)
        self.diagnostics: dict[str, list[float]] = {}

        x_base = fourier_r2c if periodic else cheb_dirichlet
        x_full = fourier_r2c if periodic else chebyshev
        x_neumann = fourier_r2c if periodic else cheb_neumann

        # spaces per variable (/root/reference/src/navier_stokes/navier.rs:235-256,356-376);
        # velx/vely share one space object (identical bases -> shared operator
        # constants on device)
        self.velx_space = Space2(x_base(nx), cheb_dirichlet(ny))
        self.vely_space = self.velx_space
        temp_ybase = cheb_dirichlet(ny) if bc == "rbc" else cheb_dirichlet_neumann(ny)
        self.temp_space = Space2(x_neumann(nx), temp_ybase)
        self.pres_space = Space2(x_full(nx), chebyshev(ny))
        self.pseu_space = Space2(x_neumann(nx), cheb_neumann(ny))
        # scratch space for convection/observables (full ortho bases)
        self.field_space = Space2(x_full(nx), chebyshev(ny))

        # grid (unscaled master coords; physical coords = coords * scale)
        self.x = [b.points * s for b, s in zip(self.field_space.bases, self.scale)]
        xs, ys = (b.points for b in self.field_space.bases)
        # average weights dx/L as in the reference's average_axis
        # (/root/reference/src/field/average.rs:26-35), with this repo's
        # full-period normalization for periodic axes (field.average_weights)
        w0 = average_weights(xs, self.field_space.base_x.is_periodic)
        w1 = average_weights(ys, False)
        rdt = config.real_dtype()
        self._w0 = jnp.asarray(w0, dtype=rdt)
        self._w1 = jnp.asarray(w1, dtype=rdt)
        # per-point inverse grid spacing (physical, scaled) for the pointwise
        # advective CFL sentinel dt*max(|ux|/dx + |uy|/dy): cell widths from
        # the same midpoint rule the averages use — near a Chebyshev wall the
        # spacing is O(1/N^2) but the no-slip velocity vanishes linearly, so
        # the pointwise ratio self-limits to the local shear rate
        from ..field import grid_deltas

        dx0 = grid_deltas(xs, self.field_space.base_x.is_periodic) * self.scale[0]
        dy0 = grid_deltas(ys, False) * self.scale[1]
        self._inv_dx = jnp.asarray(1.0 / dx0, dtype=rdt)
        self._inv_dy = jnp.asarray(1.0 / dy0, dtype=rdt)

        # the poisoned layout's mesh program is parallel/decomp.py's regions
        # (or the eager fallback), not GSPMD's to place: its spaces' spectral
        # operators state no layout of their own there, and its transforms
        # and solves (built below) keep the x-pencil rest those regions take
        if self._split_sep_poisoned():
            for space in (self.velx_space, self.temp_space, self.pres_space,
                          self.pseu_space, self.field_space):
                space.states_layout = False

        # implicit solvers (/root/reference/src/navier_stokes/navier.rs:263-275)
        sx2, sy2 = self.scale[0] ** 2, self.scale[1] ** 2
        self.solver_velx = HholtzAdi(self.velx_space, (dt * nu / sx2, dt * nu / sy2))
        self.solver_vely = self.solver_velx  # identical operator, shared factors
        self.solver_temp = HholtzAdi(self.temp_space, (dt * ka / sx2, dt * ka / sy2))
        self.solver_pres = Poisson(self.pseu_space, (1.0 / sx2, 1.0 / sy2))
        # passive-scalar solver (scenario modifier): the scalar shares the
        # temperature's composite space and BC lift; at matched diffusivity
        # it shares the temperature solver's factorizations outright
        self.solver_scal = self._build_scalar_solver()

        # dealiasing mask over the scratch spectral shape (split-aware)
        self._dealias = jnp.asarray(self.field_space.dealias_mask(), dtype=rdt)

        # fused convection-chain impls keyed by id(space) — FusedConv
        # (RUSTPDE_CONV_KERNEL=pallas, ops/pallas_conv.py) or ShardedConv
        # (manual-partitioned split-sep path, parallel/decomp.py); None
        # keeps the unfused dense chain (the measured default)
        self._conv_impl = self._build_conv_kernels()
        # fused projection-gradient operators for the velocity correction
        # (confined only; the periodic x-axis gradient is diagonal logic):
        # velx -= P_u (D S_q) pseu / sx  per axis — one cross-space matrix
        # per axis instead of gradient + to_ortho + 2 projection applies
        from ..bases import fused_projection_gradient

        gx = fused_projection_gradient(self.velx_space, self.pseu_space, (1, 0))
        gy = fused_projection_gradient(self.vely_space, self.pseu_space, (0, 1))
        self._proj_grad = (*gx, *gy) if gx and gy else None

        # boundary-condition lift fields as device constants: set from
        # physical values through the scratch space's transforms, op by op
        with _tr.span("model.set_field", layer=_LAYER, fields=("tempbc",)), self._scope():
            self._build_bc_fields(xs, ys)

        # fused implicit-half stage kernels (RUSTPDE_STEP_KERNEL=pallas,
        # ops/pallas_step.py): Helmholtz/Poisson solves + divergence +
        # projection as VMEM-resident Pallas stages; None keeps the dense
        # solver chain (the measured default).  Built AFTER the BC fields
        # (the buoyancy/diffusion lift constants fold into the stages).
        self._step_impl = self._build_step_kernels()

        # jitted step + observables
        # jit with closure-converted constants: the dense transform / solver
        # matrices are hoisted out of the traced program and passed as
        # device-resident runtime arguments instead of being embedded in the
        # HLO — at 2049^2 the embedded-constant program is hundreds of MB to
        # parse, hash and cache, while the hoisted program is a few hundred
        # KB for any grid size.
        self._compile_entry_points()

        with self._scope():
            self.state = self._state_cls()(
                **{
                    name: self._place(space.ndarray_spectral())
                    for name, space in self._state_fields()
                }
            )

    # one-time-warning latch for the GSPMD split-sep fallback (class-level:
    # one warning per process, not per model)
    _warned_split_sep_fallback = False

    def _build_conv_kernels(self):
        """Fused convection-chain implementations the step's ``conv()``
        routes through by space identity (None: the unfused dense chain).

        * no mesh + ``RUSTPDE_CONV_KERNEL=pallas``: the VMEM-tiled Pallas
          kernel (ops/pallas_conv.py; interpreted on the CPU, native on a
          TPU or a typed refusal at build);
        * active mesh on the split-sep periodic layout (default mode
          "manual"): the manually-partitioned shard_map region
          (parallel/decomp.ShardedConv) — explicit per-pencil GEMMs +
          transposes instead of the GSPMD propagation that miscompiles the
          fused step there;
        * any other meshed model keeps the dense chain: its convection
          GEMMs partition cleanly under GSPMD."""
        from ..ops import pallas_conv

        if self.mesh is not None:
            if self._split_sep_mode() == "manual":
                from ..parallel.decomp import (
                    ShardedConv,
                    ShardedPoisson,
                    ShardedSynthesis,
                )

                specs = {}
                for space in (self.velx_space, self.temp_space):
                    if id(space) not in specs:
                        specs[id(space)] = ShardedConv(
                            space, self.field_space, self.scale, self.mesh
                        )
                # the convection-velocity syntheses ride their own region,
                # and the pressure-Poisson fast-diag solve — the stage the
                # miscompile bisects to (see ShardedPoisson) — MUST be
                # manual for the fused step to compile correctly
                self._manual_synth = {
                    id(self.velx_space): ShardedSynthesis(
                        self.velx_space, None, self.mesh
                    )
                }
                self._manual_poisson = ShardedPoisson(
                    self.solver_pres, self.pseu_space, self.mesh
                )
                return specs
            self._manual_synth = None
            self._manual_poisson = None
            return None
        self._manual_synth = None
        self._manual_poisson = None
        if pallas_conv.conv_kernel_choice() != "pallas":
            return None
        specs = pallas_conv.build_model_convs(self)
        for fc in specs.values():
            fc.check_compiles()  # typed refusal at build on a TPU
        return specs

    def _exchanges_per_step(self) -> tuple:
        """``(exchanges, bytes one device sends)`` of one step's hand-placed
        pencil transposes, reckoned from the manual regions' block shapes
        (parallel/decomp.Sharded*): three convection chains (four with the
        passive scalar), the two convection-velocity syntheses and the
        pressure-Poisson solve.  Where no region is manual, the flips the
        step states, as every model counts them (the compiler places their
        all-to-alls)."""
        if getattr(self, "_manual_poisson", None) is None:
            return super()._exchanges_per_step()
        from ..parallel.decomp import sent_bytes

        conv_u = self._conv_impl[id(self.velx_space)].exchanges
        conv_t = self._conv_impl[id(self.temp_space)].exchanges
        synth = self._manual_synth[id(self.velx_space)].exchanges
        blocks = (
            2 * conv_u
            + (2 if self._scalar_active() else 1) * conv_t
            + 2 * synth
            + self._manual_poisson.exchanges
        )
        itemsize = np.dtype(config.real_dtype()).itemsize
        return len(blocks), sent_bytes(blocks, self.mesh.size, itemsize)

    def _build_step_kernels(self):
        """Fused implicit-half stage kernels the step routes through
        (None: the dense solver chain).  Single-device only — meshed
        models keep the dense/manual-shard_map paths; the sharded fused
        stages ride the shard_map follow-up (ROADMAP)."""
        from ..ops import pallas_step

        if self.mesh is not None:
            return None
        if pallas_step.step_kernel_choice() != "pallas":
            return None
        stages = pallas_step.build_model_step(self)
        for stage in stages.values():
            stage.check_compiles()  # typed refusal at build on a TPU
        return stages

    def _split_sep_poisoned(self) -> bool:
        """The layout the upstream GSPMD bug miscompiles: split Re/Im
        Fourier x sep Chebyshev under an active mesh (see
        ``_gspmd_split_sep_fallback``)."""
        if self.mesh is None or not self.periodic:
            return False
        sp = self.temp_space
        return sp.bases[0].kind.is_split and any(sp.sep)

    def _split_sep_eager_unless_forced(self) -> bool:
        """Eager-guard policy for wrapper models (Navier2DLnse /
        Navier2DAdjoint) whose steps have no manual shard_map counterpart
        yet: per-stage eager whenever the poisoned layout is active, unless
        ``RUSTPDE_FORCE_FUSED_GSPMD=1`` pins the fused path — ONE shared
        helper so the two wrappers cannot drift when their manual regions
        eventually land."""
        if config.env_get("RUSTPDE_FORCE_FUSED_GSPMD") == "1":
            return False
        return self._split_sep_poisoned()

    def _split_sep_mode(self) -> str:
        """How a split-sep periodic model executes under an active mesh:

        * ``"fused"`` — the plain GSPMD-fused step (non-poisoned layouts;
          or ``RUSTPDE_FORCE_FUSED_GSPMD=1``, which keeps the pinned xfail
          tracking the upstream miscompile);
        * ``"manual"`` (default on the poisoned layout) — fused scanned
          step with the convection transforms in manually-partitioned
          shard_map regions (ShardedConv): correct AND compiled, retiring
          the per-stage eager fallback;
        * ``"eager"`` (``RUSTPDE_SPLIT_SEP_FALLBACK=eager``) — the old
          per-stage dispatch path, kept for triage A/Bs."""
        if config.env_get("RUSTPDE_FORCE_FUSED_GSPMD") == "1":
            return "fused"
        if not self._split_sep_poisoned():
            return "fused"
        mode = config.env_get("RUSTPDE_SPLIT_SEP_FALLBACK", "manual")
        if mode not in ("manual", "eager"):
            raise ValueError(
                f"RUSTPDE_SPLIT_SEP_FALLBACK must be 'manual' or 'eager', got {mode!r}"
            )
        return mode

    # -- scenario modifiers ---------------------------------------------------

    def _scn(self, key, default=None):
        """Scenario attribute lookup (dataclass or request-carried dict)."""
        scn = self._scenario
        if scn is None:
            return default
        if isinstance(scn, dict):
            return scn.get(key, default)
        return getattr(scn, key, default)

    def _coriolis(self) -> float:
        return float(self._scn("coriolis", 0.0) or 0.0)

    def _scalar_active(self) -> bool:
        return bool(self._scn("passive_scalar", False))

    def _scalar_kappa(self) -> float:
        """Scalar diffusivity (``None`` defaults to the thermal one — the
        matched-diffusivity configuration whose scalar mirrors the
        temperature; non-positive values are rejected, see
        :func:`scenario_signature`)."""
        kappa = self._scn("scalar_kappa", None)
        if kappa is None:
            return float(self.params["ka"])
        kappa = float(kappa)
        if kappa <= 0.0:
            raise ValueError(f"scalar_kappa must be positive, got {kappa}")
        return kappa

    def _build_scalar_solver(self):
        if not self._scalar_active():
            return None
        kc = self._scalar_kappa()
        if kc == float(self.params["ka"]):
            return self.solver_temp  # identical operator, shared factors
        sx2, sy2 = self.scale[0] ** 2, self.scale[1] ** 2
        return HholtzAdi(self.temp_space, (self.dt * kc / sx2, self.dt * kc / sy2))

    def _scan_ok(self, state):
        """The in-scan divergence detector.  A NaN in the FLOW infects temp
        within one step (buoyancy/convection), but the passive scalar is
        one-way coupled — a scal-only NaN would never reach temp — so the
        scalar leaf joins the finiteness probe when the scenario carries
        one (one extra reduction, scalar models only)."""
        probe = jnp.sum(state.temp)
        if self._scalar_active():
            probe = probe + jnp.sum(state.scal)
        return jnp.isfinite(probe)

    @property
    def scal_space(self):
        """The passive scalar rides the temperature's composite space."""
        return self.temp_space

    @property
    def scenario(self):
        return self._scenario

    def set_scenario(self, scenario) -> None:
        """Install (or clear, ``None``) the scenario step modifiers on a
        live model: the modifier terms are operator constants, so the entry
        points recompile and every dt rung is invalidated.  Toggling the
        passive scalar restructures the state pytree (the ``scal`` leaf is
        added zero-initialized / dropped); all other leaves are kept."""
        self._scenario = scenario
        self._dt_cache.clear()
        self.solver_scal = self._build_scalar_solver()
        # scenario terms (Coriolis cross-coupling, the scalar stage) are
        # baked into the fused stage kernels — rebuild alongside the solver
        self._step_impl = self._build_step_kernels()
        want_scal = self._scalar_active()
        have_scal = hasattr(self.state, "scal")
        if want_scal and not have_scal:
            with self._scope():
                self.state = NavierScalarState(
                    *self.state,
                    scal=self._place(self.temp_space.ndarray_spectral()),
                )
        elif not want_scal and have_scal:
            self.state = NavierState(*self.state[:5])
        self._compile_entry_points()
        self._obs_cache = None

    def _state_fields(self) -> list:
        """Ordered ``(leaf_name, space)`` of the state pytree (the scenario
        decides whether the scalar leaf exists)."""
        fields = [
            ("temp", self.temp_space),
            ("velx", self.velx_space),
            ("vely", self.vely_space),
            ("pres", self.pres_space),
            ("pseu", self.pseu_space),
        ]
        if self._scalar_active():
            fields.append(("scal", self.temp_space))
        return fields

    def _state_cls(self):
        return NavierScalarState if self._scalar_active() else NavierState

    def _state_example(self):
        return self._state_cls()(
            **{
                name: jax.ShapeDtypeStruct(
                    space.shape_spectral, space.spectral_dtype()
                )
                for name, space in self._state_fields()
            }
        )

    @property
    def snapshot_vars(self) -> tuple:
        """``(h5 var name, state attr)`` rows the gathered snapshot format
        carries — the checkpoint layer consults this so scenario-extended
        states round-trip (utils/checkpoint)."""
        base = (("ux", "velx"), ("uy", "vely"), ("temp", "temp"), ("pres", "pres"))
        if self._scalar_active():
            return base + (("scal", "scal"),)
        return base

    def _compile_eager_entry_points(self) -> None:
        """The campaign base's per-stage eager fallback, plus the one-time
        (per-process) warning naming the GSPMD miscompile it routes around."""
        if not Navier2D._warned_split_sep_fallback:
            import warnings

            warnings.warn(
                "the fused split-sep periodic step is miscompiled by "
                "GSPMD under an active mesh (xfailed in "
                "tests/test_parallel.py); falling back to per-stage "
                "eager execution — multichip periodic runs are slower "
                "but correct.  Set RUSTPDE_FORCE_FUSED_GSPMD=1 to force "
                "the fused path.",
                RuntimeWarning,
                stacklevel=2,
            )
            Navier2D._warned_split_sep_fallback = True
        super()._compile_eager_entry_points()

    def _gspmd_split_sep_fallback(self) -> bool:
        """True when the step must run the per-stage EAGER path.  GSPMD
        miscompiles the fused split-sep periodic step under an active mesh
        (container jax 0.4.37 regression — every stage matches serial to
        ~1e-17 jitted separately and the eager per-op sharded step is exact,
        but the fused program yields wrong vely/pres from step 1; pinned
        xfail in tests/test_parallel.py under RUSTPDE_FORCE_FUSED_GSPMD=1).
        The DEFAULT on that layout is no longer eager: the convection
        transforms run as manually-partitioned shard_map regions
        (``_split_sep_mode() == "manual"``, parallel/decomp.ShardedConv),
        which sidesteps the broken SPMD propagation by construction and
        keeps the fused scanned chunk — eager remains only as the
        ``RUSTPDE_SPLIT_SEP_FALLBACK=eager`` triage pin."""
        return self._split_sep_mode() == "eager"

    def _compat_fields(self) -> tuple:
        """Everything (beyond the kind prefix) baked into the model's
        operator constants — grid, physics parameters, dt (the implicit
        solvers factorize ``dt*nu``), geometry, BC family, and the scenario
        modifier signature.  Two requests with equal keys can share one
        compiled step jaxpr (and therefore one ensemble batch: the serve
        scheduler buckets by this key); anything differing forces a fresh
        model build + compile."""
        return (
            int(self.nx),
            int(self.ny),
            float(self.params["ra"]),
            float(self.params["pr"]),
            float(self.dt),
            float(self.scale[0]),
            str(self.bc),
            bool(self.periodic),
            scenario_signature(self._scenario),
        )

    # -- construction --------------------------------------------------------

    @classmethod
    def new_confined(cls, nx, ny, ra, pr, dt, aspect, bc, mesh=None) -> "Navier2D":
        """Chebyshev x Chebyshev (fully confined cell), with random IC as in
        the reference (/root/reference/src/navier_stokes/navier.rs:215-308)."""
        model = cls(nx, ny, ra, pr, dt, aspect, bc, periodic=False, mesh=mesh)
        model.init_random(0.1)
        return model

    @classmethod
    def new_periodic(cls, nx, ny, ra, pr, dt, aspect, bc, mesh=None) -> "Navier2D":
        """Fourier x Chebyshev (horizontally periodic)
        (/root/reference/src/navier_stokes/navier.rs:336-428)."""
        model = cls(nx, ny, ra, pr, dt, aspect, bc, periodic=True, mesh=mesh)
        model.init_random(0.1)
        return model

    @classmethod
    def from_config(cls, cfg, mesh=None) -> "Navier2D":
        """Construct from a :class:`~rustpde_mpi_tpu.config.NavierConfig`."""
        model = cls(
            *cfg.ctor_args(),
            periodic=cfg.periodic,
            mesh=mesh,
            scenario=getattr(cfg, "scenario", None),
        )
        if cfg.init_random_amp:
            model.init_random(cfg.init_random_amp)
        model.write_intervall = cfg.write_intervall
        model.params.update(cfg.params)
        if getattr(cfg, "stability", None) is not None:
            model.set_stability(cfg.stability)
        stats_cfg = getattr(cfg, "stats", None)
        if stats_cfg is None and config.env_get("RUSTPDE_STATS") == "1":
            stats_cfg = config.StatsConfig()
        if stats_cfg is not None:
            model.set_stats(stats_cfg)
        integ_cfg = getattr(cfg, "integrity", None)
        if integ_cfg is None and config.env_get("RUSTPDE_INTEGRITY") == "1":
            integ_cfg = config.IntegrityConfig()
        if integ_cfg is not None:
            model.set_integrity(integ_cfg)
        return model

    def _build_bc_fields(self, xs: np.ndarray, ys: np.ndarray) -> None:
        """Transform the BC lift profiles into ortho-space constants and
        precompute every derivative the step needs (the reference recomputes
        these each step from the stored lift field)."""
        sp = self.field_space
        scale = self.scale
        dt, ka = self.dt, self.params["ka"]
        if self.bc == "rbc":
            tempbc_v = bcs.bc_rbc_values(xs, ys)
        else:
            tempbc_v = bcs.bc_hc_values(xs, ys)
        rdt = config.real_dtype()
        that = sp.forward(jnp.asarray(tempbc_v, dtype=rdt))
        self.tempbc_ortho = that
        # physical gradients for the convection bc-contribution
        self._tempbc_dx = sp.backward_ortho(sp.gradient(that, (1, 0), scale))
        self._tempbc_dy = sp.backward_ortho(sp.gradient(that, (0, 1), scale))
        # diffusion source dt*ka*(d2/dx2 + d2/dy2) bc  (navier_eq.rs:214-218)
        self._tempbc_diff = dt * ka * (
            sp.gradient(that, (2, 0), scale) + sp.gradient(that, (0, 2), scale)
        )
        # NOTE: the reference also builds a presbc lift field but never
        # consumes it in the time loop or the snapshot writer
        # (/root/reference/src/navier_stokes/navier_io.rs:44-62); the profile
        # itself remains available as bcs.pres_bc_rbc_values.

    # -- solid obstacles (volume penalization) -------------------------------

    def set_solid(self, mask, value=None, eta: float | None = None) -> None:
        """Add a solid obstacle via Brinkman volume penalization.

        ``mask`` (nx, ny): 1 inside the solid, 0 in the fluid, smooth layer in
        between (models/solid_masks.py builders); ``value``: temperature the
        solid enforces (default 0); ``eta``: penalty time scale (default
        dt/10).  The reference stores the mask but never applies it
        (/root/reference/src/navier_stokes/navier.rs:86); here the step gains
        an *implicit pointwise* relaxation, solved exactly per sub-step:

            u    <- u / (1 + dt/eta * mask)
            temp <- (temp + dt/eta * mask * value) / (1 + dt/eta * mask)

        which is unconditionally stable for any eta.  Pass ``mask=None`` to
        remove the obstacle.

        The factor math lives in :func:`brinkman_factors` — shared verbatim
        with the vmapped geometry sweep
        (workloads/modifiers.geometry_sweep), whose bit-match-solo guarantee
        depends on the two paths never diverging."""
        # cached per-dt artifacts embed the penalization factors of the OLD
        # obstacle — changing the obstacle invalidates every rung
        self._dt_cache.clear()
        if mask is None:
            self._solid = None
            self._compile_entry_points()
            return
        mask = np.asarray(mask, dtype=np.float64)
        if value is None:
            value = np.zeros_like(mask)
        if eta is None:
            eta = self.dt / 10.0
        fac, temp_add = brinkman_factors(self, mask, value, eta)
        self._solid = {
            "mask": mask,
            "value": value,
            "eta": float(eta),  # retained so set_dt can rebuild the factors
            "fac": fac,
            "temp_add": temp_add,
        }
        self._compile_entry_points()

    @property
    def solid(self):
        """Reference-parity accessor: ``model.solid = (mask, value)``
        (navier.rs:86 ``navier.solid = Some(mask)``)."""
        if self._solid is None:
            return None
        return (self._solid["mask"], self._solid["value"])

    @solid.setter
    def solid(self, mask_value) -> None:
        if mask_value is None:
            self.set_solid(None)
        else:
            self.set_solid(mask_value[0], mask_value[1])

    # -- initial conditions --------------------------------------------------

    def init_random(self, amp: float, seed: int = 0) -> None:
        """Random uniform disturbance on temp/velx/vely
        (/root/reference/src/navier_stokes/navier.rs:173-182)."""
        rng = np.random.default_rng(seed)
        for name in ("temp", "velx", "vely"):
            space: Space2 = getattr(self, f"{name}_space")
            v = fns.random_values(space.shape_physical, amp, rng)
            self.set_field(name, v)

    def set_velocity(self, amp: float, m: float, n: float) -> None:
        """velx = amp sin(pi m x~) cos(pi n y~), vely = -amp cos sin
        (/root/reference/src/navier_stokes/navier.rs:161-164)."""
        xs, ys = (b.points for b in self.field_space.bases)
        self.set_field("velx", fns.sin_cos_values(xs, ys, amp, m, n))
        self.set_field("vely", fns.cos_sin_values(xs, ys, -amp, m, n))

    def set_temperature(self, amp: float, m: float, n: float) -> None:
        xs, ys = (b.points for b in self.field_space.bases)
        self.set_field("temp", fns.cos_sin_values(xs, ys, -amp, m, n))

    def set_field(self, name: str, values: np.ndarray) -> None:
        """Set one variable from physical values (host -> device forward)."""
        space: Space2 = getattr(self, f"{name}_space")
        with _tr.span("model.set_field", layer=_LAYER, fields=(name,)), self._scope():
            vhat = space.forward(jnp.asarray(values, dtype=config.real_dtype()))
            self.state = self.state._replace(**{name: self._place(vhat)})

    def get_field(self, name: str) -> np.ndarray:
        """Physical values of one variable (device backward -> host)."""
        space: Space2 = getattr(self, f"{name}_space")
        with _tr.span("model.get_field", layer=_LAYER, fields=(name,)), self._scope():
            return np.asarray(space.backward(getattr(self.state, name)))

    # -- the time step -------------------------------------------------------

    def _make_step(self, with_sentinels: bool = False):
        """The jitted step.  ``with_sentinels=True`` returns
        ``(state, (cfl, ke, div_norm))`` instead of just the state: pointwise
        advective CFL ``dt*max(|ux|/dx + |uy|/dy)`` and volume-averaged
        kinetic energy of the *consumed* state, plus the pre-projection
        divergence residual — all cheap reductions over arrays the step
        already materializes (the physical convection velocities and the
        projection RHS), so the state math is untouched and the overhead is
        a handful of elementwise ops per step."""
        dt = self.dt
        scale = self.scale
        nu = self.params["nu"]
        inv_dx, inv_dy = self._inv_dx, self._inv_dy
        w0s, w1s = self._w0, self._w1
        sp_t, sp_u, sp_v = self.temp_space, self.velx_space, self.vely_space
        sp_p, sp_q, sp_f = self.pres_space, self.pseu_space, self.field_space
        rest = sp_t.rest
        mask = self._dealias
        tb_ortho = self.tempbc_ortho
        tb_dx, tb_dy = self._tempbc_dx, self._tempbc_dy
        tb_diff = self._tempbc_diff
        sol_u, sol_v, sol_t, sol_p = (
            self.solver_velx,
            self.solver_vely,
            self.solver_temp,
            self.solver_pres,
        )
        solid = self._solid
        proj_grad = self._proj_grad
        # scenario step modifiers (operator constants — signed into
        # compat_key): rotating-frame Coriolis rate + passive scalar
        coriolis = self._coriolis()
        has_scal = self._scalar_active()
        sol_c = self.solver_scal
        kc_over_ka = (self._scalar_kappa() / self.params["ka"]) if has_scal else 1.0

        # RUSTPDE_SOLVE_PRECISION: experiment knob (default OFF) scoping a
        # matmul-precision override to the four implicit solves ONLY — the
        # remaining 6-pass GEMM family after the fast-synthesis work.  A
        # trace-time jax.default_matmul_precision context covers every GEMM
        # inside the solves (precond matvecs, dense inverses, modal maps)
        # without touching the shared impl classes.  f64 never downgrades.
        # Gates if ever defaulted: div-norm decay, Poisson MMS, shadow,
        # FAST_SYNTH-style long-horizon stats (the r2 NaN came from a GLOBAL
        # "high"; this is the scoped form).
        solve_prec = (
            config.env_get("RUSTPDE_SOLVE_PRECISION") or None
            if not config.X64
            else None
        )

        def solve_scope():
            if solve_prec:
                return jax.default_matmul_precision(solve_prec)
            import contextlib

            return contextlib.nullcontext()

        conv_impl = self._conv_impl
        step_impl = self._step_impl
        # one name per step stage on every device operation it lowers to
        # (instruction metadata only: the compiled program is unchanged), so
        # a device trace reads by stage and not by fusion number
        stage = jax.named_scope
        manual_synth = getattr(self, "_manual_synth", None)
        manual_poisson = getattr(self, "_manual_poisson", None)

        # A velocity is synthesised along the first axis of ``synthesis_axes``
        # once: ``ux``/``uy`` and the derivative synthesis of the velocity's
        # own chain along the second axis are finished from that one partial
        # (Space2.synthesis_first / synthesis_finish), which under a mesh
        # carries the pencil flip with it.  The fused chain (``conv_impl``)
        # and the hand-partitioned synthesis (``manual_synth``) take their
        # inputs whole and share nothing.
        self._shared_syntheses = 2 if conv_impl is None and manual_synth is None else 0

        @stage("convection")  # named under each caller's stage
        def conv(ux, uy, space, vhat, with_bc=False, partial=None):
            """u . grad(v), dealiased, in scratch-ortho space
            (/root/reference/src/navier_stokes/functions.rs:56-69 +
            navier_eq.rs:60-101).  ``partial``: the first-axis synthesis of
            ``vhat`` that the caller already took for the plain synthesis of
            the same field (``velx`` for ``ux``, ``vely`` for ``uy``; the two
            share one space object, so it is handed in by field): the
            derivative along the second axis of ``synthesis_axes`` is
            finished from it, and only the other one is synthesised whole.

            Deliberately per-field, NOT stacked: batching the two derivative
            syntheses into one (2, n, n) transform was measured 18% SLOWER
            for the whole step at 1025^2 f32 (4.01 vs 3.41 ms) — inside one
            compiled program the extra stack/unstack HBM copies and the
            batched dot_generals cost more than the saved op count.  Sharing
            a partial stacks nothing: one product fewer, the others as they
            were."""
            if conv_impl is not None:
                # the whole chain as one fused region: the Pallas VMEM
                # kernel (physical intermediates never touch HBM, dealias
                # row-drop in the epilogue) or the manually-partitioned
                # shard_map region on the split-sep mesh layout — both
                # exact to the chain below at fp reassociation
                fc = conv_impl[id(space)]
                if with_bc:
                    return fc.apply(ux, uy, vhat, tb_dx, tb_dy)
                return fc.apply(ux, uy, vhat)
            # fused synthesis-of-derivative: one GEMM per axis on sep spaces
            # (Space2.backward_gradient == backward_ortho(gradient(.)));
            # fast=True: 3-pass synthesis for the dealiased products
            second = space.synthesis_axes[1]

            def grad(deriv):
                if partial is not None and deriv[second]:
                    return space.synthesis_finish(partial, deriv, scale, fast=True)
                return space.backward_gradient(vhat, deriv, scale, fast=True)

            dvdx, dvdy = grad((1, 0)), grad((0, 1))
            total = ux * dvdx + uy * dvdy
            if with_bc:
                total = total + ux * tb_dx + uy * tb_dy
            if any(sp_f.sep):
                # dealias folded into the forward GEMMs (dead rows dropped
                # on sep axes, vector cut on the rest); fast=True
                # additionally honors RUSTPDE_FWD_PRECISION
                return sp_f.forward_dealiased(total, fast=True)
            return sp_f.forward(total) * mask

        def step(state: NavierState) -> NavierState:
            # pin the implicit-solve inputs to the layout the spaces'
            # spectral arrays rest in (Space2.rest: the x-pencil of a confined
            # space, the y-pencil of a periodic one; all five spaces share
            # their x-base's kind, so one layout serves; no-op without a
            # mesh; a non-divisible extent is padded inside
            # the jit and only a jit OUTPUT comes back replicated,
            # parallel/mesh.py): asserts the pencil
            # discipline at the solve boundaries so GSPMD propagation cannot
            # drift the solve internals onto other layouts on real
            # (divisible) meshes.  NOTE it does NOT cure the fused split-sep
            # miscompile tracked in test_parallel.py::
            # test_sharded_split_periodic_mixed_sep_matches_serial (xfail).
            from ..parallel.mesh import constrain

            def pin(a):
                return constrain(a, rest)

            temp, velx, vely, pres, pseu = (
                state.temp, state.velx, state.vely, state.pres, state.pseu
            )
            # buoyancy (full ortho space, includes the lift field)
            with stage("buoyancy"):
                that = sp_t.to_ortho(temp) + tb_ortho
            # convection velocity in physical space (old time level; fast
            # 3-pass synthesis — feeds only the dealiased products); the
            # manual split-sep path runs these through their own shard_map
            # region (decomp.ShardedSynthesis)
            part_u = part_v = None
            with stage("synthesis"):
                if manual_synth is not None:
                    ux = manual_synth[id(sp_u)].apply(velx)
                    uy = manual_synth[id(sp_v)].apply(vely)
                else:
                    # backward_fast in its two steps; the partials go on to
                    # the velocities' own chains.  The shared product runs as
                    # the plain synthesis asks (the same fast key as the
                    # chain's on a confined space, the exact backward
                    # elsewhere): never below what either consumer had
                    part_u = sp_u.synthesis_first(velx, fast=True)
                    part_v = sp_v.synthesis_first(vely, fast=True)
                    ux = sp_u.synthesis_finish(part_u, fast=True)
                    uy = sp_v.synthesis_finish(part_v, fast=True)

            if with_sentinels:
                # sentinels of the consumed state, from the velocities the
                # convection terms need anyway (no extra transforms)
                with stage("sentinels"):
                    cfl = dt * jnp.max(
                        jnp.abs(ux) * inv_dx[:, None] + jnp.abs(uy) * inv_dy[None, :]
                    )
                    ke = 0.5 * jnp.sum((ux**2 + uy**2) * w0s[:, None] * w1s[None, :])

            if step_impl is not None:
                # fused implicit half (ops/pallas_step.py): each stage ONE
                # Pallas kernel — rhs terms with the Helmholtz inverse
                # folded in for the velocities/temperature, divergence ->
                # fast-diag Poisson (singular pin in the epilogue mask) ->
                # pressure-gradient projection.  The convection chain feeds
                # the stages unchanged (dense or FusedConv per
                # RUSTPDE_CONV_KERNEL); the stage dots pin HIGHEST matmul
                # precision themselves, so no solve_scope here.  Mesh-free
                # by construction (_build_step_kernels), hence no pins.
                with stage("momentum_x"):
                    cx = conv(ux, uy, sp_u, velx, partial=part_u)
                    args = (velx, pres, cx) + ((vely,) if coriolis else ())
                    velx_n = step_impl["velx"].apply(*args)
                with stage("momentum_y"):
                    cy = conv(ux, uy, sp_v, vely, partial=part_v)
                    args = (vely, pres, temp, cy) + ((velx,) if coriolis else ())
                    vely_n = step_impl["vely"].apply(*args)
                with stage("divergence"):
                    div = step_impl["div"].apply(velx_n, vely_n)
                with stage("poisson"):
                    pseu_n = sp_q.pin_zero_mode(step_impl["poisson"].apply(div))
                with stage("projection"):
                    velx_n = velx_n - step_impl["projx"].apply(pseu_n)
                    vely_n = vely_n - step_impl["projy"].apply(pseu_n)
                with stage("pressure"):
                    pres_n = pres - nu * div + sp_q.to_ortho(pseu_n) / dt
                with stage("temperature"):
                    ct = conv(ux, uy, sp_t, temp, with_bc=True)
                    temp_n = step_impl["temp"].apply(temp, ct)
                if has_scal:
                    with stage("scalar"):
                        cs = conv(ux, uy, sp_t, state.scal, with_bc=True)
                        scal_n = step_impl["scal"].apply(state.scal, cs)
            else:
                # horizontal momentum (navier_eq.rs:176-187)
                with stage("momentum_x"):
                    rhs = sp_u.to_ortho(velx)
                    rhs = rhs - dt * sp_p.gradient(pres, (1, 0), scale)
                    rhs = rhs - dt * conv(ux, uy, sp_u, velx, partial=part_u)
                    if coriolis:
                        # rotating-frame f-plane term +f*v (velx/vely share one
                        # space, so the cross-coupling is a plain ortho-space
                        # add); in exactly incompressible 2-D flow this force is
                        # irrotational and absorbed by the pressure — the
                        # scenario's analytic validation case
                        # (tests/test_workloads.py)
                        rhs = rhs + dt * coriolis * sp_v.to_ortho(vely)
                    with solve_scope():
                        velx_n = sol_u.solve(pin(rhs))

                # vertical momentum + buoyancy (navier_eq.rs:190-203)
                with stage("momentum_y"):
                    rhs = sp_v.to_ortho(vely)
                    rhs = rhs - dt * sp_p.gradient(pres, (0, 1), scale)
                    rhs = rhs + dt * that
                    rhs = rhs - dt * conv(ux, uy, sp_v, vely, partial=part_v)
                    if coriolis:
                        rhs = rhs - dt * coriolis * sp_u.to_ortho(velx)
                    with solve_scope():
                        vely_n = sol_v.solve(pin(rhs))

                # pressure projection
                # (navier_eq.rs:19-25,117-125,137-143,158-162)
                with stage("divergence"):
                    div = sp_u.gradient(velx_n, (1, 0), scale) + sp_v.gradient(
                        vely_n, (0, 1), scale
                    )
                with stage("poisson"):
                    with solve_scope():
                        if manual_poisson is not None:
                            # the manually-partitioned fast-diag region — the
                            # one stage whose GSPMD fusion miscompiles on the
                            # split-sep layout (parallel/decomp.ShardedPoisson
                            # bisection)
                            pseu_n = manual_poisson.solve(div)
                        else:
                            # the pressure update below reads the pinned sum
                            # too: left free, a confined space's is taken in
                            # the solve's y-pencil layout and flipped back
                            div = pin(div)
                            pseu_n = sol_p.solve(div)
                    pseu_n = sp_q.pin_zero_mode(pseu_n)  # remove singularity
                with stage("projection"):
                    if proj_grad is not None:
                        gx0, gx1, gy0, gy1 = proj_grad
                        ax = pseu_n.ndim - 2
                        velx_n = velx_n - gx1.apply(gx0.apply(pseu_n, ax), ax + 1) / scale[0]
                        vely_n = vely_n - gy1.apply(gy0.apply(pseu_n, ax), ax + 1) / scale[1]
                    else:
                        velx_n = velx_n - sp_q.gradient(pseu_n, (1, 0), scale, into=sp_u)
                        vely_n = vely_n - sp_q.gradient(pseu_n, (0, 1), scale, into=sp_v)
                with stage("pressure"):
                    pres_n = pres - nu * div + sp_q.to_ortho(pseu_n) / dt

                # temperature (navier_eq.rs:209-224)
                with stage("temperature"):
                    rhs = sp_t.to_ortho(temp)
                    rhs = rhs + tb_diff
                    rhs = rhs - dt * conv(ux, uy, sp_t, temp, with_bc=True)
                    with solve_scope():
                        temp_n = sol_t.solve(pin(rhs))

                if has_scal:
                    with stage("scalar"):
                        # passive scalar (scenario modifier): the temperature's
                        # advection-diffusion at the scalar diffusivity, same BC
                        # lift — with matched diffusivity a scalar released
                        # equal to the temperature stays identically equal
                        # (exact validation case); the buoyancy never reads it
                        # (one-way coupling, hence "passive")
                        rhs = sp_t.to_ortho(state.scal)
                        rhs = rhs + kc_over_ka * tb_diff  # dt*kc*lap(bc lift)
                        rhs = rhs - dt * conv(ux, uy, sp_t, state.scal, with_bc=True)
                        with solve_scope():
                            scal_n = sol_c.solve(pin(rhs))

            if solid is not None:
                # implicit pointwise Brinkman penalization (set_solid):
                # elementwise in physical space, exact for the sub-step
                fac, temp_add = solid["fac"], solid["temp_add"]
                with stage("solid"):
                    velx_n = sp_u.forward(sp_u.backward(velx_n) * fac)
                    vely_n = sp_v.forward(sp_v.backward(vely_n) * fac)
                    temp_n = sp_t.forward(sp_t.backward(temp_n) * fac + temp_add)
                    if has_scal:
                        # the solid enforces the same target on the scalar
                        scal_n = sp_t.forward(
                            sp_t.backward(scal_n) * fac + temp_add
                        )

            # pin the step outputs too: the next step's transforms assume the
            # resting layout, and XLA's sharding propagation is free to emit
            # replicated outputs otherwise — which silently serializes a
            # multi-chip run
            if has_scal:
                state_n = NavierScalarState(
                    pin(temp_n), pin(velx_n), pin(vely_n), pin(pres_n),
                    pin(pseu_n), pin(scal_n),
                )
            else:
                state_n = NavierState(
                    pin(temp_n), pin(velx_n), pin(vely_n), pin(pres_n),
                    pin(pseu_n),
                )
            if with_sentinels:
                # |div| of the uncorrected velocities — the residual the
                # projection removes this step; its blow-up tracks the flow's
                with stage("sentinels"):
                    return state_n, (cfl, ke, norm_l2(div))
            return state_n

        return step

    def _make_div(self):
        sp_u, sp_v = self.velx_space, self.vely_space
        scale = self.scale

        def div(state: NavierState):
            return sp_u.gradient(state.velx, (1, 0), scale) + sp_v.gradient(
                state.vely, (0, 1), scale
            )

        return div

    def _make_observables(self):
        """One fused jitted function returning (Nu, Nuvol, Re, |div|).

        Formulas match /root/reference/src/navier_stokes/functions.rs:146-233.
        """
        sp_t, sp_u, sp_v = self.temp_space, self.velx_space, self.vely_space
        sp_f = self.field_space
        scale = self.scale
        nu, ka = self.params["nu"], self.params["ka"]
        tb = self.tempbc_ortho
        w0, w1 = self._w0, self._w1
        div_fn = self._make_div()
        scalar_active = self._scalar_active()

        def avg_x(v):
            return jnp.sum(v * w0[:, None], axis=0)

        def avg(v):
            return jnp.sum(v * w0[:, None] * w1[None, :])

        def observables(state: NavierState):
            that = sp_t.to_ortho(state.temp) + tb
            # physical dT/dy, computed ONCE via the fused synthesis-of-
            # derivative chain (backward_ortho(gradient(.)) collapsed to one
            # GEMM per axis on sep spaces) and shared by the plate-flux Nu
            # and the volume Nuvol — the unfused form ran the gradient and
            # two separate backward_orthos (VERDICT r4 next #7)
            dtdy_p = sp_f.backward_gradient(that, (0, 1), None)
            # Nu: plate heat flux <-2/sy * dT/dy>_x averaged over both plates
            x_avg = avg_x(dtdy_p) * (-2.0 / scale[1])
            nu_plate = 0.5 * (x_avg[0] + x_avg[-1])
            # Nuvol: <2 sy (uy T / ka - dT/dy / sy)>_V
            temp_p = sp_f.backward_ortho(that)
            uy = sp_v.backward(state.vely)
            nu_vol = avg(
                (dtdy_p / (-scale[1]) + uy * temp_p / ka) * 2.0 * scale[1]
            )
            # Re: <sqrt(ux^2+uy^2) * 2 sy / nu>_V
            ux = sp_u.backward(state.velx)
            re = avg(jnp.sqrt(ux**2 + uy**2) * 2.0 * scale[1] / nu)
            # divergence norm
            dnorm = norm_l2(div_fn(state))
            if scalar_active:
                # fold the scalar's finiteness into the NaN-detector
                # observable (a scal-only NaN is invisible to the flow —
                # exit()/state_healthy/serve isolation all watch dnorm)
                dnorm = dnorm + 0.0 * jnp.sum(jnp.abs(state.scal))
                # Sherwood number: the scalar-transfer analog of the
                # plate-flux Nu — the scalar shares the temperature's
                # composite space AND BC lift, so at matched diffusivity a
                # scalar released equal to T yields sherwood == nu exactly
                # (the scenario's validation identity).  Appended AFTER the
                # conventional four so |div| stays the index-3 NaN detector.
                shat = sp_t.to_ortho(state.scal) + tb
                dsdy_p = sp_f.backward_gradient(shat, (0, 1), None)
                s_avg = avg_x(dsdy_p) * (-2.0 / scale[1])
                sherwood = 0.5 * (s_avg[0] + s_avg[-1])
                return nu_plate, nu_vol, re, dnorm, sherwood
            return nu_plate, nu_vol, re, dnorm

        return observables

    # -- Integrate protocol / campaign machinery ------------------------------
    # update/update_n/update_n_pending, sentinels, set_stability, the dt rung
    # cache, observable futures and exit/exit_future live in
    # models/campaign.CampaignModelBase — this class only lists what a dt
    # change invalidates and how to rebuild it.

    # attributes a dt change swaps out, cached per rung so a governor
    # cycling a bounded dt ladder refactorizes/re-jits each rung ONCE
    # (solver_pres is dt-independent; tempbc_ortho/_tempbc_dx/_tempbc_dy are
    # cached alongside because _build_bc_fields rebuilds them together)
    _DT_ARTIFACTS = (
        "solver_velx",
        "solver_vely",
        "solver_temp",
        "solver_scal",
        "tempbc_ortho",
        "_tempbc_dx",
        "_tempbc_dy",
        "_tempbc_diff",
        "_step_impl",
        "_solid",
    ) + CampaignModelBase._DT_ARTIFACTS

    def _rebuild_dt_artifacts(self) -> None:
        """First visit to a dt rung: dt is baked deep into the pipeline —
        the implicit Helmholtz solvers factorize ``dt*nu`` / ``dt*ka``, the
        BC diffusion source scales with dt, and a solid mask's penalization
        factors use dt/eta — so rebuild solvers + lift-field derivatives and
        re-trace the jitted entry points (see CampaignModelBase.set_dt for
        the rung-cache contract)."""
        dt = self.dt
        nu, ka = self.params["nu"], self.params["ka"]
        sx2, sy2 = self.scale[0] ** 2, self.scale[1] ** 2
        self.solver_velx = HholtzAdi(self.velx_space, (dt * nu / sx2, dt * nu / sy2))
        self.solver_vely = self.solver_velx
        self.solver_temp = HholtzAdi(self.temp_space, (dt * ka / sx2, dt * ka / sy2))
        self.solver_scal = self._build_scalar_solver()
        # solver_pres is dt-independent (pure Poisson)
        xs, ys = (b.points for b in self.field_space.bases)
        with self._scope():
            self._build_bc_fields(xs, ys)
        # the fused stage kernels bake dt into every term matrix (and the
        # BC-lift constants above into the Helmholtz stages)
        self._step_impl = self._build_step_kernels()
        if self._solid is not None:
            # rebuilds the dt/eta factors AND recompiles the entry points;
            # the obstacle itself is unchanged, so the per-rung cache stays
            # valid (set_solid clears it — shield it across the call)
            cache, self._dt_cache = self._dt_cache, {}
            try:
                self.set_solid(
                    self._solid["mask"], self._solid["value"], self._solid["eta"]
                )
            finally:
                self._dt_cache = cache
        else:
            self._compile_entry_points()

    def eval_nu(self) -> float:
        return self.get_observables()[0]

    def eval_nuvol(self) -> float:
        return self.get_observables()[1]

    def eval_re(self) -> float:
        return self.get_observables()[2]

    def write(self, filename: str) -> None:
        """Write a flow snapshot in the reference HDF5 layout."""
        from ..utils import checkpoint

        checkpoint.write_snapshot(self, filename)

    def read(self, filename: str) -> None:
        """Restore from a snapshot (supports resolution change via spectral
        interpolation; sharded-checkpoint manifests restore topology-
        elastically, see utils/checkpoint.read_sharded_snapshot)."""
        from ..utils import checkpoint

        checkpoint.read_snapshot(self, filename)

    def read_unwrap(self, filename: str) -> None:
        from ..utils.checkpoint import CheckpointError

        try:
            self.read(filename)
        except (OSError, KeyError, CheckpointError) as exc:
            print(f"error while reading file {filename}: {exc}")

    def callback(self) -> None:
        from ..utils import navier_io

        navier_io.callback(self)
