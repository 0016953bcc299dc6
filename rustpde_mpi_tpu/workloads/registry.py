"""Model-kind registry: one table of campaign-model builders.

The multi-model half of the workload subsystem: every physics model that
satisfies the :class:`~rustpde_mpi_tpu.models.campaign.CampaignModelBase`
contract registers a builder under its ``MODEL_KIND``, and everything
downstream — the serve scheduler's campaign construction, the workload
drivers, the parity recorder — builds models through :func:`build_model`
instead of hard-wiring ``Navier2D``.  A request's ``compat_key`` starts
with the kind, so mixed-model traffic buckets correctly by construction.

Built-in kinds:

* ``dns`` — :class:`~rustpde_mpi_tpu.models.navier.Navier2D` (full DNS,
  scenario modifiers allowed),
* ``lnse`` — :class:`~rustpde_mpi_tpu.models.lnse.Navier2DLnse` linearized
  about the analytic conduction base state (eigenmode sweeps),
* ``adjoint`` — :class:`~rustpde_mpi_tpu.models.steady_adjoint.Navier2DAdjoint`
  (steady-state finds by adjoint descent),
* ``swift`` — :class:`~rustpde_mpi_tpu.models.swift_hohenberg.SwiftHohenberg2D`
  (``SwiftHohenberg1D`` for ``ny == 1``): the upstream's bring-your-own-PDE
  demo.  The builders' signature is the DNS's, so the control parameter ``r``
  rides the ``ra`` slot and ``length`` the ``aspect`` slot; ``pr``, ``bc`` and
  ``periodic`` say nothing of it.  Its ``compat_key`` is its own 7-tuple
  ``(kind, nx, ny, r, dt, length, dtype)``, so it registers a ``from_key`` and
  :func:`build_model_for_key` builds it from that.
"""

from __future__ import annotations

from ..models.campaign import CAMPAIGN_MODEL_ATTRS

_REGISTRY: dict[str, callable] = {}
_FROM_KEY: dict[str, callable] = {}


def register_model_kind(kind: str, builder, from_key=None) -> None:
    """Register ``builder(nx, ny, ra, pr, dt, aspect, bc, periodic, *,
    mesh=None, scenario=None) -> CampaignModel`` under ``kind``.  A kind whose
    ``compat_key`` is not the DNS's 10-tuple also gives ``from_key(key, *,
    mesh=None) -> CampaignModel``, the model its own key describes."""
    _REGISTRY[str(kind)] = builder
    if from_key is not None:
        _FROM_KEY[str(kind)] = from_key


def model_kinds() -> tuple:
    """The registered kinds (sorted, for stable error messages/docs)."""
    return tuple(sorted(_REGISTRY))


def build_model(
    kind: str,
    nx: int,
    ny: int,
    ra: float,
    pr: float,
    dt: float,
    aspect: float,
    bc: str,
    periodic: bool,
    *,
    mesh=None,
    scenario=None,
):
    """Build a campaign model of ``kind`` (raises ``KeyError`` naming the
    registered kinds for an unknown one)."""
    try:
        builder = _REGISTRY[str(kind)]
    except KeyError:
        raise KeyError(
            f"unknown model kind {kind!r}; registered: {list(model_kinds())}"
        ) from None
    return builder(
        nx, ny, ra, pr, dt, aspect, bc, periodic, mesh=mesh, scenario=scenario
    )


def build_model_for_key(key: tuple, *, mesh=None, phase: str = "build"):
    """Build the campaign model one compat-key bucket needs (the serve
    scheduler's campaign constructor): ``key`` is the 10-tuple
    ``(kind, nx, ny, ra, pr, dt, aspect, bc, periodic, scenario_sig)``,
    or the 11-tuple SERVE key with the sub-mesh stamp appended
    (two-level serving) — the stamp selects the mesh upstream and is
    stripped here; the model's own compat key stays the 10-tuple.  A kind
    registered with a ``from_key`` is built from its own key instead.

    This is THE model-build/jit seam for every bucket, so compile
    attribution hangs here: build wall time and the recompile count are
    recorded per compat key (telemetry/compile_log.py) — the cold-start
    ROADMAP item's baseline numbers.  ``phase`` stamps the attribution row
    ("build" for live campaign opens, "aot" when the warm pool builds
    ahead of traffic)."""
    key = tuple(key)
    kind = key[0]
    from_key = _FROM_KEY.get(str(kind))
    if from_key is not None:
        return _observed_build(key, kind, phase, lambda: from_key(key, mesh=mesh))
    if len(key) == 11:
        key = key[:10]
    kind, nx, ny, ra, pr, dt, aspect, bc, periodic, scenario_sig = key
    scenario = dict(scenario_sig) if scenario_sig else None
    if scenario and "passive_scalar" in scenario:
        # the signature packs the kappa into the value slot (0.0 = thermal)
        kappa = scenario.pop("passive_scalar")
        scenario["passive_scalar"] = True
        scenario["scalar_kappa"] = kappa or None
    if scenario and kind == "dns":
        from ..models.navier import scenario_signature

        if scenario_signature(scenario) != tuple(scenario_sig):
            raise ValueError(f"non-canonical scenario signature {scenario_sig}")
    return _observed_build(
        key, kind, phase,
        lambda: build_model(
            kind, nx, ny, ra, pr, dt, aspect, bc, periodic, mesh=mesh, scenario=scenario
        ),
    )


def _observed_build(key: tuple, kind, phase: str, build):
    """``build()`` under the seam's one clock: the span ``registry.build_model``
    (it holds the model's own ``model.build``) is what the histogram observes."""
    from ..telemetry import compile_log
    from ..telemetry import tracing as _tr

    seam = _tr.timed("registry.build_model", layer="model step", kind=str(kind), phase=phase)
    with seam:
        model = build()
    if model.compat_key != tuple(key):
        raise ValueError(
            f"registry builder for {kind!r} produced compat_key "
            f"{model.compat_key} for requested key {tuple(key)}"
        )
    compile_log.observe_build(key, seam.seconds, kind=str(kind), phase=phase)
    return model


def validate_campaign_model(model) -> list:
    """The protocol check: every attribute/method of the CampaignModel
    contract (models/campaign.CAMPAIGN_MODEL_ATTRS) must be present.
    Returns the list of missing names (empty = conforms)."""
    return [name for name in CAMPAIGN_MODEL_ATTRS if not hasattr(model, name)]


# -- built-in kinds -----------------------------------------------------------


def _build_dns(nx, ny, ra, pr, dt, aspect, bc, periodic, *, mesh=None, scenario=None):
    from ..models.navier import Navier2D

    return Navier2D(
        nx, ny, ra, pr, dt, aspect, bc, periodic=periodic, mesh=mesh,
        scenario=scenario,
    )


def _build_lnse(nx, ny, ra, pr, dt, aspect, bc, periodic, *, mesh=None, scenario=None):
    from ..models.lnse import Navier2DLnse
    from ..models.meanfield import MeanFields

    if scenario:
        raise ValueError("scenario modifiers are a DNS axis (model='dns')")
    # deterministic analytic base state (no mean.h5 file dependency): the
    # conduction profile for rbc, the cos-bottom parabola for hc
    mean = (
        MeanFields.new_hc(nx, ny, periodic)
        if bc == "hc"
        else MeanFields.new_rbc(nx, ny, periodic)
    )
    return Navier2DLnse(
        nx, ny, ra, pr, dt, aspect, bc, periodic=periodic, mean=mean, mesh=mesh
    )


def _build_adjoint(
    nx, ny, ra, pr, dt, aspect, bc, periodic, *, mesh=None, scenario=None
):
    from ..models.steady_adjoint import RES_TOL, Navier2DAdjoint

    res_tol = RES_TOL
    if scenario:
        extra = dict(
            scenario if isinstance(scenario, dict) else dict(scenario)
        )
        # the adjoint's variant slot carries its convergence tolerance
        # (compiled into the chunk's exit sentinel, hence part of the key)
        res_tol = float(extra.pop("res_tol", res_tol))
        if extra:
            raise ValueError(
                f"unsupported adjoint variant fields: {sorted(extra)}"
            )
    return Navier2DAdjoint(
        nx, ny, ra, pr, dt, aspect, bc, periodic=periodic, mesh=mesh,
        res_tol=res_tol,
    )


def _build_swift(nx, ny, ra, pr, dt, aspect, bc, periodic, *, mesh=None, scenario=None):
    from ..models.swift_hohenberg import SwiftHohenberg1D, SwiftHohenberg2D

    del pr, bc, periodic  # a doubly periodic scalar PDE has none of them
    if mesh is not None or scenario:
        raise ValueError("the Swift-Hohenberg models take no mesh and no scenario")
    if ny == 1:
        return SwiftHohenberg1D(nx, ra, dt, aspect)
    return SwiftHohenberg2D(nx, ny, ra, dt, aspect)


def _swift_from_key(key, *, mesh=None):
    _, nx, ny, r, dt, length, _dtype = key  # the dtype is the process's: the seam checks it
    return _build_swift(nx, ny, r, None, dt, length, None, True, mesh=mesh)


register_model_kind("dns", _build_dns)
register_model_kind("lnse", _build_lnse)
register_model_kind("adjoint", _build_adjoint)
register_model_kind("swift", _build_swift, from_key=_swift_from_key)
