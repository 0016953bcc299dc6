"""Multi-model workload subsystem: campaign drivers over the CampaignModel
contract (models/campaign.py).

* ``registry`` — one table mapping model kinds (``dns`` / ``lnse`` /
  ``adjoint``) to campaign-model builders; the serve scheduler and every
  workload driver build models through it,
* ``eigenmodes`` — lnse eigenmode sweeps (leading growth rates, critical
  Rayleigh number) as governed, checkpointed, vmapped ensembles,
* ``steady`` — adjoint steady-state finds with residual convergence as the
  compiled exit sentinel, kill/resume-safe under ``ResilientRunner``,
* ``modifiers`` — the scenario axis: config-carried step modifiers
  (rotating frame, passive scalar) and the vmapped solid-mask geometry
  sweep,
* ``parity`` — per-model solo-vs-ensemble drift probe.
"""

from .eigenmodes import (  # noqa: F401
    AC_RIGID,
    RAC_RIGID,
    build_eigenmode_ensemble,
    critical_aspect,
    critical_rayleigh,
    eigenmode_sweep,
    growth_rates,
)
from .modifiers import (  # noqa: F401
    ScenarioConfig,
    geometry_sweep,
    penalization_factors,
)
from .parity import solo_ensemble_parity  # noqa: F401
from .registry import (  # noqa: F401
    build_model,
    build_model_for_key,
    model_kinds,
    register_model_kind,
    validate_campaign_model,
)
from .steady import build_steady_ensemble, steady_state_find  # noqa: F401
