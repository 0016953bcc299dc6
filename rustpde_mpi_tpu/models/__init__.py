"""Physics model layer: Navier-Stokes DNS and derived solvers.

Every model here satisfying the CampaignModel contract
(:mod:`~rustpde_mpi_tpu.models.campaign`) — ``Navier2D``, ``Navier2DLnse``,
``Navier2DAdjoint`` — runs under the shared ensemble/resilience/serve
stack; the workload drivers live in :mod:`rustpde_mpi_tpu.workloads`.
"""

from .campaign import CAMPAIGN_MODEL_ATTRS, CampaignModelBase  # noqa: F401
from .ensemble import NavierEnsemble  # noqa: F401
from .lnse import Navier2DLnse, Navier2DNonLin  # noqa: F401
from .meanfield import MeanFields  # noqa: F401
from .navier import (  # noqa: F401
    Navier2D,
    NavierScalarState,
    NavierState,
    scenario_signature,
)
from .opt_routines import (  # noqa: F401
    descent_iteration,
    mirrored_target,
    steepest_descent_energy_constrained,
)
from .statistics import Statistics  # noqa: F401
from .stats import (  # noqa: F401
    HEALTH_NAMES,
    StatsEngine,
    StatsState,
    export_stats,
)
from .steady_adjoint import AdjointState, Navier2DAdjoint  # noqa: F401
