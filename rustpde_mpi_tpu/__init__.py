"""rustpde_mpi_tpu — a TPU-native spectral-method PDE framework.

A from-scratch JAX/XLA rebuild with the capabilities of the Rust
``rustpde-mpi`` framework (2-D Navier–Stokes / Rayleigh–Bénard convection with
Chebyshev/Fourier spectral-Galerkin discretisation; serial, single-chip and
mesh-sharded multi-chip execution).  See SURVEY.md for the component map.

Public API vocabulary mirrors the reference (``/root/reference/src/lib.rs``):
bases, Field2/Space2, solvers (Poisson/Hholtz/HholtzAdi), Navier2D models and
an ``integrate`` driver — redesigned functionally for XLA: states are pytrees,
steps are pure jitted functions, parallelism is `jax.sharding` over a Mesh.
"""

from . import config  # noqa: F401  (must import first: enables x64)
from .bases import (  # noqa: F401
    Base,
    BaseKind,
    Space2,
    cheb_dirichlet,
    cheb_dirichlet_neumann,
    cheb_neumann,
    chebyshev,
    fourier_c2c,
    fourier_r2c,
    fourier_r2c_split,
)
from .bases import BiPeriodicSpace2, Space1  # noqa: F401
from .field import Field1, Field2, average, average_axis, norm_l2  # noqa: F401
from .models.ensemble import NavierEnsemble  # noqa: F401
from .models.lnse import Navier2DLnse, Navier2DNonLin  # noqa: F401
from .models.meanfield import MeanFields  # noqa: F401
from .models.navier import Navier2D, NavierState  # noqa: F401
from .models.opt_routines import (  # noqa: F401
    descent_iteration,
    mirrored_target,
    steepest_descent_energy_constrained,
)
from .models.statistics import Statistics  # noqa: F401
from .models.stats import StatsEngine, StatsState, export_stats  # noqa: F401
from .models.steady_adjoint import Navier2DAdjoint  # noqa: F401
from .models.swift_hohenberg import SwiftHohenberg1D, SwiftHohenberg2D  # noqa: F401
from .utils.governor import (  # noqa: F401
    ChunkStatus,
    DtLadder,
    RunHealth,
    StabilityGovernor,
)
from .utils.integrate import Integrate, integrate  # noqa: F401
from .utils.io_pipeline import (  # noqa: F401
    AsyncWriteError,
    IOPipeline,
    ObservableFuture,
)
from .models.campaign import CampaignModelBase  # noqa: F401
from .serve import (  # noqa: F401
    AdmissionError,
    RequestFailed,
    SimRequest,
    SimServer,
)
from .workloads import (  # noqa: F401
    ScenarioConfig,
    build_model,
    critical_rayleigh,
    eigenmode_sweep,
    geometry_sweep,
    model_kinds,
    register_model_kind,
    steady_state_find,
    validate_campaign_model,
)
from . import telemetry  # noqa: F401
from .telemetry import (  # noqa: F401
    MetricsRegistry,
    ThroughputMonitor,
)
from .parallel.sanitizer import CollectiveDesyncError  # noqa: F401
from .utils.checkpoint import CheckpointError  # noqa: F401
from .utils.faults import FaultSpecError  # noqa: F401
from .utils.resilience import (  # noqa: F401
    DispatchHang,
    DivergenceError,
    ResilientRunner,
)
from .utils.vorticity import (  # noqa: F401
    vorticity_auto,
    vorticity_from_file,
    vorticity_from_file_periodic,
)

__version__ = "0.1.0"
