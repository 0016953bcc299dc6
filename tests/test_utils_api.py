"""Config dataclass, diagnostics map, profiling API, BC helper coverage."""

import numpy as np

from rustpde_mpi_tpu import Navier2D
from rustpde_mpi_tpu.config import NavierConfig
from rustpde_mpi_tpu.models.boundary_conditions import (
    bc_zero_values,
    transfer_function,
)


def _tiny_model():
    return Navier2D.from_config(NavierConfig(nx=17, ny=17, ra=1e4, dt=0.01))


def test_from_config_matches_ctor():
    cfg = NavierConfig(nx=17, ny=17, ra=1e4, dt=0.01, write_intervall=2.0)
    m = Navier2D.from_config(cfg)
    assert (m.nx, m.ny) == (17, 17)
    assert m.params["ra"] == 1e4
    assert m.write_intervall == 2.0
    m.update()
    assert np.isfinite(m.get_observables()[0])


def test_diagnostics_map_filled_by_callback(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    m = _tiny_model()
    m.update_n(5)
    m.callback()
    m.update_n(5)
    m.callback()
    assert len(m.diagnostics["time"]) == 2
    assert len(m.diagnostics["nu"]) == 2
    assert m.diagnostics["time"][1] > m.diagnostics["time"][0]


def test_profiling_api_exports():
    """``utils.profiling`` is the trace context and the memory stats (API
    pin): rates, flop counts and peaks are the benchmark's, and a second
    yardstick does not grow back here."""
    from rustpde_mpi_tpu.utils import profiling

    own = {
        name
        for name, value in vars(profiling).items()
        if not name.startswith("_")
        and getattr(value, "__module__", None) == profiling.__name__
    }
    assert own == set(profiling.__all__) == {"trace", "device_memory_stats"}


def test_workload_api_exports():
    """The workloads satellite: the multi-model campaign surface must be
    importable from the package root (API pin — mirrors the robustness pin
    in test_serve.py)."""
    import rustpde_mpi_tpu as rp

    for name in (
        "CampaignModelBase",
        "ScenarioConfig",
        "build_model",
        "model_kinds",
        "register_model_kind",
        "validate_campaign_model",
        "eigenmode_sweep",
        "critical_rayleigh",
        "steady_state_find",
        "geometry_sweep",
        "Navier2DLnse",
        "Navier2DAdjoint",
    ):
        assert hasattr(rp, name), name
    assert set(rp.model_kinds()) >= {"dns", "lnse", "adjoint"}
    # the models package exports the campaign contract + both ported models
    from rustpde_mpi_tpu import models as mdl

    for name in ("CampaignModelBase", "CAMPAIGN_MODEL_ATTRS",
                 "Navier2DLnse", "Navier2DAdjoint", "AdjointState",
                 "NavierScalarState", "scenario_signature"):
        assert hasattr(mdl, name), name


def test_transfer_function_limits():
    """Smooth three-level transfer (boundary_conditions.rs:262-274): hits
    v_l at the left edge, v_m in the middle, v_r at the right edge."""
    x = np.linspace(-1, 1, 201)
    v = transfer_function(x, 0.5, 0.0, -0.5, k=50.0)
    assert abs(v[0] - 0.5) < 1e-6
    assert abs(v[100]) < 1e-6
    assert abs(v[-1] + 0.5) < 1e-6
    mask = bc_zero_values(x, x, k=50.0)
    assert mask.shape == (201, 201)
    assert abs(mask[0, 0] - 0.5) < 1e-6  # bottom plate value


def test_telemetry_api_exports():
    """The telemetry subsystem's public surface (API pin): the package
    root carries the module + the two classes other layers hand around,
    and the telemetry package itself exports the full documented set."""
    import rustpde_mpi_tpu as rp

    for name in ("telemetry", "MetricsRegistry", "ThroughputMonitor"):
        assert hasattr(rp, name), name
    for name in (
        "REGISTRY",
        "RECORDER",
        "counter",
        "gauge",
        "histogram",
        "snapshot",
        "span",
        "instant",
        "prometheus_text",
        "PROMETHEUS_CONTENT_TYPE",
        "MetricsDumper",
        "read_metrics_jsonl",
        "FlightRecorder",
        "dump_flight_record",
        "arm_exit_dump",
        "gather_global_snapshot",
        "merge_snapshots",
        "set_enabled",
        "enabled",
    ):
        assert hasattr(rp.telemetry, name), name
    # the default registry is ONE process-wide object shared by every layer
    assert rp.telemetry.default_registry() is rp.telemetry.REGISTRY
