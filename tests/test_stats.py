"""In-scan physics-stats engine tests (models/stats.py, ISSUE 14): the
bit-identity hard contract (stats-on stepping == stats-off stepping,
exact float equality), engine-vs-eager-legacy accumulator parity,
per-member ensemble windows + lane-refill resets, checkpoint durability
(gathered + sharded + a real SIGKILL/resume cycle bit-equal to an
uninterrupted run — the PR-2/PR-5 kill-window contract extended to the
stats leaves), the typed journal events replacing the legacy flow's
silent prints, the runner's health streaming, and both export layouts
(legacy statistics.h5 root + per-member engine groups) through the plot
reader."""

import os
import subprocess
import sys

import numpy as np
import pytest

from rustpde_mpi_tpu import (
    Navier2D,
    NavierEnsemble,
    ResilientRunner,
    Statistics,
    export_stats,
)
from rustpde_mpi_tpu.config import StabilityConfig, StatsConfig
from rustpde_mpi_tpu.models.stats import HEALTH_NAMES, StatsEngine
from rustpde_mpi_tpu.telemetry import metrics as tm
from rustpde_mpi_tpu.utils import checkpoint as cp
from rustpde_mpi_tpu.utils.journal import JournalWriter, read_journal

h5py = pytest.importorskip("h5py")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the tier-canonical tiny shape (model_builders): every stats-armed test
# shares stride=2 on 17^2/dt=0.01 so the whole file compiles each stats
# entry point once per pytest process
from model_builders import build_rbc17 as _build

_STRIDE = 2


def _armed(stride=_STRIDE):
    m = _build()
    m.set_stats(StatsConfig(stride=stride))
    return m


def _assert_state_equal(a, b):
    for name in a._fields:
        assert np.array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        ), name


def _assert_stats_equal(pa, pb):
    for name in pa.stats_state._fields:
        assert np.array_equal(
            np.asarray(getattr(pa.stats_state, name)),
            np.asarray(getattr(pb.stats_state, name)),
        ), name
    assert np.array_equal(
        np.asarray(pa._stats_tick), np.asarray(pb._stats_tick)
    )


# -- the hard contract: stats-on stepping is bit-identical to stats-off -------


def test_stats_on_bit_identical_to_stats_off_and_matches_legacy():
    """The accumulators only READ the stepped state: the committed
    trajectory must be EXACTLY equal (float equality) with the engine
    armed, the sample counter follows the stride — and over that same
    trajectory the engine's running averages of the legacy-parity set
    (T/ux/uy spectral sums + the pointwise Nusselt field) match the eager
    models/statistics.py accumulator sampling the stats-off twin at the
    same cadence, to fp tolerance."""
    on, off = _armed(), _build()
    on.update_n(12)
    legacy = Statistics(off, _STRIDE * off.dt, 1.0)
    for _ in range(12 // _STRIDE):
        off.update_n(_STRIDE)
        legacy.update(off)
    _assert_state_equal(on.state, off.state)
    n = float(np.asarray(on.stats_state.samples)[0])
    assert n == 12 // _STRIDE == legacy.num_save
    assert int(np.asarray(on._stats_tick)[0]) == 12
    for e, l in (
        ("t_sum", "t_avg"),
        ("ux_sum", "ux_avg"),
        ("uy_sum", "uy_avg"),
        ("nusselt_sum", "nusselt"),
    ):
        a = np.asarray(getattr(on.stats_state, e)) / n
        b = np.asarray(getattr(legacy, l))
        assert np.abs(a - b).max() <= 1e-12 * max(np.abs(b).max(), 1.0), e


def test_stats_governed_bit_identical_and_survives_rollback_contract():
    """Sentinels + stats share one scanned chunk (the production shape):
    the governed trajectory stays bit-identical to a governed stats-off
    run, and the sums accumulate on the sentinel carry."""
    on, off = _armed(), _build()
    for m in (on, off):
        m.set_stability(StabilityConfig())
    on.update_n(8)
    off.update_n(8)
    _assert_state_equal(on.state, off.state)
    assert float(np.asarray(on.stats_state.samples)[0]) == 8 // _STRIDE


def test_stats_ensemble_bit_identical_per_member_windows_and_refill():
    """Vmapped engine: member trajectories bit-equal to a stats-off
    ensemble, per-member sample counters, and a ``set_member`` lane refill
    resets ONLY that member's averaging window."""
    on = NavierEnsemble(_armed(), [_build().state for _ in range(2)])
    off = NavierEnsemble(_build(), [_build().state for _ in range(2)])
    assert on.stats_armed and not off.stats_armed
    on.update_n(8)
    off.update_n(8)
    _assert_state_equal(on.state, off.state)
    samples = np.asarray(on.stats_state.samples).reshape(-1)
    assert samples.tolist() == [4.0, 4.0]
    keep = np.asarray(on.stats_state.t_sum)[0].copy()
    on.set_member(1, _build().state)
    samples = np.asarray(on.stats_state.samples).reshape(-1)
    assert samples.tolist() == [4.0, 0.0]
    assert np.array_equal(np.asarray(on.stats_state.t_sum)[0], keep)


# -- layout generality --------------------------------------------------------


def test_stats_spectra_natural_mode_order_on_split_layout(monkeypatch):
    """Review regression: split-Fourier storage is [Re | Im] half-blocks,
    so a naive 'top third of stored rows' tail reads Im parts of mid-range
    modes instead of high wavenumbers.  The engine folds per-mode energies
    into natural ascending order: the forced-split model's accumulated
    spectra (and the tail sentinels) match the complex default's to fp
    (the two trajectories are equal to ~1e-15, tests/test_split.py)."""

    def build():
        m = Navier2D(16, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=True)
        m.set_velocity(0.1, 1.0, 1.0)
        m.set_temperature(0.1, 1.0, 1.0)
        m.set_stats(StatsConfig(stride=2))
        m.update_n(8)
        return m

    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    split = build()
    from rustpde_mpi_tpu.bases import BaseKind

    assert split.temp_space.base_kind(0) == BaseKind.FOURIER_R2C_SPLIT
    monkeypatch.delenv("RUSTPDE_FORCE_TPU_PATH")
    cplx = build()
    for leaf in ("spec_x", "spec_y"):
        a = np.asarray(getattr(split.stats_state, leaf))
        b = np.asarray(getattr(cplx.stats_state, leaf))
        assert a.shape == b.shape, leaf  # per-MODE rows, not storage rows
        assert np.abs(a - b).max() <= 1e-9 * np.abs(b).max(), leaf
    hs, hc = split.stats_summary(), cplx.stats_summary()
    for k in HEALTH_NAMES:
        if k.startswith("bl_"):
            continue  # discrete grid-point counts may flip on an fp tie
        assert hs[k] == pytest.approx(hc[k], rel=1e-6, abs=1e-12), k


def test_stats_engine_rejects_non_dns():
    class Fake:
        MODEL_KIND = "lnse"

    with pytest.raises(TypeError, match="not supported"):
        StatsEngine(Fake())


# -- checkpoint durability ----------------------------------------------------


def test_stats_gathered_checkpoint_roundtrip_bit_equal(tmp_path):
    """Gathered single-file snapshots carry the stats leaves exactly: a
    restore + continued stepping is bit-equal to the uninterrupted run."""
    a = _armed()
    a.update_n(6)
    path = str(tmp_path / "snap.h5")
    cp.write_snapshot(a, path)
    b = _armed()
    cp.read_snapshot(b, path)
    _assert_stats_equal(a, b)
    a.update_n(6)
    b.update_n(6)
    _assert_state_equal(a.state, b.state)
    _assert_stats_equal(a, b)


def test_stats_sharded_checkpoint_roundtrip_and_legacy_restart(tmp_path):
    """The sharded two-phase format carries the ``stats/`` datasets
    bit-exactly; a sharded checkpoint written BEFORE the engine was armed
    restores the state exactly and restarts the averaging window at zero
    instead of failing."""
    a = _armed()
    a.update_n(6)
    path = str(tmp_path / "ckpt_0000000006.h5")
    cp.write_sharded_snapshot(a, path, step=6)
    b = _armed()
    cp.read_sharded_snapshot(b, path)
    _assert_stats_equal(a, b)
    _assert_state_equal(a.state, b.state)
    # stats-off-written checkpoint into an armed model: window restarts
    off = _build()
    off.update_n(6)
    old = str(tmp_path / "ckpt_0000000007.h5")
    cp.write_sharded_snapshot(off, old, step=6)
    c = _armed()
    c.update_n(4)  # non-zero sums that must reset
    cp.read_sharded_snapshot(c, old)
    _assert_state_equal(off.state, c.state)
    assert float(np.asarray(c.stats_state.samples)[0]) == 0.0
    assert int(np.asarray(c._stats_tick)[0]) == 0


def test_stats_ensemble_checkpoint_roundtrip_bit_equal(tmp_path):
    """Per-member gathered snapshots carry the stacked stats leaves."""
    a = NavierEnsemble(_armed(), [_build().state for _ in range(2)])
    a.update_n(6)
    path = str(tmp_path / "ens.h5")
    cp.write_ensemble_snapshot(a, path)
    b = NavierEnsemble(_armed(), [_build().state for _ in range(2)])
    cp.read_ensemble_snapshot(b, path)
    _assert_stats_equal(a, b)
    a.update_n(6)
    b.update_n(6)
    _assert_state_equal(a.state, b.state)
    _assert_stats_equal(a, b)


_KILL_CHILD = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["RUSTPDE_X64"] = "1"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
from rustpde_mpi_tpu import Navier2D, ResilientRunner, config
from rustpde_mpi_tpu.config import StatsConfig
config.enable_compilation_cache()

m = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
m.set_velocity(0.1, 1.0, 1.0); m.set_temperature(0.1, 1.0, 1.0)
m.write_intervall = 1e9
m.set_stats(StatsConfig(stride=2))
# host-scoped kill = hard SIGKILL at global step 12 (utils/faults.py) —
# checkpoints exist at the 0.05 save cadence (steps 5 and 10) before it
ResilientRunner(
    m, max_time=0.3, save_intervall=0.05, run_dir=sys.argv[1],
    checkpoint_every_s=None, max_chunk_steps=4, fault="kill@12:host0",
).run()
os._exit(1)  # unreachable: the SIGKILL fired mid-run
"""


@pytest.mark.slow
def test_stats_sigkill_resume_bit_equal_to_uninterrupted(tmp_path):
    """The durability headliner (acceptance criterion): a child process is
    SIGKILLed mid-campaign — no drain, no final checkpoint — and the
    resumed run's final state AND running averages are bit-equal to an
    uninterrupted run of the same horizon.  This is the PR-2/PR-5
    kill-window contract extended to the stats leaves (slow tier, like
    those suites' own kill e2e legs; the fast tier pins the same
    mechanism via the gathered/sharded roundtrip bit-equality above)."""
    run_dir = str(tmp_path / "killed")
    proc = subprocess.run(
        [sys.executable, "-c", _KILL_CHILD.format(repo=_REPO), run_dir],
        capture_output=True,
        text=True,
        timeout=500,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == -9, (proc.returncode, proc.stderr[-2000:])
    assert cp.latest_checkpoint(run_dir) is not None
    resumed = _armed()
    r2 = ResilientRunner(
        resumed,
        max_time=0.3,
        save_intervall=0.05,
        run_dir=run_dir,
        checkpoint_every_s=None,
        max_chunk_steps=4,
    )
    s2 = r2.run()
    assert s2["outcome"] == "done" and r2.resumed
    straight = _armed()
    s1 = ResilientRunner(
        straight,
        max_time=0.3,
        save_intervall=0.05,
        run_dir=str(tmp_path / "straight"),
        checkpoint_every_s=None,
        max_chunk_steps=4,
    ).run()
    assert s1["outcome"] == "done" and s1["step"] == s2["step"]
    _assert_state_equal(straight.state, resumed.state)
    _assert_stats_equal(straight, resumed)
    assert s1["stats"] == s2["stats"]  # the health readout agrees too


def test_stats_span_exact_across_dt_rung_moves():
    """Review regression: the dKE/dt window span is accumulated per sample
    at that sample's OWN stride*dt (the accumulator is rebuilt per rung),
    so a governor ladder move mid-window keeps the kinetic-energy budget
    exact — reconstructing the span from the current dt would mis-scale
    the old-rung samples by the rung ratio."""
    m = _armed()
    m.update_n(8)  # 4 samples at dt=0.01
    m.set_dt(0.005)
    m.update_n(8)  # 4 samples at dt=0.005
    span = float(np.asarray(m.stats_state.span_sum)[0])
    first = float(np.asarray(m.stats_state.span_first)[0])
    assert span == pytest.approx(4 * _STRIDE * 0.01 + 4 * _STRIDE * 0.005)
    assert first == pytest.approx(_STRIDE * 0.01)  # anchored at sample 1
    assert float(np.asarray(m.stats_state.samples)[0]) == 8


def test_kinetic_energy_budget_closes_after_spin_up():
    """The budget readout means something: past the spin-up transient the
    kinetic-energy balance (buoyancy production - viscous dissipation -
    dKE/dt, over the larger of the first two) closes to under 5 % (0.7 %
    here at 33^2; 5-12 % at 17^2, which is too coarse to close it), over a
    window that holds samples, and the
    Nu-consistency residual, which converges only in stationarity, is
    finite."""
    m = Navier2D(33, 33, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
    m.set_velocity(0.1, 1.0, 1.0)
    m.set_temperature(0.1, 1.0, 1.0)
    m.set_stats(StatsConfig(stride=_STRIDE))
    m.update_n(32)
    m.reset_stats()  # the window covers the flow after the transient only
    m.update_n(64)
    health = m.stats_summary()
    assert health["samples"] == 64 // _STRIDE
    assert 0.0 <= health["ke_residual"] < 0.05
    assert np.isfinite(health["nu_residual"]) and health["nu_residual"] < 3.0


@pytest.mark.slow
def test_stats_resolution_elastic_restore_restarts_window(tmp_path, capsys):
    """Review regression: the gathered format restores elastically across
    resolutions (state leaves interpolate) — stale-shaped stats sums can't,
    so the averaging window restarts at zero instead of handing the stats
    chunk a shape mismatch."""
    small = _armed()
    small.update_n(4)
    path = str(tmp_path / "small.h5")
    cp.write_snapshot(small, path)
    big = Navier2D(33, 32, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
    big.set_velocity(0.1, 1.0, 1.0)
    big.set_temperature(0.1, 1.0, 1.0)
    big.write_intervall = 1e9
    big.set_stats(StatsConfig(stride=_STRIDE))
    big.update_n(4)  # non-zero sums that must reset
    cp.read_snapshot(big, path)
    assert float(np.asarray(big.stats_state.samples)[0]) == 0.0
    assert "restart from zero" in capsys.readouterr().out
    big.update_n(4)  # the stats chunk still runs on the restored state
    assert float(np.asarray(big.stats_state.samples)[0]) == 4 // _STRIDE


# -- typed events replacing the legacy flow's silent prints -------------------


def test_legacy_stats_mismatch_is_typed_journal_event(tmp_path, capsys):
    """``Statistics.update`` rejecting a time-regressed sample journals a
    typed ``stats_mismatch`` + bumps the telemetry counter (the reference
    print is kept), so a run can't silently stop averaging."""
    model = _build()
    model.update_n(2)
    stats = Statistics(model, 0.01, 1.0)
    stats.tot_time = 1e9  # a mismatched restart: navier time < stat time
    writer = JournalWriter(str(tmp_path / "journal.jsonl"))
    model.journal_writer = writer
    before = tm.counter("stats_mismatch_total").value
    try:
        stats.update(model)
    finally:
        model.journal_writer = None
        writer.close()
    assert stats.num_save == 0  # averages NOT updated
    assert tm.counter("stats_mismatch_total").value == before + 1
    events = read_journal(str(tmp_path / "journal.jsonl"))
    assert events[-1]["event"] == "stats_mismatch"
    assert events[-1]["stat_time"] == 1e9
    assert "time mismatch" in capsys.readouterr().out


def test_legacy_stats_write_failure_is_typed_journal_event(
    tmp_path, monkeypatch, capsys
):
    """The IO callback's swallowed ``unable to write statistics`` print
    becomes a typed ``stats_write_failed`` + counter; the run survives
    (reference never-fatal semantics)."""
    from rustpde_mpi_tpu.utils import navier_io

    monkeypatch.chdir(tmp_path)
    model = _build()
    model.update_n(2)
    stats = Statistics(model, 0.01, 0.01)  # update+write at every boundary
    model.statistics = stats
    monkeypatch.setattr(
        Statistics, "write", lambda self, path: (_ for _ in ()).throw(
            OSError("disk full")
        )
    )
    writer = JournalWriter(str(tmp_path / "journal.jsonl"))
    model.journal_writer = writer
    before = tm.counter("stats_write_failed_total").value
    try:
        navier_io.callback(model, suppress_io=True)
    finally:
        model.journal_writer = None
        model.statistics = None
        writer.close()
    assert tm.counter("stats_write_failed_total").value == before + 1
    events = read_journal(str(tmp_path / "journal.jsonl"))
    row = next(e for e in events if e["event"] == "stats_write_failed")
    assert "disk full" in row["error"]
    assert "unable to write statistics" in capsys.readouterr().out


# -- runner health streaming --------------------------------------------------


def test_runner_streams_health_gauges_and_threshold_events(tmp_path):
    """A stats-armed runner resolves the lag=1 health future each chunk
    boundary (the save-intervall cadence — the same boundaries checkpoints
    ride): the summary carries the HEALTH_NAMES readout, the stats_*
    gauges are live, and absurdly low thresholds make the typed
    ``resolution_warning`` / ``budget_drift`` events fire exactly once per
    excursion (crossing latch)."""
    model = _build()
    model.set_stats(
        StatsConfig(stride=_STRIDE, tail_warn=1e-12, budget_warn=1e-12)
    )
    runner = ResilientRunner(
        model,
        max_time=0.16,
        save_intervall=0.04,
        run_dir=str(tmp_path / "run"),
        checkpoint_every_s=None,
        max_chunk_steps=4,
    )
    summary = runner.run()
    st = summary["stats"]
    assert set(st) == set(HEALTH_NAMES)
    assert st["samples"] == 16 // _STRIDE
    assert np.isfinite(st["nu_residual"]) and np.isfinite(st["ke_residual"])
    snap = tm.REGISTRY.snapshot()
    assert "stats_samples" in snap and "stats_budget_residual" in snap
    events = read_journal(str(tmp_path / "run" / "journal.jsonl"))
    names = [e["event"] for e in events]
    assert names.count("resolution_warning") == 1  # latched, not per-boundary
    assert names.count("budget_drift") == 1
    warn = next(e for e in events if e["event"] == "resolution_warning")
    assert warn["field"] in ("temp", "ux", "uy") and warn["axis"] in ("x", "y")
    drift = next(e for e in events if e["event"] == "budget_drift")
    assert drift["threshold"] == 1e-12 and drift["samples"] >= 2


# -- serve: per-request stats summaries ---------------------------------------


def test_serve_done_records_carry_stats_summary(tmp_path):
    """``ServeConfig.stats`` arms the engine on every DNS campaign
    ensemble; each done record then carries the member's health vector at
    completion (captured before any lane is released or refilled)."""
    from rustpde_mpi_tpu.config import ServeConfig
    from rustpde_mpi_tpu.serve import SimServer

    srv = SimServer(
        ServeConfig(
            run_dir=str(tmp_path / "serve"),
            slots=2,
            chunk_steps=4,
            checkpoint_every_s=None,
            http_port=None,
            stats=StatsConfig(stride=_STRIDE),
        )
    )
    req = dict(ra=1e4, pr=1.0, nx=17, ny=17, dt=0.01, horizon=0.1, bc="rbc")
    ids = [srv.submit(dict(req, seed=s)).id for s in range(3)]
    summary = srv.serve()
    assert summary["completed"] == 3 and summary["failed"] == 0
    for rid in ids:
        st = srv.result(rid)["stats"]
        assert set(st) == set(HEALTH_NAMES)
        assert st["samples"] >= 1
        assert np.isfinite(st["nu_plate_avg"]) and np.isfinite(st["nu_residual"])


# -- exports + plot reader ----------------------------------------------------


@pytest.mark.slow
def test_export_layouts_and_plot_reader(tmp_path):
    """``export_stats`` writes the legacy root layout for a single model
    and ``member{i}/`` groups for an ensemble; plot/plot_statistics.py
    renders legacy files, engine ensemble exports (``--member``) and the
    engine's ``--profiles`` extras."""
    single = _armed()
    single.update_n(4)
    solo_h5 = str(tmp_path / "solo.h5")
    export_stats(single, solo_h5)
    with h5py.File(solo_h5, "r") as f:
        assert "temp/v" in f and "nusselt/v" in f  # legacy reference layout
        assert "profiles/t_mean" in f and "spectra/x" in f
        assert int(f.attrs["stride"]) == _STRIDE
    ens = NavierEnsemble(_armed(), [_build().state for _ in range(2)])
    ens.update_n(4)
    ens_h5 = str(tmp_path / "ens.h5")
    export_stats(ens, ens_h5)
    with h5py.File(ens_h5, "r") as f:
        assert int(np.asarray(f["members"])) == 2
        assert "member0/temp/v" in f and "member1/profiles/t_mean" in f
        # the RUNNING ensemble's clock, not the frozen template model's
        assert float(np.asarray(f["member0/tot_time"])) == pytest.approx(
            ens.time
        )
        assert float(np.asarray(f["member0/avg_time"])) == pytest.approx(
            2 * _STRIDE * 0.01  # span accumulated per sample at its own dt
        )
    out = str(tmp_path / "plot.png")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(_REPO, "plot", "plot_statistics.py"),
            "--file", ens_h5, "--member", "1", "--profiles", "--out", out,
        ],
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    for suffix in ("", "_nusselt", "_profiles"):
        assert os.path.exists(str(tmp_path / f"plot{suffix}.png")), suffix
    # layout selection (in-process: matplotlib stays lazy): legacy root,
    # member groups, out-of-range member as a clean typed exit
    sys.path.insert(0, os.path.join(_REPO, "plot"))
    try:
        from plot_statistics import stats_root
    finally:
        sys.path.pop(0)
    with h5py.File(solo_h5, "r") as f:
        assert stats_root(f, 0) is f
    with h5py.File(ens_h5, "r") as f:
        assert stats_root(f, 1).name == "/member1"
        with pytest.raises(SystemExit, match="out of range"):
            stats_root(f, 7)


def test_export_requires_armed_engine():
    with pytest.raises(RuntimeError, match="armed stats engine"):
        export_stats(_build(), "/tmp/never_written.h5")


# -- API pin ------------------------------------------------------------------


def test_stats_api_exports():
    """The physics-observability surface is importable from the package
    root + the models package (API pin, mirrors the workloads pin)."""
    import rustpde_mpi_tpu as rp
    from rustpde_mpi_tpu import models as mdl

    for name in ("StatsEngine", "StatsState", "export_stats"):
        assert hasattr(rp, name), name
    for name in ("HEALTH_NAMES", "StatsEngine", "StatsState", "export_stats"):
        assert hasattr(mdl, name), name
    assert "nu_residual" in HEALTH_NAMES and "samples" in HEALTH_NAMES
    from rustpde_mpi_tpu import config as cfg

    knobs = set(cfg.env_knobs())
    assert {
        "RUSTPDE_STATS",
        "RUSTPDE_STATS_STRIDE",
        "RUSTPDE_STATS_TAIL_WARN",
        "RUSTPDE_STATS_BUDGET_WARN",
    } <= knobs
