"""Stability-governor tests: on-device CFL/energy sentinels, pre-divergence
early-exit with in-memory rollback, the rung-cached dt ladder, regrowth,
ensemble batch-max CFL reduction, and the governed ResilientRunner paths
(utils/governor.py + the sentinel chunks in models/navier.py,
models/ensemble.py)."""

import json
import os

import numpy as np
import pytest

from rustpde_mpi_tpu import (
    DivergenceError,
    Navier2D,
    NavierEnsemble,
    ResilientRunner,
    integrate,
)
from rustpde_mpi_tpu.config import NavierConfig, ResilienceConfig, StabilityConfig
from rustpde_mpi_tpu.utils.governor import (
    ChunkStatus,
    DtLadder,
    StabilityGovernor,
)
from rustpde_mpi_tpu.utils.resilience import FaultPlan


def _build(dt=0.01, stability=None):
    model = Navier2D(17, 17, 1e4, 1.0, dt, 1.0, "rbc", periodic=False)
    model.set_velocity(0.1, 1.0, 1.0)
    model.set_temperature(0.1, 1.0, 1.0)
    model.write_intervall = 1e9
    if stability is not None:
        model.set_stability(stability)
    return model


def _events(run_dir):
    with open(os.path.join(run_dir, "journal.jsonl"), encoding="utf-8") as fh:
        return [json.loads(line) for line in fh]


def _status(**kw):
    base = dict(
        requested=50,
        steps_done=50,
        finite=True,
        cfl_ok=True,
        pre_divergence=False,
        cfl_max=0.1,
        ke=1.0,
        ke_growth_max=1.0,
        div_max=0.01,
        dt=0.01,
    )
    base.update(kw)
    return ChunkStatus(**base)


# -- ladder + control law (host-side units) -----------------------------------


def test_dt_ladder_quantization():
    lad = DtLadder(1e-2, ratio=2.0, dt_min=1e-3, dt_max=4e-2)
    assert lad.dt(0) == 1e-2  # the anchor is always rung 0 exactly
    assert lad.top == 2 and lad.bottom == -3
    assert lad.dt(lad.top) == pytest.approx(4e-2)
    assert lad.dt(lad.bottom) == pytest.approx(1.25e-3)
    assert lad.dt(-99) == lad.dt(lad.bottom)  # clamped
    # every visit to a rung yields the identical float (the cache contract)
    assert lad.dt(-1) is lad.dt(-1) or lad.dt(-1) == lad.dt(-1)
    assert lad.rung_for(1e-2) == 0
    assert lad.rung_for(5.1e-3) == -1  # nearest in log space
    assert lad.rung_for(1e-9) == lad.bottom
    # rungs needed to bring an observed CFL back to target
    assert lad.rungs_to_target(2.0, 0.5) == 2
    assert lad.rungs_to_target(0.9, 0.5) == 1
    assert lad.rungs_to_target(0.3, 0.5) == 1  # always at least one
    assert lad.rungs_to_target(float("inf"), 0.5) == len(lad)
    with pytest.raises(ValueError):
        DtLadder(1e-2, ratio=0.9)
    with pytest.raises(ValueError):
        DtLadder(1e-2, dt_min=2e-2)  # dt_min above the anchor


def test_governor_control_law():
    cfg = StabilityConfig(
        target_cfl=0.5, max_cfl=1.0, ladder_ratio=2.0, dt_min=1e-3, grow_after=2
    )
    gov = StabilityGovernor(cfg, 1e-2)
    # healthy chunk in the dead band: no action
    assert gov.on_chunk(_status(cfl_max=0.4)).action == "ok"
    # pre-divergence: retry at a rung that predicts cfl <= target
    d = gov.on_chunk(
        _status(pre_divergence=True, cfl_ok=False, cfl_max=1.6, steps_done=3)
    )
    assert d.action == "retry"
    assert d.dt == pytest.approx(2.5e-3)  # 1.6 -> 0.4 needs 2 rungs
    assert gov.health.pre_divergence_catches == 1
    assert gov.health.rollbacks_avoided == 1
    # proactive shrink above shrink_cfl (default 0.85*max_cfl), no rollback
    d = gov.on_chunk(_status(cfl_max=0.9, dt=2.5e-3))
    assert d.action == "adjust" and d.dt < 2.5e-3
    # regrowth: grow_after healthy chunks with predicted cfl under target
    assert gov.on_chunk(_status(cfl_max=0.2, dt=d.dt)).action == "ok"
    d2 = gov.on_chunk(_status(cfl_max=0.2, dt=d.dt))
    assert d2.action == "adjust" and d2.dt == pytest.approx(2.0 * d.dt)
    # NaN chunks belong to the reactive machinery
    assert gov.on_chunk(_status(finite=False, cfl_max=float("nan"))).action == "ok"
    # bottom rung still tripping: give up (reactive path takes over)
    gov.rung = gov.ladder.bottom
    d = gov.on_chunk(_status(pre_divergence=True, cfl_ok=False, cfl_max=2.0))
    assert d.action == "give_up"


def test_align_floors_and_keeps_trajectory_honest():
    """align() (reactive rollback / resume re-anchoring) must round DOWN —
    nearest-rung rounding would restore the very dt that just diverged for
    any backoff milder than sqrt(ratio) — and must record on-ladder external
    changes in the health trajectory instead of overwriting history."""
    cfg = StabilityConfig(dt_min=1e-4)
    gov = StabilityGovernor(cfg, 2e-3)
    # a 0.8x reactive backoff: nearest rung would be 0 (the diverged dt!)
    assert gov.align(1.6e-3, step=5) == pytest.approx(1e-3)
    assert gov.rung == -1
    assert gov.health.dt_trajectory[-1] == (5, pytest.approx(1e-3))
    # an exactly-on-ladder backoff (the 0.5 x ratio-2 default) needs no
    # set_dt but still lands in the trajectory/extrema bookkeeping
    gov2 = StabilityGovernor(cfg, 2e-3)
    d = gov2.on_chunk(_status(cfl_max=0.9, dt=2e-3), step=10)
    assert d.action == "adjust"
    n_before = len(gov2.health.dt_trajectory)
    assert gov2.align(2.5e-4, step=30) is None
    assert len(gov2.health.dt_trajectory) == n_before + 1
    assert gov2.health.dt_trajectory[-1] == (30, pytest.approx(2.5e-4))
    assert gov2.health.dt_trajectory[-2][0] == 10  # history preserved
    assert gov2.health.dt_min_seen == pytest.approx(2.5e-4)


def test_governor_kills_persistently_pinned_members():
    cfg = StabilityConfig(member_pin_patience=2, dt_min=1e-3)
    gov = StabilityGovernor(cfg, 1e-2)
    pinned = _status(
        pre_divergence=True,
        cfl_ok=False,
        cfl_max=1.5,
        cfl_members=(0.1, 1.5, 0.2),
        pinned=(False, True, False),
    )
    # first pin: a dt drop is tried
    assert gov.on_chunk(pinned).action == "retry"
    # second consecutive pin of the SAME member: feed it to respawn_dead
    d = gov.on_chunk(pinned)
    assert d.action == "kill_members" and d.members == (1,)
    assert gov.health.members_killed == 1
    # a healthy chunk resets the pin counters
    gov2 = StabilityGovernor(cfg, 1e-2)
    assert gov2.on_chunk(pinned).action == "retry"
    assert gov2.on_chunk(_status()).action == "ok"
    assert gov2.on_chunk(pinned).action == "retry"  # count restarted


# -- sentinel chunks on the model ---------------------------------------------


def test_governed_stable_run_bit_identical(tmp_path):
    """A governed run at an already-stable dt must be BIT-identical to the
    ungoverned run: the sentinel step variant adds reductions over arrays
    the step already materializes, never touching the state math, and the
    governor in the dead band issues no dt change."""
    r1 = ResilientRunner(
        _build(),
        max_time=0.2,
        save_intervall=0.05,
        run_dir=str(tmp_path / "plain"),
        checkpoint_every_s=None,
    )
    s1 = r1.run()
    r2 = ResilientRunner(
        _build(),
        max_time=0.2,
        save_intervall=0.05,
        run_dir=str(tmp_path / "governed"),
        checkpoint_every_s=None,
        stability=StabilityConfig(),
    )
    s2 = r2.run()
    assert s2["outcome"] == "done" and s1["outcome"] == "done"
    for attr in ("temp", "velx", "vely", "pres", "pseu"):
        np.testing.assert_array_equal(
            np.asarray(getattr(r1.pde.state, attr)),
            np.asarray(getattr(r2.pde.state, attr)),
            err_msg=attr,
        )
    health = s2["health"]
    assert health["pre_divergence_catches"] == 0
    assert health["dt_adjusts"] == 0
    assert health["cfl_max"] < 1.0
    assert s1["health"] is None  # ungoverned runs carry no telemetry


def test_spike_caught_pre_divergence_in_memory(tmp_path):
    """The acceptance demo: a deterministic velocity spike.  Governed, the
    CFL sentinel early-exits the chunk BEFORE NaNs, the rollback happens in
    memory and dt descends the ladder — zero reactive checkpoint restores.
    Ungoverned, the same spike grows into NaN divergence and needs the
    checkpoint-rollback path (>= 1 retry)."""
    gov_dir = str(tmp_path / "gov")
    r1 = ResilientRunner(
        _build(),
        max_time=0.5,
        save_intervall=0.05,
        run_dir=gov_dir,
        checkpoint_every_s=None,
        max_retries=2,
        fault="spike@10",
        spike_factor=200.0,
        stability=StabilityConfig(),
    )
    s1 = r1.run()
    assert s1["outcome"] == "done"
    assert s1["retries"] == 0  # NO reactive rollback
    assert s1["time"] == pytest.approx(0.5)
    assert np.isfinite(s1["nu"])
    assert s1["dt"] < 0.01  # descended the ladder
    events = [e["event"] for e in _events(gov_dir)]
    assert "pre_divergence" in events and "dt_adjust" in events
    assert "retry" not in events and "divergence" not in events
    # exactly the anchor + final checkpoints — recovery wrote none
    assert events.count("checkpoint") == 2
    health = s1["health"]
    assert health["pre_divergence_catches"] >= 1
    assert health["rollbacks_avoided"] >= 1
    assert health["cfl_max"] > 1.0  # the spike was seen...
    assert health["dt_trajectory"][0][1] == 0.01  # ...and the dt ladder walked

    ungov_dir = str(tmp_path / "ungov")
    r2 = ResilientRunner(
        _build(),
        max_time=0.5,  # the spike needs ~0.4 time units to grow into NaN

        save_intervall=0.05,
        run_dir=ungov_dir,
        checkpoint_every_s=None,
        max_retries=3,
        fault="spike@10",
        spike_factor=200.0,
    )
    try:
        s2 = r2.run()
        assert s2["retries"] >= 1  # survived, but only via checkpoint rollback
    except DivergenceError:
        pass  # or it never recovered — either way the governed run wins
    assert "divergence" in [e["event"] for e in _events(ungov_dir)]


def test_ungoverned_sentinels_break_cleanly():
    """Sentinels armed but no governor: a CFL trip rolls the chunk back,
    latches exit(), and plain integrate() stops at the finite rolled-back
    state instead of stepping into NaNs or looping forever."""
    model = _build(stability=StabilityConfig())
    model.update_n(4)
    model.state = model.state._replace(
        velx=model.state.velx * 200.0, vely=model.state.vely * 200.0
    )
    model._obs_cache = None
    t_spike = model.time
    assert integrate(model, 0.3, None) == "break"
    assert model.time == t_spike  # rolled back, not advanced
    assert bool(np.isfinite(np.asarray(model.state.temp)).all())
    model.clear_pre_divergence()
    assert not model.exit()


@pytest.mark.slow
def test_dt_ladder_cache_bounds_rejits():
    """Cycling the governor's dt ladder re-traces/refactorizes each rung at
    most once: revisits swap the cached artifacts back in (and the restored
    jit closures keep their identity, so XLA's executable cache hits)."""
    model = _build(stability=StabilityConfig())
    model.update_n(2)
    rungs = [0.01, 0.005, 0.0025, 0.00125]
    base = model.recompile_count
    for _ in range(3):  # three full down-up sweeps
        for dt in rungs + rungs[::-1]:
            model.set_dt(dt)
    assert model.recompile_count - base == len(rungs) - 1  # only first visits
    # cached rungs step correctly after a revisit
    model.set_dt(0.005)
    status = model.update_n(3)
    assert not status.pre_divergence and status.dt == 0.005
    fresh = _build(dt=0.005)
    fresh.state = model.state
    model.update_n(4)
    fresh.update_n(4)
    np.testing.assert_allclose(
        np.asarray(model.state.temp), np.asarray(fresh.state.temp), atol=1e-13
    )


@pytest.mark.slow
def test_ensemble_batch_max_cfl_matches_serial():
    """The ensemble's per-member CFL sentinel must equal stepping each
    member through the single-run sentinel path, and the batch reduction is
    exactly the max over members (members share the baked dt)."""
    model = _build(stability=StabilityConfig())
    ens = NavierEnsemble.from_seeds(model, seeds=range(3))
    members0 = [ens.member_state(i) for i in range(3)]
    status = ens.update_n(6)
    assert status.cfl_members is not None and len(status.cfl_members) == 3
    assert status.cfl_max == max(status.cfl_members)
    for i, m0 in enumerate(members0):
        solo = _build(stability=StabilityConfig())
        solo.state = m0
        r = solo.update_n(6)
        np.testing.assert_allclose(
            status.cfl_members[i], r.cfl_max, rtol=1e-12, err_msg=f"member {i}"
        )


def test_ensemble_spike_rolls_back_and_respawn_reproducible(tmp_path):
    """One spiked member pins the batch CFL ceiling: the whole chunk rolls
    back in memory (shared dt), mark_dead + respawn_dead revive it, and the
    config-carried respawn seed makes the revived state reproducible."""
    import jax

    def spiked_ensemble():
        model = _build(stability=StabilityConfig())
        ens = NavierEnsemble.from_seeds(model, seeds=range(3))
        ens.update_n(4)
        bad = jax.tree.map(lambda x: x * 300.0, ens.member_state(1))
        ens.set_member(1, bad._replace(temp=ens.member_state(1).temp))
        return ens

    ens = spiked_ensemble()
    snap = np.asarray(ens.state.velx).copy()
    status = ens.update_n(5)
    assert status.pre_divergence and status.pinned == (False, True, False)
    np.testing.assert_array_equal(np.asarray(ens.state.velx), snap)
    assert ens.exit()  # latched until a governor acts
    ens.clear_pre_divergence()
    ens.mark_dead([1])
    assert list(ens.alive()) == [True, False, True]
    ens.respawn_seed = 1234  # the config-carried stream
    assert ens.respawn_dead(amp=1e-3) == 1
    ens2 = spiked_ensemble()
    ens2.update_n(5)
    ens2.clear_pre_divergence()
    ens2.mark_dead([1])
    ens2.respawn_seed = 1234
    assert ens2.respawn_dead(amp=1e-3) == 1
    np.testing.assert_array_equal(
        np.asarray(ens.state.velx), np.asarray(ens2.state.velx)
    )


@pytest.mark.slow
def test_governor_climbs_back_up(tmp_path):
    """Regrowth: with headroom above the anchor (dt_max > dt0) and a calm
    flow, the governor climbs the ladder after each healthy stretch — the
    path the reactive backoff never had."""
    run_dir = str(tmp_path / "run")
    runner = ResilientRunner(
        _build(dt=0.0025),
        max_time=0.4,
        save_intervall=0.02,
        run_dir=run_dir,
        checkpoint_every_s=None,
        stability=StabilityConfig(dt_max=0.01, grow_after=2),
    )
    summary = runner.run()
    assert summary["outcome"] == "done"
    assert summary["dt"] > 0.0025  # climbed at least one rung
    grow = [
        e
        for e in _events(run_dir)
        if e["event"] == "dt_adjust" and "healthy" in e.get("reason", "")
    ]
    assert len(grow) >= 1
    assert summary["health"]["dt_max_seen"] > 0.0025


# -- reactive-path satellites -------------------------------------------------


def test_spike_fault_spec():
    plan = FaultPlan.from_spec("spike@7")
    assert (plan.kind, plan.step, plan.fired) == ("spike", 7, False)
    with pytest.raises(ValueError, match="spike"):
        FaultPlan.from_spec("warp@7")


def test_dt_min_floors_reactive_backoff_and_error_has_trajectory(tmp_path):
    """The compounding divergence backoff stops at the dt_min floor, and a
    retries-exhausted DivergenceError reports the journaled dt trajectory."""
    run_dir = str(tmp_path / "run")

    class AlwaysDiverges(ResilientRunner):
        def _rollback(self):
            super()._rollback()
            self.fault = FaultPlan.from_spec(f"nan@{self.step + 4}")

    runner = AlwaysDiverges(
        _build(),
        max_time=0.5,
        save_intervall=0.05,
        run_dir=run_dir,
        checkpoint_every_s=None,
        max_retries=3,
        dt_backoff=0.5,
        dt_min=0.004,
        fault="nan@4",
    )
    with pytest.raises(DivergenceError, match="dt trajectory") as err:
        runner.run()
    # 0.01 -> 0.005 -> floor 0.004 -> stays 0.004 (no denormal death spiral)
    assert runner.pde.get_dt() == pytest.approx(0.004)
    assert "retry" in str(err.value)
    retries = [e for e in _events(run_dir) if e["event"] == "retry"]
    assert [e["dt"] for e in retries] == pytest.approx([0.005, 0.004, 0.004])
    assert retries[-1]["dt_floor"] is True


@pytest.mark.slow
def test_governed_config_roundtrip(tmp_path):
    """StabilityConfig flows through NavierConfig/ResilienceConfig +
    from_config (as the dataclass, not an asdict casualty) and the governed
    runner works end to end off configs alone."""
    scfg = StabilityConfig(grow_after=2)
    rcfg = ResilienceConfig(
        run_dir=str(tmp_path / "run"),
        checkpoint_every_s=None,
        max_retries=1,
        respawn_seed=7,
        dt_min=1e-4,
        stability=scfg,
    )
    cfg = NavierConfig(nx=17, ny=17, ra=1e4, dt=0.01, resilience=rcfg, stability=scfg)
    model = Navier2D.from_config(cfg)
    assert model._stability is scfg  # armed at construction
    model.set_velocity(0.1, 1.0, 1.0)
    model.set_temperature(0.1, 1.0, 1.0)
    model.write_intervall = 1e9
    runner = ResilientRunner.from_config(
        model, cfg.resilience, max_time=0.1, save_intervall=0.05
    )
    assert runner.stability is scfg
    assert runner.dt_min == 1e-4 and runner.respawn_seed == 7
    summary = runner.run()
    assert summary["outcome"] == "done"
    assert summary["health"] is not None
    events = [e["event"] for e in _events(str(tmp_path / "run"))]
    assert "cfl" in events and "run_health" in events


# -- integrate save-window robustness (satellite) ------------------------------


class _FakePde:
    """Minimal Integrate implementer at a huge start time: exercises the
    absolute-boundary save-window test where the legacy ``t % save`` form
    has lost the float resolution for a half-dt window."""

    def __init__(self, t0, dt, chunked):
        self.time, self.dt = t0, dt
        self.calls = []
        if chunked:
            self.update_n = self._update_n

    def _update_n(self, n):
        self.time += n * self.dt

    def update(self):
        self.time += self.dt

    def get_time(self):
        return self.time

    def get_dt(self):
        return self.dt

    def callback(self):
        self.calls.append(self.time)

    def exit(self):
        return False


@pytest.mark.parametrize("chunked", [True, False])
def test_save_window_robust_at_large_t(chunked):
    t0 = 1_048_576.0  # 2^20: ulp territory where modulo windows get noisy
    pde = _FakePde(t0, dt=1e-3, chunked=chunked)
    status = integrate(pde, t0 + 1.0, save_intervall=0.1)
    assert status == "time_limit"
    # one callback per boundary, each within a half-dt of k*0.1
    assert len(pde.calls) == 10
    for t in pde.calls:
        k = round(t / 0.1)
        assert abs(t - k * 0.1) < pde.dt / 2.0
