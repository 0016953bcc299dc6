"""Resilience-harness tests: atomic crash-safe checkpoints, corrupt-file
rejection, NaN-divergence rollback with dt backoff, SIGTERM
checkpoint-then-exit + resume, dispatch watchdogs, and ensemble member
respawn (utils/resilience.py + the durable layer in utils/checkpoint.py)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from rustpde_mpi_tpu import (
    DispatchHang,
    DivergenceError,
    Navier2D,
    NavierEnsemble,
    ResilientRunner,
    integrate,
)
from rustpde_mpi_tpu.utils import checkpoint as cp
from rustpde_mpi_tpu.utils.resilience import FaultPlan, call_with_watchdog

h5py = pytest.importorskip("h5py")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# shared tier-wide builder + session-scoped stepped model (conftest.py):
# test_io_pipeline/test_sharded_ckpt/test_serve reuse the same jit shapes
from model_builders import build_rbc17 as _build


def _events(run_dir):
    with open(os.path.join(run_dir, "journal.jsonl"), encoding="utf-8") as fh:
        return [json.loads(line) for line in fh]


# -- durable checkpoints ------------------------------------------------------


def test_atomic_write_crash_safety(tmp_path, stepped_rbc17):
    """Kill the writer mid-``write_snapshot``: the previous checkpoint must
    still read back digest-clean (atomicity), with at worst a ``.tmp``
    leftover that the checkpoint listing ignores."""
    path = str(tmp_path / "ckpt_0000000002.h5")
    child = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["RUSTPDE_X64"] = "1"
import jax
from rustpde_mpi_tpu import Navier2D
from rustpde_mpi_tpu.utils import checkpoint as cp

m = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
m.set_velocity(0.1, 1.0, 1.0); m.set_temperature(0.1, 1.0, 1.0)
m.update_n(2)
path = sys.argv[1]
cp.write_snapshot(m, path, step=2)          # the checkpoint that must survive
cp.verify_snapshot(path)
m.update_n(2)

calls = [0]
orig = cp._write_array
def bomb(group, name, data):
    calls[0] += 1
    if calls[0] > 7:
        os._exit(9)                          # simulated preemption mid-write
    orig(group, name, data)
cp._write_array = bomb
cp.write_snapshot(m, path, step=4)           # must die before os.replace
os._exit(1)                                  # unreachable if the kill fired
""".format(repo=_REPO)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", child, path],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert proc.returncode == 9, proc.stderr
    # the step-2 checkpoint is intact and digest-clean
    attrs = cp.verify_snapshot(path)
    assert int(attrs["step"]) == 2
    stepped_rbc17.read(path)
    assert stepped_rbc17.time == pytest.approx(0.02)
    # listing skips any .tmp corpse the kill left behind
    assert cp.checkpoint_files(str(tmp_path)) == [path]


def test_truncated_file_rejected_and_latest_skips(tmp_path, stepped_rbc17):
    model = stepped_rbc17
    good = cp.checkpoint_path(str(tmp_path), 2)
    cp.write_snapshot(model, good, step=2)
    model.update_n(2)
    newer = cp.checkpoint_path(str(tmp_path), 4)
    cp.write_snapshot(model, newer, step=4)
    with open(newer, "r+b") as fh:
        fh.truncate(os.path.getsize(newer) // 2)
    with pytest.raises(cp.CheckpointError, match="truncated"):
        cp.verify_snapshot(newer)
    with pytest.raises(cp.CheckpointError):
        model.read(newer)
    # latest falls back to the previous valid checkpoint
    assert cp.latest_checkpoint(str(tmp_path)) == good


def test_digest_mismatch_rejected(tmp_path, stepped_rbc17):
    model = stepped_rbc17
    path = cp.checkpoint_path(str(tmp_path), 0)
    cp.write_snapshot(model, path, step=0)
    with h5py.File(path, "r+") as h5:
        h5["temp/v"][0, 0] = 1e6  # bit rot: content changed, digest not
    with pytest.raises(cp.CheckpointError, match="digest mismatch"):
        cp.verify_snapshot(path)
    with pytest.raises(cp.CheckpointError, match="digest mismatch"):
        model.read(path)
    assert cp.latest_checkpoint(str(tmp_path)) is None


def test_checkpoint_errors_are_typed(tmp_path, stepped_rbc17):
    """Malformed files raise CheckpointError naming the file and the missing
    group/dataset — not bare KeyError / h5py OSError."""
    model = stepped_rbc17
    empty = str(tmp_path / "empty.h5")
    with h5py.File(empty, "w"):
        pass
    with pytest.raises(cp.CheckpointError, match="ux"):
        model.read(empty)
    # a group with no datasets: the missing dataset is named
    partial = str(tmp_path / "partial.h5")
    with h5py.File(partial, "w") as h5:
        h5.require_group("ux")
    with pytest.raises(cp.CheckpointError, match="vhat"):
        model.read(partial)
    # not an HDF5 file at all
    garbage = str(tmp_path / "garbage.h5")
    with open(garbage, "wb") as fh:
        fh.write(b"not hdf5 at all")
    with pytest.raises(cp.CheckpointError, match="truncated"):
        model.read(garbage)
    # ensemble reader gets the same treatment
    ens = NavierEnsemble.from_seeds(model, seeds=range(2))
    with pytest.raises(cp.CheckpointError, match="members"):
        ens.read(empty)
    # read_unwrap swallows it like the reference's unwrap-or-print
    model.read_unwrap(empty)


def test_rotation_keeps_window(tmp_path, stepped_rbc17):
    model = stepped_rbc17
    for step in range(5):
        cp.write_snapshot(model, cp.checkpoint_path(str(tmp_path), step), step=step)
        cp.rotate_checkpoints(str(tmp_path), keep=3)
    files = cp.checkpoint_files(str(tmp_path))
    assert [os.path.basename(f) for f in files] == [
        "ckpt_0000000002.h5",
        "ckpt_0000000003.h5",
        "ckpt_0000000004.h5",
    ]
    assert cp.latest_checkpoint(str(tmp_path)) == files[-1]


# -- watchdog / fault plumbing ------------------------------------------------


def test_call_with_watchdog():
    import time as _time

    assert call_with_watchdog(lambda: 42, None) == 42
    assert call_with_watchdog(lambda: 42, 5.0) == 42
    with pytest.raises(ValueError, match="boom"):
        call_with_watchdog(lambda: (_ for _ in ()).throw(ValueError("boom")), 5.0)
    with pytest.raises(DispatchHang, match="deadline-test"):
        call_with_watchdog(lambda: _time.sleep(5.0), 0.2, label="deadline-test")


def test_fault_spec_parsing():
    assert FaultPlan.from_spec(None) is None
    assert FaultPlan.from_spec("") is None
    plan = FaultPlan.from_spec("nan@12")
    assert (plan.kind, plan.step, plan.fired) == ("nan", 12, False)
    for bad in ("nan", "typo@3", "nan@x"):
        with pytest.raises(ValueError):
            FaultPlan.from_spec(bad)


# -- the runner ---------------------------------------------------------------


@pytest.mark.slow
def test_nan_rollback_dt_backoff_matches_clean_run(tmp_path):
    """The end-to-end recovery demo: a NaN injected mid-run rolls back to
    the anchor checkpoint, halves dt, and completes; the journal records the
    retry and the final state equals an unfaulted run at the reduced dt
    (rollback target is the step-0 anchor, so the recovered trajectory IS
    the clean reduced-dt trajectory)."""
    run_dir = str(tmp_path / "run")
    runner = ResilientRunner(
        _build(),
        max_time=0.2,
        save_intervall=0.05,
        run_dir=run_dir,
        checkpoint_every_s=None,
        max_retries=2,
        dt_backoff=0.5,
        fault="nan@6",
    )
    summary = runner.run()
    assert summary["outcome"] == "done"
    assert summary["retries"] == 1
    assert summary["dt"] == pytest.approx(0.005)
    assert summary["time"] == pytest.approx(0.2)
    assert np.isfinite(summary["nu"])

    events = [e["event"] for e in _events(run_dir)]
    assert events == [
        "start",
        "checkpoint",  # anchor
        "fault_injected",
        "divergence",
        "retry",
        "checkpoint",  # final
        "io_overlap",  # run-end pipeline summary (async IO is the default)
        "done",
    ]
    retry = next(e for e in _events(run_dir) if e["event"] == "retry")
    assert retry["dt"] == pytest.approx(0.005)
    assert retry["attempt"] == 1

    clean = _build(dt=0.005)
    integrate(clean, 0.2, None)
    assert summary["nu"] == pytest.approx(clean.eval_nu(), rel=1e-10)
    # final checkpoint reads back digest-clean
    assert cp.verify_snapshot(summary["checkpoint"])["digest"]


@pytest.mark.slow
def test_retries_exhausted_raises(tmp_path):
    """Faults every attempt (nan at a step the retry revisits) exhaust
    max_retries and surface as DivergenceError, journaled as giveup."""
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
        max_retries=1,
        fault="nan@4",
    )
    with pytest.raises(DivergenceError, match="exhausted"):
        runner.run()
    events = [e["event"] for e in _events(run_dir)]
    assert events.count("divergence") == 2
    # giveup is the terminal RUN event; the telemetry layer appends its
    # flight_record pointer behind it as the session unwinds (PR 8)
    assert [e for e in events if e != "flight_record"][-1] == "giveup"
    assert events[-1] == "flight_record"


def test_sigterm_checkpoints_then_resume_continues(tmp_path):
    """SIGTERM mid-flight (the kill fault signals this very process)
    checkpoints-then-exits cleanly; a fresh runner on the same run_dir
    resumes from that checkpoint and completes with a digest-valid final
    snapshot."""
    run_dir = str(tmp_path / "run")
    r1 = ResilientRunner(
        _build(),
        max_time=0.3,
        save_intervall=0.05,
        run_dir=run_dir,
        checkpoint_every_s=None,
        fault="kill@12",
    )
    s1 = r1.run()
    assert s1["outcome"] == "preempted"
    ckpt = s1["checkpoint"]
    assert ckpt is not None
    step1 = int(cp.verify_snapshot(ckpt)["step"])
    assert step1 >= 12

    r2 = ResilientRunner(
        _build(),  # fresh model: resume must restore state AND step counter
        max_time=0.3,
        save_intervall=0.05,
        run_dir=run_dir,
        checkpoint_every_s=None,
    )
    s2 = r2.run()
    assert s2["outcome"] == "done"
    assert s2["time"] == pytest.approx(0.3)
    assert s2["step"] == 30
    events = [e["event"] for e in _events(run_dir)]
    assert "preempted" in events and "resumed" in events and events[-1] == "done"
    resumed = next(e for e in _events(run_dir) if e["event"] == "resumed")
    assert resumed["step"] == step1
    assert cp.verify_snapshot(s2["checkpoint"])["digest"]
    assert np.isfinite(s2["nu"])


@pytest.mark.slow
def test_preempt_without_save_intervall(tmp_path):
    """Even with no save boundaries (save_intervall=None would otherwise
    dispatch the whole horizon as ONE chunk), dispatches are capped at
    max_chunk_steps, so a SIGTERM is honored mid-horizon with a checkpoint
    at the break — not after max_time."""
    run_dir = str(tmp_path / "run")
    runner = ResilientRunner(
        _build(),
        max_time=0.3,
        save_intervall=None,
        run_dir=run_dir,
        checkpoint_every_s=None,
        fault="kill@7",
        max_chunk_steps=5,
    )
    summary = runner.run()
    assert summary["outcome"] == "preempted"
    assert summary["step"] < 30  # stopped mid-horizon
    assert int(cp.verify_snapshot(summary["checkpoint"])["step"]) == summary["step"]


def test_fresh_run_refuses_stale_run_dir(tmp_path, stepped_rbc17):
    """resume=False on a run_dir holding a previous campaign's checkpoints
    must refuse: a later rollback would silently splice the old campaign's
    trajectory into the new run."""
    run_dir = str(tmp_path / "run")
    cp.write_snapshot(stepped_rbc17, cp.checkpoint_path(run_dir, 7), step=7)
    runner = ResilientRunner(
        stepped_rbc17, max_time=0.1, run_dir=run_dir, resume=False
    )  # raises before touching the model
    with pytest.raises(ValueError, match="previous run"):
        runner.run()


@pytest.mark.slow
def test_resume_restores_backed_off_dt(tmp_path):
    """A checkpoint written after a dt backoff carries its dt as a root
    attr; resuming a fresh runner (constructed at the original dt) must
    restore the backed-off dt — otherwise every preemption cycle would
    re-diverge at the original step size and burn a fresh retry budget."""
    run_dir = str(tmp_path / "run")
    donor = _build(dt=0.005)  # stands in for a post-backoff run
    donor.update_n(4)
    cp.write_snapshot(donor, cp.checkpoint_path(run_dir, 4), step=4)
    runner = ResilientRunner(
        _build(dt=0.01),  # rerun of the original command: original dt
        max_time=0.1,
        save_intervall=0.05,
        run_dir=run_dir,
        checkpoint_every_s=None,
    )
    summary = runner.run()
    assert summary["outcome"] == "done"
    assert summary["dt"] == pytest.approx(0.005)
    assert summary["time"] == pytest.approx(0.1)
    events = [e["event"] for e in _events(run_dir)]
    assert "dt_restored" in events


@pytest.mark.slow
def test_slow_fault_trips_dispatch_watchdog(tmp_path):
    """The slow fault stalls a dispatch past the watchdog deadline: thread
    stacks are dumped and a structured DispatchHang is raised (instead of a
    silent hang), with the hang journaled."""
    model = _build()
    # warm the jit caches (scan buckets 4/2/1 + observables) so compile time
    # cannot eat the watchdog deadline
    model.update_n(7)
    model.eval_nu()
    model.set_velocity(0.1, 1.0, 1.0)
    model.set_temperature(0.1, 1.0, 1.0)
    model.reset_time()
    run_dir = str(tmp_path / "run")
    runner = ResilientRunner(
        model,
        max_time=0.3,
        save_intervall=0.05,
        run_dir=run_dir,
        checkpoint_every_s=None,
        fault="slow@7",
        dispatch_timeout_s=3.0,
    )
    with pytest.raises(DispatchHang, match="update_n"):
        runner.run()
    events = [e["event"] for e in _events(run_dir)]
    # dispatch_hang is the terminal RUN event; the flight-record pointer
    # rides behind it as the session unwinds (PR 8)
    assert [e for e in events if e != "flight_record"][-1] == "dispatch_hang"
    assert events[-1] == "flight_record"
    assert "fault_injected" in events


@pytest.mark.slow
def test_checkpoint_cadence_sim_time(tmp_path):
    """checkpoint_every_t drops a rolling window of checkpoints at the
    sim-time cadence, pruned to ``keep``."""
    run_dir = str(tmp_path / "run")
    runner = ResilientRunner(
        _build(),
        max_time=0.3,
        save_intervall=0.05,
        run_dir=run_dir,
        checkpoint_every_s=None,
        checkpoint_every_t=0.1,
        keep=2,
    )
    summary = runner.run()
    assert summary["outcome"] == "done"
    files = cp.checkpoint_files(run_dir)
    assert len(files) == 2  # retention window
    cadence = [e for e in _events(run_dir) if e.get("reason") == "cadence"]
    assert len(cadence) >= 2


# -- dt backoff + ensembles ---------------------------------------------------


@pytest.mark.slow
def test_set_dt_matches_fresh_model():
    """set_dt rebuilds the dt-baked solver pipeline exactly: a live model
    switched to dt/2 steps identically to a fresh dt/2 model handed the same
    state."""
    model = _build()
    model.update_n(5)
    fresh = Navier2D(17, 17, 1e4, 1.0, 0.005, 1.0, "rbc", periodic=False)
    fresh.state = model.state
    model.set_dt(0.005)
    model.update_n(4)
    fresh.update_n(4)
    for attr in ("temp", "velx", "vely", "pres", "pseu"):
        np.testing.assert_allclose(
            np.asarray(getattr(model.state, attr)),
            np.asarray(getattr(fresh.state, attr)),
            atol=1e-13,
            err_msg=attr,
        )
    with pytest.raises(ValueError):
        model.set_dt(-1.0)


def test_ensemble_respawn_equivalence():
    """Respawning a dead member from a perturbed healthy donor revives it
    without touching any surviving member's state (bitwise)."""
    import jax

    model = _build()
    ens = NavierEnsemble.from_seeds(model, seeds=range(3))
    ens.update_n(4)
    dead = jax.tree.map(lambda x: x * float("nan"), ens.member_state(1))
    ens.set_member(1, dead)
    assert list(ens.alive()) == [True, False, True]
    before = {
        attr: np.asarray(getattr(ens.state, attr)).copy()
        for attr in ("temp", "velx", "vely", "pres", "pseu")
    }
    assert ens.respawn_dead(amp=1e-3, seed=0) == 1
    assert ens.alive().all()
    for attr, prev in before.items():
        arr = np.asarray(getattr(ens.state, attr))
        np.testing.assert_array_equal(arr[0], prev[0], err_msg=attr)
        np.testing.assert_array_equal(arr[2], prev[2], err_msg=attr)
        assert np.isfinite(arr[1]).all(), attr
    # respawned member steps fine at the ensemble's (possibly backed-off) dt
    ens.set_dt(0.005)
    ens.update_n(2)
    assert ens.alive().all()
    # no-ops: all alive / all dead
    assert ens.respawn_dead() == 0
    ens.set_member(0, jax.tree.map(lambda x: x * float("nan"), ens.member_state(0)))
    ens.set_member(1, jax.tree.map(lambda x: x * float("nan"), ens.member_state(1)))
    ens.set_member(2, jax.tree.map(lambda x: x * float("nan"), ens.member_state(2)))
    assert ens.respawn_dead() == 0


@pytest.mark.slow
def test_runner_drives_ensemble(tmp_path):
    """The runner wraps an ensemble unchanged: NaN-poisoning all members
    fires the all-dead break criterion, rolls back, backs off dt, and
    completes; the restored checkpoint carries the per-member layout."""
    model = _build()
    ens = NavierEnsemble.from_seeds(model, seeds=range(2))
    run_dir = str(tmp_path / "run")
    runner = ResilientRunner(
        ens,
        max_time=0.2,
        save_intervall=0.05,
        run_dir=run_dir,
        checkpoint_every_s=None,
        max_retries=1,
        fault="nan@6",
        respawn_members=True,
    )
    summary = runner.run()
    assert summary["outcome"] == "done"
    assert summary["retries"] == 1
    assert ens.alive().all()
    assert summary["dt"] == pytest.approx(0.005)
    assert np.isfinite(summary["nu"])
    with h5py.File(summary["checkpoint"], "r") as h5:
        assert "member0" in h5 and "member1" in h5


@pytest.mark.slow
def test_resilience_config_roundtrip(tmp_path):
    from rustpde_mpi_tpu.config import NavierConfig, ResilienceConfig

    rcfg = ResilienceConfig(
        run_dir=str(tmp_path / "run"),
        checkpoint_every_s=None,
        checkpoint_every_t=0.1,
        keep=2,
        max_retries=1,
    )
    cfg = NavierConfig(nx=17, ny=17, ra=1e4, dt=0.01, resilience=rcfg)
    model = Navier2D.from_config(cfg)
    model.set_velocity(0.1, 1.0, 1.0)
    model.set_temperature(0.1, 1.0, 1.0)
    runner = ResilientRunner.from_config(
        model, cfg.resilience, max_time=0.1, save_intervall=0.05
    )
    assert runner.keep == 2 and runner.max_retries == 1
    assert runner.run()["outcome"] == "done"
