"""Overlapped-I/O pipeline tests (utils/io_pipeline.py + the host-snapshot
split in utils/checkpoint.py + the overlapped driver in utils/integrate.py):
write-side digests, async==sync bit-identity, future semantics, lagged break
checks, and the resilient runner's async checkpoint path."""

import json
import os

import numpy as np
import pytest

from rustpde_mpi_tpu import (
    AsyncWriteError,
    IOPipeline,
    NavierEnsemble,
    ResilientRunner,
    integrate,
)
from rustpde_mpi_tpu.config import IOConfig
from rustpde_mpi_tpu.utils import checkpoint as cp
from rustpde_mpi_tpu.utils.io_pipeline import AsyncCheckpointWriter
from rustpde_mpi_tpu.utils.resilience import poison_state

h5py = pytest.importorskip("h5py")


# shared tier-wide builder (model_builders.py) + session-scoped stepped
# model (conftest.stepped_rbc17): same jit shapes as test_resilience etc.
from model_builders import build_rbc17 as _build


def _events(run_dir):
    with open(os.path.join(run_dir, "journal.jsonl"), encoding="utf-8") as fh:
        return [json.loads(line) for line in fh]


# -- write-side digest + host-snapshot split ---------------------------------


def test_write_side_digest_matches_readback(tmp_path, stepped_rbc17):
    """The digest stamped from the in-memory arrays (no file read-back) must
    equal the digest a reader computes from the file — the contract the
    whole verify/corrupt-skip machinery rides on."""
    path = str(tmp_path / "snap.h5")
    cp.write_snapshot(stepped_rbc17, path, step=4)
    attrs = cp.verify_snapshot(path)  # raises on any digest mismatch
    with h5py.File(path, "r") as h5:
        assert attrs["digest"] == cp.content_digest(h5)


def test_ensemble_write_side_digest_and_dtypes(tmp_path):
    """Ensemble snapshots carry exact-dtype bookkeeping datasets; the
    write-side digest must cover them identically to the read-back pass."""
    ens = NavierEnsemble.from_seeds(_build(), [0, 1])
    ens.update_n(2)
    path = str(tmp_path / "ens.h5")
    cp.write_ensemble_snapshot(ens, path, step=2)
    attrs = cp.verify_snapshot(path)
    with h5py.File(path, "r") as h5:
        assert attrs["digest"] == cp.content_digest(h5)
        assert h5["members"].dtype == np.int64
        assert h5["alive"].dtype == np.int8
        assert h5["steps_done"].dtype == np.int64
    ens2 = NavierEnsemble.from_seeds(_build(), [7])
    ens2.read(path)
    assert ens2.k == 2
    for name in ("temp", "velx", "vely", "pres"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ens.state, name)), np.asarray(getattr(ens2.state, name))
        )


def test_async_write_bit_identical_to_sync(tmp_path, stepped_rbc17):
    """A host snapshot serialized on the background worker must be byte-level
    the file the synchronous writer produces (same content digest)."""
    sync_path = str(tmp_path / "sync.h5")
    async_path = str(tmp_path / "async.h5")
    cp.write_snapshot(stepped_rbc17, sync_path, step=4)
    snap = cp.snapshot_to_host(stepped_rbc17, step=4)
    pipe = IOPipeline()
    pipe.submit_write(lambda: cp.write_host_snapshot(snap, async_path), async_path)
    pipe.drain()
    pipe.close()
    assert (
        cp.verify_snapshot(sync_path)["digest"]
        == cp.verify_snapshot(async_path)["digest"]
    )


# -- futures ------------------------------------------------------------------


def test_observable_future_matches_sync(stepped_rbc17):
    fut = stepped_rbc17.get_observables_async()
    vals = stepped_rbc17.get_observables()  # resolves through the same future
    assert fut.ready()
    assert fut.result() == vals
    assert len(vals) == 4 and all(isinstance(v, float) for v in vals)
    assert not stepped_rbc17.exit_future().result()


def test_exit_future_detects_nan():
    model = _build()
    model.update_n(2)
    poison_state(model)
    assert model.exit_future().result() is True
    assert model.exit()  # the sync criterion agrees


def test_ensemble_exit_future_all_dead():
    ens = NavierEnsemble.from_seeds(_build(), [0, 1])
    ens.update_n(1)
    assert ens.exit_future().result() is False
    poison_state(ens)  # poisons every member and re-derives the mask
    ens.update_n(1)
    assert ens.exit_future().result() is True


def test_async_writer_error_surfaces_then_clears():
    writer = AsyncCheckpointWriter()

    def boom():
        raise OSError("disk gone")

    writer.submit(boom, "/tmp/doomed.h5")
    with pytest.raises(AsyncWriteError, match="doomed"):
        writer.drain()
    # the failure was observed: the writer accepts (and completes) new work
    ran = []
    writer.submit(lambda: ran.append(1), "ok")
    writer.drain()
    assert ran == [1]
    writer.close()


def test_async_writer_timeout_surfaces_wedged_write():
    """An armed ``timeout_s`` converts a wedged write (disk/NFS stuck in
    fsync) into a typed AsyncWriteError at the next back-pressure submit
    and at drain, instead of blocking the campaign silently; close()
    abandons the wedged daemon worker rather than joining forever."""
    import threading

    release = threading.Event()
    writer = AsyncCheckpointWriter(depth=1, timeout_s=0.2)
    writer.submit(release.wait, "/tmp/wedged.h5")  # occupies the one slot
    with pytest.raises(AsyncWriteError, match="back-pressure"):
        writer.submit(lambda: None, "/tmp/next.h5")
    with pytest.raises(AsyncWriteError, match="drain"):
        writer.drain()
    writer.close()  # must return promptly despite the stuck worker
    release.set()  # let the daemon thread finish


def test_diag_lag_queue_is_fifo_and_flushes():
    pipe = IOPipeline(diag_lag=1)

    class Manual:
        def __init__(self, value):
            self.value = value
            self._ready = False

        def ready(self):
            return self._ready

        def result(self):
            return self.value

    out = []
    futs = [Manual(i) for i in range(3)]
    for f in futs:
        pipe.push_diag(out.append, f)
    # one young unresolved entry may pend; older ones were forced in order
    assert out == [0, 1]
    futs[2]._ready = True
    pipe.flush_diags()
    assert out == [0, 1, 2]
    pipe.close()


# -- the overlapped driver ----------------------------------------------------


def test_overlapped_integrate_bit_identical():
    """Dispatch double-buffering reorders IO, never physics: the overlapped
    run's final state equals the blocking run's bit for bit."""
    a, b = _build(), _build()
    sa = integrate(a, 0.2, 0.05)
    sb = integrate(b, 0.2, 0.05, overlap=True)
    assert sa == sb == "time_limit"
    for x, y in zip(a.state, b.state):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_overlapped_integrate_reports_break_on_nan():
    """A NaN state must still end the run with "break" under overlap — at
    most one chunk late, and exactly at the horizon (the final state is
    always resolved before a time_limit return)."""
    model = _build()
    model.update_n(2)
    poison_state(model)
    assert integrate(model, 0.2, 0.05, overlap=True) == "break"


# -- the resilient runner's async path ---------------------------------------


def test_runner_async_matches_blocking(tmp_path):
    """Default IOConfig (async cadence checkpoints + overlap) against
    IOConfig.blocking(): same outcome, bit-equal Nu and state, final
    checkpoints byte-identical, and the journal records async cadence
    checkpoints with the step they snapshot."""
    run_a = str(tmp_path / "async")
    run_b = str(tmp_path / "block")
    ra = ResilientRunner(
        _build(), 0.3, 0.05, run_dir=run_a,
        checkpoint_every_s=None, checkpoint_every_t=0.1,
    )
    sa = ra.run()
    rb = ResilientRunner(
        _build(), 0.3, 0.05, run_dir=run_b,
        checkpoint_every_s=None, checkpoint_every_t=0.1,
        io=IOConfig.blocking(),
    )
    sb = rb.run()
    assert sa["outcome"] == sb["outcome"] == "done"
    assert sa["nu"] == sb["nu"]
    for x, y in zip(ra.pde.state, rb.pde.state):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert (
        cp.verify_snapshot(sa["checkpoint"])["digest"]
        == cp.verify_snapshot(sb["checkpoint"])["digest"]
    )
    async_ckpts = [
        e for e in _events(run_a) if e["event"] == "checkpoint" and e.get("async")
    ]
    assert async_ckpts, "no async checkpoints journaled"
    assert all("write_s" in e and "snapshot_s" in e for e in async_ckpts)
    assert sa["io"]["writes"] >= len(async_ckpts)
    assert sb["io"] is None


def test_runner_async_rollback_after_nan(tmp_path):
    """Divergence recovery under the overlapped pipeline: the writer drains
    before the rollback read, so the retry restores a settled, digest-valid
    checkpoint and completes like the synchronous harness."""
    run_dir = str(tmp_path / "nan")
    runner = ResilientRunner(
        _build(), 0.3, 0.05, run_dir=run_dir,
        checkpoint_every_s=None, max_retries=1, dt_backoff=0.5,
        fault="nan@15",
    )
    summary = runner.run()
    assert summary["outcome"] == "done"
    assert summary["retries"] == 1
    assert summary["dt"] == pytest.approx(0.005)
    assert np.isfinite(summary["nu"])
    events = [e["event"] for e in _events(run_dir)]
    assert "divergence" in events and "retry" in events
    assert events[-1] == "done"


def test_runner_async_write_failure_raises(tmp_path, monkeypatch):
    """A background cadence-write failure must stop the campaign at the
    next submission — not be silently dropped — and leave a
    ``checkpoint_failed`` journal line."""
    run_dir = str(tmp_path / "failing")
    calls = {"n": 0}
    real = cp.write_host_snapshot

    def flaky(snap, filename):
        calls["n"] += 1
        if calls["n"] >= 2:  # the anchor write succeeds, cadence writes die
            raise OSError("disk gone")
        real(snap, filename)

    monkeypatch.setattr(cp, "write_host_snapshot", flaky)
    runner = ResilientRunner(
        _build(), 0.4, 0.05, run_dir=run_dir,
        checkpoint_every_s=None, checkpoint_every_t=0.05,
    )
    with pytest.raises(AsyncWriteError, match="disk gone"):
        runner.run()
    assert any(e["event"] == "checkpoint_failed" for e in _events(run_dir))


def test_callback_pipeline_lags_then_flushes(tmp_path, monkeypatch):
    """With an attached pipeline the callback's diagnostics are emitted
    lazily but completely: after the run every boundary's row is in
    info.txt and the in-memory diagnostics map, in chronological order."""
    monkeypatch.chdir(tmp_path)
    model = _build()
    pipe = IOPipeline()
    model.io_pipeline = pipe
    try:
        integrate(model, 0.2, 0.05, overlap=True)
        pipe.drain()
    finally:
        model.io_pipeline = None
        pipe.close()
    times = model.diagnostics["time"]
    assert times == sorted(times) and len(times) == 4
    with open("data/info.txt", encoding="utf-8") as fh:
        rows = [line.split()[0] for line in fh if line.strip()]
    assert [float(r) for r in rows] == pytest.approx(times)


# -- crash consistency + governed lag=1 (ISSUE 4 satellites) ------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_async_writer_kill_mid_background_write(tmp_path):
    """SIGKILL-equivalent death while the BACKGROUND worker is mid-write
    (the overlapped extension of the PR-2 mid-write kill test): the newest
    checkpoint that fully landed is digest-clean, ``latest_checkpoint``
    picks it, the half-written victim leaves at most a ``.tmp`` corpse the
    listing ignores, and a fresh runner resumes from it to completion."""
    import subprocess
    import sys

    run_dir = str(tmp_path / "killed")
    child = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["RUSTPDE_X64"] = "1"
import jax
from rustpde_mpi_tpu import Navier2D, ResilientRunner
from rustpde_mpi_tpu.utils import checkpoint as cp

calls = {{"snap": 0, "arr": 0}}
orig_whs = cp.write_host_snapshot
orig_wa = cp._write_array

def wa(group, name, data):
    calls["arr"] += 1
    if calls["snap"] >= 3 and calls["arr"] >= 3:
        os._exit(9)                # die mid-write, before os.replace
    orig_wa(group, name, data)

def whs(snap, filename):
    calls["snap"] += 1
    calls["arr"] = 0
    orig_whs(snap, filename)

cp._write_array = wa
cp.write_host_snapshot = whs

m = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
m.set_velocity(0.1, 1.0, 1.0); m.set_temperature(0.1, 1.0, 1.0)
m.write_intervall = 1e9
ResilientRunner(
    m, 0.3, 0.05, run_dir=sys.argv[1],
    checkpoint_every_s=None, checkpoint_every_t=0.05,
).run()                            # anchor + cadence1 land; cadence2 bombs
os._exit(1)                        # unreachable if the kill fired
""".format(repo=_REPO)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", child, run_dir],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert proc.returncode == 9, proc.stderr
    latest = cp.latest_checkpoint(run_dir)
    assert latest is not None
    attrs = cp.verify_snapshot(latest)  # digest-clean
    assert int(attrs["step"]) > 0  # a cadence checkpoint, not just the anchor
    # the half-written victim is not in the listing
    assert all(not f.endswith(".tmp") for f in cp.checkpoint_files(run_dir))
    # a fresh campaign on the same run_dir resumes from it and finishes
    runner = ResilientRunner(
        _build(), 0.3, 0.05, run_dir=run_dir,
        checkpoint_every_s=None, checkpoint_every_t=0.05,
    )
    summary = runner.run()
    assert summary["outcome"] == "done"
    assert np.isfinite(summary["nu"])
    assert any(e["event"] == "resumed" for e in _events(run_dir))


def test_rollback_read_never_races_pending_write(tmp_path, monkeypatch):
    """commit() ordering: a rollback/resume read drains the writer first,
    so picking a checkpoint while a background write is in flight returns
    the SETTLED file — never a half-written one."""
    import threading
    import time as _t

    run_dir = str(tmp_path / "race")
    os.makedirs(run_dir, exist_ok=True)
    in_write = threading.Event()
    real = cp.write_host_snapshot

    def slow(snap, filename):
        in_write.set()
        _t.sleep(0.5)
        real(snap, filename)

    monkeypatch.setattr(cp, "write_host_snapshot", slow)
    runner = ResilientRunner(
        _build(), 1.0, 0.05, run_dir=run_dir,
        checkpoint_every_s=None, checkpoint_every_t=0.05,
    )
    runner._setup_io()
    try:
        runner.pde.update_n(2)
        runner.step = 2
        path = runner._checkpoint("cadence")  # background submit
        assert in_write.wait(5.0)  # the worker is inside the slow write
        picked = runner._pick_checkpoint()  # must drain, then scan
        assert picked == path
        cp.verify_snapshot(picked)  # fully landed, digest-clean
    finally:
        runner._teardown_io()


@pytest.mark.slow
def test_governed_overlap_matches_blocking_and_catches_spike(tmp_path):
    """The lag=1 sentinel contract: a GOVERNED overlapped run at a stable
    dt is bit-identical to the blocking governed run, and a governed
    overlapped run through a velocity spike still catches it pre-NaN with
    ZERO reactive checkpoint rollbacks; the run-end journal carries the
    ``io_overlap`` summary."""
    from rustpde_mpi_tpu.config import StabilityConfig

    def governed(run_dir, io, fault=None):
        return ResilientRunner(
            _build(), 0.3, 0.05, run_dir=run_dir,
            checkpoint_every_s=None, checkpoint_every_t=0.1,
            max_retries=2, stability=StabilityConfig(),
            fault=fault, spike_factor=200.0, io=io,
        )

    ra = governed(str(tmp_path / "lag1"), IOConfig())
    sa = ra.run()
    rb = governed(str(tmp_path / "block"), IOConfig.blocking())
    sb = rb.run()
    assert sa["outcome"] == sb["outcome"] == "done"
    assert sa["nu"] == sb["nu"]  # bit-identical under reordering
    for x, y in zip(ra.pde.state, rb.pde.state):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    overlap_ev = [e for e in _events(str(tmp_path / "lag1"))
                  if e["event"] == "io_overlap"]
    assert overlap_ev and overlap_ev[0]["bytes"] > 0
    assert overlap_ev[0]["queue_depth"] == 1

    spike_dir = str(tmp_path / "spike")
    ss = governed(spike_dir, IOConfig(), fault="spike@10").run()
    assert ss["outcome"] == "done"
    assert ss["retries"] == 0  # caught pre-NaN: no reactive rollback
    assert np.isfinite(ss["nu"])
    events = [e["event"] for e in _events(spike_dir)]
    assert "pre_divergence" in events and "dt_adjust" in events
    assert "divergence" not in events and "retry" not in events
    assert ss["health"]["pre_divergence_catches"] >= 1
