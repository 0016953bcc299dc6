"""Collective-sequence sanitizer (parallel/sanitizer.py).

Tier-1: single-process recording/ring/digest/injection-parsing semantics +
the disabled-mode ~free contract.  Slow tier: the 2-process desync
injection — a deliberately skipped broadcast on host 1 must raise a typed
CollectiveDesyncError naming the divergent call site on BOTH hosts within
one verification cadence (the PR-10 drain-check bug, diagnosed at runtime
instead of wedging the fleet).
"""

import json
import os

import numpy as np
import pytest

from rustpde_mpi_tpu import CollectiveDesyncError
from rustpde_mpi_tpu.parallel import multihost, sanitizer


@pytest.fixture()
def armed(monkeypatch):
    monkeypatch.setenv("RUSTPDE_SANITIZE", "1")
    monkeypatch.delenv("RUSTPDE_SANITIZE_INJECT", raising=False)
    sanitizer.reset()
    yield
    monkeypatch.setenv("RUSTPDE_SANITIZE", "0")
    sanitizer.reset()


def test_disabled_records_nothing():
    sanitizer.reset()
    assert not sanitizer.enabled()
    before = sanitizer.stats()
    multihost.sync_hosts("san-off")
    multihost.broadcast(np.int32(3))
    multihost.root_decides(True)
    after = sanitizer.stats()
    assert after["records"] == before["records"] == 0
    assert after["seq"] == 0


def test_recording_ring_and_sites(armed):
    multihost.sync_hosts("san-tag")
    multihost.broadcast(np.float64([1.0, 2.0]))
    multihost.root_decides(False)
    multihost.allgather_host(np.int64(7))
    st = sanitizer.stats()
    assert st["enabled"] and st["records"] == 4 and st["seq"] == 4
    ring = list(sanitizer._STATE.ring)
    kinds = [e["kind"] for e in ring]
    assert kinds == ["sync", "broadcast", "root_decides", "allgather"]
    assert ring[0]["tag"] == "san-tag"
    # payload-schema digests carry dtype+shape, not values
    assert ring[1]["schema"] == "float64[2]"
    assert ring[3]["schema"] == "int64[]"
    # call sites resolve OUTSIDE multihost.py, to this test file
    for e in ring:
        assert "test_sanitizer.py" in e["site"], e


def test_ring_is_bounded_and_hash_covers_history(armed):
    cap = sanitizer._STATE.ring.maxlen
    for _ in range(cap + 5):
        multihost.root_decides(True)
    assert len(sanitizer._STATE.ring) == cap
    assert sanitizer.stats()["seq"] == cap + 5  # running hash keeps counting


def test_single_process_verify_is_noop(armed):
    for _ in range(3):
        multihost.broadcast(np.int32(1))
    sanitizer.verify()  # must not raise nor exchange anything
    assert sanitizer.stats()["desyncs"] == 0


def test_values_unchanged_when_armed(armed):
    # host-side only: the sanitizer must never alter what the collectives
    # return (bit-identity of a full run: the test below)
    assert int(multihost.broadcast(np.int32(41))) == 41
    assert multihost.root_decides(True) is True
    assert multihost.root_decides(False) is False
    out = multihost.allgather_host(np.float64(2.5))
    assert out.shape == (1,) and float(out[0]) == 2.5


def test_armed_run_bit_identical_to_sanitizer_off(tmp_path, monkeypatch):
    """The sanitizer is host-side only: a governed runner stepped through
    its boundaries with the sanitizer armed ends in the same bits as the
    same run with it off, and the armed run did record its handshakes (or
    the comparison would be vacuous)."""
    from model_builders import build_rbc17
    from rustpde_mpi_tpu import ResilientRunner
    from rustpde_mpi_tpu.config import StabilityConfig

    states, records = {}, {}
    monkeypatch.delenv("RUSTPDE_SANITIZE_INJECT", raising=False)
    try:
        for key in ("on", "off"):
            monkeypatch.setenv("RUSTPDE_SANITIZE", "1" if key == "on" else "0")
            sanitizer.reset()
            model = build_rbc17()
            model.set_stability(StabilityConfig())
            runner = ResilientRunner(
                model,
                max_time=float("inf"),
                run_dir=str(tmp_path / key),
                checkpoint_every_s=None,
                max_chunk_steps=4,
            )
            runner.advance(16)
            states[key] = [np.asarray(leaf) for leaf in model.state]
            records[key] = sanitizer.stats()["records"]
    finally:
        monkeypatch.setenv("RUSTPDE_SANITIZE", "0")
        sanitizer.reset()
    assert records["on"] > 0 and records["off"] == 0
    for a, b in zip(states["on"], states["off"]):
        np.testing.assert_array_equal(a, b)


def test_np_schema():
    assert sanitizer.np_schema(np.zeros((2, 3), np.uint8)) == "uint8[2, 3]"
    assert sanitizer.np_schema(3) == "int64[]"


def test_inject_spec_strict_parse():
    good = sanitizer._InjectPlan.from_spec("skip_broadcast@5:host1")
    assert good.call == 5 and good.host == 1
    assert sanitizer._InjectPlan.from_spec(None) is None
    for bad in ("skip@5", "skip_broadcast@x", "skip_broadcast@5:h1", "skip_broadcast5"):
        with pytest.raises(ValueError):
            sanitizer._InjectPlan.from_spec(bad)


def test_desync_error_shape():
    exc = CollectiveDesyncError("msg", seq=7, sites={0: {"site": "a.py:1"}}, site="a.py:1")
    assert exc.seq == 7 and exc.site == "a.py:1" and 0 in exc.sites
    assert isinstance(exc, RuntimeError)


def test_env_cadence_and_capacity(monkeypatch):
    monkeypatch.setenv("RUSTPDE_SANITIZE", "1")
    monkeypatch.setenv("RUSTPDE_SANITIZE_CADENCE", "5")
    monkeypatch.setenv("RUSTPDE_SANITIZE_RING", "16")
    sanitizer.reset()
    assert sanitizer.stats()["cadence"] == 5
    assert sanitizer._STATE.ring.maxlen == 16
    monkeypatch.setenv("RUSTPDE_SANITIZE", "0")
    monkeypatch.delenv("RUSTPDE_SANITIZE_CADENCE")
    monkeypatch.delenv("RUSTPDE_SANITIZE_RING")
    sanitizer.reset()


# -- 2-process desync injection (slow tier) -----------------------------------


@pytest.mark.slow
def test_mp_desync_injection_raises_on_both_hosts(tmp_path):
    """Host 1 silently skips one broadcast (the PR-10 drain-check shape):
    both ranks must raise CollectiveDesyncError naming the divergent call
    site within ONE verification cadence — and a clean run under the same
    arming must not trip."""
    from mp_harness import spawn_cluster

    env = {
        "RUSTPDE_SANITIZE": "1",
        "RUSTPDE_SANITIZE_CADENCE": "8",
        "RUSTPDE_SYNC_TIMEOUT_S": "60",
    }
    # clean leg: armed, no injection, no trips
    clean_dir = str(tmp_path / "clean")
    os.makedirs(clean_dir)
    outs = spawn_cluster(clean_dir, mode="sanitize_desync", timeout=300, env_extra=env)
    assert outs is not None, "clean sanitize spawn timed out"
    for rank in (0, 1):
        with open(os.path.join(clean_dir, f"sanitize_rank{rank}.json")) as fh:
            r = json.load(fh)
        assert r["raised"] is None, r
        assert r["stats"]["verifies"] >= 1 and r["stats"]["desyncs"] == 0

    # injected leg: host1 skips its 5th broadcast
    inj_dir = str(tmp_path / "inject")
    os.makedirs(inj_dir)
    outs = spawn_cluster(
        inj_dir,
        mode="sanitize_desync",
        timeout=300,
        env_extra={**env, "RUSTPDE_SANITIZE_INJECT": "skip_broadcast@5:host1"},
    )
    assert outs is not None, "injected sanitize spawn timed out"
    for rank in (0, 1):
        with open(os.path.join(inj_dir, f"sanitize_rank{rank}.json")) as fh:
            r = json.load(fh)
        assert r["raised"] == "CollectiveDesyncError", (rank, r)
        # the first divergent call site is named, and it is the worker's
        # root_decides loop
        assert r["site"] and "mp_worker.py" in r["site"], r
        assert r["seq"] is not None and r["seq"] > 0
        # detected at the FIRST verification after the skip (cadence 8
        # executed collectives; the skip lands at call 5)
        assert r["stats"]["verifies"] == 1, r
        assert r["stats"]["desyncs"] == 1, r
