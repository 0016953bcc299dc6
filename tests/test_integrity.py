"""End-to-end integrity tests (rustpde_mpi_tpu/integrity/ + the runner,
checkpoint, queue, and fleet wiring): on-device state digests (determinism,
single-bit sensitivity, per-member localization), the shadow re-execution
audit catching an injected silent bitflip and rolling back to a
bit-identical trajectory, the quarantine ledger's strike/expiry
bookkeeping, digest-verified sharded checkpoints, disk-full containment
(ENOSPC -> storage_full 503 at admission, in-memory-rollback-only
degradation on the checkpoint writer), idempotency-key dedupe, clock-jump
hardening, and the fleet proxy's cross-replica digest voting.

The 2-process ``bitflip@<n>:host1`` soak (host quarantined, zero requests
lost) rides tests/mp_worker.py mode ``integrity_serve`` in the slow tier.
"""

import dataclasses
import errno
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from model_builders import build_rbc17
from rustpde_mpi_tpu.config import IntegrityConfig, IOConfig, ServeConfig
from rustpde_mpi_tpu.integrity import (
    IntegrityError,
    QuarantineLedger,
    flip_state_bit,
)
from rustpde_mpi_tpu.serve import AdmissionError, DurableQueue, SimRequest, SimServer
from rustpde_mpi_tpu.utils import checkpoint as cp
from rustpde_mpi_tpu.utils.journal import read_journal
from rustpde_mpi_tpu.utils.resilience import ResilientRunner

_FIELDS = ("temp", "velx", "vely", "pres")


def _events(run_dir):
    return [e for e in read_journal(os.path.join(run_dir, "journal.jsonl"),
                                    on_error="skip")]


def _armed17(cadence=1):
    model = build_rbc17()
    model.set_integrity(IntegrityConfig(cadence=cadence))
    return model


def _digest(model):
    return np.asarray(model.state_digest_async().result())


# -- digests ------------------------------------------------------------------


def test_digest_deterministic_and_single_bit_sensitive():
    model = _armed17()
    d0 = _digest(model)
    assert d0.dtype == np.uint32
    assert np.array_equal(d0, _digest(model))  # pure consumer, no drift
    # one mantissa-bit flip is visible; flipping the same bit back restores
    clean = model.state
    model.state, info = flip_state_bit(model.state, step=7)
    model._obs_cache = None
    d1 = _digest(model)
    assert not np.array_equal(d0, d1), info
    model.state, _ = flip_state_bit(model.state, step=7)
    model._obs_cache = None
    assert np.array_equal(d0, _digest(model))
    model.state = clean


def test_ensemble_member_digests_localize_the_flip():
    from rustpde_mpi_tpu import NavierEnsemble

    ens = NavierEnsemble.from_seeds(build_rbc17(), seeds=range(3))
    ens.set_integrity(IntegrityConfig())
    d0 = _digest(ens)
    assert d0.shape == (3,)
    ens.state, info = flip_state_bit(ens.state, step=4, member=1)
    ens._obs_cache = None
    d1 = _digest(ens)
    assert info["member"] == 1
    changed = [int(i) for i in np.flatnonzero(d0 != d1)]
    assert changed == [1]


@pytest.mark.parametrize("n", [8, 14])  # one bucket, and three (8 + 4 + 2)
@pytest.mark.parametrize("kind", ["model", "ensemble"])
def test_shadow_digest_replays_the_live_chunk_and_keeps_its_snapshot(kind, n):
    """The audit re-steps the snapshot through the live chunk's own
    programs: same digest, and the snapshot, which nothing copies for the
    audit, stays whole."""
    import jax

    from rustpde_mpi_tpu import NavierEnsemble

    sim = _armed17()
    if kind == "ensemble":
        sim = NavierEnsemble.from_seeds(build_rbc17(), seeds=range(2))
        sim.set_integrity(IntegrityConfig())
    snap = sim.integrity_snapshot()
    shadow = np.asarray(sim.shadow_digest_async(snap, n).result())
    sim.update_n(n)
    assert np.array_equal(shadow, _digest(sim))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(snap["state"]))
    again = np.asarray(sim.shadow_digest_async(snap, n).result())
    assert np.array_equal(shadow, again)  # the snapshot was not consumed


# -- runner: detection, rollback, bit-equality --------------------------------


def _run17(tmp_path, name, *, integrity, fault=None):
    model = build_rbc17()
    if integrity:
        model.set_integrity(IntegrityConfig(cadence=1))
    runner = ResilientRunner(
        model,
        max_time=0.4,
        run_dir=str(tmp_path / name),
        checkpoint_every_s=None,
        max_chunk_steps=8,
        fault=fault,
        io=IOConfig(async_checkpoints=False, overlap_dispatch=False),
    )
    summary = runner.run()
    return model, summary


def test_bitflip_caught_rolled_back_and_bit_equal_to_clean(tmp_path):
    """The tentpole acceptance path: an injected silent flip is detected
    by the shadow audit, contained by an in-memory rollback to the last
    verified state, and the completed run's final state is BIT-EQUAL to
    an uninjected run's — and arming digests does not perturb the
    trajectory (clean armed == clean disarmed)."""
    clean_off, _ = _run17(tmp_path, "clean_off", integrity=False)
    clean_on, _ = _run17(tmp_path, "clean_on", integrity=True)
    hit, summary = _run17(tmp_path, "flip", integrity=True, fault="bitflip@16")
    assert summary["outcome"] == "done"
    for name in _FIELDS:
        a = np.asarray(getattr(clean_off.state, name))
        b = np.asarray(getattr(clean_on.state, name))
        c = np.asarray(getattr(hit.state, name))
        np.testing.assert_array_equal(a, b, err_msg=f"armed-vs-off {name}")
        np.testing.assert_array_equal(a, c, err_msg=f"injected {name}")
    names = [e.get("event") for e in _events(tmp_path / "flip")]
    assert "bitflip_injected" in names
    assert "integrity_mismatch" in names
    assert "integrity_rollback" in names
    # audits resume (and pass) after the rollback
    assert names.index("integrity_rollback") < len(names) - 1
    ok_audits = [e for e in _events(tmp_path / "flip")
                 if e.get("event") == "integrity_audit"
                 and e.get("result") == "ok"]
    assert ok_audits
    # the clean run never fired a mismatch
    clean_names = [e.get("event") for e in _events(tmp_path / "clean_on")]
    assert "integrity_mismatch" not in clean_names


def test_bitflip_without_integrity_is_silent_wrong_but_finite(tmp_path):
    """Integrity OFF control: the same injection completes with no
    detection — a wrong-but-finite answer, which is exactly the failure
    mode the digests exist to close."""
    clean, _ = _run17(tmp_path, "ctl_clean", integrity=False)
    hit, summary = _run17(tmp_path, "ctl_flip", integrity=False,
                          fault="bitflip@16")
    assert summary["outcome"] == "done"
    names = [e.get("event") for e in _events(tmp_path / "ctl_flip")]
    assert "bitflip_injected" in names
    assert "integrity_mismatch" not in names
    diff = False
    for name in _FIELDS:
        a = np.asarray(getattr(clean.state, name))
        b = np.asarray(getattr(hit.state, name))
        assert np.isfinite(b).all(), name
        diff = diff or not np.array_equal(a, b)
    assert diff  # wrong: the corruption propagated into the answer


# -- quarantine ledger --------------------------------------------------------


def test_quarantine_ledger_strikes_expiry_and_persistence(tmp_path):
    now = [1000.0]
    led = QuarantineLedger(str(tmp_path), strikes=2, strike_ttl_s=60.0,
                           clock=lambda: now[0])
    assert led.strike("cpu:0@proc0", step=5, detail="shadow") is False
    assert led.strikes_for("cpu:0@proc0") == 1
    assert led.quarantined() == ()
    # a second strike within the TTL quarantines, exactly once
    assert led.strike("cpu:0@proc0", step=9, detail="chain") is True
    assert led.strike("cpu:0@proc0", step=11) is False  # already quarantined
    assert led.quarantined() == ("cpu:0@proc0",)
    # strikes EXPIRE: a transient upset decays instead of accumulating
    assert led.strike("cpu:1@proc0", step=2) is False
    now[0] += 120.0
    assert led.strikes_for("cpu:1@proc0") == 0
    assert led.strike("cpu:1@proc0", step=3) is False  # count restarted
    # quarantine does NOT expire, and the file round-trips a fresh reader
    led2 = QuarantineLedger(str(tmp_path), strikes=2, clock=lambda: now[0])
    assert led2.is_quarantined("cpu:0@proc0")
    assert led2.quarantined() == ("cpu:0@proc0",)


# -- verified checkpoints -----------------------------------------------------


def test_sharded_checkpoint_carries_and_verifies_digest(tmp_path):
    model = _armed17()
    model.update_n(4)
    path = cp.checkpoint_path(str(tmp_path), 4)
    cp.write_sharded_snapshot(model, path, step=4)
    # the manifest's replicated root data carries the on-device digest
    assert "integrity_digest" in {k for k, *_ in model.snapshot_root_items()}
    # restore recomputes and compares: the device->disk->device loop closes
    target = _armed17()
    target.read(path)
    for name in _FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(model.state, name)),
            np.asarray(getattr(target.state, name)),
            err_msg=name,
        )
    # a manifest digest that does not match the restored state is a typed
    # rejection naming the checkpoint check
    with pytest.raises(IntegrityError) as exc:
        target._verify_restored_digest(np.uint32(0xDEAD))
    assert exc.value.check == "checkpoint"


# -- disk-full containment ----------------------------------------------------


def test_enospc_checkpoint_degrades_to_memory_rollback(tmp_path, monkeypatch):
    """ENOSPC on the async checkpoint writer journals
    ``checkpoint_failed{errno}`` and flips the run to
    in-memory-rollback-only: later checkpoints are skipped (journaled),
    the writer is unwedged, and the run still completes."""
    model = build_rbc17()
    run_dir = str(tmp_path / "run")

    def boom(snap, path):
        raise OSError(errno.ENOSPC, "No space left on device", path)

    monkeypatch.setattr(cp, "write_host_snapshot", boom)
    runner = ResilientRunner(
        model,
        max_time=0.04,
        run_dir=run_dir,
        checkpoint_every_s=0.0,
        max_chunk_steps=8,
        io=IOConfig(async_checkpoints=True, overlap_dispatch=False),
    )
    summary = runner.run()
    assert summary["outcome"] == "done"
    assert runner._ckpt_disabled
    rows = _events(tmp_path / "run")
    failed = [e for e in rows if e.get("event") == "checkpoint_failed"]
    assert any(e.get("errno") == errno.ENOSPC for e in failed)
    assert any(e.get("degraded") == "in_memory_rollback_only" for e in failed)
    assert any(e.get("event") == "checkpoint_skipped"
               and e.get("cause") == "storage_full" for e in rows)


def test_enospc_admission_is_typed_storage_full(tmp_path, monkeypatch):
    q = DurableQueue(str(tmp_path / "q"))

    def full(req):
        raise OSError(errno.ENOSPC, "No space left on device")

    monkeypatch.setattr(q, "_enqueue", full)
    with pytest.raises(AdmissionError) as exc:
        q.submit(SimRequest(ra=1e4, horizon=0.1))
    assert exc.value.reason == "storage_full"
    assert exc.value.retry_after_s > 0
    # any OTHER OSError still propagates raw — only disk-full is admission
    monkeypatch.setattr(
        q, "_enqueue",
        lambda req: (_ for _ in ()).throw(OSError(errno.EACCES, "denied")),
    )
    with pytest.raises(OSError):
        q.submit(SimRequest(ra=1e4, horizon=0.1))


# -- idempotency keys ---------------------------------------------------------


def test_idempotency_key_dedupes_across_queue_reopen(tmp_path):
    q = DurableQueue(str(tmp_path / "q"))
    first = SimRequest(ra=1e4, horizon=0.1, idempotency_key="job-42")
    q.submit(first)
    retry = SimRequest(ra=1e4, horizon=0.1, idempotency_key="job-42")
    q.submit(retry)
    assert retry.deduped and retry.id == first.id
    assert q.counts()["queued"] == 1  # nothing new enqueued
    # the index is durable: a fresh queue over the same dir still dedupes
    q2 = DurableQueue(str(tmp_path / "q"))
    retry2 = SimRequest(ra=1e4, horizon=0.1, idempotency_key="job-42")
    q2.submit(retry2)
    assert retry2.deduped and retry2.id == first.id
    # different key -> ordinary admission
    other = SimRequest(ra=1e4, horizon=0.1, idempotency_key="job-43")
    q2.submit(other)
    assert not getattr(other, "deduped", False) and other.id != first.id


def test_idempotency_key_validation():
    from rustpde_mpi_tpu.serve.request import RequestError

    for bad in ("", 7, "x" * 257):
        with pytest.raises(RequestError, match="idempotency_key"):
            SimRequest(ra=1e4, horizon=0.1, idempotency_key=bad).validate()
    SimRequest(ra=1e4, horizon=0.1, idempotency_key="ok").validate()


def _serve_cfg(tmp_path, **kw):
    kw.setdefault("run_dir", str(tmp_path / "serve"))
    kw.setdefault("slots", 2)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("checkpoint_every_s", None)
    kw.setdefault("http_port", None)
    return ServeConfig(**kw)


def test_server_dedupes_before_admission_policy(tmp_path):
    """A retry of already-accepted work must get its ack back even
    through a FULL queue: the dedupe check runs before every admission
    bound, so backpressure cannot 429 an idempotent replay."""
    srv = SimServer(_serve_cfg(tmp_path, max_queue=2))
    req = dict(ra=1e4, pr=1.0, nx=17, ny=17, dt=0.01, horizon=0.1,
               idempotency_key="retry-me")
    first = srv.submit(dict(req))
    srv.submit(dict(ra=1e4, pr=1.0, nx=17, ny=17, dt=0.01, horizon=0.1))
    with pytest.raises(AdmissionError):  # queue now full for NEW work
        srv.submit(dict(ra=1e4, pr=1.0, nx=17, ny=17, dt=0.01, horizon=0.1))
    replay = srv.submit(dict(req))
    assert replay.deduped and replay.id == first.id
    assert replay.trace_id == first.trace_id
    names = [e.get("event") for e in _events(tmp_path / "serve")]
    assert "request_deduped" in names


def test_http_front_deduped_200_and_storage_full_503(tmp_path, monkeypatch):
    from rustpde_mpi_tpu.serve.http_front import HttpFront

    srv = SimServer(_serve_cfg(tmp_path))
    front = HttpFront(srv)
    front.start()
    try:
        host, port = front.address

        def post(payload):
            req = urllib.request.Request(
                f"http://{host}:{port}/requests",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            try:
                with urllib.request.urlopen(req) as resp:
                    return resp.status, json.load(resp), dict(resp.headers)
            except urllib.error.HTTPError as err:
                return err.code, json.load(err), dict(err.headers)

        body = dict(ra=1e4, pr=1.0, nx=17, ny=17, dt=0.01, horizon=0.1,
                    idempotency_key="http-key")
        code, ack, _ = post(body)
        assert code == 202 and "deduped" not in ack
        code, ack2, _ = post(body)
        assert code == 200 and ack2["deduped"] is True
        assert ack2["id"] == ack["id"]
        # ENOSPC surfaces as 503 + Retry-After (service impaired, not the
        # client over a bound — load balancers fail over on 5xx)
        monkeypatch.setattr(
            srv.queue, "_enqueue",
            lambda req: (_ for _ in ()).throw(
                OSError(errno.ENOSPC, "No space left on device")
            ),
        )
        code, payload, headers = post(
            dict(ra=1e4, pr=1.0, nx=17, ny=17, dt=0.01, horizon=0.1)
        )
        assert code == 503
        assert payload["reason"] == "storage_full"
        assert int(headers["Retry-After"]) >= 1
    finally:
        front.stop()


# -- quarantine-aware carving + unhealthy heartbeat ---------------------------


def test_carve_excludes_quarantined_devices_and_waives_total_loss(tmp_path):
    import jax

    from rustpde_mpi_tpu.config import SubmeshConfig

    cfg = _serve_cfg(
        tmp_path,
        submesh=SubmeshConfig(shapes=(2,), shard_min_nx=34),
        integrity=IntegrityConfig(strikes=1),
    )
    srv = SimServer(cfg)
    devs = jax.devices()

    def key(d):
        return f"{d.platform}:{d.id}@proc{getattr(d, 'process_index', 0)}"

    led = QuarantineLedger(cfg.run_dir, strikes=1)
    led.strike(key(devs[0]), step=1, detail="shadow")
    plan = srv._carve_plan()
    planned = {key(d) for s in plan.submeshes for d in s.devices}
    if plan.default is not None:
        planned |= {key(d) for d in plan.default.devices}
    assert key(devs[0]) not in planned
    rows = [e for e in _events(tmp_path / "serve")
            if e.get("event") == "carve_excluded_quarantined"]
    assert rows and rows[-1]["waived"] is False
    # every device struck: quarantine is WAIVED — never carve an empty fleet
    for d in devs:
        led.strike(key(d), step=2, detail="shadow")
    srv._submesh_plan = None
    srv._submesh_meshes.clear()
    plan = srv._carve_plan()
    planned = {key(d) for s in plan.submeshes for d in s.devices}
    if plan.default is not None:
        planned |= {key(d) for d in plan.default.devices}
    assert key(devs[0]) in planned
    rows = [e for e in _events(tmp_path / "serve")
            if e.get("event") == "carve_excluded_quarantined"]
    assert rows[-1]["waived"] is True


# -- clock-jump hardening -----------------------------------------------------


def test_clock_monitor_one_shot_journal_and_reanchor():
    from rustpde_mpi_tpu.serve.fleet.clock import ClockMonitor

    wall, mono = [1000.0], [50.0]
    mon = ClockMonitor(wall=lambda: wall[0], mono=lambda: mono[0])
    rows = []
    assert mon.check(30.0, journal=rows.append, where="t") == 0.0
    wall[0] += 5.0
    mono[0] += 5.0  # ordinary passage of time: no skew
    assert mon.check(30.0, journal=rows.append, where="t") == 0.0
    wall[0] += 300.0  # NTP step forward, monotonic unchanged
    with pytest.warns(RuntimeWarning, match="clock stepped"):
        skew = mon.check(30.0, journal=rows.append, where="t")
    assert skew == pytest.approx(300.0)
    assert [r["event"] for r in rows] == ["clock_skew"]
    # re-anchored: the step became the new normal after one grace scan
    assert mon.check(30.0, journal=rows.append, where="t") == 0.0
    assert len(rows) == 1
    # a BACKWARD step is still compensated, but the warn/journal latch is
    # one-shot per process — later steps ride the same root cause silently
    wall[0] -= 200.0
    assert mon.check(30.0, journal=rows.append, where="t") < 0.0
    assert len(rows) == 1


def test_replica_status_clamps_negative_ages(tmp_path):
    from rustpde_mpi_tpu.serve.fleet.proxy import (
        read_replica_status,
        write_replica_heartbeat,
    )

    write_replica_heartbeat(str(tmp_path), "r0", {"slots": []})
    # a file stamped in the future (writer's clock ahead of the reader's)
    # must clamp to age 0, not go negative / mass-expire
    path = os.path.join(str(tmp_path), "replicas", "r0.json")
    future = os.path.getmtime(path) + 3600.0
    os.utime(path, (future, future))
    (status,) = read_replica_status(str(tmp_path), ttl_s=10.0)
    assert status["hb_age_s"] == 0.0
    assert not status["stale"]


# -- cross-replica voting -----------------------------------------------------


def _done_record(run_dir, rid, digest):
    done = os.path.join(run_dir, "queue", "done")
    os.makedirs(done, exist_ok=True)
    result = {} if digest is None else {"state_digest": int(digest)}
    with open(os.path.join(done, f"{rid}.json"), "w") as fh:
        json.dump({"request": {"id": rid}, "result": result}, fh)


def test_proxy_vote_assignment_and_digest_comparison(tmp_path):
    from rustpde_mpi_tpu.serve.fleet.proxy import FleetProxy

    proxy = FleetProxy(str(tmp_path), vote_rate=1.0)
    proxy_journal = os.path.join(
        str(tmp_path), "replicas", proxy.proxy_id
    )
    req = proxy.submit(
        dict(ra=1e4, pr=1.0, nx=17, ny=17, dt=0.01, horizon=0.1)
    )
    counts = proxy.queue.counts()
    assert counts["queued"] == 2  # original + its .vote twin
    names = [e.get("event") for e in _events(proxy_journal)]
    assert "vote_assigned" in names
    # matching digests -> match True; differing -> mismatch journaled;
    # missing digests (integrity off) -> match None, never a false alarm
    _done_record(str(tmp_path), req.id, 77)
    _done_record(str(tmp_path), f"{req.id}.vote", 77)
    _done_record(str(tmp_path), "bad", 1)
    _done_record(str(tmp_path), "bad.vote", 2)
    _done_record(str(tmp_path), "off", None)
    _done_record(str(tmp_path), "off.vote", None)
    verdicts = {v["id"]: v["match"] for v in proxy.check_votes()}
    assert verdicts == {req.id: True, "bad": False, "off": None}
    assert proxy.check_votes() == []  # each pair verdicted exactly once
    events = _events(proxy_journal)
    mism = [e for e in events if e.get("event") == "integrity_vote_mismatch"]
    assert [e["id"] for e in mism] == ["bad"]
    assert len([e for e in events if e.get("event") == "integrity_vote"]) == 3
    # voting never votes on a vote (no .vote.vote amplification)
    assert not any(r.endswith(".vote.vote.json")
                   for r in os.listdir(os.path.join(str(tmp_path), "queue",
                                                    "queued")))


def test_vote_rate_sampling_is_deterministic(tmp_path):
    from rustpde_mpi_tpu.serve.fleet.proxy import FleetProxy

    off = FleetProxy(str(tmp_path / "a"), vote_rate=0.0)
    assert not off._vote_sampled(SimRequest(ra=1e4, horizon=0.1))
    on = FleetProxy(str(tmp_path / "b"), vote_rate=1.0)
    req = SimRequest(ra=1e4, horizon=0.1)
    assert on._vote_sampled(req)
    twin = dataclasses.replace(req, id=f"{req.id}.vote")
    assert not on._vote_sampled(twin)


# -- serve-level SDC soak (single-process CPU, slow tier) ---------------------


@pytest.mark.slow
def test_serve_bitflip_quarantine_containment(tmp_path):
    """Single-process serve soak: a bitflip mid-campaign with a
    single-strike ledger must quarantine the device (journal
    ``device_quarantined``), contain via IntegrityError (journal
    ``integrity_contained``, requeue), flag the replica unhealthy, and
    still complete every request — zero lost."""
    cfg = _serve_cfg(
        tmp_path,
        max_queue=16,
        checkpoint_every_s=2.0,
        integrity=IntegrityConfig(cadence=1, strikes=1),
    )
    srv = SimServer(cfg, fault="bitflip@8")
    for seed in range(3):
        srv.submit(dict(ra=1e4, pr=1.0, nx=17, ny=17, dt=0.01,
                        horizon=0.1, seed=seed))
    summary = srv.serve()
    assert summary["completed"] == 3 and summary["failed"] == 0
    counts = srv.queue.counts()
    assert counts["queued"] == 0 and counts["running"] == 0
    names = [e.get("event") for e in _events(tmp_path / "serve")]
    assert "bitflip_injected" in names
    assert "integrity_mismatch" in names
    assert "device_quarantined" in names
    assert "integrity_contained" in names
    assert QuarantineLedger(cfg.run_dir, strikes=1).quarantined()
    assert srv._integrity_unhealthy
    # every done record carries the on-device digest (the vote currency)
    done_dir = os.path.join(cfg.run_dir, "queue", "done")
    for name in os.listdir(done_dir):
        with open(os.path.join(done_dir, name)) as fh:
            rec = json.load(fh)
        assert "state_digest" in rec["result"], name


@pytest.mark.slow
def test_mp_integrity_serve_host_bitflip_quarantined_zero_lost(tmp_path):
    """The acceptance soak: 2-process serve under
    ``RUSTPDE_FAULT=bitflip@<n>:host1`` — the audit catches the flip, the
    single-strike ledger quarantines, containment requeues, and every
    request completes."""
    from mp_harness import spawn_cluster

    outs = spawn_cluster(
        str(tmp_path),
        mode="integrity_serve",
        env_extra={
            "RUSTPDE_FAULT": "bitflip@6:host1",
            "RUSTPDE_MP_SERVE_REQUESTS": "3",
        },
    )
    if outs is None:
        pytest.skip("2-process cluster spawn timed out on this machine")
    with open(os.path.join(str(tmp_path), "result.json")) as fh:
        result = json.load(fh)
    assert result["nproc"] == 2
    assert result["bitflip_injected"] >= 1
    assert result["integrity_mismatch"] >= 1
    assert result["device_quarantined"] >= 1
    assert result["integrity_contained"] >= 1
    assert result["quarantined"], result
    # zero lost: everything admitted completed; nothing stranded
    assert result["completed"] == 3 and result["failed"] == 0
    assert result["queue"]["queued"] == 0
    assert result["queue"]["running"] == 0


def test_integrity_exports_and_env_knobs():
    import rustpde_mpi_tpu.integrity as integ
    from rustpde_mpi_tpu import config

    for name in ("IntegrityError", "QuarantineLedger", "digest_tree",
                 "flip_one_bit", "flip_state_bit"):
        assert hasattr(integ, name), name
    knobs = dict(config.env_knobs())
    for knob in ("RUSTPDE_INTEGRITY", "RUSTPDE_INTEGRITY_CADENCE",
                 "RUSTPDE_VOTE_RATE"):
        assert knob in knobs, knob
    assert threading  # imported for parity with the serve test style
