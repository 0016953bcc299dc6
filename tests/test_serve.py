"""Simulation-service tests (rustpde_mpi_tpu/serve/): durable queue +
admission control, continuous batching with per-request fault isolation,
dt-backoff retries into the typed RequestFailed terminal state, SIGTERM
graceful drain + restart-with-restore, the thin HTTP front, strict fault
spec parsing, torn-journal tolerance, and the public robustness API.

The chaos soak (≥200 requests / ≤8 slots under NaNs + a hard kill + a
drain/restart cycle, driven through subprocesses) lives in the slow tier;
the tier-1 tests here exercise every code path at small scale on the
shared 17^2 jit shapes (tests/model_builders.py).
"""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import pytest

from rustpde_mpi_tpu import Navier2D, RequestFailed
from rustpde_mpi_tpu.config import ServeConfig
from rustpde_mpi_tpu.serve import (
    AdmissionError,
    DurableQueue,
    RequestError,
    SimRequest,
    SimServer,
)
from rustpde_mpi_tpu.utils.faults import FaultSpecError
from rustpde_mpi_tpu.utils.journal import JournalError, JournalWriter, read_journal

h5py = pytest.importorskip("h5py")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the shared tier shapes: 17^2 rbc, dt=0.01 (and dt=0.005 on the retry
# bucket — the same shapes test_resilience's backoff tests compile)
_REQ = dict(ra=1e4, pr=1.0, nx=17, ny=17, dt=0.01, horizon=0.1, bc="rbc")


def _cfg(tmp_path, **kw):
    kw.setdefault("run_dir", str(tmp_path / "serve"))
    kw.setdefault("slots", 2)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("checkpoint_every_s", None)
    kw.setdefault("http_port", None)
    return ServeConfig(**kw)


def _events(run_dir):
    return read_journal(os.path.join(run_dir, "journal.jsonl"))


def _solo_nu(result):
    """Solo rerun of one done-record's trajectory: same seed/dt/steps, the
    single-model step path (no vmap, no batching)."""
    m = Navier2D(17, 17, 1e4, 1.0, result["dt"], 1.0, "rbc", periodic=False)
    m.init_random(result.get("amp") or 0.1, seed=result["seed"])
    m.update_n(result["steps"])
    return float(m.eval_nu())


def _parse_prometheus(text):
    """Strict-enough parser for the exposition format: every line must be a
    ``# HELP``/``# TYPE`` comment or ``name[{labels}] value``; returns
    ``{name: {labels_str: (value,)}}`` and asserts the format en route."""
    import re

    samples = {}
    line_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? ([0-9eE+.\-]+|NaN|[+-]Inf)$"
    )
    for line in text.splitlines():
        if not line or line.startswith("#"):
            if line:
                assert line.startswith(("# HELP ", "# TYPE ")), line
            continue
        m = line_re.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        name, labels, value = m.groups()
        samples.setdefault(name, {})[labels or ""] = (float(value),)
    return samples


# -- requests + queue ---------------------------------------------------------


def test_request_validation_and_compat_key():
    req = SimRequest(**_REQ, seed=3)
    assert req.id and req.steps == 10
    assert req.compat_key == Navier2D(
        17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False
    ).compat_key
    for bad in (
        dict(_REQ, dt=-1.0),
        dict(_REQ, horizon=0.0),
        dict(_REQ, bc="typo"),
        dict(_REQ, nx=2),
        dict(_REQ, ra=-5.0),
    ):
        with pytest.raises(RequestError):
            SimRequest(**bad).validate()
    with pytest.raises(RequestError, match="unknown request fields"):
        SimRequest.from_dict(dict(_REQ, nonsense=1))
    # dt backoff re-buckets and records the trajectory
    retry = req.backed_off(0.5)
    assert retry.dt == pytest.approx(0.005)
    assert retry.retries == 1 and retry.dts == [0.01, 0.005]
    assert retry.compat_key != req.compat_key


def test_request_json_roundtrip_and_progress():
    req = SimRequest(**_REQ, seed=4)
    clone = SimRequest.from_json(req.to_json())
    assert clone == req
    assert clone.steps_remaining == clone.steps == 10
    # drained-campaign bookkeeping: progress reduces the remaining debt
    import dataclasses as dc

    resumed = dc.replace(clone, progress=6)
    assert resumed.steps_remaining == 4
    # backoff discards progress (a diverged trajectory is not resumable)
    assert resumed.backed_off(0.5).progress == 0


def test_admission_rejects_while_draining(tmp_path):
    srv = SimServer(_cfg(tmp_path))
    srv.request_drain()
    with pytest.raises(AdmissionError) as exc:
        srv.submit(dict(_REQ, seed=0))
    assert exc.value.reason == "draining"


def test_queue_lifecycle_recovery_and_admission(tmp_path):
    q = DurableQueue(str(tmp_path / "q"), max_queue=2)
    a = q.submit(SimRequest(**_REQ, seed=0))
    b = q.submit(SimRequest(**_REQ, seed=1))
    # bounded-queue backpressure: typed reject-with-reason, nothing written
    with pytest.raises(AdmissionError, match="queue_full") as exc:
        q.submit(SimRequest(**_REQ, seed=2))
    assert exc.value.reason == "queue_full"
    with pytest.raises(AdmissionError, match="draining"):
        q.submit(SimRequest(**_REQ, seed=2), admit_open=False)
    assert q.counts() == {"queued": 2, "running": 0, "done": 0, "failed": 0}
    # FIFO claim into running/, resolution into done/
    got = q.claim(a.compat_key)
    assert got.id == a.id
    q.complete(got, {"nu": 1.0})
    assert q.lookup(a.id)[0] == "done"
    # claim_id targets a specific queued request
    assert q.claim_id("nonexistent") is None
    assert q.claim_id(b.id).id == b.id
    # a crashed owner's running request is recovered, never lost
    assert q.recover() == [b.id]
    assert q.counts()["queued"] == 1
    assert q.lookup(b.id)[0] == "queued"
    # terminal failure record keeps the dt trajectory
    bad = q.claim()
    q.fail(bad, "diverged hard")
    state, record = q.lookup(bad.id)
    assert state == "failed" and record["error"]["dts"] == [0.01]


# -- torn journal (SIGKILL mid-append) ----------------------------------------


def test_torn_journal_tail_skipped_interior_raises(tmp_path, capsys):
    path = str(tmp_path / "journal.jsonl")
    w = JournalWriter(path)
    w.append({"event": "a"})
    w.append({"event": "b"})
    w.close()
    # a SIGKILL mid-append tears the FINAL line: skipped with a warning
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"event": "torn-mid-wri')
    records = read_journal(path)
    assert [r["event"] for r in records] == ["a", "b"]
    assert "torn trailing record" in capsys.readouterr().err
    # interior garbage is NOT a crash artifact: typed raise (or skip on ask)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write('{"event": "a"}\nGARBAGE\n{"event": "c"}\n')
    with pytest.raises(JournalError, match="interior"):
        read_journal(path)
    assert [r["event"] for r in read_journal(path, on_error="skip")] == ["a", "c"]
    # a missing journal is an empty one
    assert read_journal(str(tmp_path / "nope.jsonl")) == []


# -- strict fault-spec parsing at startup -------------------------------------


def test_malformed_fault_specs_raise_at_startup(tmp_path, monkeypatch, stepped_rbc17):
    from rustpde_mpi_tpu import ResilientRunner
    from rustpde_mpi_tpu.utils.faults import parse_shard_crash_spec

    # RUSTPDE_SHARD_CRASH is validated by the harness constructors even
    # though only the checkpoint writer consumes it: a chaos spec that
    # cannot fire must die before any stepping
    monkeypatch.setenv("RUSTPDE_SHARD_CRASH", "mid_write@4")
    with pytest.raises(FaultSpecError, match="crash point"):
        ResilientRunner(stepped_rbc17, max_time=0.1, run_dir=str(tmp_path))
    with pytest.raises(FaultSpecError):
        SimServer(_cfg(tmp_path))
    monkeypatch.delenv("RUSTPDE_SHARD_CRASH")
    monkeypatch.setenv("RUSTPDE_FAULT", "nan@notastep")
    with pytest.raises(FaultSpecError, match="bad step"):
        ResilientRunner(stepped_rbc17, max_time=0.1, run_dir=str(tmp_path))
    monkeypatch.delenv("RUSTPDE_FAULT")
    for bad in ("after_shard", "after_shard@x", "before_manifest@3:hostX"):
        with pytest.raises(FaultSpecError):
            parse_shard_crash_spec(bad)
    assert parse_shard_crash_spec(None) is None
    assert parse_shard_crash_spec("before_manifest@7") == ("before_manifest", 7, None)


def test_fault_plan_host_scope_parsing_and_locality():
    from rustpde_mpi_tpu.utils.faults import FaultPlan

    plan = FaultPlan.from_spec("kill@9:host2")
    assert (plan.kind, plan.step, plan.host) == ("kill", 9, 2)
    # single-process runtime: only host 0's scope acts here
    assert FaultPlan.from_spec("nan@3:host0").scoped_here() is True
    assert FaultPlan.from_spec("nan@3:host2").scoped_here() is False
    assert FaultPlan.from_spec("nan@3").scoped_here() is True
    assert FaultPlan.from_spec(None) is None and FaultPlan.from_spec("") is None
    for bad in ("nan@3:hostX", "nan@3:2", "kill@3:"):
        with pytest.raises(FaultSpecError):
            FaultPlan.from_spec(bad)


def test_read_journal_blank_lines_and_bad_mode(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with open(path, "w", encoding="utf-8") as fh:
        fh.write('{"event": "a"}\n\n   \n{"event": "b"}\n')
    assert [r["event"] for r in read_journal(path)] == ["a", "b"]
    with pytest.raises(ValueError, match="on_error"):
        read_journal(path, on_error="ignore")


def test_queue_rejects_malformed_without_writing(tmp_path):
    q = DurableQueue(str(tmp_path / "q"), max_queue=4)
    with pytest.raises(RequestError):
        q.submit(SimRequest(**dict(_REQ, dt=-1.0), seed=0))
    assert q.counts() == {"queued": 0, "running": 0, "done": 0, "failed": 0}
    assert q.lookup("nope") is None
    assert q.oldest_bucket() is None and q.claim() is None


def test_request_failed_carries_trajectory():
    err = RequestFailed("abc123", "diverged", [0.01, 0.005])
    assert err.request_id == "abc123"
    assert err.dt_trajectory == [0.01, 0.005]
    assert "abc123" in str(err) and "0.005" in str(err)


def test_campaign_dirs_stable_per_bucket(tmp_path):
    srv = SimServer(_cfg(tmp_path))
    a = SimRequest(**_REQ, seed=0)
    b = SimRequest(**dict(_REQ, dt=0.005), seed=0)
    assert srv._campaign_dir(a.compat_key) == srv._campaign_dir(a.compat_key)
    assert srv._campaign_dir(a.compat_key) != srv._campaign_dir(b.compat_key)
    assert srv.http_address is None  # http_port=None: no front bound


# -- the service: batching, isolation, retries --------------------------------


def test_serve_batch_completes_and_matches_solo(tmp_path):
    """5 requests through 2 slots: continuous refill (a finished slot is
    handed the next queued request mid-campaign), every request resolves,
    and each result matches its solo single-model run — the per-request
    isolation contract, asserted against ground truth."""
    srv = SimServer(_cfg(tmp_path, slots=2))
    ids = [srv.submit(dict(_REQ, seed=s)).id for s in range(5)]
    summary = srv.serve()
    assert summary["outcome"] == "idle"
    assert summary["completed"] == 5 and summary["failed"] == 0
    assert srv.queue.counts() == {"queued": 0, "running": 0, "done": 5, "failed": 0}
    slots_used = set()
    for i, rid in enumerate(ids):
        res = srv.result(rid)
        assert res["steps"] == 10 and res["retries"] == 0
        assert res["latency_s"] > 0
        slots_used.add(res["slot"])
        if i % 2 == 0:  # solo reruns are the slow part: sample every other
            assert res["nu"] == pytest.approx(_solo_nu(res), rel=1e-9)
    assert slots_used == {0, 1}  # both lanes actually batched work
    events = [e["event"] for e in _events(srv.cfg.run_dir)]
    assert events.count("request_done") == 5
    assert events.count("request_scheduled") == 5
    assert "campaign_end" in events and events[-1] == "server_stop"


def test_serve_divergent_member_is_isolated_and_fails_typed(tmp_path):
    """The multi-tenant nightmare scenario: one co-batched request diverges
    (absurd IC amplitude — same compat bucket, so it shares the batch).
    Its neighbours must complete bit-equal to their solo runs, and the bad
    request must land in the typed RequestFailed terminal state after its
    bounded retries."""
    srv = SimServer(_cfg(tmp_path, slots=3, request_max_retries=1))
    good = [srv.submit(dict(_REQ, seed=s)).id for s in (0, 1)]
    bad = srv.submit(dict(_REQ, seed=7, amp=1e12)).id  # diverges in-batch
    summary = srv.serve()
    assert summary["completed"] == 2 and summary["failed"] == 1
    for rid in good:
        res = srv.result(rid)
        assert res["nu"] == pytest.approx(_solo_nu(res), rel=1e-9)
    with pytest.raises(RequestFailed) as exc:
        srv.result(bad)
    assert exc.value.request_id == bad
    assert exc.value.dt_trajectory == [0.01, 0.005]  # one backoff retry
    events = [e["event"] for e in _events(srv.cfg.run_dir)]
    assert "request_retry" in events and "request_failed" in events


def test_serve_nan_fault_retries_all_members(tmp_path):
    """RUSTPDE_FAULT=nan@k poisons the whole running batch: every in-flight
    request retries at dt/2 (a fresh bucket/campaign) and completes; the
    late-queued request completes at the original dt untouched."""
    srv = SimServer(_cfg(tmp_path, slots=2), fault="nan@6")
    ids = [srv.submit(dict(_REQ, seed=s)).id for s in range(3)]
    summary = srv.serve()
    assert summary["completed"] == 3 and summary["failed"] == 0
    assert summary["retried"] == 2
    dts = sorted(srv.result(r)["dt"] for r in ids)
    assert dts == pytest.approx([0.005, 0.005, 0.01])
    for rid in ids[:2]:  # one retried + one untouched request vs solo
        res = srv.result(rid)
        assert res["nu"] == pytest.approx(_solo_nu(res), rel=1e-9)


def test_serve_admission_and_http_front(tmp_path):
    """Daemon mode behind the HTTP front: submit over POST (202 + fsynced
    durable queue), status/stats/healthz over GET, 400 on garbage, 429
    with a reason once the queue is full, drain over POST — and the drain
    resolves the in-flight request before the server returns."""
    cfg = _cfg(tmp_path, slots=2, max_queue=3, idle_exit=False, poll_s=0.05,
               http_port=0)
    srv = SimServer(cfg)
    done = {}
    thread = threading.Thread(target=lambda: done.update(srv.serve()))
    thread.start()
    try:
        for _ in range(100):
            if srv.http_address is not None:
                break
            thread.join(0.1)
        host, port = srv.http_address
        base = f"http://{host}:{port}"

        def post(path, payload):
            req = urllib.request.Request(
                base + path, data=json.dumps(payload).encode(), method="POST"
            )
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as err:
                return err.code, json.loads(err.read())

        def get(path):
            try:
                with urllib.request.urlopen(base + path, timeout=30) as resp:
                    return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as err:
                return err.code, json.loads(err.read())

        def get_text(path):
            with urllib.request.urlopen(base + path, timeout=30) as resp:
                return resp.status, resp.read().decode("utf-8")

        # enriched /healthz: liveness PLUS queue depth + slot utilization
        # PLUS the fleet shape (process count / mesh topology) an operator
        # needs to see what is serving, not just that it is up
        code, health = get("/healthz")
        assert code == 200
        assert health["ok"] is True and health["draining"] is False
        assert set(health["queue"]) == {"queued", "running", "done", "failed"}
        assert {
            "running", "total", "utilization",
            "process_count", "devices", "mesh",
        } <= set(health["slots"])
        assert health["slots"]["total"] == 2
        assert health["slots"]["process_count"] == 1
        assert health["slots"]["devices"] >= 1
        assert health["slots"]["mesh"] is None  # single-controller run
        code, ack = post("/requests", dict(_REQ, seed=0))
        assert code == 202 and ack["steps"] == 10
        code, err = post("/requests", dict(_REQ, dt=-1.0))
        assert code == 400
        code, err = post("/requests", "not a dict")
        assert code == 400
        # fill the bounded queue: the 429 carries the backpressure reason
        rejected = None
        for seed in range(1, 8):
            code, body = post("/requests", dict(_REQ, seed=seed))
            if code == 429:
                rejected = body
                break
        assert rejected is not None and rejected["reason"] == "queue_full"
        # live /metrics scrape MID-SOAK: the daemon campaign is running the
        # queued requests while this GET renders the registry (the ISSUE's
        # acceptance criterion) — Prometheus-parseable, serve series present
        code, text = get_text("/metrics")
        assert code == 200
        samples = _parse_prometheus(text)
        assert "serve_queue_depth" in samples
        assert "serve_requests_admitted_total" in samples
        assert any(s[0] >= 1 for s in samples["http_requests_total"].values())
        code, status = get(f"/requests/{ack['id']}")
        assert code == 200 and status["state"] in ("queued", "running", "done")
        assert get("/requests/unknown-id")[0] == 404
        code, stats = get("/stats")
        assert code == 200 and "queue" in stats and "slots" in stats
        code, body = post("/drain", {})
        assert code == 202 and body["draining"] is True
        # concurrent submits during the drain: typed 429 with the reason
        code, body = post("/requests", dict(_REQ, seed=99))
        assert code == 429 and body["reason"] == "draining"
    finally:
        srv.request_drain()
        thread.join(timeout=300)
    assert not thread.is_alive()
    assert done["outcome"] == "drained"
    # everything admitted is either resolved or still durably queued: the
    # drain lost nothing
    counts = srv.queue.counts()
    assert counts["running"] == 0
    assert counts["done"] + counts["queued"] + counts["failed"] >= 2


def test_http_front_error_paths(tmp_path):
    """Broken HTTP frames must map to typed statuses, not tracebacks or
    hangs: non-integer / negative Content-Length -> 400, an oversized body
    -> 413 (rejected BEFORE reading), a truncated body (client hung up
    mid-send) -> 400 — and the front serves /metrics + enriched /healthz
    standalone (it only touches the scheduler's thread-safe surface)."""
    import socket

    from rustpde_mpi_tpu.serve.http_front import HttpFront

    srv = SimServer(_cfg(tmp_path))
    front = HttpFront(srv)
    front.start()
    try:
        host, port = front.address

        def raw(request: bytes) -> str:
            # send, then half-close the write side: the server sees EOF on
            # any body read it attempts (the hung-up-client shape), while
            # the read side stays open for the response
            with socket.create_connection((host, port), timeout=30) as sock:
                sock.sendall(request)
                sock.shutdown(socket.SHUT_WR)
                sock.settimeout(30)
                chunks = []
                while True:
                    data = sock.recv(65536)
                    if not data:
                        break
                    chunks.append(data)
            return b"".join(chunks).decode("utf-8", "replace")

        def post_head(extra_headers: str, body: bytes = b"") -> str:
            return raw(
                (
                    "POST /requests HTTP/1.1\r\n"
                    f"Host: {host}\r\nConnection: close\r\n"
                    f"{extra_headers}\r\n"
                ).encode()
                + body
            )

        # bad Content-Length: not an integer
        resp = post_head("Content-Length: nope\r\n")
        assert " 400 " in resp.splitlines()[0], resp.splitlines()[0]
        assert "Content-Length" in resp
        # negative Content-Length
        resp = post_head("Content-Length: -5\r\n")
        assert " 400 " in resp.splitlines()[0], resp.splitlines()[0]
        # oversized body: rejected by the declared length, nothing read
        resp = post_head(f"Content-Length: {(1 << 20) + 1}\r\n")
        assert " 413 " in resp.splitlines()[0], resp.splitlines()[0]
        # truncated body: client promises 100 bytes, sends 12, hangs up
        resp = post_head("Content-Length: 100\r\n", body=b'{"ra": 1e4, ')
        assert " 400 " in resp.splitlines()[0], resp.splitlines()[0]
        assert "truncated" in resp
        # nothing malformed was admitted
        assert srv.queue.counts()["queued"] == 0
        # standalone /metrics + /healthz (no campaign running)
        base = f"http://{host}:{port}"
        with urllib.request.urlopen(base + "/metrics", timeout=30) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            _parse_prometheus(resp.read().decode("utf-8"))
        with urllib.request.urlopen(base + "/healthz", timeout=30) as resp:
            health = json.loads(resp.read())
        assert health["ok"] is True and health["slots"]["running"] == 0
    finally:
        front.stop()


def test_serve_sigterm_drain_checkpoint_restart_resumes(tmp_path):
    """The graceful-drain contract end-to-end, in-process: kill@k fires a
    real SIGTERM mid-campaign -> the server checkpoints the slot table via
    the sharded two-phase writer, re-enqueues unfinished requests and
    returns "drained"; a SECOND server on the same run_dir re-claims the
    requests into their restored slots (mid-trajectory, not from scratch)
    and the final observables still match full solo runs."""
    mk = lambda: _cfg(tmp_path, slots=2)
    srv = SimServer(mk(), fault="kill@8")
    ids = [srv.submit(dict(_REQ, seed=s, horizon=0.2)).id for s in range(3)]
    s1 = srv.serve()
    assert s1["outcome"] == "drained"
    counts = srv.queue.counts()
    assert counts["running"] == 0 and counts["queued"] >= 2  # requeued
    events = _events(str(tmp_path / "serve"))
    requeued = [e for e in events if e["event"] == "request_requeued"]
    assert requeued and all(e["checkpoint"] for e in requeued)
    drained_ids = {e["id"] for e in requeued}

    srv2 = SimServer(mk())
    s2 = srv2.serve()
    assert s2["outcome"] == "idle"
    assert srv2.queue.counts() == {
        "queued": 0, "running": 0, "done": 3, "failed": 0
    }
    events = _events(str(tmp_path / "serve"))
    restored = {
        e["id"]: e for e in events
        if e["event"] == "request_scheduled" and e.get("restored")
    }
    # the drained requests came back mid-trajectory (steps_done > 0)
    assert set(restored) == drained_ids
    assert all(e["steps_done"] > 0 for e in restored.values())
    for rid in ids:
        res = srv2.result(rid)
        assert res["steps"] == 20
        assert res["nu"] == pytest.approx(_solo_nu(res), rel=1e-9)


def test_serve_config_and_ensemble_compat_key(tmp_path):
    from rustpde_mpi_tpu.models.ensemble import NavierEnsemble

    cfg = ServeConfig(run_dir=str(tmp_path), slots=3, max_queue=7)
    assert cfg.slots == 3 and cfg.request_dt_backoff == 0.5
    srv = SimServer(cfg)
    assert srv.queue.max_queue == 7
    # the ensemble's key IS its template model's key (one vmapped jaxpr)
    model = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
    ens = NavierEnsemble.replicate(model, 2)
    assert ens.compat_key == model.compat_key
    assert ens.compat_key == SimRequest(**_REQ).compat_key
    # fresh_member_state leaves the template model's own state untouched
    before = model.state
    state = ens.fresh_member_state(seed=5, amp=0.1)
    assert model.state is before
    assert state.temp.shape == model.state.temp.shape


def test_queue_fifo_order_survives_reopen(tmp_path):
    q = DurableQueue(str(tmp_path / "q"), max_queue=8)
    ids = [q.submit(SimRequest(**_REQ, seed=s)).id for s in range(3)]
    # a NEW queue object over the same directory (process restart) claims
    # in the original submit order — ordering is on-disk, not in-memory
    q2 = DurableQueue(str(tmp_path / "q"), max_queue=8)
    assert [q2.claim().id for _ in range(3)] == ids
    assert q2.claim() is None


def test_journal_writer_reopens_after_close(tmp_path):
    path = str(tmp_path / "j.jsonl")
    w = JournalWriter(path)
    w.append({"event": "a"})
    w.close()
    w.append({"event": "b"})  # lazily reopens
    w.close()
    assert [r["event"] for r in read_journal(path)] == ["a", "b"]


def test_runner_embedding_surface(tmp_path, stepped_rbc17):
    """The session()/advance()/checkpoint_now()/on_boundary() surface the
    serve scheduler embeds: services armed without run()'s driver loop,
    drain flag via request_drain, manual checkpoints on demand."""
    from rustpde_mpi_tpu import ResilientRunner
    from rustpde_mpi_tpu.utils import checkpoint as cp

    runner = ResilientRunner(
        stepped_rbc17,
        max_time=float("inf"),
        run_dir=str(tmp_path / "run"),
        checkpoint_every_s=None,
    )
    with runner.session(install_signals=False, resume=False):
        assert runner.resumed is False
        before = runner.step
        runner.advance(3)
        assert runner.step == before + 3
        assert runner.on_boundary() is False  # no drain requested yet
        path = runner.checkpoint_now("drain")
        assert path and cp.verify_snapshot(path)
        assert int(cp.read_attrs(path)["step"]) == runner.step
        runner.request_drain()
        assert runner.drain_requested() is True
        assert runner.on_boundary() is True  # the embedder's stop signal


def test_drain_restart_grow_replans_and_continues(tmp_path):
    """Elastic fleet GROW across a drain/restart cycle: the restart builds
    the fleet at the checkpoint's slot count, restores every drained
    trajectory MID-FLIGHT, then re-plans onto the larger configured fleet
    — kept requests continue from their checkpointed step counters in the
    new lanes, grown lanes refill from the queue, and the journal records
    a ``campaign_replanned`` event with old/new K."""
    srv = SimServer(_cfg(tmp_path, slots=2), fault="kill@8")
    ids = [srv.submit(dict(_REQ, seed=s, horizon=0.2)).id for s in range(4)]
    assert srv.serve()["outcome"] == "drained"

    srv2 = SimServer(_cfg(tmp_path, slots=3))  # ops grew the fleet
    s2 = srv2.serve()
    assert s2["outcome"] == "idle"
    assert srv2.queue.counts()["done"] == 4 and s2["failed"] == 0
    assert s2["replans"] == 1
    events = _events(str(tmp_path / "serve"))
    replans = [e for e in events if e["event"] == "campaign_replanned"]
    assert len(replans) == 1
    assert replans[0]["old_slots"] == 2 and replans[0]["new_slots"] == 3
    assert replans[0]["kept"] == 2 and replans[0]["parked"] == 0
    # NOT the degrade path: the old checkpoint restored, nothing was swept
    assert all(e["event"] != "campaign_restore_failed" for e in events)
    # the kept requests came back mid-trajectory (steps_done > 0)
    restored = [
        e for e in events
        if e["event"] == "request_scheduled" and e.get("restored")
    ]
    assert len(restored) == 2
    assert all(e["steps_done"] > 0 for e in restored)
    for rid in ids:
        res = srv2.result(rid)
        assert res["steps"] == 20
        assert res["nu"] == pytest.approx(_solo_nu(res), rel=1e-9)
    # fleet-shape telemetry: the re-plan left its marks on the live gauges
    from rustpde_mpi_tpu import telemetry

    snap = telemetry.snapshot()
    assert "serve_fleet_size" in snap and "serve_replans_total" in snap


def test_drain_restart_shrink_replans_parks_and_continues(tmp_path):
    """Elastic fleet SHRINK: 3 drained mid-flight trajectories restart on
    a 2-slot fleet.  Two move into the new lanes; the surplus one is
    PARKED (member state held) and re-enqueued at its checkpointed
    progress — when a lane frees it continues MID-FLIGHT (scheduled with
    ``parked: true`` and a nonzero base), and its final result still
    matches the full solo trajectory."""
    srv = SimServer(_cfg(tmp_path, slots=3), fault="kill@8")
    ids = [srv.submit(dict(_REQ, seed=s, horizon=0.2)).id for s in range(3)]
    assert srv.serve()["outcome"] == "drained"

    srv2 = SimServer(_cfg(tmp_path, slots=2))  # ops shrank the fleet
    s2 = srv2.serve()
    assert s2["outcome"] == "idle"
    assert srv2.queue.counts()["done"] == 3 and s2["failed"] == 0
    events = _events(str(tmp_path / "serve"))
    replans = [e for e in events if e["event"] == "campaign_replanned"]
    assert len(replans) == 1
    assert replans[0]["old_slots"] == 3 and replans[0]["new_slots"] == 2
    assert replans[0]["kept"] == 2 and replans[0]["parked"] == 1
    # the surplus request was requeued parked at its checkpointed progress
    parked_requeues = [
        e for e in events
        if e["event"] == "request_requeued" and e.get("parked")
    ]
    assert len(parked_requeues) == 1 and parked_requeues[0]["progress"] > 0
    # ... and later CONTINUED mid-flight in a freed lane, not restarted
    parked_scheduled = [
        e for e in events
        if e["event"] == "request_scheduled" and e.get("parked")
    ]
    assert len(parked_scheduled) == 1
    assert parked_scheduled[0]["id"] == parked_requeues[0]["id"]
    assert parked_scheduled[0]["base"] == parked_requeues[0]["progress"]
    for rid in ids:
        res = srv2.result(rid)
        assert res["steps"] == 20
        assert res["nu"] == pytest.approx(_solo_nu(res), rel=1e-9)


def test_serve_governed_bucket_dt_rebucket(tmp_path, monkeypatch):
    """The governed-campaign gate, in-process: a velocity spike hits the
    running bucket mid-campaign.  With ``cfg.stability`` armed the
    on-device CFL sentinels catch it while every member is still FINITE,
    the chunk rolls back in memory, and the pinned requests are re-bucketed
    at a lower rung of the per-bucket dt ladder WITH their state (journal
    ``bucket_dt_adjust``) — the campaign finishes with ZERO reactive
    retries and zero failures, where the ungoverned path would NaN and
    burn the per-request retry budget."""
    from rustpde_mpi_tpu.config import StabilityConfig

    # size the spike well past the CFL ceiling: the base flow at this
    # config runs at CFL ~0.035 and the spike partially decays through the
    # step's velocity recomputation, so x500 lands the chunk at CFL ~3.4 —
    # over the 1.0 ceiling with margin, under the NaN horizon
    monkeypatch.setenv("RUSTPDE_SPIKE_FACTOR", "500")
    srv = SimServer(
        _cfg(tmp_path, slots=2, stability=StabilityConfig(ladder_ratio=4.0)),
        fault="spike@6",
    )
    ids = [srv.submit(dict(_REQ, seed=s)).id for s in range(2)]
    summary = srv.serve()
    assert summary["outcome"] == "idle"
    assert summary["completed"] == 2 and summary["failed"] == 0
    assert summary["retried"] == 0  # zero REACTIVE retries: caught pre-NaN
    assert summary["bucket_dt_adjusts"] >= 2  # both pinned members moved
    events = _events(srv.cfg.run_dir)
    names = [e["event"] for e in events]
    assert "bucket_dt_adjust" in names
    assert "request_retry" not in names  # the reactive path never fired
    adjusts = [e for e in events if e["event"] == "bucket_dt_adjust"]
    assert all(e["dt"] < e["prev_dt"] for e in adjusts)
    assert all(e["rung"] < 0 and e["cfl"] > 0 for e in adjusts)
    # the re-bucketed requests CONTINUED (parked state, nonzero base) and
    # completed at the reduced dt with MORE total steps, finite results
    import math

    for rid in ids:
        res = srv.result(rid)
        assert res["dt"] < 0.01 and res["steps"] > 10
        assert res["retries"] == 0
        assert math.isfinite(res["nu"])
    sched = [
        e for e in events
        if e["event"] == "request_scheduled" and e.get("parked")
    ]
    assert len(sched) >= 2 and all(e["base"] > 0 for e in sched)
    from rustpde_mpi_tpu import telemetry

    assert "serve_bucket_dt_rung" in telemetry.snapshot()


def test_serve_governed_stable_dt_bit_identical(tmp_path):
    """At a stable dt the governed campaign must be BIT-identical to the
    ungoverned one: the sentinels only reduce arrays the step already
    materializes, and with no ceiling trip the scheduler takes the exact
    same claim/chunk/settle sequence."""
    from rustpde_mpi_tpu.config import StabilityConfig

    results = {}
    for tag, stab in (("plain", None), ("governed", StabilityConfig())):
        srv = SimServer(
            _cfg(tmp_path, run_dir=str(tmp_path / tag), slots=2, stability=stab)
        )
        ids = [srv.submit(dict(_REQ, seed=s)).id for s in range(3)]
        summary = srv.serve()
        assert summary["completed"] == 3 and summary["failed"] == 0
        results[tag] = [srv.result(r) for r in ids]
    for plain, governed in zip(results["plain"], results["governed"]):
        assert plain["steps"] == governed["steps"]
        assert plain["nu"] == governed["nu"]  # bit-equal, not approx
        assert plain["nuvol"] == governed["nuvol"]
        assert plain["re"] == governed["re"]


def _solo_lnse_energy(result):
    """Solo rerun of one lnse done-record's trajectory through the
    workloads registry — the mixed-campaign isolation ground truth."""
    from rustpde_mpi_tpu.workloads import build_model

    m = build_model("lnse", 17, 17, 1e4, 1.0, result["dt"], 1.0, "rbc", False)
    m.init_random(result.get("amp") or 0.1, seed=result["seed"])
    m.update_n(result["steps"])
    return float(m.get_observables()[0])


def test_serve_mixed_model_campaign(tmp_path):
    """The multi-model serving contract end-to-end: DNS and lnse requests
    through ONE server — the kind-prefixed compat key buckets them into
    separate registry-built campaigns, every request resolves (zero lost),
    results carry each model's own observable vocabulary, and per-request
    isolation holds against solo ground truth for BOTH kinds."""
    srv = SimServer(_cfg(tmp_path, slots=2))
    dns_ids = [srv.submit(dict(_REQ, seed=s)).id for s in range(2)]
    lnse_ids = [
        srv.submit(dict(_REQ, model="lnse", seed=s, amp=1e-3)).id
        for s in range(2)
    ]
    summary = srv.serve()
    assert summary["completed"] == 4 and summary["failed"] == 0
    assert srv.queue.counts() == {"queued": 0, "running": 0, "done": 4, "failed": 0}
    for rid in dns_ids:
        res = srv.result(rid)
        assert res["model"] == "dns" and "nu" in res
        assert res["nu"] == pytest.approx(_solo_nu(res), rel=1e-9)
    for rid in lnse_ids:
        res = srv.result(rid)
        assert res["model"] == "lnse" and "energy" in res and "nu" not in res
        assert res["energy"] == pytest.approx(_solo_lnse_energy(res), rel=1e-9)
    # two separate campaigns ran (one per model-kind bucket)
    events = _events(srv.cfg.run_dir)
    keys = [tuple(e["key"]) for e in events if e["event"] == "campaign_start"]
    assert {k[0] for k in keys} == {"dns", "lnse"}
    # malformed model kinds die at admission, before any compile
    with pytest.raises(RequestError, match="unknown model kind"):
        srv.submit(dict(_REQ, model="nope"))
    with pytest.raises(RequestError, match="DNS axis"):
        srv.submit(dict(_REQ, model="lnse", scenario={"coriolis": 1.0}))
    # bad scenario VALUES die at admission too — compat_key is evaluated
    # after admission, so a bad-typed value admitted here would be a
    # durable poison pill crashing every later serve() pass
    with pytest.raises(RequestError, match="bad scenario values"):
        srv.submit(dict(_REQ, scenario={"coriolis": "fast"}))
    with pytest.raises(RequestError, match="bad scenario values"):
        srv.submit(
            dict(_REQ, scenario={"passive_scalar": True, "scalar_kappa": 0.0})
        )
    assert srv.queue.counts()["queued"] == 0  # nothing poisonous persisted


def test_serve_passive_scalar_surfaces_sherwood(tmp_path):
    """The scalar observable vocabulary rides the serve path end-to-end: a
    passive-scalar request's done record carries ``sherwood`` next to the
    conventional four (streamed through the same observable futures), and
    a plain DNS record does not."""
    srv = SimServer(_cfg(tmp_path, slots=2))
    scal = srv.submit(
        dict(_REQ, seed=0, scenario={"passive_scalar": True})
    ).id
    plain = srv.submit(dict(_REQ, seed=0)).id
    summary = srv.serve()
    assert summary["completed"] == 2 and summary["failed"] == 0
    res = srv.result(scal)
    assert res["steps"] == 10
    import math

    assert math.isfinite(res["sherwood"])
    assert {"nu", "nuvol", "re", "div", "sherwood"} <= set(res)
    assert "sherwood" not in srv.result(plain)


def test_serve_bucket_fairness_no_starvation(tmp_path):
    """The fairness regression (ROADMAP-flagged): two buckets with skewed
    arrivals — 6 hot-bucket requests queued ahead of 2 cold-bucket ones.
    With round-robin bucket selection + the claim quantum, the cold bucket
    is served after one quantum of the hot one instead of waiting for its
    whole backlog: every cold request completes before the hot tail is even
    scheduled."""
    srv = SimServer(_cfg(tmp_path, slots=2, bucket_quantum=2))
    hot = [srv.submit(dict(_REQ, seed=s)).id for s in range(6)]
    cold = [srv.submit(dict(_REQ, dt=0.005, seed=s)).id for s in range(2)]
    summary = srv.serve()
    assert summary["completed"] == 8 and summary["failed"] == 0

    events = _events(srv.cfg.run_dir)
    order = [
        (e["event"], e["id"]) for e in events
        if e["event"] in ("request_scheduled", "request_done")
    ]
    last_cold_done = max(
        i for i, (ev, rid) in enumerate(order)
        if ev == "request_done" and rid in cold
    )
    hot_sched = [
        i for i, (ev, rid) in enumerate(order)
        if ev == "request_scheduled" and rid in hot
    ]
    # the hot tail (claims 5..6) was scheduled only AFTER the cold bucket
    # fully completed — the quantum actually preempted the hot campaign
    assert sum(1 for i in hot_sched if i < last_cold_done) <= 4
    assert sum(1 for i in hot_sched if i > last_cold_done) >= 2
    names = [e["event"] for e in events]
    assert "bucket_quantum" in names  # the cap fired, not a coincidence


def test_public_robustness_api_exports():
    """The README-documented robustness surface must be importable from the
    package root (satellite: pin the API)."""
    import rustpde_mpi_tpu as rp

    for name in (
        "ResilientRunner",
        "CheckpointError",
        "DivergenceError",
        "DispatchHang",
        "RequestFailed",
        "AdmissionError",
        "FaultSpecError",
        "SimServer",
        "SimRequest",
    ):
        assert hasattr(rp, name), name
    # the typed failure surface subclasses what callers already catch
    assert issubclass(rp.FaultSpecError, ValueError)
    assert issubclass(rp.RequestFailed, RuntimeError)
    assert issubclass(rp.CheckpointError, RuntimeError)


# -- the chaos soak (slow tier) ----------------------------------------------


def _summary_of(stdout):
    """The summary JSON line (restore prints and per-request lines ride the
    same stdout)."""
    for line in stdout.splitlines():
        if line.startswith('{"outcome"'):
            return json.loads(line)
    raise AssertionError(f"no summary line in: {stdout[-2000:]}")


def _run_soak_phase(run_dir, extra, timeout=900):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        RUSTPDE_X64="1",
    )
    env.pop("RUSTPDE_FAULT", None)
    return subprocess.run(
        [
            sys.executable,
            os.path.join(_REPO, "examples", "navier_rbc_serve.py"),
            "--nx", "17", "--ny", "17", "--ra", "1e4", "--dt", "0.01",
            "--horizon", "0.06", "--horizon-jitter", "8",
            "--slots", "8",
            "--max-queue", "512",
            "--run-dir", run_dir,
            "--ckpt-every-s", "5",
            *extra,
        ],
        cwd=_REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


@pytest.mark.slow
def test_serve_chaos_soak(tmp_path):
    """The soak gate: >=200 queued requests complete through 8 ensemble
    slots while the service is SIGTERM-drained mid-soak (kill@ fault),
    hard-killed (SIGKILL via the host-scoped kill fault), and NaN-poisoned
    (nan@ fault) across three process incarnations — zero requests lost or
    terminally failed, and a sample of results matches solo runs within
    the respawn-equivalence tolerance."""
    run_dir = str(tmp_path / "soak")
    n_req = int(os.environ.get("RUSTPDE_SERVE_SOAK_REQUESTS", "200"))

    # the workload is ~(n_req * ~9.5 steps) / 8 slots ≈ 1.2*n_req global
    # chunk steps.  Each later phase RESTORES the previous phase's
    # checkpoint, so its step counter resumes near the previous fault
    # point — the fault steps are spaced so every phase deterministically
    # reaches its trigger with the remaining workload to spare
    drain_at = max(16, n_req // 4)
    kill_at = drain_at + max(16, n_req // 4)
    nan_at = kill_at + max(16, n_req // 4)
    # phase 1: enqueue everything, serve until the kill@ SIGTERM drains
    p1 = _run_soak_phase(
        run_dir, ["--requests", str(n_req), "--fault", f"kill@{drain_at}"]
    )
    assert p1.returncode == 0, p1.stderr[-3000:]
    assert _summary_of(p1.stdout)["outcome"] == "drained"

    # phase 2: resume, then die HARD (host-scoped kill = SIGKILL, no drain)
    p2 = _run_soak_phase(run_dir, ["--fault", f"kill@{kill_at}:host0"])
    assert p2.returncode != 0  # SIGKILL: no clean exit, no summary
    assert "outcome" not in p2.stdout

    # phase 3: clean restart + NaN chaos mid-soak; drains everything
    p3 = _run_soak_phase(run_dir, ["--fault", f"nan@{nan_at}"], timeout=1800)
    assert p3.returncode == 0, p3.stderr[-3000:]
    assert _summary_of(p3.stdout)["outcome"] == "idle"

    # zero lost: every admitted request is terminally resolved, none failed
    q = DurableQueue(os.path.join(run_dir, "queue"), max_queue=512)
    counts = q.counts()
    assert counts == {"queued": 0, "running": 0, "done": n_req, "failed": 0}

    events = read_journal(os.path.join(run_dir, "journal.jsonl"))
    names = [e["event"] for e in events]
    assert "drain" in names  # phase-1 SIGTERM drain
    assert "request_requeued" in names  # in-flight work preserved at drain
    # later incarnations restored drained/killed slots MID-TRAJECTORY from
    # the sharded slot-table checkpoint (not from scratch)
    restored = [
        e for e in events
        if e.get("event") == "request_scheduled" and e.get("restored")
    ]
    assert restored and any(e.get("steps_done", 0) > 0 for e in restored)
    # phase 3 detected phase 2's SIGKILL as an unclean shutdown and
    # recovered its running requests
    starts = [e for e in events if e.get("event") == "server_start"]
    assert starts[-1]["unclean_shutdown"] is True
    assert any(e.get("recovered") for e in starts)
    assert "request_retry" in names  # the NaN chaos actually fired

    # isolation spot-check: sample done records against solo ground truth
    done_dir = os.path.join(run_dir, "queue", "done")
    sample = sorted(os.listdir(done_dir))[:: max(1, n_req // 5)][:5]
    for name in sample:
        with open(os.path.join(done_dir, name)) as fh:
            res = json.load(fh)["result"]
        assert res["nu"] == pytest.approx(_solo_nu(res), rel=1e-9)


# -- request tracing end-to-end (ISSUE 13 tentpole) ----------------------------


def test_trace_context_survives_drain_restart_and_rebucket(tmp_path, monkeypatch):
    """The acceptance gate: admission -> SIGTERM drain -> restart ->
    re-claim -> proactive re-bucket at a lower dt rung -> done yields ONE
    trace_id across both incarnations' journal rows, and the assembled
    Perfetto timeline (the /requests/<id>/trace payload) reconstructs the
    whole lifecycle on one ordered timeline."""
    from rustpde_mpi_tpu.config import StabilityConfig

    monkeypatch.setenv("RUSTPDE_SPIKE_FACTOR", "500")
    mk = lambda fault: SimServer(
        _cfg(tmp_path, slots=2, stability=StabilityConfig(ladder_ratio=4.0)),
        fault=fault,
    )
    # incarnation 1: admitted, scheduled, SIGTERM-drained mid-campaign
    srv = mk("kill@8")
    req = srv.submit(dict(_REQ, seed=0, horizon=0.2))
    rid, tid = req.id, req.trace_id
    assert tid and len(tid) == 16
    assert srv.serve()["outcome"] == "drained"
    # incarnation 2: re-claims mid-trajectory, a velocity spike trips the
    # CFL sentinel -> bucket_dt_adjust re-buckets at dt/4, completes
    srv2 = mk("spike@14")
    s2 = srv2.serve()
    assert s2["outcome"] == "idle"
    assert s2["completed"] == 1 and s2["failed"] == 0
    assert s2["bucket_dt_adjusts"] >= 1

    events = _events(str(tmp_path / "serve"))
    mine = [e for e in events if e.get("id") == rid]
    names = [e["event"] for e in mine]
    for expected in (
        "request_admitted",
        "request_scheduled",
        "request_requeued",  # the drain
        "bucket_dt_adjust",  # the re-bucket
        "request_done",
    ):
        assert expected in names, (expected, names)
    # ONE trace id across every lifecycle row of both incarnations
    tids = {e["trace_id"] for e in mine if e.get("trace_id")}
    assert tids == {tid}
    # the restart re-claimed the drained slot mid-trajectory
    assert any(
        e.get("restored") for e in mine if e["event"] == "request_scheduled"
    )
    # every row carries the absolute stamp assembly orders by
    assert all(isinstance(e.get("t"), float) for e in mine)

    # the assembled timeline: one trace, both incarnations, ordered
    trace = srv2.request_trace(rid)
    assert trace is not None
    other = trace["otherData"]
    assert other["trace_id"] == tid and other["request_id"] == rid
    assert other["incarnations"] == 2
    tnames = [e["name"] for e in trace["traceEvents"]]
    assert "request_admitted" in tnames and "request_done" in tnames
    assert "bucket_dt_adjust" in tnames
    assert tnames.count("chunk") >= 2  # device work in BOTH incarnations
    assert "queued" in tnames and "running" in tnames  # derived phases
    assert all(
        e["args"]["trace_id"] == tid for e in trace["traceEvents"]
    )
    ts = [e["ts"] for e in trace["traceEvents"]]
    assert ts == sorted(ts) and ts[0] == 0.0
    # the per-campaign Perfetto files the assembly read actually landed
    # (root-side write at campaign close/drain) — across TWO buckets (the
    # original dt and the re-bucketed rung)
    import glob

    tfiles = glob.glob(
        os.path.join(str(tmp_path / "serve"), "campaigns", "*", "trace_*.json")
    )
    assert len(tfiles) >= 2
    assert any(e["event"] == "campaign_trace" for e in events)
    # flight dumps of the drain are sequenced and attributable
    frs = [e for e in events if e.get("event") == "flight_record"]
    assert frs and all("seq" in e for e in frs)


def test_http_trace_and_profile_endpoints(tmp_path, monkeypatch):
    """GET /requests/<id>/trace serves the assembled timeline, POST
    /profile drives the bounded single-flight profiler capture, and the
    202 admission ack carries the trace id clients correlate on."""
    from rustpde_mpi_tpu.serve.http_front import HttpFront
    from rustpde_mpi_tpu.telemetry import compile_log

    srv = SimServer(_cfg(tmp_path, slots=2))
    req = srv.submit(dict(_REQ, seed=0))
    assert srv.serve()["completed"] == 1
    # keep the profiler itself out of the test: injected no-op trace fns
    monkeypatch.setattr(
        compile_log,
        "CAPTURE",
        compile_log.ProfilerCapture(
            start_fn=lambda d: None, stop_fn=lambda: None
        ),
    )
    front = HttpFront(srv)
    front.start()
    try:
        host, port = front.address
        base = f"http://{host}:{port}"

        def get(path):
            try:
                with urllib.request.urlopen(base + path, timeout=30) as resp:
                    return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as err:
                return err.code, json.loads(err.read())

        def post(path, payload=None):
            data = json.dumps(payload or {}).encode()
            r = urllib.request.Request(base + path, data=data, method="POST")
            try:
                with urllib.request.urlopen(r, timeout=30) as resp:
                    return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as err:
                return err.code, json.loads(err.read())

        code, trace = get(f"/requests/{req.id}/trace")
        assert code == 200
        assert trace["otherData"]["trace_id"] == req.trace_id
        names = [e["name"] for e in trace["traceEvents"]]
        assert "request_admitted" in names and "chunk" in names
        assert get("/requests/unknown-id/trace")[0] == 404
        # profile endpoint: bad args typed, good args 202, concurrent 409
        assert post("/profile?seconds=nope")[0] == 400
        assert post("/profile?seconds=-1")[0] == 400
        code, status = post("/profile?seconds=2")
        assert code == 202 and status["started"] is True
        code, refusal = post("/profile?seconds=1")
        assert code == 409 and "already running" in refusal["error"]
        # the admission ack names the trace id
        code, ack = post("/requests", dict(_REQ, seed=5))
        assert code == 202 and len(ack["trace_id"]) == 16
    finally:
        front.stop()
    # the capture was journaled (observability events ride the journal too)
    events = [e["event"] for e in _events(srv.cfg.run_dir)]
    assert "profile_capture" in events


def test_compile_attribution_rides_serve_journal(tmp_path):
    """Every campaign build journals phase-stamped compile_build rows
    (key-tagged, wall time, recompile flag): one "build" row for the
    registry's model construction plus one "entry_points" row for the
    campaign-level remainder — summing to the bucket's true cold cost —
    and the first committed chunk a first_chunk row — the cold-start
    item's baseline numbers, durably recorded."""
    srv = SimServer(_cfg(tmp_path, slots=2))
    # unique ra => compat keys no other test in this process has built,
    # so the recompile=False assertion holds under any suite ordering
    # (the build counter is process-global by design)
    srv.submit(dict(_REQ, ra=1.2e4, seed=0))
    srv.submit(dict(_REQ, ra=1.2e4, dt=0.005, seed=1))  # second bucket
    assert srv.serve()["completed"] == 2
    events = _events(srv.cfg.run_dir)
    builds = [e for e in events if e["event"] == "compile_build"]
    assert len(builds) == 4
    assert all(len(e["key_tag"]) == 12 for e in builds)
    by_phase = {"build": [], "entry_points": []}
    for e in builds:
        by_phase[e["phase"]].append(e)
    assert len(by_phase["build"]) == 2 and len(by_phase["entry_points"]) == 2
    assert all(e["wall_s"] > 0 for e in by_phase["build"])
    assert all(e["wall_s"] >= 0 for e in by_phase["entry_points"])
    # no phase recompiles on first builds, and the rows carry the campaign k
    assert all(e["recompile"] is False and e["k"] == 2 for e in builds)
    firsts = [e for e in events if e["event"] == "first_chunk"]
    assert len(firsts) == 2
    assert all(e["wall_s"] > 0 for e in firsts)
    # the done records carry the HA gate metric
    done = [e for e in events if e["event"] == "request_done"]
    assert all(e["first_observable_s"] > 0 for e in done)


def test_build_runner_traces_the_step_only_for_its_entry_points(tmp_path, monkeypatch):
    """Building a campaign traces the member step as often as compiling its
    entry points does (the K-member ensemble's vmapped chunk) and not once
    more: no second pass over the step for a flop count, on every bucket
    visit of the service loop."""
    from rustpde_mpi_tpu.serve import scheduler
    from rustpde_mpi_tpu.workloads.registry import build_model_for_key

    calls = []
    make_step = Navier2D._make_step

    def counted(self, *args, **kwargs):
        calls.append(1)
        return make_step(self, *args, **kwargs)

    monkeypatch.setattr(Navier2D, "_make_step", counted)
    key = SimRequest(**_REQ).compat_key
    model = build_model_for_key(key, mesh=None)
    scheduler._ServedEnsemble(model, [model.state] * 2)
    entry_points = len(calls)
    assert entry_points >= 1
    del calls[:]
    srv = SimServer(_cfg(tmp_path, slots=2))
    srv._build_runner(key, k=2)
    assert len(calls) == entry_points


def test_served_run_reports_member_rate_and_no_utilisation_gauge(tmp_path):
    """The rate an operator can act on is set at the chunk boundaries; no
    series prices it against a peak (the yardstick is the benchmark's, one
    flop counted once, and lives with it)."""
    from rustpde_mpi_tpu.telemetry import metrics as tm

    srv = SimServer(_cfg(tmp_path, slots=2))
    for s in range(3):
        srv.submit(dict(_REQ, seed=s))
    assert srv.serve()["completed"] == 3
    snap = tm.REGISTRY.snapshot()
    rate = snap["serve_member_steps_per_sec"]["series"]
    assert rate and rate[0]["value"] > 0
    assert not [name for name in snap if name.endswith("_mfu")]
