"""Telemetry subsystem tests (rustpde_mpi_tpu/telemetry/): the metrics
registry (counters/gauges/log-bucket histograms, snapshot/delta/merge), the
Prometheus text exposition, flight-recorder tracing + incident dumps, the
ThroughputMonitor SLO loop, and the hard contract — instrumented runs are
BIT-identical to telemetry-off runs.

Runner/serve integration reuses the 17^2 shapes every other harness test
compiles; the live mid-soak ``/metrics`` scrape rides test_serve.py's HTTP
tests (same daemon-server machinery)."""

import json
import math
import os

import numpy as np
import pytest

import jax

from rustpde_mpi_tpu import (
    DivergenceError,
    Navier2D,
    ResilientRunner,
    telemetry,
)
from rustpde_mpi_tpu.telemetry import (
    FlightRecorder,
    MetricsDumper,
    MetricsRegistry,
    ThroughputMonitor,
    prometheus_text,
)
from rustpde_mpi_tpu.telemetry import metrics as tmetrics
from rustpde_mpi_tpu.telemetry import tracing as ttracing

h5py = pytest.importorskip("h5py")


def _model(seed=0):
    m = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
    m.init_random(0.1, seed=seed)
    return m


# -- registry ------------------------------------------------------------------


def test_counters_gauges_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "help text", result="done")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    # same (name, labels) -> same handle; different labels -> distinct series
    assert reg.counter("requests_total", result="done") is c
    other = reg.counter("requests_total", result="failed")
    assert other is not c and other.value == 0.0
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(7)
    g.inc(3)
    g.dec(1)
    assert g.value == 9
    # a name cannot change kind
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("requests_total")


def test_histogram_log_buckets_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("latency_seconds")
    values = [0.001, 0.01, 0.05, 0.1, 0.1, 0.2, 1.0, 5.0, 0.0]
    for v in values:
        h.observe(v)
    assert h.count == len(values)
    assert h.sum == pytest.approx(sum(values))
    assert h.min == 0.0 and h.max == 5.0
    # log-bucketed: the quantile is bucket-accurate (ratio ~1.26), NOT exact
    assert h.quantile(0.5) == pytest.approx(0.1, rel=0.3)
    assert h.quantile(0.99) == pytest.approx(5.0, rel=0.3)
    assert h.quantile(0.0) == 0.0  # the zero bucket
    # cumulative buckets are monotone and end at the total count
    buckets = h.buckets()
    counts = [n for _, n in buckets]
    assert counts == sorted(counts) and counts[-1] == h.count
    edges = [le for le, _ in buckets]
    assert edges == sorted(edges)
    # no sample retention: storage is bucket counts, not the observations
    d = h.to_dict()
    assert d["count"] == len(values) and "p99" in d
    assert len(d["counts"]) < len(values)
    # a non-finite observation is COUNTED but must not poison sum/min/max
    # (a single NaN would otherwise NaN every rate()/avg query forever)
    h.observe(float("nan"))
    h.observe(float("inf"))
    assert h.count == len(values) + 2
    assert math.isfinite(h.sum) and h.max == 5.0
    assert math.isfinite(h.quantile(0.9))


def test_snapshot_delta_and_multihost_merge():
    reg = MetricsRegistry()
    reg.counter("steps_total").inc(10)
    reg.gauge("dt").set(0.01)
    reg.histogram("write_seconds").observe(0.5)
    snap = reg.snapshot()
    assert snap["steps_total"]["kind"] == "counter"
    json.dumps(snap)  # plain-JSON contract
    reg.counter("steps_total").inc(5)
    reg.histogram("write_seconds").observe(0.5)
    delta = reg.delta(snap)
    assert delta["steps_total"]["series"][0]["value"] == 5.0
    assert delta["write_seconds"]["series"][0]["count"] == 1
    # merge: counters/histograms sum, gauges keep per-host labeled values
    merged = tmetrics.merge_snapshots([reg.snapshot(), snap])
    assert merged["steps_total"]["series"][0]["value"] == 25.0
    assert merged["write_seconds"]["series"][0]["count"] == 3
    hosts = {s["labels"].get("host") for s in merged["dt"]["series"]}
    assert hosts == {"0", "1"}
    # single process: the gathered view IS the local snapshot
    assert tmetrics.gather_global_snapshot(reg) == reg.snapshot()


def test_prometheus_exposition_format():
    from test_serve import _parse_prometheus

    reg = MetricsRegistry()
    reg.counter("a_total", "things", kind="x\"y\\z").inc(2)
    reg.gauge("b").set(1.5)
    h = reg.histogram("c_seconds", "hist help")
    for v in (0.1, 0.2, 3.0):
        h.observe(v)
    text = prometheus_text(reg)
    samples = _parse_prometheus(text)  # asserts every line parses
    assert samples["b"][""] == (1.5,)
    assert "# TYPE c_seconds histogram" in text
    assert "# HELP c_seconds hist help" in text
    # cumulative le series with +Inf == _count
    inf = [k for k in samples["c_seconds_bucket"] if '+Inf' in k]
    assert inf and samples["c_seconds_bucket"][inf[0]] == (3.0,)
    assert samples["c_seconds_count"][""] == (3.0,)
    assert samples["c_seconds_sum"][""][0] == pytest.approx(3.3)
    # label escaping survives the round trip
    assert '\\"' in text and "\\\\" in text


def test_disabled_registry_is_noop_and_cheap():
    prev = tmetrics.enabled()
    try:
        telemetry.set_enabled(False)
        c = telemetry.counter("nope_total")
        c.inc(100)
        assert c.value == 0.0
        # the shared null span: no allocation per call
        assert ttracing.span("a") is ttracing.span("b")
        assert telemetry.dump_flight_record("/nonexistent", "x") is None
    finally:
        telemetry.set_enabled(prev)


# -- tracing -------------------------------------------------------------------


def test_flight_recorder_spans_ring_and_dump(tmp_path):
    rec = FlightRecorder(capacity=32)
    t0 = rec.now_us()
    rec.add_complete("dispatch", t0, 125.0, {"steps": 4})
    rec.add_instant("fault", {"kind": "nan"})
    for i in range(100):  # the ring stays bounded
        rec.add_complete("spam", rec.now_us(), 1.0)
    events = rec.events()
    assert len(events) == 32
    path = rec.dump(str(tmp_path / "flight.json"), reason="test")
    data = json.load(open(path))
    assert isinstance(data["traceEvents"], list) and data["traceEvents"]
    ev = data["traceEvents"][-1]
    # the Perfetto/Chrome trace-event contract
    assert ev["ph"] == "X" and {"name", "ts", "dur", "pid", "tid"} <= set(ev)
    assert data["otherData"]["reason"] == "test"
    assert rec.dumped == 1


def test_span_records_and_annotates_errors():
    with telemetry.span("outer", step=3) as outer:
        pass
    with pytest.raises(RuntimeError):
        with telemetry.span("failing") as failing:
            raise RuntimeError("boom")
    # the ring is the process's, shared with every other span and bounded (a
    # full one keeps its length): the two are found by their names and ids
    mine = {("outer", outer.id), ("failing", failing.id)}
    named = {e["name"]: e for e in ttracing.RECORDER.events()
             if (e["name"], e["args"].get("id")) in mine}
    assert set(named) == {"outer", "failing"}
    # the call site's args, beside the span's identity (id, parent)
    assert named["outer"]["args"]["step"] == 3
    assert named["outer"]["args"]["parent"] is None
    assert named["failing"]["args"]["error"] == "RuntimeError"
    assert named["failing"]["args"]["id"] > named["outer"]["args"]["id"]


def test_metrics_dumper_cadence_and_reader(tmp_path):
    reg = MetricsRegistry()
    reg.counter("x_total").inc(3)
    path = str(tmp_path / "metrics.jsonl")
    d = MetricsDumper(path, every_s=1e9, registry=reg)
    assert d.maybe_dump() is False  # first call only arms the clock
    assert d.maybe_dump() is False  # cadence not elapsed
    assert d.dump(step=7) is True  # force
    reg.counter("x_total").inc(2)
    assert d.dump(step=9) is True
    records = telemetry.read_metrics_jsonl(path)
    assert len(records) == 2
    assert records[0]["step"] == 7
    assert records[1]["delta"]["x_total"]["series"][0]["value"] == 2.0
    # torn tail tolerated
    with open(path, "a") as fh:
        fh.write('{"torn')
    assert len(telemetry.read_metrics_jsonl(path)) == 2


# -- the SLO monitor -----------------------------------------------------------


def test_throughput_monitor_detects_regression():
    clock = iter([0.0, 1.0, 2.0, 3.0, 4.0, 14.0, 15.0]).__next__
    mon = ThroughputMonitor(
        window=4, warmup=2, tolerance=0.5, min_interval_s=0.0, clock=clock
    )
    verdicts = [mon.record(100) for _ in range(6)]
    assert all(v is None for v in verdicts[:5])
    hit = verdicts[5]  # elapsed 10s instead of 1s -> 10x regression
    assert hit is not None
    assert hit["ratio"] == pytest.approx(0.1)
    assert hit["baseline_steps_per_sec"] == pytest.approx(100.0)
    assert mon.events == 1
    # recovery at the old rate reports nothing further
    assert mon.record(100) is None


def test_throughput_monitor_rate_limited():
    # a SUSTAINED regression journals a heartbeat, not a line per chunk
    ticks = iter([0, 1, 2, 3, 4, 14, 24, 34]).__next__
    mon = ThroughputMonitor(
        window=8, warmup=2, tolerance=0.5, min_interval_s=100.0, clock=ticks
    )
    verdicts = [mon.record(10) for _ in range(8)]
    assert sum(1 for v in verdicts if v) == 1


# -- runner integration --------------------------------------------------------


@pytest.mark.parametrize("traced_request", [False, True])
def test_instrumented_run_bit_identical_to_telemetry_off(tmp_path, traced_request):
    """THE hard constraint, CI-asserted: telemetry must never touch traced
    programs — the full runner path with metrics+tracing ON produces a
    final state byte-identical to the same run with telemetry OFF; with a
    request's trace id bound to a slot too, so that the span annotator of
    the request-tracing layer is on the path as it is under the server."""
    from rustpde_mpi_tpu.telemetry import reqtrace

    states = {}
    seams = {}
    prev = tmetrics.enabled()
    try:
        for key, on in (("on", True), ("off", False)):
            telemetry.set_enabled(on)
            reqtrace.bind_slots({0: "trace0000"} if on and traced_request else {})
            ttracing.RECORDER.clear()
            m = _model(seed=3)
            runner = ResilientRunner(
                m,
                max_time=0.12,
                run_dir=str(tmp_path / key),
                checkpoint_every_s=None,
                max_chunk_steps=4,
            )
            summary = runner.run()
            assert summary["outcome"] == "done"
            states[key] = jax.device_get(m.state)
            seams[key] = {
                n: ttracing.spans(n)
                for n in ("dispatch", "model.update_n", "model.carry_copy", "model.launch")
            }
    finally:
        reqtrace.clear_active()
        telemetry.set_enabled(prev)
    if traced_request:  # the annotator was on the path, not beside it
        assert all(
            s[4].get("trace_ids") == ["trace0000"] for s in seams["on"]["dispatch"]
        )
    # the model-step seams ran on this path: recorded under the runner's
    # dispatch span when ON, nothing at all when OFF
    dispatched = {s[2] for s in seams["on"]["dispatch"]}
    assert dispatched and {s[3] for s in seams["on"]["model.update_n"]} == dispatched
    assert seams["on"]["model.carry_copy"] and seams["on"]["model.launch"]
    assert not any(seams["off"].values())
    for a, b in zip(states["on"], states["off"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the ON run left live telemetry behind; the OFF run left none
    assert os.path.exists(tmp_path / "on" / "metrics.jsonl")
    assert not os.path.exists(tmp_path / "off" / "metrics.jsonl")
    recs = telemetry.read_metrics_jsonl(str(tmp_path / "on" / "metrics.jsonl"))
    steps = recs[-1]["snapshot"]["runner_steps_total"]["series"][0]["value"]
    assert steps >= 12  # this run's steps rode the shared counter


def test_flight_record_dumped_on_divergence(tmp_path):
    m = _model(seed=1)
    runner = ResilientRunner(
        m,
        max_time=0.5,
        run_dir=str(tmp_path / "run"),
        checkpoint_every_s=None,
        max_retries=0,
        fault="nan@4",
        max_chunk_steps=4,
    )
    with pytest.raises(DivergenceError):
        runner.run()
    dumps = [f for f in os.listdir(tmp_path / "run") if f.startswith("flight_")]
    assert dumps, "no flight record dumped on DivergenceError"
    data = json.load(open(tmp_path / "run" / dumps[0]))
    names = {e["name"] for e in data["traceEvents"]}
    assert "dispatch" in names and "fault_injected" in names
    # the journal points at the incident file
    from rustpde_mpi_tpu.utils.journal import read_journal

    events = read_journal(str(tmp_path / "run" / "journal.jsonl"))
    fr = [e for e in events if e.get("event") == "flight_record"]
    assert fr and fr[0]["reason"] == "DivergenceError"


def test_flight_record_dumped_on_sigterm_preempt(tmp_path):
    m = _model(seed=2)
    runner = ResilientRunner(
        m,
        max_time=1.0,
        run_dir=str(tmp_path / "run"),
        checkpoint_every_s=None,
        fault="kill@6",  # a real SIGTERM to our own pid, mid-run
        max_chunk_steps=4,
    )
    summary = runner.run()
    assert summary["outcome"] == "preempted"
    dumps = [f for f in os.listdir(tmp_path / "run") if f.startswith("flight_preempt")]
    assert dumps, "no flight record dumped on the SIGTERM drain"


def test_perf_degraded_journaled_by_runner(tmp_path):
    """The SLO loop end-to-end: a fake-clock monitor sees the boundary rate
    collapse and the runner journals the typed perf_degraded event."""
    m = _model(seed=4)
    runner = ResilientRunner(
        m,
        max_time=0.1,
        save_intervall=0.01,  # one SLO sample per boundary
        run_dir=str(tmp_path / "run"),
        checkpoint_every_s=None,
    )
    seq = iter([0.0, 1.0, 2.0, 3.0, 103.0, 104.0, 105.0, 106.0, 107.0, 108.0])

    def clock():
        try:
            return next(seq)
        except StopIteration:
            return 1000.0

    runner.slo = ThroughputMonitor(
        window=4, warmup=2, tolerance=0.5, min_interval_s=0.0, clock=clock
    )
    summary = runner.run()
    assert summary["outcome"] == "done"
    from rustpde_mpi_tpu.utils.journal import read_journal

    events = read_journal(str(tmp_path / "run" / "journal.jsonl"))
    degraded = [e for e in events if e.get("event") == "perf_degraded"]
    assert degraded, [e.get("event") for e in events]
    assert degraded[0]["ratio"] < 0.5
    assert math.isfinite(degraded[0]["steps_per_sec"])


def test_flight_record_dumped_on_dispatch_hang(tmp_path):
    from rustpde_mpi_tpu import DispatchHang

    m = _model(seed=5)
    runner = ResilientRunner(
        m,
        max_time=0.5,
        run_dir=str(tmp_path / "run"),
        checkpoint_every_s=None,
        fault="slow@4",
        dispatch_timeout_s=0.3,
        max_chunk_steps=4,
    )
    with pytest.raises(DispatchHang):
        runner.run()
    dumps = [
        f for f in os.listdir(tmp_path / "run") if f.startswith("flight_dispatch_hang")
    ]
    assert dumps, "no flight record dumped on DispatchHang"


# -- request tracing (telemetry/reqtrace.py) -----------------------------------


def test_reqtrace_mint_and_log_roundtrip(tmp_path):
    from rustpde_mpi_tpu.telemetry import reqtrace

    ctx = reqtrace.mint()
    assert len(ctx["trace_id"]) == 16 and len(ctx["span"]) == 8
    assert reqtrace.mint()["trace_id"] != ctx["trace_id"]

    log = reqtrace.RequestTraceLog(capacity=64)
    log.record(ctx["trace_id"], "chunk", 100.0, 0.5, {"steps": 4})
    log.record(ctx["trace_id"], "marker", 101.0)
    ev = log.events()
    assert ev[0]["ph"] == "X" and ev[0]["dur"] == 0.5e6
    assert ev[0]["args"] == {"trace_id": ctx["trace_id"], "steps": 4}
    assert ev[1]["ph"] == "i"
    # bounded: past capacity events are counted dropped, not grown
    small = reqtrace.RequestTraceLog(capacity=64)
    for i in range(200):
        small.record("t", "spam", float(i))
    assert len(small.events()) == 64 and small.dropped == 136
    # drain empties
    assert len(log.drain()) == 2 and log.events() == []


def test_reqtrace_binding_annotates_spans_and_flight_dumps(tmp_path):
    from rustpde_mpi_tpu.telemetry import reqtrace
    from rustpde_mpi_tpu.telemetry import tracing as ttr

    try:
        reqtrace.bind_slots({0: "aaaa", 1: "bbbb", 2: "aaaa"})
        assert reqtrace.active_ids() == ["aaaa", "bbbb"]
        with telemetry.span("bound_span", step=1):
            pass
        ev = ttr.RECORDER.events()[-1]
        assert ev["args"]["trace_ids"] == ["aaaa", "bbbb"]
        assert ev["args"]["step"] == 1
        # sequenced, attributed flight dumps: monotonic _nNNNN filenames,
        # seq + trace_ids in the payload (the chaos-soak pile stays sorted
        # and attributable)
        p1 = telemetry.dump_flight_record(str(tmp_path), "probe")
        p2 = telemetry.dump_flight_record(str(tmp_path), "probe")
        assert p1 != p2 and os.path.exists(p1) and os.path.exists(p2)
        d1 = json.load(open(p1))
        d2 = json.load(open(p2))
        assert d2["otherData"]["seq"] == d1["otherData"]["seq"] + 1
        assert d1["otherData"]["trace_ids"] == ["aaaa", "bbbb"]
        assert f"_n{d1['otherData']['seq']:04d}" in os.path.basename(p1)
    finally:
        reqtrace.clear_active()
    # cleared: spans go back to unannotated
    with telemetry.span("unbound_span"):
        pass
    assert "trace_ids" not in (ttr.RECORDER.events()[-1].get("args") or {})


def test_reqtrace_campaign_write_and_assembly(tmp_path):
    """Single-process end-to-end of the durable pieces: chunk events land
    in a per-campaign Perfetto file; assembly reconstructs one timeline
    from journal rows + the campaign file, keyed by the trace_id."""
    from rustpde_mpi_tpu.telemetry import reqtrace
    from rustpde_mpi_tpu.utils.journal import JournalWriter

    run_dir = str(tmp_path / "serve")
    cdir = os.path.join(run_dir, "campaigns", "deadbeef0000")
    os.makedirs(cdir)
    tid = "feedfacefeedface"
    reqtrace.chunk_span(tid, 1000.0, 0.25, slot=0, steps=4)
    path = reqtrace.write_campaign_trace(cdir, "deadbeef0000")
    assert path and os.path.basename(path) == "trace_0000.json"
    # a second campaign close APPENDS a new file (incarnations never clobber)
    reqtrace.chunk_span(tid, 1001.0, 0.25, slot=0, steps=4)
    path2 = reqtrace.write_campaign_trace(cdir, "deadbeef0000")
    assert os.path.basename(path2) == "trace_0001.json"
    # no events -> no file, no error
    assert reqtrace.write_campaign_trace(cdir, "deadbeef0000") is None

    w = JournalWriter(os.path.join(run_dir, "journal.jsonl"))
    w.append({"event": "server_start"})
    w.append({"event": "request_admitted", "id": "r1", "trace_id": tid})
    w.append({"event": "request_scheduled", "id": "r1", "trace_id": tid})
    w.append({"event": "request_done", "id": "r1", "trace_id": tid})
    w.close()
    trace = reqtrace.assemble_request_trace(run_dir, "r1")
    assert trace["otherData"]["trace_id"] == tid
    assert trace["otherData"]["incarnations"] == 1
    names = [e["name"] for e in trace["traceEvents"]]
    assert "request_admitted" in names and "chunk" in names
    assert "queued" in names and "running" in names  # derived phases
    assert all(e["args"]["trace_id"] == tid for e in trace["traceEvents"])
    ts = [e["ts"] for e in trace["traceEvents"]]
    assert ts == sorted(ts) and ts[0] == 0.0
    # unknown request: None, not an empty payload
    assert reqtrace.assemble_request_trace(run_dir, "nope") is None


def test_journal_rows_carry_absolute_time(tmp_path):
    from rustpde_mpi_tpu.utils.journal import JournalWriter, read_journal
    import time as _t

    path = str(tmp_path / "j.jsonl")
    w = JournalWriter(path)
    before = _t.time()
    w.append({"event": "a"})
    w.append({"event": "b", "t": 123.0})  # caller-provided stamps win
    w.close()
    rows = read_journal(path)
    assert before - 1 <= rows[0]["t"] <= _t.time() + 1
    assert rows[1]["t"] == 123.0


# -- compile/device attribution (telemetry/compile_log.py) ---------------------


def test_compile_log_build_attribution_and_recompile_count():
    from rustpde_mpi_tpu.telemetry import compile_log

    key = ("dns", 17, 17, 1e4, 1.0, 0.123456, 1.0, "rbc", False, ())
    tag = compile_log.key_tag(key)
    assert len(tag) == 12
    first = compile_log.observe_build(key, 0.5, kind="dns")
    assert first["recompile"] is False and first["builds"] >= 1
    assert first["phase"] == "build"
    again = compile_log.observe_build(key, 0.25, kind="dns")
    assert again["recompile"] is True and again["builds"] == first["builds"] + 1
    snap = telemetry.snapshot()
    series = {
        tuple(sorted(s["labels"].items())): s
        for s in snap["compile_build_seconds"]["series"]
    }
    assert (("key", tag), ("phase", "build")) in series
    assert series[(("key", tag), ("phase", "build"))]["count"] >= 2
    # a non-build phase rides its own series and does NOT bump the per-key
    # build count (TTFC attribution sums across phases instead of ~2x)
    entry = compile_log.observe_build(key, 0.1, kind="dns", phase="entry_points")
    assert entry["phase"] == "entry_points" and entry["recompile"] is False
    assert compile_log.build_counts()[tag] == again["builds"]
    assert compile_log.last_build_wall(key) == 0.25
    recomp = {
        s["labels"]["key"]: s["value"]
        for s in snap["compile_recompiles_total"]["series"]
    }
    assert recomp[tag] >= 1
    assert compile_log.build_counts()[tag] >= 2
    # time-to-first-chunk rides the same label
    compile_log.observe_first_chunk(key, 1.5)
    ttfc = telemetry.snapshot()["serve_time_to_first_chunk_seconds"]
    assert any(s["labels"]["key"] == tag for s in ttfc["series"])


def test_device_memory_gauges_none_safe():
    """CPU backends report no memory stats: the helper returns the
    None-marked dict and the gauge update counts zero devices instead of
    inventing zeros."""
    from rustpde_mpi_tpu.telemetry import compile_log
    from rustpde_mpi_tpu.utils.profiling import device_memory_stats

    stats = device_memory_stats()
    assert stats  # at least one local device
    reported = compile_log.update_device_memory_gauges()
    with_stats = sum(1 for v in stats.values() if v)
    assert reported == with_stats


def test_profiler_capture_single_flight_and_bounds(tmp_path):
    from rustpde_mpi_tpu.telemetry.compile_log import ProfilerCapture

    started, stopped = [], []
    cap = ProfilerCapture(
        start_fn=lambda d: started.append(d), stop_fn=lambda: stopped.append(1)
    )
    assert cap.start(str(tmp_path), "nope")["started"] is False
    assert cap.start(str(tmp_path), -1)["started"] is False
    status = cap.start(str(tmp_path / "p"), 0.4, reason="test")
    assert status["started"] is True and status["seconds"] == 0.4
    # single-flight: a second start while the window runs is refused
    refused = cap.start(str(tmp_path / "p2"), 0.1)
    assert refused["started"] is False and "already running" in refused["error"]
    for _ in range(200):
        if not cap.busy:
            break
        import time as _t

        _t.sleep(0.01)
    assert not cap.busy and cap.captures == 1
    assert started == [str(tmp_path / "p")] and stopped == [1]
    assert cap.last.get("done") is True
    # the cap clamps absurd windows
    import rustpde_mpi_tpu.config  # noqa: F401 — registry import for env_get

    assert cap.start(str(tmp_path / "p3"), 1e9)["seconds"] <= cap.max_seconds()


def test_perf_degraded_auto_capture_one_shot(tmp_path, monkeypatch):
    from rustpde_mpi_tpu.telemetry import compile_log

    cap = compile_log.ProfilerCapture(
        start_fn=lambda d: None, stop_fn=lambda: None
    )
    monkeypatch.setattr(compile_log, "CAPTURE", cap)
    monkeypatch.setattr(compile_log, "_degrade_fired", False)
    first = compile_log.capture_on_perf_degraded(str(tmp_path))
    assert first is not None and first["reason"] == "perf_degraded"
    # one-shot per process: a second regression only counts
    assert compile_log.capture_on_perf_degraded(str(tmp_path)) is None


def test_metrics_dumper_single_process_path_unchanged(tmp_path):
    """The multihost collision fix suffixes NON-root ranks only; on a
    single process (and on root) the path — and every existing reader —
    is untouched.  The 2-proc suffix assertion lives in mp_worker's
    serve_campaign mode."""
    path = str(tmp_path / "metrics.jsonl")
    d = MetricsDumper(path, every_s=1e9)
    assert d.path == path
