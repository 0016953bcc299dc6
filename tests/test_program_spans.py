"""The program's spans at the model-step and ensemble boundaries
(telemetry/tracing.py, models/campaign.py, models/ensemble.py), the named
scopes of the step stages (models/navier.py, solver.py) and the benchmark's
readers of the span ring (benchmark/layer_metrics/).

What is held here: a span opened inside a profiler session is in the trace's
host plane under ``rustpde:<name>``, nested as the calls are; ids and parents;
the ``launches`` count; the scopes change no instruction of the compiled
chunk; states are bit-identical with the recorder on and off, and off opens
no annotation; each reader gives the mean it should; the build path's spans
(``model.build`` and what it holds) and jax's compile events on the span that
is open; the five readers of the set-up's spans.  No wall-clock cost is
asserted."""

import contextlib
import glob
import importlib
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax

from benchmark.meter import CompileMeter
from rustpde_mpi_tpu import Navier2D, NavierEnsemble
from rustpde_mpi_tpu.telemetry import FlightRecorder, compile_log
from rustpde_mpi_tpu.telemetry import tracing as ttracing
from rustpde_mpi_tpu.utils.jit import scan_buckets

MODEL_SPANS = ("model.update_n", "model.carry_copy", "model.launch", "model.observe",
               "model.observe_launch", "model.observe_fetch")
ENSEMBLE_SPANS = ("ensemble.update_n", "ensemble.carry_copy", "ensemble.launch")
PARENT = {"model.carry_copy": "model.update_n", "model.launch": "model.update_n",
          "model.observe_launch": "model.observe", "model.observe_fetch": "model.observe",
          "ensemble.carry_copy": "ensemble.update_n", "ensemble.launch": "ensemble.update_n"}


def _model(seed=0):
    m = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
    m.init_random(0.1, seed=seed)
    return m


@pytest.fixture
def ring(monkeypatch):
    """A recorder of the test's own, recording on."""
    rec = FlightRecorder(capacity=512)
    monkeypatch.setattr(ttracing, "RECORDER", rec)
    monkeypatch.setattr(ttracing, "_ENABLED", True)
    return rec


# -- (a) the device trace's clock ----------------------------------------------


def test_spans_land_in_the_profilers_host_plane(ring, tmp_path):
    """Every span of the model-step and ensemble seams is in the host plane
    of a trace taken the way the service's own capture takes it (Python
    tracer off), the child inside its parent, as long as the ring says."""
    from jax.profiler import ProfileData

    m = _model()
    ens = NavierEnsemble.from_seeds(_model(), seeds=[1, 2], amp=0.1)
    m.update_n(4), m.get_observables(), ens.update_n(4)  # compiled outside the trace
    ring.clear()
    compile_log._start_trace(str(tmp_path))
    try:
        m.update_n(4)
        m.get_observables()
        ens.update_n(4)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    found: dict = {}
    python_frames = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                python_frames += ev.name.startswith("$")
                if ev.name.startswith("rustpde:"):
                    found.setdefault(ev.name[len("rustpde:"):], []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert python_frames == 0  # the capture's options switch the Python tracer off
    for name in MODEL_SPANS + ENSEMBLE_SPANS:
        assert len(found.get(name, ())) == 1, (name, sorted(found))
        (start, end), = found[name]
        (_, dur_ns, *_), = ttracing.spans(name)
        assert abs((end - start) - dur_ns) < 1e6, name
        if name in PARENT:
            (p0, p1), = found[PARENT[name]]
            assert p0 <= start and end <= p1, name


def test_profiler_capture_default_start_switches_the_python_tracer_off(monkeypatch, tmp_path):
    seen = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, profiler_options=None: seen.append((d, profiler_options)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: seen.append("stop"))
    cap = compile_log.ProfilerCapture()
    assert cap.start(str(tmp_path / "p"), 0.01)["started"] is True
    for _ in range(500):
        if not cap.busy:
            break
        threading.Event().wait(0.01)
    assert cap.last.get("done") is True and seen[-1] == "stop"
    logdir, options = seen[0]
    assert logdir == str(tmp_path / "p") and options.python_tracer_level == 0


# -- (b) identity ----------------------------------------------------------------


def test_span_ids_parents_and_layers(ring):
    m = _model()
    m.update_n(4)
    m.get_observables()
    by_name = {name: ttracing.spans(name) for name in MODEL_SPANS}
    ids = [s[2] for found in by_name.values() for s in found]
    assert len(set(ids)) == len(ids) and None not in ids
    for name, parent in PARENT.items():
        if name.startswith("model."):
            (_, _, parent_id, _, _), = by_name[parent]
            assert [s[3] for s in by_name[name]] == [parent_id], name
    for name in ("model.update_n", "model.observe"):
        assert by_name[name][0][3] is None
        assert by_name[name][0][4]["layer"] == "model step"
    assert by_name["model.observe"][0][4]["cached"] is False
    m.get_observables()  # the same state again: no launch, and the span says so
    assert ttracing.spans("model.observe")[-1][4]["cached"] is True
    assert len(ttracing.spans("model.observe_launch")) == 1
    # the ring's events stay Perfetto's: the identity rides in args
    ev = ring.events()[-1]
    assert ev["ph"] == "X" and {"id", "parent", "layer"} <= set(ev["args"])


def test_sibling_threads_do_not_adopt_each_others_parents(ring):
    inside, release = threading.Event(), threading.Event()

    def other():
        with ttracing.span("other_outer", layer="runner"):
            inside.set()
            release.wait(10)

    thread = threading.Thread(target=other)
    thread.start()
    assert inside.wait(10)
    with ttracing.span("main_outer"):
        with ttracing.span("main_inner"):
            pass
    release.set()
    thread.join(10)
    assert not thread.is_alive()
    (outer,), (inner,), (sibling,) = (
        ttracing.spans(n) for n in ("main_outer", "main_inner", "other_outer"))
    assert outer[3] is None and sibling[3] is None  # opened while the other was open
    assert inner[3] == outer[2]


# -- (c) the count at the boundary ---------------------------------------------


@pytest.mark.parametrize("n", [8, 11])
@pytest.mark.parametrize("kind", ["model", "ensemble"])
def test_launches_counts_leaves_copied_and_buckets(ring, kind, n):
    """Nothing is copied at the seam any more: ``launches`` is the buckets
    (the name is from when it also counted the leaves copied)."""
    if kind == "model":
        sim = _model()
    else:
        sim = NavierEnsemble.from_seeds(_model(), seeds=[1, 2, 3], amp=0.1)
    sim.update_n(n)
    (_, _, _, _, args), = ttracing.spans(f"{kind}.update_n")
    assert args["steps"] == n
    assert args["launches"] == len(scan_buckets(n))
    (copy,) = ttracing.spans(f"{kind}.carry_copy")  # the seam stays, empty
    assert copy[4]["leaves"] == 0 and copy[4]["fresh"] == 0
    launched = ttracing.spans(f"{kind}.launch")
    assert [s[4]["steps"] for s in launched] == scan_buckets(n)
    assert not any(s[4]["aot"] for s in launched)
    if kind == "ensemble":
        assert args["members"] == 3 and args["layer"] == "ensemble"


@pytest.mark.parametrize("kind", ["model", "ensemble"])
@pytest.mark.parametrize("folded", [True, False])
def test_update_n_spans_count_the_parity_folds_reverses(ring, monkeypatch, fold_gate, folded, kind):
    """``reverses`` beside ``f32_products`` / ``f64_products`` /
    ``sliced_products`` (the float64 products of the TPU path, ops/folded.py)
    and ``int8_blocks`` (the operator slices those stream):
    the ``rev`` equations of one traced step, the member step's on the
    ensemble's span.
    On the matmul-transform path a confined step applies 20 transforms
    (4 in ``synthesis``, 6 in each of the three convection chains, less the
    x-synthesis of ``velx`` and of ``vely``, which ``ux`` / ``uy`` and the
    velocity's own d/dy share: ``shared_syntheses``): folded,
    each is two products and one reverse; below ops/folded.py's gates, where
    every small float32 grid stands, one plain product and none, and each
    dense checkerboard operator (24 at this size) one product for its two."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    fold_gate(4 if folded else fold_gate.NEVER)
    sim = Navier2D.new_confined(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc")
    sim.init_random(0.1, seed=0)
    if kind == "ensemble":
        sim = NavierEnsemble.from_seeds(sim, seeds=[1, 2], amp=0.1)
    sim.update_n(2)
    args = ttracing.spans(f"{kind}.update_n")[-1][-1]
    assert args["reverses"] == (20 if folded else 0)
    products = args["f64_products"] + args["f32_products"] + args["sliced_products"]
    assert products == (100 if folded else 100 - 20 - 24)
    # the int8 operator slices the sliced products stream: one for each slice
    # pair (45) of each sliced operator, none where no product is sliced
    assert args["int8_blocks"] == 45 * args["sliced_products"]
    assert args["shared_syntheses"] == 2


@pytest.mark.parametrize("kind, fresh", [("model", 7), ("ensemble", 5)])
def test_sentinel_seam_names_the_arrays_it_builds(ring, kind, fresh):
    """The sentinel branch still builds its initial flags and maxima eagerly
    inside ``carry_copy``: the span says how many, ``launches`` leaves them
    out."""
    from rustpde_mpi_tpu.config import StabilityConfig

    model = _model()
    model.set_stability(StabilityConfig())
    sim = model if kind == "model" else NavierEnsemble.from_seeds(model, seeds=[1, 2], amp=0.1)
    sim.update_n(11)
    (copy,) = ttracing.spans(f"{kind}.carry_copy")
    assert copy[4]["leaves"] == 0 and copy[4]["fresh"] == fresh
    assert ttracing.spans(f"{kind}.update_n")[0][4]["launches"] == len(scan_buckets(11))


def test_launch_span_says_when_a_prebuilt_executable_served_it(ring):
    m = _model()
    assert m.aot_compile(8) == 1
    m.update_n(8)
    assert [s[4]["aot"] for s in ttracing.spans("model.launch")] == [True]


@pytest.mark.parametrize("chunk_steps", [8, 14])
@pytest.mark.parametrize("kind", ["model", "ensemble"])
def test_aot_compile_serves_every_bucket_of_the_schedule(ring, kind, chunk_steps):
    """``aot_compile`` builds an executable for every bucket of the
    schedule, so a dispatch of that length enters jit nowhere."""
    if kind == "model":
        sim = _model()
    else:
        sim = NavierEnsemble.from_seeds(_model(), seeds=[1, 2], amp=0.1)
    buckets = scan_buckets(chunk_steps)
    assert sim.aot_compile(chunk_steps) == len(buckets)
    assert set(sim._aot_step_n) == set(buckets)
    assert sim.aot_compile(chunk_steps) == 0  # all there already
    kept = sim.state
    meter = CompileMeter()  # counts every backend compile, cache loads included
    sim.update_n(chunk_steps)
    jax.block_until_ready(sim.state)
    assert meter.compiles == 0
    assert sim.aot_reuse_count == len(buckets)
    launched = ttracing.spans(f"{kind}.launch")
    assert [s[4]["aot"] for s in launched] == [True] * len(buckets)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(kept))


# -- (d) the scopes alter no device code ----------------------------------------


@contextlib.contextmanager
def _no_scope(name):
    yield


def _chunk_text(named: bool, monkeypatch) -> str:
    with monkeypatch.context() as mp:
        if not named:
            mp.setattr(jax, "named_scope", _no_scope)
        m = _model()
        return m._step_n_jit.lower(m._step_consts, m.state, n=8).compile().as_text()


@pytest.mark.parametrize("path", ["dense", "step_impl"])
def test_named_scopes_change_metadata_only(monkeypatch, no_compile_cache, path):
    if path == "step_impl":
        monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    named, bare = _chunk_text(True, monkeypatch), _chunk_text(False, monkeypatch)

    def strip(text):
        """The module without what only names it: each instruction's
        ``metadata`` and the tables of files, functions and stack frames the
        metadata points into."""
        blocks = [b for b in text.split("\n\n") if b.split("\n", 1)[0] not in
                  ("FileNames", "FunctionNames", "FileLocations", "StackFrames")]
        return re.sub(r",? ?metadata=\{[^}]*\}", "", "\n\n".join(blocks))

    assert strip(named) == strip(bare)
    scopes = set(re.findall(r'op_name="([^"]*)"', named))
    for stage in ("synthesis", "momentum_x", "momentum_y", "divergence", "poisson",
                  "projection", "pressure", "temperature"):
        assert any(f"/{stage}/" in s for s in scopes), stage
    assert any("/momentum_x/convection/" in s for s in scopes)
    assert any("/temperature/convection/" in s for s in scopes)
    if path == "dense":
        assert any("/temperature/helmholtz/" in s for s in scopes)
    assert not any("/momentum_x/" in s for s in re.findall(r'op_name="([^"]*)"', bare))


# -- (e) off is off, and on changes no state -----------------------------------


def test_states_bit_identical_and_off_opens_no_annotation(monkeypatch):
    opened = []

    class Counting(ttracing.TraceAnnotation):
        def __init__(self, name, **kw):
            opened.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(ttracing, "TraceAnnotation", Counting)
    monkeypatch.setattr(ttracing, "RECORDER", FlightRecorder(capacity=512))
    out = {}
    for on in (True, False):
        monkeypatch.setattr(ttracing, "_ENABLED", on)
        opened.clear()
        ttracing.RECORDER.clear()
        m = _model(seed=3)
        ens = NavierEnsemble.from_seeds(_model(seed=3), seeds=[4, 5], amp=0.1)
        m.update_n(11)
        obs = m.get_observables()
        ens.update_n(11)
        out[on] = (jax.device_get(m.state), obs, jax.device_get(ens.state),
                   np.asarray(ens.steps_done))
        if on:
            assert {"rustpde:" + n for n in MODEL_SPANS + ENSEMBLE_SPANS} <= set(opened)
            assert ttracing.spans("ensemble.update_n")
        else:
            assert opened == [] and ttracing.RECORDER.events() == []
            assert ttracing.span("anything") is ttracing._NULL_SPAN
    for a, b in zip(jax.tree.leaves(out[True]), jax.tree.leaves(out[False])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- (f) the benchmark's readers -------------------------------------------------

READERS = {
    # reader: (span it reads, what the hand-made ring must give)
    "update_n_host_ms": ("model.update_n", 2.5),
    "chunk_host_ms": ("ensemble.update_n", 2.5),
    "chunk_copy_ms": ("ensemble.carry_copy", 2.5),
    "launches_per_chunk": ("ensemble.update_n", 8.5),
}


@pytest.mark.parametrize("reader", sorted(READERS))
def test_reader_means_the_last_traced_spans(ring, reader):
    mod = importlib.import_module(f"benchmark.layer_metrics.{reader}")
    name, want = READERS[reader]
    run = {"traced_dispatches": 2}
    assert mod.read({}, run) is None  # an empty ring reads nothing, not 0
    # an untraced dispatch first (40 ms, 99 launches), then the two traced ones
    for dur_us, launches in ((40000.0, 99), (2000.0, 8), (3000.0, 9)):
        ring.add_complete(name, ring.now_us(), dur_us, {"id": 1, "parent": None,
                                                        "launches": launches})
    ring.add_complete("something.else", ring.now_us(), 7.0)
    assert mod.read({}, run) == pytest.approx(want)
    assert mod.read({}, {"traced_dispatches": 0}) is None
    assert mod.read({}, {"traced_dispatches": 4}) is None  # fewer spans than dispatches
    ttracing.set_enabled(False)
    assert mod.read({}, run) is None  # the recorder is off


# -- (g) the build path and jax's compile events --------------------------------

BUILD_LAYERS = {"model.build": "model step", "space.build": "operators and kernels",
                "solver.build": "operators and kernels",
                "model.compile_entry_points": "model step", "model.set_field": "model step",
                "ensemble.build": "ensemble", "ensemble.compile_entry_points": "model step"}


def _events(ring):
    return [ev for ev in ring.events() if ev["ph"] == "X"]


def test_a_build_opens_its_spans_under_model_build(ring):
    """Five spaces, three solvers (velx and vely share one), the lift field's
    transforms and the entry points, all children of ``model.build``."""
    m = Navier2D.new_confined(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc")
    (build,) = ttracing.spans("model.build")
    assert build[3] is None
    assert {"nx": 17, "ny": 17, "devices": 1}.items() <= build[4].items()
    assert build[4]["dtype"] in ("float32", "float64")
    children = [ev for ev in _events(ring) if ev["args"]["parent"] == build[2]]
    names = [ev["name"] for ev in children]
    assert names.count("space.build") == 5 and names.count("solver.build") == 3
    assert names.count("model.compile_entry_points") == 1 and names.count("model.set_field") == 1
    assert set(names) == {"space.build", "solver.build", "model.compile_entry_points",
                          "model.set_field"}
    for ev in children:
        assert ev["args"]["layer"] == BUILD_LAYERS[ev["name"]], ev["name"]
    kinds = [ev["args"]["kind"] for ev in children if ev["name"] == "solver.build"]
    assert kinds == ["hholtz_adi", "hholtz_adi", "poisson"]
    (poisson,) = [ev for ev in children if ev["args"].get("kind") == "poisson"]
    assert poisson["args"]["eigs"] == 1 and poisson["args"]["eig_cached"] == 0  # below the cache's gate
    (entry,) = [ev for ev in children if ev["name"] == "model.compile_entry_points"]
    assert entry["args"]["pass"] == 1 and entry["args"]["traces"] > 0
    assert entry["args"]["consts"] == len(m._step_consts) + len(m._obs_consts)
    assert entry["args"]["const_bytes"] == sum(
        c.nbytes for c in (*m._step_consts, *m._obs_consts))
    m.set_field("temp", np.zeros((17, 17)))
    (*_, parent, args) = ttracing.spans("model.set_field")[-1]
    assert parent is None and args["fields"] == ("temp",) and args["layer"] == "model step"
    m._compile_entry_points()  # a dt-ladder re-jit: the same seam, outside any build
    again = ttracing.spans("model.compile_entry_points")[-1]
    assert again[3] is None and again[4]["pass"] == 2


def test_an_ensemble_build_is_a_span_of_its_own(ring):
    ens = NavierEnsemble.from_seeds(_model(), seeds=[1, 2, 3], amp=0.1)
    (build,) = ttracing.spans("ensemble.build")
    assert build[3] is None and build[4]["members"] == 3 == ens.k
    assert build[4]["layer"] == "ensemble"
    (entry,) = ttracing.spans("ensemble.compile_entry_points")
    assert entry[3] == build[2] and entry[4]["layer"] == "model step" and entry[4]["pass"] == 1
    # from_seeds sets three fields a member, before the ensemble is built
    assert sum(s[3] is None for s in ttracing.spans("model.set_field")) >= 9


def test_compile_events_land_on_the_span_that_is_open(ring):
    """The first dispatch lowers and compiles under ``model.launch``, the
    second fires nothing; every event of the stretch is on a span or in
    ``unattributed``, and the two sum to the harness's meter."""
    meter = CompileMeter()
    mark, before = meter.mark(), ttracing.compile_totals()
    m = _model()
    m.update_n(8)
    m.get_observables()
    jax.block_until_ready(m.state)
    first = ttracing.spans("model.launch")[-1][4]
    assert first["lowerings"] >= 1 and first["backend_compiles"] >= 1
    assert first["lower_s"] > 0 and first["compile_s"] > 0 and first["traces"] >= 1
    assert ttracing.spans("model.observe_launch")[-1][4]["backend_compiles"] >= 1
    since = meter.since(mark)
    totals = ttracing.compile_totals_since(before)
    assert totals["backend_compiles"] == since["compiled"] + since["cache_loads"] > 0
    assert totals["cache_hits"] == since["cache_loads"]
    assert totals["cache_misses"] == since["cache_misses"]
    assert totals["compiled"] == since["compiled"]
    assert totals["compile_s"] == pytest.approx(since["compile_s"])
    # the ring's own counts are the attributed side of the totals
    now = ttracing.compile_totals()
    for key in ("backend_compiles", "lowerings", "traces", "cache_hits"):
        on_spans = sum(ev["args"].get(key, 0) for ev in _events(ring))
        assert on_spans == now["attributed"][key] - before["attributed"][key], key
    # the read-back is a span too: nothing the program ran was off a span
    m.get_field("temp")
    assert ttracing.spans("model.get_field")[-1][4]["fields"] == ("temp",)
    assert ttracing.compile_totals()["unattributed"] == before["unattributed"]
    mark, before = meter.mark(), ttracing.compile_totals()
    m.update_n(8)
    jax.block_until_ready(m.state)
    warm = ttracing.spans("model.launch")[-1][4]
    assert not set(warm) & set(ttracing.COMPILE_KEYS)
    assert meter.since(mark)["compiled"] + meter.since(mark)["cache_loads"] == 0
    assert ttracing.compile_totals() == before


def test_an_event_with_no_span_open_is_unattributed_and_threads_do_not_share(ring):
    name = "/jax/core/compile/backend_compile_duration"
    before = ttracing.compile_totals()
    ttracing._on_event(name, 0.25)  # nothing open on this thread
    ttracing._on_event("/jax/compilation_cache/cache_hits")
    ttracing._on_event("/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
    ttracing._on_event("/jax/some/other/event", 9.0)  # not a compile event
    after = ttracing.compile_totals()
    assert after["attributed"] == before["attributed"]
    assert after["unattributed"]["backend_compiles"] == before["unattributed"]["backend_compiles"] + 1
    assert after["unattributed"]["compile_s"] == pytest.approx(before["unattributed"]["compile_s"] + 0.25)
    assert after["unattributed"]["cache_hits"] == before["unattributed"]["cache_hits"] + 1
    assert after["unattributed"]["cache_load_s"] == pytest.approx(
        before["unattributed"]["cache_load_s"] + 0.125)
    fired = threading.Event()

    def sibling():
        ttracing._on_event(name, 0.5)  # this thread has no span open
        fired.set()

    with ttracing.span("main_outer") as outer:
        thread = threading.Thread(target=sibling)
        thread.start()
        assert fired.wait(10)
        thread.join(10)
        with ttracing.span("main_inner"):
            ttracing._on_event(name, 1.0)
        ttracing._on_event("/jax/core/compile/jaxpr_to_mlir_module_duration", 2.0)
    (inner,) = ttracing.spans("main_inner")
    assert inner[4]["backend_compiles"] == 1 and inner[4]["compile_s"] == 1.0
    assert outer.args == {"lowerings": 1, "lower_s": 2.0}  # self counts: not the child's
    end = ttracing.compile_totals()
    assert end["unattributed"]["backend_compiles"] == after["unattributed"]["backend_compiles"] + 1
    assert end["attributed"]["backend_compiles"] == after["attributed"]["backend_compiles"] + 1
    ttracing.set_enabled(False)  # switched off later: each listener returns at its first branch
    ttracing._on_event(name, 1.0)
    ttracing._on_event("/jax/compilation_cache/cache_hits")
    assert ttracing.compile_totals() == end


def test_compile_totals_lose_no_event_under_threads(ring):
    """More threads than cores firing events, each under a span of its own:
    every span holds its thread's events and the totals hold all of them."""
    threads, each = 16, 500
    before = ttracing.compile_totals()["attributed"]["lowerings"]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def fire(i):
        with ttracing.span(f"stress{i}"):
            for _ in range(each):
                ttracing._on_event("/jax/core/compile/jaxpr_to_mlir_module_duration", 1e-3)

    try:
        workers = [threading.Thread(target=fire, args=(i,)) for i in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(60)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(old)
    for i in range(threads):
        (found,) = ttracing.spans(f"stress{i}")
        assert found[4]["lowerings"] == each
    assert ttracing.compile_totals()["attributed"]["lowerings"] == before + threads * each


def test_a_process_that_starts_with_tracing_off_registers_no_listener():
    code = (
        "from jax._src import monitoring as m\n"
        "n = (len(m.get_event_listeners()), len(m.get_event_duration_listeners()))\n"
        "from rustpde_mpi_tpu.telemetry import tracing\n"
        "print(len(m.get_event_listeners()) - n[0], len(m.get_event_duration_listeners()) - n[1],"
        " tracing.enabled())\n"
    )
    out = {}
    for value in ("0", "1"):
        env = dict(os.environ, RUSTPDE_TRACE=value, JAX_PLATFORMS="cpu")
        out[value] = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                                    capture_output=True, check=True).stdout.split()
    assert out == {"0": ["0", "0", "False"], "1": ["1", "1", "True"]}


@pytest.mark.parametrize("seam", ["model", "ensemble", "registry"])
def test_compile_log_observes_the_spans_own_duration(ring, monkeypatch, seam):
    """One timing per seam: what ``/metrics``' histograms are handed is the
    duration the ring holds."""
    seen = []
    monkeypatch.setattr(compile_log, "observe_entry_compile",
                        lambda kind, wall_s: seen.append((kind, wall_s)))
    sound = compile_log.observe_build
    monkeypatch.setattr(compile_log, "observe_build",
                        lambda key, wall_s, **kw: seen.append(("build", wall_s)) or sound(key, wall_s, **kw))
    if seam == "registry":
        from rustpde_mpi_tpu.workloads.registry import build_model_for_key

        build_model_for_key(_model().compat_key)
        name, kind = "registry.build_model", "build"
    elif seam == "model":
        _model()
        name, kind = "model.compile_entry_points", "dns"
    else:
        NavierEnsemble.from_seeds(_model(), seeds=[1, 2], amp=0.1)
        name, kind = "ensemble.compile_entry_points", "ensemble:dns"
    (_, dur_ns, *_), = ttracing.spans(name)[-1:]
    walls = [wall for k, wall in seen if k == kind]
    assert walls[-1] == pytest.approx(dur_ns * 1e-9, abs=1e-9)
    # with the recorder off the seam still hands the histogram a duration
    ttracing.set_enabled(False)
    seen.clear()
    _model()
    assert seen and seen[-1][1] > 0 and ttracing.timed("x") is not ttracing._NULL_SPAN


SETUP_READERS = ("operator_build_s", "eager_programs", "entry_trace_s", "first_dispatch_s",
                 "cache_load_s")


def test_setup_readers_read_the_spans_before_the_first_traced_dispatch(ring):
    """The five readers on a real set-up: a build, initial values, a warm-up
    that compiles, an interval that only runs, then two "traced" dispatches.
    What they read is the set-up's spans, not the last traced ones."""
    mods = {r: importlib.import_module(f"benchmark.layer_metrics.{r}") for r in SETUP_READERS}
    run = {"traced_dispatches": 2}
    assert all(mod.read({}, run) is None for mod in mods.values())  # an empty ring
    m = _model()
    for _ in range(2):
        m.update_n(8)
        m.get_observables()
    setup = _events(ring)
    for _ in range(2):  # the traced ones
        m.update_n(8)
        m.get_observables()
    got = {r: mod.read({}, run) for r, mod in mods.items()}
    launches = [ev for ev in setup if ev["name"] in ("model.launch", "model.observe_launch")]
    assert len(launches) == 4
    by_name = lambda n: [ev for ev in setup if ev["name"] == n]  # noqa: E731
    assert got["operator_build_s"] == pytest.approx(
        1e-6 * sum(ev["dur"] for ev in by_name("space.build") + by_name("solver.build")))
    assert got["entry_trace_s"] == pytest.approx(
        1e-6 * by_name("model.compile_entry_points")[0]["dur"])
    assert got["first_dispatch_s"] == pytest.approx(1e-6 * sum(
        ev["dur"] for ev in launches if ev["args"].get("lowerings")))
    assert sum(1 for ev in launches if ev["args"].get("lowerings")) == 2
    eager = sum(ev["args"].get("backend_compiles", 0) for ev in setup if ev not in launches)
    assert got["eager_programs"] == eager  # 0 once an earlier test ran the same small programs
    assert got["cache_load_s"] == pytest.approx(
        sum(ev["args"].get("cache_load_s", 0.0) for ev in setup))
    assert isinstance(got["cache_load_s"], float)
    # a traced dispatch that compiled (it must not) would not be counted as set-up
    assert mods["first_dispatch_s"].read({}, {"traced_dispatches": 4}) < got["first_dispatch_s"]
    assert mods["eager_programs"].read({}, {"traced_dispatches": 5}) is None  # four dispatches
    ttracing.set_enabled(False)
    assert all(mod.read({}, run) is None for mod in mods.values())  # the recorder is off


def test_setup_readers_read_nothing_without_build_spans_or_from_a_full_ring(ring, monkeypatch):
    mod = importlib.import_module("benchmark.layer_metrics.eager_programs")
    m = _model()
    m.update_n(8), m.update_n(8)
    run = {"traced_dispatches": 1}
    assert mod.read({}, run) is not None
    monkeypatch.setattr(ring, "capacity", len(ring.events()))  # as if the head were gone
    assert mod.read({}, run) is None
    monkeypatch.undo()


# -- (h) the yardstick's own checks ----------------------------------------------


def test_benchmark_selfcheck_passes(capsys):
    from benchmark import selfcheck

    assert selfcheck.main([]) == 0
    assert "8 cells, 27 readers agree" in capsys.readouterr().out
