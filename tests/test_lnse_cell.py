"""What the ``lnse_opt128_f32.loop`` cell rests on, at sizes a CPU can hold:

* the plain reference with an adjoint (``benchmark/reference_lnse.py``)
  against the program's own float64 path: the state and the stored trajectory
  after n forward steps, J, the three gradient fields and the updated initial
  condition, at 14 x 11, 24 x 21 and 32 x 17 (an even extent);
* the library's iteration (``models/opt_routines.descent_iteration``) against
  the loop body the example carried before it, step for step, and the energy
  of every new initial condition;
* ``grad_adjoint`` on the normal path: a horizon seen before builds nothing,
  the spans nest and count as PERF.md section 3 says, and the stage scopes are
  metadata only;
* the driver's way of making the base state's mean against the example's;
* ``benchmark/work_lnse.py``'s count against a hand count, and the new
  per-layer readers.
"""

import contextlib
import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import work, work_lnse
from benchmark.drivers import descent_loop
from benchmark.ic import smooth_fields
from benchmark.ic_lnse import conduction_profile, perturbation
from benchmark.meter import CompileMeter
from benchmark.reference_lnse import FIELDS, STATE, Reference
from benchmark.reference_lnse import mirrored_target as reference_target
from benchmark.reference_lnse import steepest_descent_energy_constrained as reference_descent
from rustpde_mpi_tpu import (
    MeanFields,
    Navier2D,
    Navier2DLnse,
    Navier2DNonLin,
    config,
    descent_iteration,
    mirrored_target,
    steepest_descent_energy_constrained,
)
from rustpde_mpi_tpu.models.lnse import l2_norm
from rustpde_mpi_tpu.telemetry import FlightRecorder
from rustpde_mpi_tpu.telemetry import tracing as ttracing
from rustpde_mpi_tpu.utils.jit import scan_buckets

RA, PR, DT, ASPECT = 1e4, 1.0, 0.01, 1.0
BETA = (0.5, 0.5)
GRIDS = [(14, 11), (24, 21), (32, 17)]
needs_x64 = pytest.mark.skipif(not config.X64, reason="the reference is pinned in float64")


def base_of(nx, ny, steps=40) -> dict:
    """A base state with a flow in it: a short DNS, its temperature made the
    total field."""
    dns = Navier2D.new_confined(nx, ny, RA, PR, DT, ASPECT, "rbc")
    for name, values in smooth_fields(nx, ny, 3, 0.1, 4).items():
        dns.set_field(name, values)
    dns.update_n(steps)
    base = {k: np.asarray(dns.get_field(k), np.float64) for k in FIELDS}
    base["temp"] = base["temp"] + conduction_profile(nx, ny)
    return base


def problem(nx, ny, cls=Navier2DNonLin, seed=4):
    """(model holding the seed's initial condition, target, base, that
    initial condition as the model holds it)."""
    base = base_of(nx, ny)
    mean = descent_loop.mean_fields(nx, ny, base)
    model = cls.new_confined(nx, ny, RA, PR, DT, ASPECT, "rbc", mean=mean)
    for name, values in smooth_fields(nx, ny, seed, 0.05, 4).items():
        model.set_field(name, values)
    held = {k: np.asarray(model.get_field(k), np.float64) for k in FIELDS}
    return model, mirrored_target(mean), base, held


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture
def ring(monkeypatch):
    ring = FlightRecorder(capacity=512)
    monkeypatch.setattr(ttracing, "RECORDER", ring)
    monkeypatch.setattr(ttracing, "_ENABLED", True)
    return ring


# -- the reference ---------------------------------------------------------------


@needs_x64
@pytest.mark.parametrize("grid", GRIDS)
def test_reference_is_pinned_to_the_programs_f64_iteration(grid):
    """One whole iteration, 12 steps each way: J, the gradient and the new
    initial condition of the program's float64 CPU path (banded solves, folded
    operators, hoisted constants) and of the reference (dense products, its own
    operators, one scan a sweep) agree to rounding."""
    nx, ny = grid
    model, target, base, held = problem(nx, ny)
    step = descent_iteration(model, 12 * DT, *BETA, target, alpha=1.0)
    out = Reference(nx, ny, RA, PR, DT, ASPECT, base, dtype=np.float64).iteration(
        held, 12, *BETA, 1.0)
    assert abs(step.fun_val / out["fun_val"] - 1.0) < 1e-9
    for i, k in enumerate(FIELDS):
        assert rel(step.grads[i], out[f"grad_{k}"]) < 1e-9, k
        assert rel(step.fields[i], out[f"new_{k}"]) < 1e-9, k
        assert rel(model.get_field(k), out[f"new_{k}"]) < 1e-9, k  # as the model holds it


@needs_x64
@pytest.mark.parametrize("grid", GRIDS)
def test_reference_is_pinned_to_the_programs_f64_forward_sweep(grid):
    """The state and every entry of the stored trajectory after 11 forward
    steps (one program a sweep in the program, one scan in the reference)."""
    nx, ny = grid
    model, _, base, held = problem(nx, ny)
    history, launches = model._forward_sweep(11)
    assert launches == 1 and [h.shape for h in history] == [(11, nx, ny)] * 3
    ref = Reference(nx, ny, RA, PR, DT, ASPECT, base, dtype=np.float64)
    after, stored = ref.sweep_forward(ref.initial_state(held), 11)
    for name in ("temp", "velx", "vely", "pres"):
        assert rel(model.get_field(name), ref.backward(name, after[STATE.index(name)])) < 1e-9
    for i in range(3):
        assert rel(history[i], stored[i]) < 1e-9, FIELDS[i]


@needs_x64
def test_reference_tells_the_linear_adjoint_and_a_forward_history_apart():
    """What the broken-path controls of benchmark/tests lean on: without the
    trajectory's terms, or with the trajectory read first to last, the
    gradient is another gradient by far more than rounding."""
    nx, ny = 24, 21
    model, _, base, held = problem(nx, ny)
    ref = Reference(nx, ny, RA, PR, DT, ASPECT, base, dtype=np.float64)
    sound = ref.iteration(held, 12, *BETA, 1.0)
    start = ref.terminal(sound["state"], *BETA)
    flipped = ref.sweep_adjoint(start, jax.tree.map(lambda x: x[::-1], sound["history"]))
    empty = ref.sweep_adjoint(start, jax.tree.map(jnp.zeros_like, sound["history"]))
    for wrong in (flipped, empty):
        grad = -ref.backward("velx", wrong[STATE.index("velx")])
        assert rel(grad, sound["grad_velx"]) > 1e-6  # rounding is 1e-13


def test_reference_target_and_descent_are_the_programs():
    nx, ny = 14, 11
    base = base_of(nx, ny, steps=10)
    want = mirrored_target(descent_loop.mean_fields(nx, ny, base)).physical()
    got = reference_target(base)
    for i, k in enumerate(FIELDS):
        assert np.abs(want[i] - got[k]).max() < 1e-6 * np.abs(got[k]).max()
    rng = np.random.default_rng(1)
    old, grad = ({k: rng.normal(size=(nx, ny)) for k in FIELDS} for _ in range(2))
    theirs = reference_descent(old, grad, 0.5, 0.25, 0.7)
    ours = steepest_descent_energy_constrained(
        *(old[k] for k in FIELDS), *(grad[k] for k in FIELDS), 0.5, 0.25, 0.7)
    for i, k in enumerate(FIELDS):
        np.testing.assert_allclose(ours[i], theirs[k], rtol=1e-5, atol=1e-6)


# -- the library's iteration ----------------------------------------------------------


def old_loop_body(model, max_time, beta1, beta2, target, alpha, alpha_0, it, j_old):
    """examples/navier_lnse_opt_reversals.py's loop body as it stood before
    the library took it over (PR 29's tree), kept here to hold the library to
    it."""
    model.state = model.state._replace(
        pres=jnp.zeros_like(model.state.pres),
        pseu=jnp.zeros_like(model.state.pseu),
    )
    model.reset_time()
    u0, v0, t0 = (np.asarray(a) for a in model._phys(model.state))
    fun_val, grads = model.grad_adjoint(max_time, None, beta1, beta2, target=target)
    if it > 0 and fun_val > j_old:
        alpha /= 2.0
        if alpha < 1e-3:
            alpha = alpha_0
    gu, gv, gt = (np.asarray(g) for g in grads)
    un, vn, tn = steepest_descent_energy_constrained(u0, v0, t0, gu, gv, gt, beta1, beta2, alpha)
    model.reset_time()
    model.set_field("velx", un)
    model.set_field("vely", vn)
    model.set_field("temp", tn)
    return fun_val, alpha


@pytest.mark.parametrize("cls", [Navier2DNonLin, Navier2DLnse])
def test_library_iteration_is_the_examples_old_loop_body_step_for_step(cls):
    """Four iterations from alpha_0 = 4 (large enough that J rises and the
    backtracking rule fires): J, alpha and the state agree at every one."""
    nx, ny = 14, 11
    mine, target, _, _ = problem(nx, ny, cls)
    theirs, _, _, _ = problem(nx, ny, cls)
    alpha = alpha_old = 4.0
    fun_old, j_old, halved = None, 0.0, False
    for it in range(4):
        step = descent_iteration(mine, 10 * DT, *BETA, target, alpha, 4.0, fun_old)
        j_old, alpha_old = old_loop_body(theirs, 10 * DT, *BETA, target, alpha_old, 4.0, it, j_old)
        halved |= step.alpha != alpha
        alpha, fun_old = step.alpha, step.fun_val
        # to rounding, not to the bit: the library reads the fields, J and the
        # terminal condition and sets the new fields through one jitted program
        # each, where the old body dispatched the same operators one by one
        assert step.alpha == alpha_old and step.fun_val == pytest.approx(j_old, rel=1e-12), it
        for a, b in zip(jax.tree.leaves(mine.state), jax.tree.leaves(theirs.state)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-16)
    assert halved


@pytest.mark.parametrize("alpha", [1.0, 0.25])
def test_every_new_initial_condition_keeps_the_energy(alpha):
    nx, ny = 24, 21
    model, target, _, _ = problem(nx, ny)
    start = perturbation(nx, ny, 7, 4.64e-4, *BETA)
    for name in FIELDS:
        model.set_field(name, start[name])
    assert descent_loop.point_energy([start[k] for k in FIELDS], *BETA) == pytest.approx(4.64e-4, rel=1e-12)
    fun_old = None
    for _ in range(3):
        step = descent_iteration(model, 8 * DT, *BETA, target, alpha, 1.0, fun_old)
        fun_old = step.fun_val
        assert descent_loop.point_energy(step.fields, *BETA) == pytest.approx(4.64e-4, rel=1e-4)
        u, v, t = (np.asarray(a) for a in model._phys(model.state))  # as the model holds it
        held = float(l2_norm(u, u, v, v, t, t, *BETA)) / u.size
        assert held == pytest.approx(4.64e-4, rel=1e-4)


# -- the normal path -------------------------------------------------------------------


@pytest.mark.parametrize("cls", [Navier2DNonLin, Navier2DLnse])
def test_a_horizon_seen_before_builds_nothing(cls, ring):
    model, target, _, _ = problem(14, 11, cls)
    start = model.state
    meter = CompileMeter()  # every backend compile, cache loads included
    model.grad_adjoint(11 * DT, None, *BETA, target=target)
    first = meter.compiles
    assert first > 0
    model.state = start
    mark = meter.compiles
    model.grad_adjoint(11 * DT, None, *BETA, target=target)
    assert meter.compiles == mark
    # a new horizon builds its own programs, one a sweep; the linear model's
    # sweeps both run in update_n's buckets (11 = 8 + 3 is there, 12 = 8 + 4
    # adds one each).  The count is jax's own, on the launch that compiled:
    # ``backend_compiles`` of the ``model.launch`` spans under each call
    model.state = start
    model.grad_adjoint(12 * DT, None, *BETA, target=target)
    events = [ev for ev in ring.events() if ev["ph"] == "X"]
    parent = {ev["args"]["id"]: ev["args"]["parent"] for ev in events}

    def under(ev, root):
        at = ev["args"]["parent"]
        while at is not None and at != root:
            at = parent[at]
        return at == root

    built = [
        sum(ev["args"].get("backend_compiles", 0) for ev in events
            if ev["name"] == "model.launch" and under(ev, call[2]))
        for call in ttracing.spans("lnse.grad_adjoint")
    ]
    assert built == ([2, 0, 2] if cls is Navier2DNonLin else [4, 0, 2])
    assert 0 < meter.compiles - mark <= first


def test_sweep_constants_go_through_the_hoisting_seam():
    """The sweeps' constants are the arrays ``_hoist`` committed (not closed
    over by a jit of their own) and are counted where the chunks' are."""
    model, _, _, _ = problem(14, 11)
    assert len(model._fwd_consts) > 10 and len(model._adj_consts) > 10
    assert model._unplaced_consts == 0
    assert {"_fwd_n", "_adj_n", "_fwd_consts", "_adj_consts"} <= set(model._DT_ARTIFACTS)


def test_spans_nest_and_count(ring):
    nx, ny, n = 14, 11, 11
    model, target, _, _ = problem(nx, ny)
    ring.clear()  # the base state's DNS launched too
    step = descent_iteration(model, n * DT, *BETA, target, 1.0)
    by_name = {name: ttracing.spans(name) for name in (
        "lnse.descent_iteration", "lnse.grad_adjoint", "lnse.forward_sweep",
        "lnse.adjoint_sweep", "lnse.descent_update", "model.launch")}
    (whole,), (grad,), (fwd,), (adj,), (upd,) = (
        by_name[k] for k in list(by_name)[:5])
    assert whole[3] is None
    assert grad[3] == upd[3] == whole[2]
    assert fwd[3] == adj[3] == grad[2]
    launches = by_name["model.launch"]  # one program a sweep
    assert [s[3] for s in launches] == [fwd[2], adj[2]]
    assert [s[4]["steps"] for s in launches] == [n, n]
    assert whole[4]["steps"] == grad[4]["steps"] == 2 * n
    assert fwd[4]["steps"] == adj[4]["steps"] == n
    assert (whole[4]["alpha"], whole[4]["fun_val"]) == (step.alpha, step.fun_val)
    assert grad[4]["launches"] == 2
    itemsize = np.dtype(config.real_dtype()).itemsize
    assert grad[4]["history_bytes"] == 3 * n * nx * ny * itemsize
    assert all(s[4]["layer"] == "model step" for spans in by_name.values() for s in spans)
    # the sweeps and the update are all but all of the iteration
    assert grad[1] + upd[1] <= whole[1] and fwd[1] + adj[1] <= grad[1]


def test_linear_models_spans(ring):
    model, target, _, _ = problem(14, 11, Navier2DLnse)
    ring.clear()
    model.grad_adjoint(11 * DT, None, *BETA, target=target)
    (grad,), (fwd,), (adj,) = (ttracing.spans(f"lnse.{k}") for k in (
        "grad_adjoint", "forward_sweep", "adjoint_sweep"))
    (update,) = ttracing.spans("model.update_n")
    assert update[3] == fwd[2] and fwd[3] == adj[3] == grad[2]
    assert grad[4]["history_bytes"] == 0 and grad[4]["launches"] == 4
    launches = ttracing.spans("model.launch")  # both ways in update_n's buckets
    assert [s[3] for s in launches] == [update[2]] * 2 + [adj[2]] * 2
    assert [s[4]["steps"] for s in launches] == 2 * scan_buckets(11)


# -- the scopes ---------------------------------------------------------------------


@contextlib.contextmanager
def _no_scope(name):
    yield


def _strip(text: str) -> str:
    """The module without what only names it: each instruction's ``metadata``,
    the tables the metadata points into, and the numbers in the instructions'
    own names (an instruction made late in the compile is numbered after the
    lowered module's last, and jax lowers a function called under two scopes
    twice: ``%transpose.711`` here is ``%transpose.735`` there), which are
    renumbered in order of appearance."""
    blocks = [b for b in text.split("\n\n") if b.split("\n", 1)[0] not in
              ("FileNames", "FunctionNames", "FileLocations", "StackFrames")]
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", "\n\n".join(blocks))
    seen: dict = {}
    return re.sub(r"%[\w.\-]+", lambda m: seen.setdefault(m.group(0), f"%{len(seen)}"), text)


def _sweep_texts(named: bool, monkeypatch, cls) -> tuple:
    """The compiled text of a 4-step forward sweep (nonlinear model only: the
    linear one's is ``update_n``) and of a 4-step adjoint sweep."""
    with monkeypatch.context() as mp:
        if not named:
            mp.setattr(jax, "named_scope", _no_scope)
        model, _, _, _ = problem(14, 11, cls)
        if cls is Navier2DLnse:
            lowered = [model._adj_n_jit.lower(model._adj_consts, model.state, n=4)]
        else:
            _, chunk = model._fwd_n(model.state, 4)
            lowered = [model._fwd_n_jit.lower(model._fwd_consts, model.state, n=4),
                       model._adj_n_jit.lower(model._adj_consts, model.state, chunk)]
        return tuple(low.compile().as_text() for low in lowered)


@pytest.mark.parametrize("cls", [Navier2DNonLin, Navier2DLnse])
def test_stage_scopes_change_metadata_only(monkeypatch, no_compile_cache, cls):
    named, bare = _sweep_texts(True, monkeypatch, cls), _sweep_texts(False, monkeypatch, cls)
    assert len(named) == (2 if cls is Navier2DNonLin else 1)
    for with_names, without in zip(named, bare):
        assert _strip(with_names) == _strip(without)
        assert not any("/momentum_x/" in s for s in re.findall(r'op_name="([^"]*)"', without))
    stages = ("synthesis", "momentum_x", "momentum_y", "divergence", "poisson", "projection",
              "pressure", "temperature")
    for text in named:
        scopes = set(re.findall(r'op_name="([^"]*)"', text))
        for stage in stages:
            assert any(f"/{stage}/" in s for s in scopes), stage
        assert any("/momentum_x/convection/" in s for s in scopes)
        assert any("/temperature/convection/" in s for s in scopes)
    if cls is Navier2DNonLin:
        forward, adjoint = (set(re.findall(r'op_name="([^"]*)"', t)) for t in named)
        assert any("/history/" in s for s in forward)
        assert any("/history_terms/" in s for s in adjoint)
        assert not any("/history_terms/" in s for s in forward)


# -- the base state ------------------------------------------------------------------


def test_drivers_mean_is_the_examples(tmp_path):
    """The driver hands ``MeanFields`` the DNS's fields with the conduction
    profile added to the temperature; the example writes the DNS's snapshot,
    reads it back and adds ``MeanFields.new_rbc``'s profile.  The same mean,
    and its temperature is the total field: +0.5 / -0.5 on the plates."""
    nx, ny = 16, 13
    cfg = {"grid": {"nx": nx, "ny": ny}, "optimisation": {"base_time": 30 * DT},
           "physics": {"ra": RA, "pr": PR, "dt": DT, "aspect": ASPECT, "bc": "rbc"}}
    base = descent_loop.base_state(cfg, {"base_ic": {"amp": 0.1, "modes": 4}}, seed=5)
    mine = descent_loop.mean_fields(nx, ny, base)
    dns = Navier2D.new_confined(nx, ny, RA, PR, DT, ASPECT, "rbc")
    for name, values in smooth_fields(nx, ny, 5, 0.1, 4).items():
        dns.set_field(name, values)
    dns.update_n(30)
    dns.write(str(tmp_path / "mean.h5"))
    theirs = MeanFields.read_from(nx, ny, str(tmp_path / "mean.h5"), bc="rbc")
    theirs.temp = theirs.temp + MeanFields.new_rbc(nx, ny).temp
    for a, b in zip(mine.physical(), theirs.physical()):
        np.testing.assert_allclose(a, b, atol=1e-6)
    total = mine.physical()[2]
    np.testing.assert_allclose(total[:, 0], 0.5, atol=1e-6)
    np.testing.assert_allclose(total[:, -1], -0.5, atol=1e-6)
    assert np.abs(mine.physical()[0]).max() > 1e-3  # a flow, not the conduction state


# -- the yardstick's new pieces ------------------------------------------------------


def test_work_count_against_a_hand_count():
    """8 x 6: 35 + 53 products, half along x (8 x 8 on 8 x 6, parity-split:
    8 * 8 * 6 flops) and half along y (8 * 6 * 6)."""
    w = work_lnse.pair_work(8, 6)
    assert w["products"] == 35 + 53 == 88
    assert w["flops"] == 44 * (8 * 8 * 6) + 44 * (8 * 6 * 6) == 29568
    field = 8 * 6 * 4
    # five state fields in and out of two steps; 12 half-operators (x and y ones
    # alike) read by two steps; nine base fields read by two steps; the
    # trajectory's three fields written once and read once
    assert w["bytes"] == 20 * field + 2 * 12 * 0.25 * (64 + 36) * 4 + 18 * field + 6 * field
    square = work_lnse.pair_work(100, 100)
    assert square["flops"] == 88 * 100**3
    assert square["flops"] == pytest.approx(
        88 / 35 * work.step_work(100, 100)["flops"])  # work.py's rule at nx = ny
    full = work_lnse.pair_work(128, 57)
    assert full["flops"] == pytest.approx(5.94e7, rel=1e-3)
    assert work.roofline(full, "TPU v5 lite", 1e-3)["bound"] == "memory"


def _read(name, trace, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(trace, run)


def test_span_readers_read_nothing_without_the_spans_and_means_with_them(ring):
    run = {"traced_dispatches": 2}
    names = ("forward_step_us", "adjoint_step_us", "descent_update_ms", "history_mb")
    for name in names:
        assert _read(name, {}, run) is None  # the parent commit: no such span
    for i, dur_us in enumerate((9e5, 1e6, 3e6)):  # the first is the warm-up's: not traced
        ring.add_complete("lnse.forward_sweep", ring.now_us(), dur_us, {"id": i, "steps": 2500})
        ring.add_complete("lnse.adjoint_sweep", ring.now_us(), 2 * dur_us, {"id": i, "steps": 2500})
        ring.add_complete("lnse.descent_update", ring.now_us(), 4000.0 + i, {"id": i})
        ring.add_complete("lnse.grad_adjoint", ring.now_us(), 3 * dur_us,
                          {"id": i, "history_bytes": 218_880_000})
    assert _read("forward_step_us", {}, run) == pytest.approx(0.5 * (1e6 + 3e6) / 2500)
    assert _read("adjoint_step_us", {}, run) == pytest.approx((1e6 + 3e6) / 2500)
    assert _read("descent_update_ms", {}, run) == pytest.approx(4.0015)
    assert _read("history_mb", {}, run) == pytest.approx(218.88)
    # a span that lacks its count reads nothing, never 0
    ring.add_complete("lnse.grad_adjoint", ring.now_us(), 1.0, {"id": 9})
    ring.add_complete("lnse.forward_sweep", ring.now_us(), 1.0, {"id": 9})
    assert _read("history_mb", {}, run) is None
    assert _read("forward_step_us", {}, run) is None


def test_roofline_reader_counts_a_pair_of_steps():
    run = {"traced_steps": 5000, "cfg": {"grid": {"nx": 128, "ny": 57}},
           "device": {"kind": "TPU v5 lite"}}
    least = work_lnse.pair_work(128, 57)["bytes"] / 819e9
    got = _read("descent_step_roofline", {"busy_s": 1.0}, run)  # 400 us a pair
    assert got == pytest.approx(100.0 * least / 400e-6)
    assert 0.0 < got < 100.0
    assert _read("descent_step_roofline", {"busy_s": 1.0}, {**run, "traced_steps": 0}) is None
