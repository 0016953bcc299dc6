"""What the ``swift512_f32.solo`` cell rests on, at sizes a CPU can hold (the
suite's process is float64; the cell runs float32 on the chip):

* the plain reference with two Fourier axes (``benchmark/reference_swift.py``)
  against the program's float64 FFT path and against its ``matmul`` path;
* the Swift-Hohenberg models as campaign models: the protocol, the registry
  kind, the scanned chunk against single steps, the in-chunk early exit, the
  dt rungs, the sentinel triple, ``compat_key``;
* the spans and counters the cell's readers read: ``model.build`` round
  ``space.build``, the ``gathers`` count, the five stage scopes;
* the cell's driver through ``run_cell`` and the faults a run can have, each
  judged by limits placed by the cell's own rule: the tests of
  ``benchmark/tests/test_correct_swift.py``, collected here so that tier 1
  holds them, and the faults of the step itself;
* ``benchmark/work_swift.py``'s count against the reference's own products,
  the two new readers, and that the base's hooks left the traced programs of
  the cells that stand as they were (a hash of each).
"""

import copy
import hashlib
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_swift, run, work, work_swift
from benchmark.drivers import swift_interval
from benchmark.ic_swift import uniform_noise
from benchmark.layer_metrics import gathers_per_step, swift_step_roofline
from benchmark.reference_swift import Reference
from rustpde_mpi_tpu import Navier2D, Navier2DNonLin, SwiftHohenberg1D, SwiftHohenberg2D, config
from rustpde_mpi_tpu.config import StabilityConfig
from rustpde_mpi_tpu.models.meanfield import MeanFields
from rustpde_mpi_tpu.ops import folded
from rustpde_mpi_tpu.telemetry import FlightRecorder
from rustpde_mpi_tpu.telemetry import tracing as ttracing
from rustpde_mpi_tpu.utils.jit import equations, gathers
from rustpde_mpi_tpu.workloads.registry import (
    build_model,
    build_model_for_key,
    model_kinds,
    validate_campaign_model,
)

needs_x64 = pytest.mark.skipif(not config.X64, reason="the reference is pinned in float64")
R, DT = 0.35, 0.02  # the configuration's own physics; the length follows the grid


def _load(path: str):
    spec = importlib.util.spec_from_file_location("correct_swift", os.path.join(run.ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- the cell's own tests of what decides `correct` ------------------------------

correct_swift = _load("benchmark/tests/test_correct_swift.py")
files = correct_swift.files
test_sound_run_is_correct_and_one_pass_in_its_place_is_not = (
    correct_swift.test_sound_run_is_correct_and_one_pass_in_its_place_is_not
)
test_fault_state_left_unchanged = correct_swift.test_fault_state_left_unchanged
test_fault_interval_cut_to_half_its_steps = correct_swift.test_fault_interval_cut_to_half_its_steps


@pytest.fixture
def ring(monkeypatch):
    """A recorder of the test's own, recording on."""
    rec = FlightRecorder(capacity=512)
    monkeypatch.setattr(ttracing, "RECORDER", rec)
    monkeypatch.setattr(ttracing, "_ENABLED", True)
    return rec


@pytest.fixture
def tpu_path(monkeypatch):
    """The layout and transforms a TPU runs: ``matmul`` on the split spectra."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")


# -- the reference ----------------------------------------------------------------


@needs_x64
@pytest.mark.parametrize("method", ["fft", "matmul"])
@pytest.mark.parametrize("steps", [10, 64])
@pytest.mark.parametrize("grid", [(16, 16), (24, 16), (17, 16)])
def test_reference_is_pinned_to_the_programs_f64_paths(monkeypatch, grid, steps, method):
    """The program's float64 CPU path (FFT, the default there) and its
    ``matmul`` path (the split Re/Im products a TPU runs) against the
    reference's dense products from its own matrices: odd and even extents
    along x, the Nyquist column of the even ny.  Read: 2e-15..5e-15 on the
    field, under 3e-15 on |F|; pinned at 1e-11."""
    nx, ny = grid
    if method == "matmul":
        monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    model = SwiftHohenberg2D(nx, ny, R, DT, nx / 4.0)
    assert model.space.method == method
    initial = uniform_noise(nx, ny, 5, 0.1)
    model.set_theta(initial)
    model.update_n(steps)
    ref = Reference(nx, ny, R, DT, nx / 4.0, dtype=np.float64)
    state = ref.run(ref.initial_state(initial), steps)
    answer = {"theta": model.theta_physical(), "norm": model.get_observables()[0]}
    got = swift_interval.compare(answer, ref, state, {"theta_rel": 1e-11, "norm_rel": 1e-11})
    assert all(value <= limit for value, limit in got.values()), got


def test_reference_transforms_are_numpys():
    nx, ny = 12, 10
    ref = Reference(nx, ny, R, DT, 3.0, dtype=np.float64)
    v = uniform_noise(nx, ny, 2**31 + 9, 1.0)
    want = np.fft.fft(np.fft.rfft(v, axis=1) / ny, axis=0) / nx
    re, im = ref.forward(v)
    np.testing.assert_allclose(re + 1j * im, want, atol=1e-14)
    np.testing.assert_allclose(ref.backward((re, im)), v, atol=1e-13)
    np.testing.assert_array_equal(reference_swift.wavenumbers_c2c(9), np.fft.fftfreq(9, 1 / 9))
    np.testing.assert_array_equal(reference_swift.wavenumbers_c2c(8), np.fft.fftfreq(8, 1 / 8))
    assert ref.norm((re, im)) == pytest.approx(np.sqrt(np.sum(np.abs(want) ** 2)) / want.size)


def test_initial_condition_follows_the_seed():
    a, b = uniform_noise(16, 12, 2**31 + 5, 0.1), uniform_noise(16, 12, 2**31 + 6, 0.1)
    assert a.shape == (16, 12) and np.abs(a).max() <= 0.1 and a.tolist() != b.tolist()
    np.testing.assert_array_equal(a, uniform_noise(16, 12, 2**31 + 5, 0.1))


# -- the models on the normal path --------------------------------------------------


def _model(nx=16, ny=16, r=R, dt=DT, length=4.0):
    return SwiftHohenberg2D(nx, ny, r, dt, length)


@pytest.mark.parametrize("ny", [16, 1])
def test_registry_builds_a_conforming_campaign_model(ny):
    assert "swift" in model_kinds()
    model = build_model("swift", 16, ny, R, 1.0, DT, 4.0, "", True)
    assert isinstance(model, SwiftHohenberg2D if ny > 1 else SwiftHohenberg1D)
    assert validate_campaign_model(model) == []
    assert model.compat_key == ("swift", 16, ny, R, DT, 4.0, np.dtype(config.real_dtype()).name)
    assert model.state._fields == ("temp",) and model.theta is model.state.temp
    assert model.observable_names == ("norm", "energy", "amp", "mean")
    assert build_model_for_key(model.compat_key).compat_key == model.compat_key
    with pytest.raises(ValueError, match="no mesh and no scenario"):
        build_model("swift", 16, ny, R, 1.0, DT, 4.0, "", True, scenario={"coriolis": 1.0})


@pytest.mark.parametrize("dim", [1, 2])
def test_update_n_over_two_buckets_equals_single_updates(dim):
    """``update_n(5)`` is the buckets 2 + 3 of the scanned chunk; five
    ``update()`` calls are the bare step five times."""
    build = (lambda: _model()) if dim == 2 else (lambda: SwiftHohenberg1D(32, 0.2, 0.01, 10.0))
    chunked, single = build(), build()
    if dim == 1:
        chunked.init_random(0.1, seed=3), single.init_random(0.1, seed=3)
    chunked.update_n(5)
    for _ in range(5):
        single.update()
    assert chunked.time == pytest.approx(5 * chunked.dt) and single.time == pytest.approx(chunked.time)
    np.testing.assert_allclose(np.asarray(chunked.theta), np.asarray(single.theta),
                               rtol=1e-12, atol=1e-15)
    for a, b in zip(chunked.get_observables(), single.get_observables()):
        assert a == pytest.approx(b, rel=1e-10, abs=1e-15)
    assert not chunked.exit() and chunked.state_healthy()


def test_in_chunk_early_exit_stops_a_poisoned_state():
    """A NaN in the state: the chunk's first step fails ``_scan_ok`` and the
    other seven take the identity branch; the detector (observable 3) is NaN
    although the step pins the constant mode to zero."""
    model = _model()
    poisoned = model.state._replace(temp=model.theta.at[0, 3, 2].set(jnp.nan))
    _, done = model._step_n(poisoned, 8)
    assert int(done) == 1
    _, done = model._step_n(model.state, 8)
    assert int(done) == 8
    model.theta = poisoned.temp
    model.update_n(8)
    assert np.isnan(model.get_observables()[3]) and model.exit() and not model.state_healthy()


def test_compat_key_differs_in_r_dt_length_and_dtype(monkeypatch):
    base = _model().compat_key
    assert base == ("swift", 16, 16, R, DT, 4.0, np.dtype(config.real_dtype()).name)
    assert _model(r=0.3).compat_key != base
    assert _model(dt=0.01).compat_key != base
    assert _model(length=5.0).compat_key != base
    assert _model().compat_key == base
    other = np.float32 if config.X64 else np.float64
    monkeypatch.setattr(config, "real_dtype", lambda: other)
    assert _model().compat_key == base[:-1] + (np.dtype(other).name,)


def test_set_dt_rebuilds_the_implicit_operator_once_per_rung():
    model, fresh = _model(), _model(dt=0.01)
    model.update_n(4), fresh.update_n(4)  # both from the constructor's seed-0 noise
    start = model.state
    fresh.state = start
    built = model.recompile_count
    model.set_dt(0.01)
    assert model.recompile_count == built + 1
    np.testing.assert_array_equal(np.asarray(model._matl), np.asarray(fresh._matl))
    model.update_n(6), fresh.update_n(6)
    np.testing.assert_array_equal(np.asarray(model.theta), np.asarray(fresh.theta))
    model.set_dt(DT), model.set_dt(0.01)  # both rungs are cached now
    assert model.recompile_count == built + 1
    assert model.compat_key[4] == 0.01


def test_sentinel_triple_of_a_scalar_pde():
    """``(0, energy, |mean|)`` in the base's ``(cfl, ke, div)`` slots: nothing
    advects, so no ceiling trips; the armed chunk's state is the plain chunk's
    bit for bit, and a NaN still stops it."""
    plain, armed = _model(), _model()
    armed.set_stability(StabilityConfig(max_cfl=1.0))
    plain.update_n(12)
    status = armed.update_n(12)
    assert status.finite and status.cfl_ok and not status.pre_divergence
    assert (status.steps_done, status.cfl_max, status.div_max) == (12, 0.0, 0.0)
    assert status.ke == pytest.approx(float(np.mean(plain_before_last(plain) ** 2)), rel=1e-9)
    np.testing.assert_array_equal(np.asarray(armed.theta), np.asarray(plain.theta))
    armed.theta = armed.theta.at[1, 2, 2].set(jnp.nan)
    status = armed.update_n(4)
    assert not status.finite and status.steps_done == 1 and armed.exit()


def plain_before_last(model) -> np.ndarray:
    """The physical field one step before ``model``'s state: the sentinel's
    energy is of the field the last step synthesised."""
    twin = _model()
    twin.update_n(11)
    return twin.theta_physical()


def test_stats_engine_refuses_the_kind():
    from rustpde_mpi_tpu.config import StatsConfig

    with pytest.raises(TypeError, match="'swift'"):
        _model().set_stats(StatsConfig())


# -- spans, counters, scopes ----------------------------------------------------------


def test_build_spans_and_the_gathers_count(ring, tpu_path, monkeypatch):
    """``model.build`` holds ``space.build`` (which builds the matmul path's
    operators) and the entry points' compile seam; ``model.update_n`` counts
    the step's gathers: the two projected columns' conjugate pairing, Re and
    Im, below the circular fold's gate, and the folds' index gathers above it.
    The count is of the traced step: a chunk's length does not multiply it."""
    model = _model(nx=32, ny=32, length=4.0)
    (build,) = ttracing.spans("model.build")
    assert {k: build[4][k] for k in ("nx", "ny", "dtype", "devices")} == {
        "nx": 32, "ny": 32, "dtype": np.dtype(config.real_dtype()).name, "devices": 1}
    (space,) = ttracing.spans("space.build")
    assert space[4]["parent"] == build[4]["id"]
    assert space[4]["shape"] == (32, 32) and space[4]["bases"] == ("fourier_c2c", "fourier_r2c")
    assert "_x_cos" in vars(model.space) and "_y_fwd" in vars(model.space)
    (seam,) = ttracing.spans("model.compile_entry_points")
    assert seam[4]["parent"] == build[4]["id"]
    model.update_n(5), model.update_n(37)
    counts = [s[4]["gathers"] for s in ttracing.spans("model.update_n")]
    assert counts == [4, 4] == [gathers(model._step_cc.jaxpr)] * 2
    assert ttracing.spans("model.update_n")[-1][4]["launches"] == 3  # 37 = 32 + 2 + 3
    names = {eqn.primitive.name for eqn in equations(model._step_cc.jaxpr)}
    assert "gather" in names and "rev" not in names
    monkeypatch.setattr(folded, "_CIRC_MIN_DIM", 8)
    assert _model(nx=32, ny=32, length=4.0)._step_products["gathers"] > 4


def test_an_fft_space_builds_no_operator(ring):
    model = _model()
    assert model.space.method == "fft" and "_x_cos" not in vars(model.space)
    assert len(ttracing.spans("space.build")) == 1


def test_step_stages_are_named_in_the_chunks_text(tpu_path, no_compile_cache):
    model = _model()
    text = model._step_n_jit.lower(model._step_consts, model.state, n=4).compile().as_text()
    scopes = set(re.findall(r'op_name="([^"]*)"', text))
    for stage in ("synthesis", "cubic", "analysis", "implicit", "symmetry"):
        assert any(f"/{stage}/" in s for s in scopes), stage


def test_readers_of_the_new_metrics(ring, tpu_path):
    model = _model()
    model.update_n(4), model.update_n(4)
    run_info = {"traced_dispatches": 2, "traced_steps": 8, "device": {"kind": "TPU v5 lite"},
                "cfg": {"grid": {"nx": 512, "ny": 512}}}
    assert gathers_per_step.read(None, run_info) == 4.0
    # a program whose span carries no such count (the parent commit) reads nothing
    ring.clear()
    del model._step_products["gathers"]
    model.update_n(4), model.update_n(4)
    assert gathers_per_step.read(None, run_info) is None
    # 1.62e9 flops a step at 512 x 512: 8.2 us at the one-pass peak
    w = work_swift.step_work(512, 512)
    assert w["flops"] == 2 * (2 * 512 * 512 * 514 + 8 * 512 * 512 * 257) and w["products"] == 12
    least = work.roofline(w, "TPU v5 lite", 1.0)
    assert least["bound"] == "compute" and least["share"] == pytest.approx(8.21e-6, rel=1e-3)
    share = swift_step_roofline.read({"busy_s": 8 * 164e-6}, run_info)
    assert share == pytest.approx(100.0 * least["share"] / 164e-6) and 4.9 < share < 5.1
    assert swift_step_roofline.read({"busy_s": 1.0}, {"traced_steps": 0}) is None


def test_work_count_is_the_references_own_products():
    """``work_swift.py`` counts the unfolded dense step as the reference runs
    it: the ``dot_general``s of the reference's traced step, 2 M N K each."""
    nx, ny = 16, 16
    ref = Reference(nx, ny, R, DT, 4.0)
    consts = {k: jnp.asarray(v, np.float32) for k, v in ref._host.items()}
    state = tuple(jnp.zeros((nx, ref.my), np.float32) for _ in range(2))
    traced = jax.make_jaxpr(
        lambda c, s: reference_swift._run(c, s, jnp.int32(3), (DT, True), "f32"))(consts, state)
    flops = products = 0
    for eqn in equations(traced.jaxpr):
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            a, b = (v.aval.shape for v in eqn.invars)
            k = a[contract[0]]  # (M, K) by (K, N): 2 M N K
            flops += 2 * (a[0] * a[1] // k) * (b[0] * b[1] // k) * k
            products += 1
    want = work_swift.step_work(nx, ny)
    assert (flops, products) == (want["flops"], want["products"]) == (55296.0, 12)


# -- the cell through run_cell ----------------------------------------------------------


def test_rehearsal_of_the_cell_is_correct():
    """What ``selfcheck --rehearse`` does to a cell (it stops at the float64
    cell before it reaches this one): 17 x 17, dt 2e-3, 16 steps an interval,
    the cell's own limits."""
    manifest, cell, cfg, traffic = run.load_cell(correct_swift.CELL)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg["grid"] = {"nx": 17, "ny": 17}
    cfg["physics"].update(ra=1e5, dt=2e-3)
    traffic["steps_per_interval"] = 16
    res = run.run_cell(manifest, cell, cfg, traffic, seed=2**31 + 5, seconds=0.3, trace=0,
                       log=lambda line: None)
    assert res["correct"] and res["failed"] == 0, res


def test_fault_cubic_term_left_out(monkeypatch, files):
    monkeypatch.setattr(SwiftHohenberg2D, "_analysis",
                        lambda self: lambda cube: 0.0 * self.space.forward(cube))
    res = correct_swift.drive(files)
    assert not res["correct"], res["compared"]


def test_fault_zero_mode_pin_left_out(monkeypatch, files):
    """The field is off by the mean the pin would have removed, and every
    interval of the window fails on its ``mean`` observable."""
    monkeypatch.setattr(SwiftHohenberg2D, "_symmetry",
                        lambda self: self.space.enforce_hermitian_x)
    res = correct_swift.drive(files)
    assert not res["correct"], res["compared"]
    assert res["failed"] == res["attempted"] > 0


def test_fault_hermitian_projection_left_out(monkeypatch, files):
    """From a spectrum whose ky = 0 column has an anti-Hermitian part in a
    linearly unstable pair of modes: the projection removes it in the first
    step (program and reference agree), without it the part grows by 1/matl a
    step where no physical value shows it, and |F| gives it away."""
    cfg, traffic = files[2], files[3]
    g, ph, n = cfg["grid"], cfg["physics"], traffic["steps_per_interval"]
    ref = swift_interval.reference_for(cfg)
    re, im = ref.initial_state(uniform_noise(g["nx"], g["ny"], 7, 0.1))
    k = int(ph["length"])  # |k| / length = 1: the band's centre
    re[k, 0] += 1e-2
    re[-k, 0] -= 1e-2
    want = ref.run((re, im), n)

    def answer():
        model = SwiftHohenberg2D(g["nx"], g["ny"], ph["r"], ph["dt"], ph["length"])
        model.theta = jnp.asarray(np.stack([re, im]), config.real_dtype())
        model.update_n(n)
        return {"theta": model.theta_physical(), "norm": model.get_observables()[0]}

    sound = swift_interval.compare(answer(), ref, want, traffic["check"])
    assert all(value <= limit for value, limit in sound.values()), sound
    monkeypatch.setattr(SwiftHohenberg2D, "_symmetry", lambda self: self.space.pin_zero_mode)
    broken = swift_interval.compare(answer(), ref, want, traffic["check"])
    assert broken["norm_rel"][0] > broken["norm_rel"][1], broken


# -- the cells that stand ------------------------------------------------------------


def _digest(jaxpr) -> str:
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]


@needs_x64
def test_traced_programs_of_the_standing_cells_are_as_they_were():
    """``CampaignModelBase``'s hooks for a model that is no DNS changed no
    equation of the models that are: the traced step of ``Navier2D`` confined
    and periodic and ``Navier2DNonLin``'s two sweeps print the text they
    printed at the parent of PR 37 (float64, the CPU path).  A change to one of
    those steps changes its line here, on purpose."""
    got = {}
    model = Navier2D.new_confined(17, 17, 1e5, 1.0, 0.01, 1.0, "rbc")
    got["confined 17 x 17"] = _digest(model._step_cc.jaxpr)
    model = Navier2D.new_periodic(16, 17, 1e5, 1.0, 0.01, 1.0, "rbc")
    got["periodic 16 x 17"] = _digest(model._step_cc.jaxpr)
    model = Navier2DNonLin.new_confined(14, 11, 1e5, 1.0, 0.01, 1.0, "rbc",
                                        mean=MeanFields.new_rbc(14, 11, False))
    start = model.state
    forward = jax.make_jaxpr(lambda c, s: model._fwd_n_jit(c, s, n=2))(model._fwd_consts, start)
    got["forward sweep 14 x 11"] = _digest(forward.jaxpr)
    after, history = model._fwd_n(start, 2)
    adjoint = jax.make_jaxpr(lambda c, s, h: model._adj_n_jit(c, s, h))(
        model._adj_consts, after, history)
    got["adjoint sweep 14 x 11"] = _digest(adjoint.jaxpr)
    assert got == {
        "confined 17 x 17": "cd373a50a276e61c",
        "periodic 16 x 17": "d3a581585a8fe894",
        "forward sweep 14 x 11": "ff17597241173404",
        "adjoint sweep 14 x 11": "ed1a0afc1d420526",
    }
