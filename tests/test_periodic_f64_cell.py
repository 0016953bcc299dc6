"""What the ``periodic1024_f64.mesh4`` cell rests on, at sizes a CPU can hold
(the suite's process is float64, the configuration's own precision):

* the periodic plain reference in float64 (``benchmark/reference_periodic.py``,
  ``Reference(dtype=numpy.float64)``) against the program's own float64 CPU
  path (FFT, banded solves) and against the layout a TPU runs on four virtual
  devices, on the normal mesh path (split spectra, every float64 product a
  sliced product), after 10 and 64 steps at 16 x 17 and 32 x 33;
* the ``transposes`` and ``exchange_bytes`` of a meshed model's
  ``model.update_n`` span, counted where the step is traced, against the
  all-to-alls the CPU's partitioner places in the compiled chunk, in float64
  here and in float32 in a process of its own, and against a hand count of
  what the flips send;
* the chunk compiled in float64 for a described ``v5e:2x2`` at 256 x 257: the
  flips of the float32 chunk and no more, each a pair of float32 all-to-alls;
* the driver's refusal of a process whose precision is not the
  configuration's;
* the cell's driver through ``run_cell``, the float32 control and the faults a
  run can have, each judged by limits placed by the cell's own rule: the tests
  of ``benchmark/tests/test_correct_periodic_f64.py``, collected here too so
  that tier 1 holds them.
"""

import collections
import importlib.util
import json
import os
import subprocess
import sys
import weakref

import jax
import pytest

from benchmark import check, run
from benchmark.drivers import periodic_interval_f64
from benchmark.ic_periodic import smooth_periodic_fields
from rustpde_mpi_tpu import Navier2D, bases, config
from rustpde_mpi_tpu.parallel.mesh import make_mesh
from rustpde_mpi_tpu.telemetry import FlightRecorder
from rustpde_mpi_tpu.telemetry import tracing as ttracing

from test_periodic_cell import FLIPS, chunk_text, collectives, sums_no_field

pytestmark = pytest.mark.skipif(not config.X64, reason="the cell's precision is float64")

RA, PR, DT, ASPECT = 1e7, 1.0, 5e-4, 1.0  # the configuration's own physics
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path: str):
    spec = importlib.util.spec_from_file_location("correct_periodic_f64", os.path.join(run.ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- the cell's own tests of what decides `correct` ------------------------------

correct = _load("benchmark/tests/test_correct_periodic_f64.py")
files = correct.files
test_sound_run_is_correct_and_float32_in_its_place_is_not = (
    correct.test_sound_run_is_correct_and_float32_in_its_place_is_not
)
test_fault_state_left_unchanged = correct.test_fault_state_left_unchanged
test_fault_interval_cut_to_half_its_steps = correct.test_fault_interval_cut_to_half_its_steps
test_fault_answer_read_back_cut_in_two = correct.test_fault_answer_read_back_cut_in_two


# -- the reference ----------------------------------------------------------------


def model_at(monkeypatch, nx, ny, layout):
    """The periodic model as the CPU runs it (``cpu``), or as four TPU chips
    do, on four virtual devices (``mesh4``: split spectra, sliced products,
    the normal mesh path)."""
    if layout == "cpu":
        return Navier2D.new_periodic(nx, ny, RA, PR, DT, ASPECT, "rbc")
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    # a base cache of its own: a ``Base`` keeps the operators it built, so a
    # CPU-path model of the same size still alive would hand back its float64
    # dots in place of sliced products
    monkeypatch.setattr(bases, "_BASE_CACHE", weakref.WeakValueDictionary())
    monkeypatch.delenv("RUSTPDE_SEP", raising=False)
    model = Navier2D.new_periodic(nx, ny, RA, PR, DT, ASPECT, "rbc", mesh=make_mesh(jax.devices()[:4]))
    assert model.temp_space.bases[0].kind.is_split and model._manual_poisson is None
    assert model._step_products["sliced_products"] > 0 and model._step_products["f64_products"] == 0
    return model


@pytest.mark.parametrize("steps", [10, 64])
@pytest.mark.parametrize("grid", [(16, 17), (32, 33)])
@pytest.mark.parametrize("layout", ["cpu", "mesh4"])
def test_reference_is_pinned_to_the_programs_f64_paths(monkeypatch, layout, grid, steps):
    """Read on the CPU from the cell's initial values at the configuration's
    physics: 3.0e-15..5.0e-14 per field on the CPU path and 9.8e-15..5.4e-13
    on four devices over the four cases each (the float32 reference in the
    same place reads 1e-6); pinned at 1e-11."""
    nx, ny = grid
    model = model_at(monkeypatch, nx, ny, layout)
    initial = smooth_periodic_fields(nx, ny, 2**31 + 11, 0.1, 4, ASPECT)
    for name, values in initial.items():
        model.set_field(name, values)
    model.update_n(steps)
    program = {k: model.get_field(k) for k in check.FIELDS}
    cfg = {"grid": {"nx": nx, "ny": ny},
           "physics": {"ra": RA, "pr": PR, "dt": DT, "aspect": ASPECT}}
    fields = check.reference_fields(periodic_interval_f64.reference_for(cfg), initial, steps)
    gaps = check.field_gaps(program, fields)
    assert max(gaps.values()) < 1e-11, gaps


# -- the refusal ------------------------------------------------------------------


def test_driver_refuses_a_process_of_another_precision(monkeypatch):
    monkeypatch.setattr(config, "X64", False)
    with pytest.raises(RuntimeError, match="RUSTPDE_X64=1"):
        correct.drive(correct.small())


# -- the span's flips against the partitioner's all-to-alls ------------------------

#: what one periodic step's flips send from one device on four, in numbers:
#: a flip of an (r, c) array sends 3 of a device's 4 tiles of
#: ceil(r / 4) x ceil(c / 4).  Six syntheses (``ux``, ``uy``, and the chains'
#: first-axis partials: 2 for the temperature, 1 a velocity) flip the
#: (split rows, ny points) partial, three dealiased analyses the (split rows,
#: ny points) x-analysed product, and the pairs round the three odd
#: x-derivatives an (split rows, ny) pressure and two (split rows, ny - 2)
#: composite fields.  32 x 33: 34 split rows -> 9, 33 -> 9, 31 -> 8.
SENT = {
    (16, 17): 3 * (6 * 5 * 5 + 3 * 5 * 5 + 2 * 5 * 5 + 4 * 5 * 4),
    (32, 33): 3 * (6 * 9 * 9 + 3 * 9 * 9 + 2 * 9 * 9 + 4 * 9 * 8),
}

FLIPS_F32 = """
import json
import jax
from rustpde_mpi_tpu import Navier2D
from rustpde_mpi_tpu.parallel.mesh import make_mesh
from test_periodic_cell import chunk_text, collectives

out = {}
for nx, ny in ((16, 17), (32, 33)):
    model = Navier2D.new_periodic(nx, ny, 1e7, 1.0, 5e-4, 1.0, "rbc", mesh=make_mesh(jax.devices()[:4]))
    out[f"{nx}x{ny}"] = {"flips": model._step_flips, "found": collectives(chunk_text(model))}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def float32_flips():
    env = dict(os.environ, JAX_PLATFORMS="cpu", RUSTPDE_FORCE_TPU_PATH="1", RUSTPDE_X64="0",
               RUSTPDE_COMPILE_CACHE="0", PYTHONPATH=os.pathsep.join([REPO, os.path.join(REPO, "tests")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("RUSTPDE_SEP", None)
    done = subprocess.run([sys.executable, "-W", "ignore", "-c", FLIPS_F32], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def flips_placed(found, nx, ny) -> int:
    """The flips a chunk's step body holds: its all-to-alls, and the
    all-gathers of half a spectral field or more into which this partitioner
    lowers some flips of small arrays (test_periodic_cell.sums_no_field)."""
    half = (nx + 2) * (ny - 2) // 2
    return sum(body and (kind == "all-to-all" or (kind == "all-gather" and size >= half))
               for kind, size, body in found)


@pytest.fixture
def ring(monkeypatch):
    rec = FlightRecorder(capacity=64)
    monkeypatch.setattr(ttracing, "RECORDER", rec)
    monkeypatch.setattr(ttracing, "_ENABLED", True)
    return rec


@pytest.mark.parametrize("grid", [(16, 17), (32, 33)])
def test_span_counts_the_flips_the_partitioner_places(monkeypatch, no_compile_cache, ring,
                                                      float32_flips, grid):
    """The normal mesh path: the compiler places the all-to-alls of the flips
    the step states, and the span counts those flips where the step is
    traced, 15 in both precisions (the hand count of test_periodic_cell's
    FLIPS).  In float64 every one of them is an all-to-all here; in float32
    this partitioner lowers nine of the smaller ones to all-gathers.  What
    they send is the same count of numbers, twice the bytes in float64."""
    nx, ny = grid
    model = model_at(monkeypatch, nx, ny, "mesh4")
    model.update_n(2)
    args = ttracing.spans("model.update_n")[-1][-1]
    found = collectives(chunk_text(model))
    assert args["transposes"] == FLIPS == flips_placed(found, nx, ny)
    assert collections.Counter(kind for kind, _, body in found if body)["all-to-all"] == FLIPS
    assert args["exchange_bytes"] == 8 * SENT[grid]
    f32 = float32_flips[f"{nx}x{ny}"]
    assert f32["flips"] == [FLIPS, 4 * SENT[grid]]
    assert flips_placed(f32["found"], nx, ny) == FLIPS


def test_an_unmeshed_models_span_counts_no_flip(monkeypatch, ring):
    model = model_at(monkeypatch, 16, 17, "cpu")
    model.update_n(2)
    args = ttracing.spans("model.update_n")[-1][-1]
    assert model._step_flips == (0, 0)
    assert not {"transposes", "exchange_bytes"} & set(args)


def test_exchange_reader(ring):
    from benchmark.layer_metrics import exchange_mb_per_step

    run_info = {"traced_dispatches": 2}
    assert exchange_mb_per_step.read({}, run_info) is None
    for sent in (1_500_000, 2_500_000):
        ring.add_complete("model.update_n", ring.now_us(), 700.0,
                          {"id": 1, "parent": None, "exchange_bytes": sent})
    assert exchange_mb_per_step.read({}, run_info) == pytest.approx(2.0)
    # the span of an unmeshed model has no such count
    ring.add_complete("model.update_n", ring.now_us(), 700.0, {"id": 1, "parent": None})
    assert exchange_mb_per_step.read({}, run_info) is None


# -- the chip's own compiler ------------------------------------------------------

def test_the_chips_own_compiler_places_the_float32_flips_in_float64():
    """The meshed float64 chunk compiled for a described v5e:2x2 at 256 x 257
    (where the TPU's compiler makes the program it makes at the cell's 1024 x
    1025), in a process of its own: every sliced product keeps the layout of
    the float32 dot it replaces, so the step body holds the float32 chunk's 15
    flips and nothing else: no all-gather, no all-reduce of half a field.
    The chip has no float64 type, so each flip crosses as the two float32
    words of its float64 array: 30 all-to-alls, in 15 pairs of one result type
    each (read at 1024 x 1025 too: 30, 37 s to compile, 158 MB of code).
    Skipped only where the TPU's library is not installed."""
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no libtpu installed: no compiler for a described v5e")
    import test_periodic_cell

    nx, ny = 256, 257
    env = dict(os.environ, JAX_PLATFORMS="cpu", RUSTPDE_FORCE_TPU_PATH="1", RUSTPDE_COMPILE_CACHE="0",
               RUSTPDE_X64="1", ALLOW_MULTIPLE_LIBTPU_LOAD="1", PYTHONPATH=REPO)
    env.pop("RUSTPDE_SEP", None)
    done = subprocess.run([sys.executable, "-c", test_periodic_cell.V5E_CHUNK, str(nx), str(ny)], env=env,
                          cwd=REPO, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    found = collectives(done.stdout)
    words = collections.Counter(
        line.split(" = ", 1)[1].split(" all-to-all(")[0]
        for line in done.stdout.splitlines() if " all-to-all(" in line and "/while/body/" in line)
    assert sum(words.values()) == 2 * FLIPS and all(n % 2 == 0 for n in words.values()), words
    assert all(kind.startswith("f32[") for kind in words), words
    sums_no_field(done.stdout, nx, ny, flips=2 * FLIPS, gathered_flips=0)
    assert not any(kind == "all-reduce" and body for kind, _, body in found)
