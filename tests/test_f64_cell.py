"""What the ``rbc513_f64.solo`` cell rests on, at sizes a CPU can hold (the
suite's process is float64, the configuration's own precision):

* the plain reference in float64 (``benchmark/reference.py``,
  ``Reference(dtype=numpy.float64)``) against the program's own float64 CPU
  path after 10 and 64 steps at 17 x 17 and 24 x 21;
* the cell's driver through ``run_cell``, the float32 control and the faults a
  run can have, each judged by limits placed by the cell's own rule: the tests
  of ``benchmark/tests/test_correct_f64.py``, collected here too so that tier 1
  holds them;
* the f64 hybrid (``RUSTPDE_F64_HYBRID=1``) in the program's place, in a
  process of its own: its reading beside the limit, whichever way it falls;
* the driver's refusal of a process whose precision is not the
  configuration's;
* the ``sliced_products`` / ``int8_products`` / ``f64_products`` /
  ``f32_products`` counts of the ``model.update_n`` span against a hand count
  of one confined step's products, the readers of ``f64_products`` and of
  ``sliced_f64_multiplies``, and that ``RUSTPDE_SOLVE_PRECISION`` leaves a
  float64 step as it is.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import check, run
from benchmark.drivers import interval_f64
from benchmark.ic import smooth_fields
from benchmark.layer_metrics import f64_products_per_step, int8_blocks_per_step, sliced_f64_multiplies_per_step
from rustpde_mpi_tpu import Navier2D, config
from rustpde_mpi_tpu.ops import folded as folded_ops
from rustpde_mpi_tpu.telemetry import FlightRecorder
from rustpde_mpi_tpu.telemetry import tracing as ttracing
from rustpde_mpi_tpu.utils.jit import dot_generals_by_operand

pytestmark = pytest.mark.skipif(not config.X64, reason="the cell's precision is float64")

RA, PR, DT, ASPECT = 1e5, 1.0, 0.01, 1.0  # the configuration's own physics


def _load(path: str):
    spec = importlib.util.spec_from_file_location("correct_f64", os.path.join(run.ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- the cell's own tests of what decides `correct` ------------------------------

correct_f64 = _load("benchmark/tests/test_correct_f64.py")
files = correct_f64.files
test_sound_run_is_correct_and_float32_in_its_place_is_not = (
    correct_f64.test_sound_run_is_correct_and_float32_in_its_place_is_not
)
test_fault_state_left_unchanged = correct_f64.test_fault_state_left_unchanged
test_fault_interval_cut_to_half_its_steps = correct_f64.test_fault_interval_cut_to_half_its_steps


# -- the reference ----------------------------------------------------------------


@pytest.mark.parametrize("steps", [10, 64])
@pytest.mark.parametrize("grid", [(17, 17), (24, 21)])
def test_reference_is_pinned_to_the_programs_f64_path(grid, steps):
    """Read on the CPU: 1.2e-14..6.1e-13 per field over the four cases (the
    float32 reference in the same place reads 4e-7..4e-6); pinned at 1e-11."""
    nx, ny = grid
    model = Navier2D.new_confined(nx, ny, RA, PR, DT, ASPECT, "rbc")
    initial = smooth_fields(nx, ny, 7, 0.1, 4)
    for name, values in initial.items():
        model.set_field(name, values)
    model.update_n(steps)
    program = {k: model.get_field(k) for k in check.FIELDS}
    cfg = {"grid": {"nx": nx, "ny": ny},
           "physics": {"ra": RA, "pr": PR, "dt": DT, "aspect": ASPECT}}
    fields = check.reference_fields(interval_f64.reference_for(cfg), initial, steps)
    gaps = check.field_gaps(program, fields)
    assert max(gaps.values()) < 1e-11, gaps


# -- the hybrid, in a process of its own -------------------------------------------

HYBRID = """
import json, sys
sys.path.insert(0, {root!r})
sys.path.insert(0, {tests!r})
import test_correct_f64 as cell
from rustpde_mpi_tpu.telemetry import tracing
files = cell.small()
res = cell.drive(files)
args = tracing.spans("model.update_n")[-1][-1]
print(json.dumps({{"compared": res["compared"], "f64_products": args["f64_products"],
                  "f32_products": args["f32_products"], "sliced_products": args["sliced_products"]}}))
"""


def test_hybrid_reads_beside_the_limit(files):
    """``RUSTPDE_F64_HYBRID=1`` is the one float64 lever the program has
    (float32 operators for the convection transforms).  The cell runs without
    it; with it the run is the A/B the cell is the yardstick of, and its
    reading is reported beside the limit whichever way it falls.  What is held
    is that the lever moved products out of float64 and that the run finished."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", RUSTPDE_X64="1", RUSTPDE_F64_HYBRID="1",
               RUSTPDE_FORCE_TPU_PATH="1")
    code = HYBRID.format(root=run.ROOT, tests=os.path.join(run.ROOT, "benchmark", "tests"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         cwd=run.ROOT, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    limits = files[3]["check"]
    for key, pair in got["compared"].items():
        side = "under" if pair["value"] <= limits[key] else "over"
        print(f"hybrid at 17 x 17, 64 steps: {key} = {pair['value']:.3e}, {side} the limit "
              f"{limits[key]:.3e} the cell's rule places here")
        assert np.isfinite(pair["value"])
    # the three convection chains and the synthesis of ux, uy: 20 transforms
    # (a velocity's x-synthesis serves ux / uy and its own chain's d/dy, PR 36),
    # in float32 each one plain product below ops/folded.py's fold gate, of the
    # forced TPU path's 80 dot_generals at this size (folded they are 40 of
    # 100; the float64 operators fold at every size, and on the TPU path each
    # of theirs is a sliced product: ops/folded.py)
    assert (got["f64_products"], got["f32_products"], got["sliced_products"]) == (0, 20, 60), got


# -- the refusal ------------------------------------------------------------------


def test_driver_refuses_a_process_of_another_precision(monkeypatch, files):
    monkeypatch.setattr(config, "X64", False)
    with pytest.raises(RuntimeError, match="RUSTPDE_X64=1"):
        correct_f64.drive(files)


# -- the span's counters -----------------------------------------------------------

#: one-axis operator applications of one confined step that are dense products
#: at 33 x 33 on the TPU path (``_make_step``, read stage by stage), each two
#: ``dot_general``s where it is parity-folded: one per parity block.  Stencils (``to_ortho``, the
#: Helmholtz preconditions) are shifted adds and count nothing.  At 513 x 513
#: the four derivative operators (the pressure gradient's and the
#: divergence's) are cut into two trapezoid strips a block (ops/folded.py,
#: blocks of 192 rows and more): 84 (CPU count, PR 36; 88 while a velocity's
#: x-synthesis was stated twice, which the compiler merged: PR 33 counted 84
#: half-products in the chip's compiled text for the 88 traced).
HAND_COUNT = {
    "synthesis of ux, uy: 2 fields x 2 axes": 4,
    "3 convection chains x (2 derivative syntheses + 1 dealiased analysis) x 2 axes": 18,
    "of them the x-synthesis under d/dy velx and d/dy vely: the one ux and uy took": -2,
    "3 Helmholtz solves x 1 dense inverse per axis": 6,
    "fast-diagonalisation Poisson: 2 modal maps in, 2 out": 4,
    "pressure gradient: d/dx in momentum_x, d/dy in momentum_y": 2,
    "divergence: d/dx of velx, d/dy of vely": 2,
    "projection: the fused projection-gradient operator, 2 velocities x 2 axes": 4,
}


@pytest.fixture
def ring(monkeypatch):
    rec = FlightRecorder(capacity=64)
    monkeypatch.setattr(ttracing, "RECORDER", rec)
    monkeypatch.setattr(ttracing, "_ENABLED", True)
    return rec


#: of them the transforms between physical and spectral space, from
#: ops/folded.py's fold gate up a parity fold each: two products and one array
#: reverse.  Below the module's gates every entry above is one plain product.
TRANSFORMS = 4 + 18 - 2


@pytest.mark.parametrize("folded", [True, False])
def test_span_counts_one_steps_products_by_operand_type(monkeypatch, ring, fold_gate, folded):
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    fold_gate(4 if folded else fold_gate.NEVER)
    products = sum(HAND_COUNT.values()) * (2 if folded else 1)
    assert products == (76 if folded else 38)
    model = Navier2D.new_confined(33, 33, RA, PR, DT, ASPECT, "rbc")
    model.init_random(0.1, seed=0)
    model.update_n(4)
    args = ttracing.spans("model.update_n")[-1][-1]
    # on the TPU path every float64 product is a sliced product (ops/folded.py):
    # none is left to XLA's float64 dot; a parity fold's two halves are one
    # sliced product, which states one int8 product for each slice pair and
    # streams that many operator slices for each of its operators
    assert args["sliced_products"] == products
    calls = products // 2 if folded else products
    assert args["int8_products"] == len(folded_ops.PAIRS) * calls
    assert args["int8_blocks"] == len(folded_ops.PAIRS) * products
    assert args["f64_products"] == 0
    assert args["f32_products"] == 0
    assert args["sliced_f64_multiplies"] == 0
    assert args["reverses"] == (TRANSFORMS if folded else 0)
    # counted in the traced step, once: the chunk's scan does not multiply it
    model.update_n(8)
    assert ttracing.spans("model.update_n")[-1][-1]["sliced_products"] == products
    assert f64_products_per_step.read({}, {"traced_dispatches": 2}) == 0.0
    assert f64_products_per_step.read({}, {"traced_dispatches": 3}) is None
    # a span without the count (the parent commit's) reads nothing
    ring.add_complete("model.update_n", ring.now_us(), 5.0, {"id": 9, "parent": None, "steps": 8})
    assert f64_products_per_step.read({}, {"traced_dispatches": 1}) is None


def test_sliced_f64_multiplies_reader(monkeypatch, ring):
    """``sliced_f64_multiplies_per_step`` reads the mean of the span's count
    over the traced dispatches: 0 where a step's sliced products state no
    float64 multiply; nothing where a traced span lacks the count (the
    parent commit's), or where the ring holds fewer spans than were traced."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    model = Navier2D.new_confined(17, 17, RA, PR, DT, ASPECT, "rbc")
    model.init_random(0.1, seed=0)
    model.update_n(2)
    model.update_n(4)
    assert model._step_products["sliced_products"] > 0
    assert sliced_f64_multiplies_per_step.read({}, {"traced_dispatches": 2}) == 0.0
    assert sliced_f64_multiplies_per_step.read({}, {"traced_dispatches": 3}) is None
    args = {"parent": None, "steps": 8, "sliced_f64_multiplies": 126}
    ring.add_complete("model.update_n", ring.now_us(), 5.0, dict(args, id=7))
    assert sliced_f64_multiplies_per_step.read({}, {"traced_dispatches": 2}) == 63.0
    ring.add_complete("model.update_n", ring.now_us(), 5.0, {"id": 9, "parent": None, "steps": 8})
    assert sliced_f64_multiplies_per_step.read({}, {"traced_dispatches": 1}) is None


def test_int8_blocks_reader(monkeypatch, ring):
    """``int8_blocks_per_step`` reads the mean of the span's count over the
    traced dispatches: 45 operator slices for each sliced operator of the
    step; nothing where a traced span lacks the count (the parent commit's),
    or where the ring holds fewer spans than were traced."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    model = Navier2D.new_confined(17, 17, RA, PR, DT, ASPECT, "rbc")
    model.init_random(0.1, seed=0)
    model.update_n(2)
    model.update_n(4)
    blocks = 45 * model._step_products["sliced_products"]
    assert blocks > 0
    assert int8_blocks_per_step.read({}, {"traced_dispatches": 2}) == blocks
    assert int8_blocks_per_step.read({}, {"traced_dispatches": 3}) is None
    args = {"parent": None, "steps": 8, "int8_blocks": blocks + 2}
    ring.add_complete("model.update_n", ring.now_us(), 5.0, dict(args, id=7))
    assert int8_blocks_per_step.read({}, {"traced_dispatches": 2}) == blocks + 1
    ring.add_complete("model.update_n", ring.now_us(), 5.0, {"id": 9, "parent": None, "steps": 8})
    assert int8_blocks_per_step.read({}, {"traced_dispatches": 1}) is None


def test_products_are_counted_inside_nested_programs():
    import jax
    import jax.numpy as jnp

    a64, a32 = jnp.ones((4, 4), jnp.float64), jnp.ones((4, 4), jnp.float32)

    def fn(x, y):
        inner = jax.jit(lambda v: v @ a64)(x)
        loop = jax.lax.fori_loop(0, 3, lambda _, v: v @ a64, inner)
        mixed = jax.lax.cond(x[0, 0] > 0, lambda v: v @ a32, lambda v: a32 @ v, y)
        return loop, mixed, y.astype(jnp.float64) @ a64

    counts = dot_generals_by_operand(jax.make_jaxpr(fn)(a64, a32).jaxpr)
    assert counts == {"float64": 3, "float32": 2}


def test_solve_precision_leaves_a_float64_step_as_it_is(monkeypatch):
    """``RUSTPDE_SOLVE_PRECISION`` scopes a lower matmul precision to the four
    implicit solves of a float32 model; float64 never downgrades."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")

    def lowered() -> str:
        model = Navier2D.new_confined(17, 17, RA, PR, DT, ASPECT, "rbc")
        return model._step_n_jit.lower(model._step_consts, model.state, n=4).as_text()

    plain = lowered()
    monkeypatch.setenv("RUSTPDE_SOLVE_PRECISION", "high")
    assert lowered() == plain
    assert "f64" in plain and "precision = [HIGHEST, HIGHEST]" in plain
