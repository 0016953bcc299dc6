"""``test_correct.py``'s questions, asked of ``lnse_opt128_f32.loop``:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_correct_lnse.py -q
    python3 -m pytest benchmark/tests/test_correct_lnse.py -q -k own_size   (on the chip)

* a sound run, driven past the harness's look for a chip at 32 x 17, is
  correct;
* the control (the plain reference in the program's place, every matrix
  product in three bfloat16 passes) goes through ``run_cell``'s own comparison
  and comes out not correct: at the cell's own size against its own limits
  where the chip is there (``own_size``: PERF.md section 2's readings), and on
  the CPU at 32 x 17 against limits placed between the two readings;
* five broken paths come out not correct: the trajectory's terms left out of
  the adjoint sweep (the linear adjoint in the nonlinear one's place), the
  trajectory consumed first to last, the adjoint sweep cut to half its steps,
  an iteration that hands back the initial condition it was given, and an
  update that does not keep the energy.
"""

from __future__ import annotations

import copy
import math
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["RUSTPDE_X64"] = "0"
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                 ".jax_cache"),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from benchmark import run  # noqa: E402
from benchmark.drivers import descent_loop  # noqa: E402
from benchmark.meter import CompileMeter  # noqa: E402

CELL = "lnse_opt128_f32.loop"
CONTROL = "bf16_3x"


def small():
    """The cell's own files and limits, cut to 32 x 17, a base state after 200
    steps and sweeps of 256 steps."""
    manifest, cell, cfg, traffic = run.load_cell(CELL)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg["grid"] = {"nx": 32, "ny": 17}
    cfg["optimisation"]["base_time"] = 4.0
    traffic["steps_per_interval"] = 256
    return manifest, cell, cfg, traffic


def drive(seconds: float = 0.05, seed: int = 2**31 + 77, files=None) -> dict:
    manifest, cell, cfg, traffic = files or small()
    return run.run_cell(manifest, cell, cfg, traffic, seed, seconds, trace=0,
                        log=lambda line: None)


def test_sound_run_is_correct():
    res = drive()
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "steps_per_s"}
    assert set(res["compared"]) == set(run.load_cell(CELL)[3]["check"])


def test_window_counts_both_sweeps_and_whole_iterations():
    files = small()
    drv = descent_loop.Driver(run.Context(files[2], files[3], 5, 0.0, lambda line: None,
                                          CompileMeter(), run.Tracer(False)))
    drv.setup()
    win = drv.window()
    assert win["attempted"] == win["dispatches"] == 1 and win["steps"] == 2 * 256
    assert win["metrics"]["steps_per_s"] == pytest.approx(512 / win["window_s"])
    assert win["compiles"]["compiled"] == 0  # the warm-up met both programs


def control_in_the_programs_place(monkeypatch) -> None:
    """From here on the driver's ``release`` hands the comparison the
    reference's own iteration in three bfloat16 passes instead of the
    program's."""

    def release(self):
        out = descent_loop.reference_for(self.cfg, self.base).iteration(
            self.initial, self.n, self.beta1, self.beta2, self.alpha_0, CONTROL)
        self.answer = {k: v for k, v in out.items() if k not in ("state", "history")}
        self.answer["energy_rel"] = self.energy_rel
        self.model = self.target = self.compared_state = self.compared_step = None

    monkeypatch.setattr(descent_loop.Driver, "release", release)


@pytest.mark.parametrize("seed", [2**31 + 501, 502, 503])
def test_control_is_not_correct_at_the_cells_own_size(monkeypatch, seed):
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the cell's own size and limits are a chip reading")
    control_in_the_programs_place(monkeypatch)
    res = drive(seconds=1.0, seed=seed, files=run.load_cell(CELL))
    print(f"control {CELL} seed {seed}: {res['compared']}")
    assert not res["correct"], res["compared"]


def test_control_is_not_correct_at_a_size_a_test_can_hold(monkeypatch):
    """32 x 17: the control reads at least three times what sound runs read in
    one number at least, and with that number's limit placed between the two
    readings ``run_cell`` calls the program correct and the control not."""
    files = small()
    seeds = (1, 2, 2**31 + 3)
    sound = [drive(0.05, s, files)["compared"] for s in seeds]
    with monkeypatch.context() as patch:
        control_in_the_programs_place(patch)
        low = [drive(0.05, s, files)["compared"] for s in seeds]
    keys = [k for k in sound[0] if k != "energy_rel"]
    lower = {k: max(r[k]["value"] for r in sound) for k in keys}
    upper = {k: min(r[k]["value"] for r in low) for k in keys}
    apart = [k for k in keys if upper[k] >= 3.0 * lower[k]]
    assert apart, (lower, upper)
    traffic = files[3]
    for k in keys:
        traffic["check"][k] = math.sqrt(lower[k] * upper[k]) if k in apart else math.inf
    assert drive(0.05, seeds[0], files)["correct"]
    control_in_the_programs_place(monkeypatch)
    res = drive(0.05, seeds[0], files)
    assert not res["correct"], res["compared"]


def test_fault_history_terms_left_out(monkeypatch):
    """The linear adjoint in the nonlinear one's place: the adjoint step sees a
    trajectory of zeros."""
    from rustpde_mpi_tpu import Navier2DNonLin

    sound = Navier2DNonLin._make_adjoint_step

    def linear(self):
        step = sound(self)
        return lambda s, history=None: step(s, history=jax.tree.map(jnp.zeros_like, history))

    monkeypatch.setattr(Navier2DNonLin, "_make_adjoint_step", linear)
    res = drive()
    assert not res["correct"], res["compared"]
    assert res["compared"]["fun_val_rel"]["value"] <= res["compared"]["fun_val_rel"]["limit"]


def test_fault_history_consumed_first_to_last(monkeypatch):
    from rustpde_mpi_tpu import Navier2DNonLin

    sound = Navier2DNonLin._adjoint_sweep

    def forwards(self, n, history):
        return sound(self, n, tuple(h[::-1] for h in history))

    monkeypatch.setattr(Navier2DNonLin, "_adjoint_sweep", forwards)
    res = drive()
    assert not res["correct"], res["compared"]


def test_fault_adjoint_sweep_cut_to_half(monkeypatch):
    from rustpde_mpi_tpu import Navier2DNonLin

    sound = Navier2DNonLin._adjoint_sweep

    def half(self, n, history):
        return sound(self, n // 2, tuple(h[n - n // 2:] for h in history))

    monkeypatch.setattr(Navier2DNonLin, "_adjoint_sweep", half)
    res = drive()
    assert not res["correct"], res["compared"]


def test_fault_iteration_returns_the_old_initial_condition(monkeypatch):
    from rustpde_mpi_tpu.models import opt_routines

    monkeypatch.setattr(opt_routines, "steepest_descent_energy_constrained",
                        lambda u, v, t, *rest: (u, v, t))
    res = drive()
    assert not res["correct"], res["compared"]
    gaps = res["compared"]
    assert all(gaps[f"grad_{k}_rel"]["value"] <= gaps[f"grad_{k}_rel"]["limit"]
               for k in descent_loop.FIELDS)  # the sweeps were sound


def test_fault_update_does_not_keep_the_energy(monkeypatch):
    """A plain descent step in the constrained one's place: ``energy_rel``
    reads hundreds of times its limit."""
    from rustpde_mpi_tpu.models import opt_routines

    monkeypatch.setattr(opt_routines, "steepest_descent_energy_constrained",
                        lambda u, v, t, gu, gv, gt, b1, b2, alpha: (u + gu, v + gv, t + gt))
    res = drive()
    assert not res["correct"], res["compared"]
    gap = res["compared"]["energy_rel"]
    assert gap["value"] > 100.0 * gap["limit"], gap
