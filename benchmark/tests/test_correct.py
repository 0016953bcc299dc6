"""What decides ``correct``, held to account:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q      (sizes a test can hold)
    python3 -m pytest benchmark/tests -q -k own_size            (on the chip: the cells' own sizes)

* every cell's run, driven past the harness's look for a chip, is correct;
* the reference stays pinned to the program's float64 CPU path (1e-9);
* the control -- the reference put in the program's place with every matrix
  product in three bfloat16 passes (``high``, the nearest precision below the
  configuration's ``highest``) -- goes through ``run_cell``'s own comparison
  and comes out not correct: at the cell's own size against the cell's own
  limits where a TPU is there (``own_size``; the readings of PERF.md, section
  2, are this test's), and on the CPU at 33 x 33 against limits placed between
  the two readings by the same rule, since the operators' conditioning, and
  with it every rounding error, grows with the grid;
* the timed path broken underneath -- a step that leaves the state unchanged,
  half of the batch left out, an answer altered where it is produced -- comes
  out as not correct, once for each fault a cell can have.
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
import pytest  # noqa: E402

from benchmark import check, run  # noqa: E402
from benchmark.drivers import ensemble, interval  # noqa: E402
from benchmark.reference import random_fields  # noqa: E402

CELLS = ["rbc513_f32.solo", "swarm129_f32.batch"]
CONTROL = "bf16_3x"


def small(name: str, n: int = 17):
    """The cell's own files and limits, cut to n x n and short intervals."""
    manifest, cell, cfg, traffic = run.load_cell(name)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg["grid"] = {"nx": n, "ny": n}
    cfg["physics"].update(ra=1e5, dt=2e-3)
    traffic["steps_per_interval"] = 128
    traffic["members"] = 4
    return manifest, cell, cfg, traffic


def drive(name: str, seconds: float = 1.5, seed: int = 2**31 + 77, files=None) -> dict:
    manifest, cell, cfg, traffic = files or small(name)
    return run.run_cell(manifest, cell, cfg, traffic, seed, seconds, trace=0,
                        log=lambda line: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = drive(name)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(res)[-1] == "compared"


def test_reference_is_pinned_to_the_programs_f64_path():
    """One more witness for the reference's semantics: the program's own CPU
    path in float64 (FFT transforms, banded solves) agrees to rounding."""
    import subprocess
    import sys

    code = (
        "import os, sys; os.environ['RUSTPDE_X64']='1'; sys.path.insert(0, '.');"
        "import numpy as np; from rustpde_mpi_tpu import Navier2D;"
        "from benchmark.reference import Reference, random_fields;"
        "m = Navier2D.new_confined(17, 17, 1e6, 1.0, 2e-3, 1.0, 'rbc'); m.init_random(0.1, seed=5);"
        "m.update_n(20); ref = Reference(17, 17, 1e6, 1.0, 2e-3, dtype=np.float64);"
        "out = ref.run(ref.initial_state(random_fields((17, 17), 0.1, 5)), 20);"
        "g = max(np.linalg.norm(m.get_field(k) - ref.backward(k, out[i])) / np.linalg.norm(m.get_field(k))"
        " for i, k in enumerate(('temp', 'velx', 'vely', 'pres')));"
        "print(g)"
    )
    env = dict(os.environ, JAX_ENABLE_X64="1", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         cwd=run.ROOT, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert float(out.stdout.strip().splitlines()[-1]) < 1e-9


# -- the control, through run_cell's own comparison ------------------------------


def control_in_the_programs_place(monkeypatch) -> None:
    """From here on a driver's ``release`` hands the comparison the reference's
    own answer in three bfloat16 passes instead of the program's."""

    def solo(self):
        ref = check.reference_for(self.cfg)
        self.answer = check.reference_fields(ref, self.initial, self.n, CONTROL)
        self.model = self.compared_state = None

    def batch(self):
        g, ref = self.cfg["grid"], check.reference_for(self.cfg)
        self.answer = [
            check.reference_fields(
                ref, random_fields((g["nx"], g["ny"]), self.traffic["amp"], s), self.n, CONTROL)
            for s in self.seeds
        ]
        self.ens = self.compared_state = None

    monkeypatch.setattr(interval.Driver, "release", solo)
    monkeypatch.setattr(ensemble.Driver, "release", batch)


@pytest.mark.parametrize("seed", [2**31 + 501, 502, 503])
@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct_at_the_cells_own_size(monkeypatch, name, seed):
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the cell's own size and limits are a chip reading")
    control_in_the_programs_place(monkeypatch)
    res = drive(name, seconds=1.0, seed=seed, files=run.load_cell(name))
    print(f"control {name} seed {seed}: {res['compared']}")
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct_at_a_size_a_test_can_hold(monkeypatch, name):
    """33 x 33: the control reads at least three times what sound runs read in
    one number at least, and with that number's limit placed between the two
    readings ``run_cell`` calls the program correct and the control not."""
    files = small(name, 33)
    seeds = (1, 2, 2**31 + 3)
    sound = [drive(name, 0.2, s, files)["compared"] for s in seeds]
    with monkeypatch.context() as patch:
        control_in_the_programs_place(patch)
        low = [drive(name, 0.2, s, files)["compared"] for s in seeds]
    keys = [k for k in sound[0] if k.endswith("_rel")]
    lower = {k: max(r[k]["value"] for r in sound) for k in keys}
    upper = {k: min(r[k]["value"] for r in low) for k in keys}
    apart = [k for k in keys if upper[k] >= 3.0 * lower[k]]
    assert apart, (lower, upper)
    traffic = files[3]
    for k in keys:
        traffic["check"][k] = math.sqrt(lower[k] * upper[k]) if k in apart else math.inf
    assert drive(name, 0.2, seeds[0], files)["correct"]
    control_in_the_programs_place(monkeypatch)
    res = drive(name, 0.2, seeds[0], files)
    assert not res["correct"], res["compared"]


# -- the timed path broken underneath ------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_fault_state_left_unchanged(monkeypatch, name):
    from rustpde_mpi_tpu.models.ensemble import NavierEnsemble
    from rustpde_mpi_tpu.models.navier import Navier2D

    monkeypatch.setattr(Navier2D, "update_n", lambda self, n: None)
    monkeypatch.setattr(NavierEnsemble, "update_n", lambda self, n: None)
    res = drive(name)
    assert not res["correct"], res["compared"]


def test_fault_half_of_the_batch_left_out(monkeypatch):
    from rustpde_mpi_tpu.models.ensemble import NavierEnsemble

    sound = NavierEnsemble.update_n

    def half(self, n):
        before = self.state
        sound(self, n)
        k = self.k // 2
        self.state = jax.tree.map(lambda new, old: new.at[k:].set(old[k:]), self.state, before)

    monkeypatch.setattr(NavierEnsemble, "update_n", half)
    res = drive("swarm129_f32.batch")
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_fault_answer_altered_where_it_is_produced(monkeypatch, name):
    from rustpde_mpi_tpu.models.ensemble import NavierEnsemble
    from rustpde_mpi_tpu.models.navier import Navier2D

    for cls in (Navier2D, NavierEnsemble):
        sound = cls.get_field
        monkeypatch.setattr(
            cls, "get_field", lambda self, *a, _sound=sound: 1.05 * _sound(self, *a))
    res = drive(name)
    assert not res["correct"], res["compared"]
