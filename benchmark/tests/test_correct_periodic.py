"""``test_correct.py``'s questions, asked of the two periodic cells:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_correct_periodic.py -q
    python3 -m pytest benchmark/tests/test_correct_periodic.py -q -k own_size   (on the chip; mesh4 needs 4)

* a sound run of either mix, driven past the harness's look for a chip at
  32 x 33, is correct;
* the control (the plain reference in the program's place, every matrix
  product in three bfloat16 passes) goes through ``run_cell``'s own comparison
  and comes out not correct: at the cells' own size against their own limits
  where the chips are there (``own_size``: PERF.md section 2's readings), and
  on the CPU at 32 x 33 against limits placed between the two readings;
* a left-out interval (``update_n`` returns without stepping) and an answer
  altered where it is produced come out not correct.
"""

from __future__ import annotations

import copy
import math
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["RUSTPDE_X64"] = "0"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4").strip()
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
from benchmark.drivers import periodic_interval  # noqa: E402

CELLS = ["periodic1024_f32.solo", "periodic1024_f32.mesh4"]
CONTROL = "bf16_3x"


def small(name: str):
    """The cell's own files and limits, cut to 32 x 33 and short intervals."""
    manifest, cell, cfg, traffic = run.load_cell(name)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg["grid"] = {"nx": 32, "ny": 33}
    cfg["physics"].update(ra=1e5, dt=2e-3)
    traffic["steps_per_interval"] = 64
    return manifest, cell, cfg, traffic


def drive(name: str, seconds: float = 0.2, seed: int = 2**31 + 77, files=None) -> dict:
    manifest, cell, cfg, traffic = files or small(name)
    return run.run_cell(manifest, cell, cfg, traffic, seed, seconds, trace=0,
                        log=lambda line: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = drive(name)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "steps_per_s"}


def control_in_the_programs_place(monkeypatch) -> None:
    """From here on the driver's ``release`` hands the comparison the
    reference's own answer in three bfloat16 passes instead of the program's."""

    def release(self):
        ref = periodic_interval.reference_for(self.cfg)
        self.answer = check.reference_fields(ref, self.initial, self.n, CONTROL)
        self.model = self.compared_state = None

    monkeypatch.setattr(periodic_interval.Driver, "release", release)


@pytest.mark.parametrize("seed", [2**31 + 501, 502, 503])
@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct_at_the_cells_own_size(monkeypatch, name, seed):
    chips = run.load_cell(name)[1]["chips"]
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < chips:
        pytest.skip("the cell's own size and limits are a chip reading")
    control_in_the_programs_place(monkeypatch)
    res = drive(name, seconds=1.0, seed=seed, files=run.load_cell(name))
    print(f"control {name} seed {seed}: {res['compared']}")
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct_at_a_size_a_test_can_hold(monkeypatch, name):
    """32 x 33: the control reads at least three times what sound runs read in
    one number at least, and with that number's limit placed between the two
    readings ``run_cell`` calls the program correct and the control not."""
    files = small(name)
    seeds = (1, 2, 2**31 + 3)
    sound = [drive(name, 0.1, s, files)["compared"] for s in seeds]
    with monkeypatch.context() as patch:
        control_in_the_programs_place(patch)
        low = [drive(name, 0.1, s, files)["compared"] for s in seeds]
    keys = [k for k in sound[0] if k.endswith("_rel")]
    lower = {k: max(r[k]["value"] for r in sound) for k in keys}
    upper = {k: min(r[k]["value"] for r in low) for k in keys}
    apart = [k for k in keys if upper[k] >= 3.0 * lower[k]]
    assert apart, (lower, upper)
    traffic = files[3]
    for k in keys:
        traffic["check"][k] = math.sqrt(lower[k] * upper[k]) if k in apart else math.inf
    assert drive(name, 0.1, seeds[0], files)["correct"]
    control_in_the_programs_place(monkeypatch)
    res = drive(name, 0.1, seeds[0], files)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_fault_interval_left_out(monkeypatch, name):
    from rustpde_mpi_tpu.models.navier import Navier2D

    monkeypatch.setattr(Navier2D, "update_n", lambda self, n: None)
    res = drive(name)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_fault_answer_altered_where_it_is_produced(monkeypatch, name):
    from rustpde_mpi_tpu.models.navier import Navier2D

    sound = Navier2D.get_field
    monkeypatch.setattr(Navier2D, "get_field", lambda self, *a: 1.05 * sound(self, *a))
    res = drive(name)
    assert not res["correct"], res["compared"]
