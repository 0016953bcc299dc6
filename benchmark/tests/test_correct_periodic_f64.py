"""What decides ``correct`` in ``periodic1024_f64.mesh4``, held to account:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_correct_periodic_f64.py -q   (sizes a test can hold)
    python3 -m pytest benchmark/tests/test_correct_periodic_f64.py -q -k own_size         (on four chips: the cell's own size)

The cell runs in float64 and precision is fixed when the program is imported,
so this file wants a process of its own: collected together with the float32
cells' files (``pytest benchmark/tests``), whose imports set ``RUSTPDE_X64=0``,
every test here skips and says so.  ``tests/test_periodic_f64_cell.py``
collects these tests again in the tier-1 suite, whose process is float64.

On the CPU the cell is driven on four virtual devices (the mix's mesh) in the
layout a TPU runs (``RUSTPDE_FORCE_TPU_PATH=1``: split spectra, every float64
product a sliced product), at 32 x 33:

* the cell's run, driven past the harness's look for a chip, is correct;
* the control -- float32 arithmetic in the program's place: the periodic
  reference built and run in float32, the nearest precision below the
  configuration's float64 -- goes through ``run_cell``'s own comparison and
  comes out not correct by every limit: at the cell's own size against the
  cell's own limits where four TPU chips are there (``own_size``; the readings
  of PERF.md, section 2, are this test's), and on the CPU against limits
  placed between the two readings by the cell's rule (the geometric middle);
* the timed path broken underneath -- a state left unchanged, an interval cut
  to half its steps, the answer read back cut in two along the pencil's
  distributed axis (one flip's result left on the wrong devices) -- comes out
  not correct by those limits.
"""

from __future__ import annotations

import copy
import math
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("RUSTPDE_X64", "1")
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
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmark import check, run  # noqa: E402
from benchmark.drivers import periodic_interval_f64  # noqa: E402
from benchmark.reference_periodic import Reference  # noqa: E402

CELL = "periodic1024_f64.mesh4"
SEEDS = (1, 2, 2**31 + 3)


@pytest.fixture(scope="module", autouse=True)
def float64_process():
    from rustpde_mpi_tpu import config

    if not config.X64:
        pytest.skip("this process imported the program in float32 (RUSTPDE_X64=0): "
                    "run benchmark/tests/test_correct_periodic_f64.py in a process of its own")


def small(steps: int = 64):
    """The cell's own files, cut to 32 x 33 and short intervals."""
    manifest, cell, cfg, traffic = run.load_cell(CELL)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg["grid"] = {"nx": 32, "ny": 33}
    cfg["physics"].update(ra=1e5, dt=2e-3)
    traffic["steps_per_interval"] = steps
    return manifest, cell, cfg, traffic


def drive(files, seed: int = 2**31 + 77, seconds: float = 0.2) -> dict:
    manifest, cell, cfg, traffic = files
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")  # the chip's layout and products
        return run.run_cell(manifest, cell, cfg, traffic, seed, seconds, trace=0,
                            log=lambda line: None)


def float32_in_the_programs_place(monkeypatch) -> None:
    """From here on the driver's ``release`` hands the comparison the answer
    of float32 arithmetic instead of the program's: the periodic plain
    reference built and run in float32 from the same initial values."""

    def release(self):
        g, ph = self.cfg["grid"], self.cfg["physics"]
        ref = Reference(g["nx"], g["ny"], ph["ra"], ph["pr"], ph["dt"], ph["aspect"],
                        dtype=np.float32)
        self.answer = check.reference_fields(ref, self.initial, self.n)
        self.model = self.compared_state = None

    monkeypatch.setattr(periodic_interval_f64.Driver, "release", release)


@pytest.fixture(scope="module")
def files():
    """The cell at 32 x 33 with its limits placed by the cell's own rule: each
    the geometric middle of the largest sound reading and the control's
    smallest, over ``SEEDS``."""
    out = small()
    sound = [drive(out, s)["compared"] for s in SEEDS]
    with pytest.MonkeyPatch.context() as patch:
        float32_in_the_programs_place(patch)
        control = [drive(out, s)["compared"] for s in SEEDS]
    limits = out[3]["check"]
    for key in limits:
        lower = max(r[key]["value"] for r in sound)
        upper = min(r[key]["value"] for r in control)
        assert upper > 1e3 * lower, (key, lower, upper)
        limits[key] = math.sqrt(lower * upper)
    print(f"32 x 33 on 4 devices, 64 steps: sound {sound}\ncontrol {control}\nlimits {limits}")
    return out


def test_sound_run_is_correct_and_float32_in_its_place_is_not(monkeypatch, files):
    res = drive(files)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "steps_per_s"}
    float32_in_the_programs_place(monkeypatch)
    res = drive(files)
    assert not res["correct"], res["compared"]
    assert all(v["value"] > v["limit"] for v in res["compared"].values()), res["compared"]


@pytest.mark.parametrize("seed", [2**31 + 501, 502, 503])
def test_control_is_not_correct_at_the_cells_own_size(monkeypatch, seed):
    chips = run.load_cell(CELL)[1]["chips"]
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < chips:
        pytest.skip("the cell's own size and limits are a reading of four chips")
    float32_in_the_programs_place(monkeypatch)
    res = drive(run.load_cell(CELL), seed=seed, seconds=1.0)
    print(f"control {CELL} seed {seed}: {res['compared']}")
    assert not res["correct"], res["compared"]
    assert all(v["value"] > v["limit"] for v in res["compared"].values()), res["compared"]


def test_fault_state_left_unchanged(monkeypatch, files):
    from rustpde_mpi_tpu.models.navier import Navier2D

    monkeypatch.setattr(Navier2D, "update_n", lambda self, n: None)
    res = drive(files)
    assert not res["correct"], res["compared"]


def test_fault_interval_cut_to_half_its_steps(monkeypatch, files):
    from rustpde_mpi_tpu.models.navier import Navier2D

    sound = Navier2D.update_n
    monkeypatch.setattr(Navier2D, "update_n", lambda self, n: sound(self, n // 2))
    res = drive(files)
    assert not res["correct"], res["compared"]


def test_fault_answer_read_back_cut_in_two(monkeypatch, files):
    """A physical field of this space rests as an x-pencil, y cut over the
    devices (``Space2.physical``); read back with its two halves along y in
    each other's place, as a flip whose result stayed on the wrong devices
    would leave it."""
    from rustpde_mpi_tpu.models.navier import Navier2D

    sound = Navier2D.get_field

    def cut(self, *args):
        values = sound(self, *args)
        half = values.shape[1] // 2
        return np.concatenate([values[:, half:], values[:, :half]], axis=1)

    monkeypatch.setattr(Navier2D, "get_field", cut)
    res = drive(files)
    assert not res["correct"], res["compared"]
