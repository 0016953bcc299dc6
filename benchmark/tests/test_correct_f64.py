"""What decides ``correct`` in ``rbc513_f64.solo``, held to account:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_correct_f64.py -q   (sizes a test can hold)
    python3 -m pytest benchmark/tests/test_correct_f64.py -q -k own_size         (on the chip: the cell's own size)

The cell runs in float64 and precision is fixed when the program is imported,
so this file wants a process of its own: collected together with the float32
cells' files (``pytest benchmark/tests``), whose imports set ``RUSTPDE_X64=0``,
every test here skips and says so.  ``tests/test_f64_cell.py`` collects these
tests again in the tier-1 suite, whose process is float64.

* the cell's run, driven past the harness's look for a chip, is correct;
* the control -- float32 arithmetic in the program's place: the reference
  built and run in float32, the nearest precision below the configuration's
  float64 -- goes through ``run_cell``'s own comparison and comes out not
  correct by every limit: at the cell's own size against the cell's own limits
  where a TPU is there (``own_size``; the readings of PERF.md, section 2, are
  this test's), and on the CPU at 17 x 17 against limits placed between the
  two readings by the cell's rule (the geometric middle);
* the timed path broken underneath -- a state left unchanged, an interval cut
  to half its steps -- comes out not correct by those limits.
"""

from __future__ import annotations

import copy
import math
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("RUSTPDE_X64", "1")
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
from benchmark.drivers import interval_f64  # noqa: E402
from benchmark.reference import Reference  # noqa: E402

CELL = "rbc513_f64.solo"
SEEDS = (1, 2, 2**31 + 3)


@pytest.fixture(scope="module", autouse=True)
def float64_process():
    from rustpde_mpi_tpu import config

    if not config.X64:
        pytest.skip("this process imported the program in float32 (RUSTPDE_X64=0): "
                    "run benchmark/tests/test_correct_f64.py in a process of its own")


def small(n: int = 17, steps: int = 64):
    """The cell's own files, cut to n x n and short intervals."""
    manifest, cell, cfg, traffic = run.load_cell(CELL)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg["grid"] = {"nx": n, "ny": n}
    traffic["steps_per_interval"] = steps
    return manifest, cell, cfg, traffic


def drive(files, seed: int = 2**31 + 77, seconds: float = 0.2) -> dict:
    manifest, cell, cfg, traffic = files
    return run.run_cell(manifest, cell, cfg, traffic, seed, seconds, trace=0,
                        log=lambda line: None)


def float32_in_the_programs_place(monkeypatch) -> None:
    """From here on the driver's ``release`` hands the comparison the answer
    of float32 arithmetic instead of the program's: the plain reference built
    and run in float32 from the same initial values."""

    def release(self):
        g, ph = self.cfg["grid"], self.cfg["physics"]
        ref = Reference(g["nx"], g["ny"], ph["ra"], ph["pr"], ph["dt"], ph["aspect"],
                        dtype=np.float32)
        self.answer = check.reference_fields(ref, self.initial, self.n)
        self.model = self.compared_state = None

    monkeypatch.setattr(interval_f64.Driver, "release", release)


@pytest.fixture(scope="module")
def files():
    """The cell at 17 x 17 with its limits placed by the cell's own rule: each
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
    print(f"17 x 17, 64 steps: sound {sound}\ncontrol {control}\nlimits {limits}")
    return out


def test_sound_run_is_correct_and_float32_in_its_place_is_not(monkeypatch, files):
    res = drive(files)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "compared"
    float32_in_the_programs_place(monkeypatch)
    res = drive(files)
    assert not res["correct"], res["compared"]
    assert all(v["value"] > v["limit"] for v in res["compared"].values()), res["compared"]


@pytest.mark.parametrize("seed", [2**31 + 501, 502, 503])
def test_control_is_not_correct_at_the_cells_own_size(monkeypatch, seed):
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the cell's own size and limits are a chip reading")
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
