"""What decides ``correct`` in ``swift512_f32.solo``, held to account:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_correct_swift.py -q   (sizes a test can hold)
    python3 -m pytest benchmark/tests/test_correct_swift.py -q -k own_size         (on the chip: the cell's own size)

* the cell's run, driven past the harness's look for a chip, is correct;
* the control -- the plain reference in the program's place with every matrix
  product in lower precision -- goes through ``run_cell``'s own comparison and
  comes out not correct: at the cell's own size against the cell's own limits
  where a TPU is there (``own_size``: three bfloat16 passes, the nearest
  precision below float32; the readings of PERF.md, section 2, are this
  test's), and on the CPU at 48 x 48 against limits placed between the two
  readings by the cell's rule (the geometric middle).  At 48 x 48 a product
  sums 48 terms and three passes read BELOW the float32 program's own distance
  from the reference (1e-6 against 1e-5), so the control there is one
  bfloat16 pass (1e-3): the next precision that a run of this size can tell;
* the timed path broken underneath -- a state left unchanged, an interval cut
  to half its steps -- comes out not correct by those limits
  (``tests/test_swift_cell.py`` collects these tests in tier 1 and adds the
  faults of the step itself).

The tests place their limits from their own readings, so they hold in a
float64 process too (tier 1's): the program then runs float64 against the
float32 reference and reads lower still.
"""

from __future__ import annotations

import copy
import math
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("RUSTPDE_X64", "0")
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                 ".jax_cache"),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

import jax  # noqa: E402
import pytest  # noqa: E402

from benchmark import run  # noqa: E402
from benchmark.drivers import swift_interval  # noqa: E402

CELL = "swift512_f32.solo"
SEEDS = (1, 2, 2**31 + 3)
COMPARED = ("theta_rel", "norm_rel")


def small(n: int = 48, steps: int = 512):
    """The cell's own files, cut to n x n on a square that holds as many
    critical wavelengths per point as the cell's (length = n / 8), and to
    short intervals."""
    manifest, cell, cfg, traffic = run.load_cell(CELL)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg["grid"] = {"nx": n, "ny": n}
    cfg["physics"]["length"] = n / 8.0
    traffic["steps_per_interval"] = steps
    return manifest, cell, cfg, traffic


def drive(files, seed: int = 2**31 + 77, seconds: float = 0.2) -> dict:
    manifest, cell, cfg, traffic = files
    return run.run_cell(manifest, cell, cfg, traffic, seed, seconds, trace=0,
                        log=lambda line: None)


def control_in_the_programs_place(monkeypatch, mode: str) -> None:
    """From here on the driver's ``release`` hands the comparison the
    reference's own answer with every product in ``mode`` instead of the
    program's."""

    def release(self):
        ref = swift_interval.reference_for(self.cfg)
        state = ref.run(ref.initial_state(self.initial), self.n, mode)
        self.answer = {"theta": ref.backward(state), "norm": ref.norm(state)}
        self.model = self.compared_state = None

    monkeypatch.setattr(swift_interval.Driver, "release", release)


@pytest.fixture(scope="module")
def files():
    """The cell at 48 x 48 with its limits placed by the cell's own rule: each
    the geometric middle of the largest sound reading and the control's
    smallest, over ``SEEDS``."""
    out = small()
    sound = [drive(out, s)["compared"] for s in SEEDS]
    with pytest.MonkeyPatch.context() as patch:
        control_in_the_programs_place(patch, "bf16")
        control = [drive(out, s)["compared"] for s in SEEDS]
    limits = out[3]["check"]
    for key in COMPARED:
        lower = max(r[key]["value"] for r in sound)
        upper = min(r[key]["value"] for r in control)
        assert upper > 9.0 * lower, (key, lower, upper)  # a factor 3 either side
        limits[key] = math.sqrt(lower * upper)
    print(f"48 x 48, 512 steps: sound {sound}\ncontrol {control}\nlimits {limits}")
    return out


def test_sound_run_is_correct_and_one_pass_in_its_place_is_not(monkeypatch, files):
    res = drive(files)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "steps_per_s"}
    assert list(res)[-1] == "compared" and set(res["compared"]) == set(COMPARED)
    control_in_the_programs_place(monkeypatch, "bf16")
    res = drive(files)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("seed", [2**31 + 501, 502, 503])
def test_control_is_not_correct_at_the_cells_own_size(monkeypatch, seed):
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the cell's own size and limits are a chip reading")
    control_in_the_programs_place(monkeypatch, "bf16_3x")
    res = drive(run.load_cell(CELL), seed=seed, seconds=1.0)
    print(f"control {CELL} seed {seed}: {res['compared']}")
    assert not res["correct"], res["compared"]


def test_fault_state_left_unchanged(monkeypatch, files):
    from rustpde_mpi_tpu import SwiftHohenberg2D

    monkeypatch.setattr(SwiftHohenberg2D, "update_n", lambda self, n: None)
    res = drive(files)
    assert not res["correct"], res["compared"]


def test_fault_interval_cut_to_half_its_steps(monkeypatch, files):
    from rustpde_mpi_tpu import SwiftHohenberg2D

    sound = SwiftHohenberg2D.update_n
    monkeypatch.setattr(SwiftHohenberg2D, "update_n", lambda self, n: sound(self, n // 2))
    res = drive(files)
    assert not res["correct"], res["compared"]
