"""Nu-parity regression gate.

PARITY.json (written by scripts/record_parity.py) holds the f64 golden
Nusselt trajectory for the reference's flagship config
(/root/reference/src/main.rs:37-58: confined RBC 129^2, Ra=1e7, dt=2e-3) and
the recorded f32-vs-f64 drift.  This test re-runs the head of that trajectory
and asserts reproduction to the 1e-6 parity tolerance (BASELINE.json
north-star), making parity a number the suite enforces rather than an
aspiration.
"""

import json
import os

import pytest

from rustpde_mpi_tpu import Navier2D, config

PARITY = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "PARITY.json")


@pytest.mark.skipif(not os.path.exists(PARITY), reason="PARITY.json not recorded")
def test_f64_nu_trajectory_matches_recorded():
    if not config.X64:
        pytest.skip("parity gold is f64")
    with open(PARITY, encoding="utf-8") as fh:
        gold = json.load(fh)
    cfg = gold["config"]
    model = Navier2D(
        cfg["nx"], cfg["ny"], cfg["ra"], cfg["pr"], cfg["dt"], cfg["aspect"],
        cfg["bc"], periodic=False,
    )
    model.init_random(cfg["amp"], seed=0)
    n_check = 4  # first 200 steps keep CI fast; full trajectory via the script
    for row in gold["nu_f64"][:n_check]:
        model.update_n(cfg["sample_every"])
        nu, nuvol, re, div = model.get_observables()
        assert model.time == pytest.approx(row["time"], abs=1e-9)
        assert nu == pytest.approx(row["nu"], rel=1e-6)
        assert nuvol == pytest.approx(row["nuvol"], rel=1e-6)
        assert re == pytest.approx(row["re"], rel=1e-6)


def test_recorded_f32_drift_is_small():
    if not os.path.exists(PARITY):
        pytest.skip("PARITY.json not recorded")
    with open(PARITY, encoding="utf-8") as fh:
        gold = json.load(fh)
    # the f32 path must statistically track f64: drift well below 1% over
    # the recorded window (actual recorded value ~3e-5)
    assert gold["max_drift"] < 1e-2
