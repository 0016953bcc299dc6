"""Nu-parity regression gate.

PARITY.json (written by scripts/record_parity.py) holds the f64 golden
Nusselt trajectory for the reference's flagship config
(/root/reference/src/main.rs:37-58: confined RBC 129^2, Ra=1e7, dt=2e-3) and
the recorded f32-vs-f64 drift.  This test re-runs the head of that trajectory
and asserts reproduction to the 1e-6 parity tolerance against the upstream
example's configuration (examples/navier_rbc.rs), making parity a number the
suite enforces rather than an aspiration.
"""

import json
import os
import subprocess
import sys

import numpy as np
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


# -- the float32 path the chip runs, against float64 --------------------------
#
# Precision is an import-time switch, so the float32 side runs in a child:
# RUSTPDE_X64=0 on the forced TPU path (matmul transforms, dense solves, split
# Fourier), which is what a v5e executes.  This process is the float64 CPU
# path (FFT, banded solves): the plain reference.

_SHADOW_STEPS = 8
_LAYOUTS = {"confined": (33, 33, False), "periodic": (32, 33, True)}
_N_MMS = 33


def _shadow_temp(layout):
    """Temperature after ``_SHADOW_STEPS`` steps from a smooth deterministic
    initial condition: over so short a horizon nothing chaotic has grown, so
    two precisions differ by accumulated rounding and nothing else."""
    nx, ny, periodic = _LAYOUTS[layout]
    model = Navier2D(nx, ny, 1e7, 1.0, 2e-3, 1.0, "rbc", periodic=periodic)
    model.set_velocity(0.1, 2.0, 2.0)
    model.set_temperature(0.1, 2.0, 2.0)
    model.update_n(_SHADOW_STEPS)
    return np.asarray(model.get_field("temp"), dtype=np.float64)


def _poisson_mms_error(n):
    """Max error of the pressure solver's configuration (pure Neumann) on a
    manufactured zero-mean solution."""
    import jax.numpy as jnp

    from rustpde_mpi_tpu import Space2, cheb_neumann
    from rustpde_mpi_tpu.solver import Poisson

    space = Space2(cheb_neumann(n), cheb_neumann(n))
    xs, ys = (b.points for b in space.bases)
    u = np.cos(np.pi * xs)[:, None] * np.cos(np.pi * ys)[None, :]
    fhat = space.to_ortho(space.forward(jnp.asarray(-2.0 * np.pi**2 * u)))
    got = np.array(space.backward(Poisson(space, (1.0, 1.0)).solve(fhat)))
    got -= got.mean() - u.mean()  # defined up to a constant
    return float(np.abs(got - u).max())


_F32_CHILD = """
import sys
sys.path.insert(0, {tests!r})
import numpy as np
import test_parity as tp
from rustpde_mpi_tpu import config
assert not config.X64 and config.is_tpu_like()
np.savez(sys.argv[1], mms=tp._poisson_mms_error(tp._N_MMS),
         **{{name: tp._shadow_temp(name) for name in tp._LAYOUTS}})
"""


@pytest.fixture(scope="module")
def f32_side(tmp_path_factory):
    if not config.X64:
        pytest.skip("this side of the comparison is float64")
    out = str(tmp_path_factory.mktemp("f32") / "f32.npz")
    env = dict(os.environ, RUSTPDE_X64="0", RUSTPDE_FORCE_TPU_PATH="1")
    proc = subprocess.run(
        [sys.executable, "-c", _F32_CHILD.format(tests=os.path.dirname(__file__)), out],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=os.path.dirname(PARITY),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return np.load(out)


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_f32_field_shadows_f64_over_eight_steps(f32_side, layout):
    """Short-horizon shadowing: a broken float32 path drifts by order one
    within a step, a sound one stays at accumulated rounding (2.5e-6 and
    2.1e-6 here).  The limit sits forty times above that."""
    ref = _shadow_temp(layout)
    drift = np.linalg.norm(f32_side[layout] - ref) / np.linalg.norm(ref)
    assert drift < 1e-4, drift


def test_f32_poisson_mms_error_is_rounding(f32_side):
    """The Neumann Poisson solve on a manufactured solution: spectrally exact
    in float64 (5.6e-12 here); on the float32 path the solve's conditioning
    costs digits (7.2e-4 here) but stays under a percent."""
    assert _poisson_mms_error(_N_MMS) < 1e-8
    assert float(f32_side["mms"]) < 1e-2, float(f32_side["mms"])
