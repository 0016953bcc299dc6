"""Smoke-run every example program (VERDICT r2 next #8).

Each of the 14 entry points runs in a subprocess on tiny grids (CPU forced
the same way tests/conftest.py does it) and must exit 0 — so the example
layer can't rot while only the models it wraps are tested.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # heavyweight end-to-end tier (VERDICT r3 #8)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# example -> fast argv (tiny grids / --quick); every program must finish in
# well under a minute on CPU
_CASES = {
    "demo_transforms.py": [],
    "solve_poisson.py": [],
    "solve_hholtz.py": ["--n", "17"],
    "navier_rbc.py": ["--quick"],
    "navier_rbc_ensemble.py": ["--quick"],
    "navier_rbc_periodic.py": ["--nx", "16", "--ny", "17", "--max-time", "0.05"],
    "navier_rbc_resilient.py": [
        "--quick", "--max-time", "0.2", "--fault", "nan@8", "--retries", "1",
    ],
    "navier_rbc_governed.py": [
        "--quick", "--max-time", "0.5", "--fault", "spike@8",
        "--spike-factor", "100", "--grow-after", "2",
    ],
    "navier_rbc_pipelined.py": ["--quick", "--max-time", "0.2"],
    "navier_rbc_serve.py": [
        "--quick", "--requests", "3", "--slots", "2", "--horizon", "0.05",
        "--run-dir", "data/serve_smoke", "--fault", "nan@3",
    ],
    "navier_rbc_roughness.py": ["--quick"],
    "navier_rbc_scenarios.py": ["--quick"],
    # an idle fleet replica in batch mode: fleet init + lease manager +
    # heartbeat publication + the idle-done handshake, then a clean exit
    "navier_rbc_fleet.py": [
        "--replica", "--replica-id", "smoke", "--run-dir", "data/fleet_smoke",
    ],
    # controller-only autoscale pass: three decide ticks over an empty
    # queue with a zero floor — exercises observe/decide/journal without
    # spawning replica subprocesses (each would pay a full JAX import)
    "navier_rbc_autoscale.py": [
        "--run-dir", "data/autoscale_smoke", "--min-replicas", "0",
        "--max-replicas", "1", "--steps", "3", "--decide-s", "0.05",
    ],
    "navier_lnse_eigenmodes.py": ["--quick", "--run-dir", "data/eig_smoke"],
    "navier_mpi.py": ["--quick"],
    "navier_rbc_steady.py": ["--quick"],
    "navier_rbc_steady_continuation.py": [
        "--nx", "17", "--ny", "17", "--num", "2", "--max-time", "2",
    ],
    "navier_lnse_test_gradient.py": ["--quick"],
    "navier_lnse_opt_reversals.py": ["--tiny"],
    "swift_hohenberg_1d.py": ["--nx", "64", "--max-time", "1.0"],
    "swift_hohenberg_2d.py": ["--quick"],
}


#: lines an example has to print.  The optimal-perturbation campaign's J
#: sequence (f64, --tiny: one iteration) since PR 30, when its loop body moved
#: into models/opt_routines.descent_iteration (same J to the last digit:
#: tests/test_lnse_cell.py) and its base state's temperature became the total
#: field (before: 1.443568e-03 about a base state without its conduction
#: profile, which is no solution of the equations it perturbs)
_PINNED = {
    "navier_lnse_opt_reversals.py": ["  iter 0: J = 2.259963e-03  alpha = 1.000"],
    # the --quick run's last line (f64, 64 x 64, 1000 steps from the constructor's
    # seed-0 noise): the same energy before and after PR 37 moved the model onto
    # CampaignModelBase.update_n (the line's wall time and rate are not pinned)
    "swift_hohenberg_2d.py": ["done: t=20.00 (1000 steps) in ", "pattern energy=2.2001e-01"],
}


def test_every_example_has_a_case():
    present = sorted(
        f for f in os.listdir(os.path.join(_REPO, "examples")) if f.endswith(".py")
    )
    assert present == sorted(_CASES), "new example without a smoke case"


@pytest.mark.parametrize("name", sorted(_CASES))
def test_example_smoke(name, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", RUSTPDE_X64="1")
    env.pop("XLA_FLAGS", None)  # plain single-device CPU: fastest compile
    res = subprocess.run(
        [
            sys.executable,
            os.path.join(_REPO, "examples", name),
            *_CASES[name],
        ],
        capture_output=True,
        text=True,
        env=env,
        cwd=str(tmp_path),  # examples that write artifacts do it in cwd
        timeout=600,
    )
    assert res.returncode == 0, f"{name} rc={res.returncode}\n{res.stderr[-2500:]}"
    for line in _PINNED.get(name, ()):
        assert line in res.stdout, f"{name}: {line!r} not in\n{res.stdout[-1500:]}"
