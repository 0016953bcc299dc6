"""A/B the f64 hybrid (RUSTPDE_F64_HYBRID=1: f32 convection transforms
feeding f64 solves — SURVEY S7, VERDICT r4 next #3b) against pure f64.

The parity leg, each side isolated in a subprocess (the sep-operator cache
is built from the env once per process; ``--cpu`` runs it on the CPU): the
PARITY.json flagship trajectory (129^2 Ra=1e7, 500 steps) run on the forced
TPU path with and without the hybrid; reports the per-sample relative Nu
drift hybrid-vs-pure.  The f32 budget for this statistic is ~3e-5
(PARITY.json max_drift); the hybrid must not exceed that scale, since its
only degradation is f32 convection roundoff.  What the hybrid is worth in
steps per second is a benchmark cell's to say (PERF.md section 7,
``rbc513_f64.solo``; ROADMAP Queue 1 item 6).

Writes F64_HYBRID_AB.json at the repo root.
"""

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PARITY_CHILD = r"""
import json, os, sys
sys.path.insert(0, %(repo)r)
from rustpde_mpi_tpu import Navier2D, config
config.enable_compilation_cache()
model = Navier2D(129, 129, 1e7, 1.0, 2e-3, 1.0, "rbc", periodic=False)
model.init_random(0.01, seed=0)
rows = []
for _ in range(10):
    model.update_n(50)
    nu, nuvol, re, div = model.get_observables()
    rows.append({"time": round(model.time, 10), "nu": nu, "re": re, "div": div})
print("ROWS:" + json.dumps(rows))
"""


def _child(code: str, extra_env: dict, timeout: int = 3600) -> str:
    env = dict(os.environ, **extra_env)
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
        cwd=REPO,
    )
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-3000:])
    return out.stdout


def run_parity(cpu: bool) -> dict:
    rows = {}
    for hybrid in ("0", "1"):
        env = {
            "RUSTPDE_X64": "1",
            "RUSTPDE_FORCE_TPU_PATH": "1",
            "RUSTPDE_F64_HYBRID": hybrid,
        }
        if cpu:
            env["JAX_PLATFORMS"] = "cpu"
        out = _child(PARITY_CHILD % {"repo": REPO}, env)
        line = next(l for l in out.splitlines() if l.startswith("ROWS:"))
        rows[hybrid] = json.loads(line[5:])
    drift = [
        abs(h["nu"] - p["nu"]) / abs(p["nu"])
        for h, p in zip(rows["1"], rows["0"])
    ]
    return {
        "pure": rows["0"],
        "hybrid": rows["1"],
        "nu_drift": drift,
        "max_nu_drift": max(drift),
        "f32_budget": 3e-5,
        "passed": max(drift) < 3e-5,
        "platform": "cpu" if cpu else "tpu",
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true", help="run on the CPU")
    args = ap.parse_args()

    path = os.path.join(REPO, "F64_HYBRID_AB.json")
    record = {"parity": run_parity(args.cpu)}
    print(
        f"parity: max Nu drift hybrid-vs-pure = "
        f"{record['parity']['max_nu_drift']:.3e} "
        f"(budget 3e-5, passed={record['parity']['passed']})"
    )
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {path}")
    return 0 if record["parity"]["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
