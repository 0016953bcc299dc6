"""Record the Nu-parity artifact: f64 golden trajectory + f32 drift.

Config is the reference's flagship serial run
(/root/reference/src/main.rs:37-58): confined RBC, 129x129, Ra=1e7, Pr=1,
dt=2e-3, amp-0.01 random IC (seeded here for reproducibility).

Writes PARITY.json at the repo root:

* ``nu_f64``: Nusselt/Nuvol/Re/|div| at each sample step on the f64 CPU
  banded path (the parity gold for tests/test_parity.py),
* ``nu_f32``: same trajectory on the f32 path, and ``drift``: the relative
  Nu deviation |Nu32 - Nu64| / |Nu64| per sample — the recorded answer to
  "does the f32 TPU trajectory track the f64 one" (VERDICT r1 weak #10).

Run from the repo root: ``python scripts/record_parity.py``.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIG = {
    "nx": 129,
    "ny": 129,
    "ra": 1e7,
    "pr": 1.0,
    "dt": 2e-3,
    "aspect": 1.0,
    "bc": "rbc",
    "amp": 0.01,
    "sample_every": 50,
    "samples": 10,
}

_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %(repo)r)
import jax
from rustpde_mpi_tpu import Navier2D

cfg = json.loads(%(cfg)r)
model = Navier2D(cfg["nx"], cfg["ny"], cfg["ra"], cfg["pr"], cfg["dt"],
                 cfg["aspect"], cfg["bc"], periodic=False)
model.init_random(cfg["amp"], seed=0)
rows = []
for _ in range(cfg["samples"]):
    model.update_n(cfg["sample_every"])
    nu, nuvol, re, div = model.get_observables()
    rows.append({"time": round(model.time, 10), "nu": nu, "nuvol": nuvol,
                 "re": re, "div": div})
print("ROWS:" + json.dumps(rows))
"""


def run_trajectory(x64: bool):
    env = dict(os.environ)
    env["RUSTPDE_X64"] = "1" if x64 else "0"
    env["JAX_PLATFORMS"] = "cpu"
    code = _CHILD % {"repo": REPO, "cfg": json.dumps(CONFIG)}
    res = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=3600, check=False,
    )
    if res.returncode != 0:
        sys.stderr.write(res.stderr)
        raise RuntimeError(f"trajectory run (x64={x64}) failed")
    for line in res.stdout.splitlines():
        if line.startswith("ROWS:"):
            return json.loads(line[len("ROWS:"):])
    raise RuntimeError("no ROWS line in child output")


def main() -> None:
    f64 = run_trajectory(x64=True)
    f32 = run_trajectory(x64=False)
    drift = [
        abs(a["nu"] - b["nu"]) / max(abs(b["nu"]), 1e-300)
        for a, b in zip(f32, f64)
    ]
    out = {
        "config": CONFIG,
        "platform": "cpu",
        "note": (
            "f64 banded-path golden trajectory for the reference flagship "
            "config (main.rs:37-58); f32 drift = |Nu32-Nu64|/Nu64 per sample"
        ),
        "nu_f64": f64,
        "nu_f32": f32,
        "drift": drift,
        "max_drift": max(drift),
    }
    path = os.path.join(REPO, "PARITY.json")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(out, fh, indent=1)
    print(f"wrote {path}; max f32 Nu drift = {max(drift):.3e}")


if __name__ == "__main__":
    main()
