"""Checks of the yardstick itself, on the CPU, in seconds:

    JAX_PLATFORMS=cpu python3 -m benchmark.selfcheck

* the manifest names files that exist, and every per-layer reader states the
  unit, layer and end-to-end metric the manifest gives it;
* the trace reducer gives, on the recorded trace under ``fixtures/``, the busy
  time, window, gap list and per-op sums written beside it;
* ``work.py`` counts 3.77e10 flops for one confined 1025 x 1025 step and an
  eighth of that at 513 x 513, and the roofline share does not depend on
  anything but shapes, peaks and time.

``--rehearse`` also drives every cell of the manifest end to end on the CPU at
17 x 17, and compiles the reference's step at each configuration's own size
for a described ``v5e:2x2`` chip.  A CPU run proves control flow and counts;
its times are not device numbers.
"""

from __future__ import annotations

import copy
import importlib
import json
import os
import sys

from . import reduce as reducer
from . import work
from .run import HERE, ROOT, load_cell, load_json

FIXTURE = os.path.join(HERE, "fixtures", "solo129_3x8steps")


def check_manifest() -> None:
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for cell in manifest["workloads"]:
        _, _, cfg, traffic = load_cell(cell["name"])
        assert cfg["name"] == cell["config"], cell
        importlib.import_module(f"benchmark.drivers.{traffic['driver']}")
    for m in manifest["per_layer"]:
        mod = importlib.import_module(f"benchmark.layer_metrics.{m['name']}")
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (m["unit"], m["layer"], m["moves"]), m["name"]
        assert m["moves"] in e2e, m["name"]
    print(f"manifest: {len(manifest['workloads'])} cells, {len(manifest['per_layer'])} readers agree")


def check_reducer() -> None:
    expected = load_json(FIXTURE + ".expected.json")
    red = reducer.reduce_xplane(FIXTURE + ".xplane.pb")
    got = {
        "window_s": red["window_s"],
        "busy_s": red["busy_s"],
        "gaps": len(red["gap_list_s"]),
        "idle_s": sum(red["gap_list_s"]),
        "top_ops": reducer.breakdown(red, top=5)["device_ops"],
    }
    for key, want in expected.items():
        have = got[key]
        assert json.dumps(have) == json.dumps(want), (key, have, want)
    assert abs(red["window_s"] - red["busy_s"] - got["idle_s"]) < 1e-9
    print(f"reducer: busy {red['busy_s']:.6f} s of {red['window_s']:.6f} s, "
          f"{got['gaps']} gaps, as recorded")
    # and on a trace written by hand
    raw = {"devices": {"/device:TPU:0": {"ops": [("a", 0.0, 4e6), ("b", 3e6, 6e6),
                                                   ("a", 8e6, 10e6)], "modules": []}},
           "host": [("bench:read", 5e6, 9e6)]}
    red = reducer.reduce_events(raw)
    assert abs(red["busy_s"] - 8e-3) < 1e-12 and abs(red["window_s"] - 10e-3) < 1e-12
    assert red["gaps"] == [("bench:read", 2e-3)], red["gaps"]
    assert red["ops"] == {"a": 6e-3, "b": 3e-3}, red["ops"]
    print("reducer: hand-written trace reduces to busy 8 ms, one gap of 2 ms in bench:read")


def check_work() -> None:
    w = work.step_work(1025, 1025)
    assert w["products"] == 35 and abs(w["flops"] / 3.77e10 - 1.0) < 1e-3, w
    r = work.roofline(w, "TPU v5 lite", 1.567e-3)
    assert r["bound"] == "compute" and abs(r["share"] - 0.1221) < 1e-3, r
    assert abs(work.step_work(513, 513)["flops"] / 4.725e9 - 1.0) < 1e-3
    try:
        work.peaks("TPU v9 imaginary")
    except LookupError:
        pass
    else:
        raise AssertionError("an unknown device kind has to be an error")
    print(f"work: {w['flops']:.4g} flops, {w['bytes']:.4g} bytes a step; "
          f"{100 * r['share']:.2f} % of the roofline at 1.567 ms ({r['bound']} bound)")


def rehearse() -> None:
    os.environ["RUSTPDE_X64"] = "0"
    from . import run

    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for cell in manifest["workloads"]:
        _, _, cfg, traffic = load_cell(cell["name"])
        cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
        cfg["grid"] = {"nx": 17, "ny": 17}
        cfg["physics"].update(ra=1e5, dt=2e-3)
        traffic["steps_per_interval"] = 16
        res = run.run_cell(manifest, cell, cfg, traffic, seed=2**31 + 5, seconds=3.0, trace=0)
        assert res["correct"], res
        print(f"rehearse: {cell['name']} correct on the CPU at 17 x 17: {res['compared']}")
    compile_reference_for_chip(manifest)


def compile_reference_for_chip(manifest: dict) -> None:
    """The reference's step at each configuration's own size, compiled for a
    described v5e chip: what the TPU compiler would refuse, it refuses here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from . import check, reference

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        print(f"rehearse: no v5e:2x2 topology can be described here: {exc}")
        return
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=chip)

    for entry in manifest["configs"]:
        ref = check.reference_for(load_json(os.path.join(ROOT, entry["file"])))
        consts = jax.tree.map(spec, ref._host)
        rest = {k: np.zeros((ref.nx, ref.ny)) for k in check.FIELDS}
        state = tuple(spec(a) for a in ref.initial_state(rest))
        steps = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        compiled = reference._run.lower(consts, state, steps, (ref.dt, ref.nu), "f32").compile()
        print(f"rehearse: reference step of {entry['name']} compiles for "
              f"{topo.devices[0].device_kind}: {compiled.memory_analysis()}")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    check_manifest()
    check_work()
    check_reducer()
    if "--rehearse" in argv:
        rehearse()
    print("selfcheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
