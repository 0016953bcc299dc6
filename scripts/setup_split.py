"""Where a cell's set-up goes, from the program's own spans.

    python scripts/setup_split.py --workload rbc513_f32.solo [--out chiprun_out/x.json]
    python scripts/setup_split.py --workload periodic1024_f32.mesh4      (4 chips)
    RUSTPDE_TRACE=0 python scripts/setup_split.py --workload ...          (the drivers' split only)

Runs the cell's own driver through its ``setup()`` (``BENCHMARK.json`` and
``benchmark/`` say what a cell is: build, initial values, two warm-up
intervals), as ``benchmark.run`` does with the same compile-cache settings,
then reads the program's span ring (``telemetry/tracing.py``) and prints one
JSON line:

* ``split``: the driver's own timing of the same path from outside;
* ``metrics``: the five per-layer metrics that move ``setup_s``, computed
  by the benchmark's own readers' functions over the same spans;
* ``outer``: the outermost set-up spans (builds, initial values, launches
  that lowered) and their sum beside the driver's ``build_s +
  first_interval_s - warm_s``; ``builds``: each outermost ``model.build`` /
  ``ensemble.build`` with its children by name and the share of it they cover;
* ``by_span``: per span name, the spans, their seconds and jax's compile
  events on them (self counts); ``compile_events``: the totals on spans and
  unattributed, beside the harness's own ``CompileMeter`` over the stretch.

A CPU run (``--size 17``) shows control flow and counts; its times are not
device numbers."""

import argparse
import json
import os
import sys
import time

_T0 = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def table(events, split, totals, metered) -> dict:
    from benchmark.layer_metrics import _setup_spans as ss
    from rustpde_mpi_tpu.telemetry import tracing

    spans = [ev for ev in events if ev.get("ph") == "X"]
    metrics = {name: fn(spans) for name, fn in ss.METRICS.items()}
    metrics["cache_hits"] = ss.total(spans, "cache_hits")
    outer = [ev for ev in spans if ev["args"].get("parent") is None
             and ev["name"] in ss.BUILDS + ("model.set_field",)]
    outer_s = ss.seconds(outer) + metrics["first_dispatch_s"]
    first = split.get("first_interval_s", split.get("first_iteration_s", 0.0))
    drivers = split.get("base_state_s", 0.0) + split["build_s"] + first - split["warm_s"]
    builds = []
    for top in ss.outermost(spans, ss.BUILDS):
        kids: dict = {}
        for ev in spans:
            if ev["args"].get("parent") == top["args"]["id"]:
                kids[ev["name"]] = kids.get(ev["name"], 0.0) + 1e-6 * ev["dur"]
        builds.append({"name": top["name"], "s": 1e-6 * top["dur"],
                       "children_s": {k: round(v, 4) for k, v in kids.items()},
                       "covered": round(sum(kids.values()) / (1e-6 * top["dur"]), 4),
                       "self": {k: top["args"][k] for k in tracing.COMPILE_KEYS if k in top["args"]}})
    by_span: dict = {}
    for ev in spans:
        row = by_span.setdefault(ev["name"], {"spans": 0, "s": 0.0})
        row["spans"] += 1
        row["s"] += 1e-6 * ev["dur"]
        for key in tracing.COMPILE_KEYS:
            if key in ev["args"]:
                row[key] = row.get(key, 0) + ev["args"][key]
    for row in by_span.values():
        for key, value in row.items():
            if isinstance(value, float):
                row[key] = round(value, 4)
    return {
        "split": split,
        "metrics": metrics,
        "outer": {"spans_s": round(outer_s, 4), "drivers_s": round(drivers, 4),
                  "share": round(outer_s / drivers, 4) if drivers else None},
        "builds": builds,
        "by_span": by_span,
        "compile_events": {"attributed": totals["attributed"],
                           "unattributed": totals["unattributed"], "harness_meter": metered},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 35)
    ap.add_argument("--size", type=int, default=None, help="shrink the grid (a CPU rehearsal)")
    ap.add_argument("--out", default=None, help="also write the JSON here")
    args = ap.parse_args(argv)
    import importlib

    from benchmark.meter import CompileMeter
    from benchmark.run import Context, Tracer, load_cell

    _, cell, cfg, traffic = load_cell(args.workload)
    for key, value in cfg.get("env", {}).items():
        os.environ[key] = str(value)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    if args.size:
        cfg["grid"] = {"nx": args.size, "ny": args.size}
        traffic["steps_per_interval"] = 16
        if "optimisation" in cfg:
            cfg["optimisation"]["base_time"] = 16 * cfg["physics"]["dt"]
    import jax

    devices = jax.devices()
    from rustpde_mpi_tpu import config
    from rustpde_mpi_tpu.telemetry import tracing

    meter = CompileMeter()
    ctx = Context(cfg, traffic, args.seed, 1.0, lambda msg: print(msg, file=sys.stderr),
                  meter, Tracer(False))
    drv = importlib.import_module(f"benchmark.drivers.{traffic['driver']}").Driver(ctx)
    drv.split["start_s"] = round(time.time() - _T0, 3)
    mark, before = meter.mark(), tracing.compile_totals()
    drv.setup()
    metered = meter.since(mark)
    now = tracing.compile_totals()
    totals = {side: {k: round(now[side][k] - before[side][k], 4) for k in now[side]}
              for side in now}
    out = {"cell": args.workload, "x64": config.X64, "trace": tracing.enabled(),
           "cache_dir": os.environ["JAX_COMPILATION_CACHE_DIR"],
           "device": f"{len(devices)} x {devices[0].device_kind}",
           "setup_s": round(time.time() - _T0, 3)}
    if tracing.enabled():
        out.update(table(tracing.RECORDER.events(), drv.split, totals, metered))
    else:
        out.update({"split": drv.split, "compile_events": {"harness_meter": metered}})
    line = json.dumps(out)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
