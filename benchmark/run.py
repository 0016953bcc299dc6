"""The benchmark's entry point.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It needs a TPU with at least the chips the cell asks for and
exits 3, printing no result, without one.  Everything that belongs to one
configuration, one traffic mix or one per-layer metric sits in a file of its
own, found by the name in ``BENCHMARK.json`` (see ``benchmark/README.md``):

    benchmark/configs/<config>.json           sizes, physics, precision, entry
    benchmark/traffic/<config>.<mix>.json     the mix's driver and parameters
    benchmark/drivers/<driver>.py             how a kind of traffic is offered
    benchmark/layer_metrics/<name>.py         one reader per per-layer metric

The last line of standard output is the result; the lines before it (and the
end of standard error) say what device ran, what compiled inside the window,
how long the window really was, and every number compared beside its limit.
"""

from __future__ import annotations

import time

_T0 = time.time()  # process start, to within the interpreter's own start-up

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_cell(name: str) -> tuple:
    """(manifest, cell, config, traffic) for the workload ``name``."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {c["name"]: c for c in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(
        os.path.join(HERE, "traffic", f"{cell['config']}.{cell['traffic']}.json")
    )
    return manifest, cell, cfg, traffic


class Tracer:
    """The profiler around the part of the window a driver chooses to trace
    (``begin``/``end``); with ``--trace 0`` both do nothing.  ``span`` names a
    stretch of host work in the trace either way (a TraceAnnotation costs
    nothing while no trace is being taken)."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None
        self.active = False
        self.t_begin = self.t_end = None

    def begin(self) -> None:
        if not self.on or self.dir is not None:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.active = True
        self.t_begin = time.perf_counter()

    def end(self) -> None:
        if not self.active:
            return
        import jax

        self.t_end = time.perf_counter()
        jax.profiler.stop_trace()
        self.active = False

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def xplane(self) -> str | None:
        if self.dir is None:
            return None
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        return found[0] if found else None

    def cleanup(self) -> None:
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)


class Context:
    """What a driver is given: the cell's files, the seed, the window's
    length, the compile meter, the tracer and the line logger."""

    def __init__(self, cfg, traffic, seed, seconds, log, meter, tracer):
        self.cfg, self.traffic, self.seed, self.seconds = cfg, traffic, int(seed), float(seconds)
        self.log, self.meter, self.tracer = log, meter, tracer


def run_cell(manifest, cell, cfg, traffic, seed, seconds, trace, t0=None, log=None) -> dict:
    """One run of one cell on whatever device JAX has; returns the result
    line as a dict.  ``main`` looks for the chip first; tests call this
    directly on the CPU."""
    import jax

    from .meter import CompileMeter

    t0 = time.time() if t0 is None else t0
    log = log or (lambda msg: print(msg, flush=True))
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log(f"bench: cell {cell['name']} seed {seed} seconds {seconds} trace {int(trace)} "
        f"on {device['count']} x {device['kind']} ({device['platform']})")
    tracer = Tracer(bool(trace))
    ctx = Context(cfg, traffic, seed, seconds, log, CompileMeter(), tracer)
    drv = importlib.import_module(f"benchmark.drivers.{traffic['driver']}").Driver(ctx)
    drv.split["start_s"] = round(time.time() - t0, 3)  # interpreter, jax, the chip
    try:
        drv.setup()
        win = drv.window()
        tracer.end()
        setup_s = win["started_at"] - t0
        inside = win["compiles"]
        log(f"bench: window {win['window_s']:.6f} s, {win['work']}; compiled inside the "
            f"window: {inside['compiled']} (loads from the compile cache: "
            f"{inside['cache_loads']}); set-up {setup_s:.3f} s, of it {drv.split}")
        if inside["compiled"]:
            raise RuntimeError(
                f"{inside['compiled']} XLA compilation(s) inside the measured window"
            )
        device["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices
        )
        drv.release()
        t_chk = time.perf_counter()
        compared = drv.check()
        check_s = time.perf_counter() - t_chk
    finally:
        tracer.end()

    metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
    breakdown = None
    if trace:
        from . import reduce as reducer

        metrics = {}
        path = tracer.xplane()
        if path is None:
            raise RuntimeError("the profiler wrote no trace")
        reduced = reducer.reduce_xplane(path)
        tracer.cleanup()
        if reduced["busy_s"] <= 0.0:
            raise RuntimeError("the trace holds no operation on the device")
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = reducer.breakdown(reduced)
        run_info = {**win, "cfg": cfg, "traffic": traffic, "device": device,
                    "cell": cell["name"]}
        for m in manifest["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            reader = importlib.import_module(f"benchmark.layer_metrics.{m['name']}")
            value = reader.read(reduced, run_info)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        log(f"bench: traced {reduced['window_s']:.4f} s, device busy "
            f"{reduced['busy_s']:.4f} s, idle share "
            f"{1.0 - reduced['busy_s'] / reduced['window_s']:.4f}")
    else:
        for m in manifest["end_to_end"]:
            if m["name"] in win["metrics"] and cell["name"] in m.get("workloads", [cell["name"]]):
                metrics[m["name"]] = {"value": win["metrics"][m["name"]], "unit": m["unit"]}

    log(f"bench: reference and comparison took {check_s:.2f} s")
    result = {
        "correct": all(_within(v) for v in compared.values()),
        "attempted": int(win["attempted"]),
        "failed": int(win["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": v[0], "limit": v[1]} for k, v in compared.items()}
    return result


def _within(pair) -> bool:
    value, limit = pair
    return value is not None and value == value and value <= limit


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest, cell, cfg, traffic = load_cell(args.workload)
    # the program's precision is an import-time switch: the configuration's
    # environment is set before the program (or jax) is imported
    for key, value in cfg.get("env", {}).items():
        os.environ[key] = str(value)
    # the compile cache: where the environment puts it, else a fixed
    # directory inside this checkout; every program is kept, however small
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        print(f"benchmark: JAX found no device: {exc}", file=sys.stderr)
        return 3
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(
            f"benchmark: {args.workload} needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].device_kind} ({devices[0].platform}). "
            "There is no CPU mode.",
            file=sys.stderr,
        )
        return 3
    lines: list = []
    try:
        # whatever the program prints while it runs goes to standard error;
        # standard output carries the harness's lines only
        with contextlib.redirect_stdout(sys.stderr):
            result = run_cell(manifest, cell, cfg, traffic, args.seed, args.seconds,
                              args.trace, t0=_T0, log=lines.append)
    finally:
        for line in lines:
            print(line, flush=True)
    for name, pair in result["compared"].items():
        ok = "ok" if _within((pair["value"], pair["limit"])) else "FAIL"
        print(f"bench: compared {name} = {pair['value']!r} limit {pair['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
