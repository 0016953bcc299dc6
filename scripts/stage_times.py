"""Device time per solver step by step stage, from a profiler trace.

    python scripts/stage_times.py --workload rbc513_f32.solo [--dispatches 2]
    python scripts/stage_times.py --workload swarm129_f32.batch --dispatches 6
    python scripts/stage_times.py --workload periodic1024_f32.mesh4   (4 chips)
    python scripts/stage_times.py --workload lnse_opt128_f32.loop     (two tables)
    python scripts/stage_times.py --workload rbc513_f64.solo          (float64: the cell's env sets RUSTPDE_X64)
    python scripts/stage_times.py --workload swift512_f32.solo        (the Swift-Hohenberg step's five stages)

Builds the cell's own model (``BENCHMARK.json`` and ``benchmark/`` say what a
cell is), warms one dispatch, traces a few under ``utils/profiling.trace`` and
reduces the trace with the benchmark's own reducer.  Every device operation of
the trace is then looked up, by instruction name and result shape, in the
compiled text of the same chunk program, whose ``op_name`` metadata carries
the ``jax.named_scope`` of the step stage it was traced under
(``Navier2D._make_step``, ``models/swift_hohenberg.py``).  A fusion is counted under the stage of the
instruction XLA took its metadata from.  Printed: ms per step by stage and
solve, the share under no stage, and the share of device time whose
instruction the chunk's text does not hold (the observables).  Also: the
program's ``rustpde:`` spans the trace's host plane holds, and how long after
a launch span opens the device starts on its chunk.

For a cell of ``Navier2DNonLin`` (the optimal-perturbation iteration) the
forward sweep and the adjoint sweep are traced apart, each as one bucket of
the largest power of two in the cell's sweep, and each gets a table of its
own: two programs number their fusions alike, so one trace of both could not
be told apart by name.

``--size N`` shrinks the grid for a rehearsal on the CPU, where the table is
empty (the CPU backend writes no device plane) and only control flow is shown.
"""

import argparse
import bisect
import glob
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

STAGES = ("buoyancy", "synthesis", "sentinels", "momentum_x", "momentum_y", "divergence",
          "poisson", "projection", "pressure", "temperature", "scalar", "solid",
          "history", "history_terms",
          # the Swift-Hohenberg step (models/swift_hohenberg.py); its synthesis is named above
          "cubic", "analysis", "implicit", "symmetry")
INNER = ("convection", "helmholtz", "fastdiag", "tensor_solve")
#: the mesh's own scopes (parallel/decomp.py): the manual regions and, inside
#: them, each hand-placed exchange by direction
REGIONS = ("sharded_conv", "sharded_synthesis", "sharded_poisson")
EXCHANGES = ("transpose_x_to_y", "transpose_y_to_x")


def stage_of(op_name: str) -> str | None:
    # a transform wraps the scopes it maps over: "vmap(poisson)" in the ensemble
    parts = [re.sub(r"^(?:\w+\()+([^()]*)\)+$", r"\1", p) for p in op_name.split("/")]
    stage = next((p for p in parts if p in STAGES), None)
    if stage is None:
        return None
    inner = next((p for p in parts if p in INNER), None)
    region = next((p for p in parts if p in REGIONS), None)
    exchange = next((p for p in parts if p in EXCHANGES), None)
    return "/".join(p for p in (stage, inner, region, exchange) if p)


def scopes_of(text: str, short) -> dict:
    """``{"fusion.12 f32[513,513]": op_name}`` for every instruction of a
    compiled module's text (``""`` where it carries no metadata)."""
    out = {}
    for line in text.splitlines():
        line = re.sub(r"^\s*(ROOT )?", "", line)
        if line.startswith("%") and " = " in line:
            m = re.search(r'op_name="([^"]*)"', line)
            out[short(line)] = m.group(1) if m else ""
    return out


def results_of(text: str, short) -> dict:
    """``{"all-reduce.23 f32[1,1026]": "(f32[1,1026]{1,0}, f32[512,1026]{1,0})"}``:
    an instruction's whole result type.  The trace names a collective by the
    first element of a tuple result, which may be its smallest."""
    out = {}
    for line in text.splitlines():
        line = re.sub(r"^\s*(ROOT )?", "", line)
        m = re.match(r"%[^ =]+ = (\(.*?\)|\S+) [\w\-]+\(", line)
        if m:
            out[short(line)] = re.sub(r"\{[^}]*\}", "", m.group(1))
    return out


def compiled_text(lowered) -> str:
    """Compiled afresh: the persistent compile cache keys a program without
    its metadata, so a hit hands back the names of whichever build compiled
    it first (a checkout without scopes, say)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def stage_table(red: dict, scopes: dict) -> tuple:
    """``({stage: seconds}, seconds of operations the text does not hold)`` of
    a reduced trace, each operation looked up by name and shape in ``scopes``."""
    table, unknown = {}, 0.0
    for name, seconds in red["ops"].items():
        if name not in scopes:
            unknown += seconds
            continue
        stage = stage_of(scopes[name]) or "(no stage)"
        table[stage] = table.get(stage, 0.0) + seconds
    return table, unknown


def print_table(table: dict, unknown: float, steps: int) -> None:
    total = sum(table.values()) + unknown
    for stage, seconds in sorted(table.items(), key=lambda kv: -kv[1]):
        print(f"  {stage:28s} {1e3 * seconds / steps:9.5f} ms/step  {100 * seconds / total:6.2f} %")
    named = sum(v for s, v in table.items() if s != "(no stage)")
    if total:
        print(f"  under a named stage {100 * named / total:.2f} %; in the chunk's text but under no "
              f"stage {100 * table.get('(no stage)', 0.0) / total:.2f} %; not in the chunk's text "
              f"{100 * unknown / total:.2f} %")


def sweep_tables(args, cfg: dict, traffic: dict) -> int:
    """The two tables of a ``Navier2DNonLin`` cell: its forward sweep and its
    adjoint sweep, one bucket each, traced apart."""
    import copy

    import jax

    from benchmark import reduce as reducer
    from benchmark.drivers import descent_loop
    from rustpde_mpi_tpu import Navier2DNonLin
    from rustpde_mpi_tpu.utils import profiling

    cfg = copy.deepcopy(cfg)
    if args.size:
        cfg["grid"] = {"nx": args.size, "ny": args.size}
        cfg["optimisation"]["base_time"] = 1.0
    g, ph = cfg["grid"], cfg["physics"]
    n = 1 << (int(traffic["steps_per_interval"]).bit_length() - 1)
    if args.size:
        n = min(n, 64)
    mean = descent_loop.mean_fields(g["nx"], g["ny"], descent_loop.base_state(cfg, traffic, 0))
    model = Navier2DNonLin.new_confined(
        g["nx"], g["ny"], ph["ra"], ph["pr"], ph["dt"], ph["aspect"], ph["bc"], mean=mean)
    model.init_random(1e-3, seed=0)
    start = model.state
    after, chunk = model._fwd_n(start, n)  # warms the forward bucket too
    jax.block_until_ready(model._adj_n(after, chunk, n))
    sweeps = {
        "forward": (model._fwd_n_jit.lower(model._fwd_consts, start, n=n),
                    lambda: model._fwd_n(start, n)),
        "adjoint": (model._adj_n_jit.lower(model._adj_consts, after, chunk),
                    lambda: model._adj_n(after, chunk, n)),
    }
    dev = jax.devices()[0]
    out = {"workload": args.workload, "device": dev.device_kind, "steps": args.dispatches * n}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for name, (lowered, dispatch) in sweeps.items():
        text = compiled_text(lowered)
        scopes = scopes_of(text, reducer.short)
        logdir = tempfile.mkdtemp(prefix="stage_times_")
        with profiling.trace(logdir):
            for _ in range(args.dispatches):
                jax.block_until_ready(dispatch())
        path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)[0]
        red = reducer.reduce_xplane(path)
        table, unknown = stage_table(red, scopes)
        print(f"stage_times: {args.workload}, {name} sweep at {g['nx']} x {g['ny']}, "
              f"{args.dispatches} dispatches of {n} steps on {dev.device_kind}; device busy "
              f"{red['busy_s']:.4f} s of {red['window_s']:.4f} s, sum of operations "
              f"{sum(red['ops'].values()):.4f} s")
        print_table(table, unknown, args.dispatches * n)
        out[name] = {"busy_s": red["busy_s"], "window_s": red["window_s"],
                     "ops_s": sum(red["ops"].values()), "stages_s": table,
                     "not_in_text_s": unknown}
        if args.out:
            with open(f"{os.path.splitext(args.out)[0]}.{name}.txt", "w", encoding="utf-8") as fh:
                fh.write(text)  # the names above are this text's
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump(out, fh, indent=1)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--dispatches", type=int, default=2)
    ap.add_argument("--size", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help="also write the table as JSON here (with every device operation of the trace, its "
                         "scope and its result type), and the chunk's compiled text beside it (.txt)")
    args = ap.parse_args(argv)
    from benchmark import reduce as reducer
    from benchmark.run import load_cell

    _, _, cfg, traffic = load_cell(args.workload)
    for key, value in cfg.get("env", {}).items():
        os.environ[key] = str(value)
    if "Navier2DNonLin" in cfg["entry"]:
        return sweep_tables(args, cfg, traffic)
    import jax
    import numpy as np

    from rustpde_mpi_tpu import Navier2D, NavierEnsemble, SwiftHohenberg2D
    from rustpde_mpi_tpu.utils import profiling

    g, ph = cfg["grid"], cfg["physics"]
    nx, ny = (args.size, args.size) if args.size else (g["nx"], g["ny"])
    n, k = int(traffic["steps_per_interval"]), int(traffic.get("members", 0))
    if "SwiftHohenberg2D" in cfg["entry"]:
        # keeps the critical wavelength's points when --size shrinks the grid
        length = ph["length"] * nx / g["nx"]
        model = SwiftHohenberg2D(nx, ny, ph["r"], ph["dt"], length)
    elif "new_periodic" in cfg["entry"]:
        from rustpde_mpi_tpu.parallel.mesh import make_mesh

        chips = int(traffic.get("mesh", 0))
        model = Navier2D.new_periodic(
            nx, ny, ph["ra"], ph["pr"], ph["dt"], ph["aspect"], ph["bc"],
            mesh=make_mesh(jax.devices()[:chips]) if chips else None)
    else:
        model = Navier2D.new_confined(nx, ny, ph["ra"], ph["pr"], ph["dt"], ph["aspect"], ph["bc"])
    if k:
        sim = NavierEnsemble.from_seeds(model, seeds=list(range(k)), amp=traffic["amp"])
        lowered = sim._step_n_jit.lower(model._step_consts, sim.state, sim.mask, sim.steps_done, n=n)
        read = lambda: np.asarray(sim.steps_done)  # noqa: E731
    else:
        sim = model
        model.init_random(0.1, seed=0)
        with model._scope():
            lowered = model._step_n_jit.lower(model._step_consts, model.state, n=n)
        read = model.get_observables
    text = compiled_text(lowered)
    scopes, results = scopes_of(text, reducer.short), results_of(text, reducer.short)
    sim.update_n(n), read()
    logdir = tempfile.mkdtemp(prefix="stage_times_")
    with profiling.trace(logdir):
        for _ in range(args.dispatches):
            sim.update_n(n), read()
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)[0]
    red = reducer.reduce_xplane(path)
    steps = args.dispatches * n
    total = sum(red["ops"].values())
    table, unknown = stage_table(red, scopes)
    dev = jax.devices()[0]
    print(f"stage_times: {args.workload} at {nx} x {ny}" + (f" x {k} members" if k else "")
          + f", {args.dispatches} dispatches of {n} steps on {dev.device_kind}; device busy "
          f"{red['busy_s']:.4f} s of {red['window_s']:.4f} s, sum of operations {total:.4f} s")
    print_table(table, unknown, steps)
    kinds: dict = {}
    collectives = []
    for name, seconds in red["ops"].items():
        kind = re.match(r"(all-to-all|all-gather|collective-permute|all-reduce|reduce-scatter)", name)
        if kind:
            kinds[kind.group(1)] = kinds.get(kind.group(1), 0.0) + seconds
            collectives.append({"op": name, "s": seconds, "result": results.get(name, ""),
                                "scope": stage_of(scopes.get(name, "")) or ""})
    collectives.sort(key=lambda c: -c["s"])
    for kind, seconds in sorted(kinds.items(), key=lambda kv: -kv[1]):
        print(f"  collective {kind:20s} {1e3 * seconds / steps:9.5f} ms/step  "
              f"{100 * seconds / red['busy_s']:6.2f} % of busy (mean over the device planes)")
    for c in collectives[:12]:  # by its whole result type: a tuple is named by its first element
        print(f"    {c['op']:34s} {1e6 * c['s'] / steps:8.2f} us/step  {c['scope']:28s} {c['result']}")
    host = host_spans(path)
    for name, found in sorted(host["spans"].items()):
        print(f"  host plane: {name} x {len(found)}, mean {1e-6 * sum(e - s for s, e in found) / len(found):.4f} ms")
    if host["launch_to_device_us"]:
        print(f"  launch span's start to the chunk program's start on the device: "
              f"{host['launch_to_device_us']} us")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump({"workload": args.workload, "device": dev.device_kind, "steps": steps,
                       "busy_s": red["busy_s"], "window_s": red["window_s"], "ops_s": total,
                       "stages_s": table, "collectives_s": kinds, "collective_ops": collectives,
                       "ops": sorted(({"op": name, "s": seconds, "scope": scopes.get(name, ""),
                                       "result": results.get(name, "")}
                                      for name, seconds in red["ops"].items()), key=lambda o: -o["s"]),
                       "not_in_text_s": unknown,
                       "launch_to_device_us": host["launch_to_device_us"]}, fh, indent=1)
        with open(os.path.splitext(args.out)[0] + ".txt", "w", encoding="utf-8") as fh:
            fh.write(text)  # the names above are this text's
    return 0


def host_spans(path: str) -> dict:
    """The program's ``rustpde:`` spans on the trace's host plane as
    ``{name: [(start_ns, end_ns)]}``, and for each ``.launch`` span the time
    from its start to the start of the chunk program nearest to it on the
    device (the ``XLA Modules`` row; the observables are another module).
    Read with ``ProfileData`` itself: the reducer's ``load`` keeps only
    ``bench:`` spans and Python frames once the Python tracer is on."""
    from jax.profiler import ProfileData

    from benchmark.reduce import DEVICE_PLANE

    spans: dict = {}
    chunks: list = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith("rustpde:"):
                        spans.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
            elif DEVICE_PLANE.match(plane.name) and line.name == "XLA Modules":
                chunks.extend(ev.start_ns for ev in line.events if "step_n" in ev.name)
    chunks.sort()
    gaps = []
    for name, found in spans.items():
        if name.endswith(".launch") and chunks:
            for start, _ in found:
                # the nearest chunk start, signed: host and device clocks are
                # aligned to some tens of microseconds only
                i = bisect.bisect_left(chunks, start)
                near = min(chunks[max(i - 1, 0):i + 1], key=lambda c: abs(c - start))
                gaps.append(round((near - start) * 1e-3, 1))
    return {"spans": spans, "launch_to_device_us": gaps}


if __name__ == "__main__":
    sys.exit(main())
