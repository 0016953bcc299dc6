#!/usr/bin/env python3
"""chip_smoke.py: prove the main path still runs on the chip.

Drives ``Navier2D`` -> ``NavierEnsemble`` -> ``ResilientRunner`` ->
``SimServer`` once through the entry points a user calls, at the sizes its
users run (1025^2 flagship, the 129^2 parity configuration, a 24-request
served sweep), in f32 and in f64, and checks what comes out by the repo's
own means.  It fails when JAX finds no TPU; there is no CPU fallback.

    python chip_smoke.py

The parent imports neither jax nor rustpde_mpi_tpu: one process per chip.
It runs two children one after the other (precision is an import-time
switch) and fails if either fails.  Its standard output is two lines: the
report (``{"report": {...}}``: versions, cache directory, every leg), then
as the LAST line the verdict, exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Any time in the report is a SMOKE timing: compile included or apart as
labelled, one reading, never a benchmark result.  A copy of the report and
each child's stderr land in ``chiprun_out/``.

The legs are plain functions with size arguments so that a builder, or
tests/test_chip_smoke.py, can call them at 17^2 on the CPU; the script
itself only ever runs the sizes of ``_run_legs``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_OUT = os.path.join(_HERE, "chiprun_out")
_DEADLINE_S = 1150.0  # the contract allows 1200 s, compilation included
_REMAT = "Involuntary full rematerialization"


class CompileMeter:
    """Compilations and compile-cache loads between a mark and now, from the
    program's own count of jax's compile events
    (``telemetry/tracing.compile_totals``: on a span or off, every event is
    in it), so this script keeps no listener beside the program's."""

    @staticmethod
    def mark() -> dict:
        from rustpde_mpi_tpu.telemetry import tracing

        return tracing.compile_totals()

    @staticmethod
    def since(mark: dict) -> dict:
        from rustpde_mpi_tpu.telemetry import tracing

        got = tracing.compile_totals_since(mark)
        return {
            "compiled": got["compiled"],
            "cache_loads": got["cache_hits"],
            "cache_misses": got["cache_misses"],
            "compile_s": got["compile_s"],
        }


# journal rows that mean a request did not run clean end to end
_UNCLEAN_EVENTS = frozenset(
    (
        "retry", "request_retry", "request_failed", "request_requeued",
        "divergence", "pre_divergence", "fault_injected", "bitflip_injected",
        "integrity_rollback", "integrity_mismatch", "dispatch_hang", "giveup",
        "member_killed", "respawn", "checkpoint_failed", "results_abandoned",
    )
)


# -- child side: measurement helpers ------------------------------------------


def _on_platform(tree, platform: str) -> bool:
    """Every leaf of ``tree`` lives only on devices of ``platform``."""
    import jax

    return all(
        d.platform == platform
        for leaf in jax.tree.leaves(tree)
        for d in leaf.sharding.device_set
    )


def _finite(values) -> bool:
    return all(v == v and abs(v) != float("inf") for v in values)


def _smooth_ic(model) -> None:
    # a deterministic smooth IC: the default random-noise IC is a stiff
    # transient at 1025^2 Ra=1e9
    model.set_velocity(0.1, 2.0, 2.0)
    model.set_temperature(0.1, 2.0, 2.0)


# -- legs ----------------------------------------------------------------------


def leg_flagship(meter, platform, nx=1025, ny=1025, ra=1e9, dt=1e-4,
                 steps=256, dispatches=2, div_bound=0.1) -> dict:
    """Confined RBC at 1025^2, Ra=1e9, smooth IC, ``steps``
    steps per ``update_n`` dispatch.  Gates: observables finite, |div| under
    ``div_bound`` (smooth-IC runs sit near 1e-3), state resident on
    ``platform``, zero compilations in the last dispatch (when there is
    more than one)."""
    import jax

    from rustpde_mpi_tpu import Navier2D

    start = meter.mark()
    t0 = time.perf_counter()
    model = Navier2D.new_confined(nx, ny, ra, 1.0, dt, 1.0, "rbc")
    _smooth_ic(model)
    jax.block_until_ready(model.state)
    build_s = time.perf_counter() - t0
    walls, last = [], None
    for _ in range(dispatches):
        mark = meter.mark()
        t0 = time.perf_counter()
        model.update_n(steps)
        jax.block_until_ready(model.state)
        walls.append(time.perf_counter() - t0)
        last = meter.since(mark)
    nu, nuvol, re, div = model.get_observables()[:4]
    resident = _on_platform(model.state, platform)
    # a load from the persistent cache is a compilation too, here
    compiles_last = last["compiled"] + last["cache_loads"]
    steady = dispatches < 2 or compiles_last == 0
    return {
        "passed": bool(
            _finite((nu, nuvol, re, div)) and div < div_bound and resident and steady
        ),
        "grid": [nx, ny],
        "steps_per_dispatch": steps,
        "nu": nu,
        "div": div,
        "div_bound": div_bound,
        "state_on_platform": resident,
        "compiles_in_last_dispatch": compiles_last,
        "build_s": round(build_s, 3),
        "first_dispatch_s": round(walls[0], 3),
        "stepping_s": round(walls[-1], 3),
        **meter.since(start),
    }


def leg_parity(meter, platform, cfg, gold_rows, rtol) -> dict:
    """The PARITY.json configuration (numpy-RNG IC: identical on any
    backend) against the committed f64 rows of the CPU FFT/banded path —
    the plain reference.  Gate: Nu within ``rtol`` relative at every row."""
    import jax

    from rustpde_mpi_tpu import Navier2D

    start = meter.mark()
    t0 = time.perf_counter()
    model = Navier2D(
        cfg["nx"], cfg["ny"], cfg["ra"], cfg["pr"], cfg["dt"], cfg["aspect"],
        cfg["bc"], periodic=False,
    )
    model.init_random(cfg["amp"], seed=0)
    jax.block_until_ready(model.state)
    build_s = time.perf_counter() - t0
    rows, ok = [], True
    t0 = time.perf_counter()
    for gold in gold_rows:
        model.update_n(cfg["sample_every"])
        nu = model.get_observables()[0]
        rel = abs(nu - gold["nu"]) / abs(gold["nu"])
        ok = ok and nu == nu and rel <= rtol
        rows.append({"time": gold["time"], "nu": nu, "nu_ref": gold["nu"], "rel": rel})
    stepping_s = time.perf_counter() - t0
    return {
        "passed": bool(ok and _on_platform(model.state, platform)),
        "rtol": rtol,
        "rows": rows,
        "build_s": round(build_s, 3),
        "stepping_s": round(stepping_s, 3),  # compile included: one pass
        **meter.since(start),
    }


def leg_served(meter, platform, run_dir, nx=129, ny=129, dt=2e-3,
               ras=(1e6, 3e6, 1e7), per_ra=8, base_steps=1024, step_stride=256,
               jitter_steps=64, solo_checks=2, nu_rtol=1e-3) -> dict:
    """One in-process ``SimServer`` (``ServeConfig`` defaults: 8 slots,
    256-step chunks) on a fresh run directory; a Ra sweep of
    ``len(ras) * per_ra`` requests with distinct seeds and horizons of
    ``base_steps`` + multiples of ``step_stride`` and ``jitter_steps``,
    submitted through ``SimServer.submit`` as examples/navier_rbc_serve.py
    does, then ``serve()`` to drain.  Gates: all done, none failed or lost;
    no retry, rollback or fault row in the journal; every campaign's state
    on ``platform``; ``solo_checks`` results (the shortest horizons) re-run
    solo in this process agree to ``nu_rtol`` relative Nu."""
    from rustpde_mpi_tpu import Navier2D
    from rustpde_mpi_tpu.config import ServeConfig
    from rustpde_mpi_tpu.serve import SimServer
    from rustpde_mpi_tpu.utils.journal import read_journal

    start = meter.mark()
    t0 = time.perf_counter()
    server = SimServer(ServeConfig(run_dir=run_dir))
    submitted = {}
    seed = 0
    for ra in ras:
        for k in range(per_ra):
            steps = base_steps + k * step_stride + (seed % 4) * jitter_steps
            req = server.submit(
                {"ra": ra, "pr": 1.0, "nx": nx, "ny": ny, "dt": dt,
                 "horizon": steps * dt, "seed": seed}
            )
            submitted[req.id] = {"ra": ra, "seed": seed}
            seed += 1
    summary = server.serve()
    serve_s = time.perf_counter() - t0
    served = meter.since(start)

    counts = summary["queue"]
    results = {rid: server.result(rid) for rid in submitted}
    events = read_journal(summary["journal"])
    unclean = sorted(
        {e["event"] for e in events if e.get("event") in _UNCLEAN_EVENTS}
    )
    campaign_devices = sorted(
        {d for e in events if e.get("event") == "campaign_start"
         for d in e.get("devices", ())}
    )
    all_done = (
        summary["outcome"] == "idle"
        and counts["done"] == len(submitted)
        and counts["failed"] == counts["queued"] == counts["running"] == 0
        and all(r is not None for r in results.values())
    )

    # isolation against solo ground truth
    solo, t0 = [], time.perf_counter()
    if all_done:
        shortest = sorted(results, key=lambda rid: results[rid]["steps"])
        for rid in shortest[:solo_checks]:
            res = results[rid]
            model = Navier2D(
                nx, ny, submitted[rid]["ra"], 1.0, res["dt"], 1.0, "rbc",
                periodic=False,
            )
            model.init_random(res["amp"] or 0.1, seed=res["seed"])
            model.update_n(res["steps"])
            nu_solo = float(model.eval_nu())
            solo.append(
                {"id": rid, "steps": res["steps"], "nu": res["nu"], "nu_solo": nu_solo,
                 "rel": abs(res["nu"] - nu_solo) / max(abs(nu_solo), 1e-30)}
            )
    solo_s = time.perf_counter() - t0
    return {
        "passed": bool(
            all_done
            and not unclean
            and campaign_devices
            and all(d.startswith(platform + ":") for d in campaign_devices)
            and len(solo) == solo_checks
            and all(s["rel"] <= nu_rtol for s in solo)
        ),
        "requests": len(submitted),
        "queue": counts,
        "outcome": summary["outcome"],
        "member_steps": summary["member_steps"],
        "unclean_journal_events": unclean,
        "campaign_devices": campaign_devices,
        "solo": solo,
        "nu_rtol": nu_rtol,
        "serve_s": round(serve_s, 3),  # builds + compiles + stepping: one pass
        "solo_s": round(solo_s, 3),
        "serve": served,
        **meter.since(start),
    }


def _placement(state, mesh_devices) -> dict:
    """Where a state pytree lives: do all leaves span the whole mesh, does
    any device hold a full copy of a leaf, bytes per device."""
    import jax

    want = set(mesh_devices)
    per_device: dict = {}
    spans, full_copy = True, False
    for leaf in jax.tree.leaves(state):
        spans = spans and set(leaf.sharding.device_set) == want
        for shard in leaf.addressable_shards:
            key = f"{shard.device.platform}:{shard.device.id}"
            per_device[key] = per_device.get(key, 0) + shard.data.nbytes
            full_copy = full_copy or shard.data.shape == leaf.shape
    return {
        "spans_mesh": spans,
        "full_copy_on_a_device": full_copy,
        "bytes_per_device": dict(sorted(per_device.items())),
    }


def leg_mesh(meter, platform, confined=(1025, 1025), periodic=(1024, 1025),
             ra=1e9, dt=1e-4, steps=64, re_rtol=1e-4, nu_rtol=1e-3) -> dict:
    """The pencil-sharded models over ALL devices against their one-device
    twins from the same IC: the confined flagship and the upstream's MPI
    shape (periodic, /root/reference/src/main.rs:17 — the ShardedConv
    manual path).  Gates: Re (a volume integral) within ``re_rtol`` and the
    plate-flux Nu within ``nu_rtol`` of the one-device run; after the steps
    every state leaf spans the whole mesh and no device holds a full copy of
    it.  (The parent adds: no "Involuntary full rematerialization" on
    stderr.)

    Why Nu gets the looser bound: at 1025^2 the wall derivative amplifies
    f32 roundoff, and after 64 steps the one-device TPU value already sits
    6e-4 from the f64 one (1.0014267 vs 1.0008009; the 4-chip mesh gave
    1.0012170, the CPU in f32 1.0008790 — PR 21).  Two f32 programs that
    only reassociate differently cannot be held closer than that."""
    import warnings

    import jax

    from rustpde_mpi_tpu import Navier2D
    from rustpde_mpi_tpu.parallel.mesh import ReplicatedPencilWarning, make_mesh

    if jax.device_count() < 2:
        return {"skipped": f"{jax.device_count()} device"}
    start = meter.mark()
    mesh = make_mesh()
    devices = list(mesh.devices.flat)
    out = {"devices": len(devices), "steps": steps, "re_rtol": re_rtol,
           "nu_rtol": nu_rtol, "models": {}}
    ok = True
    for name, ctor, (nx, ny) in (
        ("confined", Navier2D.new_confined, confined),
        ("periodic", Navier2D.new_periodic, periodic),
    ):
        row = {"grid": [nx, ny]}
        nus, res = {}, {}
        for label, m in (("one_device", None), ("mesh", mesh)):
            t0 = time.perf_counter()
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always", ReplicatedPencilWarning)
                model = ctor(nx, ny, ra, 1.0, dt, 1.0, "rbc", mesh=m)
                _smooth_ic(model)
            model.update_n(steps)
            jax.block_until_ready(model.state)
            nus[label], _, res[label], _ = model.get_observables()[:4]
            row[f"{label}_s"] = round(time.perf_counter() - t0, 3)
            if m is not None:
                row.update(_placement(model.state, devices))
                row["replicated_warnings"] = sorted(
                    {str(w.message) for w in caught
                     if issubclass(w.category, ReplicatedPencilWarning)}
                )[:3]
                row["on_platform"] = _on_platform(model.state, platform)
        row["nu"], row["re"] = nus, res
        row["nu_rel"] = abs(nus["mesh"] - nus["one_device"]) / abs(nus["one_device"])
        row["re_rel"] = abs(res["mesh"] - res["one_device"]) / abs(res["one_device"])
        row["passed"] = bool(
            _finite((*nus.values(), *res.values()))
            and row["nu_rel"] <= nu_rtol
            and row["re_rel"] <= re_rtol
            and row["spans_mesh"]
            and not row["full_copy_on_a_device"]
            and row["on_platform"]
        )
        ok = ok and row["passed"]
        out["models"][name] = row
    return {"passed": ok, **out, **meter.since(start)}


# -- child ---------------------------------------------------------------------


def _versions() -> dict:
    from importlib import metadata

    def version(pkg):
        try:
            return metadata.version(pkg)
        except metadata.PackageNotFoundError:
            return None

    return {p: version(p) for p in ("jax", "jaxlib", "libtpu")}


def _run_legs(precision: str, meter, run_root: str) -> dict:
    """The sizes the script runs — nothing here is configurable."""
    with open(os.path.join(_HERE, "PARITY.json"), encoding="utf-8") as fh:
        parity = json.load(fh)
    gold = parity["nu_f64"][:2]  # t=0.1 and t=0.2: 100 steps
    legs = {}
    if precision == "f32":
        legs["flagship"] = leg_flagship(meter, "tpu")
        legs["parity"] = leg_parity(meter, "tpu", parity["config"], gold, rtol=1e-4)
        os.makedirs(run_root, exist_ok=True)
        legs["served"] = leg_served(
            meter, "tpu", tempfile.mkdtemp(prefix="serve_", dir=run_root)
        )
        legs["mesh"] = leg_mesh(meter, "tpu")
    else:
        legs["parity"] = leg_parity(meter, "tpu", parity["config"], gold, rtol=1e-6)
        # libtpu emulates f64: 16 steps, one dispatch, finite
        legs["flagship"] = leg_flagship(meter, "tpu", steps=16, dispatches=1)
    return legs


def child_main(precision: str) -> int:
    # the device question comes first, before any model is built
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: jax.devices()[0].platform is "
            f"{devices[0].platform!r} ({devices[0].device_kind}), not 'tpu' — "
            "this script proves the path on the chip and has no CPU mode",
            file=sys.stderr,
        )
        return 3
    from rustpde_mpi_tpu import config

    want_x64 = precision == "f64"
    if config.X64 != want_x64:
        print(f"chip_smoke: child asked for {precision}, X64={config.X64}", file=sys.stderr)
        return 4
    from rustpde_mpi_tpu.telemetry import tracing

    if not tracing.enabled():
        print("chip_smoke: the recorder is off (RUSTPDE_TRACE=0 or RUSTPDE_TELEMETRY=0): the "
              "compile gates read the program's own count", file=sys.stderr)
        return 4
    cache_dir = config.ensure_compile_cache()
    meter = CompileMeter()
    t0 = time.perf_counter()
    legs = _run_legs(precision, meter, os.path.join(_HERE, "data", "chip_smoke"))
    payload = {
        "precision": precision,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
        "versions": _versions(),
        "compile_cache_dir": cache_dir,
        "legs": legs,
        "child_wall_s": round(time.perf_counter() - t0, 1),
    }
    print(json.dumps(payload))
    return 0


# -- parent --------------------------------------------------------------------


def _run_child(precision: str, deadline: float) -> dict:
    """One child to its end (or killed at the deadline); returns its payload,
    with ``error`` set when it failed or printed none."""
    os.makedirs(_OUT, exist_ok=True)
    err_path = os.path.join(_OUT, f"chip_smoke_{precision}.stderr")
    env = dict(os.environ, RUSTPDE_X64="1" if precision == "f64" else "0")
    remaining = max(5.0, deadline - time.monotonic())
    with open(err_path, "wb") as err:
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child", precision],
                stdout=subprocess.PIPE, stderr=err, env=env, cwd=_HERE,
                timeout=remaining,
            )
            rc, out = proc.returncode, proc.stdout.decode(errors="replace")
        except subprocess.TimeoutExpired as exc:  # run() killed the child
            rc, out = 124, (exc.stdout or b"").decode(errors="replace")
    with open(err_path, encoding="utf-8", errors="replace") as fh:
        stderr = fh.read()
    sys.stderr.write(stderr[-4000:])
    payload = None
    for line in reversed(out.strip().splitlines()):
        if line.startswith("{"):
            try:
                payload = json.loads(line)
            except ValueError:
                pass
            break
    if rc != 0 or payload is None:
        return {"error": f"child {precision} rc={rc}", "rc": rc}
    payload["rematerialization_on_stderr"] = _REMAT in stderr
    return payload


def main() -> int:
    deadline = time.monotonic() + _DEADLINE_S
    t0 = time.monotonic()
    children = {}
    for precision in ("f32", "f64"):
        children[precision] = _run_child(precision, deadline)
        if "error" in children[precision]:
            # no result is printed for a child that found no chip, died, or
            # could not even import the program
            print(f"chip_smoke: {children[precision]['error']}", file=sys.stderr)
            return children[precision]["rc"] or 1
    ok = all(
        leg.get("passed", "skipped" in leg)
        for child in children.values()
        for leg in child["legs"].values()
    ) and not any(c["rematerialization_on_stderr"] for c in children.values())
    first = children["f32"]
    verdict = {"ok": bool(ok), "device": first["device"]}
    report = {
        **verdict,
        "versions": first["versions"],
        "compile_cache_dir": first["compile_cache_dir"],
        "timings": "smoke timings: one reading each, never a benchmark result",
        "mesh": first["legs"].pop("mesh"),
        "f32": first,
        "f64": children["f64"],
        "wall_s": round(time.monotonic() - t0, 1),
    }
    with open(os.path.join(_OUT, "chip_smoke.json"), "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    # the report first; the LAST line is the verdict and holds exactly
    # {"ok", "device": {"platform", "kind", "count"}} — the driver parses it
    print(json.dumps({"report": report}))
    print(json.dumps(verdict), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--child":
        sys.exit(child_main(sys.argv[2]))
    sys.exit(main())
