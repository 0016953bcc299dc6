"""On-chip function checks that are not the main path (chip_smoke.py is):

    python scripts/chip_checks.py kernels [row ...]  # each knob-selected Pallas kernel
    python scripts/chip_checks.py riders    # optional in-scan riders at 129^2

``kernels``: at 129^2 and 1025^2, build and step once under each of
RUSTPDE_CONV_KERNEL=pallas, RUSTPDE_STEP_KERNEL=pallas, the ``pallas`` banded
solver method and (with more than one device) RUSTPDE_TRANSPOSE=ring,
natively, against the dense path.  A row either compiled and matched, or
names the typed refusal.  ``riders``: set_stability / set_stats /
set_integrity and the device-memory gauges, once each.  Precision follows
RUSTPDE_X64 (an import-time switch): run it once per precision.

Function only — nothing here is timed.  Like chip_smoke.py it refuses to run
without a TPU.  One JSON object on the last stdout line, copied to
``chiprun_out/``.
"""

import json
import os
import sys
import traceback

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# README "Interpreter-mode testing story" states ~1e-5 relative in f32 for
# the conv chain (one reassociation) and fp-epsilon in f64, on the
# physical fields; the stage kernels fold the Helmholtz inverse into their
# operands and sit at 2e-5 in the f32 CPU interpreter.  Gate one decade
# above, report the number.
_TOL = {"float32": 1e-4, "float64": 1e-12}
# (grid, Ra, dt): the parity configuration and the flagship
_SIZES = ((129, 1e7, 2e-3), (1025, 1e9, 1e-4))


def _attempt(fn) -> dict:
    """Run ``fn`` and report how it ended — a typed refusal is an expected
    outcome here, so it is recorded, not raised."""
    try:
        return fn()
    except Exception as exc:  # noqa: BLE001 — the report IS the product
        text = " ".join(str(exc).split())
        return {
            "ok": False,
            "error": type(exc).__name__,
            "message": text[:600],
            "where": traceback.extract_tb(exc.__traceback__)[-1].name,
        }


def _model(n, ra, dt, periodic=False, mesh=None, **env):
    from rustpde_mpi_tpu import Navier2D

    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        if periodic:
            model = Navier2D.new_periodic(n - 1, n, ra, 1.0, dt, 1.0, "rbc", mesh=mesh)
        else:
            model = Navier2D.new_confined(n, n, ra, 1.0, dt, 1.0, "rbc", mesh=mesh)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    model.set_velocity(0.1, 2.0, 2.0)
    model.set_temperature(0.1, 2.0, 2.0)
    return model


def _max_rel(state, ref) -> dict:
    """Max deviation per group of leaves, on the physical-field scale.  The
    gate is on the fields (temp, velx, vely).  The pressure leaves are
    reported apart: ``pres`` carries a 1/dt, and at 1025^2 EVERY f32 path
    (dense included) sits 1-2e-3 from the f64 step there while the fields
    agree to ~5e-6 (CPU attribution, CHANGES.md PR 21)."""
    import numpy as np

    scale = max(
        float(np.abs(np.asarray(leaf)).max()) for leaf in (ref.temp, ref.velx, ref.vely)
    )

    def rel(names):
        return max(
            float(
                np.abs(np.asarray(getattr(state, k)) - np.asarray(getattr(ref, k))).max()
                / max(float(np.abs(np.asarray(getattr(ref, k))).max()), scale, 1e-30)
            )
            for k in names
        )

    return {"fields": rel(("temp", "velx", "vely")), "pressure": rel(("pres", "pseu"))}


def check_kernels(only=()) -> dict:
    import jax
    import numpy as np

    from rustpde_mpi_tpu import Space2, cheb_dirichlet, config
    from rustpde_mpi_tpu.solver import HholtzAdi

    tol = _TOL[np.dtype(config.real_dtype()).name]
    out = {"tolerance": tol, "sizes": {}}
    # f64 is there to show the typed refusal: the parity size is enough
    for n, ra, dt in _SIZES[:1] if config.X64 else _SIZES:
        dense = _model(n, ra, dt)
        dense.update_n(1)

        def stepped(env, dense=dense, n=n, ra=ra, dt=dt):
            model = _model(n, ra, dt, **env)
            model.update_n(1)
            rel = _max_rel(model.state, dense.state)
            return {"ok": bool(rel["fields"] <= tol), "compiled": True, "max_rel_vs_dense": rel}

        checks = {
            "conv_kernel": lambda: stepped({"RUSTPDE_CONV_KERNEL": "pallas"}),
            "step_kernel": lambda: stepped({"RUSTPDE_STEP_KERNEL": "pallas"}),
        }

        def banded(n=n):
            space = Space2(cheb_dirichlet(n), cheb_dirichlet(n))
            rhs = jax.numpy.asarray(
                np.random.default_rng(0).standard_normal((n, n)),
                dtype=config.real_dtype(),
            )
            ref = np.asarray(HholtzAdi(space, (1e-3, 1e-3), method="dense").solve(rhs))
            got = np.asarray(HholtzAdi(space, (1e-3, 1e-3), method="pallas").solve(rhs))
            rel = float(np.abs(got - ref).max() / np.abs(ref).max())
            return {"ok": bool(rel <= tol), "compiled": True, "max_rel_vs_dense": rel}

        checks["banded_pallas"] = banded

        def ring(n=n, ra=ra, dt=dt):
            from rustpde_mpi_tpu.parallel.decomp import Decomp2d

            if jax.device_count() < 2:
                return {"skipped": f"{jax.device_count()} device"}
            dec = Decomp2d((n, n))
            arr = jax.numpy.asarray(
                np.random.default_rng(1).standard_normal((n, n)),
                dtype=config.real_dtype(),
            )
            a2a = np.asarray(dec.transpose_x_to_y(arr, method="alltoall"))
            got = np.asarray(dec.transpose_x_to_y(arr, method="ring"))
            back = np.asarray(dec.transpose_y_to_x(jax.numpy.asarray(got), method="ring"))
            same = bool(np.array_equal(got, a2a) and np.array_equal(back, np.asarray(arr)))
            # and inside the model: the periodic mesh path (ShardedConv)
            # reads the knob at build
            states = {}
            for method in ("alltoall", "ring"):
                model = _model(
                    n, ra, dt, periodic=True, mesh=dec.mesh, RUSTPDE_TRANSPOSE=method
                )
                model.update_n(1)
                states[method] = model.state
            rel = _max_rel(states["ring"], states["alltoall"])
            return {
                "ok": bool(same and rel["fields"] <= tol),
                "compiled": True,
                "bit_equal_to_alltoall": same,
                "model_step_max_rel_vs_alltoall": rel,
            }

        checks["ring_transpose"] = ring
        out["sizes"][str(n)] = {
            name: _attempt(fn) for name, fn in checks.items() if not only or name in only
        }
    return out


def check_riders() -> dict:
    import jax
    import numpy as np

    from rustpde_mpi_tpu import Navier2D
    from rustpde_mpi_tpu.config import IntegrityConfig, StabilityConfig, StatsConfig
    from rustpde_mpi_tpu.telemetry import compile_log
    from rustpde_mpi_tpu.utils.profiling import device_memory_stats

    def model():
        m = Navier2D(129, 129, 1e7, 1.0, 2e-3, 1.0, "rbc", periodic=False)
        m.init_random(0.01, seed=0)
        return m

    plain = model()
    plain.update_n(32)
    ref = np.asarray(plain.state.temp)

    def same_trajectory(m) -> bool:
        # every rider only READS the state: bit-identical to the plain run
        return bool(np.array_equal(np.asarray(m.state.temp), ref))

    def integrity():
        m = model()
        m.set_integrity(IntegrityConfig())
        m.update_n(32)
        d1 = int(m.state_digest_async().result())
        d2 = int(m.state_digest_async().result())
        return {"ok": d1 == d2 and same_trajectory(m), "digest": d1}

    def stats():
        m = model()
        m.set_stats(StatsConfig(stride=4))
        m.update_n(32)
        summary = m.stats_summary()
        return {
            "ok": bool(summary and summary.get("samples", 0) > 0 and same_trajectory(m)),
            "samples": summary and summary.get("samples"),
        }

    def stability():
        m = model()
        m.set_stability(StabilityConfig())
        status = m.update_n(32)
        return {
            "ok": bool(status is not None and same_trajectory(m)),
            "cfl_max": float(getattr(status, "cfl_max", float("nan"))),
        }

    def memory():
        stats = device_memory_stats()
        reported = compile_log.update_device_memory_gauges()
        first = next(iter(stats.values()))
        return {
            "ok": reported == jax.local_device_count() and bool(first),
            "devices_reporting": reported,
            "keys": sorted(first)[:8] if first else None,
            "peak_bytes_in_use": first.get("peak_bytes_in_use") if first else None,
        }

    return {
        "set_integrity": _attempt(integrity),
        "set_stats": _attempt(stats),
        "set_stability": _attempt(stability),
        "device_memory_gauges": _attempt(memory),
    }


def main() -> int:
    what = sys.argv[1] if len(sys.argv) > 1 else ""
    if what not in ("kernels", "riders"):
        print(__doc__, file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_checks: platform is {dev.platform!r}, not 'tpu'", file=sys.stderr)
        return 3
    from rustpde_mpi_tpu import config

    config.ensure_compile_cache()
    result = {
        "check": what,
        "precision": "f64" if config.X64 else "f32",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "result": check_kernels(sys.argv[2:]) if what == "kernels" else check_riders(),
    }
    os.makedirs(os.path.join(_REPO, "chiprun_out"), exist_ok=True)
    path = os.path.join(
        _REPO, "chiprun_out", f"chip_checks_{what}_{result['precision']}.json"
    )
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
