"""Profiling and benchmarking utilities.

Fills the reference's observability gap (SURVEY.md S5: wall-clock timing was
manual ``Instant`` prints, /root/reference/src/main.rs:27-33; no tracing):

* :func:`benchmark_steps` — slope-timed step rate over two window lengths,
  each ended by ``block_until_ready``.
* :class:`StepTimer` — lightweight per-chunk timing history a driver loop or
  callback can sample (the per-step timing API).
* :func:`trace` — ``jax.profiler`` trace context for XLA-level profiles.
* :func:`step_flops` / :func:`mfu_estimate` — XLA cost-analysis FLOPs of one
  model step (analytic GEMM-count fallback) and the resulting model-flops
  utilization against the published peak of the ``device_kind`` the chip
  reports (:data:`DEVICE_PEAKS`; an unknown kind is an error).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass

import numpy as np


def _sync(model) -> None:
    """Wait until the device has finished every dispatched step."""
    import jax

    # models without .state (e.g. Swift-Hohenberg) expose .theta
    jax.block_until_ready(model.state if hasattr(model, "state") else model.theta)


def benchmark_steps(model, steps: int, warmup: int | None = None, reps: int = 3) -> dict:
    """Slope-timed step rate.

    Times ``model.update_n`` at two window lengths (L = ``steps`` and 4L, both
    pre-compiled) and reports the slope ``(t_4L − t_L) / 3L`` — the per-step
    device time with the dispatch path's *fixed* per-call cost (host launch,
    the scan-bucket chain of ``run_scanned``, the final sync) cancelled; a
    single-window measurement folds that cost into the step time, which
    matters at the small grids where a step is tens of microseconds.
    Median of ``reps`` slope estimates; the fixed overhead is reported
    separately.

    Returns {steps_per_sec, ms_per_step, fixed_overhead_ms, elapsed_s,
    steps (timed window L), steps_total (all executed), slope_reps_ms}.
    """
    L = int(steps)
    L4 = 4 * L
    if warmup is None:
        warmup = L
    executed = 0
    if warmup:
        model.update_n(warmup)
        _sync(model)
        executed += warmup
    # compile/warm both window lengths before timing
    for n in (L, L4):
        model.update_n(n)
        _sync(model)
        executed += n
    slopes, fixeds = [], []
    t_all = time.perf_counter()
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        model.update_n(L)
        _sync(model)
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        model.update_n(L4)
        _sync(model)
        t4 = time.perf_counter() - t0
        executed += L + L4
        slopes.append((t4 - t1) / (L4 - L))
        fixeds.append(t1 - L * slopes[-1])
    elapsed = time.perf_counter() - t_all
    slope = float(np.median(slopes))
    if slope <= 0:  # trivial model / timer noise: fall back to the naive rate
        slope = t4 / L4
    res = {
        "steps_per_sec": 1.0 / slope,
        "ms_per_step": 1e3 * slope,
        "fixed_overhead_ms": 1e3 * float(np.median(fixeds)),
        "elapsed_s": elapsed,
        "steps": L,
        "steps_total": executed,
        "slope_reps_ms": [round(1e3 * s, 4) for s in slopes],
    }
    # a batched ensemble (models/ensemble.py) advances K members per step:
    # aggregate member-steps/s is the number that compares against K solo
    # runs (its MFU comes from mfu_estimate, whose step FLOPs carry the K
    # factor through the vmapped jaxpr's batched dot_generals)
    k = int(getattr(model, "ensemble_size", 0) or 0)
    if k:
        res["ensemble_size"] = k
        res["member_steps_per_sec"] = k * res["steps_per_sec"]
        res["ms_per_member_step"] = res["ms_per_step"] / k
    return res


class StepTimer:
    """Rolling per-chunk step-rate history.

    Use from a driver loop:  ``timer.tick(n_steps)`` after each dispatch;
    ``timer.summary()`` gives mean/min/max steps/s over the recorded chunks.
    """

    def __init__(self):
        self.history: list[tuple[int, float]] = []  # (steps, seconds)
        self._last = time.perf_counter()

    def reset(self) -> None:
        self._last = time.perf_counter()

    def tick(self, steps: int) -> float:
        now = time.perf_counter()
        dt = now - self._last
        self._last = now
        self.history.append((steps, dt))
        return steps / dt if dt > 0 else float("inf")

    def summary(self) -> dict:
        if not self.history:
            return {"chunks": 0}
        rates = [s / t for s, t in self.history if t > 0]
        return {
            "chunks": len(self.history),
            "steps": sum(s for s, _ in self.history),
            "seconds": sum(t for _, t in self.history),
            "steps_per_sec_mean": float(np.mean(rates)),
            "steps_per_sec_min": float(np.min(rates)),
            "steps_per_sec_max": float(np.max(rates)),
        }


@contextlib.contextmanager
def trace(logdir: str = "/tmp/jax-trace"):
    """``jax.profiler`` trace context (view with TensorBoard/XProf/Perfetto,
    or read the ``.xplane.pb`` with ``jax.profiler.ProfileData``).  A
    profiler that will not start, or will not stop, raises: a caller that
    asked for a trace must not get a silent run without one."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def device_memory_stats() -> dict:
    """Per-local-device backend memory stats, None-safe by contract:
    ``{device_label: stats_dict_or_None}`` where ``stats_dict`` is whatever
    ``Device.memory_stats()`` reports (``bytes_in_use`` /
    ``peak_bytes_in_use`` on TPU/GPU) and None where the backend exposes
    nothing (the CPU backend) — callers must treat a missing dict as
    "no data", never as zero.  The telemetry layer's device-memory
    watermark gauges (telemetry/compile_log.py) read through this one
    helper so the None-handling lives in one place."""
    out = {}
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return out
    for dev in devices:
        label = f"{dev.platform}:{dev.id}"
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        out[label] = dict(stats) if stats else None
    return out


# ---------------------------------------------------------------------------
# FLOPs / MFU
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DevicePeak:
    """Published peaks of one chip: bf16 matmul FLOP/s, HBM bytes/s, and
    where the numbers come from."""

    bf16_flops: float
    hbm_bytes_per_s: float
    source: str


#: ONE table, keyed by the ``device_kind`` string the chip itself reports
#: (``jax.devices()[0].device_kind``).  A kind that is not here is an error
#: (:class:`UnknownDevicePeak`), never a default.
DEVICE_PEAKS: dict[str, DevicePeak] = {
    # what a v5e calls itself (chip_smoke.py run of PR 21, jax 0.9.0)
    "TPU v5 lite": DevicePeak(
        bf16_flops=197e12,
        hbm_bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}

#: bf16 MXU passes one f32 matmul costs at each jax matmul precision: a
#: utilization against the bf16 peak is only comparable across precisions
#: together with this count.  f64 matmuls are emulated in software on a TPU
#: and have no fixed pass count.
BF16_PASSES = {"default": 1, "high": 3, "highest": 6}


class UnknownDevicePeak(LookupError):
    """The attached ``device_kind`` has no entry in :data:`DEVICE_PEAKS`."""


def device_peak(device_kind: str | None = None) -> DevicePeak:
    """The :data:`DEVICE_PEAKS` row for ``device_kind`` (default: what the
    first device reports).  Raises :class:`UnknownDevicePeak` otherwise."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise UnknownDevicePeak(
            f"no published peak recorded for device_kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add it to "
            "utils/profiling.DEVICE_PEAKS with its source"
        ) from None


#: analytic per-invocation flops of named Pallas kernels
#: (:func:`register_pallas_flops`): the jaxpr walk below sees a
#: ``pallas_call`` as ONE opaque eqn, so without this the MFU numbers
#: (serve_mfu gauge, bench rows) silently under-report on kernel paths.
#: Primary accounting recurses into the kernel jaxpr and multiplies by the
#: grid size (exact for GEMM kernels); the registry overrides by kernel
#: name for kernels whose body the walk cannot price (DMA/collective
#: kernels, recurrences whose flops are not dot_generals).
PALLAS_FLOPS: dict[str, float] = {}


def register_pallas_flops(name: str, flops: float) -> None:
    """Register the analytic flops of one invocation of the Pallas kernel
    dispatched under ``name`` (the ``pallas_call`` name) — kernels with
    shape-dependent cost should re-register at build time (last value
    wins; ops/pallas_conv.build_model_convs does)."""
    PALLAS_FLOPS[name] = float(flops)


def _pallas_eqn_flops(eqn) -> float:
    """Flops of one ``pallas_call`` eqn: registry by kernel name first, else
    the kernel-body dot count times the grid size."""
    import math

    name = eqn.params.get("name")
    if name in PALLAS_FLOPS:
        return PALLAS_FLOPS[name]
    grid_mapping = eqn.params.get("grid_mapping")
    grid = math.prod(getattr(grid_mapping, "grid", ()) or (1,))
    inner = eqn.params.get("jaxpr")
    if inner is not None and hasattr(inner, "eqns"):
        return grid * _jaxpr_dot_flops(inner)
    return 0.0


def _jaxpr_dot_flops(jaxpr) -> float:
    """Exact MXU flops of a jaxpr: walk every dot_general (recursing into
    scan/cond/pjit sub-jaxprs) and sum 2*batch*M*N*K from the operand
    shapes; ``pallas_call`` bodies are priced via :func:`_pallas_eqn_flops`
    (grid-scaled kernel dot count, registry override)."""
    import math

    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += _pallas_eqn_flops(eqn)
            continue
        if eqn.primitive.name == "dot_general":
            a = eqn.invars[0].aval
            b = eqn.invars[1].aval
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            k = math.prod(a.shape[i] for i in lc)
            batch = math.prod(a.shape[i] for i in lb)
            m = math.prod(
                d for i, d in enumerate(a.shape) if i not in lc and i not in lb
            )
            n = math.prod(
                d for i, d in enumerate(b.shape) if i not in rc and i not in rb
            )
            total += 2.0 * batch * m * n * k
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for v in vals:
                inner = getattr(v, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    total += _jaxpr_dot_flops(inner)
                elif hasattr(v, "eqns"):
                    total += _jaxpr_dot_flops(v)
    return total


def step_flops(model, method: str = "auto") -> float | None:
    """FLOPs of one time step: XLA cost analysis when the backend exposes it,
    else an exact jaxpr-level dot_general count (exact for this
    GEMM-dominated workload, and it tracks every fold/fusion the layout
    actually executes), else the legacy analytic estimate.

    ``method="jaxpr"`` skips the cost-analysis pass (which COMPILES a fresh
    jit of the step) and goes straight to the trace-only dot count — the
    cheap form the serve scheduler's live MFU gauge uses per campaign."""
    import jax

    example = None
    if method == "jaxpr":
        try:
            example = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), model.state
            )
        except Exception:
            return _analytic_step_flops(model)
    else:
        try:
            example = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), model.state
            )
            lowered = jax.jit(model._make_step()).lower(example)
            cost = lowered.compile().cost_analysis()
            if isinstance(cost, (list, tuple)):  # newer jaxlib: one dict per device
                cost = cost[0] if cost else None
            if cost and cost.get("flops"):
                return float(cost["flops"])
        except Exception:
            pass
    try:
        closed = jax.make_jaxpr(model._make_step())(example)
        return _jaxpr_dot_flops(closed.jaxpr)
    except Exception:
        pass
    return _analytic_step_flops(model)


def _analytic_step_flops(model) -> float:
    """GEMM-count estimate for the dense-transform TPU path of one Navier2D
    step.  Per 2-D dense transform: 2 GEMMs = 2 * 2*n^3 flops at n x n.
    Counted per step (navier.py _make_step): 2 velocity backwards, 6
    convection gradient synth + 3 forwards, 3 implicit ADI solves (matvec +
    2 dense 1-D solves each ~ 3 GEMMs), Poisson fast-diag (4 GEMMs), plus
    elementwise O(n^2) terms (ignored)."""
    from ..ops.folded import folding_enabled

    nx, ny = model.nx, model.ny
    n = 0.5 * (nx + ny)
    gemms = (
        2 * 2  # velocity backwards
        + 6 * 2  # conv gradient backward_orthos
        + 3 * 2  # conv forwards
        + 3 * 3  # ADI solves (precond matvecs + inverse GEMMs)
        + 4  # fast-diag Poisson (parity-interleaved modal maps)
    )
    # folding factor from the matrices the model actually built: average the
    # per-matrix flops_factor over the transform pair of each variable space
    # (split-Fourier axes and mixed-BC bases report 1.0 or fold their own
    # way, so "hc"/periodic models are accounted correctly).  Sep-layout
    # spaces report the factors of their sep device matrices (same 0.5 GEMM
    # halving, measured from the actual impl blocks) — the natural-layout
    # cached matrices are never built there.
    factors = []
    for attr in ("temp_space", "velx_space", "field_space"):
        space = getattr(model, attr, None)
        if space is None:
            continue
        for axis, base in enumerate(getattr(space, "bases", ())):
            if getattr(space, "sep", (False, False))[axis]:
                cache = getattr(base, "_sep_cache", {})
                keys = ("fwd", "bwd") if cache else ()
                for key in keys:
                    fm = cache.get(key)
                    if fm is not None and hasattr(fm, "flops_factor"):
                        factors.append(fm.flops_factor)
                continue
            if not folding_enabled():
                factors.append(1.0)
                continue
            for mat_attr in ("_fwd_matrix", "_bwd_matrix", "_fwd_dev", "_bwd_dev"):
                try:
                    fm = getattr(base, mat_attr)
                except (ValueError, AttributeError):
                    continue
                if hasattr(fm, "flops_factor"):
                    factors.append(fm.flops_factor)
    factor = float(np.mean(factors)) if factors else (0.5 if folding_enabled() else 1.0)
    # an ensemble's step advances K members (the jaxpr paths above count this
    # via batched dot dims; the analytic estimate must scale explicitly)
    k = max(1, int(getattr(model, "ensemble_size", 1) or 1))
    return k * gemms * factor * 2.0 * n**3


def mfu_estimate(model, steps_per_sec: float, device_kind: str | None = None) -> dict:
    """Model-flops utilization: step FLOPs x rate over the bf16 peak of the
    attached chip (``device_kind`` overrides what the chip reports — tests
    and offline reductions).  The dict names the peak it divided by, its
    source, and how many bf16 passes the configured matmul precision
    implies (None under X64: f64 is emulated).  Raises
    :class:`UnknownDevicePeak` for a chip without a table entry."""
    import jax

    from .. import config

    kind = device_kind or jax.devices()[0].device_kind
    peak = device_peak(kind)
    flops = step_flops(model)
    return {
        "flops_per_step": flops,
        "achieved_flops": flops * steps_per_sec,
        "device_kind": kind,
        "peak": "bf16",
        "peak_flops": peak.bf16_flops,
        "peak_source": peak.source,
        "matmul_precision": config.MATMUL_PRECISION,
        "bf16_passes": None if config.X64 else BF16_PASSES.get(config.MATMUL_PRECISION),
        "mfu": flops * steps_per_sec / peak.bf16_flops,
    }
