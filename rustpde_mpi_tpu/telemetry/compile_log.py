"""Compile & device attribution: where the fleet's non-stepping time goes.

The cold-start ROADMAP item needs numbers nobody records today: which
compat key paid how much build/jit wall, how often a key RE-compiled
(restart, elastic re-plan, dt re-bucket), and how long a request waits
between campaign open and the first committed chunk.  This module is the
recording half — the seams call in, the metrics registry carries the
labeled series, the journal gets one row per observation:

* :func:`observe_build` — wraps the model-build seam
  (``workloads.registry.build_model_for_key``): per-compat-key build wall
  time histogram + recompile counter (first build of a key in a process is
  a compile, every later one a RE-compile),
* :func:`observe_entry_compile` — wraps the jit-entry-point seam
  (``models.campaign._compile_entry_points``): per-model-kind lowering/jit
  wall, counted separately because dt-ladder re-jits re-enter it without a
  model rebuild,
* :func:`observe_first_chunk` — time-to-first-chunk per compat key (the
  scheduler stamps campaign open and the first committed chunk),
* :func:`update_device_memory_gauges` — live per-device memory watermarks
  from ``jax.local_devices()[i].memory_stats()`` where the backend exposes
  them (None-safe: the CPU backend reports nothing, the gauges just stay
  unset),
* :class:`ProfilerCapture` — the on-demand ``jax.profiler`` hook behind
  ``POST /profile?seconds=N`` (capped by ``RUSTPDE_PROFILE_MAX_S``), also
  fired as a ONE-SHOT when the ThroughputMonitor reports ``perf_degraded``
  (observability closing the loop on robustness: the capture of the slow
  window lands next to the journal row that flagged it).

Everything here is host-side bookkeeping around seams that already exist;
the bit-identical / ≤2% overhead telemetry contract is unchanged.
"""

from __future__ import annotations

import os
import threading
import time as _time

from .. import config as _config
from . import metrics as _tm

_builds: dict[str, int] = {}  # compat-key tag -> in-process build count
_last_walls: dict[str, float] = {}  # compat-key tag -> last build wall (phase="build")
_warm_pool: dict[str, int] = {"hit": 0, "miss": 0, "evict": 0, "aot": 0}
_lock = threading.Lock()


def key_tag(key) -> str:
    """The short stable label for a compat key — the same sha1-12 tag the
    scheduler's campaign directories use, so metrics, journal rows and
    on-disk campaign state all name a bucket identically."""
    import hashlib

    return hashlib.sha1(repr(tuple(key)).encode()).hexdigest()[:12]


def observe_build(key, wall_s: float, kind: str = "", phase: str = "build") -> dict:
    """Record one model build for a compat key; returns the journal-ready
    payload (the caller owns the journal, root-ness and all).

    ``phase`` disambiguates the layered observers around one campaign open —
    ``build`` (the registry's model construction, the only phase that bumps
    the per-key build/recompile accounting), ``entry_points`` (the
    scheduler's campaign-level remainder: ensemble wrap + arming, journaled
    so TTFC attribution SUMS across rows instead of double-counting the
    build wall ~2x), and ``aot`` (warm-pool ahead-of-time builds)."""
    tag = key_tag(key)
    if phase == "build":
        with _lock:
            _builds[tag] = _builds.get(tag, 0) + 1
            count = _builds[tag]
            _last_walls[tag] = wall_s
    else:
        with _lock:
            count = _builds.get(tag, 1)
    _tm.histogram(
        "compile_build_seconds",
        "model build + jit wall per compat key",
        key=tag,
        phase=phase,
    ).observe(wall_s)
    if phase == "build" and count > 1:
        _tm.counter(
            "compile_recompiles_total",
            "model rebuilds of an already-built compat key",
            key=tag,
        ).inc()
    return {
        "event": "compile_build",
        "key_tag": tag,
        "kind": kind,
        "phase": phase,
        "wall_s": round(wall_s, 4),
        "builds": count,
        "recompile": phase == "build" and count > 1,
    }


def build_counts() -> dict:
    """Per-key in-process build counts (read by tests)."""
    with _lock:
        return dict(_builds)


def last_build_wall(key) -> float:
    """The most recent phase="build" wall for a compat key (0.0 when the
    key never built in this process) — the scheduler subtracts it from its
    campaign-open window so the ``entry_points`` row carries only the
    remainder and the per-key rows sum to the true TTFC."""
    with _lock:
        return _last_walls.get(key_tag(key), 0.0)


def observe_warm_pool(event: str, key=None, k: int | None = None, **extra) -> dict:
    """Warm-pool accounting (serve/warmpool.py): ``event`` is one of
    ``hit`` / ``miss`` / ``evict`` / ``aot``; returns the journal-ready
    payload.  Counters ride the shared metrics registry so the journal and
    the hit-rate gates read one source of truth."""
    with _lock:
        _warm_pool[event] = _warm_pool.get(event, 0) + 1
    _tm.counter(
        "serve_warm_pool_events_total",
        "warm campaign pool events (hit/miss/evict/aot)",
        event=event,
    ).inc()
    payload = {
        "event": {
            "hit": "warm_pool_hit",
            "miss": "warm_pool_miss",
            "evict": "warm_pool_evict",
            "aot": "aot_build",
        }.get(event, f"warm_pool_{event}"),
    }
    if key is not None:
        payload["key_tag"] = key_tag(key)
    if k is not None:
        payload["k"] = int(k)
    payload.update(extra)
    return payload


def warm_pool_counts() -> dict:
    """Warm-pool event counts (read by tests), a copy."""
    with _lock:
        return dict(_warm_pool)


def observe_entry_compile(model_kind: str, wall_s: float) -> None:
    """One jit-entry-point compile (step/observables hoist+jit): re-entered
    by dt-ladder re-jits without a model rebuild, so counted separately."""
    _tm.histogram(
        "model_entry_compile_seconds",
        "entry-point hoist+jit wall per model kind",
        model=model_kind,
    ).observe(wall_s)
    _tm.counter(
        "model_entry_compiles_total",
        "entry-point compile passes per model kind",
        model=model_kind,
    ).inc()


def observe_first_chunk(key, wall_s: float) -> dict:
    """Time-to-first-chunk: campaign open (model build start) to the first
    committed chunk — the cold-start item's gate metric."""
    tag = key_tag(key)
    _tm.histogram(
        "serve_time_to_first_chunk_seconds",
        "campaign open to first committed chunk per compat key",
        key=tag,
    ).observe(wall_s)
    return {
        "event": "first_chunk",
        "key_tag": tag,
        "wall_s": round(wall_s, 4),
    }


# -- device memory watermarks --------------------------------------------------


def update_device_memory_gauges() -> int:
    """Refresh ``device_memory_bytes_in_use`` / ``device_memory_peak_bytes``
    per local device from the backend's memory stats; returns how many
    devices reported (0 on the CPU backend — None-safe by contract)."""
    from ..utils.profiling import device_memory_stats

    reported = 0
    for dev, stats in device_memory_stats().items():
        if not stats:
            continue
        reported += 1
        if "bytes_in_use" in stats:
            _tm.gauge(
                "device_memory_bytes_in_use",
                "live backend memory per device",
                device=dev,
            ).set(float(stats["bytes_in_use"]))
        peak = stats.get("peak_bytes_in_use")
        if peak is not None:
            _tm.gauge(
                "device_memory_peak_bytes",
                "peak backend memory watermark per device",
                device=dev,
            ).set(float(peak))
    return reported


# -- on-demand / auto jax.profiler capture ------------------------------------


def _start_trace(logdir: str) -> None:
    """Start a capture with the Python tracer off.  The host side of the
    profile is named by the program's own ``rustpde:`` spans
    (telemetry/tracing.py; the host TraceMe level stays at its default so
    they are kept), and stopping a capture that recorded every Python call
    of a busy server holds the host for about a minute."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)


class ProfilerCapture:
    """Bounded, single-flight ``jax.profiler`` capture.

    ``start(logdir, seconds)`` spawns a daemon thread that runs
    ``start_trace``/``stop_trace`` around a sleep; a second start while one
    is in flight is refused (409 shape at the HTTP layer).  Seconds are
    capped by ``RUSTPDE_PROFILE_MAX_S`` — a typo'd ``?seconds=86400`` must
    not pin the profiler for a day.  Injectable trace functions keep the
    unit tests off the real profiler."""

    def __init__(self, start_fn=None, stop_fn=None):
        self._lock = threading.Lock()
        self._busy = False
        self._start_fn = start_fn
        self._stop_fn = stop_fn
        self.captures = 0
        self.last: dict | None = None

    @property
    def busy(self) -> bool:
        return self._busy

    def max_seconds(self) -> float:
        return float(_config.env_get("RUSTPDE_PROFILE_MAX_S", "60") or 60.0)

    def start(self, logdir: str, seconds: float, reason: str = "manual") -> dict:
        """Begin a capture; returns the status payload (``started`` False
        carries the refusal reason)."""
        try:
            seconds = float(seconds)
        except (TypeError, ValueError):
            return {"started": False, "error": f"bad seconds {seconds!r}"}
        if seconds <= 0:
            return {"started": False, "error": "seconds must be positive"}
        seconds = min(seconds, self.max_seconds())
        with self._lock:
            if self._busy:
                return {"started": False, "error": "capture already running"}
            self._busy = True
        status = {
            "started": True,
            "dir": logdir,
            "seconds": seconds,
            "reason": reason,
        }
        self.last = status
        thread = threading.Thread(
            target=self._run,
            args=(logdir, seconds, status),
            name="profile-capture",
            daemon=True,
        )
        thread.start()
        return dict(status)

    def _run(self, logdir: str, seconds: float, status: dict) -> None:
        start = self._start_fn or _start_trace
        stop = self._stop_fn
        if stop is None:
            import jax

            stop = jax.profiler.stop_trace
        try:
            os.makedirs(logdir, exist_ok=True)
            start(logdir)
            try:
                _time.sleep(seconds)
            finally:
                stop()
            status["done"] = True
            self.captures += 1
            _tm.counter(
                "profiler_captures_total", "completed jax.profiler captures"
            ).inc()
        except Exception as exc:  # backend may refuse: recorded, never raised
            status["done"] = False
            status["error"] = f"{type(exc).__name__}: {exc}"
        finally:
            with self._lock:
                self._busy = False


#: process-wide capture the HTTP front and the perf_degraded hook share
CAPTURE = ProfilerCapture()

_degrade_fired = False


def capture_on_perf_degraded(run_dir: str) -> dict | None:
    """ONE-SHOT automatic capture when the SLO monitor reports a
    ``perf_degraded`` regression: the first event per process captures a
    short window into ``<run_dir>/profiles/degraded``; later events only
    count.  Returns the status payload on the firing call, else None."""
    global _degrade_fired
    if _degrade_fired or not _tm.enabled():
        return None
    try:
        import jax

        host = int(jax.process_index())
    except Exception:
        host = 0
    # per-host capture dir: the run_dir is shared across a multihost fleet
    logdir = os.path.join(run_dir, "profiles", f"degraded_h{host}")
    status = CAPTURE.start(
        logdir, min(2.0, CAPTURE.max_seconds()), reason="perf_degraded"
    )
    # the one-shot is spent only by a capture that actually STARTED — a
    # refusal (manual capture in flight) must leave the shot for the next
    # perf_degraded event, or the auto-profile is silently lost forever
    if status.get("started"):
        _degrade_fired = True
        return status
    return None
