"""Profiling helpers: a ``jax.profiler`` trace context and the per-device
memory stats the telemetry layer reads.  Rates, flop counts and peaks are
the benchmark's (``benchmark/``: ``work.py``, ``work_periodic.py``,
``peaks.json``); device time per step stage is ``scripts/stage_times.py``.
"""

from __future__ import annotations

import contextlib

__all__ = ["trace", "device_memory_stats"]


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
