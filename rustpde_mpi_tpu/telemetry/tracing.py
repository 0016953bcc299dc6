"""Flight-recorder tracing: Chrome/Perfetto trace events in a bounded ring.

The runner/scheduler hot seams are wrapped in :func:`span` context managers
(chunk dispatch/resolve, checkpoint stage/commit, serve settle/refill).
Every span lands in a process-wide ring buffer — the **flight recorder** —
whose contents are dumped as a ``traceEvents`` JSON file (loadable directly
in Perfetto / ``chrome://tracing``) when something goes wrong:

* a :class:`~rustpde_mpi_tpu.utils.resilience.DispatchHang` or
  :class:`~rustpde_mpi_tpu.utils.resilience.DivergenceError`,
* a SIGTERM/preemption drain,
* any other exception escaping a runner session, and unclean process exit
  while a session is armed (an ``atexit`` dump armed/disarmed per session),

so every incident ships with the timeline of its last few thousand events
instead of a bare traceback.  The ring bounds memory (default 4096 events,
``RUSTPDE_TRACE_EVENTS``); dumping never clears it.

Every span also carries an ``id``, the ``parent`` that was open on its
thread when it began and (where the call site gives one) the ``layer`` it
belongs to, so a layer's self time is its span less its children; and
while it is open it holds a ``jax.profiler.TraceAnnotation`` named
``rustpde:<name>``.  That costs an atomic load while no profiler session is
open; inside one it puts the span into the trace's host plane, on the clock
the device planes use, which is the only way to lay a span of the program
beside an idle gap of the chip (trace times count from the session's start,
so a ``perf_counter`` reading cannot be matched to them afterwards).
:func:`spans` is the read side for code in the same process.

jax's own compile events land on the span that is open.  One
``jax.monitoring`` duration listener and one event listener, registered once
unless the process starts with tracing off, add each event to the ``args`` of
the innermost span open on the thread that fired it: ``traces``/``trace_s``
(a jaxpr traced), ``lowerings``/``lower_s`` (a jaxpr lowered to a module),
``backend_compiles``/``compile_s`` (XLA compiled a program or loaded it from
the persistent cache), ``cache_hits``, ``cache_misses`` and ``cache_load_s``
(the persistent cache), so ``backend_compiles - cache_hits`` is what XLA
really compiled inside that span.  The counts are SELF counts: a parent's
total is its own plus its children's, which ``id``/``parent`` give.  A span
that saw no event carries none of the keys.  Events fired with no span open
go to a process-wide ``unattributed`` total; :func:`compile_totals` returns
both cumulative totals, so a caller takes a mark and a difference without a
listener of its own.  For that the per-thread stack of open spans holds the
``_Span`` objects themselves (not their ids): an event costs a dict lookup,
a list read, two dict adds and one lock for the totals (about a
microsecond), and jax fires none on a warm dispatch.  jax cannot take a
listener back, so with tracing switched off later each returns at its first
branch, as :func:`span` does.

Overhead contract: with tracing disabled (:func:`set_enabled` or
``RUSTPDE_TRACE=0``) :func:`span` returns a shared no-op context manager —
one function call and one branch (~ns, no allocation, no annotation), and a
process that starts that way registers no listener; enabled spans cost two
``perf_counter`` reads, one TraceAnnotation and one deque append.  Spans
wrap HOST-side seams only and never add device work, so traced runs stay
bit-identical (CI-asserted together with the metrics layer)."""

from __future__ import annotations

import itertools
import json
import os
import threading
import time as _time
from collections import deque

from jax import monitoring as _monitoring
from jax.profiler import TraceAnnotation

from .. import config as _config

# RUSTPDE_TELEMETRY=0 is the master kill switch; RUSTPDE_TRACE=0 turns off
# just the tracing half (metrics keep recording)
_ENABLED = (
    _config.env_get("RUSTPDE_TRACE", "1") != "0"
    and _config.env_get("RUSTPDE_TELEMETRY", "1") != "0"
)


def enabled() -> bool:
    return _ENABLED


def set_enabled(flag: bool) -> None:
    """Turn span recording on/off globally (``RUSTPDE_TRACE`` env default;
    the bench overhead gate toggles this together with the metrics flag).
    Switching it on in a process that started with it off registers the
    compile-event listeners then."""
    global _ENABLED
    _ENABLED = bool(flag)
    if _ENABLED:
        _register_listeners()


class FlightRecorder:
    """Bounded ring of Chrome trace events (host-side, thread-safe).

    Events use the ``traceEvents`` JSON schema: complete spans (``ph=X``,
    microsecond ``ts``/``dur`` relative to recorder start) and instant
    markers (``ph=i``).  ``tid`` is a stable small integer per thread."""

    def __init__(self, capacity: int | None = None):
        if capacity is None:
            capacity = int(_config.env_get("RUSTPDE_TRACE_EVENTS", "4096") or 4096)
        self.capacity = max(16, int(capacity))
        self._events: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._t0 = _time.perf_counter()
        self._tids: dict[int, int] = {}
        self._pid = os.getpid()
        self.dumped = 0  # dump() calls (tests/ops counters)
        self._dump_seq = 0  # monotonic dump ids (alloc_seq, lock-held)

    def alloc_seq(self) -> int:
        """Allocate the next dump sequence number (lock-held: concurrent
        incident dumps — watchdog thread vs signal/atexit path — must not
        collide on one seq and overwrite each other's file)."""
        with self._lock:
            self._dump_seq += 1
            return self._dump_seq

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)  # a thread's own entry never changes
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def now_us(self) -> float:
        return (_time.perf_counter() - self._t0) * 1e6

    def add_complete(self, name: str, t0_us: float, dur_us: float, args=None) -> None:
        event = {
            "name": name,
            "ph": "X",
            "ts": t0_us,  # not rounded: this is the per-span hot path
            "dur": dur_us,
            "pid": self._pid,
            "tid": self._tid(),
        }
        if args:
            event["args"] = args
        with self._lock:
            self._events.append(event)

    def add_instant(self, name: str, args=None) -> None:
        event = {
            "name": name,
            "ph": "i",
            "s": "g",  # global-scope instant marker
            "ts": round(self.now_us(), 3),
            "pid": self._pid,
            "tid": self._tid(),
        }
        if args:
            event["args"] = args
        with self._lock:
            self._events.append(event)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def dump(self, path: str, reason: str = "", extra: dict | None = None) -> str:
        """Write the ring as a Perfetto-loadable trace file (atomic tmp +
        replace; the ring is NOT cleared — later incidents still carry the
        shared history)."""
        payload = {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {
                "reason": reason,
                "pid": self._pid,
                "capacity": self.capacity,
                **(extra or {}),
            },
        }
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.{self._pid}.tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(payload, fh)
        os.replace(tmp, path)
        self.dumped += 1
        return path


#: process-wide recorder every span records into
RECORDER = FlightRecorder()

#: optional span-args annotator (telemetry/reqtrace.py installs one while
#: requests are bound to device slots): called once per completed span,
#: its dict — the active request trace ids — is merged into the span args,
#: so flight-recorder timelines and incident dumps are request-attributable
_ANNOTATOR = None


def set_span_annotator(fn) -> None:
    """Install/clear the span annotator (``fn() -> dict | None``); one
    global so the disabled path stays a single branch."""
    global _ANNOTATOR
    _ANNOTATOR = fn


#: span ids are process-wide; the stack of open spans is per thread, so a
#: span's parent is always one its own thread opened
_IDS = itertools.count(1)


class _OpenSpans(threading.local):
    def __init__(self):
        self.stack: list[_Span] = []


_OPEN = _OpenSpans()


class _Span:
    __slots__ = ("name", "args", "id", "parent", "seconds", "_t0", "_annotation")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args

    def set(self, **args) -> None:
        """Add counts that are known only once the work is under way."""
        self.args.update(args)

    def __enter__(self):
        stack = _OPEN.stack
        self.parent = stack[-1].id if stack else None
        self.id = next(_IDS)
        stack.append(self)
        self._annotation = TraceAnnotation("rustpde:" + self.name)
        self._annotation.__enter__()
        self._t0 = RECORDER.now_us()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = RECORDER.now_us() - self._t0
        self.seconds = dur * 1e-6
        self._annotation.__exit__(exc_type, exc, tb)
        _OPEN.stack.pop()
        args = {"id": self.id, "parent": self.parent, **self.args}
        if exc_type is not None:
            args["error"] = exc_type.__name__
        if _ANNOTATOR is not None:
            extra = _ANNOTATOR()
            if extra:
                args.update(extra)
        RECORDER.add_complete(self.name, self._t0, dur, args)
        return False


class _NullSpan:
    __slots__ = ()

    def set(self, **args) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


class _Stopwatch(_NullSpan):
    """What :func:`timed` hands out while the recorder is off: the seam's
    duration and nothing else."""

    __slots__ = ("seconds", "_t0")

    def __enter__(self):
        self._t0 = _time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.seconds = _time.perf_counter() - self._t0
        return False


def span(name: str, layer: str | None = None, **args):
    """Context manager recording one complete trace event; the shared
    no-op object when tracing is disabled (one branch, no allocation).
    ``layer`` is the layer's name as PERF.md section 3 spells it."""
    if not _ENABLED:
        return _NULL_SPAN
    if layer is not None:
        args["layer"] = layer
    return _Span(name, args)


def timed(name: str, layer: str | None = None, **args):
    """:func:`span` for a seam whose duration the caller also hands on (to a
    ``/metrics`` histogram): once closed, ``.seconds`` is the span's own
    duration, so the ring and the histogram say one number.  With the
    recorder off a bare stopwatch stands in, and the metrics half keeps
    recording."""
    return span(name, layer, **args) if _ENABLED else _Stopwatch()


def count(**increments) -> None:
    """Add to counts on the innermost span open on this thread, for a callee
    that has no handle on it; nothing with the recorder off or no span open."""
    if _ENABLED and _OPEN.stack:
        into = _OPEN.stack[-1].args
        for key, value in increments.items():
            into[key] = into.get(key, 0) + value


def spans(name: str) -> list[tuple]:
    """The ring's completed spans called ``name``, oldest first, as
    ``(t0_ns, dur_ns, id, parent, args)`` on the recorder's own clock."""
    out = []
    for ev in RECORDER.events():
        if ev["ph"] == "X" and ev["name"] == name:
            args = ev.get("args") or {}
            out.append(
                (
                    round(ev["ts"] * 1e3),
                    round(ev["dur"] * 1e3),
                    args.get("id"),
                    args.get("parent"),
                    args,
                )
            )
    return out


# -- jax's compile events, on the span that is open ---------------------------

#: jax event -> (counter, seconds) added to the open span's args
_EVENT_KEYS = {
    "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lowerings", "lower_s"),
    "/jax/core/compile/backend_compile_duration": ("backend_compiles", "compile_s"),
    "/jax/compilation_cache/cache_hits": ("cache_hits", None),
    "/jax/compilation_cache/cache_misses": ("cache_misses", None),
    "/jax/compilation_cache/cache_retrieval_time_sec": (None, "cache_load_s"),
}
COMPILE_KEYS = tuple(key for pair in _EVENT_KEYS.values() for key in pair if key)

_totals = {
    side: {key: 0.0 if key.endswith("_s") else 0 for key in COMPILE_KEYS}
    for side in ("attributed", "unattributed")
}
_totals_lock = threading.Lock()
_listening = False


def _on_event(name: str, secs: float = 0.0, **_) -> None:
    """Both listeners: jax hands a duration event its seconds, a plain event
    none."""
    if not _ENABLED:
        return
    keys = _EVENT_KEYS.get(name)
    if keys is None:
        return
    adds = [(key, add) for key, add in zip(keys, (1, secs)) if key is not None]
    stack = _OPEN.stack
    if stack:
        into = stack[-1].args
        for key, add in adds:
            into[key] = into.get(key, 0) + add
    total = _totals["attributed" if stack else "unattributed"]
    with _totals_lock:
        for key, add in adds:
            total[key] += add


def _register_listeners() -> None:
    global _listening
    with _totals_lock:
        if _listening:
            return
        _listening = True
    _monitoring.register_event_duration_secs_listener(_on_event)
    _monitoring.register_event_listener(_on_event)


if _ENABLED:
    _register_listeners()


def compile_totals() -> dict:
    """Cumulative compile events of this process since the listeners were
    registered: ``{"attributed": {...}, "unattributed": {...}}``, each with
    every key of :data:`COMPILE_KEYS`; ``attributed`` is the sum over every
    span's own counts, ``unattributed`` what fired with no span open on its
    thread.  Take it twice and subtract (:func:`compile_totals_since`)."""
    with _totals_lock:
        return {side: dict(total) for side, total in _totals.items()}


def compile_totals_since(mark: dict) -> dict:
    """``compile_totals()`` less an earlier ``mark``, both sides summed, plus
    ``compiled`` = ``backend_compiles - cache_hits``: what XLA really
    compiled since the mark, on a span or off."""
    now = compile_totals()
    out = {
        key: sum(now[side][key] - mark[side][key] for side in now) for key in COMPILE_KEYS
    }
    out["compiled"] = max(0, out["backend_compiles"] - out["cache_hits"])
    return out


def instant(name: str, **args) -> None:
    """Record an instant marker (fault injected, rollback, drain)."""
    if _ENABLED:
        RECORDER.add_instant(name, args or None)


def dump_flight_record(
    run_dir: str, reason: str, step: int | None = None, extra: dict | None = None
) -> str | None:
    """Dump the flight recorder into ``run_dir`` as
    ``flight_<reason>[_stepN]_nSEQ.json``; best-effort (an incident dump
    must never mask the incident), returns the path or None.

    ``SEQ`` is a process-monotonic dump sequence number and the payload
    carries the active request trace ids (telemetry/reqtrace.py), so a
    chaos soak's pile of dumps sorts chronologically and each one names
    the requests that were on the device — attributable, not anonymous."""
    if not _ENABLED:
        return None
    from . import reqtrace as _reqtrace

    seq = RECORDER.alloc_seq()
    trace_ids = _reqtrace.active_ids()
    tag = reason.replace(" ", "_").replace("/", "_")
    name = (
        f"flight_{tag}"
        + (f"_step{step}" if step is not None else "")
        + f"_n{seq:04d}.json"
    )
    path = os.path.join(run_dir, name)
    try:
        info = dict(extra or {})
        if step is not None:
            info["step"] = step
        info["seq"] = seq
        if trace_ids:
            info["trace_ids"] = trace_ids
        return RECORDER.dump(path, reason=reason, extra=info)
    except OSError:
        return None


# -- unclean-exit arming -------------------------------------------------------

_exit_hooks: dict[int, tuple] = {}
_exit_lock = threading.Lock()
_exit_registered = False
_hook_seq = 0


def _run_exit_hooks() -> None:
    with _exit_lock:
        hooks = list(_exit_hooks.values())
        _exit_hooks.clear()
    for run_dir, step_fn in hooks:
        try:
            dump_flight_record(
                run_dir, "unclean_exit", step=step_fn() if step_fn else None
            )
        except Exception:
            pass


def arm_exit_dump(run_dir: str, step_fn=None):
    """Arm an ``atexit`` flight-record dump for an in-flight session: if the
    process exits while armed (sys.exit, un-handled exception past the
    session, interpreter teardown after SIGTERM default handling), the ring
    is dumped into ``run_dir`` with reason ``unclean_exit``.  Returns a
    disarm callable — the session's CLEAN exit path calls it, so normal
    completions leave no incident file."""
    global _exit_registered, _hook_seq
    with _exit_lock:
        if not _exit_registered:
            import atexit

            atexit.register(_run_exit_hooks)
            _exit_registered = True
        _hook_seq += 1
        token = _hook_seq
        _exit_hooks[token] = (run_dir, step_fn)

    def disarm() -> None:
        with _exit_lock:
            _exit_hooks.pop(token, None)

    return disarm
