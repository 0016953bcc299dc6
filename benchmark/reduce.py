"""From the profiler's trace (``*.xplane.pb``) to busy intervals, idle gaps
and per-op sums, with nothing but ``jax.profiler.ProfileData``.

A device plane is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per executed operation (start and duration in nanoseconds, on the
clock the host plane shares).  Busy time is the union of those events, averaged
over the device planes.  The window is the span
from the first op's start to the last op's end over all devices: a driver
starts and stops the trace on dispatch boundaries in mid-stream, so the idle
edges of the recording belong to the profiler and not to the program.  Every
idle gap inside the window is attributed to what the host was doing at its
middle: the innermost event of the host plane there (the harness's own
``bench:`` spans and the Python functions the profiler records).

``selfcheck.py`` holds this reduction to a small recorded trace under
``fixtures/`` whose expected numbers are written beside it.
"""

from __future__ import annotations

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# operations that only contain others (the scan's loop, a branch, a call):
# their time is their children's, so the per-op sums leave them out
CONTAINER = re.compile(r"^(while|cond|conditional|call)[.\d]*( |$)")
# host events that say what the program was doing: the harness's own spans and
# the Python functions the profiler records, library plumbing left out
HOST_SKIP = ("$builtins", "$<", "$profiler.py", "$threading.py", "$contextlib.py")
NS = 1e-9


def merge(intervals: list) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def short(name: str) -> str:
    """``%fusion.144 = f32[511,1023]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.144 f32[511,1023]``: the instruction's name and result shape."""
    m = re.match(r"^%?([^ =]+) = \(?([a-z0-9]+\[[0-9,]*\])?", name)
    if not m:
        return name[:64]
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def _events(line, shorten: bool = False) -> list:
    names: dict = {}  # a trace holds millions of events and a few hundred names

    def label(name):
        if name not in names:
            names[name] = short(name) if shorten else name
        return names[name]

    return [
        (label(e.name), float(e.start_ns), float(e.start_ns + e.duration_ns)) for e in line.events
    ]


def load(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...]}}, "host": [...]}`` with events as
    (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices[plane.name] = {"ops": _events(lines["XLA Ops"], shorten=True)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    # with the Python tracer on, its frames ("$file:line name") say what the
    # host did and the runtime's own spans are left out; a trace taken without
    # it keeps every span the runtime wrote (compile, lowering, execute)
    if any(ev[0].startswith("$") for ev in host):
        host = [ev for ev in host if ev[0].startswith("bench:")
                or (ev[0].startswith("$") and not ev[0].startswith(HOST_SKIP))]
    return {"devices": devices, "host": host}


def reduce_events(raw: dict) -> dict:
    """The reduction proper, on ``load``'s output (so that a test can feed it
    a trace written by hand)."""
    devs = raw["devices"]
    starts = [ev[1] for d in devs.values() for ev in d["ops"]]
    ends = [ev[2] for d in devs.values() for ev in d["ops"]]
    if not starts:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": {}, "gaps": [], "ops": {},
                "gap_list_s": []}
    w0, w1 = min(starts), max(ends)
    host = sorted((ev for ev in raw["host"] if ev[2] > w0 and ev[1] < w1), key=lambda ev: ev[1])
    host_starts = [ev[1] for ev in host]
    out_devs = {}
    for name, d in devs.items():
        busy = merge([(s, e) for _, s, e in d["ops"]])
        ops: dict = {}
        for n, s, e in d["ops"]:
            if not CONTAINER.match(n):
                ops[n] = ops.get(n, 0.0) + (e - s) * NS
        gaps = []
        edges = [[w0, w0]] + busy + [[w1, w1]]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps.append((e0, s1))
        out_devs[name] = {
            "busy_s": covered(busy) * NS,
            "ops": ops,
            "gaps": gaps,
        }
    n = len(out_devs)
    first = out_devs[sorted(out_devs)[0]]
    named: dict = {}
    for s, e in first["gaps"]:
        what = _host_activity(host, host_starts, 0.5 * (s + e))
        named[what] = named.get(what, 0.0) + (e - s) * NS
    mean_ops: dict = {}
    for d in out_devs.values():
        for k, v in d["ops"].items():
            mean_ops[k] = mean_ops.get(k, 0.0) + v / n
    return {
        "window_s": (w1 - w0) * NS,
        "busy_s": sum(d["busy_s"] for d in out_devs.values()) / n,
        "devices": out_devs,
        "gaps": sorted(((k, v) for k, v in named.items()), key=lambda kv: -kv[1]),
        "gap_list_s": [(e - s) * NS for s, e in first["gaps"]],
        "ops": mean_ops,
    }


def _host_activity(host: list, host_starts: list, t: float) -> str:
    """What the host was doing at time ``t``: the harness's ``bench:`` span
    there, with the innermost Python function inside it."""
    i = bisect.bisect_right(host_starts, t)
    span = inner = None
    for name, s, e in reversed(host[max(0, i - 8192) : i]):
        if e < t:
            continue
        if name.startswith("bench:"):
            span = span or name
        else:
            inner = inner or name
        if span and inner:
            break
    if span or inner:
        return " / ".join(x for x in (span, inner) if x)
    return "host: nothing recorded"


def reduce_xplane(path: str) -> dict:
    return reduce_events(load(path))


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took most
    time (mean over devices) and the idle gaps by what the host was doing."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": [[k, v] for k, v in reduced["gaps"][:top]],
    }
