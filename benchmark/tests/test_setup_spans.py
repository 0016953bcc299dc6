"""The readers of the set-up's spans on a ring written by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_setup_spans.py -q

A build, initial values, the warm-up's two intervals and the window's untraced
one, then two traced dispatches.  Each reader is held to the sum it should
give; a ring that has lost its head, one without a build span (the parent
commit) and a run that traced nothing read ``None``."""

from __future__ import annotations

import importlib

import pytest

from benchmark.layer_metrics import _setup_spans

_clock = [0.0]


def span(name, dur_us, ident, parent=None, **args):
    """One closed span as the ring keeps it; the clock moves on by its
    duration unless it is a parent written after its children."""
    start = args.pop("start", _clock[0])
    _clock[0] = max(_clock[0], start + dur_us)
    return {"name": name, "ph": "X", "ts": start, "dur": dur_us, "pid": 1, "tid": 0,
            "args": {"id": ident, "parent": parent, **args}}


def ring():
    _clock[0] = 0.0
    events = [
        span("space.build", 100.0, 2, 1),
        span("space.build", 50.0, 3, 1),
        span("space.build", 25.0, 5, 4, start=150.0),  # a space a solver built for itself
        span("solver.build", 2e6, 4, 1, start=150.0, backend_compiles=2, cache_hits=2,
             cache_load_s=0.25, eigs=2, eig_cached=2),
        span("model.set_field", 1e6, 6, 1, backend_compiles=24, cache_hits=24, cache_load_s=0.5),
        span("model.compile_entry_points", 3e6, 7, 1, traces=600, consts=68),
        span("model.build", 6.5e6, 1, None, start=0.0, backend_compiles=3),
        span("model.set_field", 0.5e6, 8, None, backend_compiles=14),
        {"name": "fault", "ph": "i", "s": "g", "ts": _clock[0], "pid": 1, "tid": 0},
        # warm-up: the first interval lowers and loads, the second only runs
        span("model.launch", 7e6, 10, 9, lowerings=1, backend_compiles=1, cache_hits=1,
             cache_load_s=4.0),
        span("model.update_n", 7.1e6, 9, None, start=_clock[0] - 7e6),
        span("model.observe_launch", 1e6, 12, 11, lowerings=1, backend_compiles=1),
        span("model.observe", 1.2e6, 11, None, start=_clock[0] - 1e6),
    ]
    for ident in (13, 15, 17, 19):  # warm, the window's untraced one, two traced
        events.append(span("model.launch", 300.0, ident + 1, ident))
        events.append(span("model.update_n", 400.0, ident, None, start=_clock[0] - 300.0))
    return events


WANT = {
    "operator_build_s": 1e-6 * (100.0 + 50.0 + 2e6),  # the nested space.build is inside
    "eager_programs": 2 + 24 + 3 + 14,
    "entry_trace_s": 3.0,
    "first_dispatch_s": 8.0,
    "cache_load_s": 4.75,
}


def test_the_setup_ends_where_the_first_traced_dispatch_opens():
    found = _setup_spans.setup_of(ring(), 2, 4096)
    names = [ev["name"] for ev in found]
    assert names.count("model.update_n") == 3 and names.count("model.build") == 1
    assert all(ev["args"]["id"] < 17 for ev in found)
    outer = _setup_spans.outermost(found, ("space.build", "solver.build"))
    assert sorted(ev["args"]["id"] for ev in outer) == [2, 3, 4]


@pytest.mark.parametrize("reader", sorted(WANT))
def test_reader_sums_the_setups_spans(monkeypatch, reader):
    mod = importlib.import_module(f"benchmark.layer_metrics.{reader}")
    events = ring()
    monkeypatch.setattr(_setup_spans, "setup_spans",
                        lambda run: _setup_spans.setup_of(events, run["traced_dispatches"], 4096))
    assert mod.read({}, {"traced_dispatches": 2}) == pytest.approx(WANT[reader])
    assert mod.read({}, {"traced_dispatches": 0}) is None
    assert mod.read({}, {"traced_dispatches": 9}) is None  # fewer dispatches than traced


def test_a_ring_that_lost_its_head_or_has_no_build_reads_nothing():
    events = ring()
    assert _setup_spans.setup_of(events, 2, len(events)) is None  # full: the head is gone
    assert _setup_spans.setup_of(events, 2, len(events) + 1) is not None
    parent_commit = [ev for ev in events if not ev["name"].endswith(".build")]
    assert _setup_spans.setup_of(parent_commit, 2, 4096) is None
