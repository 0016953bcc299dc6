"""Host time the set-up spends hoisting and tracing the entry points: sum of
the durations of the ``model.compile_entry_points`` and
``ensemble.compile_entry_points`` spans that closed before the first traced
dispatch.  A program without the spans (the parent commit) reads nothing
(model step; moves setup_s)."""
UNIT, LAYER, MOVES = "s", "model step", "setup_s"


def read(trace, run):
    from ._setup_spans import read as read_setup

    return read_setup("entry_trace_s", run)
