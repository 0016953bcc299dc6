"""Host time of the set-up's first dispatches: sum of the durations of the
launch spans (``*.launch``, ``model.observe_launch``) that carry ``lowerings``
> 0, so trace, lower, compile or load, and enqueue, once per program.  A
program without the spans (the parent commit) reads nothing (model step; moves
setup_s)."""
UNIT, LAYER, MOVES = "s", "model step", "setup_s"


def read(trace, run):
    from ._setup_spans import read as read_setup

    return read_setup("first_dispatch_s", run)
