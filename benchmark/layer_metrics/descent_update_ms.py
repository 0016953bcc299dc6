"""Host half of an iteration: mean duration of the program's
``lnse.descent_update`` span (the gradient's projection and the rotation on
the energy sphere in numpy, the new initial condition transformed and set)
over the traced iterations: what a descent on the device would remove.  A program without the span reads nothing (model step;
moves steps_per_s)."""
UNIT, LAYER, MOVES = "ms", "model step", "steps_per_s"


def read(trace, run):
    from ._program_spans import mean_duration_ms

    return mean_duration_ms("lnse.descent_update", run)
