"""Wall time of the forward sweep per step: mean duration of the program's
``lnse.forward_sweep`` span (dispatch of the nonlinear forward sweep with its
trajectory stored, to the moment J is on the host) over its ``steps``, over
the traced iterations.  Read from the program's span ring; a program without
the span (the parent commit) reads nothing (model step; moves steps_per_s)."""
UNIT, LAYER, MOVES = "us", "model step", "steps_per_s"


def read(trace, run):
    from ._lnse_spans import per_step_us

    return per_step_us("lnse.forward_sweep", run)
