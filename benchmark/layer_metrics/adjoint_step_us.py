"""Wall time of the adjoint sweep per step: mean duration of the program's
``lnse.adjoint_sweep`` span (terminal condition, dispatch of the hand-adjoint
sweep backward through the stored trajectory, to the moment the gradient is on
the host) over its ``steps``, over the traced iterations.  A program without
the span reads nothing (model step; moves steps_per_s)."""
UNIT, LAYER, MOVES = "us", "model step", "steps_per_s"


def read(trace, run):
    from ._lnse_spans import per_step_us

    return per_step_us("lnse.adjoint_sweep", run)
