"""Host time the program takes to hand the chip one interval: mean duration
of the program's ``model.update_n`` span over the traced dispatches (carry
copy, bucket launches and the call's own Python; the device runs on behind
it).  Read from the program's span ring, not from the device trace (model
step, host side; moves steps_per_s)."""
UNIT, LAYER, MOVES = "ms", "model step", "steps_per_s"


def read(trace, run):
    from ._program_spans import mean_duration_ms

    return mean_duration_ms("model.update_n", run)
