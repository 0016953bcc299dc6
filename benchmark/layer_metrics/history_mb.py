"""Bytes of the stacked trajectory the forward sweep hands the adjoint sweep:
mean ``history_bytes`` of the program's ``lnse.grad_adjoint`` span over the
traced iterations, in MB (1e6 bytes): what a checkpoint-and-recompute schedule
would move.  A count, not a time; a program without the span reads nothing
(model step; moves steps_per_s)."""
UNIT, LAYER, MOVES = "MB", "model step", "steps_per_s"


def read(trace, run):
    from ._program_spans import mean_count

    found = mean_count("lnse.grad_adjoint", "history_bytes", run)
    return None if not found else found / 1e6
