"""Host time the set-up spends building spaces and solvers: sum of the
durations of the outermost ``space.build`` and ``solver.build`` spans that
closed before the first traced dispatch (the host eigendecompositions and
their disk cache, the dense inverses, the casts to the device).  Read from the
program's span ring; a program without the spans (the parent commit) reads
nothing (operators and kernels; moves setup_s)."""
UNIT, LAYER, MOVES = "s", "operators and kernels", "setup_s"


def read(trace, run):
    from ._setup_spans import read as read_setup

    return read_setup("operator_build_s", run)
