"""Index gathers of one solver step: mean of the ``gathers`` count on the
program's ``model.update_n`` span over the traced dispatches (the ``gather``
equations of the step's traced program, counted where the step is compiled: the
circular folds of the periodic axes' transforms and the conjugate pairing of
the Hermitian projection).  A count, not a time: the lever a change to the
circular fold is judged by.  A program whose span carries no such count (the
parent commit) reads nothing (operators and kernels; moves steps_per_s)."""
UNIT, LAYER, MOVES = "gathers", "operators and kernels", "steps_per_s"


def read(trace, run):
    from ._program_spans import mean_count

    return mean_count("model.update_n", "gathers", run)
