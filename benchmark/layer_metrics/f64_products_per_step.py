"""Matrix products of one solver step that were traced with float64 operands:
mean of the ``f64_products`` count on the program's ``model.update_n`` span
over the traced dispatches (the ``dot_general``s of the step's program,
counted where the step is compiled; the span's ``f32_products`` is the rest).
The chip has no float64 unit, so each of them is emulated; a change that takes
products out of emulation (the f64 hybrid, a split into float32 pieces) lowers
this number, and the cell's limits judge what that did to the answer.  A
count, not a time; a program whose span carries no such count (the parent
commit) reads nothing (operators and kernels; moves steps_per_s)."""
UNIT, LAYER, MOVES = "products", "operators and kernels", "steps_per_s"


def read(trace, run):
    from ._program_spans import mean_count

    return mean_count("model.update_n", "f64_products", run)
