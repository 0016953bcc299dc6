"""Float64 multiplies inside one solver step's sliced products: mean of the
``sliced_f64_multiplies`` count on the program's ``model.update_n`` span over
the traced dispatches (the float64 ``mul`` equations of the step's
``sliced_product`` calls, ``rustpde_mpi_tpu/ops/folded.py``, counted where the
step is compiled; the lone columns' one-row updates left out).  The chip has
no float64 unit, so each is an emulated two-word product over a whole field;
where the sliced product applies its power-of-two scalings to its float32
pieces the count is 0.  A count, not a time; a program whose span carries no
such count (the parent commit) reads nothing (operators and kernels; moves
steps_per_s)."""
UNIT, LAYER, MOVES = "multiplies", "operators and kernels", "steps_per_s"


def read(trace, run):
    from ._program_spans import mean_count

    return mean_count("model.update_n", "sliced_f64_multiplies", run)
