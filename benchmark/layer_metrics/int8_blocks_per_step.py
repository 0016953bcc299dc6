"""Int8 operator slices one solver step's sliced products stream: mean of the
``int8_blocks`` count on the program's ``model.update_n`` span over the traced
dispatches (``rustpde_mpi_tpu/ops/folded.py`` ``int8_blocks``, counted where
the step is compiled: each int8 ``dot_general`` of a ``sliced_product`` call
counts its operator operand in units of one slice of one operator).  A product
stated one slice pair at a time reads 45 for each sliced operator; the
block-Toeplitz runs it replaced streamed 61.  A count, not a time; a program
whose span carries no such count (the parent commit) reads nothing
(operators and kernels; moves steps_per_s)."""
UNIT, LAYER, MOVES = "blocks", "operators and kernels", "steps_per_s"


def read(trace, run):
    from ._program_spans import mean_count

    return mean_count("model.update_n", "int8_blocks", run)
