"""Host time of the eager per-leaf copy of the carry before a chunk is
launched (the chunk donates its input): mean duration of the program's
``ensemble.carry_copy`` span, a child of ``ensemble.update_n`` (ensemble, host
side; moves member_steps_per_s)."""
UNIT, LAYER, MOVES = "ms", "ensemble", "member_steps_per_s"


def read(trace, run):
    from ._program_spans import mean_duration_ms

    return mean_duration_ms("ensemble.carry_copy", run)
