"""Host time the ensemble takes to hand the chip one chunk: mean duration of
the program's ``ensemble.update_n`` span over the traced chunks.  Less
``chunk_copy_ms`` and less the ``ensemble.launch`` spans it is the call's
self time (ensemble, host side; moves member_steps_per_s)."""
UNIT, LAYER, MOVES = "ms", "ensemble", "member_steps_per_s"


def read(trace, run):
    from ._program_spans import mean_duration_ms

    return mean_duration_ms("ensemble.update_n", run)
