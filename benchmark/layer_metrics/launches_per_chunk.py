"""Device programs the ensemble enqueues per chunk: mean of the ``launches``
count on the program's ``ensemble.update_n`` span (leaves copied one by one
plus the buckets ``run_scanned`` dispatches).  A count, not a time (ensemble;
moves member_steps_per_s)."""
UNIT, LAYER, MOVES = "launches", "ensemble", "member_steps_per_s"


def read(trace, run):
    from ._program_spans import mean_count

    return mean_count("ensemble.update_n", "launches", run)
