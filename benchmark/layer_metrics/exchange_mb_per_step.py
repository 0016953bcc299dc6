"""What one step's pencil flips send from one device: mean of the
``exchange_bytes`` count on the program's ``model.update_n`` span over the
traced dispatches, in MB (1e6 bytes).  The count is the step's own, made once
where the step is traced: each flip it states (a spectral or physical array
changed from one pencil to the other) sends the tiles of the array that other
devices hold, at the array's own itemsize, so a float64 step reads twice a
float32 one at one grid.  A count, not a time; an unmeshed model's span (or an
older program's) carries none and reads nothing (mesh; moves steps_per_s)."""
UNIT, LAYER, MOVES = "MB", "mesh", "steps_per_s"


def read(trace, run):
    from ._program_spans import mean_count

    found = mean_count("model.update_n", "exchange_bytes", run)
    return None if found is None else found / 1e6
