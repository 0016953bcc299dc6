"""State leaves that sit whole on every device of the mesh when an interval is
dispatched: mean of the ``replicated_leaves`` count on the program's
``model.update_n`` span over the traced dispatches.  A pencil whose extent the
mesh does not divide (1023 or 1025 columns over 4 chips) cannot be held
sharded between dispatches, so each such leaf is gathered whole at the end of
every dispatch and cut again at the start of the next.  A count, not a time;
an unmeshed model's span carries none and reads nothing (mesh; moves
steps_per_s)."""
UNIT, LAYER, MOVES = "leaves", "mesh", "steps_per_s"


def read(trace, run):
    from ._program_spans import mean_count

    return mean_count("model.update_n", "replicated_leaves", run)
