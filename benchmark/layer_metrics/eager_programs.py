"""Programs the set-up path ran op by op: jax's ``backend_compiles`` summed
over the set-up's spans that are not launches (``*.launch``,
``model.observe_launch``), each one an XLA compile or a load from the
persistent cache.  A count, not a time; a program without the spans (the
parent commit) reads nothing (operators and kernels; moves setup_s)."""
UNIT, LAYER, MOVES = "programs", "operators and kernels", "setup_s"


def read(trace, run):
    from ._setup_spans import read as read_setup

    return read_setup("eager_programs", run)
