"""Time the set-up spends inside the persistent compile cache: jax's
``cache_retrieval_time_sec`` summed over the set-up's spans (``cache_load_s``
on each), launches included; 0.0 where the cache served nothing.  A program
without the spans (the parent commit) reads nothing (model step; moves
setup_s)."""
UNIT, LAYER, MOVES = "s", "model step", "setup_s"


def read(trace, run):
    from ._setup_spans import read as read_setup

    return read_setup("cache_load_s", run)
