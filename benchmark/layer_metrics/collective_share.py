"""Share of the device's busy time spent in collective operations
(``all-to-all``, ``all-gather``, ``collective-permute``, ``all-reduce``, their
``-start``/``-done`` halves included), mean over the device planes, from the
trace's per-operation sums.  What the pencil decomposition costs on the device:
time a chip spends exchanging instead of multiplying.  A trace that holds no
collective (one device) reads nothing, never 0 (mesh; moves steps_per_s)."""
UNIT, LAYER, MOVES = "%", "mesh", "steps_per_s"

COLLECTIVES = ("all-to-all", "all-gather", "collective-permute", "all-reduce")


def read(trace, run):
    spent = sum(s for name, s in trace["ops"].items() if name.startswith(COLLECTIVES))
    if not spent or not trace["busy_s"]:
        return None
    return 100.0 * spent / trace["busy_s"]
