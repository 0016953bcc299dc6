"""Device busy time per solver step (model step layer; moves steps_per_s)."""
UNIT, LAYER, MOVES = "ms", "model step", "steps_per_s"


def read(trace, run):
    if not run.get("traced_steps"):
        return None
    return 1e3 * trace["busy_s"] / run["traced_steps"]
