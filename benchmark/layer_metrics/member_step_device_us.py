"""Device busy time per member-step of the bare ensemble (ensemble layer;
moves member_steps_per_s)."""
UNIT, LAYER, MOVES = "us", "ensemble", "member_steps_per_s"


def read(trace, run):
    if not run.get("traced_steps"):
        return None
    return 1e6 * trace["busy_s"] / (run["traced_steps"] * run["members"])
