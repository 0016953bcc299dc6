"""Mean device idle time per dispatch: everything the device waits for around
one ``update_n`` program.  The traced stretch runs from the first traced
dispatch's first small operation (the step count's conversion, well before
its program) to the last one's last, so it holds every dispatch's lead-in and
all reads but the last (model step, host side; moves steps_per_s)."""
UNIT, LAYER, MOVES = "ms", "model step", "steps_per_s"


def read(trace, run):
    if not run.get("traced_dispatches"):
        return None
    return 1e3 * (trace["window_s"] - trace["busy_s"]) / run["traced_dispatches"]
