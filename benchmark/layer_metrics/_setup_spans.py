"""What the five readers of the set-up's spans share.  The program keeps every
span it closes in a ring (``rustpde_mpi_tpu/telemetry/tracing.py``), each with
its ``id``, its ``parent`` and, where jax traced, lowered, compiled or loaded
a program while the span was the innermost one open, jax's own counts of that
(``traces``, ``lowerings``, ``backend_compiles``, ``cache_hits``,
``cache_load_s``, ...).  The set-up's spans are those that closed before the
first of the run's traced dispatches opened: the builds, the initial values,
the warm-up's first dispatches, and the window's one untraced interval, which
only runs.  A traced run stops with its trace, so the last
``run["traced_dispatches"]`` outermost dispatch spans are the traced ones.

A program without these spans (an older commit: no ``model.build``), a
recorder that is off, or a ring that has lost its oldest spans reads ``None``,
never 0."""

DISPATCHES = ("model.update_n", "ensemble.update_n", "lnse.descent_iteration")
BUILDS = ("model.build", "ensemble.build")


def is_launch(name: str) -> bool:
    return name.endswith(".launch") or name == "model.observe_launch"


def setup_of(events: list, traced: int, capacity: int):
    """The set-up's spans of a ring's ``events`` (trace-event dicts, oldest
    first), or ``None``."""
    if not traced or len(events) >= capacity:  # a full ring has dropped its head
        return None
    spans = [ev for ev in events if ev.get("ph") == "X" and "id" in ev.get("args", {})]
    dispatches = [ev for ev in spans
                  if ev["name"] in DISPATCHES and ev["args"].get("parent") is None]
    if len(dispatches) < traced:
        return None
    opened = dispatches[-traced]["ts"]
    found = [ev for ev in spans if ev["ts"] + ev["dur"] <= opened]
    if not any(ev["name"] in BUILDS for ev in found):
        return None
    return found


def setup_spans(run: dict):
    try:
        from rustpde_mpi_tpu.telemetry import tracing
    except ImportError:
        return None
    ring = getattr(tracing, "RECORDER", None)
    if ring is None or not tracing.enabled():
        return None
    return setup_of(ring.events(), run.get("traced_dispatches"), ring.capacity)


def outermost(found: list, names: tuple) -> list:
    """Those of ``found`` called one of ``names`` that lie inside no other."""
    by_id = {ev["args"]["id"]: ev for ev in found}

    def inside(ev) -> bool:
        at = by_id.get(ev["args"].get("parent"))
        while at is not None:
            if at["name"] in names:
                return True
            at = by_id.get(at["args"].get("parent"))
        return False

    return [ev for ev in found if ev["name"] in names and not inside(ev)]


def seconds(spans: list) -> float:
    return 1e-6 * sum(ev["dur"] for ev in spans)


def total(found: list, key: str):
    return sum(ev["args"].get(key, 0) for ev in found)


#: each metric as a function of the set-up's spans (the readers' one-line
#: files say in words what each is)
METRICS = {
    "operator_build_s": lambda found: seconds(outermost(found, ("space.build", "solver.build"))),
    "eager_programs": lambda found: total(
        [ev for ev in found if not is_launch(ev["name"])], "backend_compiles"),
    "entry_trace_s": lambda found: seconds(
        [ev for ev in found if ev["name"].endswith(".compile_entry_points")]),
    "first_dispatch_s": lambda found: seconds(
        [ev for ev in found if is_launch(ev["name"]) and ev["args"].get("lowerings", 0) > 0]),
    "cache_load_s": lambda found: float(total(found, "cache_load_s")),
}


def read(metric: str, run: dict):
    found = setup_spans(run)
    return None if found is None else METRICS[metric](found)
