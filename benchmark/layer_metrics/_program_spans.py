"""What the four readers of the program's own spans share.  The program
keeps every span it closes in a ring (``rustpde_mpi_tpu/telemetry/tracing.py``)
and ``tracing.spans(name)`` reads it from inside the same process, which the
harness is: ``(t0_ns, dur_ns, id, parent, args)``, oldest first.  A traced
run stops with its trace and neither ``release()`` nor ``check()`` dispatches
again, so the last ``run["traced_dispatches"]`` spans of a name are the
traced ones.  A program without that read side (an older commit), a recorder
that is off, or a ring that holds too few spans reads ``None``, never 0."""


def traced_spans(name: str, run: dict):
    n = run.get("traced_dispatches")
    if not n:
        return None
    try:
        from rustpde_mpi_tpu.telemetry import tracing
    except ImportError:
        return None
    read = getattr(tracing, "spans", None)
    if read is None or not tracing.enabled():
        return None
    found = read(name)
    return found[-n:] if len(found) >= n else None


def mean_duration_ms(name: str, run: dict):
    found = traced_spans(name, run)
    if found is None:
        return None
    return 1e-6 * sum(dur_ns for _, dur_ns, *_ in found) / len(found)


def mean_count(name: str, key: str, run: dict):
    found = traced_spans(name, run)
    if found is None or any(key not in args for *_, args in found):
        return None
    return sum(args[key] for *_, args in found) / len(found)
