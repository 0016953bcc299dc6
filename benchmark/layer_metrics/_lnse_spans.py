"""What the two per-step readers of the optimisation's sweeps share."""


def per_step_us(name: str, run: dict):
    from ._program_spans import traced_spans

    found = traced_spans(name, run)
    if found is None or any(not args.get("steps") for *_, args in found):
        return None
    return 1e-3 * sum(dur_ns / args["steps"] for _, dur_ns, _, _, args in found) / len(found)
