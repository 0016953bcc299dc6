"""Share of the chip's roofline a forward step plus an adjoint step reach: the
least time the chip could take for the operations and bytes the ALGORITHM
needs for the pair (benchmark/work_lnse.py, from shapes alone, one flop once
against the one-pass bf16 peak of benchmark/peaks.json, the trajectory written
once and read once) over the measured device time per pair.  It does not
follow the program's precision, layout, buckets or scan shape (operators and
kernels; moves steps_per_s)."""
UNIT, LAYER, MOVES = "%", "operators and kernels", "steps_per_s"


def read(trace, run):
    from .. import work, work_lnse

    if not run.get("traced_steps"):
        return None
    grid = run["cfg"]["grid"]
    per_pair = 2.0 * trace["busy_s"] / run["traced_steps"]
    return 100.0 * work.roofline(
        work_lnse.pair_work(grid["nx"], grid["ny"]), run["device"]["kind"], per_pair
    )["share"]
