"""Share of the roofline the whole periodic (Fourier x Chebyshev) step
reaches: the least time the cell's chips could take for the operations and
bytes the ALGORITHM needs (benchmark/work_periodic.py, from shapes alone, one
flop counted once against the one-pass bf16 peak of benchmark/peaks.json times
the chips of the mix's mesh) over the measured device time per step (busy time,
mean over the device planes).  It does not follow the program's matmul
precision, layout or partitioning: the same device time reads the same share
whatever implements the step (operators and kernels; moves steps_per_s).
"""
UNIT, LAYER, MOVES = "%", "operators and kernels", "steps_per_s"


def read(trace, run):
    from .. import work, work_periodic

    if not run.get("traced_steps"):
        return None
    grid = run["cfg"]["grid"]
    chips = int(run["traffic"].get("mesh", 1))
    per_step = trace["busy_s"] / run["traced_steps"]
    # P chips could take a P-th of one chip's least time
    return 100.0 * work.roofline(
        work_periodic.step_work(grid["nx"], grid["ny"]), run["device"]["kind"], per_step * chips
    )["share"]
