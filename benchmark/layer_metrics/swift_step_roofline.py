"""Share of the chip's roofline the whole Swift-Hohenberg step reaches: the
least time the chip could take for the operations and bytes the ALGORITHM needs
(benchmark/work_swift.py: the unfolded dense transforms of the plain reference,
from shapes alone, one flop counted once against the one-pass bf16 peak of
benchmark/peaks.json) over the measured device time per step.  It does not
follow the program's matmul precision, its folds or its kernels: the same
device time reads the same share whatever implements the step (operators and
kernels; moves steps_per_s)."""
UNIT, LAYER, MOVES = "%", "operators and kernels", "steps_per_s"


def read(trace, run):
    from .. import work, work_swift

    if not run.get("traced_steps"):
        return None
    grid = run["cfg"]["grid"]
    per_step = trace["busy_s"] / run["traced_steps"]
    return 100.0 * work.roofline(
        work_swift.step_work(grid["nx"], grid["ny"]), run["device"]["kind"], per_step
    )["share"]
