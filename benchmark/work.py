"""Operations and bytes one solver step needs, from shapes alone.

The count follows the algorithm, not the implementation: it does not look at
the program, its jaxpr, its matmul precision or its kernels, so a roofline
share computed from it reads the same for a given device time whatever
implements the step.  One flop is one flop: a float32 product that today costs
the MXU six bfloat16 passes is counted once, against the chip's one-pass peak.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

#: dense one-axis products of one confined (Chebyshev x Chebyshev) step; a 2-D
#: operator is one product per axis.  Stencils, coefficient-space derivatives,
#: the divergence and the projection gradient are banded or triangular
#: recurrences, O(n^2), and are not counted.
CONFINED_PRODUCTS = {
    "synthesis of ux, uy": 2 * 2,
    "derivative syntheses, 3 fields x (d/dx, d/dy)": 3 * 2 * 2,
    "dealiased analysis of the 3 convection terms": 3 * 2,
    "3 ADI Helmholtz solves (quasi-inverse precondition + one inverse per axis)": 3 * 3,
    "fast-diagonalisation Poisson (2 modal maps in, 2 out)": 4,
}

#: distinct dense operators those products read (each at least once a step)
CONFINED_OPERATORS = 12


def step_work(nx: int, ny: int, members: int = 1, itemsize: int = 4) -> dict:
    """``{"flops", "bytes", "products"}`` of one step of ``members`` confined
    models on an nx x ny grid.

    flops: an n x n operator applied along one axis of an n x n field is n^3
    multiply-adds, 2 n^3 flops.  Every Chebyshev operator maps even modes and
    odd modes apart (the upstream's stride-2 structure), which halves that to
    n^3 flops in two half-size blocks; the total is ``products * n^3`` with n
    the mean extent.

    bytes: a lower bound on HBM traffic -- the five state fields read once and
    written once per member, and each distinct operator (half-size blocks)
    read once.  A fused implementation cannot move less; an unfused one moves
    more, which is its loss and not the yardstick's."""
    n = 0.5 * (nx + ny)
    products = sum(CONFINED_PRODUCTS.values())
    flops = members * products * n**3
    state = members * 2 * 5 * nx * ny * itemsize
    operators = CONFINED_OPERATORS * 0.5 * n * n * itemsize
    return {"flops": float(flops), "bytes": float(state + operators), "products": products}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json"), encoding="utf-8") as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise LookupError(
            f"no published peak for device_kind {device_kind!r} in benchmark/peaks.json "
            f"(known: {sorted(table)}); add it with its source"
        )
    return table[device_kind]


def roofline(work: dict, device_kind: str, seconds: float) -> dict:
    """Share of the roofline: least time the chip could take (the larger of
    flops over peak flops and bytes over peak bandwidth) over ``seconds``."""
    pk = peaks(device_kind)
    t_flops = work["flops"] / pk["bf16_flops_per_s"]
    t_bytes = work["bytes"] / pk["hbm_bytes_per_s"]
    return {
        "share": max(t_flops, t_bytes) / seconds,
        "bound": "compute" if t_flops >= t_bytes else "memory",
        "t_flops_s": t_flops,
        "t_bytes_s": t_bytes,
    }
