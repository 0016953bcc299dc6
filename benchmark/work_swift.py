"""Operations and bytes one doubly periodic Swift-Hohenberg step needs, from
shapes alone.  ``work.py``'s rules: the count follows the algorithm and not the
implementation (it looks at no program, jaxpr, precision or kernel, so the same
device time reads the same share whatever implements the step), and one flop is
one flop, counted once against the chip's one-pass peak.

The algorithm is the unfolded dense step as the plain reference runs it
(``reference_swift.py``): a step is one synthesis and one analysis, and with
my = ny // 2 + 1 complex modes along y each transform is

* along y the real-to-complex transform (or back) as two real products of an
  nx x ny field with an ny x my cosine and sine matrix: 2 * nx ny (2 my) flops;
* along x the complex transform as four real products of an nx x nx cosine or
  sine matrix with an nx x my real or imaginary part: 4 * 2 nx^2 my flops.

(A fast transform would need fewer, and a fold that halves every product half
as many: the dense product is the algorithm the MXU is given, as in
``work_periodic.py``.)  Left out, each O(nx ny): the cube, the implicit
division, the pin and the Hermitian projection.
"""

from __future__ import annotations

#: transforms of one step: the synthesis of theta, the analysis of theta^3
TRANSFORMS = 2
#: real matrix products of one transform: two along y, four along x
PRODUCTS = {"along y (cosine, sine)": 2, "along x (complex by complex)": 4}


def transform_flops(nx: int, ny: int) -> int:
    my = ny // 2 + 1
    return 2 * nx * ny * 2 * my + 4 * 2 * nx * nx * my


def step_work(nx: int, ny: int, itemsize: int = 4) -> dict:
    """``{"flops", "bytes", "products"}`` of one step on nx x ny.

    bytes: a lower bound on HBM traffic: the spectrum (Re and Im, nx x my)
    read once and written once, the implicit operator read once, and along
    each axis one cosine and one sine matrix read once (an analysis matrix is
    its synthesis matrix transposed and scaled, so no implementation has to
    read more)."""
    my = ny // 2 + 1
    state = (2 * 2 + 1) * nx * my * itemsize
    operators = 2 * (ny * my + nx * nx) * itemsize
    return {"flops": float(TRANSFORMS * transform_flops(nx, ny)),
            "bytes": float(state + operators),
            "products": TRANSFORMS * sum(PRODUCTS.values())}
