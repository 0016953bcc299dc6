"""Operations and bytes one forward step plus one adjoint step of the
optimal-perturbation iteration need, from shapes alone (``work.py``'s rules:
the count follows the algorithm, not the program's precision, layout, bucket
schedule or scan shape; one flop is counted once against the one-pass peak).

Left out, as in ``work.py``: stencils, coefficient-space derivatives, the
divergence, the projection gradient and the pressure update (banded or
triangular recurrences, O(n^2)); and here also the pointwise products of the
convection terms, the base state's own convection and Laplacians (constants
of the run, read like the base state's fields but not counted apart), the
functional, the terminal condition and the descent (once an iteration, not
once a step)."""

from __future__ import annotations

#: dense one-axis products of the forward step: ``work.CONFINED_PRODUCTS``'s
#: 35 (the perturbation form adds pointwise terms to the DNS step, no product)
FORWARD_PRODUCTS = {
    "synthesis of u, v": 2 * 2,
    "derivative syntheses, 3 fields x (d/dx, d/dy)": 3 * 2 * 2,
    "dealiased analysis of the 3 convection terms": 3 * 2,
    "3 ADI Helmholtz solves (quasi-inverse precondition + one inverse per axis)": 3 * 3,
    "fast-diagonalisation Poisson (2 modal maps in, 2 out)": 4,
}

#: of the adjoint step: three adjoint fields synthesised, their six
#: derivatives, and the stored trajectory's two velocities and six derivatives
ADJOINT_PRODUCTS = {
    "synthesis of u*, v*, t*": 3 * 2,
    "derivative syntheses, 3 adjoint fields x (d/dx, d/dy)": 3 * 2 * 2,
    "synthesis of the trajectory's u, v": 2 * 2,
    "derivative syntheses of the trajectory, 3 fields x (d/dx, d/dy)": 3 * 2 * 2,
    "dealiased analysis of the 3 convection terms": 3 * 2,
    "3 ADI Helmholtz solves": 3 * 3,
    "fast-diagonalisation Poisson": 4,
}

#: distinct dense operators each step reads (``work.CONFINED_OPERATORS``)
OPERATORS = 12
#: physical fields of the base state each step reads: U, V and the six
#: gradients both steps use, and dT/dx, dT/dy
BASE_FIELDS = 9


def pair_work(nx: int, ny: int, itemsize: int = 4) -> dict:
    """``{"flops", "bytes", "products"}`` of one forward step and one adjoint
    step on an nx x ny grid.

    flops: a 2-D operator is one product per axis, so half of the products
    run along x (an nx x nx operator on an nx x ny field: 2 nx^2 ny flops)
    and half along y; the Chebyshev parity split halves each.  At nx = ny
    this is ``work.step_work``'s ``products * n^3``.

    bytes, a lower bound: the five state fields read once and written once
    by each step; each distinct operator (half-size blocks) read once by each
    step; the base state's fields read once by each step; the trajectory's
    three fields written once (forward) and read once (adjoint)."""
    products = sum(FORWARD_PRODUCTS.values()) + sum(ADJOINT_PRODUCTS.values())
    flops = products * 0.5 * (nx * nx * ny + nx * ny * ny)
    field = nx * ny * itemsize
    state = 2 * 2 * 5 * field
    operators = 2 * OPERATORS * 0.5 * 0.5 * (nx * nx + ny * ny) * itemsize
    base = 2 * BASE_FIELDS * field
    history = 2 * 3 * field
    return {"flops": float(flops), "bytes": float(state + operators + base + history),
            "products": products}
