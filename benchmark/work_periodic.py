"""Operations and bytes one horizontally periodic (Fourier x Chebyshev) solver
step needs, from shapes alone.  ``work.py``'s rules: the count follows the
algorithm and not the implementation (it looks at no program, jaxpr, precision
or kernel, so the same device time reads the same share whatever implements
the step), and one flop is one flop, counted once against the chip's one-pass
peak.

With m = nx // 2 + 1 complex modes along x and ny Chebyshev points along y, a
field's spectrum is m x ny complex numbers, and the step applies two kinds of
dense one-axis product:

* a real ny x ny Chebyshev operator along y, to the real and to the imaginary
  part of a spectrum: 2 * 2 m ny^2 flops, halved because every Chebyshev
  operator maps even and odd modes apart (``work.py``'s rule): 2 m ny^2;
* the real-to-complex transform along x as a dense (2m x nx) cosine/sine
  product on nx x ny values: 2 * 2m nx ny flops, halved because cosine rows are
  even and sine rows odd under x_j -> x_(n-j): 2 m nx ny.  (A fast transform
  would need fewer; the dense product is the algorithm the MXU is given, as the
  dense Chebyshev transform is in ``work.py``.)

Counted, one step (``Y_PRODUCTS`` / ``X_PRODUCTS``):

    synthesis of ux, uy                               2 along y + 2 along x
    derivative syntheses, 3 fields x (d/dx, d/dy)     6 + 6
    dealiased analysis of the 3 convection terms      3 + 3
    3 ADI Helmholtz solves, the Chebyshev inverse     3 + 0
    Poisson, the Chebyshev modal map in and out       2 + 0

Left out, each an O(nx ny) or banded/triangular O(nx ny * band) application
by its definition, whatever the program spends on it: the Fourier factor of
the Helmholtz solves and the Poisson division (per-wavenumber scalings); d/dx
(a multiplication by i k); the quasi-inverse precondition of each Helmholtz
solve (banded); the Galerkin stencils ``to_ortho`` of temp, velx, vely, pseu
(two-band); the coefficient-space d/dy of the pressure gradient and of the
divergence (a triangular recurrence); the projection of grad(pseu) onto the
velocity space (a banded solve); the buoyancy and the right-hand sides' sums;
the lift's constants.  The program applies several of those as dense products
of its own (PERF.md, section 5, says what share of the measured step the count
describes); that is its loss and not the yardstick's.
"""

from __future__ import annotations

#: dense Chebyshev products along y of one periodic step (see above)
Y_PRODUCTS = {
    "synthesis of ux, uy": 2,
    "derivative syntheses, 3 fields x (d/dx, d/dy)": 6,
    "dealiased analysis of the 3 convection terms": 3,
    "3 ADI Helmholtz solves (the Chebyshev inverse)": 3,
    "Poisson (Chebyshev modal map in and out)": 2,
}
#: dense real-to-complex (or back) Fourier products along x
X_PRODUCTS = {
    "synthesis of ux, uy": 2,
    "derivative syntheses, 3 fields x (d/dx, d/dy)": 6,
    "dealiased analysis of the 3 convection terms": 3,
}
#: distinct dense operators those products read (each at least once a step):
#: along y the synthesis, its derivative, the dealiased analysis, two Helmholtz
#: inverses and the Poisson maps in and out; along x the transform and its inverse
Y_OPERATORS, X_OPERATORS = 7, 2


def step_work(nx: int, ny: int, itemsize: int = 4) -> dict:
    """``{"flops", "bytes", "products"}`` of one periodic step on nx x ny.

    bytes: a lower bound on HBM traffic: the five state spectra (m x ny
    complex) read once and written once, and each distinct operator
    (half-size blocks) read once."""
    m = nx // 2 + 1
    y_products, x_products = sum(Y_PRODUCTS.values()), sum(X_PRODUCTS.values())
    flops = y_products * 2 * m * ny * ny + x_products * 2 * m * nx * ny
    state = 2 * 5 * (2 * m * ny) * itemsize
    operators = (Y_OPERATORS * 0.5 * ny * ny + X_OPERATORS * 0.5 * 2 * m * nx) * itemsize
    return {"flops": float(flops), "bytes": float(state + operators),
            "products": y_products + x_products}
