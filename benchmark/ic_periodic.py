"""Initial conditions of the periodic cells, made by the benchmark from
``--seed`` (inputs of the program and of the reference alike)."""

from __future__ import annotations

import numpy as np


def smooth_periodic_fields(nx: int, ny: int, seed: int, amp: float = 0.1, modes: int = 4,
                           aspect: float = 1.0) -> dict:
    """Physical values of temp, velx, vely on uniform x in [0, 2 pi) times the
    Chebyshev-Gauss-Lobatto y in [-1, 1]: a few low modes with amplitudes,
    wavenumbers, phases and signs drawn from the seed.  Every mode is periodic
    in x.  The velocity comes from a stream function that vanishes with its
    normal derivative on both plates, so it is no-slip and divergence-free
    (``aspect`` scales x, as the program's and the reference's gradients do);
    the temperature perturbation vanishes on the plates.

    The first mode is always two roll pairs across the period (wavenumber 2,
    the integer nearest the layer's critical 1.56 for plates 2 apart), one
    half wave high, each roll turning the way its temperature drives it: the
    shape the layer's instability grows.  The instability amplifies whatever
    lies along it, rounding noise included; a draw that all but misses it
    leaves that noise large against the flow it seeds (PERF.md, section 2, the
    confined cell's single roll)."""
    rng = np.random.default_rng(int(seed))
    x = (2.0 * np.pi * np.arange(nx) / nx)[:, None]
    y = 0.5 * (1.0 - np.cos(np.pi * np.arange(ny) / (ny - 1)))[None, :]  # [0, 1], plate to plate
    temp = np.zeros((nx, ny))
    velx = np.zeros((nx, ny))
    vely = np.zeros((nx, ny))
    for i in range(modes):
        m, n = rng.integers(1, 4, size=2)
        a, b = rng.uniform(0.5, 1.0, size=2) * rng.choice([-1.0, 1.0], size=2)
        p, q = rng.uniform(0.0, 2.0 * np.pi, size=2)
        if i == 0:
            m, n, q = 2, 1, p
            b = np.sign(a) * abs(b)  # warm fluid (a cos > 0) rises
        temp += a * np.cos(m * x + p) * np.sin(np.pi * n * y)
        # psi = -(b/m) sin(m x + q) sin^2(pi n y) aspect; u = dpsi/dy, v = -dpsi/dx / aspect,
        # with dy/d(physical y) = 1/2 (the plates are 2 apart)
        sy = np.sin(np.pi * n * y)
        velx -= b * (aspect * np.pi * n / m) * np.sin(m * x + q) * sy * np.cos(np.pi * n * y)
        vely += b * np.cos(m * x + q) * sy**2
    scale = amp / modes
    return {"temp": scale * temp, "velx": scale * velx, "vely": scale * vely}
