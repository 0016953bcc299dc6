"""Initial conditions made by the benchmark from ``--seed`` (inputs of the
program and of the reference alike)."""

from __future__ import annotations

import numpy as np


def smooth_fields(nx: int, ny: int, seed: int, amp: float = 0.1, modes: int = 4) -> dict:
    """Physical values of temp, velx, vely on the Chebyshev-Gauss-Lobatto grid:
    a few low sine modes with amplitudes, wavenumbers and signs drawn from the
    seed.  The velocity comes from a stream function that vanishes with its
    normal derivative on every wall, so it is no-slip and divergence-free; the
    temperature perturbation vanishes on the plates.  Every seed gives another
    flow of the same smoothness.

    The first mode is always the single roll (m = n = 1) with its velocity
    turning the way its temperature drives it: the shape of the confined
    cell's growing instability.  The instability amplifies whatever lies along
    it, rounding noise included; a draw that all but misses it leaves that
    noise large against the flow it seeds, and two sound float32 programs then
    differ by percents (PERF.md, section 2)."""
    rng = np.random.default_rng(int(seed))
    x = 0.5 * (1.0 - np.cos(np.pi * np.arange(nx) / (nx - 1)))[:, None]  # [0, 1]
    y = 0.5 * (1.0 - np.cos(np.pi * np.arange(ny) / (ny - 1)))[None, :]
    temp = np.zeros((nx, ny))
    velx = np.zeros((nx, ny))
    vely = np.zeros((nx, ny))
    for i in range(modes):
        m, n = rng.integers(1, 4, size=2)
        a, b = rng.uniform(0.5, 1.0, size=2) * rng.choice([-1.0, 1.0], size=2)
        if i == 0:
            m = n = 1
            b = -np.sign(a) * abs(b)  # warm fluid (a > 0: at x = 0) rises
        temp += a * np.cos(np.pi * m * x) * np.sin(np.pi * n * y)
        # psi = sin^2(pi m x) sin^2(pi n y) / (pi n); u = dpsi/dy, v = -dpsi/dx
        sx, sy = np.sin(np.pi * m * x), np.sin(np.pi * n * y)
        velx += b * sx**2 * 2.0 * sy * np.cos(np.pi * n * y)
        vely -= b * (m / n) * 2.0 * sx * np.cos(np.pi * m * x) * sy**2
    scale = amp / modes
    return {"temp": scale * temp, "velx": scale * velx, "vely": scale * vely}
