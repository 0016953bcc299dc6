"""Seeded inputs of the ``lnse_opt128_f32`` cell, handed to the program and to
the reference alike: the initial perturbation of the optimisation, and the
boundary lift that makes a DNS's temperature the total field."""

from __future__ import annotations

import numpy as np

from .ic import smooth_fields
from .reference import cgl_points


def perturbation(nx: int, ny: int, seed: int, energy: float, beta1: float, beta2: float,
                 modes: int = 4) -> dict:
    """Physical values of velx, vely, temp: ``ic.smooth_fields``'s draw for
    ``seed + 1`` (the seed itself draws the base state's initial values),
    no-slip, divergence-free and zero on the plates, scaled to the energy
    ``0.5 sum(beta1 (u^2 + v^2) + beta2 t^2) / (nx ny) = energy``: the
    source's normalisation of its ``init_random`` draw
    (``navier_lnse_opt_reversals.rs:60-70``)."""
    f = smooth_fields(nx, ny, int(seed) + 1, 1.0, modes)
    e = 0.5 * np.sum(beta1 * (f["velx"] ** 2 + f["vely"] ** 2) + beta2 * f["temp"] ** 2) / (nx * ny)
    fac = np.sqrt(energy / e)
    return {k: fac * v for k, v in f.items()}


def conduction_profile(nx: int, ny: int) -> np.ndarray:
    """The ``rbc`` lift ``-y/2`` on the grid: +0.5 on the bottom plate, -0.5
    on the top.  A DNS keeps it apart from its temperature variable; the base
    state of the perturbation form is the sum."""
    return np.broadcast_to((-0.5 * cgl_points(ny))[None, :], (nx, ny)).copy()
