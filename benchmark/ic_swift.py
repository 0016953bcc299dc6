"""Initial condition of the Swift-Hohenberg cell, made by the benchmark from
``--seed`` (the input of the program and of the reference alike)."""

from __future__ import annotations

import numpy as np


def uniform_noise(nx: int, ny: int, seed: int, amp: float = 0.1) -> np.ndarray:
    """Physical values of theta on the nx x ny grid: uniform noise in
    [-amp, amp), the source's initial state (``init_random(0.1)`` in
    ``examples/swift_hohenberg_2d.rs``), here drawn from the seed."""
    return np.random.default_rng(int(seed)).uniform(-amp, amp, size=(int(nx), int(ny)))
