"""``swift_interval``: ``interval``'s traffic for the doubly periodic
Swift-Hohenberg model.  One ``SwiftHohenberg2D`` advanced in intervals of
``steps_per_interval`` steps, each ``update_n(n)`` followed by
``get_observables()`` and nothing else, closed loop, on from one state.

The window, its end rule and the compared interval are ``interval.Driver``'s
own method, inherited: the window ends at the first interval boundary at or
after ``--seconds`` and every step in it counts; compared is the window's own
first interval, from the seed's initial values, read back after the window
closes.  Set-up follows ``interval``'s line by line.

What differs:

* the model's constructor, and the initial values: the source's uniform noise
  (``ic_swift.uniform_noise``), one array handed to program and reference;
* an interval fails on a non-finite observable, as in ``interval``, and also
  where the model's ``mean`` observable (the modulus of the constant mode,
  which every step pins to zero) is not under ``check.mean_abs``: the window
  reads every interval's observables anyway and keeps them for this;
* the plain reference is ``reference_swift.py`` (two Fourier axes, no solver:
  ``check.reference_for`` builds the confined one whatever the file says).  It
  follows the compared interval from the same array on one device after the
  window, and two numbers are compared: ``theta_rel``, the field on the
  physical grid, ||program - reference|| / ||reference||, and ``norm_rel``, the
  source's |F| (the model's first observable against the reference's own sum).
"""

from __future__ import annotations

import math
import time

import numpy as np

from ..ic_swift import uniform_noise
from ..reference_swift import Reference
from . import interval


def reference_for(cfg: dict) -> Reference:
    g, ph = cfg["grid"], cfg["physics"]
    return Reference(g["nx"], g["ny"], ph["r"], ph["dt"], ph["length"])


def compare(answer: dict, ref: Reference, state, limits: dict) -> dict:
    """``name -> (value, limit)`` of the two numbers compared."""
    want = ref.backward(state)
    got = np.asarray(answer["theta"], np.float64)
    gap = float(np.linalg.norm(got - want) / np.linalg.norm(want)) if np.isfinite(got).all() \
        else float("inf")
    norm = ref.norm(state)
    return {
        "theta_rel": (gap, float(limits["theta_rel"])),
        "norm_rel": (abs(float(answer["norm"]) - norm) / norm, float(limits["norm_rel"])),
    }


class Driver(interval.Driver):
    def setup(self) -> None:
        t = time.perf_counter()
        import jax

        from rustpde_mpi_tpu import SwiftHohenberg2D, config

        config.ensure_compile_cache()
        self.split["import_s"] = round(time.perf_counter() - t, 3)
        g, ph = self.cfg["grid"], self.cfg["physics"]
        t = time.perf_counter()
        self.model = SwiftHohenberg2D(g["nx"], g["ny"], ph["r"], ph["dt"], ph["length"])
        self.initial = uniform_noise(g["nx"], g["ny"], self.seed, self.traffic["ic"]["amp"])
        self.model.set_theta(self.initial)
        jax.block_until_ready(self.model.state)
        start = self.model.state
        self.split["build_s"] = round(time.perf_counter() - t, 3)
        self.n = int(self.traffic["steps_per_interval"])
        # two intervals: the first loads (or compiles) the program, the second
        # leaves nothing of a first call's one-time work for the window
        for key in ("first_interval_s", "warm_s"):
            t = time.perf_counter()
            self.model.update_n(self.n)
            self.model.get_observables()
            jax.block_until_ready(self.model.state)
            self.split[key] = round(time.perf_counter() - t, 3)
        self.model.theta_physical()  # the read-back's own program, warmed too
        self.model.state = start

    def window(self) -> dict:
        seen, read = [], self.model.get_observables
        # the instance's own attribute for the window's length: the same call,
        # with what it returned kept
        self.model.get_observables = lambda: seen.append(read()) or seen[-1]
        try:
            win = super().window()
        finally:
            del self.model.get_observables
        ceiling = float(self.traffic["check"]["mean_abs"])
        win["failed"] = sum(
            not (all(math.isfinite(v) for v in obs) and obs[3] < ceiling) for obs in seen
        )
        return win

    def release(self) -> None:
        """Read the compared interval's field back, then drop the model."""
        self.log(f"bench: observables after the window's first interval: {self.compared_obs}")
        self.model.state = self.compared_state
        self.answer = {"theta": self.model.theta_physical(), "norm": self.compared_obs[0]}
        self.model = self.compared_state = None

    def check(self) -> dict:
        ref = reference_for(self.cfg)
        state = ref.run(ref.initial_state(self.initial), self.n)
        return compare(self.answer, ref, state, self.traffic["check"])
