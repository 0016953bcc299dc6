"""``periodic_interval``: ``interval``'s traffic for the horizontally periodic
model.  One ``Navier2D.new_periodic`` DNS advanced in intervals of
``steps_per_interval`` steps, each ``update_n(n)`` followed by
``get_observables()`` and nothing else.  With ``"mesh": P`` in the mix the
same model is pencil-decomposed over the first P chips
(``parallel.mesh.make_mesh``), one program across them; without it the model
sits whole on one device.

The window, its end rule, the compared interval and the read-back are
``interval.Driver``'s own methods, inherited: the window ends at the first
interval boundary at or after ``--seconds`` and every step in it counts;
compared is the window's own first interval, from the seed's initial values,
read back after the window closes.  Set-up follows ``interval``'s line by line.

What differs: the model's constructor and its mesh; the initial values
(``ic_periodic.smooth_periodic_fields``, periodic in x); and the plain
reference, which is the module the configuration names under ``reference``
(``reference_periodic.py``: it has a Fourier axis, which
``check.reference_for`` does not build).  The reference follows the interval
on one device whatever the mix's mesh.
"""

from __future__ import annotations

import importlib
import os
import time

from .. import check
from ..ic_periodic import smooth_periodic_fields
from . import interval


def reference_for(cfg: dict):
    """The plain reference the configuration names, at its grid and physics."""
    name = os.path.splitext(os.path.basename(cfg["reference"]))[0]
    g, ph = cfg["grid"], cfg["physics"]
    return importlib.import_module(f"benchmark.{name}").Reference(
        g["nx"], g["ny"], ph["ra"], ph["pr"], ph["dt"], ph["aspect"]
    )


class Driver(interval.Driver):
    def setup(self) -> None:
        t = time.perf_counter()
        import jax

        from rustpde_mpi_tpu import Navier2D, config
        from rustpde_mpi_tpu.parallel.mesh import make_mesh

        config.ensure_compile_cache()
        self.split["import_s"] = round(time.perf_counter() - t, 3)
        g, ph = self.cfg["grid"], self.cfg["physics"]
        t = time.perf_counter()
        chips = int(self.traffic.get("mesh", 0))
        mesh = make_mesh(jax.devices()[:chips]) if chips else None
        self.model = Navier2D.new_periodic(
            g["nx"], g["ny"], ph["ra"], ph["pr"], ph["dt"], ph["aspect"], ph["bc"], mesh=mesh
        )
        ic = self.traffic["ic"]
        self.initial = smooth_periodic_fields(
            g["nx"], g["ny"], self.seed, ic["amp"], ic["modes"], ph["aspect"]
        )
        for name, values in self.initial.items():
            self.model.set_field(name, values)
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
        self.model.get_field("temp")  # the read-back's own program, warmed too
        self.model.state = start

    def check(self) -> dict:
        fields = check.reference_fields(reference_for(self.cfg), self.initial, self.n)
        return check.compare_fields(self.answer, fields, self.traffic["check"])
