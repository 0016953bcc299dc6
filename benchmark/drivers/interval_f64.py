"""``interval_f64``: ``interval``'s traffic for a configuration that runs in
the upstream's own precision, float64 throughout (``env``: ``RUSTPDE_X64`` =
"1").  One ``Navier2D.new_confined`` DNS advanced in intervals of
``steps_per_interval`` steps, each ``update_n(n)`` followed by
``get_observables()`` and nothing else.

Set-up, the window, its end rule, the compared interval and the read-back are
``interval.Driver``'s own methods, inherited: the same calls on the same
model class, the same chunk programs, spans and stage scopes as the float32
cell makes; what the program decides by its precision it decides itself.

What differs:

* the plain reference is ``reference.py``'s ``Reference`` with
  ``dtype=numpy.float64``: operators built in float64 numpy and never cast,
  every product a float64 ``jnp.matmul`` at ``Precision.HIGHEST``
  (``check.reference_for`` builds the float32 one whatever the file says; a
  float32 reference cannot judge a float64 configuration).  It follows the
  compared interval from the same initial values on the device after the
  window, as the float32 cell's does;
* the driver refuses to run in a process whose precision is not the
  configuration's.  Precision is an import-time switch of the program: a
  process that has imported it in float32 cannot run this cell, and there is
  no second set of limits for such a process to be held to.
"""

from __future__ import annotations

import numpy as np

from .. import check
from ..reference import Reference
from . import interval


def reference_for(cfg: dict) -> Reference:
    """The confined plain reference in float64, at the configuration's grid
    and physics."""
    g, ph = cfg["grid"], cfg["physics"]
    return Reference(g["nx"], g["ny"], ph["ra"], ph["pr"], ph["dt"], ph["aspect"],
                     dtype=np.float64)


class Driver(interval.Driver):
    def setup(self) -> None:
        from rustpde_mpi_tpu import config

        want = str(self.cfg["env"]["RUSTPDE_X64"]) != "0"
        if config.X64 != want:
            raise RuntimeError(
                f"{self.cfg['name']} runs with RUSTPDE_X64={self.cfg['env']['RUSTPDE_X64']} "
                f"(float64 state, operators and products) and this process imported the "
                f"program with X64={config.X64}: precision is fixed when the program is "
                "imported, so run the cell in a process of its own (python3 -m benchmark.run "
                "sets the configuration's env first)"
            )
        super().setup()

    def check(self) -> dict:
        fields = check.reference_fields(reference_for(self.cfg), self.initial, self.n)
        return check.compare_fields(self.answer, fields, self.traffic["check"])
