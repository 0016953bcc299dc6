"""``interval``: one DNS advanced in intervals of ``steps_per_interval`` steps,
each ``Navier2D.update_n(n)`` followed by ``get_observables()`` (the read a
user's ``integrate`` callback makes) and nothing else.

The window ends at the first interval boundary at or after ``--seconds``;
elapsed time runs to the moment that interval's state and observables are on
the host; every step in it counts.  No interval is cut, none is dropped.

The compared interval is the window's first.  Set-up builds the one model
object, sets the seed's initial values, keeps that state, warms the program up
with two intervals and puts the kept state back; the window then starts from
the seed's initial values, through the same call on the same object, and the
state after its first interval is kept (a reference to device buffers: the
program never donates what the caller can see) and read back once the window
has closed.  The reference follows that interval from the same values.
"""

from __future__ import annotations

import math
import time

from .. import check
from ..ic import smooth_fields


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic, self.seed, self.seconds, self.log = (
            ctx.cfg, ctx.traffic, ctx.seed, ctx.seconds, ctx.log
        )
        self.split = {}

    def setup(self) -> None:
        t = time.perf_counter()
        import jax

        from rustpde_mpi_tpu import Navier2D, config

        config.ensure_compile_cache()
        self.split["import_s"] = round(time.perf_counter() - t, 3)
        g, ph = self.cfg["grid"], self.cfg["physics"]
        t = time.perf_counter()
        self.model = Navier2D.new_confined(
            g["nx"], g["ny"], ph["ra"], ph["pr"], ph["dt"], ph["aspect"], ph["bc"]
        )
        ic = self.traffic["ic"]
        self.initial = smooth_fields(g["nx"], g["ny"], self.seed, ic["amp"], ic["modes"])
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

    def window(self) -> dict:
        tracer = self.ctx.tracer
        import jax

        model, n = self.model, self.n
        trace_from = 1
        trace_to = trace_from + int(self.traffic["trace_intervals"])
        done = bad = traced = 0
        mark, started_at = self.ctx.meter.mark(), time.time()
        t0 = time.perf_counter()
        while True:
            if done == trace_from:
                tracer.begin()
            with tracer.span("bench:dispatch"):
                model.update_n(n)
            with tracer.span("bench:read"):
                obs = model.get_observables()
                jax.block_until_ready(model.state)
            now = time.perf_counter()
            done += 1
            bad += not all(math.isfinite(v) for v in obs)
            if done == 1:
                self.compared_state, self.compared_obs = model.state, obs
            if trace_from < done <= trace_to:
                traced += 1
            if done == trace_to:
                tracer.end()
            # a traced run stops with its trace: it reports no end-to-end number
            if done >= trace_to if tracer.on else now - t0 >= self.seconds:
                break
        elapsed = now - t0
        return {
            "started_at": started_at,
            "compiles": self.ctx.meter.since(mark),
            "window_s": elapsed,
            "attempted": done,
            "failed": bad,
            "metrics": {"steps_per_s": done * n / elapsed},
            "work": f"{done} intervals of {n} steps = {done * n} steps",
            "steps": done * n,
            "dispatches": done,
            "traced_steps": traced * n,
            "traced_dispatches": traced,
            "members": 1,
            "last_observables": obs,
        }

    def release(self) -> None:
        """Read the compared interval's fields back, then drop the model."""
        self.log(f"bench: observables after the window's first interval: {self.compared_obs}")
        self.model.state = self.compared_state
        self.answer = {k: self.model.get_field(k) for k in check.FIELDS}
        self.model = self.compared_state = None

    def check(self) -> dict:
        ref = check.reference_for(self.cfg)
        fields = check.reference_fields(ref, self.initial, self.n)
        return check.compare_fields(self.answer, fields, self.traffic["check"])
