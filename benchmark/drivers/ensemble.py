"""``ensemble``: K members of one configuration advanced together, without the
service: ``NavierEnsemble.from_seeds`` then ``update_n(steps_per_interval)``
with the per-chunk ``steps_done`` read the server's campaign loop makes.  The
window ends as in ``interval``.  Member i starts from the program's random
initial condition for seed ``seed * K + i`` (uniform noise from
``numpy.random.default_rng``, which the reference draws for itself).

The compared chunk is the window's first, as in ``interval``: set-up keeps the
ensemble's state as built from the seeds, warms the program up with two chunks
and puts that state back; every member's state after the window's first chunk
is kept and read back once the window has closed, and the reference follows
every member through that chunk."""

from __future__ import annotations

import time

import numpy as np

from .. import check
from ..reference import random_fields


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

        from rustpde_mpi_tpu import Navier2D, NavierEnsemble, config

        config.ensure_compile_cache()
        self.split["import_s"] = round(time.perf_counter() - t, 3)
        g, ph, tr = self.cfg["grid"], self.cfg["physics"], self.traffic
        self.k = int(tr["members"])
        self.seeds = [self.seed * self.k + i for i in range(self.k)]
        t = time.perf_counter()
        model = Navier2D.new_confined(
            g["nx"], g["ny"], ph["ra"], ph["pr"], ph["dt"], ph["aspect"], ph["bc"]
        )
        self.ens = ens = NavierEnsemble.from_seeds(model, seeds=self.seeds, amp=tr["amp"])
        jax.block_until_ready(ens.state)
        start = (ens.state, ens.mask, ens.steps_done, ens.time)
        self.split["build_s"] = round(time.perf_counter() - t, 3)
        self.n = int(tr["steps_per_interval"])
        for key in ("first_interval_s", "warm_s"):
            t = time.perf_counter()
            ens.update_n(self.n)
            np.asarray(ens.steps_done)
            self.split[key] = round(time.perf_counter() - t, 3)
        ens.get_field("temp", 0)  # the read-back's own program, warmed too
        ens.state, ens.mask, ens.steps_done, ens.time = start

    def window(self) -> dict:
        tracer = self.ctx.tracer
        ens, n = self.ens, self.n
        trace_from = 1
        trace_to = trace_from + int(self.traffic["trace_intervals"])
        chunks = traced = 0
        start = np.asarray(ens.steps_done).copy()
        mark, started_at = self.ctx.meter.mark(), time.time()
        t0 = time.perf_counter()
        while True:
            if chunks == trace_from:
                tracer.begin()
            with tracer.span("bench:dispatch"):
                ens.update_n(n)
            with tracer.span("bench:read"):
                done = np.asarray(ens.steps_done)
            now = time.perf_counter()
            chunks += 1
            if chunks == 1:
                self.compared_state, self.compared_steps = ens.state, (done - start).tolist()
            if trace_from < chunks <= trace_to:
                traced += 1
            if chunks == trace_to:
                tracer.end()
            # a traced run stops with its trace: it reports no end-to-end number
            if chunks >= trace_to if tracer.on else now - t0 >= self.seconds:
                break
        elapsed = now - t0
        advanced = (done - start).astype(int)
        member_steps = int(advanced.sum())
        return {
            "started_at": started_at,
            "compiles": self.ctx.meter.since(mark),
            "window_s": elapsed,
            "attempted": chunks * self.k,
            # a member that stopped advancing (diverged and frozen) failed
            "failed": int(((chunks * n) - advanced).sum() // n),
            "metrics": {"member_steps_per_s": member_steps / elapsed},
            "work": f"{chunks} chunks of {n} steps x {self.k} members = {member_steps} member-steps",
            "steps": chunks * n,
            "dispatches": chunks,
            "traced_steps": traced * n,
            "traced_dispatches": traced,
            "members": self.k,
        }

    def release(self) -> None:
        """Read every member's fields after the compared chunk back, then drop
        the ensemble."""
        self.ens.state = self.compared_state
        self.answer = [
            {name: self.ens.get_field(name, i) for name in check.FIELDS} for i in range(self.k)
        ]
        self.ens = self.compared_state = None

    def check(self) -> dict:
        g, tr = self.cfg["grid"], self.traffic
        ref = check.reference_for(self.cfg)
        worst = {f"{k}_rel": 0.0 for k in check.FIELDS}
        for i, seed in enumerate(self.seeds):
            initial = random_fields((g["nx"], g["ny"]), tr["amp"], seed)
            gaps = check.field_gaps(self.answer[i], check.reference_fields(ref, initial, self.n))
            for k in check.FIELDS:
                worst[f"{k}_rel"] = max(worst[f"{k}_rel"], gaps[k])
        out = {k: (v, float(tr["check"][k])) for k, v in worst.items()}
        short = sum(self.n - s for s in self.compared_steps)
        out["steps_short"] = (float(short), 0.0)
        return out
