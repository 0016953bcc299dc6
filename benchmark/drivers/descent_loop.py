"""``descent_loop``: one optimisation of the upstream's optimal-perturbation
campaign (``examples/navier_lnse_opt_reversals.rs``), iterations back to
back.  One interval is one call of the library's iteration
(``rustpde_mpi_tpu.descent_iteration``): fresh pressure, the nonlinear forward
sweep of ``steps_per_interval`` steps with its trajectory stored, J, the
hand-adjoint sweep of as many steps backward through it, the source's
backtracking rule for alpha and the energy-constrained update, the new initial
condition set on the model.  ``steps_per_s`` counts forward and backward steps
alike: twice ``steps_per_interval`` an iteration.

The window's rule is ``interval``'s: it ends at the first iteration boundary
at or after ``--seconds``, elapsed time runs to the moment that iteration's
new initial condition is set on the device, nothing is cut or dropped.
``failed`` counts iterations whose J is not finite.

Set-up makes the base state (the program's own confined DNS at the cell's
grid from ``ic.smooth_fields``, run to ``base_time``; its temperature plus the
conduction profile is the total field, ``ic_lnse.py``), the mirrored target
and the model, sets the seed's initial perturbation at the mix's energy, keeps
that state, warms up with two iterations and puts the kept state back.

Compared is the window's own first iteration: its J, its three gradient
fields, and the initial condition it leaves, read back from the device after
the window has closed; the plain reference (the module the configuration
names, ``reference_lnse.py``) follows that iteration from the same base state
and the same initial perturbation.  Every later iteration is held to a finite
J and to the energy constraint, from numbers the loop has anyway: the largest
change of energy from one initial condition to the next is compared too
(``energy_rel``; the first against the mix's energy).  How far the last one
has drifted from the mix's energy is in the window's log line: the update
keeps the energy of the fields it is given, so float32 rounding adds up over
the iterations of a window.
"""

from __future__ import annotations

import importlib
import math
import os
import time

import numpy as np

from ..ic import smooth_fields
from ..ic_lnse import conduction_profile, perturbation

FIELDS = ("velx", "vely", "temp")


def base_state(cfg: dict, traffic: dict, seed: int) -> dict:
    """Physical velx, vely and total temp of the base state: the confined DNS
    at the cell's grid and physics from the seed's smooth initial values,
    after ``base_time``."""
    from rustpde_mpi_tpu import Navier2D

    g, ph = cfg["grid"], cfg["physics"]
    dns = Navier2D.new_confined(g["nx"], g["ny"], ph["ra"], ph["pr"], ph["dt"], ph["aspect"], ph["bc"])
    ic = traffic["base_ic"]
    for name, values in smooth_fields(g["nx"], g["ny"], seed, ic["amp"], ic["modes"]).items():
        dns.set_field(name, values)
    dns.update_n(round(cfg["optimisation"]["base_time"] / ph["dt"]))
    base = {k: np.asarray(dns.get_field(k), np.float64) for k in FIELDS}
    base["temp"] = base["temp"] + conduction_profile(g["nx"], g["ny"])
    return base


def mean_fields(nx: int, ny: int, base: dict):
    """The program's ``MeanFields`` of the base state's physical values."""
    from rustpde_mpi_tpu import MeanFields

    space = MeanFields._space(nx, ny, False)
    return MeanFields(space, *(space.forward(np.asarray(base[k])) for k in FIELDS))


def reference_for(cfg: dict, base: dict):
    """The plain reference the configuration names, about ``base``."""
    name = os.path.splitext(os.path.basename(cfg["reference"]))[0]
    g, ph = cfg["grid"], cfg["physics"]
    return importlib.import_module(f"benchmark.{name}").Reference(
        g["nx"], g["ny"], ph["ra"], ph["pr"], ph["dt"], ph["aspect"], base
    )


def point_energy(fields, beta1: float, beta2: float) -> float:
    u, v, t = (np.asarray(a, np.float64) for a in fields)
    return float(0.5 * np.sum(beta1 * (u * u + v * v) + beta2 * t * t) / u.size)


def compare(answer: dict, ref: dict, limits: dict) -> dict:
    """``name -> (value, limit)``: J relative to the reference's, each
    gradient field and each field of the new initial condition as
    ||program - reference|| / ||reference||, and the energy constraint."""
    def gap(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b)) if np.isfinite(a).all() else math.inf

    out = {"fun_val_rel": (abs(answer["fun_val"] / ref["fun_val"] - 1.0), float(limits["fun_val_rel"]))}
    for kind in ("grad", "new"):
        for k in FIELDS:
            name = f"{kind}_{k}"
            out[f"{name}_rel"] = (gap(answer[name], ref[name]), float(limits[f"{name}_rel"]))
    out["energy_rel"] = (float(answer["energy_rel"]), float(limits["energy_rel"]))
    return out


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic, self.seed, self.seconds, self.log = (
            ctx.cfg, ctx.traffic, ctx.seed, ctx.seconds, ctx.log
        )
        self.split = {}

    def iterate(self, alpha: float, fun_old):
        """One interval: the library's iteration, to the moment the new
        initial condition is on the device."""
        import jax

        from rustpde_mpi_tpu import descent_iteration

        step = descent_iteration(
            self.model, self.max_time, self.beta1, self.beta2, self.target,
            alpha, self.alpha_0, fun_old,
        )
        jax.block_until_ready(self.model.state)
        return step

    def setup(self) -> None:
        t, mark = time.perf_counter(), self.ctx.meter.mark()
        import jax

        from rustpde_mpi_tpu import Navier2DNonLin, config, mirrored_target

        config.ensure_compile_cache()
        self.split["import_s"] = round(time.perf_counter() - t, 3)
        g, ph, opt = self.cfg["grid"], self.cfg["physics"], self.cfg["optimisation"]
        self.n = int(self.traffic["steps_per_interval"])
        self.max_time = self.n * ph["dt"]
        self.beta1, self.beta2, self.alpha_0 = opt["beta1"], opt["beta2"], opt["alpha_0"]
        self.energy = float(opt["energies"][int(self.traffic["energy_index"])])
        t = time.perf_counter()
        self.base = base_state(self.cfg, self.traffic, self.seed)
        self.split["base_state_s"] = round(time.perf_counter() - t, 3)
        t = time.perf_counter()
        mean = mean_fields(g["nx"], g["ny"], self.base)
        self.target = mirrored_target(mean)
        self.model = Navier2DNonLin.new_confined(
            g["nx"], g["ny"], ph["ra"], ph["pr"], ph["dt"], ph["aspect"], ph["bc"], mean=mean
        )
        self.initial = perturbation(
            g["nx"], g["ny"], self.seed, self.energy, self.beta1, self.beta2,
            self.traffic["ic"]["modes"],
        )
        for name in FIELDS:
            self.model.set_field(name, self.initial[name])
        jax.block_until_ready(self.model.state)
        start = self.model.state
        self.split["build_s"] = round(time.perf_counter() - t, 3)
        self.log(f"bench: horizon {self.max_time:g} = {self.n} steps a sweep, energy {self.energy:.4g}")
        # two iterations: the first loads (or compiles) the sweeps' programs,
        # the second leaves nothing of a first call's one-time work behind
        alpha, fun_old = self.alpha_0, None
        for key in ("first_iteration_s", "warm_s"):
            t = time.perf_counter()
            step = self.iterate(alpha, fun_old)
            alpha, fun_old = step.alpha, step.fun_val
            self.split[key] = round(time.perf_counter() - t, 3)
        self.model.get_field("temp")  # the read-back's own program, warmed too
        self.model.state = start
        built = self.ctx.meter.since(mark)
        self.split["programs"] = {"compiled": built["compiled"], "loaded": built["cache_loads"],
                                  "compile_s": round(built["compile_s"], 3)}

    def window(self) -> dict:
        tracer = self.ctx.tracer
        n2 = 2 * self.n
        trace_from = 1
        trace_to = trace_from + int(self.traffic["trace_intervals"])
        done = bad = traced = 0
        off, held = 0.0, self.energy
        alpha, fun_old = self.alpha_0, None
        mark, started_at = self.ctx.meter.mark(), time.time()
        t0 = time.perf_counter()
        while True:
            if done == trace_from:
                tracer.begin()
            with tracer.span("bench:iteration"):
                step = self.iterate(alpha, fun_old)
            now = time.perf_counter()
            alpha, fun_old = step.alpha, step.fun_val
            done += 1
            bad += not math.isfinite(step.fun_val)
            energy = point_energy(step.fields, self.beta1, self.beta2)
            off, held = max(off, abs(energy / held - 1.0)), energy
            if done == 1:
                self.compared_step, self.compared_state = step, self.model.state
            if trace_from < done <= trace_to:
                traced += 1
            if done == trace_to:
                tracer.end()
            # a traced run stops with its trace: it reports no end-to-end number
            if done >= trace_to if tracer.on else now - t0 >= self.seconds:
                break
        elapsed = now - t0
        self.energy_rel = off
        return {
            "started_at": started_at,
            "compiles": self.ctx.meter.since(mark),
            "window_s": elapsed,
            "attempted": done,
            "failed": bad,
            "metrics": {"steps_per_s": done * n2 / elapsed},
            "work": f"{done} iterations of {self.n} + {self.n} steps = {done * n2} steps; "
                    f"last J {fun_old!r}, alpha {alpha!r}, energy drift {held / self.energy - 1.0:.3g}",
            "steps": done * n2,
            "dispatches": done,
            "traced_steps": traced * n2,
            "traced_dispatches": traced,
            "members": 1,
        }

    def release(self) -> None:
        """The compared iteration's J and gradient (on the host since the
        iteration made them) and the initial condition it left, read back
        from the device; then drop the model."""
        step = self.compared_step
        self.log(f"bench: the window's first iteration: J {step.fun_val!r}, alpha {step.alpha!r}")
        self.model.state = self.compared_state
        self.answer = {"fun_val": step.fun_val, "alpha": step.alpha, "energy_rel": self.energy_rel}
        for name, grad in zip(FIELDS, step.grads):
            self.answer[f"grad_{name}"] = np.asarray(grad)
            self.answer[f"new_{name}"] = self.model.get_field(name)
        self.model = self.target = self.compared_state = self.compared_step = None

    def check(self) -> dict:
        ref = reference_for(self.cfg, self.base).iteration(
            self.initial, self.n, self.beta1, self.beta2, self.alpha_0
        )
        return compare(self.answer, ref, self.traffic["check"])
