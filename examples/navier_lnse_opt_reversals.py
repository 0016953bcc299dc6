"""Optimal-perturbation campaign: gradient-based search for disturbances
that trigger flow reversals.

Port of /root/reference/examples/navier_lnse_opt_reversals.rs:24-80: find a
large-scale-circulation base state with the DNS, build its mirrored state as
the optimization target, then iterate energy-constrained steepest descent on
the initial perturbation using the adjoint gradient of the final-time
distance to the target.

Usage:  python examples/navier_lnse_opt_reversals.py [--quick]
  --quick shrinks the grid/horizons so the whole campaign runs in ~a minute.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from rustpde_mpi_tpu import (  # noqa: E402
    MeanFields,
    Navier2D,
    Navier2DNonLin,
    descent_iteration,
    integrate,
    mirrored_target,
)
from rustpde_mpi_tpu.models.lnse import l2_norm  # noqa: E402


def find_base_field(nx, ny, dt, ra, pr, aspect, max_time):
    model = Navier2D.new_confined(nx, ny, ra, pr, dt, aspect, "rbc")
    model.init_random(1e-3)
    model.write_intervall = max_time * 10
    integrate(model, max_time, save_intervall=max_time)
    return model


def main() -> int:
    quick = "--quick" in sys.argv
    tiny = "--tiny" in sys.argv  # CI smoke tier
    nx, ny = (12, 11) if tiny else (24, 21) if quick else (128, 57)
    ra, pr, aspect = 1e5, 1.0, 1.0
    dt = 0.02
    base_time = 4.0 if tiny else 20.0 if quick else 300.0
    max_iter = 1 if tiny else 3 if quick else 30
    horizons = [2.0] if tiny else [5.0] if quick else np.linspace(5.0, 50.0, 5)
    energies = [1e-4] if (tiny or quick) else np.logspace(10.0, 0.0, 7) / 1e10
    alpha_0 = 1.0
    beta1 = beta2 = 0.5

    base = find_base_field(nx, ny, dt, ra, pr, aspect, base_time)
    base.write("data/mean.h5")
    mean = MeanFields.read_from(nx, ny, "data/mean.h5", bc="rbc")
    # a snapshot holds the DNS's temperature without the boundary lift the
    # DNS keeps apart; the perturbation form's base state is the total field
    mean.temp = mean.temp + MeanFields.new_rbc(nx, ny).temp

    # target: mirrored base state, expressed as a perturbation about the mean
    target = mirrored_target(mean)

    for max_time in horizons:
        for e_constraint in energies:
            print(f"MAX TIME {max_time}  ENERGY {e_constraint:.2e}")
            model = Navier2DNonLin.new_confined(
                nx, ny, ra, pr, dt, aspect, "rbc", mean=mean
            )
            model.init_random(1e-3)
            # scale IC to the energy constraint
            u, v, t = (np.asarray(a) for a in model._phys(model.state))
            e0 = float(l2_norm(u, u, v, v, t, t, beta1, beta2)) / u.size
            fac = np.sqrt(e_constraint / e0)
            model.set_field("velx", u * fac)
            model.set_field("vely", v * fac)
            model.set_field("temp", t * fac)

            best = np.inf
            alpha, j_old = alpha_0, None
            for it in range(max_iter):
                # the campaign's loop body (navier_lnse_opt_reversals.rs:124-165)
                # lives in the library: models/opt_routines.py
                step = descent_iteration(
                    model, max_time, beta1, beta2, target, alpha, alpha_0, j_old
                )
                if step.alpha != alpha:
                    print(f"  set alpha: {step.alpha:4.2e}")
                alpha = step.alpha
                j_old = step.fun_val
                print(f"  iter {it}: J = {step.fun_val:.6e}  alpha = {alpha:.3f}")
                best = min(best, step.fun_val)
            print(f"  best J = {best:.6e}")
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
