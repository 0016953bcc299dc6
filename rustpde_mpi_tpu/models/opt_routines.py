"""Gradient-based optimization routines.

Rebuild of /root/reference/src/navier_stokes_lnse/opt_routines.rs:15-56, and
the loop body of the optimal-perturbation campaign that drives it
(/root/reference/examples/navier_lnse_opt_reversals.rs:24-80).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import tracing as _tr
from .campaign import _LAYER
from .meanfield import MeanFields


def steepest_descent_energy_constrained(
    velx_0: np.ndarray,
    vely_0: np.ndarray,
    temp_0: np.ndarray,
    grad_velx: np.ndarray,
    grad_vely: np.ndarray,
    grad_temp: np.ndarray,
    beta1: float,
    beta2: float,
    alpha: float,
):
    """Steepest descent without energy increase: project the gradient
    perpendicular to the state, then rotate on the constant-energy sphere by
    angle ``alpha`` (opt_routines.rs:15-56).

    Returns ``(velx_new, vely_new, temp_new)`` (the reference mutates its
    output arguments; this is the functional form).
    """
    if alpha > 2.0 * np.pi:
        raise ValueError("alpha must be less than 2 pi")

    def inner(a1, a2, b1, b2, c1, c2):
        """``l2_norm`` (functions.rs:32-57) per grid point, on the host
        arrays handed in, accumulated in float64 whatever their dtype: the
        projection below is the small difference of two such numbers."""
        total = np.sum(beta1 * (a1 * a2 + b1 * b2) + beta2 * (c1 * c2), dtype=np.float64)
        return 0.5 * float(total) / a1.size

    e0 = inner(velx_0, velx_0, vely_0, vely_0, temp_0, temp_0)
    eg = inner(grad_velx, velx_0, grad_vely, vely_0, grad_temp, temp_0)

    # project gradient perpendicular to x0
    ee = eg / e0
    gu = grad_velx - ee * velx_0
    gv = grad_vely - ee * vely_0
    gt = grad_temp - ee * temp_0

    # linear combination of old field and gradient on the energy sphere
    ee2 = np.sqrt(e0 / inner(gu, gu, gv, gv, gt, gt))
    ca, sa = np.cos(alpha), np.sin(alpha)
    velx_new = velx_0 * ca + gu * (ee2 * sa)
    vely_new = vely_0 * ca + gv * (ee2 * sa)
    temp_new = temp_0 * ca + gt * (ee2 * sa)
    return velx_new, vely_new, temp_new


def mirrored_target(mean: MeanFields) -> MeanFields:
    """The x-mirrored base state (the reversed circulation) as a perturbation
    about ``mean``: what the campaign steers the final state towards
    (navier_lnse_opt_reversals.rs:7-13, 40-52)."""
    mu, mv, mt = mean.physical()
    space = mean.space
    return MeanFields(
        space,
        velx=space.forward(np.asarray(-mu[::-1, :] - mu)),
        vely=space.forward(np.asarray(-mv - mv)),
        temp=space.forward(np.asarray(mt[::-1, :] - mt)),
    )


class DescentStep(NamedTuple):
    """What one :func:`descent_iteration` leaves on the host."""

    fun_val: float  #: J of the initial condition the iteration started from
    alpha: float  #: the rotation angle used, after the backtracking rule
    grads: tuple  #: (grad_u, grad_v, grad_t), physical space
    fields: tuple  #: (velx, vely, temp) of the new initial condition


def descent_iteration(
    model,
    max_time: float,
    beta1: float,
    beta2: float,
    target: MeanFields | None,
    alpha: float,
    alpha_0: float = 1.0,
    fun_old: float | None = None,
) -> DescentStep:
    """One iteration of the campaign's loop on ``model``, whose state holds
    the current initial condition (navier_lnse_opt_reversals.rs:124-165):
    fresh pressure, ``grad_adjoint`` to ``max_time``, the backtracking rule
    (``alpha`` halved when J rose above ``fun_old``, back to ``alpha_0`` once
    below 1e-3), the energy-constrained steepest-descent update, and the new
    initial condition set on the model.

    Spans (model step): ``lnse.descent_iteration`` (``steps`` forward +
    backward, ``alpha``, ``fun_val``) > ``lnse.grad_adjoint`` and
    ``lnse.descent_update`` (projection, rotation on the energy sphere, the
    new initial condition transformed and set)."""
    steps = 2 * max(1, round(max_time / model.dt))
    with _tr.span("lnse.descent_iteration", layer=_LAYER, steps=steps) as whole:
        # fresh pressure every iteration (navier_lnse_opt_reversals.rs:127-131)
        model.state = model.state._replace(
            pres=jnp.zeros_like(model.state.pres),
            pseu=jnp.zeros_like(model.state.pseu),
        )
        model.reset_time()
        u0, v0, t0 = jax.device_get(model.physical())  # one wait for the three
        fun_val, grads = model.grad_adjoint(max_time, None, beta1, beta2, target=target)
        # backtracking step control (navier_lnse_opt_reversals.rs:143-152)
        if fun_old is not None and fun_val > fun_old:
            alpha /= 2.0
            if alpha < 1e-3:
                alpha = alpha_0
        with _tr.span("lnse.descent_update", layer=_LAYER):
            fields = steepest_descent_energy_constrained(
                u0, v0, t0, *(np.asarray(g) for g in grads), beta1, beta2, alpha
            )
            model.reset_time()
            model.set_fields(*fields)
        whole.set(alpha=float(alpha), fun_val=float(fun_val))
    return DescentStep(float(fun_val), float(alpha), tuple(grads), fields)
