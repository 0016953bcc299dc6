"""Physics-level tests for the Navier2D model.

Mirrors the reference's observational validation strategy (SURVEY.md S4):
divergence-free projection, decay to the conduction state below the critical
Rayleigh number, convective instability above it, and the periodic
(Fourier x Chebyshev) configuration.
"""

import numpy as np
import pytest

from rustpde_mpi_tpu import Navier2D


def test_step_runs_and_is_finite():
    model = Navier2D.new_confined(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc")
    model.update()
    for arr in model.state:
        assert np.all(np.isfinite(np.asarray(arr)))
    assert model.get_time() == pytest.approx(0.01)


def test_projection_controls_divergence():
    # incremental pressure correction: the divergence error is O(dt) per step
    # and shrinks as the accumulated pressure converges
    model = Navier2D(25, 25, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
    model.set_velocity(0.1, 1.0, 1.0)
    model.set_temperature(0.1, 1.0, 1.0)
    model.update_n(20)
    div_early = model.div_norm()
    model.update_n(180)
    # measured ~1.2e-4 at t=2 under the truncated-B2 (reference-exact)
    # discretization (was ~1e-4 before it); decays to ~2e-5 by t=6
    assert model.div_norm() < 2e-4
    assert model.div_norm() < 0.5 * div_early


def test_subcritical_decay_to_conduction():
    # Ra = 100 << Ra_c ~ 1708: any disturbance decays; Nu -> 1 (pure conduction)
    model = Navier2D.new_confined(17, 17, 100.0, 1.0, 0.05, 1.0, "rbc")
    re_start = model.eval_re()
    model.update_n(400)
    assert model.eval_re() < 0.05 * max(re_start, 1e-10)
    assert model.eval_nu() == pytest.approx(1.0, abs=1e-3)
    assert model.eval_nuvol() == pytest.approx(1.0, abs=1e-3)


def test_supercritical_convection_grows():
    # Ra = 1e5 >> Ra_c: kinetic energy must grow from a small smooth seed
    model = Navier2D(33, 33, 1e5, 1.0, 0.005, 1.0, "rbc", periodic=False)
    model.set_velocity(0.01, 1.0, 1.0)
    model.set_temperature(0.01, 1.0, 1.0)
    model.update_n(100)
    re_early = model.eval_re()
    model.update_n(500)
    assert model.eval_re() > 2.0 * re_early


def test_conduction_state_is_near_fixed_point():
    # zero IC: temp stays zero (lift field carries the linear profile, whose
    # laplacian vanishes); velocity stays small once pressure absorbs buoyancy
    model = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
    model.update_n(200)
    assert np.abs(np.asarray(model.state.temp)).max() < 1e-5
    assert np.abs(model.get_field("vely")).max() < 1e-4
    assert model.eval_nu() == pytest.approx(1.0, abs=1e-4)


def test_hc_boundary_condition_runs():
    model = Navier2D.new_confined(17, 17, 1e4, 1.0, 0.01, 1.0, "hc")
    model.update_n(10)
    for arr in model.state:
        assert np.all(np.isfinite(np.asarray(arr)))


def test_periodic_hc_runs_and_convects():
    """Horizontally-periodic horizontal convection (the reference's
    navier_periodic_hc_mpi example config): the cos-bottom heating drives a
    finite circulation."""
    model = Navier2D.new_periodic(16, 17, 1e5, 1.0, 0.01, 1.0, "hc")
    model.set_velocity(0.2, 1.0, 1.0)
    model.set_temperature(0.2, 1.0, 1.0)
    model.update_n(100)
    nu, nuvol, re, div = model.get_observables()
    assert np.isfinite([nu, nuvol, re, div]).all()
    assert re > 0.1  # flow actually moves
    assert div < 1e-1


def test_periodic_model_runs_divergence_controlled():
    model = Navier2D(16, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=True)
    model.set_velocity(0.1, 1.0, 1.0)
    model.set_temperature(0.1, 1.0, 1.0)
    model.update_n(100)
    assert np.iscomplexobj(np.asarray(model.state.temp))
    assert model.div_norm() < 5e-3
    for arr in model.state:
        assert np.all(np.isfinite(np.asarray(arr)))


def test_periodic_subcritical_decay():
    model = Navier2D.new_periodic(16, 17, 100.0, 1.0, 0.05, 1.0, "rbc")
    model.update_n(400)
    # subcritical: convection decays to the conduction state, Nu -> 1.
    # (The reference's periodic-axis weights sum to n/(n-1) so its periodic Nu
    # carries a resolution-dependent factor, /root/reference/src/field.rs:139-141
    # + field/average.rs:28-35; this repo deliberately normalizes over the full
    # period — see field._axis_length — so Nu is exactly 1 here.)
    assert model.eval_nu() == pytest.approx(1.0, abs=1e-3)


def test_exit_is_false_for_healthy_run():
    model = Navier2D.new_confined(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc")
    model.update()
    assert model.exit() is False


def test_nan_divergence_early_exit_in_chunk():
    """In-chunk failure detection (reference: per-step ``pde.exit()``,
    /root/reference/src/lib.rs:187-219): once the flow is NaN the scanned
    chunk stops stepping on device — the step counter threaded through the
    scan carry freezes at the first NaN step instead of burning the chunk."""
    import jax.numpy as jnp

    model = Navier2D.new_confined(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc")

    # healthy state: all 64 scheduled steps execute (_step_n donates
    # nothing, so model.state stays usable after the call)
    _, done = model._step_n(model.state, 64)
    assert int(done) == 64
    # poison one temperature mode: the first step produces a NaN field, the
    # remaining 63 iterations take the identity branch
    bad = model.state._replace(
        temp=model.state.temp.at[(0,) * model.state.temp.ndim].set(jnp.nan)
    )
    frozen, done = model._step_n(bad, 64)
    assert int(done) == 1
    # the driver-visible criterion fires at the next boundary
    model.state = frozen
    model._obs_cache = None
    assert model.exit() is True
