"""Pallas fused step (Helmholtz/Poisson megakernel): interpreter-mode parity.

Mirrors tests/test_pallas_conv.py for the implicit half of the step
(ops/pallas_step.py, RUSTPDE_STEP_KERNEL knob): the fused solve/projection
kernels run in Pallas interpreter mode on CPU so tier-1 exercises the real
kernel path on every layout without a chip.  Documented tolerances: the
fused chain computes the same linear solves with one reassociation (tiled
GEMM accumulation vs the dense solver chain), so 5-step trajectory parity
is fp-epsilon in f64 — the acceptance floor is 1e-12 on the physical-field
scale, observed ~1e-15.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import rustpde_mpi_tpu as rp
from rustpde_mpi_tpu.ops.pallas_step import (
    FusedStage,
    StageTerm,
    build_model_step,
    step_kernel_choice,
    step_traffic_estimate,
)

_LAYOUTS = {
    # CPU-default confined: non-sep Chebyshev x Chebyshev, fft transforms
    "confined": (False, {}),
    # CPU-default periodic: complex r2c Fourier x Chebyshev
    "periodic": (True, {}),
    # TPU confined layout: sep Chebyshev x sep Chebyshev, matmul transforms
    "confined_sep": (False, {"RUSTPDE_FORCE_TPU_PATH": "1"}),
    # TPU periodic layout: split Re/Im Fourier x sep Chebyshev
    "split_sep": (True, {"RUSTPDE_FORCE_TPU_PATH": "1", "RUSTPDE_SEP": "1"}),
}


def _build_navier(periodic, nx=None, ny=None, **kw):
    if nx is None:
        nx, ny = (16, 17) if periodic else (17, 17)
    m = rp.Navier2D(nx, ny, 1e4, 1.0, 5e-3, 1.0, "rbc", periodic=periodic, **kw)
    m.set_velocity(0.1, 1.0, 1.0)
    m.set_temperature(0.1, 1.0, 1.0)
    return m


def _assert_trajectory_parity(dense, pal, steps=3, atol=1e-13):
    dense.update_n(steps)
    pal.update_n(steps)
    attrs = ["temp", "velx", "vely", "pres", "pseu"]
    if hasattr(dense.state, "scal"):
        attrs.append("scal")
    for attr in attrs:
        np.testing.assert_allclose(
            np.asarray(getattr(pal.state, attr)),
            np.asarray(getattr(dense.state, attr)),
            atol=atol,
            err_msg=attr,
        )
    assert pal.eval_nu() == pytest.approx(dense.eval_nu(), abs=1e-12)


# -- model-level dense-vs-pallas parity, all four layouts ---------------------


@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_navier_step_knob_parity(monkeypatch, layout):
    """RUSTPDE_STEP_KERNEL=pallas: 3-step trajectories match the dense
    solver chain at fp-epsilon per layout (acceptance floor 1e-12 on the
    physical-field scale; observed ~1e-15)."""
    periodic, env = _LAYOUTS[layout]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    # the sep layouts are exercised at 33^2 like the conv suite (17 is
    # below the auto-sep threshold; FORCE_TPU_PATH pins the layout anyway)
    nx, ny = ((16, 17) if periodic else (33, 33)) if env else (None, None)
    dense = _build_navier(periodic, nx, ny)
    assert dense._step_impl is None  # default knob: byte-identical dense path
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    assert step_kernel_choice() == "pallas"
    pal = _build_navier(periodic, nx, ny)
    assert pal._step_impl is not None
    _assert_trajectory_parity(dense, pal)


@pytest.mark.slow
def test_navier_step_knob_parity_scenario(monkeypatch):
    """Coriolis + passive-scalar scenario: the extra stage terms (rotation
    coupling) and the scal stage ride the fused path."""
    scn = {"coriolis": 2.0, "passive_scalar": True, "scalar_kappa": None}
    dense = _build_navier(False, scenario=scn)
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    pal = _build_navier(False, scenario=scn)
    ic = np.random.default_rng(0).standard_normal((17, 17)) * 0.1
    dense.set_field("scal", ic)
    pal.set_field("scal", ic)
    assert pal._step_impl is not None and "scal" in pal._step_impl
    _assert_trajectory_parity(dense, pal)


@pytest.mark.slow
def test_navier_step_knob_parity_solid(monkeypatch):
    """The solid-mask penalization epilogue is shared by both branches of
    _make_step; the fused solves must compose with it unchanged."""
    dense = _build_navier(False)
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    pal = _build_navier(False)
    mask = np.zeros((17, 17))
    mask[6:10, 6:10] = 1.0
    dense.set_solid(mask, 0.3, 1e-2)
    pal.set_solid(mask, 0.3, 1e-2)
    _assert_trajectory_parity(dense, pal)


@pytest.mark.slow
def test_set_dt_rebuilds_step_kernels(monkeypatch):
    """dt appears in the Helmholtz factors and lift constants: a dt rung
    change must rebuild the fused stages (the _DT_ARTIFACTS contract)."""
    dense = _build_navier(False)
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    pal = _build_navier(False)
    old = pal._step_impl
    dense.set_dt(2.5e-3)
    pal.set_dt(2.5e-3)
    assert pal._step_impl is not None and pal._step_impl is not old
    _assert_trajectory_parity(dense, pal)


# -- stage-level kernel-vs-reference parity -----------------------------------


def _stage_inputs(m, rng):
    def rnd(sp):
        return sp.forward(jnp.asarray(rng.standard_normal(sp.shape_physical)))

    sp_u, sp_p, sp_t = m.velx_space, m.pres_space, m.temp_space
    sp_f, sp_q = m.field_space, m.pseu_space
    ins = {
        "velx": [rnd(sp_u), rnd(sp_p), rnd(sp_f)],
        "vely": [rnd(sp_u), rnd(sp_p), rnd(sp_t), rnd(sp_f)],
        "temp": [rnd(sp_t), rnd(sp_f)],
        "scal": [rnd(sp_t), rnd(sp_f)],
        "div": [rnd(sp_u), rnd(sp_u)],
        "poisson": [rnd(sp_q)],
        "projx": [rnd(sp_q)],
        "projy": [rnd(sp_q)],
    }
    if m._coriolis():
        ins["velx"].append(rnd(sp_u))
        ins["vely"].append(rnd(sp_u))
    return ins


@pytest.mark.parametrize("periodic", [False, True])
def test_stage_apply_matches_reference(monkeypatch, periodic):
    """Every fused stage: pallas_call == the same padded chain as plain XLA
    dots (kernel-plumbing parity, isolated from the model surroundings)."""
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    m = _build_navier(periodic)
    rng = np.random.default_rng(7)
    ins = _stage_inputs(m, rng)
    for name, stage in m._step_impl.items():
        xs = ins[name]
        ref = np.asarray(stage.reference(*xs))
        out = np.asarray(stage.apply(*xs))
        assert out.dtype == ref.dtype
        np.testing.assert_allclose(
            out, ref, atol=1e-12 * max(1.0, np.abs(ref).max()), err_msg=name
        )


def test_poisson_stage_pins_singular_mode(monkeypatch):
    """The pressure Poisson kernel's output mask hard-zeros the singular
    mean mode — the downstream pin_zero_mode is then the identity."""
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    m = _build_navier(False)
    rng = np.random.default_rng(3)
    div = m.pseu_space.forward(
        jnp.asarray(rng.standard_normal(m.pseu_space.shape_physical))
    )
    out = m._step_impl["poisson"].apply(div)
    assert np.asarray(out)[0, 0] == 0.0
    np.testing.assert_array_equal(
        np.asarray(m.pseu_space.pin_zero_mode(out)), np.asarray(out)
    )


# -- dtype / cast contracts ---------------------------------------------------


def _toy_modal_stage(cast=None):
    rng = np.random.default_rng(0)
    r0, k0, k1, q1 = 9, 11, 13, 10
    terms = [
        StageTerm(rng.standard_normal((r0, k0)), rng.standard_normal((q1, k1)), False),
        StageTerm(rng.standard_normal((r0, k0)), rng.standard_normal((q1, k1)), False),
    ]
    dinv = 1.0 / (1.0 + np.arange(r0)[:, None] + np.arange(q1)[None, :])
    b0 = rng.standard_normal((r0, r0))
    b1 = rng.standard_normal((q1, q1))
    xs = [rng.standard_normal((k0, k1)) for _ in terms]
    return FusedStage("toy", terms, False, modal=(dinv, b0, b1), cast=cast), xs


def test_f32_cast_stage():
    stage, xs = _toy_modal_stage(cast=np.float32)
    xs = [jnp.asarray(x, dtype=jnp.float32) for x in xs]
    out = np.asarray(stage.apply(*xs))
    ref = np.asarray(stage.reference(*xs))
    assert out.dtype == np.float32
    np.testing.assert_allclose(out, ref, atol=2e-5 * max(1.0, np.abs(ref).max()))


def test_f64_hybrid_keeps_solves_in_f64(monkeypatch):
    """RUSTPDE_F64_HYBRID casts only the convection transforms to f32; the
    implicit solves stay f64 on BOTH paths (build_model_step passes
    cast=None), so knob parity holds at fp-epsilon even under hybrid."""
    monkeypatch.setenv("RUSTPDE_F64_HYBRID", "1")
    dense = _build_navier(False)
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    pal = _build_navier(False)
    for stage in pal._step_impl.values():
        assert stage._cast is None
    _assert_trajectory_parity(dense, pal)


# -- batching -----------------------------------------------------------------


def test_vmapped_stage_bit_equality(monkeypatch):
    """vmap over a fused stage == per-member applies, bit-identical (the
    ensemble engine re-vmaps the step jaxpr through the pallas_call)."""
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    m = _build_navier(False)
    stage = m._step_impl["velx"]
    rng = np.random.default_rng(1)
    K = 3
    xs = [
        jnp.stack([sp.forward(jnp.asarray(rng.standard_normal(sp.shape_physical)))
                   for _ in range(K)])
        for sp in (m.velx_space, m.pres_space, m.field_space)
    ]
    batched = np.asarray(jax.vmap(stage.apply)(*xs))
    solo = np.stack(
        [np.asarray(stage.apply(*(x[k] for x in xs))) for k in range(K)]
    )
    np.testing.assert_array_equal(batched, solo)


def test_navier_ensemble_knob_parity(monkeypatch):
    """The vmapped ensemble dispatch rides the fused solve path unchanged."""
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    model = _build_navier(False)
    ens = rp.NavierEnsemble.from_seeds(model, seeds=range(2))
    ens.update_n(3)
    assert ens.alive().all()
    solo = _build_navier(False)
    solo.init_random(0.1, seed=0)
    solo.update_n(3)
    np.testing.assert_allclose(
        np.asarray(ens.state.temp[0]), np.asarray(solo.state.temp), atol=1e-13
    )


# -- governed bit-path contracts ----------------------------------------------


def test_recompile_flat_across_knob_flips(monkeypatch):
    """The knob binds at model build: flipping RUSTPDE_STEP_KERNEL under a
    LIVE model must not leak rebuilds (recompile_count stays flat) and must
    not change which path the live model runs."""
    dense = _build_navier(False)
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    pal = _build_navier(False)
    before = (dense.recompile_count, pal.recompile_count)
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "dense")
    pal.update_n(4)
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    dense.update_n(4)
    assert (dense.recompile_count, pal.recompile_count) == before
    assert dense._step_impl is None and pal._step_impl is not None


def test_default_dense_builds_no_kernels(monkeypatch):
    """Knob default `dense`: no fused stages are built, the step closure
    takes the existing dense branch — byte-identical prior behavior."""
    monkeypatch.delenv("RUSTPDE_STEP_KERNEL", raising=False)
    assert step_kernel_choice() == "dense"
    m = _build_navier(False)
    assert m._step_impl is None


# -- traffic accounting --------------------------------------------------------


def test_step_traffic_estimate(monkeypatch):
    """The HBM-bytes-per-step model: at toy grids the LANE-quantized
    operator padding dominates (ratio < 1 — honest, not hidden); at
    production grids the fused path moves strictly less than the dense
    dispatch chain.  The crossover sits between 129^2 and 257^2."""
    monkeypatch.setenv("RUSTPDE_STEP_KERNEL", "pallas")
    toy = step_traffic_estimate(_build_navier(False))
    assert toy["pallas_bytes_per_step"] > 0
    assert toy["dense_bytes_per_step"] > 0
    big = step_traffic_estimate(
        rp.Navier2D(257, 257, 1e4, 1.0, 5e-3, 1.0, "rbc", periodic=False)
    )
    assert big["traffic_ratio"] > 1.0 > toy["traffic_ratio"]


def test_build_model_step_standalone():
    """build_model_step works on a dense-knob model too (bench/traffic
    probes build throwaway kernel sets without flipping the model)."""
    m = _build_navier(False)
    assert m._step_impl is None
    impl = build_model_step(m, interpret=True)
    assert set(impl) >= {"velx", "vely", "temp", "div", "poisson", "projx", "projy"}
