"""Multi-model workload subsystem tests (rustpde_mpi_tpu/workloads/ +
models/campaign.py): the CampaignModel protocol across dns/lnse/adjoint,
solo-vs-ensemble equivalence of the ported models (including across a
drain/restore cycle), the eigenmode-sweep and steady-find workload gates,
and the scenario step modifiers (passive scalar, rotating frame, vmapped
geometry sweep) with their analytic validation cases."""

import os

import numpy as np
import pytest

from rustpde_mpi_tpu import (
    MeanFields,
    Navier2D,
    Navier2DAdjoint,
    Navier2DLnse,
    NavierEnsemble,
    ScenarioConfig,
    SimRequest,
)
from rustpde_mpi_tpu.config import IOConfig
from rustpde_mpi_tpu.models.navier import scenario_signature
from rustpde_mpi_tpu.utils.resilience import ResilientRunner
from rustpde_mpi_tpu.workloads import (
    build_model,
    build_model_for_key,
    critical_rayleigh,
    eigenmode_sweep,
    geometry_sweep,
    growth_rates,
    model_kinds,
    solo_ensemble_parity,
    steady_state_find,
    validate_campaign_model,
)

h5py = pytest.importorskip("h5py")

_ARGS = dict(nx=17, ny=17, ra=1e4, pr=1.0, dt=0.01, aspect=1.0, bc="rbc")


def _dns(**kw):
    args = {**_ARGS, **kw}
    m = Navier2D(
        args["nx"], args["ny"], args["ra"], args["pr"], args["dt"],
        args["aspect"], args["bc"], periodic=False,
        scenario=args.get("scenario"),
    )
    m.set_velocity(0.1, 1.0, 1.0)
    m.set_temperature(0.1, 1.0, 1.0)
    m.write_intervall = 1e9
    return m


def _lnse():
    m = Navier2DLnse.new_confined(
        17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", mean=MeanFields.new_rbc(17, 17)
    )
    m.write_intervall = 1e9
    return m


# -- the CampaignModel protocol ----------------------------------------------


def test_campaign_model_protocol_all_kinds():
    """Every registered kind builds a model satisfying the full contract,
    with a kind-prefixed compat key that round-trips through the registry's
    key-based builder (the serve scheduler's campaign constructor)."""
    assert set(model_kinds()) >= {"dns", "lnse", "adjoint"}
    for kind in model_kinds():
        model = build_model(kind, 17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", False)
        assert validate_campaign_model(model) == [], kind
        key = model.compat_key
        assert key[0] == kind  # the rest is the kind's own (the DNS family's 10-tuple, swift's 7)
        rebuilt = build_model_for_key(key)
        assert rebuilt.compat_key == key
    with pytest.raises(KeyError, match="unknown model kind"):
        build_model("nope", 17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", False)


def test_scenario_signature_canonical():
    """Dataclass and request-dict forms sign identically; defaults sign
    empty (equal to no scenario); modifiers re-bucket compat keys."""
    assert scenario_signature(None) == ()
    assert scenario_signature(ScenarioConfig()) == ()
    assert scenario_signature({"coriolis": 0.0}) == ()
    cfg = ScenarioConfig(coriolis=2.0, passive_scalar=True)
    assert scenario_signature(cfg) == scenario_signature(cfg.to_dict())
    assert cfg.signature == (("coriolis", 2.0), ("passive_scalar", 0.0))

    plain = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
    rot = Navier2D(
        17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False,
        scenario=ScenarioConfig(coriolis=1.0),
    )
    assert plain.compat_key != rot.compat_key
    assert rot.compat_key == build_model_for_key(rot.compat_key).compat_key
    # requests sign the same way — scenario traffic buckets separately
    req = SimRequest(ra=1e4, horizon=0.1, nx=17, ny=17, dt=0.01,
                     scenario={"coriolis": 1.0})
    assert req.compat_key == rot.compat_key


# -- scenario modifiers: analytic validation ----------------------------------


def test_passive_scalar_mirrors_temperature_exactly():
    """The new-physics validation case (exact): a passive scalar at matched
    diffusivity with the temperature's BC lift, released equal to the
    temperature, stays identically equal — same advection-diffusion
    operator, same boundary forcing, machine-precision agreement."""
    m = _dns(scenario=ScenarioConfig(passive_scalar=True))
    m.set_field("scal", m.get_field("temp"))
    m.update_n(50)
    t = m.get_field("temp")
    c = m.get_field("scal")
    np.testing.assert_allclose(c, t, atol=1e-13)
    # and the scalar leaf rides snapshots (gathered layout)
    assert ("scal", "scal") in m.snapshot_vars
    # the Sherwood observable (scalar-transfer analog of the plate-flux
    # Nu) joins the vocabulary AFTER the conventional four — |div| stays
    # the index-3 NaN detector — and the mirror identity transfers:
    # matched diffusivity + equal release => sherwood == nu to fp noise
    assert m.observable_names == ("nu", "nuvol", "re", "div", "sherwood")
    obs = m.get_observables()
    assert len(obs) == 5
    assert obs[4] == pytest.approx(obs[0], rel=1e-11)


def test_passive_scalar_with_distinct_kappa_diverges_from_temp():
    """At a different scalar diffusivity the mirror breaks — the scalar is
    genuinely its own field, not an aliased temperature."""
    ka = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False).params["ka"]
    m = _dns(scenario=ScenarioConfig(passive_scalar=True, scalar_kappa=3.0 * ka))
    m.set_field("scal", m.get_field("temp"))
    m.update_n(30)
    diff = np.abs(m.get_field("scal") - m.get_field("temp")).max()
    assert np.isfinite(diff) and diff > 1e-6


def test_coriolis_absorbed_by_pressure():
    """The rotating-frame validation case: in incompressible 2-D flow the
    f-plane Coriolis force is irrotational (curl = -f div u = 0), so the
    velocity/temperature trajectory matches the non-rotating run (to the
    scheme's splitting error) while the PRESSURE absorbs the geostrophic
    correction — a large, O(1) relative change.  Measured at f=2, 50 steps:
    vel/temp drift ~1e-5, pressure drift ~0.6."""
    base = _dns()
    rot = _dns(scenario=ScenarioConfig(coriolis=2.0))
    base.update_n(50)
    rot.update_n(50)

    def rel(name):
        a, b = base.get_field(name), rot.get_field(name)
        return np.abs(a - b).max() / max(np.abs(a).max(), 1e-300)

    for name in ("velx", "vely", "temp"):
        assert rel(name) < 1e-3, name
    assert rel("pres") > 1e-2  # the force went SOMEWHERE: into the pressure
    # f=0 compiles the unmodified program: bit-equal to no scenario at all
    zero = _dns(scenario=ScenarioConfig(coriolis=0.0))
    zero.update_n(50)
    for name in ("temp", "velx", "vely", "pres", "pseu"):
        np.testing.assert_array_equal(
            np.asarray(getattr(zero.state, name)),
            np.asarray(getattr(base.state, name)),
        )


def test_geometry_sweep_matches_solo_set_solid():
    """The vmapped solid-mask geometry sweep: K obstacle geometries stepped
    as one donated vmapped scan each match a solo ``set_solid`` run — the
    penalize-after-step factoring is an identity, not an approximation."""
    from rustpde_mpi_tpu.models.solid_masks import solid_cylinder_inner

    template = _dns()
    xs, ys = (b.points for b in template.field_space.bases)
    geoms = [
        solid_cylinder_inner(xs, ys, 0.0, 0.0, 0.3),
        solid_cylinder_inner(xs, ys, 0.4, -0.2, 0.2),
    ]
    steps = 5
    final, obs = geometry_sweep(template, geoms, steps)
    assert obs[0].shape == (2,)
    for i, (mask, value) in enumerate(geoms):
        solo = _dns()
        solo.set_solid(mask, value)
        solo.update_n(steps)
        for name in ("temp", "velx", "vely", "pres", "pseu"):
            np.testing.assert_allclose(
                np.asarray(getattr(final, name)[i]),
                np.asarray(getattr(solo.state, name)),
                rtol=1e-9, atol=1e-13,
            )
    with pytest.raises(ValueError, match="plain template"):
        solo = _dns()
        solo.set_solid(geoms[0][0])
        geometry_sweep(solo, geoms, 1)


# -- solo-vs-ensemble equivalence of the ported models ------------------------


def test_lnse_ensemble_matches_solo_and_survives_restore(tmp_path):
    """lnse as a campaign model: a K=2 vmapped ensemble's member states and
    energy observables match solo runs to the Navier-ensemble tolerance —
    INCLUDING across a drain (checkpoint) / restore cycle through the
    sharded writer under ResilientRunner."""
    mean = MeanFields.new_rbc(17, 17)

    def solo_state(seed, steps):
        solo = Navier2DLnse.new_confined(
            17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", mean=mean
        )
        solo.init_random(1e-3, seed=seed)
        solo.update_n(steps)
        return solo

    def members(model):
        out = []
        for seed in (0, 1):
            model.init_random(1e-3, seed=seed)
            out.append(model.state)
        return out

    run_dir = str(tmp_path / "lnse_campaign")
    model = Navier2DLnse.new_confined(
        17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", mean=mean
    )
    ens = NavierEnsemble(model, members(model))
    io = IOConfig(sharded_checkpoints=True, overlap_dispatch=False)
    runner = ResilientRunner(
        ens, max_time=float("inf"), run_dir=run_dir,
        checkpoint_every_s=None, io=io,
    )
    with runner.session(install_signals=False, resume=False):
        runner.advance(10)
        assert runner.checkpoint_now("drain")  # the drain half

    # a NEW incarnation restores mid-trajectory and continues
    model2 = Navier2DLnse.new_confined(
        17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", mean=mean
    )
    ens2 = NavierEnsemble(model2, members(model2))
    runner2 = ResilientRunner(
        ens2, max_time=float("inf"), run_dir=run_dir,
        checkpoint_every_s=None, io=io,
    )
    with runner2.session(install_signals=False):
        assert runner2.resumed and runner2.step == 10
        runner2.advance(10)
    for i, seed in enumerate((0, 1)):
        solo = solo_state(seed, 20)
        for got, want in zip(ens2.member_state(i), solo.state):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-9, atol=1e-12
            )
        energy = float(np.asarray(ens2.get_observables()[0])[i])
        assert energy == pytest.approx(solo.get_observables()[0], rel=1e-9)


def test_adjoint_ensemble_matches_solo_residual_trajectory():
    """The steady-adjoint as a campaign model: a vmapped K=2 ensemble's
    per-member residual trajectories match solo finds to the pinned
    ensemble tolerance at every sampled chunk boundary."""

    def build(i):
        m = Navier2DAdjoint.new_confined(17, 17, 5e3, 1.0, 5e-3, 1.0, "rbc")
        m.set_temperature(0.3 + 0.2 * i, 1.0, 1.0)
        m.set_velocity(0.3 + 0.2 * i, 1.0, 1.0)
        return m

    model = build(0)
    states = [build(i).state for i in range(2)]
    ens = NavierEnsemble(model, states)
    solos = [build(i) for i in range(2)]
    for _ in range(3):
        ens.update_n(30)
        res_ens = np.asarray(ens.get_observables()[0])
        for i, solo in enumerate(solos):
            solo.update_n(30)
            assert res_ens[i] == pytest.approx(solo.residual(), rel=1e-9)
    for i, solo in enumerate(solos):
        for got, want in zip(ens.member_state(i), solo.state):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-9, atol=1e-12
            )


def test_adjoint_convergence_freezes_scan():
    """The residual-based exit sentinel: a converged member freezes INSIDE
    the scanned chunk (steps_done stalls, done_ok reports success, the
    batch exit fires) instead of burning GEMMs past convergence."""
    model = Navier2DAdjoint(
        17, 17, 100.0, 1.0, 1e-3, 1.0, "rbc", periodic=False, res_tol=1e-5
    )
    ens = NavierEnsemble(model, [model.state])
    ens.update_n(800)  # converges well before 800 at Ra=100 from rest
    done = int(np.asarray(ens.steps_done)[0])
    assert done < 800  # froze mid-chunk at convergence
    assert ens.done_ok_members()[0]
    assert not ens.alive()[0]  # stopped advancing...
    assert ens.state_healthy()  # ...but the state is an ANSWER, not a corpse
    assert ens.exit()  # the campaign's exit sentinel fired
    res = float(np.asarray(ens.get_observables()[0])[0])
    assert res < 1e-5


def test_workloads_parity_probe():
    """Per-kind solo-vs-ensemble drift is at numerical noise for every
    registered model."""
    deltas = solo_ensemble_parity(steps=5)
    assert set(deltas) == {"dns", "lnse", "adjoint"}
    for kind, row in deltas.items():
        assert row["max_rel_diff"] < 1e-9, (kind, row)


# -- the eigenmode-sweep workload ---------------------------------------------


def test_eigenmode_growth_rate_signs(tmp_path):
    """Tier-1 sibling of the Ra_c gate: far below onset the leading growth
    rate is negative, far above it positive (periodic-x rigid-rigid layer
    at the critical wavelength)."""
    res = eigenmode_sweep(
        [800.0, 4000.0], nx=8, ny=17, dt=0.05, horizon=16.0, samples=8,
        run_dir=str(tmp_path / "eig"),
    )
    assert res[0]["sigma_max"] < 0.0 < res[1]["sigma_max"]
    # a completed Ra campaign sweeps its spent checkpoints: a RERUN over
    # the same directory measures fresh instead of "resuming" complete
    # with zero samples (which would report NaN rates)
    res2 = eigenmode_sweep(
        [800.0], nx=8, ny=17, dt=0.05, horizon=16.0, samples=8,
        run_dir=str(tmp_path / "eig"),
    )
    assert not res2[0]["resumed"]
    assert np.isfinite(res2[0]["sigma_max"]) and res2[0]["sigma_max"] < 0.0
    # growth_rates flags members whose energy went bad instead of lying
    bad = growth_rates([0.0, 1.0, 2.0], np.asarray([[1.0], [np.nan], [1.0]]))
    assert np.isnan(bad[0])


@pytest.mark.slow
def test_eigenmode_sweep_reproduces_critical_rayleigh(tmp_path):
    """The workload gate: the lnse eigenmode sweep's leading growth rate
    changes sign at the rigid-rigid critical Rayleigh number Ra_c = 1707.76
    (Chandrasekhar; periodic-x box at the critical wavelength) within
    discretization tolerance — measured 1727.8 (1.2%) at ny=17."""
    res = eigenmode_sweep(
        [1500.0, 1650.0, 1800.0, 1950.0],
        nx=8, ny=17, dt=0.05, horizon=40.0, samples=16,
        run_dir=str(tmp_path / "eig"),
    )
    sigmas = [r["sigma_max"] for r in res]
    assert all(np.isfinite(sigmas))
    assert sigmas == sorted(sigmas)  # growth rate increases with Ra
    rac = critical_rayleigh(res)
    assert rac == pytest.approx(1707.762, rel=0.05)


# -- the steady-find workload -------------------------------------------------


def test_steady_find_kill_resume_converges(tmp_path):
    """Tier-1 kill/resume gate: the steady finder is preempted mid-find by
    a kill fault (checkpoint-then-exit through the sharded writer) and the
    re-invocation RESUMES the same descent mid-trajectory and converges
    (modest tolerance here; the reference-threshold gate is the slow-tier
    sibling below)."""
    run_dir = str(tmp_path / "steady")
    common = dict(
        nx=17, ny=17, ra=100.0, dt=1e-3, res_tol=1e-5, k=1, amp=0.005,
        max_iters=2500, chunk=200, run_dir=run_dir, install_signals=True,
    )
    r1 = steady_state_find(**common, fault="kill@400")
    assert r1["preempted"] and r1["checkpoint"]
    assert r1["iterations"] >= 400
    assert not all(r1["converged"])

    r2 = steady_state_find(**common)
    assert r2["resumed"]  # continued the SAME descent, not a fresh start
    assert r2["iterations"] > r1["iterations"]
    assert all(r2["converged"]), r2
    assert all(res < 1e-5 for res in r2["residuals"])
    # Ra=100 << Ra_c: the steady state is conduction, Nu -> 1
    for nu in r2["nu"]:
        assert nu == pytest.approx(1.0, abs=1e-3)
    # the journal names both incarnations' lifecycles
    from rustpde_mpi_tpu.utils.journal import read_journal

    events = [e["event"] for e in read_journal(os.path.join(run_dir, "journal.jsonl"))]
    assert "checkpoint" in events and "resumed" in events


@pytest.mark.slow
def test_steady_find_reference_threshold_through_kill(tmp_path):
    """The full workload gate: a K=2 find (LSC-mode + random IC members)
    killed mid-descent resumes and converges EVERY member's residual below
    the reference threshold RES_TOL = 1e-7 (steady_adjoint.rs:60), landing
    on the conduction state (Nu = 1) at Ra = 100."""
    run_dir = str(tmp_path / "steady_ref")
    common = dict(
        nx=17, ny=17, ra=100.0, dt=1e-3, res_tol=1e-7, k=2, amp=0.005,
        max_iters=8000, chunk=250, run_dir=run_dir, install_signals=True,
    )
    r1 = steady_state_find(**common, fault="kill@500")
    assert r1["preempted"] and not all(r1["converged"])
    r2 = steady_state_find(**common)
    assert r2["resumed"] and all(r2["converged"]), r2
    assert all(res < 1e-7 for res in r2["residuals"])
    for nu in r2["nu"]:
        assert nu == pytest.approx(1.0, abs=1e-4)
