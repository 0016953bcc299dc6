"""Ensemble engine tests (models/ensemble.py).

The three contract points of the batched execution engine: a K-member
vmapped step is bit-for-tolerance equivalent to K sequential solo runs (one
physics code path), a diverging member freezes without corrupting the batch
(per-member fault isolation), and no dispatch donates or overwrites a
buffer the user retained through the public API.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rustpde_mpi_tpu import Navier2D, NavierEnsemble
from rustpde_mpi_tpu.config import StabilityConfig, StatsConfig
from rustpde_mpi_tpu.utils.jit import scan_buckets


def _model(nx=17, ny=17, ra=1e4, dt=5e-3, periodic=False):
    return Navier2D(nx, ny, ra, 1.0, dt, 1.0, "rbc", periodic=periodic)


def _solo(seed, steps, **kw):
    m = _model(**kw)
    m.init_random(0.1, seed=seed)
    m.update_n(steps)
    return m


@pytest.mark.slow
def test_ensemble_matches_sequential_solo_runs():
    K, steps = 3, 7
    ens = NavierEnsemble.from_seeds(_model(), seeds=range(K))
    ens.update_n(steps)
    assert np.asarray(ens.mask).all()
    assert (np.asarray(ens.steps_done) == steps).all()
    for i in range(K):
        solo = _solo(i, steps)
        for got, want in zip(ens.member_state(i), solo.state):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-9, atol=1e-12
            )
        # per-member fused observables match the solo model's
        nu, nuvol, re, div = (v[i] for v in ens.get_observables())
        assert nu == pytest.approx(solo.eval_nu(), rel=1e-9)
        assert re == pytest.approx(solo.eval_re(), rel=1e-9)


def test_ensemble_matches_solo_periodic():
    # the split re/im Fourier layout must batch identically
    ens = NavierEnsemble.from_seeds(_model(nx=16, periodic=True), seeds=[0, 1])
    ens.update_n(5)
    solo = _solo(1, 5, nx=16, periodic=True)
    for got, want in zip(ens.member_state(1), solo.state):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-9, atol=1e-12
        )


def test_per_member_nan_isolation():
    K, steps = 3, 5
    ens = NavierEnsemble.from_seeds(_model(), seeds=range(K))
    bad = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), ens.member_state(0))
    ens.set_member(0, bad)
    ens.update_n(steps)
    mask = np.asarray(ens.mask)
    done = np.asarray(ens.steps_done)
    # the poisoned member is dead from step 0 and frozen at its IC ...
    assert not mask[0] and done[0] == 0
    assert np.isnan(np.asarray(ens.member_state(0).temp)).all()
    # ... while the others advance and match their solo runs exactly
    assert mask[1:].all() and (done[1:] == steps).all()
    for i in (1, 2):
        solo = _solo(i, steps)
        for got, want in zip(ens.member_state(i), solo.state):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-9, atol=1e-12
            )
    # observables report per member: NaN for the dead one, finite for alive
    nu = ens.eval_nu()
    assert not np.isfinite(nu[0]) and np.isfinite(nu[1:]).all()
    # graceful degradation: the batch is not dead
    assert not ens.exit()


def test_all_members_dead_triggers_exit():
    ens = NavierEnsemble.from_seeds(_model(), seeds=[0])
    ens.set_member(
        0, jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), ens.member_state(0))
    )
    ens.update_n(3)
    assert ens.exit()
    assert (np.asarray(ens.steps_done) == 0).all()


def _sim(kind, branch="plain"):
    """A model or a two-member ensemble with the ``branch`` of ``update_n``
    armed: plain, the stats carry, or the sentinel carry."""
    model = _model(dt=0.01)
    model.init_random(0.1, seed=0)
    if branch == "stats":
        model.set_stats(StatsConfig(stride=2))
    if branch == "sentinel":
        model.set_stability(StabilityConfig())
    if kind == "model":
        return model
    return NavierEnsemble.from_seeds(model, seeds=range(2))


def _visible(sim):
    """Every device buffer of the carry that the public API hands out."""
    held = {"state": sim.state, "stats": sim.stats_state}
    if isinstance(sim, NavierEnsemble):
        held.update(mask=sim.mask, steps_done=sim.steps_done)
    return held


def _values(held):
    """Host values of ``held``, read from device-side copies: a host read of
    a CPU buffer pins it, and a pinned buffer cannot be donated, which would
    make the test pass whatever the program did."""
    return jax.device_get(jax.tree.map(jnp.copy, held))


def _assert_untouched(held, values):
    for leaf, value in zip(jax.tree.leaves(held), jax.tree.leaves(values)):
        assert not leaf.is_deleted()  # no use-after-donate
        np.testing.assert_array_equal(np.asarray(leaf), value)


@pytest.mark.parametrize("n", [8, 14])  # one bucket, and three (8 + 4 + 2)
@pytest.mark.parametrize("branch", ["plain", "stats", "sentinel"])
@pytest.mark.parametrize("kind", ["model", "ensemble"])
def test_donation_preserves_retained_references(kind, branch, n):
    """No chunk donates a buffer the caller retained through the public
    API, although nothing is copied for it any more (the name is from when
    the chunk donated a copy)."""
    assert len(scan_buckets(n)) == (1 if n == 8 else 3)
    sim = _sim(kind, branch)
    held = _visible(sim)
    values = _values(held)
    sim.update_n(n)
    _assert_untouched(held, values)
    assert sim.state is not held["state"]
    assert np.isfinite(np.asarray(sim.state.temp)).all()
    if kind == "ensemble":
        assert (np.asarray(sim.steps_done) == n).all()
    if branch != "sentinel":
        return
    # a CFL trip rolls the chunk back: what comes back is the very carry the
    # caller saw before the call, which no bucket may have written to
    if kind == "model":
        sim.state = sim.state._replace(
            velx=sim.state.velx * 200.0, vely=sim.state.vely * 200.0
        )
    else:
        bad = jax.tree.map(lambda x: x * 300.0, sim.member_state(1))
        sim.set_member(1, bad._replace(temp=sim.member_state(1).temp))
    held = _visible(sim)
    values = _values(held)
    assert sim.update_n(n).pre_divergence
    assert all(a is b for a, b in zip(jax.tree.leaves(_visible(sim)), jax.tree.leaves(held)))
    _assert_untouched(held, values)


@pytest.mark.parametrize("kind", ["model", "ensemble"])
def test_chunk_takes_the_visible_carry_and_splits_bit_identically(kind):
    """The chunk program run straight on the caller-visible carry: 4 steps
    in one bucket and 2 + 2 in two end in the same bits, leave the carry
    whole, and agree with four single ``update()`` steps to the suite's
    tolerance."""
    sim = _sim(kind)
    carry = sim.state if kind == "model" else (sim.state, sim.mask, sim.steps_done)

    def chunk(c, k):
        return sim._step_n(c, k)[0] if kind == "model" else sim._step_n(*c, k)

    whole = chunk(carry, 4)
    split = chunk(chunk(carry, 2), 2)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(carry))
    for a, b in zip(jax.tree.leaves(split), jax.tree.leaves(whole)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for _ in range(4):
        sim.update()
    for a, b in zip(jax.tree.leaves(whole if kind == "model" else whole[0]),
                    jax.tree.leaves(sim.state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-9, atol=1e-12)


def test_ensemble_snapshot_roundtrip(tmp_path):
    pytest.importorskip("h5py")
    ens = NavierEnsemble.from_seeds(_model(), seeds=range(2))
    ens.update_n(3)
    fn = str(tmp_path / "ens.h5")
    ens.write(fn)
    ens2 = NavierEnsemble.from_seeds(_model(), seeds=[5, 6])
    ens2.update_n(1)
    ens2.read(fn)
    assert ens2.k == ens.k
    assert ens2.time == pytest.approx(ens.time)
    assert (np.asarray(ens2.steps_done) == np.asarray(ens.steps_done)).all()
    for attr in ("temp", "velx", "vely", "pres"):
        np.testing.assert_allclose(
            np.asarray(getattr(ens2.state, attr)),
            np.asarray(getattr(ens.state, attr)),
            rtol=1e-10,
            atol=1e-13,
        )
    # restored ensemble steps on (mask/counters consistent)
    ens2.update_n(2)
    assert np.asarray(ens2.mask).all()
    assert (np.asarray(ens2.steps_done) == 5).all()


def test_from_config_builds_k_members():
    from rustpde_mpi_tpu.config import NavierConfig

    cfg = NavierConfig(nx=17, ny=17, ra=1e4, dt=5e-3, ensemble=3)
    ens = NavierEnsemble.from_config(cfg)
    assert ens.k == 3
    # distinct seeds -> distinct members
    a = np.asarray(ens.state.temp[0])
    b = np.asarray(ens.state.temp[1])
    assert not np.allclose(a, b)
