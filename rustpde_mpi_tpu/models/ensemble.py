"""Batched ensemble execution engine: K independent RBC simulations per dispatch.

The inference-stack analogue of request batching, applied to DNS: at the
small/medium grids that dominate parameter sweeps and optimal-perturbation
campaigns a single 129² step leaves most of the chip idle (0.046 ms of
device work per step when last recorded, 2026-07-31), so K independent
members are stacked on a leading axis and
advanced by ONE vmapped, jitted, chunked ``lax.scan`` dispatch.  Design
points:

* **one physics code path** — the member step is :class:`Navier2D`'s own
  hoisted jaxpr (``model._step_cc``) under ``jax.vmap``; the ensemble forks
  no physics, it only adds the batch axis.  Members therefore share the
  model's operator constants (grid, Ra, Pr, dt — the implicit solvers bake
  ``dt*nu`` into their factorizations), so a parameter *scan* maps to one
  ensemble per parameter value with K seed-decorrelated members inside
  (``examples/navier_rbc_ensemble.py``).
* **no donation, no copy** — the chunked step donates nothing: it takes
  the caller-visible states + mask + counters as they are and XLA writes its
  result to fresh buffers, so references retained to ``.state`` / ``.mask``
  stay valid across :meth:`update_n` with no copy made for them.  The
  footprint while a bucket runs is two stacked states (a few MB at the sizes
  served), and a third between the buckets of a non-power-of-two ``n``.
* **per-member fault isolation** — the single-run in-chunk NaN early-exit
  (a scalar is-finite carry flag, models/navier.py) generalizes to a
  per-member finite **mask**: a diverging member freezes at its last finite
  state (``jnp.where`` select — inside a vmapped batch a ``lax.cond`` lowers
  to a select anyway, so the frozen member costs its lanes but cannot
  corrupt or kill the batch), ``steps_done`` records how far each member
  got, and the whole-batch scalar early-exit still fires once EVERY member
  is dead.  Graceful degradation, reported per member.
* **batched observables / IO** — the fused ``(Nu, Nuvol, Re, |div|)``
  diagnostics vmap to shape ``(K,)``; snapshots write per-member groups
  (utils/checkpoint.write_ensemble_snapshot).

Composes with the pencil-sharding mesh: the member axis is a leading batch
dim, which the transform layer replicates across shards (bases.Space2), so
members are batched *within* each pencil shard.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import tracing as _tr
from ..utils.integrate import Integrate
from .campaign import DispatchSpans
from .navier import Navier2D, NavierState


class NavierEnsemble(Integrate):
    """K member states of one :class:`Navier2D`, stepped as one dispatch.

    ``states`` is either a sequence of K :class:`NavierState` pytrees or an
    already-stacked state (every leaf carrying a leading K axis).  Members
    share ``model``'s spaces, solvers and parameters; only the state differs.
    """

    # overlapped-IO hooks — see Navier2D: class-level defaults keep plain
    # ensembles fully synchronous
    io_pipeline = None
    io_overlap = False
    # journal hook — see CampaignModelBase.journal_writer
    journal_writer = None

    def __init__(self, model, states):
        with _tr.span("ensemble.build", layer="ensemble") as sp:
            self._build(model, states)
            sp.set(members=self.k)

    def _build(self, model, states) -> None:
        if hasattr(states, "_fields"):  # a state pytree, maybe pre-stacked
            if np.ndim(states.temp) != np.ndim(model.state.temp) + 1:
                raise TypeError(
                    "NavierEnsemble expects a sequence of member states or a "
                    "state pytree whose leaves carry a leading K axis; got "
                    "an unbatched state — wrap it in a list for K=1"
                )
            stacked = states
        else:
            members = list(states)
            if not members:
                raise ValueError("ensemble needs at least one member state")
            with model._scope():
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *members)
        self.model = model
        self.k = int(stacked.temp.shape[0])
        self.dt = model.dt
        self.time = 0.0
        self.write_intervall = model.write_intervall
        # per-member diagnostics history: each append is a length-K list
        self.diagnostics: dict[str, list] = {}
        self._obs_cache: tuple | None = None
        # stability sentinels (mirrors Navier2D; armed when the template
        # model's set_stability was called) + per-rung artifact cache
        self.last_chunk_status = None
        self._pre_div_latch = False
        self._dt_cache: dict[float, dict] = {}
        self.recompile_count = 0
        # AOT executables (aot_compile, mirrors the template model): static-n
        # batched-chunk executables built ahead of traffic; dispatch prefers
        # them, aot_reuse_count tallies dispatches they served
        self._aot_step_n: dict[int, object] = {}
        self.aot_reuse_count = 0
        # config-carried PRNG stream for respawn_dead donor perturbations
        # (reproducible recovery runs); None falls back to per-call seeds
        self.respawn_seed: int | None = None
        self._respawn_rng = None
        # in-scan stats (models/stats.py): per-member running sums with a
        # leading K axis, armed when the template model's engine is
        self.stats_state = None
        self._stats_tick = None
        self._compile_entry_points()
        with model._scope():
            self.state = stacked
            self.mask = self._finite_mask(stacked)
            self.steps_done = jnp.zeros((self.k,), jnp.int32)
            self._init_stats_state()

    # -- construction --------------------------------------------------------

    @classmethod
    def from_seeds(cls, model: Navier2D, seeds, amp: float = 0.1) -> "NavierEnsemble":
        """K members from the model's random-IC generator, one seed each —
        the DNS-statistics / parameter-scan workload (decorrelated initial
        conditions under shared operators).  The model's own state is
        restored afterwards."""
        keep = model.state
        members = []
        try:
            for seed in seeds:
                model.init_random(amp, seed=int(seed))
                members.append(model.state)
        finally:
            model.state = keep
        return cls(model, members)

    @classmethod
    def replicate(cls, model: Navier2D, k: int) -> "NavierEnsemble":
        """K copies of the model's current state (perturbation campaigns
        differentiate members afterwards via :meth:`set_member`)."""
        return cls(model, [model.state] * int(k))

    @classmethod
    def from_config(cls, cfg, mesh=None) -> "NavierEnsemble":
        """Build the template model from a
        :class:`~rustpde_mpi_tpu.config.NavierConfig` and seed
        ``cfg.ensemble`` members (seeds 0..K-1).  An unset/zero
        ``init_random_amp`` means what it means on the single-run path — no
        random IC — so the members replicate the model's current state
        (differentiate them afterwards via :meth:`set_member`)."""
        model = Navier2D.from_config(cfg, mesh=mesh)
        k = max(1, cfg.ensemble)
        if not cfg.init_random_amp:
            ens = cls.replicate(model, k)
        else:
            ens = cls.from_seeds(model, range(k), amp=cfg.init_random_amp)
        if cfg.resilience is not None:
            ens.respawn_seed = cfg.resilience.respawn_seed
        return ens

    # -- member access -------------------------------------------------------

    @property
    def ensemble_size(self) -> int:
        """Member count."""
        return self.k

    @property
    def nx(self) -> int:
        return self.model.nx

    @property
    def ny(self) -> int:
        return self.model.ny

    @property
    def compat_key(self) -> tuple:
        """The template model's operator-constant key
        (:attr:`Navier2D.compat_key`): members NECESSARILY share it — the
        batch is one vmapped jaxpr over shared constants — so a slot can be
        refilled mid-campaign (``set_member``) by any request with an equal
        key, without recompiling."""
        return self.model.compat_key

    def member_state(self, i: int) -> NavierState:
        """Member ``i``'s state as an unbatched :class:`NavierState`."""
        return jax.tree.map(lambda x: x[i], self.state)

    def fresh_member_state(self, seed: int, amp: float = 0.1) -> NavierState:
        """A new random-IC member state from the template model's generator
        (the slot-refill donor for a freshly admitted request): the model's
        own state is restored afterwards, and the returned state is ready
        for :meth:`set_member` — same shapes/dtypes by construction."""
        keep = self.model.state
        try:
            self.model.init_random(float(amp), seed=int(seed))
            return self.model.state
        finally:
            self.model.state = keep

    def set_member(self, i: int, state: NavierState) -> None:
        """Replace member ``i``'s state (and re-derive its mask/counter).
        With the stats engine armed the member's running sums reset too —
        a refilled lane is a NEW trajectory (the serve scheduler's
        per-request averaging window starts at claim time)."""
        with self.model._scope():
            self.state = jax.tree.map(
                lambda st, leaf: st.at[i].set(leaf), self.state, state
            )
            self.mask = self.mask.at[i].set(self.model._scan_ok(state))
            self.steps_done = self.steps_done.at[i].set(0)
            if self.stats_state is not None:
                zero = self.model.stats_engine.init_state()
                self.stats_state = jax.tree.map(
                    lambda full, z: full.at[i].set(z), self.stats_state, zero
                )
        self._obs_cache = None

    def get_field(self, name: str, member: int) -> np.ndarray:
        """Physical values of one member's variable."""
        space = getattr(self.model, f"{name}_space")
        with _tr.span("model.get_field", layer="model step", fields=(name,)), self.model._scope():
            return np.asarray(space.backward(getattr(self.member_state(member), name)))

    # -- the batched step ----------------------------------------------------

    def _finite_mask(self, stacked):
        """Per-member continue criterion — the template model's ``_scan_ok``
        vmapped over the member axis.  For the DNS that is the one-reduction
        is-finite detector (a NaN anywhere infects temp within one step via
        buoyancy/convection); the steady-state adjoint additionally drops a
        member on residual CONVERGENCE, so a frozen member there may be a
        finished one, not a corpse (``done_ok_members`` tells them apart)."""
        return jax.vmap(self.model._scan_ok)(stacked)

    def done_ok_members(self) -> np.ndarray:
        """Per-member successfully-finished mask (host bools): members that
        stopped advancing via the model's *success* criterion (e.g. the
        adjoint finder's residual convergence) rather than by divergence."""
        with self.model._scope():
            done = jax.vmap(self.model._scan_done_ok)(self.state)
        return np.asarray(done)

    def state_healthy(self) -> bool:
        """Checkpoint guard (utils/resilience._state_ok): an ensemble is
        worth checkpointing while any member is still advancing OR any
        member finished successfully — but an all-dead batch must never
        overwrite the rollback target."""
        if self._pre_div_latch:
            return False
        if bool(np.any(self.alive())):
            return True
        return bool(self.done_ok_members().any())

    @property
    def observable_names(self) -> tuple:
        """The template model's observable vocabulary (shape (K,) each)."""
        return self.model.observable_names

    def _compile_entry_points(self) -> None:
        # same attribution seam as the base model's entry-point compile
        # (models/campaign.py), as the span ``ensemble.compile_entry_points``:
        # the K-member vmap trace is the serving path's dominant build cost
        # and is re-entered by set_dt/set_stability without a model rebuild —
        # it must not vanish from the per-kind compile metrics
        from ..telemetry import compile_log as _compile_log

        seam = _tr.timed("ensemble.compile_entry_points", layer="model step")
        try:
            with seam:
                self._compile_entry_points_impl()
                seam.set(**{"pass": self.recompile_count})
        finally:
            _compile_log.observe_entry_compile(
                f"ensemble:{getattr(self.model, 'MODEL_KIND', 'model')}", seam.seconds
            )

    def _compile_entry_points_impl(self) -> None:
        model = self.model
        step_cc = model._step_cc
        obs_cc = model._obs_cc
        self.recompile_count += 1
        self._step_n_jit = None
        self._aot_step_n = {}
        self._step_n_sent = None
        self._step_n_stats = None
        self._stats_health_fn = None
        self._dig_fn = None

        if model._dig_cc is not None:
            # the per-member digest is a pure elementwise+reduction read of
            # the stacked states — safe on every layout, including the
            # eager fallback below (the template model compiles its own
            # digest before ITS fallback return for the same reason)
            self._compile_integrity_entry_points()

        if model._gspmd_split_sep_fallback():
            # same poisoned layout the single-run guard reroutes (fused
            # split-sep periodic step miscompiled by GSPMD under a mesh): a
            # jitted vmap of step_cc would compile the SAME fused program,
            # and an eager vmap trips with_sharding_constraint on batch
            # tracers — so members step per-member through the eager path
            # proven correct for the single run.  Slow but right; the
            # per-member freeze semantics (keep the last FINITE state, stop
            # counting) are preserved.
            step_fn = model._make_step()
            obs_fn = model._make_observables()

            def ens_step_n_eager(states, mask, done, n):
                alive = np.asarray(mask).copy()
                counts = np.asarray(done).copy()
                members = [
                    jax.tree.map(lambda x, i=i: x[i], states) for i in range(self.k)
                ]
                for i in range(self.k):
                    if not alive[i]:
                        continue
                    st = members[i]
                    for _ in range(int(n)):
                        cand = step_fn(st)
                        if bool(self.model._scan_commit_ok(cand)):
                            st = cand
                            counts[i] += 1
                        if not bool(self.model._scan_ok(cand)):
                            alive[i] = False
                            break
                    members[i] = st
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *members)
                return (
                    stacked,
                    jnp.asarray(alive),
                    jnp.asarray(counts, dtype=jnp.int32),
                )

            def obs_eager(states):
                outs = [
                    obs_fn(jax.tree.map(lambda x, i=i: x[i], states))
                    for i in range(self.k)
                ]
                return tuple(jnp.stack(vals) for vals in zip(*outs))

            self._step_n = ens_step_n_eager
            self._obs_fn = obs_eager
            return

        def ens_step_n(consts, states, mask, done, n: int):
            """n vmapped steps with per-member fault isolation: the carry
            holds (states, alive-mask, per-member step counters).  An alive
            member whose stepped temp goes non-finite is frozen at its last
            finite state via a per-member select; once NO member is alive the
            remaining iterations take the identity branch of the scalar
            ``lax.cond`` (the single-run early-exit, batch-wide)."""

            vstep = jax.vmap(lambda s: step_cc(consts, s))
            vcommit = jax.vmap(self.model._scan_commit_ok)

            def advance(carry):
                st, ok, dn = carry
                st2 = vstep(st)
                # commit any candidate the model deems valid (finite; for
                # the DNS identical to the continue mask), CONTINUE only
                # while _scan_ok holds — the adjoint finder's converged
                # state commits on its final step before the freeze
                commit = ok & vcommit(st2)
                ok2 = ok & self._finite_mask(st2)

                def freeze(new, old):
                    sel = jnp.reshape(commit, commit.shape + (1,) * (new.ndim - 1))
                    return jnp.where(sel, new, old)

                return (
                    jax.tree.map(freeze, st2, st),
                    ok2,
                    dn + commit.astype(jnp.int32),
                )

            def body(carry, _):
                carry2 = jax.lax.cond(jnp.any(carry[1]), advance, lambda c: c, carry)
                return carry2, None

            (st, mk, dn), _ = jax.lax.scan(body, (states, mask, done), None, length=n)
            return model._hand_back(st), mk, dn

        # nothing is donated (see module docstring): the chunk takes the
        # caller-visible carry as it is and writes fresh buffers
        ens_jit = jax.jit(ens_step_n, static_argnames=("n",))
        # retained for aot_compile: the warm pool lowers+compiles the
        # batched chunk for the scheduler's static dispatch sizes ahead of
        # traffic; dispatch prefers a prebuilt executable when one exists
        self._step_n_jit = ens_jit

        def dispatch_step_n(st, mk, dn, n):
            exe = self._aot_step_n.get(int(n))
            if exe is not None:
                self.aot_reuse_count += 1
                return exe(model._step_consts, st, mk, dn)
            return ens_jit(model._step_consts, st, mk, dn, n=n)

        self._step_n = dispatch_step_n

        # fused (Nu, Nuvol, Re, |div|) vmapped to shape (K,)
        obs_jit = jax.jit(jax.vmap(obs_cc, in_axes=(None, 0)))
        self._obs_fn = lambda st: obs_jit(model._obs_consts, st)

        if model._stats_cc is not None:
            self._compile_stats_entry_points()

        if model._sent_cc is not None:
            self._compile_sentinel_entry_points()

    def _compile_stats_entry_points(self) -> None:
        """Vmapped stats-carrying chunk (template model's ``set_stats``):
        the per-member running sums ride the carry with a leading K axis, a
        SHARED scalar sample tick drives the stride cond (one real branch,
        not a per-member select), and accumulation commits per member only
        where the step itself commits — a frozen member's averages freeze
        with it.  Pure consumers of the stepped states: the member
        trajectories stay bit-identical to the stats-off chunk."""
        model = self.model
        step_cc = model._step_cc
        stats_cc = model._stats_cc
        stride = int(model.stats_engine.stride)

        def ens_step_n_stats(consts, sconsts, states, ss, tick, mask, done, n: int):
            vstep = jax.vmap(lambda s: step_cc(consts, s))
            vcommit = jax.vmap(model._scan_commit_ok)
            vaccum = jax.vmap(lambda s, st: stats_cc(sconsts, s, st))

            def advance(carry):
                st, ss, tk, ok, dn = carry
                st2 = vstep(st)
                commit = ok & vcommit(st2)
                ok2 = ok & self._finite_mask(st2)
                tk2 = tk + 1

                def do_accum(ss):
                    ss_new = vaccum(ss, st2)

                    def sel(new, old):
                        m = jnp.reshape(
                            commit, commit.shape + (1,) * (new.ndim - 1)
                        )
                        return jnp.where(m, new, old)

                    return jax.tree.map(sel, ss_new, ss)

                ss2 = jax.lax.cond(
                    (tk2[0] % stride) == 0, do_accum, lambda s: s, ss
                )

                def freeze(new, old):
                    m = jnp.reshape(commit, commit.shape + (1,) * (new.ndim - 1))
                    return jnp.where(m, new, old)

                return (
                    jax.tree.map(freeze, st2, st),
                    ss2,
                    tk2,
                    ok2,
                    dn + commit.astype(jnp.int32),
                )

            def body(carry, _):
                carry2 = jax.lax.cond(
                    jnp.any(carry[3]), advance, lambda c: c, carry
                )
                return carry2, None

            (st, ss, tk, mk, dn), _ = jax.lax.scan(
                body, (states, ss, tick, mask, done), None, length=n
            )
            return model._hand_back(st), ss, tk, mk, dn

        stats_jit = jax.jit(ens_step_n_stats, static_argnames=("n",))
        self._step_n_stats = lambda st, ss, tk, mk, dn, n: stats_jit(
            model._step_consts, model._stats_consts, st, ss, tk, mk, dn, n=n
        )

        h_jit = jax.jit(jax.vmap(model._stats_health_cc, in_axes=(None, 0)))
        self._stats_health_fn = lambda ss: h_jit(model._stats_health_consts, ss)

    def _compile_sentinel_entry_points(self) -> None:
        """Vmapped sentinel chunk (stability governor, utils/governor.py):
        the per-member carry holds finite AND CFL-ok masks plus running
        per-member sentinel reductions.  A member whose per-step CFL exceeds
        the ceiling freezes at its last under-ceiling state (it does NOT
        take the tripping step) while staying finite — distinct from death —
        and the batch-wide scalar early-exit fires once no member is both
        finite and under the ceiling.  Per-member CFL reduces to the batch
        max host-side (members share the baked dt)."""
        model = self.model
        sent_cc = model._sent_cc
        ceiling = float(model._stability.max_cfl)
        # stats engine armed: running sums + shared tick appended to the
        # carry (after the sentinel slots — fetch indices stay put); a
        # member samples only where its step commits under the ceiling
        stats_cc = model._stats_cc
        stats_stride = (
            int(model.stats_engine.stride) if stats_cc is not None else 0
        )

        def ens_step_n_sent(consts, sconsts, carry, n: int):
            vstep = jax.vmap(lambda s: sent_cc(consts, s))
            vcommit = jax.vmap(model._scan_commit_ok)
            vaccum = (
                jax.vmap(lambda s, st: stats_cc(sconsts, s, st))
                if stats_cc is not None
                else None
            )

            def advance(carry):
                st, fin, cok, dn, cflm, gm, dvm, kep = carry[:8]
                st2, (cfl, ke, dv) = vstep(st)
                active = fin & cok
                fin2 = jnp.where(active, self._finite_mask(st2), fin)
                cok2 = jnp.where(active, jnp.logical_not(cfl > ceiling), cok)
                # commit-vs-continue split, as in the plain chunk: a
                # convergence-stopped member's final state still commits
                keep = active & vcommit(st2) & cok2

                def freeze(new, old):
                    sel = jnp.reshape(keep, keep.shape + (1,) * (new.ndim - 1))
                    return jnp.where(sel, new, old)

                def upd(old, new):
                    return jnp.where(active, jnp.maximum(old, new), old)

                growth = jnp.where(kep > 0.0, ke / kep, 1.0)
                out = (
                    jax.tree.map(freeze, st2, st),
                    fin2,
                    cok2,
                    dn + keep.astype(jnp.int32),
                    upd(cflm, cfl),
                    upd(gm, growth),
                    upd(dvm, dv),
                    jnp.where(active, ke, kep),
                )
                if vaccum is not None:
                    ss, tk = carry[8], carry[9]
                    tk2 = tk + 1

                    def do_accum(ss):
                        ss_new = vaccum(ss, st2)

                        def sel(new, old):
                            m = jnp.reshape(
                                keep, keep.shape + (1,) * (new.ndim - 1)
                            )
                            return jnp.where(m, new, old)

                        return jax.tree.map(sel, ss_new, ss)

                    ss2 = jax.lax.cond(
                        (tk2[0] % stats_stride) == 0, do_accum, lambda s: s, ss
                    )
                    out = out + (ss2, tk2)
                return out

            def body(carry, _):
                carry2 = jax.lax.cond(
                    jnp.any(carry[1] & carry[2]), advance, lambda c: c, carry
                )
                return carry2, None

            final, _ = jax.lax.scan(body, carry, None, length=n)
            return (model._hand_back(final[0]),) + final[1:]

        sent_jit = jax.jit(ens_step_n_sent, static_argnames=("n",))
        self._step_n_sent = lambda c, n: sent_jit(
            model._sent_consts, model._stats_consts, c, n=n
        )

    def _compile_integrity_entry_points(self) -> None:
        """Vmapped on-device state digest (integrity/digest.py): the
        template model's retained digest jaxpr re-vmapped over the member
        axis — ONE fused dispatch returns a ``(K,)`` uint32 vector, one
        digest per member, localizing a corrupted member exactly like the
        observables localize NaNs.  The digest's positional mix uses
        LOGICAL indices, so member ``i``'s entry equals the digest the
        same state would produce solo (the layout-invariance the tests
        assert)."""
        model = self.model
        dig_jit = jax.jit(jax.vmap(model._dig_cc, in_axes=(None, 0)))
        self._dig_fn = lambda st: dig_jit(model._dig_consts, st)

    def aot_compile(self, chunk_steps: int) -> int:
        """AOT-build the batched-chunk executables a ``chunk_steps``-sized
        dispatch needs (every static scan bucket of ``run_scanned``'s
        decomposition) via ``.lower().compile()`` — the warm pool's
        cold-start killer: populates the persistent compile cache and
        retains the executables so the first live dispatch reuses them
        instead of entering jit.  Returns how many executables were newly
        built (0 on the eager-fallback path)."""
        from ..utils.jit import scan_buckets

        step_n_jit = getattr(self, "_step_n_jit", None)
        if step_n_jit is None:
            return 0
        built = 0
        with self.model._scope():
            for n in scan_buckets(chunk_steps):
                if n in self._aot_step_n:
                    continue
                self._aot_step_n[n] = step_n_jit.lower(
                    self.model._step_consts,
                    self.state,
                    self.mask,
                    self.steps_done,
                    n=n,
                ).compile()
                built += 1
        return built

    # -- Integrate protocol --------------------------------------------------

    def update(self) -> None:
        self.update_n(1)

    def update_n(self, n: int):
        """Advance every alive member n steps in scanned power-of-two chunks.

        The chunked dispatch donates nothing: it takes the user-visible
        (state, mask, counters) as they are and writes fresh buffers, so
        retained references stay valid with no copy made for them.
        ``self.time`` counts scheduled steps; ``self.steps_done`` records how
        far each member actually advanced.

        With stability sentinels armed (template model's ``set_stability``)
        the chunk returns a :class:`~rustpde_mpi_tpu.utils.governor.ChunkStatus`
        carrying per-member chunk-max CFL (``cfl_members``) and ceiling-trip
        masks (``pinned``); ANY alive member tripping the hard CFL ceiling
        rolls the whole chunk back in memory (members share the baked dt, so
        the dt response is batch-wide) and latches ``exit()`` until a
        governor acknowledges."""
        if self._step_n_sent is not None:
            return self._update_n_sentinel(n)
        with self._seams(n) as seams, self.model._scope():
            if self._step_n_stats is not None:
                with seams.handover():
                    carry = (
                        self.state,
                        self.stats_state,
                        self._stats_tick,
                        self.mask,
                        self.steps_done,
                    )
                (
                    self.state,
                    self.stats_state,
                    self._stats_tick,
                    self.mask,
                    self.steps_done,
                ) = seams.run(lambda c, k: self._step_n_stats(*c, k), carry, n)
            else:
                with seams.handover():
                    carry = (self.state, self.mask, self.steps_done)
                self.state, self.mask, self.steps_done = seams.run(
                    lambda c, k: self._step_n(*c, k), carry, n, aot=self._aot_step_n
                )
        self.time += n * self.dt
        self._obs_cache = None
        return None

    def _seams(self, n: int) -> DispatchSpans:
        """The spans ``ensemble.update_n`` / ``.carry_copy`` / ``.launch``
        of one chunk (models/campaign.DispatchSpans); the product and
        reverse counts are the member step's, the template model's own."""
        return DispatchSpans(
            "ensemble", "ensemble", steps=int(n), members=self.k, **self.model._step_products
        )

    def _update_n_sentinel(self, n: int):
        """Sentinel-armed batched chunk (see :meth:`update_n`)."""
        return self.update_n_pending(n).resolve()

    def update_n_pending(self, n: int):
        """Batched sentinel chunk with a DEFERRED commit decision — the
        ensemble form of :meth:`Navier2D.update_n_pending` (the lag=1
        contract of the overlapped driver): state/mask/counters advance
        PROVISIONALLY at dispatch, and ``resolve()`` fetches the per-member
        sentinels in one transfer, rolling the whole chunk back (and
        latching ``exit()``) when any member pinned the CFL ceiling.  The
        previous chunk-start ``steps_done`` rides the same deferred fetch —
        the synchronous form used to pay a blocking pre-dispatch read for
        it."""
        from .. import config
        from ..utils.governor import ChunkStatus
        from ..utils.io_pipeline import PendingChunkStatus

        if self._step_n_sent is None:
            raise RuntimeError(
                "update_n_pending requires armed stability sentinels "
                "(set_stability)"
            )
        self._pre_div_latch = False
        rdt = config.real_dtype()
        stats_on = self.model._stats_cc is not None
        done_before = self.steps_done  # fetched with the sentinel scalars
        with self._seams(n) as seams, self.model._scope():
            with seams.handover(fresh=5):
                carry = (
                    self.state,
                    self.mask,
                    jnp.ones((self.k,), bool),
                    self.steps_done,
                    jnp.zeros((self.k,), rdt),  # per-member cfl max
                    jnp.zeros((self.k,), rdt),  # per-member ke growth max
                    jnp.zeros((self.k,), rdt),  # per-member |div| max
                    jnp.zeros((self.k,), rdt),  # per-member previous-step ke
                ) + ((self.stats_state, self._stats_tick) if stats_on else ())
            carry = seams.run(self._step_n_sent, carry, n)
        st, fin, cok, dn, cflm, gm, dvm, kep = carry[:8]
        snapshot = (
            self.state,
            self.mask,
            self.steps_done,
            self.time,
            self.stats_state,
            self._stats_tick,
        )
        self.state, self.mask, self.steps_done = st, fin, dn  # provisional
        if stats_on:
            self.stats_state, self._stats_tick = carry[8], carry[9]
        self.time += n * self.dt
        self._obs_cache = None
        dt = self.dt

        def finish(fetched):
            fin_h, cok_h, dn_h, cflm_h, gm_h, dvm_h, kep_h, before_h = (
                np.asarray(a) for a in fetched
            )
            pinned = fin_h & ~cok_h
            pre_div = bool(pinned.any())
            if pre_div:
                # in-memory rollback of the whole chunk: state/mask/counters
                # (and the stats sums) are the un-donated chunk-start
                # snapshots — put them back
                (
                    self.state,
                    self.mask,
                    self.steps_done,
                    self.time,
                    self.stats_state,
                    self._stats_tick,
                ) = snapshot
                self._pre_div_latch = True
                self._obs_cache = None
            delta = dn_h - before_h
            status = ChunkStatus(
                requested=int(n),
                steps_done=int(delta.max(initial=0)),
                finite=bool(fin_h.any()),
                cfl_ok=not pre_div,
                pre_divergence=pre_div,
                cfl_max=float(cflm_h.max(initial=0.0)),  # batch-max reduction
                ke=float(kep_h.max(initial=0.0)),
                ke_growth_max=float(gm_h.max(initial=0.0)),
                div_max=float(dvm_h.max(initial=0.0)),
                dt=dt,
                cfl_members=tuple(float(c) for c in cflm_h),
                pinned=tuple(bool(p) for p in pinned),
            )
            self.last_chunk_status = status
            return status

        return PendingChunkStatus(
            (fin, cok, dn, cflm, gm, dvm, kep, done_before), finish
        )

    @property
    def _stability(self):
        """The sentinel config lives on the shared template model."""
        return self.model._stability

    def set_stability(self, cfg) -> None:
        """Arm/disarm the stability sentinels on the shared template model
        and re-vmap the ensemble entry points on top."""
        self.model.set_stability(cfg)
        self._dt_cache.clear()
        self._compile_entry_points()
        self.last_chunk_status = None
        self._pre_div_latch = False

    def clear_pre_divergence(self) -> None:
        """Acknowledge a ``pre_divergence`` catch (governor handled it)."""
        self._pre_div_latch = False

    # -- in-scan physics statistics (models/stats.py) --------------------------

    def _init_stats_state(self) -> None:
        """Zeroed per-member running sums when the template model's engine
        is armed (callers hold the model scope)."""
        if self._step_n_stats is None:
            self.stats_state = None
            self._stats_tick = None
            return
        self.stats_state = self.model.stats_engine.init_state(k=self.k)
        self._stats_tick = jnp.zeros((1,), jnp.int32)

    def set_stats(self, cfg) -> None:
        """Arm/disarm the in-scan stats engine on the shared template model
        and re-vmap the ensemble entry points on top; per-member running
        sums zero-initialize (a fresh averaging window for every member)."""
        self.model.set_stats(cfg)
        self._dt_cache.clear()
        self._compile_entry_points()
        with self.model._scope():
            self._init_stats_state()

    def reset_stats(self) -> None:
        """Zero every member's running sums + the shared sample tick."""
        with self.model._scope():
            self._init_stats_state()

    @property
    def stats_engine(self):
        """The template model's engine (None when disarmed)."""
        return self.model.stats_engine

    @property
    def stats_armed(self) -> bool:
        return self._step_n_stats is not None and self.stats_state is not None

    def stats_health_async(self):
        """Vmapped :data:`~rustpde_mpi_tpu.models.stats.HEALTH_NAMES`
        readout — an observable future of (K,) arrays, one health vector
        per member (the serve scheduler summarizes a finished member's
        entry into its done record)."""
        from ..utils.io_pipeline import ObservableFuture

        if not self.stats_armed:
            raise RuntimeError("stats_health_async needs an armed stats engine")
        with self.model._scope():
            return ObservableFuture(
                self._stats_health_fn(self.stats_state),
                convert=lambda vals: tuple(np.asarray(v) for v in vals),
            )

    def stats_summary(self) -> dict | None:
        """Synchronous per-member health readout (None when disarmed):
        each name maps to a length-K list."""
        if not self.stats_armed:
            return None
        from .stats import HEALTH_NAMES

        vals = self.stats_health_async().result()
        return {
            name: [float(x) for x in np.asarray(v).reshape(-1)]
            for name, v in zip(HEALTH_NAMES, vals)
        }

    def stats_host_items(self) -> list:
        """Gathered-snapshot rows for the stacked stats leaves
        (:meth:`StatsEngine.host_items`); empty when disarmed."""
        if not self.stats_armed:
            return []
        return self.model.stats_engine.host_items(
            self.stats_state, self._stats_tick
        )

    def apply_restored_stats(self, data: dict | None) -> None:
        """Install stacked stats leaves from a gathered snapshot (leading
        axis = the file's member count, which the caller already installed
        as ``self.k``) via :meth:`StatsEngine.restore_state`;
        ``None``/missing leaves reset to zero."""
        if not self.stats_armed:
            return
        with self.model._scope():
            self.stats_state, self._stats_tick = (
                self.model.stats_engine.restore_state(data, k=self.k)
            )

    # -- end-to-end integrity (integrity/) ------------------------------------

    def set_integrity(self, cfg) -> None:
        """Arm/disarm the integrity layer on the shared template model and
        re-vmap the ensemble entry points on top (the per-member digest
        rides the same retained jaxpr, ``_compile_integrity_entry_points``)."""
        self.model.set_integrity(cfg)
        self._dt_cache.clear()
        self._compile_entry_points()

    @property
    def integrity_config(self):
        """The template model's integrity config (None when disarmed)."""
        return self.model.integrity_config

    @property
    def integrity_armed(self) -> bool:
        return (
            self.model.integrity_config is not None
            and getattr(self, "_dig_fn", None) is not None
        )

    def _digest_future(self, device_val):
        from ..utils.io_pipeline import ObservableFuture

        return ObservableFuture(
            device_val,
            convert=lambda v: np.asarray(v)  # lint-ok: RPD005 a (K,) uint32 vector
        )

    def state_digest_async(self):
        """Dispatch the vmapped digest of the CURRENT member states and
        return an observable future of a ``(K,)`` uint32 vector — one
        digest per member (a mismatch names the corrupted member)."""
        if not self.integrity_armed:
            raise RuntimeError(
                "state_digest_async needs an armed integrity layer "
                "(set_integrity)"
            )
        with self.model._scope():
            return self._digest_future(self._dig_fn(self.state))

    def digest_of_async(self, state):
        """Digest an arbitrary stacked state pytree (the runner's retained
        chunk-start copies) without touching ``self.state``."""
        with self.model._scope():
            return self._digest_future(self._dig_fn(state))

    def shadow_digest_async(self, snap: dict, n: int):
        """Shadow re-execution audit kernel (ensemble form): re-step ``n``
        steps from the retained :meth:`integrity_snapshot` through the
        PLAIN batched chunk — threading the snapshot's alive mask and step
        counters, so per-member freeze decisions replay exactly — and
        digest the resulting member states.  Bit-equal to the live chunk's
        digests by XLA determinism, unless the state was corrupted.  The
        snapshot is not consumed (the chunk donates nothing)."""
        from ..utils.jit import run_scanned

        if not self.integrity_armed:
            raise RuntimeError(
                "shadow_digest_async needs an armed integrity layer "
                "(set_integrity)"
            )
        with self.model._scope():
            carry = run_scanned(
                lambda c, k: self._step_n(*c, k),
                (snap["state"], snap["mask"], snap["steps_done"]),
                n,
            )
            return self._digest_future(self._dig_fn(carry[0]))

    def integrity_snapshot(self) -> dict:
        """Un-donated device-side copy of everything an in-memory
        integrity rollback must restore: member states + alive mask +
        per-member counters + time (+ armed stats sums)."""
        with self.model._scope():
            snap = {
                "state": jax.tree.map(jnp.copy, self.state),
                "mask": jnp.copy(self.mask),
                "steps_done": jnp.copy(self.steps_done),
                "time": self.time,
            }
            if self.stats_armed:
                snap["stats"] = (
                    jax.tree.map(jnp.copy, self.stats_state),
                    jnp.copy(self._stats_tick),
                )
        return snap

    def integrity_restore(self, snap: dict) -> None:
        """Roll back to a digest-verified :meth:`integrity_snapshot` (the
        snapshot stays reusable — the install copies)."""
        with self.model._scope():
            self.state = jax.tree.map(jnp.copy, snap["state"])
            self.mask = jnp.copy(snap["mask"])
            self.steps_done = jnp.copy(snap["steps_done"])
            self.time = snap["time"]
            if "stats" in snap and self.stats_armed:
                ss, tick = snap["stats"]
                self.stats_state = jax.tree.map(jnp.copy, ss)
                self._stats_tick = jnp.copy(tick)
        self._obs_cache = None
        self._pre_div_latch = False

    def _verify_restored_digest(self, expected) -> None:
        """Recompute the per-member digests after a bit-exact sharded
        restore and compare with the manifest's ``(K,)`` vector (see
        ``CampaignModelBase._verify_restored_digest``)."""
        if expected is None or not self.integrity_armed:
            return
        got = np.asarray(self.state_digest_async().result())
        exp = np.asarray(expected).astype(got.dtype).reshape(got.shape)
        if not np.array_equal(got, exp):
            from ..integrity import IntegrityError

            bad = [int(i) for i in np.flatnonzero(got != exp)]
            raise IntegrityError(
                f"restored member digests differ from the checkpoint "
                f"manifest for members {bad} — the snapshot was corrupted "
                "between device and disk",
                check="checkpoint",
                member=bad[0] if bad else None,
            )

    @property
    def pre_divergence_latched(self) -> bool:
        """True while an unacknowledged sentinel catch latches ``exit()`` —
        the public form the serve scheduler's per-bucket dt governor reads
        (``last_chunk_status.pinned`` names the tripping members)."""
        return bool(self._pre_div_latch)

    def mark_dead(self, members) -> None:
        """Declare members dead (persistently CFL-pinned, governor decision):
        they freeze like diverged members and become ``respawn_dead``
        candidates."""
        with self.model._scope():
            mask = self.mask
            for i in members:
                mask = mask.at[int(i)].set(False)
            self.mask = mask
        self._obs_cache = None

    def get_time(self) -> float:
        return self.time

    def get_dt(self) -> float:
        return self.dt

    # swapped per dt change, cached per rung like Navier2D._DT_ARTIFACTS
    _DT_ARTIFACTS = (
        "_step_n",
        "_obs_fn",
        "_step_n_sent",
        "_step_n_stats",
        "_stats_health_fn",
        "_dig_fn",
    )

    def set_dt(self, dt: float) -> None:
        """Propagate a dt change (the governor's ladder / divergence-retry
        backoff) through the shared template model — which rebuilds its
        dt-baked solvers and re-traces ``_step_cc``, both cached per dt rung
        — then re-vmap the ensemble entry points on top of the new jaxpr
        (also rung-cached: a revisited rung restores the retained closures,
        so the jit executable cache hits).  Member states are untouched."""
        dt = float(dt)
        if dt == self.dt:
            return
        self._dt_cache[self.dt] = {
            k: getattr(self, k, None) for k in self._DT_ARTIFACTS
        }
        self.model.set_dt(dt)
        self.dt = self.model.dt
        cached = self._dt_cache.get(dt)
        if cached is not None:
            for key, value in cached.items():
                setattr(self, key, value)
        else:
            self._compile_entry_points()
        self._obs_cache = None

    def reset_time(self) -> None:
        self.time = 0.0

    def respawn_dead(self, amp: float = 1e-3, seed=None) -> int:
        """Re-seed every dead member from a perturbed healthy donor instead
        of leaving it frozen forever (utils/resilience.py calls this at
        rollback when ``respawn_members`` is on).

        Each dead member receives a healthy member's state with a small
        multiplicative spectral perturbation (``coeff * (1 + amp*noise)``) —
        enough to decorrelate the respawned trajectory without restarting
        the transient from scratch.  Donors round-robin over the healthy
        members; surviving members' states are NOT touched (their buffers
        are updated per-index, ``set_member``).  Returns the number of
        members respawned (0 when all alive or none alive — with no healthy
        donor there is nothing to copy from).

        ``seed`` may be an int or a sequence of ints (a SeedSequence
        entropy key, e.g. ``(campaign_seed, step, attempt)``); when ``None``
        and a config-carried ``respawn_seed`` is set
        (``ResilienceConfig.respawn_seed``), draws come from that persistent
        stream — so two identical recovery runs respawn identically."""
        alive = self.alive()
        if alive.all() or not alive.any():
            return 0
        if seed is None and self.respawn_seed is not None:
            if self._respawn_rng is None:
                self._respawn_rng = np.random.default_rng(self.respawn_seed)
            rng = self._respawn_rng
        else:
            rng = np.random.default_rng(seed)
        donors = np.flatnonzero(alive)
        respawned = 0
        for i in np.flatnonzero(~alive):
            donor = int(donors[respawned % len(donors)])
            state = self.member_state(donor)
            with self.model._scope():
                perturbed = jax.tree.map(
                    lambda x: x
                    * (
                        1.0
                        + amp
                        * jnp.asarray(
                            rng.standard_normal(x.shape),
                            dtype=jnp.real(x).dtype,
                        )
                    ),
                    state,
                )
            self.set_member(int(i), perturbed)
            respawned += 1
        return respawned

    def alive(self) -> np.ndarray:
        """Per-member alive mask as a host bool array of shape (K,)."""
        return np.asarray(self.mask)

    def exit(self) -> bool:
        """Graceful degradation: the break criterion fires only when EVERY
        member has diverged — one NaN member freezes (update_n) and is
        reported per member, it does not kill the batch.  A latched
        ``pre_divergence`` catch (stability sentinels) also reads as a break
        until a governor clears it (see ``Navier2D.exit``)."""
        if self._pre_div_latch:
            return True
        return not bool(np.any(self.alive()))

    def exit_future(self):
        """Non-blocking :meth:`exit` for the overlapped driver: the
        all-members-dead reduction rides the device queue (the mask is
        maintained on device by the chunked step) and resolves when the
        driver fetches it — a latched sentinel catch resolves immediately."""
        import jax.numpy as jnp

        from ..utils.io_pipeline import ObservableFuture, immediate

        if self._pre_div_latch:
            return immediate(True)
        with self.model._scope():
            dead = jnp.logical_not(jnp.any(self.mask))
        return ObservableFuture(dead, convert=bool)

    # -- observables / IO ----------------------------------------------------

    def get_observables_async(self):
        """Dispatch the vmapped observables and return an
        :class:`~rustpde_mpi_tpu.utils.io_pipeline.ObservableFuture` (shape
        ``(K,)`` per entry) without waiting — cached per state and shared
        with the synchronous accessors, like the single-run form."""
        from ..utils.io_pipeline import ObservableFuture

        if self._obs_cache is None or self._obs_cache[0] is not self.state:
            with self.model._scope():
                fut = ObservableFuture(
                    self._obs_fn(self.state),
                    convert=lambda vals: tuple(np.asarray(v) for v in vals),
                )
            self._obs_cache = (self.state, fut)
        return self._obs_cache[1]

    def get_observables(self) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """(Nu, Nuvol, Re, |div|), each a float ndarray of shape (K,) — one
        fused vmapped dispatch, cached per state, fetched in ONE host
        transfer.  NOTE a member that diverged mid-run is frozen at its last
        FINITE state, so its entries are finite but STALE; only a member
        whose IC was already non-finite reports NaN.  Liveness is
        :meth:`alive` / ``mask``, not ``isfinite(nu)``."""
        return self.get_observables_async().result()

    def device_fence(self) -> None:
        """Block until every dispatched device computation whose output this
        ensemble still holds has completed: the vmapped state chunk, the
        stats sums, and the cached observables dispatch.  Same contract as
        the sharded campaign's fence — the serve scheduler runs it before
        host-level collectives while the ensemble occupies a proper
        sub-mesh (multihost.set_device_fence)."""
        if self.state is not None:
            jax.block_until_ready(self.state)
        stats = getattr(self, "stats_state", None)
        if stats is not None:
            jax.block_until_ready(stats)
        cache = self._obs_cache
        if cache is not None and not cache[1].ready():
            cache[1].result()

    def eval_nu(self) -> np.ndarray:
        return self.get_observables()[0]

    def eval_nuvol(self) -> np.ndarray:
        return self.get_observables()[1]

    def eval_re(self) -> np.ndarray:
        return self.get_observables()[2]

    def div_norm(self) -> np.ndarray:
        return self.get_observables()[3]

    def _emit_callback_line(self, t: float, vals, alive: np.ndarray) -> None:
        """Diagnostics append + aggregate print for one boundary (shared by
        the synchronous path and the io_pipeline's lagged emission)."""
        nu, nuvol, re, div = vals[:4]
        # extended vocabularies (the passive-scalar sherwood) append by name
        extra_names = tuple(self.observable_names)[4:]
        for key, val in (
            ("time", [t] * self.k),
            ("nu", nu),
            ("nuvol", nuvol),
            ("re", re),
            ("div", div),
            *zip(extra_names, vals[4:]),
            ("alive", alive.astype(float)),
        ):
            self.diagnostics.setdefault(key, []).append(list(map(float, val)))
        n_alive = int(alive.sum())
        if n_alive:
            live = np.asarray(nu)[alive]
            nu_info = f"Nu = {live.mean():5.3e} [{live.min():5.3e}, {live.max():5.3e}]"
        else:
            nu_info = "Nu = --- (all members diverged)"
        print(f"time = {t:9.3f}      alive = {n_alive}/{self.k}      {nu_info}")

    def callback(self) -> None:
        """Per-interval reporting: append per-member diagnostics, print an
        aggregate line, write the ensemble snapshot when ``write_intervall``
        says so (the single-run callback's throttling rule).

        With an attached ``io_pipeline`` the diagnostics ride observable
        futures (emitted at most one boundary late, FIFO) and the snapshot
        serialization runs on the background worker — the device queue is
        never fenced at the boundary (see utils/navier_io.callback)."""
        t = self.time
        pipeline = self.io_pipeline
        if pipeline is not None:
            from ..utils.io_pipeline import ObservableFuture

            obs_fut = self.get_observables_async()
            # the mask rides the same device queue as the observables: when
            # the obs future resolves, this fetch is already complete
            mask_fut = ObservableFuture(self.mask, convert=np.asarray)

            def emit(vals, t=t):
                self._emit_callback_line(t, vals, mask_fut.result().astype(bool))

            pipeline.push_diag(emit, obs_fut)
        else:
            self._emit_callback_line(t, self.get_observables(), self.alive())
        # single-run rule (utils/navier_io.callback): write every save
        # interval unless write_intervall throttles it further
        wi = self.write_intervall
        if wi is None or (t + self.dt / 2.0) % wi < self.dt:
            fname = f"data/ensemble{t:08.2f}.h5"
            if pipeline is not None:
                from ..utils import checkpoint

                snap = checkpoint.ensemble_snapshot_to_host(self)

                def write_snap(snap=snap, fname=fname):
                    try:
                        checkpoint.write_host_snapshot(snap, fname)
                    except OSError as exc:
                        print(f"unable to write ensemble snapshot: {exc}")

                pipeline.submit_write(write_snap, fname, nbytes=snap.nbytes)
            else:
                try:
                    self.write(fname)
                except OSError as exc:  # never fatal, like the single-run callback
                    print(f"unable to write ensemble snapshot: {exc}")

    @property
    def mesh(self):
        """The template model's pencil mesh (None = single device) — the
        sharded-checkpoint layer reads this to build target layouts."""
        return self.model.mesh

    # -- sharded (shard-wise) snapshot surface -------------------------------

    def snapshot_state_items(self) -> list:
        """``(name, device_array)`` per batched state leaf (leading K axis
        rides along as replicated batch under the pencil spec) — see
        ``Navier2D.snapshot_state_items``.  Armed stats leaves join the set
        so per-member running averages survive kill/resume bit-exactly."""
        items = [
            (f"state/{name}", getattr(self.state, name))
            for name in self.state._fields
        ]
        if self.stats_armed:
            items += [
                (f"stats/{name}", getattr(self.stats_state, name))
                for name in self.stats_state._fields
            ]
            items.append(("stats/tick", self._stats_tick))
        return items

    def _split_restored_stats(self, updates: dict) -> None:
        """Sharded-restore side of the stats leaves (mirrors
        ``CampaignModelBase._split_restored_stats``): present leaves
        install exactly, missing ones zero — then the caller installs the
        remaining state leaves."""
        if not self.stats_armed:
            return
        self.apply_restored_stats(
            self.model.stats_engine.split_restored(updates)
        )

    def snapshot_root_items(self) -> list:
        """Replicated manifest-root data: time, params AND the ensemble
        bookkeeping (member count, alive mask, per-member step counters)."""
        items = [("time", np.asarray(float(self.time), dtype=np.float64), "raw")]
        items.append(("members", np.asarray(int(self.k), dtype=np.int64), "raw"))
        items.append(("alive", np.asarray(self.mask).astype(np.int8), "raw"))
        items.append(
            ("steps_done", np.asarray(self.steps_done, dtype=np.int64), "raw")
        )
        for key, value in self.model.params.items():
            items.append((key, np.asarray(float(value), dtype=np.float64), "raw"))
        if self.integrity_armed:
            items.append((
                "integrity_digest",
                np.asarray(self.state_digest_async().result()),  # lint-ok: RPD005 (K,) uint32 manifest row
                "raw",
            ))
        return items

    def apply_restored_state(self, updates: dict, attrs: dict, root: dict) -> None:
        """Install the assembled batched leaves + bookkeeping.  The sharded
        format is exact (bit-equal restore), so the member count must match
        — the reader rejects K mismatches before assembly (K-elastic
        restarts go through the gathered per-member layout)."""
        self._split_restored_stats(updates)
        self.state = self.state._replace(**updates)
        self.mask = jnp.asarray(np.asarray(root["alive"], dtype=bool))
        self.steps_done = jnp.asarray(np.asarray(root["steps_done"]), jnp.int32)
        self.time = float(np.asarray(root["time"]))
        self._obs_cache = None
        self._pre_div_latch = False
        self._verify_restored_digest(root.get("integrity_digest"))

    def write(self, filename: str) -> None:
        """Write a K-member snapshot (per-member groups, utils/checkpoint)."""
        from ..utils import checkpoint

        checkpoint.write_ensemble_snapshot(self, filename)

    def read(self, filename: str) -> None:
        """Restore members (+ mask, counters, time) from an ensemble snapshot."""
        from ..utils import checkpoint

        checkpoint.read_ensemble_snapshot(self, filename)
