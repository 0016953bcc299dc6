"""The CampaignModel contract: what a physics model must provide to run
under everything PRs 1–6 built — vmapped ensembles, the stability governor,
elastic checkpoints, ``ResilientRunner`` and the ``SimServer`` scheduler.

PRs 1–6 grew this contract ad hoc on :class:`~.navier.Navier2D`; this module
makes it explicit so the rest of the reference's physics (``Navier2DLnse``,
``Navier2DAdjoint``, scenario-modified DNS) plugs into the same serving and
resilience stack.  The contract has two halves:

**The protocol** (:data:`CAMPAIGN_MODEL_ATTRS`, checked by
:func:`~rustpde_mpi_tpu.workloads.registry.validate_campaign_model`):

* a ``state`` pytree (NamedTuple of device arrays) threaded through a pure
  jitted step,
* hoisted entry points — ``_step_cc``/``_step_consts`` and
  ``_obs_cc``/``_obs_consts`` (the closure-converted step and observables
  jaxprs the ensemble engine re-vmaps; one physics code path, batch as a
  leading axis),
* ``update_n`` with the in-chunk early-exit, ``update_n_pending`` (the
  lag=1 deferred-commit sentinel chunk of the overlapped driver), and
  ``set_stability`` compiling on-device sentinels into the scanned chunk,
* ``set_dt`` with per-rung artifact caching (bounded re-jits under a
  governor ladder),
* ``compat_key`` — the operator-constant bucket key, now prefixed with the
  model kind so mixed-model campaigns bucket correctly,
* observable futures (``get_observables_async``) with per-model
  ``observable_names``,
* the sharded-snapshot surface (``snapshot_state_items`` /
  ``snapshot_root_items`` / ``apply_restored_state``) plus ``read``/``write``.

**The machinery** (:class:`CampaignModelBase`): everything in that list that
is generic over the step function is implemented HERE, once — the scanned
chunk with divergence early-exit, the sentinel-armed
variant, the deferred-commit pending chunk, the dt-rung cache, the cached
observable future, exit/exit_future.  A model supplies the physics hooks:

* ``_make_step(with_sentinels=False)`` — the pure step, with the optional
  sentinel triple: a rate the chunk holds under ``max_cfl``, an energy whose
  growth it tracks and a residual norm, for the DNS ``(cfl, ke, |div|)``; a
  model without one of them gives what stands in its slot (a scalar PDE with
  nothing to advect: ``(0, energy, |mean|)``, models/swift_hohenberg.py),
* ``_make_observables()`` — the fused per-state scalar diagnostics, four
  floats named by ``observable_names`` (more may follow), the NaN detector
  at index 3,
* ``_state_example()`` — ShapeDtypeStructs of one state,
* ``_scan_ok(state)`` — the in-scan continue criterion (default: temp is
  finite; the steady-state finder additionally stops on residual
  convergence — the residual-based exit sentinel),
* ``_rebuild_dt_artifacts()`` — rebuild whatever a dt change invalidates.

``Navier2D`` inherits this base (its PR 1–4 behavior is unchanged — the
code moved, the traced programs did not), and ``Navier2DLnse`` /
``Navier2DAdjoint`` ride the same machinery instead of hand-rolled loops.
So do the Swift–Hohenberg models (models/swift_hohenberg.py), the first that
are no variant of ``Navier2D``: one field, no velocity, no pressure, no mesh.
What the base takes from a DNS it takes through a hook whose default is the
DNS's (``_scan_ok``: the leaf ``temp``; ``_rest_layout``: ``temp_space``;
``_build_span``: a 2-D grid and a mesh); the stats engine alone refuses
another kind (models/stats.py).
"""

from __future__ import annotations

import numpy as np

from .. import config
from ..telemetry import tracing as _tr


class DispatchSpans:
    """The host seams of one ``update_n`` call, as spans of ``layer``
    (PERF.md section 3): ``<prefix>.update_n`` around the call, inside it
    ``<prefix>.carry_copy`` where the carry is assembled and handed over, and
    one ``<prefix>.launch`` per bucket that ``run_scanned`` dispatches.  The
    outer span's ``launches`` counts the device programs the call enqueued:
    the buckets.  ``prefix`` is ``model`` here and ``ensemble`` in
    models/ensemble.py.

    The chunk programs donate nothing, so the carry the caller can still see
    (``self.state`` and what rides with it) goes to the first bucket as it
    is, uncopied: XLA writes each bucket's result to fresh buffers."""

    def __init__(self, prefix: str, layer: str, **args):
        self.prefix, self.layer, self.launches = prefix, layer, 0
        self._span = _tr.span(prefix + ".update_n", layer=layer, **args)

    def __enter__(self):
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._span.set(launches=self.launches)
        return self._span.__exit__(exc_type, exc, tb)

    def handover(self, fresh: int = 0):
        """The ``carry_copy`` span, held while the carry is assembled.
        ``leaves`` is the number of the caller's leaves copied: 0.  ``fresh``
        is the number of arrays built eagerly on the device inside it (the
        sentinel branch's initial flags and maxima), which ``launches`` does
        not count."""
        return _tr.span(
            self.prefix + ".carry_copy", layer=self.layer, leaves=0, fresh=fresh
        )

    def run(self, step_n, carry, n: int, aot=()):
        """``run_scanned`` over ``step_n(carry, k)``, each bucket under a
        ``launch`` span; ``aot`` holds the bucket sizes a prebuilt executable
        serves."""
        from ..utils.jit import run_scanned

        def launch(c, k):
            self.launches += 1
            with _tr.span(
                self.prefix + ".launch", layer=self.layer, steps=int(k), aot=int(k) in aot
            ):
                return step_n(c, k)

        return run_scanned(launch, carry, n)


_LAYER = "model step"

#: the attribute surface the workloads registry validates a campaign model
#: against (see module docstring) — kept as data so the check and the docs
#: cannot drift apart
CAMPAIGN_MODEL_ATTRS = (
    "MODEL_KIND",
    "observable_names",
    "state",
    "compat_key",
    "update_n",
    "update_n_pending",
    "set_stability",
    "clear_pre_divergence",
    "set_stats",
    "stats_armed",
    "set_integrity",
    "integrity_armed",
    "state_digest_async",
    "set_dt",
    "get_dt",
    "get_time",
    "get_observables_async",
    "exit",
    "exit_future",
    "state_healthy",
    "init_random",
    "snapshot_state_items",
    "snapshot_root_items",
    "apply_restored_state",
    "read",
    "write",
    "_step_cc",
    "_step_consts",
    "_obs_cc",
    "_obs_consts",
    "_make_step",
    "_make_observables",
    "_scan_ok",
    "_scope",
)


class CampaignModelBase:
    """Generic campaign-model machinery (see module docstring).

    Subclasses must call :meth:`_init_campaign` early in ``__init__`` (before
    :meth:`_compile_entry_points`) and provide the physics hooks."""

    #: registry kind prefix of :attr:`compat_key` (per subclass)
    MODEL_KIND = "model"
    #: names of the four scalars ``_make_observables`` returns, in order;
    #: index 3 is by convention the NaN detector (a divergence norm)
    observable_names = ("obs0", "obs1", "obs2", "div")

    # overlapped-IO hooks (utils/io_pipeline.py): an attached IOPipeline
    # routes callback IO through the background writer / lag queue, and
    # io_overlap opts the chunked driver into lagged break checks
    # (utils/integrate.py).  Class-level defaults keep plain models fully
    # synchronous.
    io_pipeline = None
    io_overlap = False
    # journal hook (utils/journal.JournalWriter): the resilient runner
    # attaches its writer for the duration of a session so model-side
    # statistics failures surface as typed journal events
    # (models/stats.report_stats_event) instead of swallowed prints
    journal_writer = None
    #: first-axis syntheses of one step that two consumers are finished from
    #: (``Navier2D._make_step`` sets it: a velocity's plain synthesis and its
    #: own chain's derivative); a step that shares none says 0 on its spans
    _shared_syntheses = 0

    # -- construction-time bookkeeping ---------------------------------------

    def _init_campaign(self) -> None:
        self.time = 0.0
        self._obs_cache: tuple | None = None
        # stability sentinels (utils/governor.py): None = plain stepping
        self._stability = None
        self.last_chunk_status = None
        self._pre_div_latch = False
        # per-rung cache of dt-baked artifacts (solvers + compiled entry
        # points), so a governor cycling a bounded dt ladder re-jits each
        # rung at most once; recompile_count tracks actual rebuilds
        self._dt_cache: dict[float, dict] = {}
        self.recompile_count = 0
        # AOT executables (aot_compile): static-n chunk executables built
        # ahead of traffic via .lower().compile() — dispatch prefers them,
        # aot_reuse_count tallies dispatches served by a prebuilt executable
        self._aot_step_n: dict[int, object] = {}
        self.aot_reuse_count = 0
        # in-scan physics-stats engine (models/stats.py): None = off;
        # set_stats arms it — the running-sum pytree + its sample-cadence
        # tick then ride the scanned chunks, the snapshot surface and the
        # rollback snapshots exactly like the state itself
        self._stats_engine = None
        self.stats_state = None
        self._stats_tick = None
        # end-to-end integrity layer (integrity/): None = off; set_integrity
        # arms it — the on-device digest entry point is compiled next to the
        # step/observables jaxprs and streamed as futures by the runner
        self._integrity_cfg = None

    # -- physics hooks (per subclass) ----------------------------------------

    def _make_step(self, with_sentinels: bool = False):
        raise NotImplementedError

    def _make_observables(self):
        raise NotImplementedError

    def _state_example(self):
        """ShapeDtypeStruct pytree of one state (hoisting example)."""
        raise NotImplementedError

    def _scan_ok(self, state):
        """In-scan continue criterion over a (traced) state: keep stepping
        while True.  The default is the PR-1 divergence detector — temp is
        finite (a NaN anywhere infects temp within one step via buoyancy/
        convection).  The steady-state finder overrides this with
        ``finite AND residual > tol`` so convergence freezes the member
        inside the chunk — the residual-based exit sentinel."""
        import jax.numpy as jnp

        return jnp.isfinite(jnp.sum(state.temp))

    def _scan_done_ok(self, state):
        """True when a member that STOPPED advancing (``_scan_ok`` False)
        stopped *successfully* (converged) rather than by divergence.
        Default: stopping is always a failure (the DNS semantics)."""
        import jax.numpy as jnp

        del state
        return jnp.asarray(False)

    def _scan_commit_ok(self, state):
        """Is a CANDIDATE stepped state worth committing?  The ensemble's
        per-member freeze keeps the previous state when this is False (the
        NaN-isolation semantics: never commit a poisoned state).  Default:
        same as ``_scan_ok`` — but a model whose ``_scan_ok`` also stops on
        SUCCESS (the adjoint finder's convergence) overrides this to plain
        finiteness, so the converged state IS committed before the member
        freezes (discarding it would pin the member one step shy of its
        answer forever)."""
        return self._scan_ok(state)

    def _gspmd_split_sep_fallback(self) -> bool:
        """True when the fused jitted chunk must be avoided (the GSPMD
        split-sep miscompile guard — see Navier2D); the base assumes no
        such poisoned layout."""
        return False

    def restart_fill(self, name: str, like):
        """Fill value for a state leaf a gathered (restart-equivalent)
        snapshot does not carry — default zero; override for leaves whose
        pristine value is not zero (the adjoint's residual norms)."""
        import jax.numpy as jnp

        del name
        return jnp.zeros_like(like)

    def _rebuild_dt_artifacts(self) -> None:
        """Rebuild everything ``self.dt`` is baked into (solvers, lift
        fields, compiled entry points) — called by :meth:`set_dt` on a
        cache-miss rung, AFTER ``self.dt`` was updated."""
        self._compile_entry_points()

    def _dt_changed(self, dt: float) -> None:
        """Propagation hook run on EVERY dt change (cache hit or miss),
        before artifacts are restored/rebuilt — a wrapper model syncs its
        embedded model here (``Navier2DLnse`` -> inner ``Navier2D``)."""

    @staticmethod
    def _build_span(nx: int, ny: int, mesh):
        """The span a model's ``__init__`` holds from its first line to its
        last: ``model.build``.  A 1-D model says ``ny=1``, one without a mesh
        ``None``."""
        return _tr.span(
            "model.build", layer=_LAYER, nx=int(nx), ny=int(ny),
            dtype=np.dtype(config.real_dtype()).name,
            devices=1 if mesh is None else int(mesh.size),
        )

    # -- sharding helpers ----------------------------------------------------

    def _scope(self):
        """Activate this model's mesh for the duration of a trace/dispatch."""
        from ..parallel.mesh import use_mesh

        if getattr(self, "mesh", None) is None:
            import contextlib

            return contextlib.nullcontext()
        return use_mesh(self.mesh)

    def _rest_layout(self):
        """The pencil layout a meshed model's spectral arrays rest in between
        dispatches (``Space2.rest``; all of a model's spaces rest alike).  The
        default reads the DNS's ``temp_space``; asked only under a mesh."""
        return self.temp_space.rest

    def _place(self, arr):
        """Put a spectral array into the pencil layout its space rests in
        under the mesh (:meth:`_rest_layout`)."""
        from ..parallel.mesh import device_put

        return device_put(arr, self._rest_layout())

    def _hand_back(self, state):
        """A chunk program's state as it leaves the program: every leaf laid
        out as ``_place`` laid it out going in (``parallel.mesh.settle``), so
        that one executable serves every dispatch.  The state as it is
        without a mesh."""
        import jax

        from ..parallel.mesh import settle

        if getattr(self, "mesh", None) is None:
            return state
        rest = self._rest_layout()
        return jax.tree.map(lambda leaf: settle(leaf, rest), state)

    def _hoist(self, fn, *example):
        """``hoist_constants`` under this model's mesh, with the constants
        committed to that mesh here, once, whole on every device
        (``parallel.mesh.replicate``): every compiled entry point takes its
        constants through this seam, so a dispatch places none.  Without a
        mesh they are the arrays ``hoist_constants`` returned."""
        from ..parallel.mesh import replicate
        from ..utils.jit import hoist_constants

        with self._scope():
            converted, consts = hoist_constants(fn, *example)
            _tr.count(consts=len(consts), const_bytes=sum(c.nbytes for c in consts))
            return converted, replicate(consts)

    def _exchanges_per_step(self) -> tuple:
        """``(flips, bytes one device sends)`` of one step: the pencil flips
        the step states (``parallel.mesh.flip``), counted where it is traced
        (``_compile_entry_points``); the compiler places their all-to-alls.
        A model with hand-placed regions counts those (``Navier2D``)."""
        return getattr(self, "_step_flips", (0, 0))

    def _mesh_span_args(self) -> dict:
        """What a meshed model's ``model.update_n`` span says of its
        decomposition: ``devices`` of the mesh, ``transposes`` and
        ``exchange_bytes`` per step (:meth:`_exchanges_per_step`), and
        ``replicated_leaves``, the state leaves that sit whole on every
        device at dispatch, and ``unplaced_args``, the leaves of the chunk's
        constants (counted where they are hoisted) and of the state that are
        not laid out over the mesh's devices, each of which the dispatch
        would place anew: 0 unless a path hands the chunk a single-device
        array.  Nothing without a mesh."""
        mesh = getattr(self, "mesh", None)
        if mesh is None:
            return {}
        import jax

        from ..parallel.mesh import unplaced

        transposes, sent = self._exchanges_per_step()
        return {
            "devices": int(mesh.size),
            "transposes": transposes,
            "exchange_bytes": sent,
            "replicated_leaves": sum(
                leaf.sharding.is_fully_replicated for leaf in jax.tree.leaves(self.state)
            ),
            "unplaced_args": self._unplaced_consts + unplaced(self.state, mesh),
        }

    # -- compiled entry points ------------------------------------------------

    def _compile_entry_points(self) -> None:
        """Hoist + jit the step/observables entry points (see Navier2D's
        original docstring: closure-converted constants keep the HLO small
        at large grids) and build the chunked ``step_n`` with the in-chunk
        early-exit.

        Every pass through here is the span ``model.compile_entry_points``
        (``consts`` and ``const_bytes`` hoisted, ``pass`` = how many passes
        this model has made), and its duration is recorded per model kind
        (telemetry/compile_log.py): dt-ladder re-jits and restores
        re-enter this seam without a model rebuild, and the cold-start
        ROADMAP item needs that attribution separated from build time."""
        from ..parallel.mesh import unplaced
        from ..telemetry import compile_log
        from ..ops.folded import int8_blocks, sliced_f64_multiplies, sliced_products
        from ..utils.jit import dot_generals_by_operand, gathers, reverses

        seam = _tr.timed("model.compile_entry_points", layer=_LAYER, consts=0, const_bytes=0)
        try:
            with seam:
                self._compile_entry_points_impl()
                seam.set(**{"pass": self.recompile_count})
                # the ``dot_general``s of one step's traced program by operand
                # type, counted once per pass for the ``update_n`` spans (the
                # ensemble's too): which arithmetic the step's products were
                # compiled in (float64 ones on the TPU path as sliced products,
                # ops/folded.py, the int8 products behind them and the operator
                # slices those stream), how many array flips its parity folds brought,
                # how many index gathers (the circular folds of the periodic
                # axes), and how many first-axis syntheses served two consumers
                products = dot_generals_by_operand(self._step_cc.jaxpr)
                self._step_products = {
                    "f64_products": products.get("float64", 0),
                    "f32_products": products.get("float32", 0),
                    "sliced_products": sliced_products(self._step_cc.jaxpr),
                    "sliced_f64_multiplies": sliced_f64_multiplies(self._step_cc.jaxpr),
                    "int8_products": products.get("int8", 0),
                    "int8_blocks": int8_blocks(self._step_cc.jaxpr),
                    "reverses": reverses(self._step_cc.jaxpr),
                    "gathers": gathers(self._step_cc.jaxpr),
                    "shared_syntheses": self._shared_syntheses,
                }
                # the scanned chunks' constants, counted once per pass for the
                # span's ``unplaced_args`` (:meth:`_mesh_span_args`)
                self._unplaced_consts = unplaced(
                    (self._step_consts, self._stats_consts, self._sent_consts),
                    getattr(self, "mesh", None),
                )
        finally:
            compile_log.observe_entry_compile(
                str(getattr(self, "MODEL_KIND", type(self).__name__)), seam.seconds
            )

    def _compile_entry_points_impl(self) -> None:
        import jax
        import jax.numpy as jnp

        from ..parallel.mesh import flips

        example = self._state_example()
        self.recompile_count += 1
        self._step_n_jit = None
        self._aot_step_n = {}
        self._sent_cc = None
        self._sent_consts = None
        self._step_n_sent = None
        self._stats_cc = None
        self._stats_consts = None
        self._step_n_stats = None
        self._stats_health_cc = None
        self._stats_health_consts = None
        self._stats_health_fn = None
        self._dig_cc = None
        self._dig_consts = None
        self._dig_fn = None
        with flips() as tally:
            step_cc, step_consts = self._hoist(self._make_step(), example)
        self._step_flips = (tally["flips"], tally["bytes"])
        obs_cc, obs_consts = self._hoist(self._make_observables(), example)
        self._step_consts = step_consts
        self._obs_consts = obs_consts
        # retained for the ensemble engine (models/ensemble.py): the SAME
        # traced jaxpr is vmapped over a leading member axis there — one
        # physics code path, batch as a leading axis, no forked step
        self._step_cc = step_cc
        self._obs_cc = obs_cc

        # the digest is a pure elementwise+reduction read of the state —
        # safe on every layout, including the eager fallback below
        if self._integrity_cfg is not None:
            self._compile_integrity_entry_points(example)

        if self._gspmd_split_sep_fallback():
            self._compile_eager_entry_points()
            return

        def step_once(consts, state):
            return self._hand_back(step_cc(consts, state))

        step_jit = jax.jit(step_once)
        self._step = lambda s: step_jit(self._step_consts, s)

        def step_n(consts, state, n: int):
            """n scanned steps with in-chunk early-exit: a continue flag
            (``_scan_ok`` — is-finite for the DNS, finite-and-unconverged
            for the steady finder) rides the carry, and once it drops the
            remaining iterations take the identity branch of a ``lax.cond``
            — the device stops paying for GEMMs mid-chunk.  Returns
            ``(state, steps_done)``."""

            def advance(carry):
                st, _, done = carry
                st2 = step_cc(consts, st)
                ok2 = self._scan_ok(st2)
                return st2, ok2, done + 1

            def body(carry, _):
                carry2 = jax.lax.cond(carry[1], advance, lambda c: c, carry)
                return carry2, None

            init = (state, jnp.asarray(True), jnp.asarray(0, jnp.int32))
            (final, _, done), _ = jax.lax.scan(body, init, None, length=n)
            return self._hand_back(final), done

        # the chunk donates nothing: XLA writes the scan's result to fresh
        # buffers, so update_n hands it ``self.state`` as it is and a
        # reference retained to that state stays valid across the call,
        # without a copy on the host's side of the launch.  The price is a
        # second state resident while a bucket runs (a few MB at the sizes
        # served).
        step_n_jit = jax.jit(step_n, static_argnames=("n",))
        # retained for aot_compile: .lower(...).compile() against this jit
        # object builds static-n executables ahead of traffic; a recompile
        # pass invalidates any prebuilt executables (the consts changed)
        self._step_n_jit = step_n_jit

        def dispatch_step_n(s, n):
            exe = self._aot_step_n.get(int(n))
            if exe is not None:
                self.aot_reuse_count += 1
                return exe(self._step_consts, s)
            return step_n_jit(self._step_consts, s, n=n)

        self._step_n = dispatch_step_n
        obs_jit = jax.jit(obs_cc)
        self._obs_fn = lambda s: obs_jit(self._obs_consts, s)

        if self._stats_engine is not None:
            self._compile_stats_entry_points(step_cc, example)

        if self._stability is not None:
            self._compile_sentinel_entry_points(example)

    def _compile_stats_entry_points(self, step_cc, example) -> None:
        """Stats-armed variant of the scanned chunk: the StatsState running
        sums and a sample-cadence tick ride the carry next to the state.
        The accumulator only READS the stepped state — it is a pure
        consumer, so the state trajectory stays BIT-identical to the plain
        chunk (the same contract the sentinel reductions ship under,
        CI-asserted).  Accumulation is gated on the stride cond AND on the
        step surviving ``_scan_ok`` (a corpse is never sampled)."""
        import jax
        import jax.numpy as jnp

        eng = self._stats_engine
        sx = eng.state_example()
        stats_cc, stats_consts = self._hoist(eng.accum_fn(), sx, example)
        health_cc, health_consts = self._hoist(eng.health_fn(), sx)
        self._stats_cc = stats_cc
        self._stats_consts = stats_consts
        self._stats_health_cc = health_cc
        self._stats_health_consts = health_consts
        health_jit = jax.jit(health_cc)
        self._stats_health_fn = lambda ss: health_jit(health_consts, ss)
        stride = int(eng.stride)

        def step_n_stats(consts, sconsts, state, ss, tick, n: int):
            def advance(carry):
                st, ss, tk, ok, done = carry
                st2 = step_cc(consts, st)
                ok2 = self._scan_ok(st2)
                tk2 = tk + 1
                take = jnp.logical_and(ok2, (tk2[0] % stride) == 0)
                ss2 = jax.lax.cond(
                    take, lambda s: stats_cc(sconsts, s, st2), lambda s: s, ss
                )
                return st2, ss2, tk2, ok2, done + 1

            def body(carry, _):
                carry2 = jax.lax.cond(carry[3], advance, lambda c: c, carry)
                return carry2, None

            init = (state, ss, tick, jnp.asarray(True), jnp.asarray(0, jnp.int32))
            (st, ss, tk, _, done), _ = jax.lax.scan(body, init, None, length=n)
            return self._hand_back(st), ss, tk, done

        stats_jit = jax.jit(step_n_stats, static_argnames=("n",))
        self._step_n_stats = lambda s, ss, tk, n: stats_jit(
            self._step_consts, self._stats_consts, s, ss, tk, n=n
        )

    def _compile_eager_entry_points(self) -> None:
        """Per-stage eager fallback (the GSPMD split-sep miscompile guard):
        slow but right; same early-exit semantics as the scanned fast path
        (the state that first failed ``_scan_ok`` is kept, later steps are
        identity)."""
        import jax.numpy as jnp

        step_fn = self._make_step()
        obs_fn = self._make_observables()
        self._step = step_fn

        def step_n_eager(state, n):
            done = 0
            for _ in range(int(n)):
                state = step_fn(state)
                done += 1
                if not bool(self._scan_ok(state)):
                    break
            return state, jnp.asarray(done, jnp.int32)

        self._step_n = step_n_eager
        self._obs_fn = obs_fn

    def aot_compile(self, chunk_steps: int) -> int:
        """AOT-build the chunked-step executables a ``chunk_steps``-sized
        dispatch needs — every static scan bucket of ``run_scanned``'s
        decomposition — via ``.lower().compile()`` on the retained jit
        objects.  Populates the persistent compile cache (the executables
        survive process death when it is armed) AND retains the compiled
        objects so dispatch skips the jit machinery entirely (reuse tallied
        in :attr:`aot_reuse_count`).  Returns how many executables were
        newly built (0 on the eager-fallback path, where there is nothing
        to compile ahead of time)."""
        from ..utils.jit import scan_buckets

        step_n_jit = getattr(self, "_step_n_jit", None)
        if step_n_jit is None:
            return 0
        built = 0
        with self._scope():
            for n in scan_buckets(chunk_steps):
                if n in self._aot_step_n:
                    continue
                self._aot_step_n[n] = step_n_jit.lower(
                    self._step_consts, self.state, n=n
                ).compile()
                built += 1
        return built

    def _compile_sentinel_entry_points(self, example) -> None:
        """Sentinel variant of the scanned chunk (set_stability): the carry
        additionally holds a CFL-ok flag and running sentinel reductions, and
        the early-exit fires on EITHER a failed ``_scan_ok`` (the NaN path)
        or a per-step CFL above ``max_cfl`` — the *pre-divergence* catch,
        taken while the state is still finite so the chunk can be recovered
        by an in-memory rollback instead of a checkpoint restore."""
        import jax
        import jax.numpy as jnp

        sent_cc, sent_consts = self._hoist(
            self._make_step(with_sentinels=True), example
        )
        self._sent_cc = sent_cc
        self._sent_consts = sent_consts
        ceiling = float(self._stability.max_cfl)
        # with the stats engine armed, the running sums + sample tick ride
        # the sentinel carry too (appended AFTER the sentinel slots, so the
        # fetch indices the pending-resolve path reads stay put); sampling
        # is gated on the step being finite AND under the ceiling — a
        # tripping chunk's accumulation is discarded by the rollback anyway
        stats_cc = self._stats_cc
        stats_stride = int(self._stats_engine.stride) if stats_cc is not None else 0

        def step_n_sent(consts, sconsts, carry, n: int):
            def advance(carry):
                st, fin, cok, done, cflm, gm, dvm, kep = carry[:8]
                st2, (cfl, ke, dv) = sent_cc(consts, st)
                fin2 = self._scan_ok(st2)
                # NaN cfl must read as the NaN path, not a ceiling trip:
                # NaN > ceiling is False, so ~(cfl > ceiling) stays True
                cok2 = jnp.logical_not(cfl > ceiling)
                growth = jnp.where(kep > 0.0, ke / kep, 1.0)
                out = (
                    st2,
                    fin2,
                    cok2,
                    done + 1,
                    jnp.maximum(cflm, cfl),
                    jnp.maximum(gm, growth),
                    jnp.maximum(dvm, dv),
                    ke,
                )
                if stats_cc is not None:
                    ss, tk = carry[8], carry[9]
                    tk2 = tk + 1
                    take = fin2 & cok2 & ((tk2[0] % stats_stride) == 0)
                    ss2 = jax.lax.cond(
                        take,
                        lambda s: stats_cc(sconsts, s, st2),
                        lambda s: s,
                        ss,
                    )
                    out = out + (ss2, tk2)
                return out

            def body(carry, _):
                carry2 = jax.lax.cond(
                    carry[1] & carry[2], advance, lambda c: c, carry
                )
                return carry2, None

            final, _ = jax.lax.scan(body, carry, None, length=n)
            return (self._hand_back(final[0]),) + final[1:]

        sent_jit = jax.jit(step_n_sent, static_argnames=("n",))
        self._step_n_sent = lambda c, n: sent_jit(
            self._sent_consts, self._stats_consts, c, n=n
        )

    # -- Integrate protocol ---------------------------------------------------

    def update(self) -> None:
        with self._scope():
            self.state = self._step(self.state)
        self.time += self.dt

    def update_n(self, n: int):
        """Advance n steps on the device via scanned power-of-two chunks
        (utils/jit.run_scanned).  Dispatches stay asynchronous and donate
        nothing: the chunk takes the caller-visible state as it is and
        writes its result to fresh buffers, so a retained reference to the
        state stays readable and nothing is copied for it.  On divergence
        the in-scan early exit freezes the state, ``exit()`` reports it at
        the next chunk boundary, and ``self.time`` deliberately counts the
        scheduled steps.

        With stability sentinels armed (:meth:`set_stability`) the chunk
        additionally returns a
        :class:`~rustpde_mpi_tpu.utils.governor.ChunkStatus` (also stored as
        ``self.last_chunk_status``): a per-step CFL above the hard ceiling
        early-exits the scan with ``pre_divergence`` while the state is
        still finite, the chunk is rolled back in memory and ``exit()``
        latches True until a governor acknowledges
        (:meth:`clear_pre_divergence`)."""
        if self._step_n_sent is not None:
            return self._update_n_sentinel(n)
        with DispatchSpans(
            "model", _LAYER, steps=int(n), **self._step_products, **self._mesh_span_args()
        ) as seams, self._scope():
            if self._step_n_stats is not None:
                with seams.handover():
                    carry = (self.state, self.stats_state, self._stats_tick)
                self.state, self.stats_state, self._stats_tick = seams.run(
                    lambda c, k: self._step_n_stats(*c, k)[:3], carry, n
                )
            else:
                with seams.handover():
                    carry = self.state
                self.state = seams.run(
                    lambda s, k: self._step_n(s, k)[0], carry, n, aot=self._aot_step_n
                )
        self.time += n * self.dt
        return None

    def _update_n_sentinel(self, n: int):
        """Sentinel-armed chunk: scan with CFL/KE/|div| reductions riding the
        carry, one scalar fetch at the end (the only extra host sync)."""
        return self.update_n_pending(n).resolve()

    def update_n_pending(self, n: int):
        """Sentinel-armed chunk with a DEFERRED commit decision (the lag=1
        contract of the overlapped driver, utils/io_pipeline.py): dispatch
        the scanned chunk, PROVISIONALLY advance ``state``/``time`` to its
        end, and return a
        :class:`~rustpde_mpi_tpu.utils.io_pipeline.PendingChunkStatus` whose
        ``resolve()`` fetches the sentinel scalars and either confirms the
        advance or restores the chunk-start snapshot (+ latches ``exit()``)
        — exactly the synchronous :meth:`update_n` outcome, decided one host
        round-trip later."""
        import jax.numpy as jnp

        from ..utils.governor import ChunkStatus
        from ..utils.io_pipeline import PendingChunkStatus

        if self._step_n_sent is None:
            raise RuntimeError(
                "update_n_pending requires armed stability sentinels "
                "(set_stability)"
            )
        self._pre_div_latch = False
        rdt = config.real_dtype()
        stats_on = self._stats_cc is not None
        with DispatchSpans(
            "model", _LAYER, steps=int(n), **self._step_products, **self._mesh_span_args()
        ) as seams, self._scope():
            # the running sums + tick ride the sentinel carry (and the
            # rollback snapshot below — a tripped chunk's samples are
            # discarded with its steps)
            with seams.handover(fresh=7):
                carry = (
                    self.state,
                    jnp.asarray(True),
                    jnp.asarray(True),
                    jnp.asarray(0, jnp.int32),
                    jnp.asarray(0.0, rdt),  # cfl max
                    jnp.asarray(0.0, rdt),  # ke growth max
                    jnp.asarray(0.0, rdt),  # |div| max
                    jnp.asarray(0.0, rdt),  # previous-step ke
                ) + ((self.stats_state, self._stats_tick) if stats_on else ())
            carry = seams.run(self._step_n_sent, carry, n)
        st, fin, cok, done, cflm, gm, dvm, ke = carry[:8]
        snapshot = (self.state, self.time, self.stats_state, self._stats_tick)
        self.state = st  # provisional: resolve() confirms or restores
        if stats_on:
            self.stats_state, self._stats_tick = carry[8], carry[9]
        self.time += n * self.dt
        dt = self.dt

        def finish(fetched):
            fin_h, cok_h, done_h, cflm_h, gm_h, dvm_h, ke_h = fetched
            fin_b, cok_b = bool(fin_h), bool(cok_h)
            pre_div = fin_b and not cok_b
            if pre_div:
                # in-memory rollback: the chunk donated nothing, so the
                # snapshot still holds the chunk-start state — put it
                # back and latch exit() until a governor acts
                (self.state, self.time, self.stats_state, self._stats_tick) = (
                    snapshot
                )
                self._pre_div_latch = True
            status = ChunkStatus(
                requested=int(n),
                steps_done=int(done_h),
                finite=fin_b,
                cfl_ok=cok_b,
                pre_divergence=pre_div,
                cfl_max=float(cflm_h),
                ke=float(ke_h),
                ke_growth_max=float(gm_h),
                div_max=float(dvm_h),
                dt=dt,
            )
            self.last_chunk_status = status
            return status

        return PendingChunkStatus((fin, cok, done, cflm, gm, dvm, ke), finish)

    def set_stability(self, cfg) -> None:
        """Arm/disarm (``None``) the on-device stability sentinels
        (:class:`~rustpde_mpi_tpu.config.StabilityConfig`): compiles the
        sentinel variant of the scanned chunk into :meth:`update_n`.  Under
        the GSPMD split-sep fallback the sentinel path is unavailable and
        stepping stays plain (a one-time warning is emitted)."""
        self._stability = cfg
        self._dt_cache.clear()  # cached artifacts lack/stale sentinel entries
        self._compile_entry_points()
        if cfg is not None and self._step_n_sent is None:
            import warnings

            warnings.warn(
                "stability sentinels are not available on the per-stage "
                "eager GSPMD fallback path; stepping stays plain",
                RuntimeWarning,
                stacklevel=2,
            )
        self.last_chunk_status = None
        self._pre_div_latch = False

    def clear_pre_divergence(self) -> None:
        """Acknowledge a ``pre_divergence`` catch (the governor changed dt /
        killed members and wants the chunk retried): unlatch ``exit()``."""
        self._pre_div_latch = False

    # -- in-scan physics statistics (models/stats.py) --------------------------

    def set_stats(self, cfg) -> None:
        """Arm/disarm (``None``) the in-scan physics-stats engine
        (:class:`~rustpde_mpi_tpu.config.StatsConfig`): compiles the
        stats-carrying variants of the scanned chunks and zero-initializes
        the running sums.  Under the GSPMD split-sep eager fallback the
        in-scan engine is unavailable and stepping stays plain (a one-time
        warning, like the sentinels)."""
        import jax.numpy as jnp

        if cfg is None:
            self._stats_engine = None
            self.stats_state = None
            self._stats_tick = None
            self._dt_cache.clear()
            self._compile_entry_points()
            return
        from .stats import StatsEngine

        self._stats_engine = StatsEngine(self, cfg)
        self._dt_cache.clear()
        self._compile_entry_points()
        if self._stats_cc is None:
            import warnings

            warnings.warn(
                "the in-scan stats engine is not available on the "
                "per-stage eager GSPMD fallback path; stats stay disarmed",
                RuntimeWarning,
                stacklevel=2,
            )
            self._stats_engine = None
            return
        with self._scope():
            self.stats_state = self._stats_engine.init_state()
            self._stats_tick = jnp.zeros((1,), jnp.int32)

    def reset_stats(self) -> None:
        """Zero the running sums + sample tick (a fresh averaging window)."""
        import jax.numpy as jnp

        if not self.stats_armed:
            return
        with self._scope():
            self.stats_state = self._stats_engine.init_state()
            self._stats_tick = jnp.zeros((1,), jnp.int32)

    @property
    def stats_engine(self):
        """The armed :class:`~rustpde_mpi_tpu.models.stats.StatsEngine`
        (None when disarmed) — public surface for the runner/scheduler."""
        return self._stats_engine

    @property
    def stats_armed(self) -> bool:
        return self._stats_engine is not None and self.stats_state is not None

    def stats_health_async(self):
        """Dispatch the compiled :data:`~rustpde_mpi_tpu.models.stats
        .HEALTH_NAMES` readout over the running sums and return an
        observable future — the runner resolves it one boundary later and
        exports gauges / typed journal events (``resolution_warning``,
        ``budget_drift``)."""
        from ..utils.io_pipeline import ObservableFuture

        if not self.stats_armed:
            raise RuntimeError("stats_health_async needs an armed stats engine")
        with self._scope():
            return ObservableFuture(
                self._stats_health_fn(self.stats_state),
                convert=lambda vals: tuple(
                    np.asarray(v) for v in vals  # lint-ok: RPD005 health scalars are replicated reductions
                ),
            )

    def stats_summary(self) -> dict | None:
        """Synchronous health readout as a dict (None when disarmed)."""
        if not self.stats_armed:
            return None
        from .stats import HEALTH_NAMES

        vals = self.stats_health_async().result()
        return {
            name: (float(v) if np.ndim(v) == 0 else [float(x) for x in v])
            for name, v in zip(HEALTH_NAMES, vals)
        }

    def stats_host_items(self) -> list:
        """Gathered-snapshot rows for the stats leaves
        (:meth:`StatsEngine.host_items`); empty when disarmed."""
        if not self.stats_armed:
            return []
        return self._stats_engine.host_items(self.stats_state, self._stats_tick)

    def apply_restored_stats(self, data: dict | None) -> None:
        """Install stats leaves read back from a gathered snapshot (keys =
        leaf names + ``tick``) via :meth:`StatsEngine.restore_state`:
        ``None``/missing leaves reset to zero — a checkpoint written before
        stats were armed restarts the averaging window instead of failing
        the restore."""
        if not self.stats_armed:
            return
        with self._scope():
            self.stats_state, self._stats_tick = (
                self._stats_engine.restore_state(
                    data, k=self.k if hasattr(self, "k") else None
                )
            )

    # -- end-to-end integrity (integrity/) ------------------------------------

    def _compile_integrity_entry_points(self, example) -> None:
        """Hoist + jit the on-device state digest (integrity/digest.py):
        a pure uint32 read of the state, retained closure-converted
        (``_dig_cc``/``_dig_consts``) so the ensemble engine re-vmaps the
        SAME jaxpr over the member axis — per-member digests localize a
        corrupted member exactly like the observables localize NaNs."""
        import jax

        from ..integrity import digest_tree

        # a function of this model's own: the digest states a layout under a
        # mesh (whole on every device), and a trace of the module's one
        # ``digest_tree`` would be found again, mesh and all, by the next model
        dig_cc, dig_consts = self._hoist(lambda state: digest_tree(state), example)
        self._dig_cc = dig_cc
        self._dig_consts = dig_consts
        dig_jit = jax.jit(dig_cc)
        self._dig_fn = lambda s: dig_jit(self._dig_consts, s)

    def set_integrity(self, cfg) -> None:
        """Arm/disarm (``None``) the integrity layer
        (:class:`~rustpde_mpi_tpu.config.IntegrityConfig`): compiles the
        on-device digest entry point.  The digest is a pure consumer of
        the state — the trajectory stays bit-identical armed vs not (the
        same CI-asserted contract the stats/sentinel chunks ship under)."""
        self._integrity_cfg = cfg
        self._dt_cache.clear()
        self._compile_entry_points()

    @property
    def integrity_config(self):
        return self._integrity_cfg

    @property
    def integrity_armed(self) -> bool:
        return (
            self._integrity_cfg is not None
            and getattr(self, "_dig_fn", None) is not None
        )

    def _digest_future(self, device_val):
        from ..utils.io_pipeline import ObservableFuture

        return ObservableFuture(
            device_val,
            convert=lambda v: np.asarray(v)  # lint-ok: RPD005 a replicated uint32 scalar
        )

    def state_digest_async(self):
        """Dispatch the on-device digest of the CURRENT state and return
        an observable future (uint32 scalar; ``(k,)`` per-member vector on
        ensembles) — streamed by the runner with the observables futures,
        no extra host sync per chunk."""
        if not self.integrity_armed:
            raise RuntimeError(
                "state_digest_async needs an armed integrity layer "
                "(set_integrity)"
            )
        with self._scope():
            return self._digest_future(self._dig_fn(self.state))

    def digest_of_async(self, state):
        """Digest an arbitrary state pytree (the runner's retained
        chunk-start copies) without touching ``self.state``."""
        with self._scope():
            return self._digest_future(self._dig_fn(state))

    def shadow_digest_async(self, snap: dict, n: int):
        """Shadow re-execution audit kernel: re-step ``n`` steps from the
        retained :meth:`integrity_snapshot` through the PLAIN chunked path
        and digest the result.  The snapshot is not consumed (the chunk
        donates nothing).  The plain chunk is bit-identical to the
        live sentinel/stats chunks by the pure-consumer contract, and XLA
        executables are deterministic — a digest differing from the live
        chunk's means corrupted state."""
        from ..utils.jit import run_scanned

        if not self.integrity_armed:
            raise RuntimeError(
                "shadow_digest_async needs an armed integrity layer "
                "(set_integrity)"
            )
        with self._scope():
            st = run_scanned(lambda s, k: self._step_n(s, k)[0], snap["state"], n)
            return self._digest_future(self._dig_fn(st))

    def integrity_snapshot(self) -> dict:
        """Un-donated device-side copy of everything an in-memory
        integrity rollback must restore (state/time + armed stats)."""
        import jax
        import jax.numpy as jnp

        with self._scope():
            snap = {
                "state": jax.tree.map(jnp.copy, self.state),
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
        import jax
        import jax.numpy as jnp

        with self._scope():
            self.state = jax.tree.map(jnp.copy, snap["state"])
            self.time = snap["time"]
            if "stats" in snap and self.stats_armed:
                ss, tick = snap["stats"]
                self.stats_state = jax.tree.map(jnp.copy, ss)
                self._stats_tick = jnp.copy(tick)
        self._obs_cache = None
        self._pre_div_latch = False

    def _verify_restored_digest(self, expected) -> None:
        """Recompute the on-device digest after a (bit-exact, sharded)
        restore and compare with the manifest's — the device→disk→device
        loop the host-side sha256 cannot close.  No-op when the
        checkpoint predates the integrity layer or it is disarmed."""
        if expected is None or not self.integrity_armed:
            return
        got = np.asarray(  # lint-ok: RPD005 fully-replicated uint32 digest
            self.state_digest_async().result()
        )
        exp = np.asarray(  # lint-ok: RPD005 manifest root data, host array
            expected
        ).astype(got.dtype).reshape(got.shape)
        if not np.array_equal(got, exp):
            from ..integrity import IntegrityError

            raise IntegrityError(
                f"restored state digest {got.tolist()} does not match the "
                f"checkpoint manifest digest {exp.tolist()} — the snapshot "
                "was corrupted between device and disk",
                check="checkpoint",
            )

    def get_time(self) -> float:
        return self.time

    def get_dt(self) -> float:
        return self.dt

    def reset_time(self) -> None:
        self.time = 0.0

    # -- dt rung cache --------------------------------------------------------

    #: attributes a dt change swaps out, cached per rung so a governor
    #: cycling a bounded dt ladder re-jits each rung ONCE (per subclass —
    #: extend with whatever else dt is baked into)
    _DT_ARTIFACTS = (
        "_step",
        "_step_n",
        "_obs_fn",
        "_step_cc",
        "_obs_cc",
        "_step_consts",
        "_obs_consts",
        "_sent_cc",
        "_sent_consts",
        "_step_n_sent",
        "_stats_cc",
        "_stats_consts",
        "_step_n_stats",
        "_stats_health_cc",
        "_stats_health_consts",
        "_stats_health_fn",
        "_dig_cc",
        "_dig_consts",
        "_dig_fn",
        "_unplaced_consts",
    )

    def _dt_artifacts(self) -> dict:
        return {k: getattr(self, k, None) for k in self._DT_ARTIFACTS}

    def set_dt(self, dt: float) -> None:
        """Change the time-step size of a live model (the governor's dt
        ladder and the divergence-retry backoff).

        dt is baked deep into the pipeline, so a FIRST visit to a dt
        rebuilds the dt-baked artifacts (:meth:`_rebuild_dt_artifacts`) and
        re-traces the jitted entry points.  Every artifact is then cached
        per dt value, so revisiting a rung swaps the cached objects back in
        — the retained jit closures keep their identity, so XLA's executable
        cache hits and the total re-jit count over a long governed run is
        bounded by the ladder size.  State and time are untouched either
        way: the run continues from the same fields at the new step size."""
        dt = float(dt)
        if dt <= 0.0:
            raise ValueError(f"dt must be positive, got {dt}")
        if dt == self.dt:
            return
        self._dt_cache[self.dt] = self._dt_artifacts()
        self.dt = dt
        self._dt_changed(dt)
        cached = self._dt_cache.get(dt)
        if cached is not None:
            for key, value in cached.items():
                setattr(self, key, value)
            self._obs_cache = None
            return
        self._rebuild_dt_artifacts()
        self._obs_cache = None

    # -- observables / exit ---------------------------------------------------

    def get_observables_async(self):
        """Dispatch the fused observables computation and return an
        :class:`~rustpde_mpi_tpu.utils.io_pipeline.ObservableFuture` WITHOUT
        waiting for it — the device keeps working while the host decides
        when (if ever) to fetch.  Cached per state, shared with the
        synchronous accessors and :meth:`exit_future`, so diagnostics +
        break checks cost ONE dispatch and ONE host transfer per state."""
        from ..utils.io_pipeline import ObservableFuture

        if self._obs_cache is None or self._obs_cache[0] is not self.state:
            with _tr.span("model.observe_launch", layer=_LAYER), self._scope():
                fut = ObservableFuture(
                    self._obs_fn(self.state),
                    convert=lambda vals: tuple(float(v) for v in vals),
                )
            self._obs_cache = (self.state, fut)
        return self._obs_cache[1]

    def get_observables(self) -> tuple:
        """The model's scalars as floats, in the order of
        :attr:`observable_names` (four, the NaN detector at index 3; a
        scenario may append more) — one fused device dispatch, cached per
        state, fetched in ONE host transfer."""
        with _tr.span("model.observe", layer=_LAYER) as sp:
            before = self._obs_cache
            fut = self.get_observables_async()
            sp.set(cached=self._obs_cache is before)  # no launch was needed
            # the one place the host blocks on the device
            with _tr.span("model.observe_fetch", layer=_LAYER):
                return fut.result()

    def device_fence(self) -> None:
        """Block until every dispatched device computation whose output this
        model still holds has completed: the state chunk, the running stats
        sums, and the cached observables dispatch.  The serve scheduler runs
        this before any host-level collective while the campaign occupies a
        PROPER sub-mesh — a full-device barrier would otherwise start on the
        sub-mesh's idle complement and its wire traffic interleaves with the
        campaign's in-flight collectives (multihost.set_device_fence)."""
        if self.state is not None:
            jax.block_until_ready(self.state)
        stats = getattr(self, "stats_state", None)
        if stats is not None:
            jax.block_until_ready(stats)
        cache = self._obs_cache
        if cache is not None and not cache[1].ready():
            cache[1].result()

    def div_norm(self) -> float:
        """The NaN-detector observable (index 3 by convention)."""
        return self.get_observables()[3]

    def exit(self) -> bool:
        """NaN-divergence break criterion, extended by the pre-divergence
        latch: a CFL-ceiling catch (sentinels armed) reads as a break until
        a governor clears it."""
        if self._pre_div_latch:
            return True
        return bool(np.isnan(self.div_norm()))

    def exit_future(self):
        """Non-blocking form of :meth:`exit` for the overlapped driver
        (utils/integrate.py ``overlap``): a latched pre-divergence catch
        resolves immediately (host-side fact); otherwise the break flag
        rides the cached observables dispatch."""
        from ..utils.io_pipeline import MappedFuture, immediate

        if self._pre_div_latch:
            return immediate(True)
        return MappedFuture(
            self.get_observables_async(), lambda vals: bool(np.isnan(vals[3]))
        )

    def state_healthy(self) -> bool:
        """Is the current state worth checkpointing?  Distinct from
        :meth:`exit`: a steady-state finder that CONVERGED exits the run
        loop but its state is the answer, not a corpse.  The resilient
        runner consults this before every checkpoint."""
        if self._pre_div_latch:
            return False
        return bool(np.isfinite(self.div_norm()))

    # -- sharded (shard-wise) snapshot surface --------------------------------

    def snapshot_state_items(self) -> list:
        """``(name, device_array)`` for every state leaf the sharded
        checkpoint must carry — the full restart set, generic over the
        state NamedTuple.  With the stats engine armed the running sums +
        sample tick join the set, so long-horizon averages ride the
        two-phase sharded checkpoints and survive kill/resume bit-exactly."""
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
        """Pull the stats leaves out of a sharded-restore ``updates`` dict
        (missing ones reset to zero — an older checkpoint restarts the
        averaging window) and install them; the remaining entries are the
        state leaves the caller installs."""
        if not self.stats_armed:
            return
        self.apply_restored_stats(self._stats_engine.split_restored(updates))

    def snapshot_root_items(self) -> list:
        """Replicated host-side data for the sharded manifest root.  With
        the integrity layer armed the on-device state digest rides the
        manifest: the sharded format is bit-exact, so a restore recomputes
        and compares it (:meth:`_verify_restored_digest`) — a verified
        checkpoint closes the device→disk→device loop."""
        items = [("time", np.asarray(float(self.time), dtype=np.float64), "raw")]
        for key, value in getattr(self, "params", {}).items():
            items.append((key, np.asarray(float(value), dtype=np.float64), "raw"))
        if self.integrity_armed:
            items.append((
                "integrity_digest",
                np.asarray(self.state_digest_async().result()),  # lint-ok: RPD005 a replicated uint32 scalar
                "raw",
            ))
        return items

    def apply_restored_state(self, updates: dict, attrs: dict, root: dict) -> None:
        """Install state leaves assembled by the sharded reader (already
        placed in this model's target layout) + the manifest's time.  Stats
        leaves (engine armed) are split off first — restored exactly when
        the checkpoint carries them, reset to zero when it predates the
        arming."""
        self._split_restored_stats(updates)
        self.state = self.state._replace(**updates)
        self.time = float(np.asarray(root["time"]))
        self._obs_cache = None
        self._pre_div_latch = False
        self._verify_restored_digest(root.get("integrity_digest"))

    # -- compatibility bucketing ----------------------------------------------

    def _compat_fields(self) -> tuple:
        """Everything (beyond the model kind) baked into the compiled step —
        per subclass."""
        raise NotImplementedError

    @property
    def compat_key(self) -> tuple:
        """Operator-constant bucket key, prefixed with the model kind: two
        requests/models with equal keys share one compiled (vmapped) step
        jaxpr — the serve scheduler buckets by this; anything differing
        forces a fresh model build + compile."""
        return (str(self.MODEL_KIND),) + tuple(self._compat_fields())
