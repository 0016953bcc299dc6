"""Resilient run harness: the machinery that keeps long DNS campaigns alive.

The reference treats restart-from-HDF5 as a first-class operation
(navier_io.rs; rebuilt in utils/checkpoint.py) but has no story for
*surviving* the failures long Rayleigh–Bénard campaigns actually hit.  This
module adds the production-harness layer on top of the ``integrate`` driver:

* **durable checkpoints** — rolling, atomic, digest-stamped snapshots
  (utils/checkpoint.py) written on a wall-clock and/or sim-time cadence,
  with a retention window and auto-resume from the newest *valid* file;
  on multi-process meshes (or with ``IOConfig.sharded_checkpoints``) the
  SHARDED two-phase format is used — each host writes only its addressable
  shards, root commits via an atomic manifest whose presence is the commit
  marker, and restore is topology-elastic (a checkpoint written at one
  mesh/host count resumes on another, or serially, bit-equal),
* **preemption safety** — SIGTERM/SIGINT handlers that finish the in-flight
  chunk, checkpoint, journal and exit cleanly; on multihost meshes rank 0
  decides and the decision is broadcast so every host snapshots the same
  step,
* **proactive stability governance** — with a ``StabilityConfig`` the model
  compiles on-device CFL/energy sentinels into its scanned chunks and a
  :class:`~rustpde_mpi_tpu.utils.governor.StabilityGovernor` drives dt
  toward a target Courant number on a rung-cached geometric ladder: a hard
  CFL ceiling early-exits the chunk *before* NaNs appear and the recovery
  is a cheap in-memory rollback (no checkpoint IO), with regrowth back up
  the ladder after a healthy stretch (utils/governor.py),
* **divergence recovery** — when the model's NaN break criterion fires (the
  reactive last resort once the governor is out of ladder), roll back to
  the last good checkpoint, shrink dt by ``dt_backoff`` (rebuilding the
  dt-baked solvers via ``set_dt``, floored at ``dt_min``) and retry up to
  ``max_retries``; ensembles can additionally respawn dead members from
  perturbed healthy donors at rollback,
* **hang watchdogs** — device dispatches run under a deadline
  (:func:`call_with_watchdog`); expiry dumps all-thread stacks via
  ``faulthandler`` and raises a structured :class:`DispatchHang` instead of
  wedging the job silently (the failure mode that ate PR 1's tier-1 budget),
* **an overlapped I/O pipeline** — with the default
  :class:`~rustpde_mpi_tpu.config.IOConfig`, cadence checkpoints are
  fetched to host on the main thread and serialized/hashed/fsynced on a
  background worker, break checks and callback diagnostics ride observable
  futures one chunk behind the device, and dispatches are no longer fenced
  per chunk (``block_until_ready`` only runs when a watchdog deadline needs
  it) — so at a given cadence the device steps through checkpoint writes
  instead of idling behind them (utils/io_pipeline.py; the writer drains
  before every rollback/resume read and at run end, so durability and
  recovery semantics are unchanged),
* **a JSONL run journal** — every checkpoint, fault, retry and outcome is an
  appended JSON line (step, time, Nu, wall seconds, attempt), so a campaign's
  failure history is machine-readable after the fact,
* **deterministic fault injection** — ``RUSTPDE_FAULT=nan@<step>`` /
  ``spike@<step>`` / ``kill@<step>`` / ``slow@<step>`` (or the ``fault=``
  argument) exercises every recovery path — including every governor path,
  via the finite velocity-spike incipient blow-up — in tests without
  waiting for real failures.

This checkpoint/resume/watchdog shape is exactly the preemption-safe
training-loop pattern (ROADMAP north star): swap "spectral coefficients" for
"optimizer state" and the harness transfers unchanged.
"""

from __future__ import annotations

import contextlib
import dataclasses
import errno as _errno
import faulthandler
import os
import signal
import sys
import threading
import time as _time

import numpy as np

from ..telemetry import metrics as _tm
from ..telemetry import tracing as _tr
from ..telemetry.exporters import MetricsDumper
from . import checkpoint
from .faults import FaultPlan, FaultSpecError, validate_fault_env  # noqa: F401
from .governor import StabilityGovernor
from .integrate import integrate

from .. import config
from ..config import env_get
from ..parallel import sanitizer as _sanitizer
from .io_pipeline import AsyncWriteError, IOPipeline
from .journal import JournalWriter, read_journal


class DispatchHang(RuntimeError):
    """A device dispatch (or host barrier) exceeded its watchdog deadline.

    Raised with all-thread stacks already dumped to stderr — the structured
    replacement for a silent job-wide hang.  The abandoned worker thread may
    still be blocked inside the runtime; the process should checkpoint what
    it can and exit/restart rather than keep dispatching."""

    def __init__(self, label: str, timeout_s: float):
        super().__init__(
            f"{label} did not complete within {timeout_s:.1f}s "
            "(all-thread stacks dumped to stderr)"
        )
        self.label = label
        self.timeout_s = timeout_s


class DivergenceError(RuntimeError):
    """A run diverged and could not be recovered (retries exhausted, or no
    valid checkpoint to roll back to)."""


def call_with_watchdog(fn, timeout_s: float | None, label: str = "dispatch"):
    """Run ``fn()`` under a deadline: the call executes in a worker thread
    while the caller waits ``timeout_s``; on expiry every thread's stack is
    dumped via ``faulthandler`` and :class:`DispatchHang` is raised.  A
    ``None``/non-positive timeout calls ``fn()`` directly (no thread).

    The expired worker is a daemon and keeps blocking in the background —
    by design: there is no safe way to cancel a wedged runtime call, so the
    caller gets control back to checkpoint/exit while the corpse is left to
    the OS."""
    if not timeout_s or timeout_s <= 0:
        return fn()
    result: list = []
    error: list = []

    def target():
        try:
            result.append(fn())
        except BaseException as exc:  # re-raised in the caller below
            error.append(exc)

    worker = threading.Thread(target=target, name=f"watchdog:{label}", daemon=True)
    worker.start()
    worker.join(timeout_s)
    if worker.is_alive():
        sys.stderr.write(
            f"[resilience] {label} stuck past its {timeout_s:.1f}s deadline; "
            "all-thread stacks:\n"
        )
        sys.stderr.flush()
        faulthandler.dump_traceback(all_threads=True)
        raise DispatchHang(label, timeout_s)
    if error:
        raise error[0]
    return result[0]


def _single_process() -> bool:
    """True when the JAX runtime is (or defaults to) one process.  The
    blanket except treats an unimportable/uninitialized runtime as single —
    the caller then takes the local (non-collective) path, which is the
    only one that can work without a runtime."""
    try:
        import jax

        return jax.process_count() == 1
    except Exception:
        return True


def _host_column_mask(pde, host: int, leaf, hit, miss=1.0):
    """Per-leaf multiplier that applies ``hit`` only on the spectral
    columns owned by process ``host``'s devices (the pencil axis is the
    LAST one under the x-pencil SPEC layout) and ``miss`` elsewhere.

    Every process builds the identical mask from the mesh metadata alone,
    so a host-scoped fault stays a CONSISTENT collective dispatch — the
    fault originates on one host's shard and propagates through the
    coupled step, like a real single-host memory corruption would."""
    import jax.numpy as jnp

    from ..parallel.mesh import SPEC, pencil_sharding

    mesh = getattr(pde, "mesh", None)
    n = leaf.shape[-1]
    # dtype from metadata only — np.asarray(leaf) would fetch the whole
    # leaf, which raises on a real multi-controller mesh (non-addressable
    # shards), the very platform host-scoped faults exist for
    cols = np.full(n, miss, dtype=np.empty(0, leaf.dtype).real.dtype)
    if mesh is None:
        if host in (0, None):
            cols[:] = hit
    else:
        s = pencil_sharding(mesh, SPEC, ndim=len(leaf.shape))
        try:
            imap = s.devices_indices_map(tuple(leaf.shape))
        except ValueError:  # uneven dim: replicated layout, host 0 owns all
            imap = None
        if imap is None:
            if host == 0:
                cols[:] = hit
        else:
            for dev, idx in imap.items():
                if dev.process_index != host:
                    continue
                start, stop, _ = idx[-1].indices(n)
                cols[start:stop] = hit
    return jnp.asarray(cols)


def poison_state(pde, host: int | None = None) -> None:
    """Multiply every state leaf by NaN (the deterministic stand-in for a
    numerical blow-up; used by fault injection).  With ``host`` given, only
    the spectral columns owned by that process's devices are poisoned —
    the multihost single-host-corruption shape (the NaN infects the rest
    of the domain through the next coupled step)."""
    import jax

    scope = pde.model._scope if hasattr(pde, "model") else pde._scope
    with scope():
        if host is None:
            pde.state = jax.tree.map(lambda x: x * float("nan"), pde.state)
        else:
            mdl = pde.model if hasattr(pde, "model") else pde
            pde.state = jax.tree.map(
                lambda x: x * _host_column_mask(mdl, host, x, float("nan")),
                pde.state,
            )
        if hasattr(pde, "mask") and hasattr(pde, "_finite_mask"):
            pde.mask = pde._finite_mask(pde.state)
    pde._obs_cache = None


def spike_state(pde, factor: float = 50.0, host: int | None = None) -> None:
    """Scale the velocity fields by ``factor`` on-device: a deterministic
    incipient blow-up — finite state, CFL far past the stability ceiling.
    Under the governor this is caught pre-NaN (rollback happens in memory
    and dt descends the ladder until the spiked flow is Courant-stable);
    without sentinels the over-CFL explicit convection grows it into the
    NaN divergence path within a few steps.  For ensembles the spike hits
    every member (the state leaves carry the leading K axis).  With
    ``host``, only that process's spectral columns are scaled."""
    scope = pde.model._scope if hasattr(pde, "model") else pde._scope
    with scope():
        st = pde.state
        if host is None:
            fx = fy = factor
        else:
            mdl = pde.model if hasattr(pde, "model") else pde
            fx = _host_column_mask(mdl, host, st.velx, factor)
            fy = _host_column_mask(mdl, host, st.vely, factor)
        pde.state = st._replace(velx=st.velx * fx, vely=st.vely * fy)
    pde._obs_cache = None


def _host_owned_column(pde, host: int, leaf, step: int = 0) -> int | None:
    """One spectral column (last/pencil axis) owned by process ``host``'s
    devices, hashed from ``step`` within the owned span — computed from
    mesh metadata alone, so every process picks the SAME column and a
    host-scoped bitflip stays a consistent collective dispatch.  ``None``
    when ``host`` owns no columns (caller falls back to the hashed
    default)."""
    from ..parallel.mesh import SPEC, pencil_sharding

    mesh = getattr(pde, "mesh", None)
    n = leaf.shape[-1]
    if mesh is None:
        return None
    s = pencil_sharding(mesh, SPEC, ndim=len(leaf.shape))
    try:
        imap = s.devices_indices_map(tuple(leaf.shape))
    except ValueError:  # uneven dim: replicated layout, host 0 owns all
        imap = None
    if imap is None:
        return 0 if host == 0 else None
    spans = []
    for dev, idx in imap.items():
        if dev.process_index != host:
            continue
        start, stop, _ = idx[-1].indices(n)
        if stop > start:
            spans.append((start, stop))
    if not spans:
        return None
    start, stop = min(spans)
    return start + int(step) * 40503 % (stop - start)


def bitflip_state(pde, step: int, host: int | None = None,
                  member: int | None = None, bit: int | None = None) -> dict:
    """Flip ONE mantissa bit of one spectral coefficient on device — the
    deterministic silent-data-corruption injection
    (``RUSTPDE_FAULT=bitflip@<step>[:host<p>|:member<k>]``).  The flipped
    state is finite and CFL-sane (integrity/digest.default_flip_bit never
    touches exponent or sign), so every loud sentinel — NaN criterion,
    CFL ceiling, watchdogs — stays quiet: only the integrity layer's
    digest audits can see it.  With ``host``, the flipped column is one
    owned by that process's devices (real single-host HBM corruption
    shape); with ``member``, only that ensemble member's leading-axis
    slice is touched (per-member digests localize it).  Returns the flip
    info dict (leaf/index/bit/member/host) for the journal."""
    from ..integrity import flip_state_bit

    scope = pde.model._scope if hasattr(pde, "model") else pde._scope
    mdl = pde.model if hasattr(pde, "model") else pde
    with scope():
        st = pde.state
        name = "temp" if hasattr(st, "temp") else st._fields[0]
        col = None
        if host is not None:
            col = _host_owned_column(mdl, host, getattr(st, name), step=step)
        pde.state, info = flip_state_bit(
            st, step, member=member, col=col, bit=bit
        )
    pde._obs_cache = None
    info["host"] = host
    return info


def _is_root() -> bool:
    try:
        from ..parallel import multihost

        return multihost.is_root()
    except Exception:
        return True


class ResilientRunner:
    """Wrap a model (``Navier2D`` / ``NavierEnsemble`` / any ``Integrate``
    implementer with ``read``/``write`` snapshots) in the full resilience
    harness: cadenced atomic checkpoints, JSONL journal, auto-resume,
    checkpoint-then-exit on SIGTERM/SIGINT, divergence retry with dt
    backoff, and dispatch watchdogs.

    Typical use (examples/navier_rbc_resilient.py)::

        model = Navier2D.new_confined(129, 129, 1e7, 1.0, 2e-3, 1.0, "rbc")
        runner = ResilientRunner(model, max_time=100.0, save_intervall=1.0,
                                 run_dir="data/run1", checkpoint_every_s=300)
        summary = runner.run()   # resumes automatically if run1 has state

    ``run()`` returns a summary dict whose ``outcome`` is ``"done"`` or
    ``"preempted"`` (clean checkpoint written either way) and raises
    :class:`DivergenceError` / :class:`DispatchHang` when recovery is
    impossible."""

    def __init__(
        self,
        pde,
        max_time: float,
        save_intervall: float | None = None,
        *,
        run_dir: str = "data/resilient",
        checkpoint_every_s: float | None = 300.0,
        checkpoint_every_t: float | None = None,
        keep: int = 3,
        max_retries: int = 3,
        dt_backoff: float = 0.5,
        dt_min: float = 0.0,
        respawn_members: bool = False,
        respawn_amp: float = 1e-3,
        respawn_seed: int | None = None,
        dispatch_timeout_s: float | None = None,
        fault: str | None = None,
        spike_factor: float | None = None,
        resume: bool = True,
        max_chunk_steps: int = 1024,
        stability=None,
        io=None,
    ):
        self.pde = pde
        self.max_time = float(max_time)
        self.save_intervall = save_intervall
        self.run_dir = run_dir
        self.checkpoint_every_s = checkpoint_every_s
        self.checkpoint_every_t = checkpoint_every_t
        self.keep = int(keep)
        self.max_retries = int(max_retries)
        self.dt_backoff = float(dt_backoff)
        # hard floor under the compounding divergence backoff AND the
        # governor ladder default — without it repeated retries drive dt
        # toward denormals (each one paying a solver refactorization for a
        # step size that can no longer make progress)
        self.dt_min = float(dt_min)
        self.respawn_members = bool(respawn_members)
        self.respawn_amp = float(respawn_amp)
        self.respawn_seed = respawn_seed
        if dispatch_timeout_s is None:
            env = env_get("RUSTPDE_DISPATCH_TIMEOUT_S", "")
            dispatch_timeout_s = float(env) if env else None
        self.dispatch_timeout_s = dispatch_timeout_s
        # STRICT env validation at construction (utils/faults): a malformed
        # RUSTPDE_FAULT / RUSTPDE_SHARD_CRASH must kill the run before any
        # stepping — a chaos spec that silently never fires reports green
        # while testing nothing
        validate_fault_env()
        self.fault = FaultPlan.from_spec(
            fault if fault is not None else env_get("RUSTPDE_FAULT")
        )
        if spike_factor is None:
            env = env_get("RUSTPDE_SPIKE_FACTOR", "")
            spike_factor = float(env) if env else 50.0
        self.spike_factor = float(spike_factor)
        self.resume = bool(resume)
        self.max_chunk_steps = int(max_chunk_steps)
        # proactive stability governor (utils/governor.py): an explicit
        # StabilityConfig wins; otherwise inherit sentinels the model
        # already has armed (NavierConfig.stability -> set_stability)
        self.stability = (
            stability if stability is not None else getattr(pde, "_stability", None)
        )
        self.governor: StabilityGovernor | None = None
        self._dt0 = float(pde.get_dt())  # governor ladder anchor (pre-resume)
        # overlapped-IO pipeline (utils/io_pipeline.py): defaults ON —
        # async cadence checkpoints + dispatch double-buffering; multihost
        # meshes keep async SHARD writes (per-host writer, commit deferred
        # to the next boundary) but disable the lagged break check
        from ..config import IOConfig

        self.io = io if io is not None else IOConfig()
        self._io: IOPipeline | None = None
        self._async_ckpt = False
        self._overlap = False
        self._sharded = False  # distributed two-phase checkpoint format
        # one deferred sharded commit may be in flight: (snap, path, reason,
        # journal event) — committed at the next chunk boundary
        self._pending_commit: tuple | None = None
        # disk-full containment: once a checkpoint write bottoms out in
        # ENOSPC the run DEGRADES to in-memory rollback only — further
        # disk checkpoints are suppressed (journaled) instead of the
        # writer's sticky failure re-wedging every later submit
        self._ckpt_disabled = False
        self._io_snapshot_s = 0.0  # main-thread seconds staging host snapshots
        self._lock = threading.Lock()  # ckpt-path updates (journal has its own)
        self.journal_path = os.path.join(run_dir, "journal.jsonl")
        # per-event-flushed shared writer (utils/journal): an embedding
        # harness (serve.SimServer) may hand the runner ITS writer so
        # request_* and checkpoint events ride one file — see set_journal
        self._journal_writer: JournalWriter | None = None
        self._journal_owned = True  # close on teardown unless set_journal'd

        # live telemetry (rustpde_mpi_tpu/telemetry): the SLO throughput
        # baseline journaling `perf_degraded` (replaceable — tests inject a
        # fake clock), the cadenced metrics.jsonl dumper (armed per session,
        # root only) and the flight-recorder exit hook disarm callable
        self.slo = _tm.ThroughputMonitor()
        self._slo_last_step = 0
        self._metrics_dumper: MetricsDumper | None = None
        self._exit_disarm = None

        # physics-health streaming (models/stats.py, armed via the model's
        # set_stats): one health future in flight, resolved a boundary
        # later (lag=1 — no fence), exported as gauges + typed journal
        # events with crossing latches (warn once per excursion, re-arm
        # after the signal halves)
        self._stats_health_pending = None
        self._stats_res_latched = False
        self._stats_budget_latched = False
        self._saved_pde_journal = None

        # end-to-end integrity (integrity/): armed when the model carries
        # an IntegrityConfig (set_integrity / RUSTPDE_INTEGRITY=1) —
        # boundary digests streamed with every commit (chain check: the
        # state must arrive at the next chunk unmutated), shadow
        # re-execution audits at the config cadence, verified-snapshot
        # in-memory rollback, and the durable per-device quarantine ledger
        self._integ_prev = None      # (step, digest future) at last commit
        self._integ_verified = None  # (step, snapshot) last audit-verified
        self._integ_chunks = 0       # committed chunks (cadence counter)
        self._integ_ledger = None    # QuarantineLedger, built lazily

        self.step = 0  # global step counter (survives resume via ckpt attrs)
        self.attempt = 0  # divergence retries so far
        self.resumed = False  # set by session(): a checkpoint was restored
        self._interrupt: int | None = None
        self._slow_pending = False
        self._t0 = _time.monotonic()
        self._last_ckpt_wall = self._t0
        self._last_ckpt_time = 0.0
        self._last_ckpt_path: str | None = None  # newest verified/written
        self._prev_handlers: dict = {}
        self._is_ensemble = hasattr(pde, "member_state")

    @classmethod
    def from_config(cls, pde, rcfg, max_time, save_intervall=None, **overrides):
        """Build from a :class:`~rustpde_mpi_tpu.config.ResilienceConfig`
        (``None`` uses the defaults); keyword overrides win.  A shallow
        field copy, NOT ``dataclasses.asdict`` — the nested
        ``StabilityConfig`` must arrive as the dataclass, not a dict."""
        kwargs = (
            {f.name: getattr(rcfg, f.name) for f in dataclasses.fields(rcfg)}
            if rcfg is not None
            else {}
        )
        kwargs.update(overrides)
        return cls(pde, max_time, save_intervall, **kwargs)

    # -- journal -------------------------------------------------------------

    def set_journal(self, writer: JournalWriter) -> None:
        """Adopt an externally-owned journal writer (the serve scheduler's:
        one journal for request_* AND runner events).  The runner then never
        closes it — the owner does."""
        self._journal_writer = writer
        self._journal_owned = False
        self.journal_path = writer.path

    def _journal(self, event: dict) -> None:
        """Append one JSON line to ``<run_dir>/journal.jsonl`` (root only).

        Thread-safe and flushed per event (utils/journal.JournalWriter):
        async checkpoint completions journal from the pipeline worker, and
        a SIGKILL can tear at most the line in flight.  Events carrying
        their own ``step``/``time`` (captured at submit) override the
        defaults, so a write that lands mid-chunk is stamped with the step
        it snapshot."""
        if not _is_root():
            return
        if self._journal_writer is None:
            self._journal_writer = JournalWriter(self.journal_path)
            self._journal_owned = True
        record = {
            "wall_s": round(_time.monotonic() - self._t0, 3),
            "step": self.step,
            "time": round(float(self.pde.get_time()), 9),
            "attempt": self.attempt,
            **event,
        }
        self._journal_writer.append(record)

    def _nu(self):
        """Scalar Nu for the journal: the value for a single run, the
        alive-member mean for an ensemble; None when unavailable."""
        try:
            nu = self.pde.eval_nu()
        except Exception:
            return None
        if self._is_ensemble:
            alive = np.asarray(self.pde.alive())
            nu = np.asarray(nu)
            return float(nu[alive].mean()) if alive.any() else None
        nu = float(nu)
        return nu if np.isfinite(nu) else None

    # -- signals -------------------------------------------------------------

    def _install_signals(self) -> None:
        try:
            for sig in (signal.SIGTERM, signal.SIGINT):
                self._prev_handlers[sig] = signal.signal(sig, self._on_signal)
        except ValueError:  # not the main thread: run un-guarded
            self._prev_handlers = {}

    def _restore_signals(self) -> None:
        for sig, handler in self._prev_handlers.items():
            signal.signal(sig, handler)
        self._prev_handlers = {}

    def _on_signal(self, signum, frame) -> None:
        # defer: the flag is acted on at the next chunk boundary, where the
        # state is at a consistent step (checkpoint-then-exit)
        self._interrupt = signum

    def _root_decides(self, local: bool) -> bool:
        """Root-decides handshake for anything that leads into a collective
        (preemption stop, cadence checkpoint): on a multihost mesh rank 0's
        flag is broadcast so every host takes the same branch — hosts
        evaluating wall clocks or signals locally would disagree and wedge
        the next collective.  Single-host: the local flag.  One shared
        primitive (:func:`~rustpde_mpi_tpu.parallel.multihost.root_decides`)
        — the serve scheduler's handshakes ride the identical code."""
        try:
            from ..parallel import multihost
        except Exception:  # no runtime at all: the local path is the only one
            return bool(local)
        return multihost.root_decides(local)

    def _preempt_agreed(self) -> bool:
        """Preemption stop (a stray local signal on a non-root host is
        ignored; real preemption hits every host)."""
        return self._root_decides(self._interrupt is not None)

    # -- checkpointing -------------------------------------------------------

    def _state_ok(self) -> bool:
        """Never checkpoint a dead state: a NaN single-run state (or an
        all-dead ensemble) must not overwrite the rollback target.  Models
        distinguishing "exit because done" from "exit because dead" (the
        steady-state finder converging is a SUCCESS worth checkpointing)
        expose ``state_healthy``; the break criterion stays ``exit()``."""
        healthy = getattr(self.pde, "state_healthy", None)
        try:
            if healthy is not None:
                return bool(healthy())
            return not self.pde.exit()
        except Exception:
            return False

    @staticmethod
    def _is_enospc(exc) -> bool:
        """True when a write failure's cause chain bottoms out in an
        out-of-space errno (:class:`AsyncWriteError` wraps the worker's
        ``OSError`` as ``__cause__``; h5/shutil re-raises chain through
        ``__context__``)."""
        hops = 0
        while exc is not None and hops < 8:
            if getattr(exc, "errno", None) == _errno.ENOSPC:
                return True
            exc = exc.__cause__ if exc.__cause__ is not None else exc.__context__
            hops += 1
        return False

    def _degrade_checkpoints(self, exc, reason: str) -> None:
        """Disk-full containment: journal ``checkpoint_failed`` WITH the
        errno, consume the writer's sticky failure backlog (later
        submits/drains must not re-raise the wedge just contained), and
        flip ``_ckpt_disabled`` — the run continues on in-memory rollback
        snapshots only.  The last durable checkpoint stays valid; only
        the on-disk chain stops advancing.  Admission-side containment
        (the queue's ``storage_full`` 503) lives in serve/queue.py."""
        self._ckpt_disabled = True
        if self._io is not None:
            try:
                self._io.writer.drain(raise_errors=False)
            except Exception:  # a wedged drain must not mask containment
                pass
            self._io.writer.consume_errors()
        _tm.counter(
            "checkpoints_degraded_total",
            "runs degraded to in-memory rollback after ENOSPC",
        ).inc()
        self._journal(
            {
                "event": "checkpoint_failed",
                "reason": reason,
                "errno": _errno.ENOSPC,
                "error": str(exc) if exc is not None else "no space left on device",
                "degraded": "in_memory_rollback_only",
                "step": self.step,
            }
        )

    def _checkpoint(self, reason: str) -> str | None:
        """Write a rolling checkpoint (root only) and barrier all hosts.

        Single-process runs with ``io.async_checkpoints`` take the
        overlapped path (:meth:`_checkpoint_async`): state fetched to host
        here, serialization/digest/fsync on the pipeline worker.  Edge
        checkpoints (anchor/final/preempt) drain immediately after
        submitting, so their durability and journal ordering match the
        synchronous writer; only cadence checkpoints overlap stepping.

        Multi-controller meshes (and forced ``io.sharded_checkpoints``)
        take the SHARDED two-phase path (:meth:`_checkpoint_sharded`): each
        process writes only its addressable shards and root commits via an
        atomic manifest — the per-host slab IO the gathered writers (which
        fetch the full state via ``np.asarray``) cannot provide.  A write
        failure on ANY host aborts the commit collectively (no manifest),
        so every host sees a clean raise instead of a wedged job."""
        if not self._state_ok():
            self._journal({"event": "checkpoint_skipped", "reason": reason})
            return None
        if self._ckpt_disabled:
            # disk full earlier in the run: in-memory rollback only
            self._journal(
                {"event": "checkpoint_skipped", "reason": reason,
                 "cause": "storage_full"}
            )
            return None
        path = checkpoint.checkpoint_path(self.run_dir, self.step)
        if self._sharded:
            return self._checkpoint_sharded(path, reason)
        if self._async_ckpt and self._io is not None:
            return self._checkpoint_async(path, reason)
        if self._io is not None:
            # a queued background write may still be in flight: settle the
            # directory before this synchronous write + rotation
            try:
                self._io.writer.drain()
            except AsyncWriteError as exc:
                if not self._is_enospc(exc):
                    raise
                self._degrade_checkpoints(exc, reason)
                return None
        t0 = _time.monotonic()
        write_error = None
        if _is_root():
            try:
                if self._is_ensemble:
                    checkpoint.write_ensemble_snapshot(self.pde, path, step=self.step)
                else:
                    checkpoint.write_snapshot(self.pde, path, step=self.step)
                checkpoint.rotate_checkpoints(self.run_dir, self.keep)
            except Exception as exc:  # must not skip the barrier below
                write_error = exc
        try:
            from ..parallel import multihost

            multihost.sync_hosts("rustpde-checkpoint")
        except DispatchHang:
            raise
        except Exception:
            pass
        # every host must agree on failure (root alone raising would leave
        # the others hanging at the next collective)
        if self._root_decides(write_error is not None):
            if self._root_decides(self._is_enospc(write_error)):
                # disk full is CONTAINED, not fatal: every host flips to
                # in-memory-rollback-only together (both branches above
                # are root-broadcast, so the flag stays host-identical)
                self._degrade_checkpoints(write_error, reason)
                return None
            self._journal(
                {"event": "checkpoint_failed", "reason": reason, "error": str(write_error)}
            )
            if write_error is not None:
                raise write_error
            raise RuntimeError("checkpoint write failed on the root host")
        self._last_ckpt_wall = _time.monotonic()
        self._last_ckpt_time = float(self.pde.get_time())
        self._last_ckpt_path = path
        write_s = _time.monotonic() - t0
        _tm.histogram(
            "checkpoint_write_seconds", "serialize+digest+fsync seconds"
        ).observe(write_s)
        _tm.counter("checkpoints_total", "checkpoints written", reason=reason).inc()
        self._journal(
            {
                "event": "checkpoint",
                "reason": reason,
                "path": path,
                "write_s": round(write_s, 3),
                "nu": self._nu(),
            }
        )
        return path

    def _checkpoint_async(self, path: str, reason: str) -> str | None:
        """Overlapped checkpoint: the device sync (host snapshot fetch) and
        the Nu readout happen here, on the boundary state the run needed
        anyway; the expensive part — h5 serialization, the content digest,
        two fsyncs, rotation — runs on the io_pipeline worker while the
        device steps on.  ``_last_ckpt_path`` only advances once the write
        is durably on disk (worker side), and every rollback/resume read
        drains the writer first, so recovery can never target a file that
        is still being written."""
        t0 = _time.monotonic()
        with _tr.span("checkpoint_stage", layer="runner", reason=reason, step=self.step):
            if self._is_ensemble:
                snap = checkpoint.ensemble_snapshot_to_host(self.pde, step=self.step)
            else:
                snap = checkpoint.snapshot_to_host(self.pde, step=self.step)
        snapshot_s = _time.monotonic() - t0
        self._io_snapshot_s += snapshot_s
        _tm.histogram(
            "checkpoint_snapshot_seconds", "main-thread device->host staging"
        ).observe(snapshot_s)
        event = {
            "event": "checkpoint",
            "reason": reason,
            "path": path,
            "async": True,
            "step": self.step,
            "time": round(float(self.pde.get_time()), 9),
            "snapshot_s": round(snapshot_s, 3),
            "nu": self._nu(),
        }

        def work():
            w0 = _time.monotonic()
            try:
                checkpoint.write_host_snapshot(snap, path)
                checkpoint.rotate_checkpoints(self.run_dir, self.keep)
            except BaseException as exc:
                self._journal(
                    {
                        "event": "checkpoint_failed",
                        "reason": reason,
                        "error": str(exc),
                        "step": event["step"],
                        **({"errno": _errno.ENOSPC}
                           if self._is_enospc(exc) else {}),
                    }
                )
                raise
            with self._lock:
                self._last_ckpt_path = path
            write_s = _time.monotonic() - w0
            _tm.histogram(
                "checkpoint_write_seconds", "serialize+digest+fsync seconds"
            ).observe(write_s)
            _tm.counter(
                "checkpoints_total", "checkpoints written", reason=reason
            ).inc()
            self._journal({**event, "write_s": round(write_s, 3)})

        try:
            self._io.submit_write(work, path, nbytes=snap.nbytes)
        except AsyncWriteError as exc:
            # an EARLIER background write failed and surfaced here; a
            # disk-full cause degrades (satellite: the writer path must
            # journal checkpoint_failed{errno} and fall back to
            # in-memory rollback, not wedge every later submit)
            if not self._is_enospc(exc):
                raise
            self._degrade_checkpoints(exc, reason)
            return None
        # cadence clocks restart at SUBMIT time: the snapshot point is what
        # bounds data loss, not when the bytes landed
        self._last_ckpt_wall = _time.monotonic()
        self._last_ckpt_time = float(self.pde.get_time())
        if reason != "cadence":
            # anchor/final/preempt must be durable before the run proceeds
            try:
                self._io.writer.drain()
            except AsyncWriteError as exc:
                if not self._is_enospc(exc):
                    raise
                self._degrade_checkpoints(exc, reason)
                return None
        return path

    def _checkpoint_sharded(self, path: str, reason: str) -> str:
        """Distributed two-phase checkpoint (every host enters together —
        the caller's decision was root-broadcast): fetch THIS host's
        addressable slabs, write+fsync the shard file, barrier, exchange
        digests, root commits the manifest (utils/checkpoint).

        With the pipeline armed, a CADENCE checkpoint overlaps: the shard
        serialization runs on this host's background writer while the
        device steps the next chunk, and the collective commit is deferred
        to the next chunk boundary (:meth:`_commit_pending`) — after a
        local drain, so the barrier only ever sees fsynced shards.  Edge
        checkpoints (anchor/final/preempt) write and commit inline."""
        self._commit_pending()  # at most one deferred commit in flight
        t0 = _time.monotonic()
        with _tr.span("checkpoint_stage", layer="runner", reason=reason, step=self.step):
            snap = checkpoint.sharded_snapshot_to_host(self.pde, step=self.step)
        snapshot_s = _time.monotonic() - t0
        self._io_snapshot_s += snapshot_s
        _tm.histogram(
            "checkpoint_snapshot_seconds", "main-thread device->host staging"
        ).observe(snapshot_s)
        event = {
            "event": "checkpoint",
            "reason": reason,
            "path": path,
            "sharded": snap.shard_count,
            "step": self.step,
            "time": round(float(self.pde.get_time()), 9),
            "snapshot_s": round(snapshot_s, 3),
            "nu": self._nu(),
        }
        if self._async_ckpt and self._io is not None and reason == "cadence":
            self._io.submit_write(
                lambda: checkpoint.write_shard_file(snap, path),
                checkpoint.shard_path(path, snap.shard_index),
                nbytes=snap.nbytes,
            )
            self._pending_commit = (snap, path, reason, dict(event, async_=True))
            self._last_ckpt_wall = _time.monotonic()
            self._last_ckpt_time = float(self.pde.get_time())
            return path
        local_ok = True
        try:
            checkpoint.write_shard_file(snap, path)
        except Exception as exc:
            local_ok = False
            self._journal(
                {"event": "checkpoint_failed", "reason": reason, "error": str(exc),
                 **({"errno": _errno.ENOSPC} if self._is_enospc(exc) else {})}
            )
        self._finish_sharded_commit(snap, path, reason, event, local_ok)
        return path

    def _commit_pending(self) -> None:
        """Settle a deferred sharded cadence commit (every host calls this
        at the same points: each chunk boundary, before any rollback/resume
        checkpoint scan, before the next checkpoint, and at run end).
        Drain-before-barrier: the local writer is drained first, so this
        host's shard is durably on disk before the commit barrier."""
        if self._pending_commit is None:
            return
        snap, path, reason, event = self._pending_commit
        self._pending_commit = None
        local_ok = True
        if self._io is not None:
            try:
                self._io.writer.drain()
            except Exception as exc:
                local_ok = False
                self._journal(
                    {
                        "event": "checkpoint_failed",
                        "reason": reason,
                        "error": str(exc),
                        "step": event["step"],
                        **({"errno": _errno.ENOSPC}
                           if self._is_enospc(exc) else {}),
                    }
                )
        is_async = event.pop("async_", False)
        self._finish_sharded_commit(
            snap, path, reason, dict(event, **({"async": True} if is_async else {})),
            local_ok,
        )

    def _finish_sharded_commit(
        self, snap, path: str, reason: str, event: dict, local_ok: bool
    ) -> None:
        """The collective half: commit (barrier + digest allgather + root
        manifest), rotate on success, journal the ``checkpoint_sharded``
        telemetry (shard count, bytes/host, barrier wait seconds)."""
        w0 = _time.monotonic()
        with _tr.span("checkpoint_commit", layer="runner", step=self.step):
            stats = checkpoint.commit_sharded_snapshot(snap, path, local_ok=local_ok)
        _tm.counter(
            "checkpoint_barrier_seconds_total",
            "seconds waiting at the two-phase commit barrier",
        ).inc(float(stats.get("barrier_s") or 0.0))
        if not stats["ok"]:
            if local_ok:
                # the failing host already journaled its local cause; only
                # hosts learning of the abort here add an event (one
                # failure = one checkpoint_failed line per host)
                self._journal(
                    {
                        "event": "checkpoint_failed",
                        "reason": reason,
                        "error": "sharded checkpoint aborted (a host failed "
                        "its shard write); no manifest committed",
                        "step": event.get("step", self.step),
                    }
                )
            raise checkpoint.CheckpointError(
                path,
                "sharded checkpoint aborted: a host failed its shard write "
                "(no manifest committed; the previous checkpoint is intact)",
            )
        if _is_root():
            checkpoint.rotate_checkpoints(self.run_dir, self.keep)
        _tm.counter("checkpoints_total", "checkpoints written", reason=reason).inc()
        with self._lock:
            self._last_ckpt_path = path
        self._last_ckpt_wall = _time.monotonic()
        self._last_ckpt_time = event.get("time", float(self.pde.get_time()))
        self._journal(
            {
                **event,
                "commit_s": round(_time.monotonic() - w0, 3),
                "checkpoint_sharded": {
                    "shards": stats["shards"],
                    "bytes_host": stats["bytes_host"],
                    "bytes_total": stats["bytes_total"],
                    "barrier_s": stats["barrier_s"],
                },
            }
        )

    def _pick_checkpoint(self) -> str | None:
        """Newest valid checkpoint, chosen by ROOT and broadcast: each host
        scanning its own view of run_dir could disagree (filesystem
        visibility skew; a host-local run_dir would be outright divergent),
        and a host restoring a different step than its peers wedges the
        next collective.  The broadcast carries the step number — the
        step-encoded filename is the cross-host contract (multihost
        resume/rollback requires run_dir on shared storage)."""
        # an uncommitted sharded cadence checkpoint must commit (or abort)
        # before any scan: rollback/resume must never race the two-phase
        # window — drain-before-barrier, then manifest, then read
        self._commit_pending()
        if self._io is not None:
            # never read/scan past an in-flight background write: rollback
            # and resume must see a settled directory (a failed write
            # re-raises here, where the caller can still decide).  A
            # disk-full failure degrades instead — the scan proceeds on
            # whatever is durably on disk (the failed file never rotated
            # in, so the newest VALID checkpoint is still correct)
            try:
                self._io.writer.drain()
            except AsyncWriteError as exc:
                if not self._is_enospc(exc):
                    raise
                self._degrade_checkpoints(exc, "scan")
        if _single_process():
            return checkpoint.latest_checkpoint(self.run_dir)
        from ..parallel import multihost

        step = -1
        if _is_root():
            path = checkpoint.latest_checkpoint(self.run_dir)
            if path is not None:
                step = int(checkpoint.read_attrs(path).get("step", -1))
        step = int(multihost.broadcast(np.int64(step)))
        if step < 0:
            return None
        return checkpoint.checkpoint_path(self.run_dir, step)

    def _maybe_resume(self) -> bool:
        if not self.resume:
            return False
        path = self._pick_checkpoint()
        if path is None:
            return False
        # latest_checkpoint digest-verified the file (and read() verifies
        # again); the attrs lookup can skip the hash pass
        attrs = checkpoint.read_attrs(path)
        self.pde.read(path)
        self.step = int(attrs.get("step", 0))
        self._restore_dt(attrs)
        self._last_ckpt_time = float(self.pde.get_time())
        self._last_ckpt_path = path
        self._journal({"event": "resumed", "path": path})
        return True

    def _restore_dt(self, attrs: dict) -> None:
        """Restore the step size the checkpoint was written at: a run whose
        dt was backed off after a divergence and then got preempted must NOT
        resume at the original (diverging) dt — that would re-diverge and
        burn a fresh retry budget every preemption cycle."""
        dt = attrs.get("dt")
        if dt is None or not hasattr(self.pde, "set_dt"):
            return
        dt = float(dt)
        if dt != float(self.pde.get_dt()):
            self.pde.set_dt(dt)
            self._journal({"event": "dt_restored", "dt": dt})

    # -- dispatch (fault injection + watchdog) -------------------------------

    def _update(self, pde, n: int):
        """One watchdog-guarded dispatch; returns the model's
        :class:`~rustpde_mpi_tpu.utils.governor.ChunkStatus` when stability
        sentinels are armed (None otherwise)."""

        def work():
            if self._slow_pending:
                self._slow_pending = False
                _time.sleep(
                    max(2.0 * (self.dispatch_timeout_s or 0.0), 1.0)
                )
            if hasattr(pde, "update_n"):
                result = pde.update_n(n)
            else:
                result = None
                for _ in range(n):
                    pde.update()
            # force the device work into the deadline window ONLY when a
            # watchdog is armed: update_n dispatches asynchronously and the
            # hang materializes at the sync — but an unconditional fence
            # here would serialize the overlapped pipeline (the whole point
            # of dispatch double-buffering is to keep the queue full)
            if self.dispatch_timeout_s:
                state = getattr(pde, "state", None)
                if state is not None:
                    import jax

                    jax.block_until_ready(state)
            return result

        with _tr.span("dispatch", layer="runner", steps=n, step=self.step):
            return call_with_watchdog(
                work, self.dispatch_timeout_s, label=f"update_n({n}) @ step {self.step}"
            )

    def _advance(self, pde, n: int) -> None:
        """Advance n steps in sub-chunks of at most ``max_chunk_steps``, so
        a run launched without save boundaries (``save_intervall=None``
        would otherwise dispatch the WHOLE horizon as one chunk) still hands
        control back at a bounded cadence for signals and checkpoints.  The
        early break is root-decided, so every host stops after the same
        sub-chunk; returning with fewer steps advanced is safe — the
        chunked driver re-reads ``pde.get_time()`` every iteration.

        With the governor active every sub-chunk's sentinel status is fed
        through it here: a ``pre_divergence`` catch was already rolled back
        in memory by ``update_n``, so the governor's dt/member decision is
        applied and the loop returns (the driver re-plans at the new dt and
        the same sim-time — that IS the retry)."""
        cap = self.max_chunk_steps if self.max_chunk_steps > 0 else n
        if (
            self._overlap
            and self.governor is not None
            and hasattr(pde, "update_n_pending")
        ):
            return self._advance_lagged(pde, n, cap)
        while n > 0:
            k = min(n, cap)
            rec = self._integ_predispatch(pde, self.step)
            dt_before = pde.get_dt()
            status = self._update(pde, k)
            if status is not None and self.governor is not None:
                committed = self._govern(pde, status)
                if committed:
                    self.step += k
                    n -= k
                    _tm.counter("runner_steps_total", "committed simulation steps").inc(k)
                    if not self._integ_commit(pde, k, rec):
                        return  # integrity rollback: driver re-plans
                else:
                    self._integ_drop()
                if not committed or pde.get_dt() != dt_before:
                    # rolled back (retry at the governor's new dt) or dt
                    # adjusted: the remaining step budget was planned at the
                    # old dt — hand control back so the driver re-plans
                    return
            elif status is not None and status.pre_divergence:
                # sentinels armed but no governor: leave the latch for the
                # reactive path (exit() fires at the chunk boundary)
                self._integ_drop()
                return
            else:
                self.step += k
                n -= k
                _tm.counter("runner_steps_total", "committed simulation steps").inc(k)
                if not self._integ_commit(pde, k, rec):
                    return  # integrity rollback: driver re-plans
            if n > 0 and self._root_decides(self._interrupt is not None):
                return  # integrate()'s on_chunk acts at the boundary

    def _advance_lagged(self, pde, n: int, cap: int) -> None:
        """Governed sub-chunking with dispatch double-buffering — the lag=1
        sentinel contract: sub-chunk i+1 is dispatched, from chunk i's
        PROVISIONAL end state, before chunk i's sentinel scalars are
        fetched, so the device queue stays full while the governor reads
        chunk i.  Exactness is preserved by construction:

        * the hard CFL ceiling lives ON DEVICE (the in-scan early exit), so
          when chunk i trips, the speculative chunk steps a finite state
          whose work is simply discarded — ``resolve()`` of chunk i
          restores the chunk-i start snapshot, and the in-flight pending
          is ``discard()``-ed unresolved,
        * a dt adjustment decided from chunk i lands after chunk i+1 was
          dispatched at the old dt: that chunk is valid physics and is
          committed — the governor rescales its stale-dt CFL
          (StabilityGovernor.on_chunk) — and control returns to the driver
          to re-plan at the new dt.

        ``self.step`` counts only resolved-and-committed chunks, so
        checkpoint filenames, journal stamps and fault-injection points are
        identical to the synchronous path."""
        # each in-flight entry: (PendingChunkStatus, k, integrity record,
        # end-of-chunk digest future).  The digest of a chunk's PROVISIONAL
        # end state is dispatched right behind the chunk itself — by its
        # commit (one iteration later) the uint32 is long on host, so the
        # lag=1 device-queue contract survives the integrity layer intact.
        # ``disp_step`` tracks the DISPATCH frontier (self.step lags it by
        # the in-flight chunk) so chain-check steps line up.
        pending: tuple | None = None
        disp_step = self.step
        while n > 0 or pending is not None:
            nxt = None
            if n > 0:
                k = min(n, cap)
                rec = self._integ_predispatch(pde, disp_step)
                chunk = self._update_pending(pde, k)
                live = (
                    pde.state_digest_async() if rec is not None else None
                )
                nxt = (chunk, k, rec, live)
                disp_step += k
                n -= k
            if pending is not None:
                chunk, kprev, rec_p, live_p = pending
                dt_before = pde.get_dt()
                status = self._resolve_pending(chunk, kprev)
                committed = self._govern(pde, status)
                if committed:
                    self.step += kprev
                    _tm.counter("runner_steps_total", "committed simulation steps").inc(kprev)
                    if not self._integ_commit(pde, kprev, rec_p, live=live_p):
                        # integrity rollback: the speculative chunk stepped
                        # a corrupt state — drop it unresolved
                        if nxt is not None:
                            nxt[0].discard()
                        return
                if not committed:
                    # chunk kprev rolled back in memory (retry/kill/giveup):
                    # the speculative chunk stepped a doomed state — drop it
                    # unresolved and let the driver re-plan
                    self._integ_drop()
                    if nxt is not None:
                        nxt[0].discard()
                    return
                if pde.get_dt() != dt_before:
                    # dt adjusted: settle the in-flight old-dt chunk (valid
                    # physics; the governor rescales its stale-dt CFL), then
                    # hand back so the driver re-plans at the new dt
                    if nxt is not None:
                        chunk2, k2, rec2, live2 = nxt
                        status2 = self._resolve_pending(chunk2, k2)
                        if self._govern(pde, status2):
                            self.step += k2
                            _tm.counter(
                                "runner_steps_total", "committed simulation steps"
                            ).inc(k2)
                            self._integ_commit(pde, k2, rec2, live=live2)
                        else:
                            self._integ_drop()
                    return
            pending = nxt
            if (
                pending is not None
                and n > 0
                and self._root_decides(self._interrupt is not None)
            ):
                n = 0  # interrupt: settle the in-flight chunk, then return

    def _update_pending(self, pde, k: int):
        """Watchdog-guarded DISPATCH of one deferred-commit sentinel chunk
        (enqueue only — the matching sync point is :meth:`_resolve_pending`,
        which carries its own watchdog)."""

        def work():
            if self._slow_pending:
                self._slow_pending = False
                _time.sleep(max(2.0 * (self.dispatch_timeout_s or 0.0), 1.0))
            return pde.update_n_pending(k)

        with _tr.span("dispatch_pending", layer="runner", steps=k, step=self.step):
            return call_with_watchdog(
                work,
                self.dispatch_timeout_s,
                label=f"update_n_pending({k}) @ step {self.step}",
            )

    def _resolve_pending(self, chunk, k: int):
        """Watchdog-guarded resolve: a wedged device materializes here, at
        the sentinel fetch, instead of at the dispatch."""
        with _tr.span("resolve", layer="runner", steps=k, step=self.step):
            return call_with_watchdog(
                chunk.resolve,
                self.dispatch_timeout_s,
                label=f"resolve({k}) @ step {self.step}",
            )

    def _govern(self, pde, status) -> bool:
        """Feed one chunk's sentinel status through the governor and apply
        its decision; returns True when the chunk was committed (state
        advanced), False when it was rolled back in memory."""
        gov = self.governor
        decision = gov.on_chunk(status, step=self.step)
        # live governor gauges: the host-side sentinel scalars the chunk
        # already fetched — never an extra device transfer
        _tm.gauge("governor_cfl", "chunk-max advective CFL").set(status.cfl_max)
        _tm.gauge("governor_rung", "dt-ladder rung index").set(gov.rung)
        _tm.gauge("governor_dt", "current governed dt").set(status.dt)
        if status.pre_divergence:
            _tm.counter(
                "runner_pre_divergence_total", "CFL-ceiling sentinel catches"
            ).inc()
        self._journal(
            {
                "event": "cfl",
                "cfl_max": status.cfl_max,
                "ke": status.ke,
                "ke_growth_max": status.ke_growth_max,
                "div_max": status.div_max,
                "dt": status.dt,
                "rung": gov.rung,
                "pre_divergence": status.pre_divergence,
            }
        )
        if status.pre_divergence:
            self._journal(
                {
                    "event": "pre_divergence",
                    "cfl_max": status.cfl_max,
                    "dt": status.dt,
                    "steps_done": status.steps_done,
                    "pinned": list(status.pinned) if status.pinned else None,
                }
            )
            if decision.action == "retry":
                pde.set_dt(decision.dt)
                _tm.counter("runner_dt_adjust_total", "governor dt changes").inc()
                self._journal(
                    {
                        "event": "dt_adjust",
                        "dt": decision.dt,
                        "rung": gov.rung,
                        "reason": decision.reason,
                    }
                )
                pde.clear_pre_divergence()
                return False
            if decision.action == "kill_members":
                pde.mark_dead(decision.members)
                self._journal(
                    {
                        "event": "member_killed",
                        "members": list(decision.members),
                        "reason": decision.reason,
                    }
                )
                if self.respawn_members and hasattr(pde, "respawn_dead"):
                    respawned = pde.respawn_dead(
                        amp=self.respawn_amp, seed=self._respawn_seed_arg()
                    )
                    self._journal({"event": "respawn", "respawned": respawned})
                pde.clear_pre_divergence()
                return False
            # give_up: the ladder is exhausted — leave the latch set so
            # integrate() returns "break" and the reactive checkpoint
            # rollback (which may shrink dt below the ladder) takes over
            self._journal({"event": "governor_giveup", "reason": decision.reason})
            return False
        if decision.action == "adjust":
            pde.set_dt(decision.dt)
            _tm.counter("runner_dt_adjust_total", "governor dt changes").inc()
            self._journal(
                {
                    "event": "dt_adjust",
                    "dt": decision.dt,
                    "rung": gov.rung,
                    "reason": decision.reason,
                }
            )
        return True

    # -- end-to-end integrity (integrity/) ------------------------------------

    def _integrity_on(self, pde) -> bool:
        return bool(getattr(pde, "integrity_armed", False))

    def _integrity_ledger(self):
        if self._integ_ledger is None:
            from ..integrity import QuarantineLedger

            cfg = getattr(self.pde, "integrity_config", None)
            self._integ_ledger = QuarantineLedger(
                self.run_dir,
                strikes=getattr(cfg, "strikes", 2),
                strike_ttl_s=getattr(cfg, "strike_ttl_s", 3600.0),
            )
        return self._integ_ledger

    def _integ_device(self, host: int | None = None) -> str:
        """Ledger/journal device key: ``<platform>:<id>@proc<p>`` — the
        localized host's first device when the audit could attribute the
        corruption, this process's first local device otherwise."""
        try:
            import jax

            if host is not None:
                for d in jax.devices():
                    if getattr(d, "process_index", 0) == host:
                        return f"{d.platform}:{d.id}@proc{host}"
            d = jax.local_devices()[0]
            return f"{d.platform}:{d.id}@proc{getattr(d, 'process_index', 0)}"
        except Exception:
            return "unknown:0@proc0"

    def _integ_predispatch(self, pde, start_step: int):
        """Chunk-start integrity bookkeeping: anchor the first verified
        snapshot (the IC, or whatever a digest-verified restore installed),
        stream the chunk-start digest for the boundary chain check, and
        retain the chunk-start state copy when this chunk is audit-due.
        Returns the record :meth:`_integ_commit` consumes, or None."""
        if not self._integrity_on(pde):
            return None
        cad = max(1, int(pde.integrity_config.resolved_cadence()))
        due = (self._integ_chunks + 1) % cad == 0
        snap = None
        if due or self._integ_verified is None:
            snap = pde.integrity_snapshot()
            if self._integ_verified is None:
                self._integ_verified = (start_step, snap)
        start_fut = pde.state_digest_async()
        return (start_step, start_fut, snap if due else None, pde.get_dt())

    def _integ_commit(self, pde, k: int, rec, live=None) -> bool:
        """Commit-side integrity hook: stream the end-of-chunk digest,
        chain-check EVERY boundary (the chunk-start digest must bit-equal
        the previous commit's — corruption of the state at rest between
        chunks is invisible to a shadow re-execution, which would
        faithfully reproduce it), and at the audit cadence re-execute the
        chunk from its retained start copy and compare (``shadow``).
        Returns False when a mismatch was contained by an in-memory
        rollback — the caller hands control back so the driver re-plans
        from the restored sim-time."""
        if rec is None:
            return True
        start_step, start_fut, snap, disp_dt = rec
        prev = self._integ_prev
        if live is None:
            with _tr.span("integrity_digest", layer="runner", step=self.step):
                live = pde.state_digest_async()
        self._integ_prev = (self.step, live)
        self._integ_chunks += 1
        checks = {}
        if prev is not None and prev[0] == start_step:
            # both futures were dispatched at least one chunk ago — these
            # resolves fetch long-materialized uint32 scalars, no fence
            checks["chain"] = (
                np.asarray(prev[1].result()),  # lint-ok: RPD005 replicated uint32 digest scalar
                np.asarray(start_fut.result()),  # lint-ok: RPD005 replicated uint32 digest scalar
            )
        if snap is not None and pde.get_dt() == disp_dt:
            # a governor dt change between dispatch and commit would make
            # the shadow re-execution run at the wrong dt — skip it for
            # this chunk (the chain check above still ran); the driver is
            # about to re-plan anyway
            with _tr.span("integrity_shadow", layer="runner", steps=k, step=self.step):
                d_shadow = np.asarray(  # lint-ok: RPD005 digest scalar
                    pde.shadow_digest_async(snap, k).result()
                )
            checks["shadow"] = (
                d_shadow,
                np.asarray(live.result()),  # lint-ok: RPD005 replicated uint32 digest scalar
            )
        failed = {c: p for c, p in checks.items() if not np.array_equal(*p)}
        if failed:
            return self._integ_contain(pde, k, rec, failed)
        if snap is not None:
            # full audit passed: the end state becomes the new verified
            # in-memory rollback target
            self._integ_verified = (self.step, pde.integrity_snapshot())
            _tm.counter(
                "runner_integrity_audit_total", "shadow audits passed"
            ).inc()
            self._journal({
                "event": "integrity_audit",
                "result": "ok",
                "chunk_steps": k,
                "checks": sorted(checks),
                "digest": [int(x) for x in
                           np.asarray(live.result()).reshape(-1)],  # lint-ok: RPD005 replicated uint32 digest
            })
        return True

    def _integ_contain(self, pde, k: int, rec, failed) -> bool:
        """Containment: journal the typed mismatch, charge a ledger strike
        (root-decided), roll back to the last digest-verified snapshot —
        or raise :class:`~rustpde_mpi_tpu.integrity.IntegrityError` when
        no verified snapshot exists or the device just crossed the
        quarantine threshold (the serve scheduler re-carves around it)."""
        from ..integrity import IntegrityError

        start_step = rec[0]
        check = "chain" if "chain" in failed else "shadow"
        want, got = failed[check]
        members = None
        if got.ndim:  # ensemble (k,) digests localize the corrupted member
            members = [int(i) for i in np.flatnonzero(got != want)]
        verified = self._integ_verified
        host = None
        if (
            check == "chain"
            and rec[2] is not None
            and verified is not None
            and verified[0] == start_step
        ):
            # clean and corrupt copies of the SAME step exist — per-host
            # masked digests attribute the corrupted pencil column
            host = self._integ_localize_host(pde, rec[2], verified[1])
        device = self._integ_device(host)
        _tm.counter(
            "runner_integrity_mismatch_total", "digest audit mismatches"
        ).inc()
        _tr.instant("integrity_mismatch", check=check, step=self.step)
        self._journal({
            "event": "integrity_mismatch",
            "check": check,
            "chunk_steps": k,
            "start_step": start_step,
            "members": members,
            "device": device,
        })
        newly = False
        if _is_root():
            newly = self._integrity_ledger().strike(
                device, step=self.step, detail=check
            )
        # the raise below must be collectively consistent — broadcast
        # root's threshold verdict like every other pre-collective decision
        newly = self._root_decides(newly)
        if newly:
            self._journal({
                "event": "device_quarantined",
                "device": device,
                "strikes": self._integrity_ledger().strikes_for(device)
                if _is_root() else None,
            })
        self._integ_prev = None
        member = members[0] if members else None
        if verified is None or newly:
            raise IntegrityError(
                f"digest {check} audit failed at step {self.step} and "
                + ("the device crossed the quarantine threshold" if newly
                   else "no verified snapshot exists to roll back to"),
                check=check, step=self.step, chunk_steps=k,
                member=member, device=device,
            )
        v_step, v_snap = verified
        pde.integrity_restore(v_snap)
        self.step = v_step
        self._slo_last_step = min(self._slo_last_step, v_step)
        _tm.counter(
            "runner_integrity_rollback_total", "in-memory integrity rollbacks"
        ).inc()
        self._journal({"event": "integrity_rollback", "to_step": v_step})
        return False

    def _integ_localize_host(self, pde, snap_corrupt, snap_clean):
        """Attribute an at-rest corruption to the owning process: digest
        each host's pencil columns of the corrupt and clean copies (mask
        built from mesh metadata — collectively consistent) and return the
        process whose masked digests differ.  None when unattributable."""
        try:
            import jax

            nproc = jax.process_count()
        except Exception:
            return None
        if nproc <= 1:
            return 0
        mdl = pde.model if hasattr(pde, "model") else pde
        scope = mdl._scope
        for h in range(nproc):
            def masked(st, h=h):
                with scope():
                    return jax.tree.map(
                        lambda x: x
                        * _host_column_mask(mdl, h, x, 1.0, miss=0.0),
                        st,
                    )

            dc = np.asarray(  # lint-ok: RPD005 replicated digest scalar
                pde.digest_of_async(masked(snap_corrupt["state"])).result()
            )
            dv = np.asarray(  # lint-ok: RPD005 replicated digest scalar
                pde.digest_of_async(masked(snap_clean["state"])).result()
            )
            if not np.array_equal(dc, dv):
                return h
        return None

    def _integ_drop(self) -> None:
        """A chunk was rolled back in memory (governor retry, sentinel
        latch): the streamed digest chain no longer describes the live
        state — restart it at the next commit.  The verified snapshot
        STAYS valid (it is a committed, audited state)."""
        self._integ_prev = None

    def _dispatch(self, pde, n: int) -> None:
        fault = self.fault
        fire_at = None
        if (
            fault is not None
            and not fault.fired
            and (fault.gang is None or fault.bound_gang == fault.gang)
        ):
            # a GANG-scoped plan is consumed only while its gang campaign
            # is bound (the serve scheduler's bind_gang at open): the step
            # threshold crossing during some other bucket's campaign must
            # not burn the trigger as a silent no-op.  If the matching
            # campaign opens after the threshold already passed, the plan
            # fires on its first gang dispatch instead — still
            # collectively aligned, because the gang binding verdict was
            # root-broadcast at campaign open.
            if self.step < fault.step <= self.step + n:
                fire_at = fault.step
            elif fault.gang is not None and fault.step <= self.step:
                fire_at = self.step
        if fire_at is not None:
            pre = fire_at - self.step
            if pre > 0:
                self._advance(pde, pre)
            if self.step != fire_at:
                return  # pre-advance stopped early (signal); fire later
            fault.fired = True
            _tr.instant("fault_injected", kind=fault.kind, step=self.step)
            row = {"event": "fault_injected", "kind": fault.kind,
                   "host": fault.host}
            if fault.gang is not None:
                row["gang"] = fault.gang
                row["member"] = fault.member
            self._journal(row)
            if fault.kind == "nan":
                # host-scoped or not, EVERY process dispatches the same
                # (masked) poison computation — collective consistency
                poison_state(pde, host=fault.host)
                return  # run is over either way; exit() fires at the boundary
            if fault.kind == "kill":
                if fault.host is None and fault.gang is None:
                    os.kill(os.getpid(), signal.SIGTERM)
                elif fault.scoped_here():
                    # hard single-host (or gang-member) death, no
                    # checkpoint-then-exit: the survivors wedge at the next
                    # collective, which the sync watchdog — or the gang
                    # barrier watchdog — converts into a structured hang
                    os.kill(os.getpid(), signal.SIGKILL)
            elif fault.kind == "slow":
                if fault.scoped_here():
                    self._slow_pending = True
            elif fault.kind == "spike":
                # finite incipient blow-up: stepping continues below, so the
                # sentinels (or, ungoverned, the NaN criterion) see it
                spike_state(pde, self.spike_factor, host=fault.host)
                # a LOUD intentional mutation — restart the digest chain so
                # the integrity layer doesn't flag physics it can see coming
                self._integ_drop()
            elif fault.kind == "bitflip":
                # one silent mantissa flip: finite, CFL-sane, invisible to
                # every loud sentinel.  Stepping continues below, and the
                # digest chain is deliberately NOT reset — the injection
                # simulates corruption the runner does not know about, and
                # only an armed integrity audit may catch it
                info = bitflip_state(
                    pde, fire_at, host=fault.host, member=fault.only_member
                )
                self._journal({
                    "event": "bitflip_injected",
                    **{kk: vv for kk, vv in info.items() if kk != "index"},
                    "index": list(info["index"]),
                })
            rem = n - pre
            if rem > 0:
                self._dispatch(pde, rem)
            return
        self._advance(pde, n)

    def _on_chunk(self, pde) -> bool:
        # settle a deferred sharded commit FIRST (collective; the pending
        # flag was set at a root-broadcast cadence decision, so every host
        # is here together) — this is where the overlapped shard write
        # rejoins the two-phase protocol, one chunk after its submit
        self._commit_pending()
        # boundary telemetry: feed the SLO throughput baseline the steps
        # committed since the previous boundary (host-side counters only);
        # a regression below the rolling baseline journals the typed
        # perf_degraded event — observability feeding back into robustness
        delta = self.step - self._slo_last_step
        self._slo_last_step = self.step
        degraded = self.slo.record(delta)
        if degraded is not None:
            _tm.counter(
                "runner_perf_degraded_total", "SLO throughput regressions"
            ).inc()
            _tr.instant("perf_degraded", **degraded)
            self._journal({"event": "perf_degraded", **degraded})
            # observability closing the loop on robustness: the FIRST
            # perf_degraded per process triggers a one-shot jax.profiler
            # capture of the slow window (telemetry/compile_log.py) — the
            # profile of the regression lands next to the row flagging it
            from ..telemetry import compile_log as _cl

            capture = _cl.capture_on_perf_degraded(self.run_dir)
            if capture is not None:
                self._journal({"event": "profile_capture", **capture})
        if self._metrics_dumper is not None:
            self._metrics_dumper.maybe_dump(step=self.step)
        self._stats_boundary()
        if self._preempt_agreed():
            return True  # integrate() returns "stopped"; run() checkpoints
        due = False
        if self.checkpoint_every_s is not None:
            due = _time.monotonic() - self._last_ckpt_wall >= self.checkpoint_every_s
        if not due and self.checkpoint_every_t is not None:
            due = (
                pde.get_time() - self._last_ckpt_time
                >= self.checkpoint_every_t - pde.get_dt() / 2.0
            )
        # the wall-clock part of `due` is host-local (clocks drift, root pays
        # the write time) but _checkpoint enters a collective barrier, so the
        # decision must be root's
        if self._root_decides(due):
            self._checkpoint("cadence")
        return False

    # -- physics-health streaming (models/stats.py) ---------------------------

    def _stats_boundary(self) -> None:
        """Per-boundary health streaming for a stats-armed model: resolve
        the PREVIOUS boundary's health future (lag=1 — by now the scalars
        are long since on host, so this fences nothing), export the gauges
        and the threshold-crossing journal events, then dispatch a fresh
        readout.  Every host dispatches (the readout is a collective
        program on a mesh); only root journals."""
        if not getattr(self.pde, "stats_armed", False):
            self._stats_health_pending = None
            return
        fut = self._stats_health_pending
        self._stats_health_pending = None
        if fut is not None:
            try:
                self._stats_health_report(fut.result())
            except Exception:
                pass  # health telemetry must never kill the run
        try:
            self._stats_health_pending = self.pde.stats_health_async()
        except Exception:
            self._stats_health_pending = None

    def _stats_health_report(self, vals) -> None:
        """Gauges + typed events from one resolved health vector (ensemble
        vectors reduce as the max over members — the worst member is the
        one the alert is about)."""
        from ..models.stats import HEALTH_NAMES

        arrs, d = {}, {}
        for name, v in zip(HEALTH_NAMES, vals):
            arr = np.asarray(v, dtype=np.float64).reshape(-1)  # lint-ok: RPD005 health futures resolve to host numpy scalars
            arrs[name] = arr
            # worst-member reduction: BL point counts are a LOW-is-bad
            # signal (too few grid points in the layer), everything else
            # is HIGH-is-bad — both reduce to the worst member
            red = np.min if name.startswith("bl_") else np.max
            d[name] = float(red(arr)) if arr.size else 0.0
        if d["samples"] < 1.0:
            return  # nothing accumulated yet — every readout would be 0
        # the budget alert must be SELF-CONSISTENT: every budget field in
        # the event comes from the one worst member (argmax nu_residual),
        # not a per-field max that mixes members into numbers whose own
        # plate/flux gap would not reproduce the reported residual
        worst_m = (
            int(arrs["nu_residual"].argmax()) if arrs["nu_residual"].size else 0
        )
        budget = {
            name: float(arrs[name][worst_m])
            for name in (
                "nu_residual", "ke_residual",
                "nu_plate_avg", "nu_flux_avg", "samples",
            )
        }
        tails = {
            ("temp", "x"): d["tail_t_x"],
            ("temp", "y"): d["tail_t_y"],
            ("ux", "x"): d["tail_ux_x"],
            ("ux", "y"): d["tail_ux_y"],
            ("uy", "x"): d["tail_uy_x"],
            ("uy", "y"): d["tail_uy_y"],
        }
        for (field, axis), val in tails.items():
            _tm.gauge(
                "stats_tail_energy_fraction",
                "energy fraction in the top third of the ortho spectrum",
                field=field,
                axis=axis,
            ).set(val)
        _tm.gauge(
            "stats_bl_points", "grid points inside the boundary layer",
            layer="thermal",
        ).set(d["bl_thermal_pts"])
        _tm.gauge(
            "stats_bl_points", "grid points inside the boundary layer",
            layer="viscous",
        ).set(d["bl_visc_pts"])
        _tm.gauge(
            "stats_budget_residual", "budget-closure residual", budget="ke"
        ).set(d["ke_residual"])
        _tm.gauge(
            "stats_budget_residual", "budget-closure residual", budget="nu"
        ).set(d["nu_residual"])
        _tm.gauge("stats_samples", "in-scan stats samples accumulated").set(
            d["samples"]
        )
        eng = self.pde.stats_engine
        tail_max = max(tails.values())
        worst = max(tails, key=tails.get)
        if tail_max > eng.tail_warn:
            if not self._stats_res_latched:
                self._stats_res_latched = True
                _tm.counter(
                    "stats_resolution_warnings_total",
                    "spectral-tail under-resolution warnings",
                ).inc()
                self._journal(
                    {
                        "event": "resolution_warning",
                        "field": worst[0],
                        "axis": worst[1],
                        "tail_fraction": tail_max,
                        "threshold": eng.tail_warn,
                        "samples": d["samples"],
                    }
                )
        elif tail_max < 0.5 * eng.tail_warn:
            self._stats_res_latched = False
        if d["nu_residual"] > eng.budget_warn and d["samples"] >= 2:
            if not self._stats_budget_latched:
                self._stats_budget_latched = True
                _tm.counter(
                    "stats_budget_drift_total",
                    "Nu budget-closure drift warnings",
                ).inc()
                self._journal(
                    {
                        "event": "budget_drift",
                        "member": worst_m,
                        "nu_residual": budget["nu_residual"],
                        "ke_residual": budget["ke_residual"],
                        "nu_plate_avg": budget["nu_plate_avg"],
                        "nu_flux_avg": budget["nu_flux_avg"],
                        "threshold": eng.budget_warn,
                        "samples": budget["samples"],
                    }
                )
        elif d["nu_residual"] < 0.5 * eng.budget_warn:
            self._stats_budget_latched = False

    # -- divergence recovery -------------------------------------------------

    def _respawn_seed_arg(self):
        """Seed handed to ``respawn_dead``: the config-carried campaign seed
        (folded with step/attempt so every respawn draws fresh-but-
        reproducible noise), the ensemble's own carried stream (``None``
        lets it use it), or the legacy step+attempt fallback."""
        if self.respawn_seed is not None:
            return (int(self.respawn_seed), self.step, self.attempt)
        if getattr(self.pde, "respawn_seed", None) is not None:
            return None
        return self.step + self.attempt

    def _dt_trajectory(self) -> list:
        """Every journaled dt change as ``(event, step, dt)`` — the evidence
        trail :class:`DivergenceError` reports when retries are exhausted."""
        traj = []
        for rec in read_journal(self.journal_path, on_error="skip"):
            dt = rec.get("dt")
            if dt is not None and rec.get("event") in (
                "start",
                "dt_restored",
                "dt_adjust",
                "retry",
                "divergence",
            ):
                traj.append((rec["event"], rec.get("step"), dt))
        return traj

    def _rollback(self) -> None:
        _tm.counter(
            "runner_rollbacks_total", "reactive checkpoint rollbacks"
        ).inc()
        _tr.instant("rollback", step=self.step, attempt=self.attempt)
        path = self._pick_checkpoint()
        if path is None:
            raise DivergenceError(
                f"diverged at step {self.step} with no valid checkpoint in "
                f"{self.run_dir!r} to roll back to; journaled dt trajectory: "
                f"{self._dt_trajectory()}"
            )
        attrs = checkpoint.read_attrs(path)  # latest_checkpoint verified it
        self.pde.read(path)
        self.step = int(attrs.get("step", 0))
        # the restored state predates everything the integrity layer
        # tracked: drop the digest chain AND the verified snapshot (it may
        # lie in the rolled-back future) — the next chunk re-anchors
        self._integ_prev = None
        self._integ_verified = None
        self._slo_last_step = min(self._slo_last_step, self.step)
        if hasattr(self.pde, "clear_pre_divergence"):
            # the restored checkpoint predates any latched sentinel catch
            self.pde.clear_pre_divergence()
        # NOTE: deliberately no _restore_dt here — backoff compounds from
        # the CURRENT dt, so consecutive retries keep shrinking instead of
        # resetting to the (larger) dt the rollback checkpoint was written
        # at — but never below the dt_min floor (a retry at a dt that can
        # no longer make progress just burns refactorizations)
        new_dt = None
        if hasattr(self.pde, "set_dt") and 0.0 < self.dt_backoff < 1.0:
            new_dt = max(self.pde.get_dt() * self.dt_backoff, self.dt_min)
            if new_dt != float(self.pde.get_dt()):
                self.pde.set_dt(new_dt)
        if self.governor is not None:
            # keep the governor's rung honest after an off-ladder backoff
            aligned = self.governor.align(float(self.pde.get_dt()), self.step)
            if aligned is not None:
                self.pde.set_dt(aligned)
        respawned = 0
        if self.respawn_members and hasattr(self.pde, "respawn_dead"):
            respawned = self.pde.respawn_dead(
                amp=self.respawn_amp, seed=self._respawn_seed_arg()
            )
        self._last_ckpt_time = float(self.pde.get_time())
        self._last_ckpt_path = path
        self._journal(
            {
                "event": "retry",
                "rollback_path": path,
                "dt": float(self.pde.get_dt()) if new_dt is not None else None,
                "dt_floor": bool(self.dt_min and new_dt == self.dt_min),
                "respawned": respawned,
            }
        )

    # -- the harness loop ----------------------------------------------------

    @contextlib.contextmanager
    def session(self, install_signals: bool = True, resume: bool | None = None):
        """Arm the harness WITHOUT the driver loop — the embedding surface
        for supervisors that own their own scheduling (serve.SimServer's
        continuously-batched slot loop).  Inside the block the runner's
        services are live exactly as under :meth:`run`: the IO pipeline and
        checkpoint format are selected, the governor armed, signals
        installed (``install_signals=False`` leaves them to the embedder),
        and a resume restores the newest valid checkpoint (``resume``
        overrides the constructor flag; the result is ``self.resumed``).
        The embedder drives :meth:`advance` / :meth:`checkpoint_now` /
        :meth:`drain_requested` and the context exit settles the pipeline
        and restores signal handlers — including on the
        :class:`DispatchHang` path, where lagged diagnostics are abandoned
        rather than resolved against a wedged device."""
        # long-lived entry point: arm the persistent compile cache so a
        # restarted incarnation deserializes its executables instead of
        # recompiling (RUSTPDE_COMPILE_CACHE=0 opts out; idempotent)
        config.ensure_compile_cache()
        self.resumed = False
        if install_signals:
            self._install_signals()
        self._setup_io()
        self._stats_health_pending = None
        # hand the model the run's journal writer for the session: model-
        # side statistics failures (stats_mismatch / stats_write_failed,
        # models/stats.report_stats_event) land as typed events in THIS
        # run's journal instead of vanishing into stdout (root only — the
        # journal is root-owned)
        self._saved_pde_journal = getattr(self.pde, "journal_writer", None)
        if _is_root():
            if self._journal_writer is None:
                self._journal_writer = JournalWriter(self.journal_path)
            self.pde.journal_writer = self._journal_writer
            if hasattr(self.pde, "model"):
                self.pde.model.journal_writer = self._journal_writer
        # telemetry arming (root only: run_dir is shared on multihost):
        # cadenced metrics.jsonl for headless runs + the unclean-exit
        # flight-record hook — disarmed on ANY session exit below (the
        # exception paths dump explicitly, with a better reason)
        if _is_root():
            self._metrics_dumper = MetricsDumper(
                os.path.join(self.run_dir, "metrics.jsonl")
            )
            self._exit_disarm = _tr.arm_exit_dump(self.run_dir, lambda: self.step)
        # a collective-desync trip mid-session should drop its flight
        # record next to the journal, like every other incident dump
        _sanitizer.set_run_dir(self.run_dir)
        try:
            if self.resume if resume is None else resume:
                self.resumed = self._maybe_resume()
            self._setup_governor()
            yield self
        except DispatchHang:
            # the runtime is wedged: teardown's diag flush would fetch from
            # the dead dispatch and block forever (un-watchdogged), eating
            # the structured raise — drop the lagged lines instead (the
            # background writer holds host-side data only, so its drain in
            # _teardown_io stays safe)
            if self._io is not None:
                self._io.abandon_diags()
            self.incident_dump("dispatch_hang")
            raise
        except BaseException as exc:
            # every incident ships with a timeline: DivergenceError, write
            # failures, KeyboardInterrupt — dumped before teardown so the
            # ring still holds the events leading in
            self.incident_dump(type(exc).__name__)
            raise
        finally:
            if self._exit_disarm is not None:
                self._exit_disarm()
                self._exit_disarm = None
            self._teardown_io()
            if install_signals:
                self._restore_signals()

    def incident_dump(self, reason: str) -> None:
        """Best-effort flight-recorder dump into the run_dir (root only) +
        a journal pointer — incident telemetry must never mask the
        incident itself.  Public: part of the embedding surface (the serve
        scheduler dumps with reason ``drain``), also driven internally on
        every exception escaping a session and on preemption."""
        if not _is_root():
            return
        try:
            path = _tr.dump_flight_record(self.run_dir, reason, step=self.step)
            if path is not None:
                # the dump's sequence number + the trace ids of the
                # requests that were on the device: a chaos soak's dump
                # pile stays attributable and chronologically sortable.
                # The seq comes from THIS dump's filename — a counter read
                # here could name a concurrent dump's id instead
                import re as _re

                from ..telemetry import reqtrace as _reqtrace

                m = _re.search(r"_n(\d+)\.json$", path)
                self._journal(
                    {
                        "event": "flight_record",
                        "reason": reason,
                        "path": path,
                        "seq": int(m.group(1)) if m else None,
                        "trace_ids": _reqtrace.active_ids() or None,
                    }
                )
        except Exception:
            pass

    # -- the embedding surface (serve.SimServer) ------------------------------

    def advance(self, n: int) -> None:
        """Advance up to ``n`` steps through the full dispatch stack —
        fault injection, watchdog deadlines, sub-chunking, governor — the
        supervisor-facing form of the private ``integrate`` hook.  May
        commit fewer than ``n`` steps (pending signal, governor re-plan);
        ``self.step`` counts what actually committed, so the caller loops
        on its own accounting."""
        self._dispatch(self.pde, n)

    def checkpoint_now(self, reason: str = "manual") -> str | None:
        """Write a checkpoint outside the cadence (drain, slot-table edge):
        same collective/async semantics as the internal cadence writer."""
        return self._checkpoint(reason)

    def request_drain(self) -> None:
        """Programmatic SIGTERM-equivalent: the next chunk boundary sees
        :meth:`drain_requested` true — the serve drain path rides the same
        deferred-interrupt machinery as real preemption."""
        self._interrupt = signal.SIGTERM

    def drain_requested(self) -> bool:
        """True when a signal (or :meth:`request_drain`) asked for a stop —
        root-decided on multihost, like every collective-adjacent flag."""
        return self._preempt_agreed()

    def on_boundary(self) -> bool:
        """Chunk-boundary housekeeping for embedding supervisors — exactly
        the hook ``integrate()`` drives: settle any deferred sharded
        commit, write a cadence checkpoint when due, and return True when
        a drain/preemption was requested."""
        return bool(self._on_chunk(self.pde))

    def run(self) -> dict:
        """Drive the model to ``max_time``, surviving what can be survived.

        Returns a summary dict (``outcome``: ``"done"`` | ``"preempted"``,
        final step/time/dt, retry count, final Nu, journal path).  Raises
        :class:`DivergenceError` once retries are exhausted and
        :class:`DispatchHang` when a dispatch blows its deadline."""
        pde = self.pde
        if not self.resume and checkpoint.checkpoint_files(self.run_dir):
            # a later rollback would splice the OLD campaign's trajectory
            # into this run — refuse rather than silently mix campaigns
            raise ValueError(
                f"resume=False but {self.run_dir!r} already holds "
                "checkpoints from a previous run; clear the directory or "
                "drop resume=False"
            )
        with self.session():
            self._journal(
                {
                    "event": "start",
                    "resumed": self.resumed,
                    "dt": float(pde.get_dt()),
                    "max_time": self.max_time,
                    "governed": self.governor is not None,
                    "io": {
                        "async_checkpoints": self._async_ckpt,
                        "overlap_dispatch": self._overlap,
                        "sharded_checkpoints": self._sharded,
                    },
                    "fault": dataclasses.asdict(self.fault) if self.fault else None,
                }
            )
            if self._last_ckpt_path is None:
                # rollback anchor: divergence recovery needs at least one
                # valid checkpoint to return to (_maybe_resume sets the
                # path when it restored one — no extra run_dir scan here)
                self._checkpoint("anchor")
            while True:
                try:
                    status = integrate(
                        pde,
                        self.max_time,
                        self.save_intervall,
                        dispatch=self._dispatch,
                        on_chunk=self._on_chunk,
                        overlap=self._overlap,
                    )
                except DispatchHang as exc:
                    self._journal(
                        {
                            "event": "dispatch_hang",
                            "label": exc.label,
                            "timeout_s": exc.timeout_s,
                        }
                    )
                    raise
                if status in ("time_limit", "timestep_limit"):
                    self._checkpoint("final")
                    self._drain_io()
                    self._journal_health()
                    self._journal({"event": "done", "status": status, "nu": self._nu()})
                    return self._summary("done")
                if status == "stopped":
                    self._checkpoint("preempt")
                    self._drain_io()
                    self._journal_health()
                    self._journal({"event": "preempted", "signal": self._interrupt})
                    # a preemption IS an incident: ship the timeline with it
                    self.incident_dump("preempt")
                    return self._summary("preempted")
                # status == "break": the model's NaN criterion fired (or a
                # sentinel catch the governor gave up on)
                self._journal({"event": "divergence", "dt": float(pde.get_dt())})
                if self.attempt >= self.max_retries:
                    self._journal({"event": "giveup", "retries": self.attempt})
                    self._journal_health()
                    raise DivergenceError(
                        f"diverged at step {self.step} and exhausted "
                        f"{self.max_retries} retries (dt now {pde.get_dt():g}); "
                        f"journaled dt trajectory: {self._dt_trajectory()}"
                    )
                self.attempt += 1
                self._rollback()

    def _setup_io(self) -> None:
        """Build the overlapped-IO pipeline for this run (run() entry).

        The checkpoint FORMAT is picked here too: ``io.sharded_checkpoints``
        ``None`` auto-selects the distributed two-phase format
        (utils/checkpoint.write_sharded_snapshot) on multi-process runtimes
        — the gathered writers need every shard addressable from root,
        which a real multi-controller mesh cannot provide — and the
        gathered single-file format otherwise; True/False force either.

        Async checkpointing runs single-process AND multihost-sharded: on a
        multihost mesh each host overlaps its own shard serialization on a
        per-host background writer, and the collective two-phase commit is
        deferred to the next chunk boundary — every host drains its writer
        before the barrier (drain-before-barrier), so the manifest only
        ever names fsynced shards.  Dispatch overlap (the lagged break
        check) stays single-process-only: a break flag resolving on
        per-host device-queue timing would desynchronize the collective
        dispatch sequence, so multihost break decisions remain un-lagged
        and root-broadcast (the same reason PR-2 made cadence decisions
        root-broadcast).  The dispatch overlap additionally needs the model
        to offer ``exit_future``.  The model's ``io_pipeline`` attribute is
        pointed at the run's pipeline so its callback IO (flow snapshots,
        diagnostics lines) shares the worker and lag queue — restored on
        exit."""
        io = self.io
        single = _single_process()
        self._sharded = bool(
            io.sharded_checkpoints
            if io.sharded_checkpoints is not None
            else not single
        ) and hasattr(self.pde, "snapshot_state_items")
        self._async_ckpt = bool(io.async_checkpoints and (single or self._sharded))
        self._overlap = bool(
            io.overlap_dispatch and single and hasattr(self.pde, "exit_future")
        )
        self._pending_commit = None
        self._io_snapshot_s = 0.0  # per-run, like the pipeline's own stats
        self._saved_pde_io = getattr(self.pde, "io_pipeline", None)
        if self._async_ckpt or self._overlap:
            self._io = IOPipeline(queue_depth=io.queue_depth, diag_lag=io.diag_lag)
            self.pde.io_pipeline = self._io

    @property
    def last_checkpoint(self) -> str | None:
        """Path of the newest verified/committed checkpoint (None before the
        first write).  Public embedding surface — workload drivers report it
        instead of reaching into runner internals."""
        return self._last_ckpt_path

    def drain_io(self) -> None:
        """Settle the IO pipeline: flush lagged diagnostics, commit any
        pending sharded write, wait for background writers and surface the
        first write failure.  Public embedding surface (workload drivers
        settle before sweeping spent checkpoints); :meth:`run` calls it at
        every normal completion."""
        self._drain_io()

    def _drain_io(self) -> None:
        """Flush lagged diagnostics + wait for background writes, surfacing
        the first write failure (the normal-completion settle point), then
        journal one ``io_overlap`` summary: payload bytes, main-thread
        staging seconds (device fetch), worker write seconds, submitter
        seconds lost to back-pressure, and the configured queue depth."""
        self._commit_pending()
        if self._io is not None:
            try:
                self._io.drain()
            except AsyncWriteError as exc:
                # normal-completion settle: a disk-full write failure is
                # contained (journaled with errno) — the run's RESULTS
                # are in memory/observables; only the checkpoint is lost
                if not self._is_enospc(exc):
                    raise
                self._degrade_checkpoints(exc, "drain")
            self._journal(
                {
                    "event": "io_overlap",
                    **self._io.stats(),
                    "snapshot_s": round(self._io_snapshot_s, 3),
                    "queue_depth": self.io.queue_depth,
                    "diag_lag": self.io.diag_lag,
                }
            )
        if self._metrics_dumper is not None:
            # run-end flush: headless campaigns always leave at least one
            # metrics.jsonl line next to the journal
            self._metrics_dumper.dump(step=self.step)

    def _teardown_io(self) -> None:
        """run() exit: settle the pipeline WITHOUT masking an in-flight
        exception (write failures were either surfaced at the last
        submit/drain or remain journaled as ``checkpoint_failed``), stop
        the worker, and give the model its previous pipeline back.

        A still-pending sharded commit is ABANDONED here, not committed:
        teardown may be running on an exception path where the collective
        barrier would wedge against hosts that already died.  The orphaned
        shard files are harmless (no manifest = not committed) and the
        rotation sweep collects them."""
        if self._pending_commit is not None:
            _, path, reason, _ = self._pending_commit
            self._pending_commit = None
            self._journal(
                {"event": "checkpoint_abandoned", "reason": reason, "path": path}
            )
        if self._io is not None:
            try:
                self._io.drain(raise_errors=False)
            finally:
                self._io.close()
        saved = getattr(self, "_saved_pde_io", None)
        if getattr(self.pde, "io_pipeline", None) is not saved:
            self.pde.io_pipeline = saved
        # give the model its previous journal hook back (adopted writers
        # belong to the embedding supervisor; owned ones close below)
        if getattr(self.pde, "journal_writer", None) is not self._saved_pde_journal:
            self.pde.journal_writer = self._saved_pde_journal
            if hasattr(self.pde, "model"):
                self.pde.model.journal_writer = self._saved_pde_journal
        self._stats_health_pending = None
        # release the journal handle (reopens lazily if journaled again);
        # an adopted writer belongs to the embedding supervisor — not ours
        if self._journal_writer is not None and self._journal_owned:
            self._journal_writer.close()

    def _setup_governor(self) -> None:
        """Arm the sentinels + build the dt governor (run() start, after a
        possible resume so an off-ladder restored dt can be re-aligned).
        The ladder anchors at the dt the runner was CONSTRUCTED with — the
        campaign's nominal dt — so a resumed backed-off run can climb back
        to it; ``dt_min`` (when set) floors the ladder too."""
        if self.stability is None or not hasattr(self.pde, "set_stability"):
            return
        if getattr(self.pde, "_stability", None) is not self.stability:
            self.pde.set_stability(self.stability)
        if getattr(self.pde, "_step_n_sent", None) is None:
            return  # GSPMD-fallback path: set_stability already warned
        cfg = self.stability
        if cfg.dt_min is None and self.dt_min > 0.0:
            cfg = dataclasses.replace(cfg, dt_min=min(self.dt_min, self._dt0))
        self.governor = StabilityGovernor(cfg, self._dt0)
        aligned = self.governor.align(float(self.pde.get_dt()), self.step)
        if aligned is not None:
            self.pde.set_dt(aligned)
            self._journal(
                {
                    "event": "dt_adjust",
                    "dt": aligned,
                    "rung": self.governor.rung,
                    "reason": "resumed dt quantized to the governor ladder",
                }
            )

    def _journal_health(self) -> None:
        """End-of-run physics health summary (governed runs)."""
        if self.governor is not None:
            self._journal({"event": "run_health", **self.governor.health.asdict()})

    def _summary(self, outcome: str) -> dict:
        return {
            "outcome": outcome,
            "step": self.step,
            "time": float(self.pde.get_time()),
            "dt": float(self.pde.get_dt()),
            "retries": self.attempt,
            "nu": self._nu(),
            "journal": self.journal_path,
            # tracked, not re-scanned: latest_checkpoint re-hashes every
            # file, which is pure waste for multi-GB snapshots
            "checkpoint": self._last_ckpt_path,
            # physics health telemetry (governed runs): dt trajectory,
            # sentinel extrema, pre-divergence catches / rollbacks avoided
            "health": (
                self.governor.health.asdict() if self.governor is not None else None
            ),
            # overlapped-IO telemetry: background writes, worker seconds,
            # submitter seconds lost to back-pressure
            "io": self._io.stats() if self._io is not None else None,
            # physics-stats health readout (stats-armed models): the
            # HEALTH_NAMES scalars — spectral tails, BL point counts,
            # budget residuals, Nu estimators, sample count
            "stats": (
                self.pde.stats_summary()
                if getattr(self.pde, "stats_armed", False)
                else None
            ),
        }
