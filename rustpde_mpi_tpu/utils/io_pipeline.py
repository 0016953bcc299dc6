"""Overlapped I/O pipeline: async checkpoint writes + observable futures.

Every host-side IO the run loop performs today is synchronous and sits on
the device's critical path: a checkpoint write fetches the state, runs the
backward transforms, sha256-hashes every dataset and fsyncs the file while
the accelerator idles; a diagnostics callback blocks on four separate
device-to-host scalar transfers before the next chunk is dispatched.  At
production grid sizes (multi-GB snapshots, a host sync per observable) that
IO tax is pure dead time — the device work for the next chunk is already
known and could be in flight.

This module supplies the three pieces that take IO off the critical path
while keeping every durability guarantee of utils/checkpoint.py:

* **observable futures** (:class:`ObservableFuture`) — a handle to device
  values that have been *dispatched* but not fetched.  ``ready()`` is a
  non-blocking completion probe (``jax.Array.is_ready``), ``result()``
  fetches the whole pytree in ONE transfer and caches it.  The Navier
  models hand these out (``get_observables_async`` / ``exit_future``) so
  diagnostics and break-criterion checks can lag one chunk behind the
  device instead of fencing it every boundary.

* **an async checkpoint writer** (:class:`AsyncCheckpointWriter`) — a
  single background worker with a bounded submission queue.  The main
  thread fetches the state to host memory (the cheap part: one device sync
  it needed anyway) and hands a :class:`~.checkpoint.HostSnapshot` over;
  the serialization, digest and fsync (the expensive part) overlap the
  next chunks' compute.  Failures are never silent: the first write error
  is re-raised at the next ``submit``/``drain`` — the same turn a
  synchronous write would have raised, one cadence later.  The queue depth
  bounds both memory (one host snapshot in flight) and staleness (a
  submission blocks until the previous write lands, so checkpoint cadence
  can never outrun the disk).

* **a diagnostics lag queue** (:class:`IOPipeline.push_diag`) — callback
  output (the printed Nu line, info.txt rows, the in-memory diagnostics
  map) is produced from a future and emitted once the values are ready,
  at most ``diag_lag`` boundaries late.  Order is strictly FIFO, and
  ``flush_diags``/``drain`` emit everything at run end, so files and
  diagnostics histories are complete and chronologically ordered — just
  not written from inside the device's dispatch window.

Threading contract: ONLY host-side work (numpy, h5py, os) runs on the
worker thread.  Device fetches happen on the submitting thread — fetching
sharded jax Arrays from pool threads can starve the runtime's own thread
pool (the PR-1 ``slice_io`` deadlock), so the split is fetch-on-main,
serialize-on-worker by design.

Multihost: the WRITE side runs here too — each host's shard of a
distributed checkpoint (utils/checkpoint.ShardSnapshot) is serialized on
that host's own writer, and the resilient runner drains the writer before
the two-phase commit barrier (drain-before-barrier), so a manifest only
ever names fsynced shards.  The lagged break check stays single-process:
futures resolving on per-host device timing would desynchronize the
collective dispatch sequence (utils/resilience._setup_io).
"""

from __future__ import annotations

import threading
import time as _time
from collections import deque

from ..telemetry import metrics as _tm
from ..config import env_get


class AsyncWriteError(RuntimeError):
    """A background checkpoint/snapshot write failed.

    Raised on the SUBMITTING thread at the next ``submit``/``drain`` after
    the failure, carrying the offending path and the original error as
    ``__cause__`` — the deferred equivalent of a synchronous writer raising
    in place."""

    def __init__(self, path: str, cause: BaseException):
        super().__init__(f"background write of {path!r} failed: {cause}")
        self.path = path


def _leaves_ready(arrays) -> bool:
    """Non-blocking completion probe shared by every future type: True once
    each leaf's device computation is done (plain-numpy leaves, which have
    no ``is_ready``, count as done)."""
    import jax

    return all(
        leaf.is_ready()
        for leaf in jax.tree.leaves(arrays)
        if hasattr(leaf, "is_ready")
    )


class ObservableFuture:
    """Handle to device values dispatched but not yet fetched.

    ``arrays`` is any pytree of jax (or numpy) arrays; ``convert`` maps the
    fetched host pytree to the user-facing value (applied once, cached).
    ``ready()`` never blocks; ``result()`` fetches the WHOLE pytree in one
    ``jax.device_get`` — one host round-trip regardless of leaf count,
    where per-leaf ``float()`` conversion costs a round-trip each."""

    def __init__(self, arrays, convert=None):
        self._arrays = arrays
        self._convert = convert
        self._value = None
        self._done = False

    def ready(self) -> bool:
        if self._done:
            return True
        return _leaves_ready(self._arrays)

    def result(self):
        """Fetch (blocking, once) and return the converted value."""
        if not self._done:
            import jax

            host = jax.device_get(self._arrays)
            self._value = host if self._convert is None else self._convert(host)
            self._done = True
            self._arrays = None  # release the device buffers
        return self._value

class MappedFuture:
    """Derived future: ``fn`` applied to another future's result.  The
    device dispatch and the single fetch are shared with the parent —
    mapping never costs an extra host round-trip."""

    def __init__(self, parent, fn):
        self._parent = parent
        self._fn = fn
        self._value = None
        self._done = False

    def ready(self) -> bool:
        return self._parent.ready()

    def result(self):
        if not self._done:
            self._value = self._fn(self._parent.result())
            self._done = True
        return self._value


def immediate(value) -> ObservableFuture:
    """A future that is already resolved (host-side facts: latches, masks)."""
    fut = ObservableFuture(None)
    fut._value = value
    fut._done = True
    return fut


class PendingChunkStatus:
    """Deferred-commit handle for one sentinel-armed chunk — the governed
    half of dispatch double-buffering (the ``lag=1`` sentinel contract).

    Created by the models' ``update_n_pending``: the chunk is dispatched
    and the model PROVISIONALLY advanced to its end state, so the next
    chunk can be enqueued before this one's sentinel scalars are fetched.
    ``resolve()`` fetches the scalars (one host transfer) and hands them to
    ``finish``, which reproduces the synchronous chunk's exact semantics —
    on a CFL-ceiling trip the chunk-start snapshot (state AND time) is
    restored and ``exit()`` latches.  The synchronous sentinel chunk is
    literally ``update_n_pending(n).resolve()``, so the two paths cannot
    drift.

    Contract for callers running ahead (the resilient runner's lagged
    ``_advance``): when a resolve rolls the model back, any LATER pending
    chunk was dispatched from the rolled-back provisional state — it must
    be ``discard()``-ed, never resolved (its ``finish`` would clobber the
    restored snapshot)."""

    def __init__(self, arrays, finish):
        self._arrays = arrays
        self._finish = finish
        self._status = None
        self._discarded = False

    def ready(self) -> bool:
        """Non-blocking: True once the sentinel scalars can be fetched
        without waiting on the device."""
        if self._status is not None or self._discarded:
            return True
        return _leaves_ready(self._arrays)

    def resolve(self):
        """Fetch the sentinel scalars and commit/roll back the provisional
        advance; idempotent, returns the chunk's ChunkStatus."""
        if self._discarded:
            raise RuntimeError("resolve() on a discarded pending chunk")
        if self._status is None:
            import jax

            self._status = self._finish(jax.device_get(self._arrays))
            self._arrays = None
            self._finish = None
        return self._status

    def discard(self) -> None:
        """Drop an invalidated speculative chunk (a previous chunk's
        rollback already restored the model past it)."""
        self._discarded = True
        self._arrays = None
        self._finish = None


class WriteTicket:
    """Completion handle for one background write."""

    def __init__(self, path: str):
        self.path = path
        self.error: BaseException | None = None
        self._event = threading.Event()

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: float | None = None) -> None:
        """Block until the write finished; re-raise its failure."""
        self._event.wait(timeout)
        if self.error is not None:
            raise AsyncWriteError(self.path, self.error) from self.error


class AsyncCheckpointWriter:
    """Single-worker background writer with a bounded in-flight window.

    ``submit(work, path)`` enqueues ``work()`` (pure host-side IO) and
    returns a :class:`WriteTicket`.  At most ``depth`` submissions are
    resident — queued *plus* the one being written — and an over-depth
    submit blocks until the oldest write LANDS, not merely until the
    worker picks it up (back-pressure: checkpoint cadence can never outrun
    the disk, and host memory holds at most ``depth`` pending snapshots).
    The first failure is sticky — it
    re-raises at every later ``submit`` and at ``drain`` until observed —
    so a dead disk stops the campaign at the next cadence, exactly where
    the synchronous writer would have stopped it.

    ``timeout_s`` (or ``RUSTPDE_IO_TIMEOUT_S`` via :class:`IOPipeline`;
    default off, like the dispatch watchdog) bounds how long ``submit``
    back-pressure and ``drain`` may block on the worker: a disk/NFS wedge
    mid-``fsync`` then dumps every thread's stack and raises a typed
    :class:`AsyncWriteError` (cause ``TimeoutError``) on the submitting
    thread instead of hanging the campaign silently — the io analogue of
    ``RUSTPDE_DISPATCH_TIMEOUT_S``/``DispatchHang``.  (A wedged disk hangs
    the SYNCHRONOUS writer identically, inside fsync; the async writer is
    simply the one that can convert it into a structured error.)"""

    def __init__(self, depth: int = 1, timeout_s: float | None = None):
        import queue

        self.depth = max(1, int(depth))
        self.timeout_s = timeout_s
        # the queue itself is unbounded: the residency bound is _slots,
        # released only after a write COMPLETES (a maxsize queue alone
        # would admit depth+1 snapshots once the worker get()s the head)
        self._queue: "queue.Queue" = queue.Queue()
        self._slots = threading.Semaphore(self.depth)
        self._worker: threading.Thread | None = None
        self._failed: deque[WriteTicket] = deque()
        self._inflight: deque[WriteTicket] = deque()
        self._lock = threading.Lock()
        self.writes = 0  # completed writes
        self.write_s = 0.0  # worker seconds spent writing
        self.wait_s = 0.0  # submitter seconds blocked on back-pressure
        self.bytes = 0  # payload bytes handed to the worker

    def _ensure_worker(self) -> None:
        if self._worker is not None and self._worker.is_alive():
            return
        self._worker = threading.Thread(
            target=self._run, name="io-pipeline-writer", daemon=True
        )
        self._worker.start()

    def _run(self) -> None:
        while True:
            item = self._queue.get()
            try:
                if item is None:
                    return
                work, ticket = item
                t0 = _time.monotonic()
                try:
                    work()
                except BaseException as exc:  # surfaced at submit/drain
                    ticket.error = exc
                    with self._lock:
                        self._failed.append(ticket)
                finally:
                    write_s = _time.monotonic() - t0
                    with self._lock:
                        self.writes += 1
                        self.write_s += write_s
                    _tm.counter(
                        "io_writes_total", "background writes completed"
                    ).inc()
                    _tm.counter(
                        "io_write_seconds_total", "worker seconds spent writing"
                    ).inc(write_s)
                    if ticket.error is not None:
                        _tm.counter(
                            "io_write_failures_total", "background writes that failed"
                        ).inc()
                    ticket._event.set()
                    self._slots.release()
            finally:
                self._queue.task_done()

    def _raise_failed(self) -> None:
        with self._lock:
            ticket = self._failed.popleft() if self._failed else None
        if ticket is not None:
            raise AsyncWriteError(ticket.path, ticket.error) from ticket.error

    def _hang(self, what: str, path: str) -> None:
        """Armed-timeout expiry: name the wedge, dump every thread's stack
        (the worker's shows where the disk is stuck), raise typed."""
        import faulthandler
        import sys

        print(
            f"io-pipeline writer stuck: {what} exceeded {self.timeout_s:.0f}s "
            f"({path!r}) — dumping all thread stacks",
            file=sys.stderr,
        )
        faulthandler.dump_traceback(all_threads=True, file=sys.stderr)
        err = TimeoutError(f"{what} exceeded {self.timeout_s:.0f}s")
        raise AsyncWriteError(path, err) from err

    def submit(self, work, path: str, nbytes: int = 0) -> WriteTicket:
        """Enqueue ``work()``; blocks while ``depth`` writes are in flight
        (at most ``timeout_s``, when armed).  Raises a pending
        :class:`AsyncWriteError` from an earlier failed write before
        enqueueing new work.  ``nbytes`` (the payload size, when the caller
        knows it) feeds the ``io_overlap`` telemetry."""
        self._raise_failed()
        self._ensure_worker()
        ticket = WriteTicket(path)
        with self._lock:
            self.bytes += int(nbytes)
        t0 = _time.monotonic()
        if not self._slots.acquire(timeout=self.timeout_s):
            self._hang(f"back-pressure wait ({self.depth} writes in flight)", path)
        waited = _time.monotonic() - t0
        self.wait_s += waited
        _tm.counter(
            "io_backpressure_seconds_total",
            "submitter seconds blocked on the in-flight write window",
        ).inc(waited)
        _tm.counter("io_bytes_total", "payload bytes handed to the writer").inc(
            int(nbytes)
        )
        with self._lock:
            while self._inflight and self._inflight[0].done():
                self._inflight.popleft()  # keep the deque bounded by depth+1
            self._inflight.append(ticket)
        self._queue.put((work, ticket))
        return ticket

    def drain(self, raise_errors: bool = True) -> None:
        """Block until every submitted write completed; re-raise the first
        unobserved failure (``raise_errors=False`` only waits — for cleanup
        paths that must not mask an in-flight exception).  With ``timeout_s``
        armed, the whole drain gets that long before the stuck write is
        surfaced as a typed hang (the in-flight window is bounded by
        ``depth``, so the budget covers at most ``depth`` writes)."""
        if self.timeout_s is None:
            self._queue.join()
        else:
            deadline = _time.monotonic() + self.timeout_s
            while True:
                with self._lock:
                    ticket = next(
                        (t for t in self._inflight if not t.done()), None
                    )
                if ticket is None:
                    break
                remaining = deadline - _time.monotonic()
                if remaining <= 0 or not ticket._event.wait(remaining):
                    self._hang("drain wait", ticket.path)
        if raise_errors:
            self._raise_failed()

    def pending_errors(self) -> bool:
        with self._lock:
            return bool(self._failed)

    def consume_errors(self) -> list[BaseException]:
        """Pop and return every sticky failure's ROOT CAUSE without
        raising.  A caller that can degrade on a failure class — the
        runner's ENOSPC containment turns disk-full checkpoints into
        in-memory-rollback-only mode — uses this to observe the causes
        and unwedge the writer; left in place, the backlog would
        re-raise at every later ``submit``, one write at a time."""
        out: list[BaseException] = []
        with self._lock:
            while self._failed:
                out.append(self._failed.popleft().error)
        return out

    def close(self) -> None:
        """Drain and stop the worker thread (errors NOT re-raised; call
        :meth:`drain` first when failures matter).  With ``timeout_s`` armed
        a wedged worker is ABANDONED (daemon thread) rather than joined
        forever — close runs on teardown paths that may already be
        propagating an exception."""
        if self._worker is None or not self._worker.is_alive():
            return
        if self.timeout_s is not None:
            try:
                self.drain(raise_errors=False)
            except AsyncWriteError:
                return  # wedged: leave the daemon thread behind
        else:
            self._queue.join()
        self._queue.put(None)
        self._worker.join(timeout=10.0)


class IOPipeline:
    """The per-run facade the models and the resilient runner share.

    One background :class:`AsyncCheckpointWriter` plus the diagnostics lag
    queue.  A model carrying this as its ``io_pipeline`` attribute has its
    callback IO (flow snapshots, the printed Nu line, info.txt rows) routed
    through it by ``utils/navier_io.callback`` / the ensemble callback."""

    def __init__(
        self,
        queue_depth: int = 1,
        diag_lag: int = 1,
        timeout_s: float | None = None,
    ):
        if timeout_s is None:
            import os

            env = env_get("RUSTPDE_IO_TIMEOUT_S")
            timeout_s = float(env) if env else None
        self.writer = AsyncCheckpointWriter(depth=queue_depth, timeout_s=timeout_s)
        self.diag_lag = max(0, int(diag_lag))
        self._diags: deque = deque()
        self._dropped_diags = 0

    # -- background writes ----------------------------------------------------

    def submit_write(self, work, path: str, nbytes: int = 0) -> WriteTicket:
        """Hand one host-side write to the worker (see
        :meth:`AsyncCheckpointWriter.submit`)."""
        return self.writer.submit(work, path, nbytes=nbytes)

    # -- lagged diagnostics ---------------------------------------------------

    def push_diag(self, emit, future) -> None:
        """Queue one callback emission: ``emit(future.result())`` runs once
        the values are ready, at most ``diag_lag`` pushes late, in FIFO
        order.  Ready entries are emitted immediately so a fast device (or
        the eager path) behaves exactly like the synchronous callback."""
        self._diags.append((emit, future))
        self._pump(block=False)

    def _pump(self, block: bool) -> None:
        while self._diags:
            emit, fut = self._diags[0]
            if not block and len(self._diags) <= self.diag_lag and not fut.ready():
                break  # young enough to stay pending
            self._diags.popleft()
            emit(fut.result())

    def flush_diags(self) -> None:
        """Emit every pending diagnostics entry (end of run)."""
        self._pump(block=True)

    def abandon_diags(self) -> int:
        """Drop pending diagnostic emissions WITHOUT resolving their
        futures.  For the :class:`~..utils.resilience.DispatchHang`
        teardown path only: those futures came from the wedged dispatch,
        so resolving them in a ``finally`` would block forever with no
        watchdog and swallow the structured raise.  Returns the number of
        lines lost (also surfaced as ``dropped_diags`` in :meth:`stats`)."""
        n = len(self._diags)
        self._dropped_diags += n
        self._diags.clear()
        return n

    # -- lifecycle ------------------------------------------------------------

    def drain(self, raise_errors: bool = True) -> None:
        """Flush diagnostics and wait for every background write; re-raises
        the first write failure unless ``raise_errors=False``."""
        self.flush_diags()
        self.writer.drain(raise_errors=raise_errors)

    def close(self) -> None:
        self.flush_diags()
        self.writer.close()

    def stats(self) -> dict:
        """Pipeline telemetry for run summaries/journals."""
        w = self.writer
        return {
            "writes": w.writes,
            "bytes": w.bytes,
            "write_s": round(w.write_s, 3),
            "queue_wait_s": round(w.wait_s, 3),
            "pending_diags": len(self._diags),
            "dropped_diags": self._dropped_diags,
        }
