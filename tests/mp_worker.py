"""Worker program for tests/test_multiprocess.py (not a pytest module).

One process of an N-process ``jax.distributed`` run on CPU devices.  Modes
(argv[5], default ``basic``):

* ``basic`` — builds the global pencil mesh, advances a sharded Navier2D,
  exercises the multihost.py host-local/global conversions + barrier,
  gathers the state and (on rank 0 only) writes a snapshot + JSON result
  for the parent to compare against a single-process run.
* ``sharded_run`` — drives a ResilientRunner over the 2-process mesh with
  SHARDED two-phase checkpoints (utils/checkpoint.write_sharded_snapshot
  via the runner).  Fault injection comes from the environment
  (``RUSTPDE_FAULT`` host-scoped specs, ``RUSTPDE_SHARD_CRASH`` two-phase
  window kills, ``RUSTPDE_SYNC_TIMEOUT_S`` barrier watchdog), so the
  parent test can kill one host between shard fsync and manifest commit
  and prove recovery.  Rank 0 dumps the final global state (allgathered)
  so the parent can assert elastic restore is bit-equal.
* ``bench_sharded`` — writes the same state sharded (two-phase) and
  gathered, with repetitions, bytes/host and the final-state dump for a
  parent's cross-topology restore check.  No test drives it since its
  caller went in PR 28 (ROADMAP Queue 3).
* ``serve_campaign`` — runs a :class:`~rustpde_mpi_tpu.serve.SimServer`
  across the 2-process mesh (root-coordinated scheduling: root owns the
  queue/journal, every slot decision is broadcast).  Root enqueues
  ``RUSTPDE_MP_SERVE_REQUESTS`` requests on the FIRST incarnation (the
  queue directory is the idempotence guard); faults come from
  ``RUSTPDE_FAULT`` (SIGTERM drain, host-scoped SIGKILL, batch NaN) and
  the slot count from ``RUSTPDE_MP_SERVE_SLOTS`` so restarts can resize
  the fleet (elastic re-plan).  Root dumps summary + journal counters.
* ``gang_serve`` — TWO-LEVEL serving over the same 2-process mesh:
  ``ServeConfig.submesh`` carves the 4 CPU devices into one 2-device
  gang sub-mesh plus a 2-device default remainder, and root enqueues
  MIXED traffic — ``RUSTPDE_MP_GANG_REQUESTS`` pencil-sharded 34^2
  flagship requests (stamped ``submesh=2`` at admission) interleaved
  with ``RUSTPDE_MP_VMAP_REQUESTS`` vmapped 18^2 requests riding the
  remainder.  Gang-scoped faults (``RUSTPDE_FAULT=kill@<n>:gang0member1``)
  SIGKILL one gang member mid-campaign; the gang barrier watchdog
  (``RUSTPDE_GANG_SYNC_TIMEOUT_S``) must convert the wedge into a typed
  ``GangMemberLost`` and containment must requeue-with-state.  Root also
  proves door-time admission: an unshardable grid comes back as a typed
  ``reason="no_submesh"`` rejection, never a durable queue row.  Root
  dumps summary + the gang journal counters.

* ``integrity_serve`` — the SDC soak: a serve campaign with on-device
  digests + shadow audits armed (cadence 1, single-strike quarantine)
  under ``RUSTPDE_FAULT=bitflip@<n>:host1`` — the audit must catch the
  flip, the quarantine must trip, containment must requeue, and zero
  requests may be lost.

argv: coordinator_port process_id num_processes out_dir [mode]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


def _build_model(mesh, nx=34, ny=34, dt=0.01):
    from rustpde_mpi_tpu import Navier2D

    # 34^2: spectral dims (32, 32) divide the 4-device mesh -- the
    # multi-process host-local/global conversions require divisible
    # pencil dims (JAX rejects uneven global shardings outside jit)
    model = Navier2D(nx, ny, 1e4, 1.0, dt, 1.0, "rbc", periodic=False, mesh=mesh)
    model.set_velocity(0.1, 1.0, 1.0)
    model.set_temperature(0.1, 1.0, 1.0)
    model.write_intervall = 1e9  # runner checkpoints are the IO under test
    return model


def _dump_state(model, path):
    """Rank-0 dump of the full global state (allgather) — the parent's
    bit-equality reference for elastic restore."""
    import numpy as np
    from jax.experimental import multihost_utils

    from rustpde_mpi_tpu.parallel import multihost

    leaves = {
        name: np.asarray(
            multihost_utils.process_allgather(getattr(model.state, name), tiled=True)
        )
        for name in model.state._fields
    }
    multihost.sync_hosts("pre-dump")
    if multihost.is_root():
        np.savez(path, time=model.time, **leaves)
    multihost.sync_hosts("post-dump")


def mode_basic(out_dir):
    import numpy as np

    from rustpde_mpi_tpu.parallel import multihost

    mesh = multihost.global_pencil_mesh()
    assert mesh.devices.size == jax.process_count() * len(jax.local_devices())

    model = _build_model(mesh)
    model.update_n(10)
    nu, nuvol, re, div = model.get_observables()

    # multihost conversions round-trip: global -> host-local slab -> global
    temp = model.state.temp
    local = multihost.host_local_array(temp)
    assert local.shape[0] == temp.shape[0]  # pencil split is along axis 1
    rebuilt = multihost.global_array(local, temp.sharding)
    diff = float(jax.jit(lambda a, b: jax.numpy.abs(a - b).max())(rebuilt, temp))
    assert diff == 0.0, diff

    # gather-to-every-host (the root-IO pattern) + rank-0 snapshot write
    from jax.experimental import multihost_utils

    full = np.asarray(multihost_utils.process_allgather(temp, tiled=True))
    checksum = float(np.abs(full).sum())
    multihost.sync_hosts("pre-write")
    if multihost.is_root():
        import h5py

        with h5py.File(os.path.join(out_dir, "snapshot_mp.h5"), "w") as f:
            f["temp"] = full
        with open(os.path.join(out_dir, "result.json"), "w") as f:
            json.dump(
                {
                    "nu": nu,
                    "nuvol": nuvol,
                    "re": re,
                    "div": div,
                    "checksum": checksum,
                    "ndev_global": int(mesh.devices.size),
                    "nproc": jax.process_count(),
                },
                f,
            )
    multihost.sync_hosts("post-write")


def mode_sharded_run(out_dir):
    from rustpde_mpi_tpu import ResilientRunner
    from rustpde_mpi_tpu.config import IOConfig
    from rustpde_mpi_tpu.parallel import multihost

    mesh = multihost.global_pencil_mesh()
    model = _build_model(mesh)
    # RUSTPDE_MP_BLOCKING_IO=1 pins synchronous shard writes so a
    # SHARD_CRASH kill lands deterministically inside the two-phase window
    # (async submits would race the surviving host's next dispatch)
    io = (
        IOConfig(async_checkpoints=False, overlap_dispatch=False, diag_lag=0)
        if os.environ.get("RUSTPDE_MP_BLOCKING_IO") == "1"
        else None
    )
    runner = ResilientRunner(
        model,
        max_time=0.2,
        save_intervall=0.05,
        run_dir=os.path.join(out_dir, "run"),
        checkpoint_every_s=None,
        checkpoint_every_t=0.05,
        keep=3,
        io=io,
    )
    summary = runner.run()  # a SHARD_CRASH/FAULT env kills us mid-protocol
    _dump_state(model, os.path.join(out_dir, "final_state.npz"))
    if multihost.is_root():
        with open(os.path.join(out_dir, "result.json"), "w") as f:
            json.dump(
                {
                    "outcome": summary["outcome"],
                    "step": summary["step"],
                    "time": summary["time"],
                    "checkpoint": summary["checkpoint"],
                    "sharded": True,
                    "nproc": jax.process_count(),
                },
                f,
            )


def mode_bench_sharded(out_dir, reps=3):
    import numpy as np
    from jax.experimental import multihost_utils

    from rustpde_mpi_tpu.parallel import multihost
    from rustpde_mpi_tpu.utils import checkpoint as cp

    mesh = multihost.global_pencil_mesh()
    nx = int(os.environ.get("RUSTPDE_BENCH_SHARDED_N", "130"))
    model = _build_model(mesh, nx=nx, ny=nx, dt=2e-3)
    model.update_n(4)

    # sharded leg: the collective two-phase writer, timed end to end
    sharded_s = []
    stats = None
    for rep in range(reps):
        path = cp.checkpoint_path(os.path.join(out_dir, "sharded"), rep)
        multihost.sync_hosts("bench-sharded-start")
        t0 = time.perf_counter()
        stats = cp.write_sharded_snapshot(model, path, step=rep)
        sharded_s.append(time.perf_counter() - t0)
    manifest = cp.checkpoint_path(os.path.join(out_dir, "sharded"), reps - 1)

    # gathered leg: what multihost checkpointing had to do before the
    # sharded path existed — allgather every leaf to every host, root
    # serializes the full state
    gathered_s = []
    for rep in range(reps):
        multihost.sync_hosts("bench-gathered-start")
        t0 = time.perf_counter()
        leaves = [
            np.asarray(
                multihost_utils.process_allgather(
                    getattr(model.state, name), tiled=True
                )
            )
            for name in model.state._fields
        ]
        if multihost.is_root():
            items = []
            for name, arr in zip(model.state._fields, leaves):
                if np.iscomplexobj(arr):
                    items.append((f"state/{name}_re", np.ascontiguousarray(arr.real), "raw"))
                    items.append((f"state/{name}_im", np.ascontiguousarray(arr.imag), "raw"))
                else:
                    items.append((f"state/{name}", arr, "raw"))
            snap = cp.HostSnapshot(datasets=items, step=rep, time=model.time)
            cp.write_host_snapshot(
                snap, os.path.join(out_dir, f"gathered_{rep}.h5")
            )
        multihost.sync_hosts("bench-gathered-end")
        gathered_s.append(time.perf_counter() - t0)

    _dump_state(model, os.path.join(out_dir, "final_state.npz"))
    if multihost.is_root():
        with open(os.path.join(out_dir, "result.json"), "w") as f:
            json.dump(
                {
                    "sharded_write_s": min(sharded_s),
                    "gathered_write_s": min(gathered_s),
                    "bytes_host": stats["bytes_host"],
                    "bytes_total": stats["bytes_total"],
                    "shards": stats["shards"],
                    "barrier_s": stats["barrier_s"],
                    "manifest": manifest,
                    "grid": [nx, nx],
                    "nproc": jax.process_count(),
                },
                f,
            )


def mode_serve_campaign(out_dir):
    from rustpde_mpi_tpu.config import ServeConfig
    from rustpde_mpi_tpu.parallel import multihost
    from rustpde_mpi_tpu.serve import AdmissionError, SimServer
    from rustpde_mpi_tpu.utils.journal import read_journal

    n_req = int(os.environ.get("RUSTPDE_MP_SERVE_REQUESTS", "5"))
    slots = int(os.environ.get("RUSTPDE_MP_SERVE_SLOTS", "2"))
    run_dir = os.path.join(out_dir, "serve")
    cfg = ServeConfig(
        run_dir=run_dir,
        slots=slots,
        max_queue=4 * n_req,
        chunk_steps=4,
        checkpoint_every_s=2.0,  # tight cadence: a SIGKILL must leave a
        # recent slot-table checkpoint to restore mid-trajectory from
        http_port=None,
    )
    srv = SimServer(cfg)  # fault rides RUSTPDE_FAULT (host-scoped specs ok)
    if multihost.is_root():
        counts = srv.queue.counts()
        if sum(counts.values()) == 0:  # first incarnation only
            for seed in range(n_req):
                # 34^2 grid: spectral dims divide the 4-device mesh; the
                # jittered horizon staggers completions off one boundary
                try:
                    srv.submit(
                        {
                            "ra": 1e4,
                            "pr": 1.0,
                            "nx": 34,
                            "ny": 34,
                            "dt": 0.01,
                            "horizon": 0.08 + (seed % 3) * 0.02,
                            "seed": seed,
                        }
                    )
                except AdmissionError:
                    pass
    summary = srv.serve()
    from rustpde_mpi_tpu.parallel import sanitizer

    # MetricsDumper multihost-collision regression (ISSUE 13 satellite):
    # every rank constructs a dumper over the SAME logical path in the
    # shared out_dir — non-root ranks must land on a .p<rank>-suffixed
    # file instead of interleaving torn lines into root's
    from rustpde_mpi_tpu.telemetry.exporters import MetricsDumper

    shared = os.path.join(out_dir, "mp_metrics.jsonl")
    dumper = MetricsDumper(shared)
    dumper.dump(step=0)
    if multihost.is_root():
        expected = shared
    else:
        expected = os.path.join(
            out_dir, f"mp_metrics.p{jax.process_index()}.jsonl"
        )
    assert dumper.path == expected, (dumper.path, expected)
    assert os.path.exists(expected), expected
    multihost.sync_hosts("metrics-suffix-dumped")

    # root-side trace assembly (ISSUE 13 tentpole): when any chunk ran,
    # the campaign-close gather must have written Perfetto trace files on
    # root with events from EVERY host
    import glob as _glob

    trace_files = sorted(
        _glob.glob(os.path.join(run_dir, "campaigns", "*", "trace_*.json"))
    )
    trace_hosts = 0
    for tf in trace_files:
        with open(tf) as fh:
            payload = json.load(fh)
        pids = {e.get("pid") for e in payload.get("traceEvents", [])}
        trace_hosts = max(trace_hosts, len(pids))
    if multihost.is_root() and summary["member_steps"] > 0:
        assert trace_files, "no campaign trace assembled on root"
        assert trace_hosts == jax.process_count(), (
            trace_hosts,
            jax.process_count(),
        )
    if multihost.is_root():
        events = [
            e.get("event")
            for e in read_journal(
                os.path.join(run_dir, "journal.jsonl"), on_error="skip"
            )
        ]
        with open(os.path.join(out_dir, "result.json"), "w") as f:
            json.dump(
                {
                    "outcome": summary["outcome"],
                    "completed": summary["completed"],
                    "failed": summary["failed"],
                    "retried": summary["retried"],
                    "replans": summary["replans"],
                    # collective-sequence sanitizer counters (armed via
                    # RUSTPDE_SANITIZE in the chaos soak / bench mp leg)
                    "sanitizer": sanitizer.stats(),
                    "trace_files": len(trace_files),
                    "trace_hosts": trace_hosts,
                    "queue": srv.queue.counts(),
                    "slots": slots,
                    "nproc": jax.process_count(),
                    "drains": events.count("drain"),
                    "requeued": events.count("request_requeued"),
                    "replanned": events.count("campaign_replanned"),
                    "dt_adjusts": events.count("bucket_dt_adjust"),
                    "retries": events.count("request_retry"),
                    "restored_sched": sum(
                        1
                        for e in read_journal(
                            os.path.join(run_dir, "journal.jsonl"),
                            on_error="skip",
                        )
                        if e.get("event") == "request_scheduled"
                        and e.get("restored")
                        and e.get("steps_done", 0) > 0
                    ),
                },
                f,
            )


def mode_gang_serve(out_dir):
    from rustpde_mpi_tpu.config import ServeConfig, SubmeshConfig
    from rustpde_mpi_tpu.parallel import multihost
    from rustpde_mpi_tpu.serve import AdmissionError, SimServer
    from rustpde_mpi_tpu.serve.request import RequestError
    from rustpde_mpi_tpu.utils.journal import read_journal

    n_gang = int(os.environ.get("RUSTPDE_MP_GANG_REQUESTS", "2"))
    n_vmap = int(os.environ.get("RUSTPDE_MP_VMAP_REQUESTS", "3"))
    slots = int(os.environ.get("RUSTPDE_MP_SERVE_SLOTS", "2"))
    run_dir = os.path.join(out_dir, "serve")
    cfg = ServeConfig(
        run_dir=run_dir,
        slots=slots,
        max_queue=4 * (n_gang + n_vmap) + 8,
        chunk_steps=4,
        checkpoint_every_s=2.0,  # tight cadence: the gang SIGKILL must
        # leave a recent sharded slot-table checkpoint to restore from
        http_port=None,
        # 4 CPU devices, 2 processes: one 2-device gang slice (one device
        # from each process) + a 2-device default remainder.  34^2 is the
        # smallest grid whose spectral extent (32) divides the slice, so
        # shard_min_nx=34 makes it the flagship gang traffic.
        submesh=SubmeshConfig(shapes=(2,), shard_min_nx=34),
    )
    srv = SimServer(cfg)  # fault rides RUSTPDE_FAULT (gang scopes ok)
    if multihost.is_root():
        counts = srv.queue.counts()
        if sum(counts.values()) == 0:  # first incarnation only
            for seed in range(n_gang):
                # flagship sharded traffic: stamped submesh=2 at the door
                try:
                    srv.submit(
                        {
                            "ra": 1e4,
                            "pr": 1.0,
                            "nx": 34,
                            "ny": 34,
                            "dt": 0.01,
                            "horizon": 0.08 + (seed % 2) * 0.04,
                            "seed": 100 + seed,
                        }
                    )
                except AdmissionError:
                    pass
            for seed in range(n_vmap):
                # co-resident vmapped traffic on the default remainder
                try:
                    srv.submit(
                        {
                            "ra": 1e4,
                            "pr": 1.0,
                            "nx": 18,
                            "ny": 18,
                            "dt": 0.01,
                            "horizon": 0.08 + (seed % 3) * 0.02,
                            "seed": seed,
                        }
                    )
                except AdmissionError:
                    pass
            # admission containment (PR-18 satellite): a grid that must
            # shard but fits no configured shape is a typed door-time
            # rejection, never a durable poison pill in the queue
            reason = None
            try:
                srv.submit(
                    {
                        "ra": 1e4,
                        "pr": 1.0,
                        "nx": 259,
                        "ny": 259,
                        "dt": 0.01,
                        "horizon": 0.02,
                        "seed": 999,
                    }
                )
            except (RequestError, ValueError) as exc:
                reason = getattr(exc, "reason", None)
            assert reason == "no_submesh", reason
    summary = srv.serve()
    if multihost.is_root():
        events = [
            e.get("event")
            for e in read_journal(
                os.path.join(run_dir, "journal.jsonl"), on_error="skip"
            )
        ]
        with open(os.path.join(out_dir, "result.json"), "w") as f:
            json.dump(
                {
                    "outcome": summary["outcome"],
                    "completed": summary["completed"],
                    "failed": summary["failed"],
                    "retried": summary["retried"],
                    "replans": summary["replans"],
                    "queue": srv.queue.counts(),
                    "slots": slots,
                    "nproc": jax.process_count(),
                    "gang_formed": events.count("gang_formed"),
                    "gang_member_lost": events.count("gang_member_lost"),
                    "gang_parked": events.count("gang_parked"),
                    "gang_replanned": events.count("gang_replanned"),
                    "gang_form_failed": events.count("gang_form_failed"),
                    "submesh_rejected": events.count("submesh_rejected"),
                    "drains": events.count("drain"),
                    "requeued": events.count("request_requeued"),
                    "replanned": events.count("campaign_replanned"),
                    "retries": events.count("request_retry"),
                    "restored_sched": sum(
                        1
                        for e in read_journal(
                            os.path.join(run_dir, "journal.jsonl"),
                            on_error="skip",
                        )
                        if e.get("event") == "request_scheduled"
                        and e.get("restored")
                        and e.get("steps_done", 0) > 0
                    ),
                },
                f,
            )


def mode_integrity_serve(out_dir):
    """SDC soak over the 2-process mesh (integrity tentpole): the serve
    campaign runs with digests + shadow audits armed at cadence 1 and a
    single-strike quarantine ledger, while ``RUSTPDE_FAULT=bitflip@<n>:host1``
    silently flips one mantissa bit of a host-1-owned spectral column
    mid-campaign.  The audit must catch it, the strike must cross the
    quarantine threshold (typed IntegrityError), the scheduler must
    contain WITHOUT killing the replica (requeue-with-progress, unhealthy
    heartbeat), and every request must still complete — zero lost.  Root
    dumps summary + journal/ledger evidence for the parent."""
    from rustpde_mpi_tpu.config import IntegrityConfig, ServeConfig
    from rustpde_mpi_tpu.integrity import QuarantineLedger
    from rustpde_mpi_tpu.parallel import multihost
    from rustpde_mpi_tpu.serve import AdmissionError, SimServer
    from rustpde_mpi_tpu.utils.journal import read_journal

    n_req = int(os.environ.get("RUSTPDE_MP_SERVE_REQUESTS", "3"))
    run_dir = os.path.join(out_dir, "serve")
    cfg = ServeConfig(
        run_dir=run_dir,
        slots=2,
        max_queue=4 * n_req,
        chunk_steps=4,
        checkpoint_every_s=2.0,
        http_port=None,
        # cadence 1: every committed chunk is shadow-audited, so the one
        # injected flip cannot slip past; one strike quarantines, so the
        # containment path (IntegrityError -> requeue -> re-carve) fires
        # on the FIRST mismatch
        integrity=IntegrityConfig(cadence=1, strikes=1),
    )
    srv = SimServer(cfg)  # fault rides RUSTPDE_FAULT=bitflip@<n>:host1
    if multihost.is_root():
        counts = srv.queue.counts()
        if sum(counts.values()) == 0:  # first incarnation only
            for seed in range(n_req):
                try:
                    srv.submit(
                        {
                            "ra": 1e4,
                            "pr": 1.0,
                            "nx": 34,
                            "ny": 34,
                            "dt": 0.01,
                            "horizon": 0.08 + (seed % 2) * 0.04,
                            "seed": seed,
                        }
                    )
                except AdmissionError:
                    pass
    summary = srv.serve()
    if multihost.is_root():
        events = [
            e.get("event")
            for e in read_journal(
                os.path.join(run_dir, "journal.jsonl"), on_error="skip"
            )
        ]
        ledger = QuarantineLedger(run_dir, strikes=1)
        with open(os.path.join(out_dir, "result.json"), "w") as f:
            json.dump(
                {
                    "outcome": summary["outcome"],
                    "completed": summary["completed"],
                    "failed": summary["failed"],
                    "queue": srv.queue.counts(),
                    "nproc": jax.process_count(),
                    "bitflip_injected": events.count("bitflip_injected"),
                    "integrity_mismatch": events.count("integrity_mismatch"),
                    "integrity_rollback": events.count("integrity_rollback"),
                    "integrity_contained": events.count("integrity_contained"),
                    "device_quarantined": events.count("device_quarantined"),
                    "requeued": events.count("request_requeued"),
                    "quarantined": list(ledger.quarantined()),
                },
                f,
            )


def mode_sanitize_desync(out_dir):
    """Collective-sequence sanitizer exercise (tests/test_sanitizer.py).

    Drives a pure root_decides loop (one fixed-shape scalar broadcast per
    call, so a skipped call leaves the transport pairable) with the
    sanitizer armed from the environment.  With
    ``RUSTPDE_SANITIZE_INJECT=skip_broadcast@<n>:host1`` armed, host 1
    silently skips its <n>-th broadcast — the PR-10 drain-check bug shape —
    and BOTH ranks must raise a typed CollectiveDesyncError naming the
    divergent call site within one verification cadence, instead of
    wedging silently.  Each rank writes its own result file."""
    from rustpde_mpi_tpu.parallel import multihost, sanitizer
    from rustpde_mpi_tpu.parallel.sanitizer import CollectiveDesyncError

    sanitizer.reset()  # pick up the spawn env on a clean ring
    result = {"raised": None, "site": None, "seq": None, "message": None}
    try:
        for i in range(40):
            multihost.root_decides(i % 3 == 0)
        multihost.sync_hosts("sanitize-clean-done")
    except CollectiveDesyncError as exc:
        result["raised"] = "CollectiveDesyncError"
        result["site"] = exc.site
        result["seq"] = exc.seq
        result["message"] = str(exc)
    result["stats"] = sanitizer.stats()
    with open(
        os.path.join(out_dir, f"sanitize_rank{jax.process_index()}.json"), "w"
    ) as f:
        json.dump(result, f)


def main():
    port, pid, nproc, out_dir = (
        sys.argv[1],
        int(sys.argv[2]),
        int(sys.argv[3]),
        sys.argv[4],
    )
    mode = sys.argv[5] if len(sys.argv) > 5 else "basic"

    from rustpde_mpi_tpu.parallel import multihost

    started = multihost.initialize_distributed(
        coordinator_address=f"localhost:{port}",
        num_processes=nproc,
        process_id=pid,
    )
    assert started and jax.process_count() == nproc

    modes = {
        "basic": mode_basic,
        "sharded_run": mode_sharded_run,
        "bench_sharded": mode_bench_sharded,
        "serve_campaign": mode_serve_campaign,
        "gang_serve": mode_gang_serve,
        "integrity_serve": mode_integrity_serve,
        "sanitize_desync": mode_sanitize_desync,
    }
    if mode not in modes:
        raise SystemExit(f"unknown mode {mode!r}")
    try:
        modes[mode](out_dir)
    except BaseException:
        # durable per-rank traceback: a peer's abort can kill this process
        # mid-stderr-print, so the parent test would otherwise never see
        # WHICH exception started the cascade
        import traceback

        with open(os.path.join(out_dir, f"rank{pid}.err"), "w") as f:
            traceback.print_exc(file=f)
        raise
    print(f"RANK{pid} OK", flush=True)


if __name__ == "__main__":
    main()
