"""Multi-host (multi-slice / DCN) entry points.

The reference scales across nodes with MPI ranks (rsmpi over system MPI,
/root/reference/src/mpi/mod.rs); the JAX equivalent is one *controller per
host* with a global device mesh — intra-slice traffic rides ICI, inter-slice
DCN, and the same GSPMD/pencil code (parallel/mesh.py, parallel/decomp.py)
runs unchanged on the larger mesh.  This module is the thin glue:

* :func:`initialize_distributed` — ``jax.distributed.initialize`` with the
  standard env-var conventions (the MPI_Init analog).
* :func:`global_pencil_mesh` — the 1-D pencil mesh over every device of
  every host.
* :func:`host_local_array` / :func:`global_array` — host-slab <-> global
  array conversion for IO (the gather/scatter-to-root analog across hosts).
* :func:`sync_hosts` — barrier.
* :func:`allgather_host` / :func:`broadcast` — small host-value collectives
  (the sharded-checkpoint digest exchange and the root-decides handshakes
  in utils/resilience.py ride these).

Single-host processes (one chip, one four-chip host, the virtual CPU mesh)
can call everything here unchanged: initialization returns False without
touching the network and the conversions degenerate to identity, which is
what the single-controller tests exercise.  True multi-host execution needs one
process per host started with the same script (the driver/launcher's job),
exactly as the reference needs ``mpirun``.
"""

from __future__ import annotations

import json
import os

import jax
import numpy as np

from .mesh import make_mesh
from . import sanitizer as _sanitizer
from ..config import env_get


def _cluster_env_configured() -> bool:
    """True when the environment really describes a multi-host cluster — an
    initialization failure must then propagate, not silently degrade to N
    independent single-host runs.  A coordinator address is definitive; a
    worker-hostname list counts only when it names more than one host (TPU
    plugins set TPU_WORKER_HOSTNAMES=localhost even on one chip)."""
    if os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "MEGASCALE_COORDINATOR_ADDRESS"
    ):
        return True
    if "," in os.environ.get("TPU_WORKER_HOSTNAMES", ""):
        return True
    # schedulers jax.distributed auto-detects: gate on *per-step* launch
    # variables (set by srun/mpirun for this very process), not allocation-
    # level ones — a single `python` inside an --ntasks=8 batch allocation
    # is still a single-host run
    for var in ("SLURM_STEP_NUM_TASKS", "OMPI_COMM_WORLD_SIZE"):
        try:
            if int(os.environ.get(var, "1")) > 1:
                return True
        except ValueError:
            pass
    return False


#: pre-collective device fence (set_device_fence): while a campaign runs on
#: a PROPER sub-mesh, host-level collectives here (full-device barriers and
#: broadcasts) can start on the sub-mesh's IDLE complement immediately and
#: their wire traffic interleaves nondeterministically with the campaign's
#: still-in-flight collectives on the same transport pairs — gloo then
#: mispairs ops across hosts ("op.preamble.length <= op.nbytes").  A full
#: mesh never hits this: the barrier executable cannot start anywhere until
#: the step program releases the devices, so wire order is host-consistent.
#: The serve scheduler installs a fence that blocks on the active campaign's
#: dispatches; every entry point below runs it before touching the wire.
_device_fence = None


def set_device_fence(fn) -> None:
    """Install (``fn``) or clear (``None``) the pre-collective device fence —
    the serve scheduler's sub-mesh campaign guard (see ``_device_fence``)."""
    global _device_fence
    _device_fence = fn


def _fence() -> None:
    fence = _device_fence
    if fence is not None:
        fence()


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Initialize the multi-process runtime (MPI_Init analog).

    Arguments default to the standard env vars (JAX_COORDINATOR_ADDRESS,
    JAX_NUM_PROCESSES, JAX_PROCESS_ID); None values are passed through to
    ``jax.distributed.initialize`` so its scheduler auto-detection fills
    them in.  With no arguments and no cluster in the environment
    (:func:`_cluster_env_configured`) this returns False WITHOUT calling
    ``jax.distributed.initialize``.  Returns True if a multi-process runtime
    was initialized — callers need no branches, jax.devices() is global
    either way."""
    if num_processes is not None and (
        coordinator_address is None
        and os.environ.get("JAX_COORDINATOR_ADDRESS") is None
    ):
        raise ValueError(
            "num_processes given but no coordinator address (argument or "
            "JAX_COORDINATOR_ADDRESS)"
        )
    # CPU clusters need an explicit cross-process collectives backend: since
    # jax 0.4.37 a multi-process CPU computation without one dies with
    # "Multiprocess computations aren't implemented on the CPU backend".
    # Select gloo BEFORE backend init when the run is pinned to CPU (the
    # 2-process test/bench harness, tests/mp_worker.py); other platforms
    # keep their native transports (ICI/DCN).
    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    explicit = any(
        v is not None for v in (coordinator_address, num_processes, process_id)
    )
    if not explicit and not _cluster_env_configured():
        # plain single-host launch: nothing to join.  jax.distributed's own
        # auto-detection is NOT probed here — on a sealed host with no
        # network its metadata lookups can block for minutes
        return False
    # a cluster is configured (explicitly or via env) — failures are real
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return jax.process_count() > 1


def global_pencil_mesh() -> jax.sharding.Mesh:
    """1-D pencil mesh over all devices of all hosts — pass as ``mesh=`` to
    any model; pencil axes then span ICI within a slice and DCN across."""
    return make_mesh()


def process_index() -> int:
    """This host's rank (the reference's ``nrank``)."""
    return jax.process_index()


def is_root() -> bool:
    """Rank-0 check for root-guarded IO/logging
    (/root/reference/src/mpi/mod.rs:57-74)."""
    return jax.process_index() == 0


def global_array(host_local: np.ndarray, sharding) -> jax.Array:
    """Assemble per-host slabs into one global sharded array
    (scatter analog).  Identity-like on a single host."""
    if jax.process_count() == 1:
        return jax.device_put(host_local, sharding)
    from jax.experimental import multihost_utils

    return multihost_utils.host_local_array_to_global_array(
        host_local, sharding.mesh, sharding.spec
    )


def host_local_array(arr: jax.Array, spec: tuple | None = None) -> np.ndarray:
    """This host's slab of a global array (gather analog for per-host IO);
    the full array on a single host.

    The conversion needs a mesh+spec.  Arrays coming out of jitted steps
    carry an inferred GSPMDSharding (no mesh attached), so such arrays are
    first re-placed onto the canonical pencil mesh with ``spec`` (default:
    the spectral x-pencil layout every model state uses) — a same-device
    resharding, metadata-only when the layouts already agree."""
    if jax.process_count() == 1:
        return np.asarray(arr)  # lint-ok: RPD005 single-process: every shard is addressable by definition
    from jax.experimental import multihost_utils

    from .mesh import SPEC, make_mesh

    if not isinstance(arr.sharding, jax.sharding.NamedSharding):
        named = jax.sharding.NamedSharding(
            make_mesh(), jax.sharding.PartitionSpec(*(SPEC if spec is None else spec))
        )
        # jit-resharding rather than device_put: GSPMD pads non-divisible
        # dims (the odd spectral grid sizes), eager placement rejects them
        arr = jax.jit(lambda a: a, out_shardings=named)(arr)
    return multihost_utils.global_array_to_host_local_array(
        arr, arr.sharding.mesh, arr.sharding.spec
    )


def allgather_host(value) -> np.ndarray:
    """Allgather a small host value across processes: every host gets the
    stacked ``(nproc, ...)`` array (rank order).  The sharded-checkpoint
    commit uses this to exchange per-shard digests/byte counts so root can
    write the manifest without re-reading any shard file.  Single-host:
    the value with a length-1 leading axis."""
    _sanitizer.record("allgather", payload=value)
    if jax.process_count() == 1:
        return np.asarray(value)[None]  # lint-ok: RPD005 allgather payloads are small host values by contract
    _fence()
    from jax.experimental import multihost_utils

    out = np.asarray(multihost_utils.process_allgather(np.asarray(value)))  # lint-ok: RPD005 allgather payloads are small host values by contract
    _sanitizer.maybe_verify()
    return out


def broadcast(value, is_source: bool | None = None):
    """Root-decides broadcast of a small host value (the preemption/rollback
    handshake in utils/resilience.py and every serve-scheduler decision:
    rank 0 decides, every host acts on the same decision).  Identity on a
    single host; returns a numpy value.

    Like :func:`sync_hosts`, honors ``RUSTPDE_SYNC_TIMEOUT_S``: a peer that
    died while this host is blocked inside the collective would otherwise
    wedge the job forever — the root-coordinated scheduler runs several
    broadcasts per boundary, most of them outside any dispatch watchdog, so
    the structured-exit contract (journaled error stop, requests recovered
    on restart) needs the timeout here too."""
    if _sanitizer.skip_broadcast_injected():
        # armed desync injection (tests): this host skips the collective
        # entirely — no record, no broadcast — the PR-10 bug shape
        return np.asarray(value)  # lint-ok: RPD005 broadcast payloads are small host values by contract
    _sanitizer.record("broadcast", payload=value)
    if jax.process_count() == 1:
        return np.asarray(value)  # lint-ok: RPD005 broadcast payloads are small host values by contract
    _fence()
    from jax.experimental import multihost_utils

    def run():
        return multihost_utils.broadcast_one_to_all(
            np.asarray(value), is_source=is_source  # lint-ok: RPD005 broadcast payloads are small host values by contract
        )

    timeout = float(env_get("RUSTPDE_SYNC_TIMEOUT_S", "0") or 0.0)
    if timeout <= 0:
        out = run()
    else:
        from ..utils.resilience import call_with_watchdog

        out = call_with_watchdog(run, timeout, label="broadcast")
    _sanitizer.maybe_verify()
    return out


def allgather_bytes(data: bytes) -> list[bytes]:
    """Allgather one variable-length byte blob per host: every host returns
    ``[host0_bytes, host1_bytes, ...]`` in rank order.  Two allgathers ride
    underneath — a length exchange, then a padded uint8 buffer — because
    ``process_allgather`` needs identical shapes on every host.  The
    telemetry layer's fleet aggregation (metrics snapshots, request-trace
    gathers) rides this one primitive.  Single-host: ``[data]``."""
    if jax.process_count() == 1:
        return [bytes(data)]
    blob = np.frombuffer(bytes(data), np.uint8)
    lengths = allgather_host(np.int64(blob.size))
    width = max(1, int(lengths.max()))
    padded = np.zeros(width, np.uint8)
    padded[: blob.size] = blob
    stack = allgather_host(padded)
    return [
        bytes(stack[i, : int(lengths[i])]) for i in range(stack.shape[0])
    ]


def broadcast_obj(obj=None):
    """Root-decides broadcast of an arbitrary JSON-able host object (the
    serve scheduler's per-boundary decision plans: bucket keys, slot
    claim/refill assignments, retry/requeue verdicts).  Non-root callers
    pass anything (ignored); every host returns root's object.  Two
    broadcasts ride underneath — a length, then a padded byte buffer —
    because ``broadcast_one_to_all`` needs an identical shape on every
    host.  Identity on a single host.

    JSON round-trips tuples into lists; callers holding tuple-shaped keys
    re-tuple with :func:`tuplify`."""
    import jax

    if jax.process_count() == 1:
        return obj
    payload = b""
    if is_root():
        payload = json.dumps(obj).encode("utf-8")
    n = int(broadcast(np.int64(len(payload))))
    buf = np.zeros(n, dtype=np.uint8)
    if is_root():
        buf[:] = np.frombuffer(payload, dtype=np.uint8)
    # the collective may widen the dtype (psum upcast): cast back before
    # reinterpreting the element values as utf-8 bytes
    data = np.asarray(broadcast(buf)).astype(np.uint8)  # lint-ok: RPD005 broadcast returns a host numpy value
    return json.loads(data.tobytes().decode("utf-8"))


def root_decides(local: bool) -> bool:
    """Root's verdict for a host flag that leads into a collective
    (preemption/drain stops, cadence checkpoints, serve-loop exits): rank
    0's value is broadcast so every host takes the same branch — hosts
    evaluating signals or wall clocks locally would disagree and wedge the
    next collective.  A stray local flag on a non-root host is therefore
    deliberately IGNORED.  Single-host (or uninitialized runtime): the
    local flag.  One copy of the primitive — the resilient runner and the
    serve scheduler both ride it, so the handshake cannot drift."""
    _sanitizer.record("root_decides")
    try:
        if jax.process_count() == 1:
            return bool(local)
    except Exception:
        return bool(local)
    return bool(int(broadcast(np.int32(1 if local else 0))))


def tuplify(obj):
    """Recursively convert lists back to tuples (the inverse of the
    tuple->list coercion a JSON round-trip applies to compat keys)."""
    if isinstance(obj, list):
        return tuple(tuplify(v) for v in obj)
    return obj


def sync_hosts(tag: str = "barrier", timeout_s: float | None = None) -> None:
    """Cross-host barrier (the reference's MPI barrier,
    src/field_mpi/io_mpi_sequ.rs:46); no-op single-host.

    ``sync_global_devices`` blocks FOREVER if a peer host died (the silent
    job-wide hang that ate PR 1's tier-1 budget).  ``RUSTPDE_SYNC_TIMEOUT_S``
    (default off) arms a watchdog: after the deadline every thread's stack is
    dumped to stderr together with the barrier tag, and a structured
    :class:`~rustpde_mpi_tpu.utils.resilience.DispatchHang` is raised so the
    scheduler sees a crash it can restart instead of a wedged job.

    ``timeout_s`` overrides the env knob for callers with a tighter
    deadline contract than the job-wide default — the gang barrier
    (serve/fleet/gang.py) passes ``RUSTPDE_GANG_SYNC_TIMEOUT_S`` here so
    a dead gang member surfaces in seconds, not the global sync budget."""
    _sanitizer.record("sync", tag=tag)
    if jax.process_count() == 1:
        return
    _fence()
    from jax.experimental import multihost_utils

    if timeout_s is not None:
        timeout = float(timeout_s)
    else:
        timeout = float(env_get("RUSTPDE_SYNC_TIMEOUT_S", "0") or 0.0)
    if timeout <= 0:
        multihost_utils.sync_global_devices(tag)
    else:
        from ..utils.resilience import call_with_watchdog

        call_with_watchdog(
            lambda: multihost_utils.sync_global_devices(tag),
            timeout,
            label=f"sync_hosts({tag!r})",
        )
    _sanitizer.maybe_verify()
