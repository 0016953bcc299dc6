"""Global configuration for rustpde_mpi_tpu.

The reference framework (rustpde-mpi, /root/reference/src/lib.rs) computes in
f64 everywhere.  On TPU, f64 is emulated and slow, so precision is a run-time
choice here:

* ``RUSTPDE_X64=1`` (default) enables ``jax_enable_x64`` at import time and all
  operators/states default to float64 — required for the 1e-6 Nusselt-parity
  gate against the CPU reference.
* ``RUSTPDE_X64=0`` leaves JAX in f32 mode for maximum TPU throughput; solver
  setup (eigendecompositions, LU factorizations) still happens on the host in
  numpy f64 and is rounded once at the end.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field


# -- Environment knob registry -------------------------------------------------
#
# Every ``RUSTPDE_*`` environment knob in the repo is declared HERE, once,
# with its default and one line of documentation.  Library modules read
# knobs through :func:`env_get` (which refuses unregistered names), the
# README "Environment knobs" table mirrors this registry, and
# tests/test_lint.py diffs all three against a grep of the source tree —
# so a new knob cannot ship unregistered or undocumented, and a typo'd
# read dies loudly instead of silently returning the default forever.
# Driver-side code (scripts/, tests/, examples/) may keep raw
# ``os.environ`` reads, but its knob NAMES must still be registered
# (scope "test"); tools/lint rule RPD006 enforces the read-path
# rule inside the package (utils/faults.py stays raw by design: it must
# not import this jax-loading module from inside the two-phase commit
# window).


@dataclass(frozen=True)
class EnvKnob:
    """One registered environment knob: ``default`` is documentation of the
    effective default (None = unset means off/auto), ``scope`` names the
    consuming layer (``lib`` | ``test``)."""

    name: str
    default: str | None
    doc: str
    scope: str = "lib"


_ENV_KNOBS: dict[str, EnvKnob] = {}


class UnregisteredKnobError(KeyError):
    """A ``RUSTPDE_*`` environment variable was read through
    :func:`env_get` without being declared in the knob registry."""


def register_knob(name: str, default: str | None, doc: str, scope: str = "lib") -> None:
    _ENV_KNOBS[name] = EnvKnob(name=name, default=default, doc=doc, scope=scope)


def env_knobs() -> dict[str, EnvKnob]:
    """The full knob registry (name -> :class:`EnvKnob`), a copy."""
    return dict(_ENV_KNOBS)


def env_get(name: str, default: str | None = None) -> str | None:
    """``os.environ.get`` with a registration gate: reading an unregistered
    ``RUSTPDE_*`` name raises :class:`UnregisteredKnobError` (a typo'd knob
    must die loudly, not silently read its default forever).  The
    ``default`` argument keeps call-site semantics — the registry default
    is documentation, not a fallback."""
    if name.startswith("RUSTPDE_") and name not in _ENV_KNOBS:
        raise UnregisteredKnobError(
            f"environment knob {name!r} is not registered in "
            "config.env_knobs() — declare it with config.register_knob"
        )
    return os.environ.get(name, default)


# precision / numerics
register_knob("RUSTPDE_X64", "1", "f64 master switch (0 = f32 throughput mode)")
register_knob("RUSTPDE_MATMUL_PRECISION", "highest",
              "global jax matmul precision (high = 3-pass bf16 on TPU)")
register_knob("RUSTPDE_FWD_PRECISION", "highest",
              "dealiased convection forward-transform matmul precision")
register_knob("RUSTPDE_SYNTH_PRECISION", "high",
              "synthesis (spectral->physical) matmul precision")
register_knob("RUSTPDE_SOLVE_PRECISION", None,
              "scoped matmul precision around the four implicit solves")
register_knob("RUSTPDE_F64_HYBRID", None,
              "1 = f32 convection transforms feeding f64 solves under X64")
# operator / kernel selection
register_knob("RUSTPDE_FORCE_TPU_PATH", None,
              "1 = exercise the TPU execution paths on CPU CI")
register_knob("RUSTPDE_SEP", "auto", "separable y-operator application mode")
register_knob("RUSTPDE_FOLDED", "1", "folded (kept-row) operator storage")
register_knob("RUSTPDE_FOURSTEP", "auto", "four-step factored transform mode")
register_knob("RUSTPDE_FOURSTEP_MIN", "2048", "four-step min size (dft)")
register_knob("RUSTPDE_FOURSTEP_MIN_C2C", "1024", "four-step min size (c2c)")
register_knob("RUSTPDE_FOURSTEP_MIN_DCT", "8192", "four-step min size (dct)")
register_knob("RUSTPDE_FOURSTEP_N1", None, "forced four-step N1 split factor")
register_knob("RUSTPDE_FAST_DERIV", "auto", "banded fast-derivative mode")
register_knob("RUSTPDE_FAST_DERIV_MIN", "2048", "fast-derivative min size")
register_knob("RUSTPDE_CONV_KERNEL", "dense",
              "convection chain: dense per-GEMM chain | pallas fused kernel")
register_knob("RUSTPDE_STEP_KERNEL", "dense",
              "implicit-solve stages: dense solver chain | pallas fused megakernel")
register_knob("RUSTPDE_PALLAS_CONV_BLOCK", "256",
              "pallas conv kernel physical-x tile")
register_knob("RUSTPDE_PALLAS_CONV_BLOCK_K", "512",
              "pallas conv kernel spectral-y contraction tile")
register_knob("RUSTPDE_TRANSPOSE", "alltoall",
              "pencil transpose collective: alltoall | ring")
register_knob("RUSTPDE_RING_IMPL", "pallas",
              "ring transpose implementation: pallas remote-copy | ppermute")
register_knob("RUSTPDE_SPLIT_SEP_FALLBACK", "manual",
              "split-sep periodic under a mesh: manual shard_map | eager triage")
register_knob("RUSTPDE_FORCE_FUSED_GSPMD", None,
              "1 = pin the known-miscompiling fused GSPMD split-sep path")
# physics observability (models/stats.py in-scan statistics engine)
register_knob("RUSTPDE_STATS", None,
              "1 = arm the in-scan physics-stats engine on from_config DNS models")
register_knob("RUSTPDE_STATS_STRIDE", "16",
              "in-scan stats sampling stride (steps between samples)")
register_knob("RUSTPDE_STATS_TAIL_WARN", "1e-3",
              "spectral-tail energy fraction above which resolution_warning fires")
register_knob("RUSTPDE_STATS_BUDGET_WARN", "0.5",
              "Nu budget-closure residual above which budget_drift fires")
# end-to-end integrity (integrity/: on-device state digests, shadow
# re-execution audits, device quarantine)
register_knob("RUSTPDE_INTEGRITY", None,
              "1 = arm on-device state digests + shadow re-execution audits "
              "on from_config DNS models")
register_knob("RUSTPDE_INTEGRITY_CADENCE", "8",
              "committed chunks between shadow re-execution audits (digests "
              "stream every chunk; 0 = digests only, never audit)")
register_knob("RUSTPDE_VOTE_RATE", "0",
              "fleet proxy cross-replica voting: fraction of requests "
              "double-assigned and digest-compared at completion (0..1)")
# telemetry
register_knob("RUSTPDE_TELEMETRY", "1", "telemetry master switch")
register_knob("RUSTPDE_TRACE", "1", "flight-recorder span tracing switch")
register_knob("RUSTPDE_TRACE_EVENTS", "4096", "flight-recorder ring capacity")
register_knob("RUSTPDE_METRICS_DUMP_S", "60", "metrics.jsonl dump cadence")
register_knob("RUSTPDE_REQTRACE", "1",
              "per-request distributed tracing switch (trace ids still mint)")
register_knob("RUSTPDE_REQTRACE_EVENTS", "16384",
              "request-trace per-process event capacity per campaign")
register_knob("RUSTPDE_PROFILE_MAX_S", "60",
              "cap on one POST /profile (or perf_degraded auto) capture")
# resilience / watchdogs / fault injection
register_knob("RUSTPDE_DISPATCH_TIMEOUT_S", None, "device-dispatch hang watchdog")
register_knob("RUSTPDE_SYNC_TIMEOUT_S", "0",
              "barrier/broadcast watchdog (0 = off): peer death -> DispatchHang")
register_knob("RUSTPDE_IO_TIMEOUT_S", None, "async checkpoint writer watchdog")
register_knob("RUSTPDE_FAULT", None,
              "fault injection <nan|spike|kill|slow|bitflip>@<step>"
              "[:host<p>|:member<k>|:gang<g>[member<m>]]")
register_knob("RUSTPDE_GANG_SYNC_TIMEOUT_S", "0",
              "gang-barrier watchdog (0 = off): a dead gang member trips "
              "this deadline and surfaces as typed GangMemberLost instead "
              "of a wedged collective")
register_knob("RUSTPDE_SHARD_CRASH", None,
              "two-phase commit window kill <after_shard|before_manifest>@<step>[:host<p>]")
register_knob("RUSTPDE_SPIKE_FACTOR", None, "spike fault velocity scale override")
# fleet layer (serve/fleet/: replicated front door + queue-level leases)
register_knob("RUSTPDE_LEASE_TTL_S", "15",
              "bucket-lease heartbeat TTL: a replica silent past this is "
              "broken by survivors and its requests re-claimed")
register_knob("RUSTPDE_FLEET_REPLICA_ID", None,
              "stable replica identity for lease/heartbeat files "
              "(unset = <hostname>-<pid>)")
register_knob("RUSTPDE_FLEET_HEARTBEAT_S", None,
              "lease/replica heartbeat cadence (unset = lease_ttl/3)")
register_knob("RUSTPDE_FLEET_QUOTA", None,
              "default per-tenant admission quota (queued+running; "
              "unset = unlimited)")
register_knob("RUSTPDE_PREEMPT_NOTICE_S", None,
              "preemption-notice window: SIGTERM on a fleet replica parks "
              "every running slot durably + releases leases within this "
              "many seconds (unset = full graceful drain)")
register_knob("RUSTPDE_PROXY_TOKENS", None,
              "comma-separated bearer-token allowlist for proxy mutating "
              "endpoints (unset = open admission)")
# collective-sequence sanitizer (parallel/sanitizer.py)
register_knob("RUSTPDE_SANITIZE", "0",
              "1 = record every multihost collective + cadenced cross-host "
              "sequence verification (CollectiveDesyncError on divergence)")
register_knob("RUSTPDE_SANITIZE_CADENCE", "32",
              "collectives between cross-host sequence verifications")
register_knob("RUSTPDE_SANITIZE_RING", "256",
              "sanitizer per-host ring capacity (records kept for diagnosis)")
register_knob("RUSTPDE_SANITIZE_INJECT", None,
              "desync injection skip_broadcast@<n>[:host<p>] (tests only)")
# persistent compile cache (cold-start elimination: serialized XLA
# executables survive process death, so restarts / incarnations / elastic
# re-plans reload instead of recompiling)
register_knob("RUSTPDE_COMPILE_CACHE", "1",
              "0 = do NOT arm the persistent JAX compilation cache in "
              "long-lived entry points (serve/replica/resilient sessions)")
# test harness (tests/ — raw reads allowed, names registered)
register_knob("RUSTPDE_BENCH_SHARDED_N", "130",
              "mp_worker bench_sharded grid size override", "test")
register_knob("RUSTPDE_SLOW", None, "1 = run the slow test tier", "test")
register_knob("RUSTPDE_TEST_BUDGET_S", "45", "per-test wall budget (fast tier)", "test")
register_knob("RUSTPDE_TEST_TRACEBACK_S", None,
              "faulthandler dump_traceback_later arming", "test")
register_knob("RUSTPDE_MP_BLOCKING_IO", None,
              "1 = pin synchronous shard writes in mp workers", "test")
register_knob("RUSTPDE_MP_SERVE_REQUESTS", "5",
              "mp_worker serve_campaign request count", "test")
register_knob("RUSTPDE_MP_SERVE_SLOTS", "2",
              "mp_worker serve_campaign slot count", "test")
register_knob("RUSTPDE_MP_GANG_REQUESTS", "2",
              "mp_worker gang_serve sharded (gang-scheduled) request count", "test")
register_knob("RUSTPDE_MP_VMAP_REQUESTS", "3",
              "mp_worker gang_serve vmapped co-resident request count", "test")
register_knob("RUSTPDE_SERVE_SOAK_REQUESTS", None,
              "serve chaos soak request count", "test")


import jax
import numpy as np

X64: bool = env_get("RUSTPDE_X64", "1") != "0"

if X64:
    jax.config.update("jax_enable_x64", True)

# Spectral transforms/solves are precision-critical: TPU f32 matmuls default
# to bf16 MXU passes (~1e-2 relative error), which destroys spectral accuracy.
# "highest" (default) keeps true f32 (or f64 under x64) accumulation via
# 6-pass bf16; RUSTPDE_MATMUL_PRECISION=high selects the 3-pass variant,
# whose Nu drift at the 129^2 parity config stayed within the f32 noise
# floor when last checked (2026-07, PARITY.json protocol).
MATMUL_PRECISION = env_get("RUSTPDE_MATMUL_PRECISION", "highest")
jax.config.update("jax_default_matmul_precision", MATMUL_PRECISION)


_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def compile_cache_dir() -> str:
    """Where the persistent compile cache lives: ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it (the directory is placed from OUTSIDE the
    program — this code neither overrides nor rewrites the variable), else
    the fixed ``<repo>/.jax_cache``.  The path is part of the cache key, so
    it must not move between runs."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _REPO_CACHE


def enable_compilation_cache() -> str:
    """Enable JAX's persistent compilation cache at :func:`compile_cache_dir`
    (every executable is kept: no minimum entry size, 0.5 s minimum compile
    time unless the ``JAX_PERSISTENT_CACHE_*`` variables say otherwise).
    Call before the first jit dispatch; idempotent.  Touches only
    ``jax.config`` — never the environment and never a backend, so a
    launcher parent can arm it and still hold no device."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes",
        int(os.environ.get("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")),
    )
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")),
    )
    return path


_cache_armed: str | None = None


def ensure_compile_cache() -> str | None:
    """Idempotently arm the persistent compile cache in a long-lived entry
    point (``SimServer.serve``, ``replica_main``, ``ResilientRunner.session``,
    the examples drivers).  ``RUSTPDE_COMPILE_CACHE=0`` disables arming
    entirely (returns None — no cache is read or written).  Returns the
    cache path when armed.  Spawned children resolve the same directory by
    the same rule (:func:`compile_cache_dir`): an inherited
    ``JAX_COMPILATION_CACHE_DIR``, else the repo-fixed default."""
    global _cache_armed
    if env_get("RUSTPDE_COMPILE_CACHE", "1") == "0":
        return None
    if _cache_armed is None:
        _cache_armed = enable_compilation_cache()
    return _cache_armed


def compile_cache_env() -> dict:
    """The cache variables of THIS process's environment, for a launcher to
    seed into a custom child ``env`` snapshot that lacks them — so a child
    resolves the same :func:`compile_cache_dir` as its parent.  Empty when
    the environment sets none (parent and child then share the repo-fixed
    default)."""
    names = (
        "JAX_COMPILATION_CACHE_DIR",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
        "RUSTPDE_COMPILE_CACHE",
    )
    return {n: os.environ[n] for n in names if n in os.environ}


def host_cache_dir() -> str:
    """Root for host-side factorization caches (modal eigs, dense inverses):
    a ``host/`` subdir of :func:`compile_cache_dir`."""
    return os.path.join(compile_cache_dir(), "host")


def host_cache_store(path: str, save_fn) -> None:
    """Best-effort atomic publish of a host cache entry: ``save_fn(tmp)``
    writes the temp file (suffix chosen by the caller), then os.replace."""
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp{os.path.splitext(path)[1]}"
        save_fn(tmp)
        os.replace(tmp, path)
    except OSError:
        pass


def real_dtype():
    """Default real dtype for device arrays."""
    return np.float64 if X64 else np.float32


def complex_dtype():
    """Default complex dtype for device arrays."""
    return np.complex128 if X64 else np.complex64


class UnknownPlatformError(RuntimeError):
    """``jax.devices()[0].platform`` is one this package has no execution
    path for — it must be named here, never guessed TPU-like."""


def default_platform() -> str:
    return jax.devices()[0].platform


def is_tpu_like() -> bool:
    """True when the TPU execution paths run: matmul transforms, dense ADI
    solves, fast-diagonalisation Poisson, split Re/Im Fourier axes.  That is
    ``platform == "tpu"``, or ``RUSTPDE_FORCE_TPU_PATH=1`` so the CPU suite
    (tests/conftest.py) can exercise those paths.  CPU and GPU run the
    FFT/complex/banded reference path; any other platform raises."""
    if env_get("RUSTPDE_FORCE_TPU_PATH") == "1":
        return True
    platform = default_platform()
    if platform == "tpu":
        return True
    if platform in ("cpu", "gpu", "cuda", "rocm"):
        return False
    raise UnknownPlatformError(
        f"platform {platform!r}: expected 'tpu' (matmul path) or "
        "cpu/gpu (FFT reference path)"
    )


def supports_complex() -> bool:
    """False on the TPU path by design: the transforms there are dense
    real-valued GEMMs on the MXU with Fourier axes in a split Re/Im
    representation (XLA's TPU FFT is not competitive with the MXU at these
    sizes), and complex128 does not exist on a TPU at all, so the f64
    parity mode could not use complex spectra anyway."""
    return not is_tpu_like()


@dataclass
class StabilityConfig:
    """Knobs for the proactive stability governor
    (:class:`~rustpde_mpi_tpu.utils.governor.StabilityGovernor` + the
    on-device sentinels compiled into the scanned step when a model's
    ``set_stability`` is called).

    * ``target_cfl`` — the Courant number the dt controller drives toward,
    * ``max_cfl`` — the hard on-device ceiling: a chunk whose per-step CFL
      exceeds it early-exits the scan with a ``pre_divergence`` status
      *before* NaNs propagate (recovered by a cheap in-memory rollback of
      just that chunk),
    * ``ladder_ratio`` — geometric spacing of the dt ladder the controller
      quantizes to (the dt-baked solver factorizations are cached per rung,
      so the re-jit/refactorization count over a long run is bounded by the
      ladder size),
    * ``dt_min``/``dt_max`` — ladder bounds (None: ``dt_max`` anchors at the
      run's initial dt, ``dt_min`` at ``dt_max * ladder_ratio**-10``),
    * ``grow_after`` — healthy chunks at a rung before the governor climbs
      back up the ladder (the regrowth the reactive backoff lacks),
    * ``shrink_cfl`` — proactive shrink threshold (None:
      ``0.85 * max_cfl``): a chunk whose max CFL exceeds it steps the ladder
      down *without* any rollback,
    * ``member_pin_patience`` — consecutive pre-divergence catches pinned on
      the same ensemble member before that member is declared dead and
      handed to the ``respawn_dead`` machinery."""

    target_cfl: float = 0.5
    max_cfl: float = 1.0
    ladder_ratio: float = 2.0
    dt_min: float | None = None
    dt_max: float | None = None
    grow_after: int = 4
    shrink_cfl: float | None = None
    member_pin_patience: int = 3


@dataclass
class StatsConfig:
    """Knobs for the in-scan physics-statistics engine
    (:class:`~rustpde_mpi_tpu.models.stats.StatsEngine`, armed via a DNS
    model's ``set_stats``): running spectral/profile/budget accumulators
    updated ON DEVICE inside the scanned step chunk, vmapped per ensemble
    member, carried through checkpoints bit-exactly.

    * ``stride`` — steps between samples (None: ``RUSTPDE_STATS_STRIDE``,
      default 16).  The sample cost is a handful of extra syntheses, so the
      amortized overhead scales as ~1/stride (not measured on the chip),
    * ``tail_warn`` — spectral-tail energy fraction (top third of the
      ortho spectrum, per field/axis) above which the runner journals a
      typed ``resolution_warning`` (None: ``RUSTPDE_STATS_TAIL_WARN``),
    * ``budget_warn`` — Nu budget-closure residual (plate-flux Nu vs the
      exact-relation ``1 + <uy*T> * 2*sy/ka``) above which the runner
      journals a typed ``budget_drift`` (None:
      ``RUSTPDE_STATS_BUDGET_WARN``).

    The hard contract (CI-asserted like the sentinel/telemetry
    layers): the accumulators READ the state and never feed back — the
    state trajectory is bit-identical stats-on vs stats-off."""

    stride: int | None = None
    tail_warn: float | None = None
    budget_warn: float | None = None


@dataclass
class IntegrityConfig:
    """Knobs for the end-to-end integrity layer (``integrity/``, armed via
    a DNS model's ``set_integrity``): an on-device state digest (bitcast
    XOR/add fold, see :func:`~rustpde_mpi_tpu.integrity.digest_tree`)
    streamed with the observables futures after every committed chunk, plus
    sampled shadow re-execution audits in the resilient runner.

    * ``cadence`` — committed chunks between audits (None:
      ``RUSTPDE_INTEGRITY_CADENCE``, default 8; 0 = stream digests but
      never audit).  An audit replays the just-completed chunk from the
      retained chunk-start copy and compares digests — deterministic XLA
      means bit-equal or corrupted,
    * ``strikes`` — audit mismatches charged to one device before the
      quarantine ledger journals ``device_quarantined`` and the serve
      scheduler re-carves sub-meshes around it,
    * ``strike_ttl_s`` — ledger strike expiry window: strikes older than
      this no longer count toward the threshold (transient upsets decay,
      sticky-bad silicon accumulates).

    The hard contract (CI-asserted like the stats engine): the digest READS
    the state and never feeds back — the trajectory is bit-identical
    integrity-on vs integrity-off."""

    cadence: int | None = None
    strikes: int = 2
    strike_ttl_s: float = 3600.0

    def resolved_cadence(self) -> int:
        if self.cadence is not None:
            return int(self.cadence)
        return int(env_get("RUSTPDE_INTEGRITY_CADENCE", "8") or 8)


@dataclass
class IOConfig:
    """Knobs for the overlapped I/O pipeline (utils/io_pipeline.py).

    * ``async_checkpoints`` — cadence checkpoints are fetched to host on the
      main thread (:func:`~rustpde_mpi_tpu.utils.checkpoint.snapshot_to_host`,
      the one device sync a checkpoint inherently needs) and serialized +
      digest-stamped + fsynced on a background worker while the device steps
      the next chunks.  Edge checkpoints (anchor/final/preempt) stay
      effectively synchronous — the runner drains right after submitting
      them.  On multihost meshes the WRITE side runs through per-host
      background shard writers (each host overlaps its own shard
      serialization; the two-phase manifest commit happens collectively at
      the next chunk boundary, after every host drained its writer —
      drain-before-barrier), while ``overlap_dispatch`` stays disabled:
      a lagged break check resolving on per-host device timing would
      desynchronize the collective dispatch sequence, so break decisions
      remain un-lagged and root-broadcast.
      Durability is unchanged: writes are still atomic and verified, the
      writer drains before any rollback/resume read, and a write failure
      re-raises at the next submit/drain (collectively, on the sharded
      path: no manifest is committed when any host failed).
    * ``overlap_dispatch`` — dispatch double-buffering in the chunked
      driver: break checks + callback observables ride futures (one-chunk
      lag, see ``integrate(overlap=...)``) instead of fencing the device
      queue every boundary.  Single-process only (see above).
    * ``sharded_checkpoints`` — the distributed two-phase checkpoint format
      (utils/checkpoint.write_sharded_snapshot: per-host shard files +
      root manifest commit marker).  ``None`` (default) = auto: sharded on
      multi-process runtimes, gathered single-file otherwise; ``True``
      forces the sharded format (CI exercises it on the single-process
      virtual mesh); ``False`` pins the legacy gathered writer (which
      REQUIRES fully-addressable state — it cannot checkpoint a real
      multi-controller mesh).
    * ``queue_depth`` — bounded in-flight background writes: a submission
      past the depth blocks (back-pressure), so host memory holds at most
      ``queue_depth`` pending snapshots and cadence can never outrun disk.
    * ``diag_lag`` — boundaries a diagnostics emission may trail the device
      before the callback blocks for it (0 = synchronous printing).
    """

    async_checkpoints: bool = True
    overlap_dispatch: bool = True
    sharded_checkpoints: bool | None = None
    queue_depth: int = 1
    diag_lag: int = 1

    @classmethod
    def blocking(cls) -> "IOConfig":
        """Fully synchronous IO (the pre-pipeline behavior)."""
        return cls(async_checkpoints=False, overlap_dispatch=False, diag_lag=0)


@dataclass
class ResilienceConfig:
    """Knobs for :class:`~rustpde_mpi_tpu.utils.resilience.ResilientRunner`
    (field names match the runner's keyword arguments; build one via
    ``ResilientRunner.from_config(pde, cfg.resilience, max_time)``).

    ``checkpoint_every_s``/``checkpoint_every_t`` are the wall-clock and
    sim-time checkpoint cadences (either may be None); ``keep`` is the
    rolling retention window; ``dt_backoff`` is the divergence-retry step
    shrink factor with ``dt_min`` as its hard floor (so compounding backoff
    cannot drive dt toward denormals); ``respawn_seed`` carries the PRNG
    seed for ``respawn_dead`` donor perturbations (recovery runs are
    reproducible when set); ``dispatch_timeout_s`` arms the device-dispatch
    hang watchdog (None = RUSTPDE_DISPATCH_TIMEOUT_S env, unset = off);
    ``stability`` enables the proactive governor
    (:class:`StabilityConfig`)."""

    run_dir: str = "data/resilient"
    checkpoint_every_s: float | None = 300.0
    checkpoint_every_t: float | None = None
    keep: int = 3
    max_retries: int = 3
    dt_backoff: float = 0.5
    dt_min: float = 0.0
    respawn_members: bool = False
    respawn_amp: float = 1e-3
    respawn_seed: int | None = None
    dispatch_timeout_s: float | None = None
    resume: bool = True
    stability: StabilityConfig | None = None
    # overlapped-IO pipeline knobs (None = IOConfig() defaults: async
    # cadence checkpoints + dispatch double-buffering ON)
    io: IOConfig | None = None


@dataclass
class FleetConfig:
    """Knobs for the fleet layer (serve/fleet/): N stateless proxy
    processes and M ``SimServer`` replicas over ONE shared durable queue,
    coordinated by queue-level lease files — no consensus service, the
    fsynced atomic-rename lifecycle is the substrate.

    * ``replica_id`` — stable identity for lease/heartbeat files (empty:
      ``RUSTPDE_FLEET_REPLICA_ID`` env, else ``<hostname>-<pid>``),
    * ``lease_ttl_s`` — a lease whose heartbeat has not advanced for this
      long (observer-monotonic, clock-skew tolerant) is STALE: survivors
      break it and re-claim its requests (None: ``RUSTPDE_LEASE_TTL_S``,
      default 15),
    * ``heartbeat_s`` — lease + replica-status heartbeat cadence (None:
      ``RUSTPDE_FLEET_HEARTBEAT_S``, else ``lease_ttl_s / 3``),
    * ``default_quota`` — per-tenant admission bound over queued+running
      requests (None: ``RUSTPDE_FLEET_QUOTA``, unset = unlimited); the
      429 carries ``Retry-After`` + the live queue depth,
    * ``quotas`` — per-tenant overrides of ``default_quota``,
    * ``preempt`` — let an at-risk deadline request park a running
      best-effort lane (requeue-with-state through the durable
      continuation dir, loss-free),
    * ``preempt_slack_s`` — remaining deadline slack below which a queued
      interactive request is AT RISK and triggers preemption,
    * ``durable_park`` — persist parked member states into
      ``parked/<id>/`` continuation dirs (two-phase: state shard +
      manifest commit marker) so requeue-with-state survives replica
      SIGKILL.  Off only for A/B debugging — fleet HA rides on it."""

    replica_id: str = ""
    lease_ttl_s: float | None = None
    heartbeat_s: float | None = None
    default_quota: int | None = None
    quotas: dict = field(default_factory=dict)
    preempt: bool = True
    preempt_slack_s: float = 30.0
    durable_park: bool = True

    def resolved_replica_id(self) -> str:
        if self.replica_id:
            return str(self.replica_id)
        rid = env_get("RUSTPDE_FLEET_REPLICA_ID")
        if rid:
            return rid
        import socket

        return f"{socket.gethostname()}-{os.getpid()}"

    def resolved_ttl(self) -> float:
        if self.lease_ttl_s is not None:
            return float(self.lease_ttl_s)
        return float(env_get("RUSTPDE_LEASE_TTL_S", "15"))

    def resolved_heartbeat(self) -> float:
        if self.heartbeat_s is not None:
            return float(self.heartbeat_s)
        hb = env_get("RUSTPDE_FLEET_HEARTBEAT_S")
        return float(hb) if hb else self.resolved_ttl() / 3.0

    def resolved_quota(self, tenant: str) -> int | None:
        if tenant in self.quotas:
            q = self.quotas[tenant]
            return None if q is None else int(q)
        if self.default_quota is not None:
            return int(self.default_quota)
        q = env_get("RUSTPDE_FLEET_QUOTA")
        return int(q) if q else None


@dataclass
class AutoscaleConfig:
    """Control law for the fleet autoscaler (serve/fleet/autoscaler.py): a
    controller that reads the signals the fleet already exports (queue
    depth + per-tenant census, deadline slack from the QoS ordering,
    replica heartbeats) and drives a pluggable ``ReplicaLauncher``.

    Scale-OUT (one replica per decision, bounded by ``max_replicas``):

    * deadline pressure — a queued deadline-bearing request's slack fell
      below ``slack_low_s`` (immediate: waiting out a sustain window is
      exactly how the deadline is missed),
    * queue pressure — queued depth above ``queue_high`` continuously for
      ``sustain_s``,
    * capacity repair — live replicas below ``min_replicas`` (immediate
      and exempt from the cooldown: replacing preempted capacity must not
      wait out the window that throttles elective growth).

    Scale-IN (one replica per decision, bounded by ``min_replicas``): the
    fleet fully idle — nothing queued, nothing running — continuously for
    ``idle_sustain_s``.  The victim is retired by SIGTERM through the
    existing park machinery (running slots persist as durable
    continuations, leases release, exit clean), never killed.

    Hysteresis = the separate sustain windows; ``cooldown_s`` additionally
    spaces consecutive elective actions.  A spawned replica counts toward
    the fleet for ``spawn_grace_s`` before its first heartbeat lands, so a
    slow JAX import cannot read as missing capacity and storm spawns.

    ``notice_s`` seeds ``RUSTPDE_PREEMPT_NOTICE_S`` in launched replicas
    (None: inherit the environment): preemptible capacity should drain
    urgently when its platform says the clock is running.

    ``gang_size`` makes capacity GANG-SHAPED (two-level serving): every
    scale decision moves ``gang_size`` replicas as one fate-shared unit —
    spawns go through the launcher's all-or-nothing ``spawn_gang`` and
    scale-in retires a whole gang or nothing, so the fleet never holds a
    lone gang member that could wedge a sharded campaign's collectives.
    The default 1 is exactly the pre-gang control law."""

    min_replicas: int = 1
    max_replicas: int = 4
    queue_high: int = 8
    sustain_s: float = 5.0
    idle_sustain_s: float = 15.0
    slack_low_s: float = 30.0
    cooldown_s: float = 30.0
    decide_s: float = 2.0
    spawn_grace_s: float = 60.0
    notice_s: float | None = None
    replica_prefix: str = "auto"
    gang_size: int = 1


@dataclass
class SubmeshConfig:
    """Two-level serving (parallel/submesh.py + serve/fleet/gang.py): the
    device fleet is carved into SUB-MESHES so one pencil-sharded flagship
    bucket runs as a gang on a slice while vmapped small-grid buckets
    keep the remainder — with the gang as the failure domain.

    * ``shapes`` — sub-mesh sizes (device counts) to carve, e.g.
      ``(2,)`` on the 2-proc CPU harness or ``(8, 4)`` on a pod slice.
      Shapes the current fleet cannot hold are dropped from the carve
      (the elastic re-planner re-maps stamped buckets, journaled
      ``gang_replanned``); on a multi-process runtime a shape must be a
      multiple of the process count so every process participates in
      every sub-mesh's collectives,
    * ``shard_min_nx`` — grids at/above this extent are SHARDED traffic:
      admission stamps them with the smallest fitting configured shape
      (the stamp joins the compat key, so equal grids bucket together);
      below it requests stay vmapped default traffic with today's keys,
    * ``max_pending`` — admission bound on QUEUED sharded requests per
      stamped shape: past it the POST gets a 429 ``reason="capacity"``
      with queue-depth-derived Retry-After (a fitting sub-mesh exists
      but is busy); a grid that fits NO configured shape is a typed 400
      ``reason="no_submesh"`` at POST time — never a durable poison
      pill."""

    shapes: tuple = (2,)
    shard_min_nx: int = 257
    max_pending: int = 32


@dataclass
class CanonicalConfig:
    """Admission canonicalization (serve/scheduler.py ``submit``): quantize
    the request onto a small, warmable compat-key space so the warm pool's
    AOT executables actually cover traffic.

    What admission may change about a request: its ``dt`` (snapped to the
    nearest rung of a service-wide geometric :class:`DtLadder` anchored at
    ``dt_anchor``, only when the relative shift stays within
    ``max_rel_dt_shift``) and the campaign slot count K (rounded UP to the
    nearest entry of ``slot_sizes`` so a prebuilt ensemble fits — extra
    lanes start dead and are refilled from the queue like any other slot).
    What it may NOT change: the simulated horizon (``SimRequest.steps``
    derives from horizon/dt, so a dt snap re-derives the step count at the
    same physical end time), the grid/Ra/Pr/BC physics of the key, seeds,
    priority, or deadlines.  Every snap is journaled
    (``request_canonicalized``) and the result is guaranteed within
    ``rtol`` of the un-canonicalized run (tests/test_coldstart.py).

    * ``dt_anchor`` / ``ladder_ratio`` — the service-wide rung grid
      (``dt = anchor * ratio**rung``); anchor defaults to the request
      default dt so default traffic is already on-rung,
    * ``dt_min`` / ``dt_max`` — ladder bounds (requests outside snap to the
      edge rung only if within ``max_rel_dt_shift``),
    * ``max_rel_dt_shift`` — admission refuses to move dt further than
      this relative fraction (the request then keeps its exact dt and pays
      its own compile),
    * ``slot_sizes`` — ascending pool sizes K is rounded up to (empty =
      keep the configured ``ServeConfig.slots``),
    * ``rtol`` — the documented parity tolerance between a canonicalized
      run and the same request served at its exact dt."""

    dt_anchor: float = 2e-3
    ladder_ratio: float = 2.0
    dt_min: float = 1e-6
    dt_max: float = 1e-1
    max_rel_dt_shift: float = 0.5
    slot_sizes: tuple = ()
    rtol: float = 5e-2


@dataclass
class ServeConfig:
    """Knobs for the fault-isolated simulation service
    (:class:`~rustpde_mpi_tpu.serve.SimServer`): a persistent driver that
    accepts simulation requests through a durable on-disk queue (plus an
    optional thin HTTP front), bucket-batches compatible requests into
    :class:`~rustpde_mpi_tpu.models.ensemble.NavierEnsemble` slots
    LLM-style, and streams per-request observables back as each resolves.

    * ``run_dir`` — service state root: the durable queue lives under
      ``<run_dir>/queue``, campaign checkpoints under
      ``<run_dir>/campaigns/<key>``, and every runner + ``request_*`` event
      rides ONE ``<run_dir>/journal.jsonl``,
    * ``slots`` — ensemble members per campaign batch (the K of the vmapped
      dispatch); a finished/failed/cancelled member's slot is refilled from
      the queue mid-campaign without recompiling,
    * ``max_queue`` — admission-control bound: a submit past this depth is
      rejected with a typed reason (bounded memory + latency instead of an
      unbounded backlog),
    * ``chunk_steps`` — upper bound on steps per dispatch between schedule
      points (slot completions land exactly on chunk boundaries because the
      chunk is also capped by the minimum remaining steps of any running
      slot),
    * ``checkpoint_every_s`` — wall-clock cadence for slot-table
      checkpoints (None: only drain/edge checkpoints); serve checkpoints
      always use the sharded two-phase writer, carrying the slot table as
      digest-covered manifest data so restarts rebuild it from the
      checkpoint alone,
    * ``request_max_retries`` / ``request_dt_backoff`` — per-request
      divergence policy: a diverged request is re-queued at
      ``dt * backoff`` (a new compatibility bucket) up to the retry budget,
      then lands in the ``failed/`` terminal state with a typed
      :class:`~rustpde_mpi_tpu.serve.RequestFailed` record,
    * ``default_amp`` — initial-condition amplitude for requests that do
      not specify one,
    * ``idle_exit`` — return from :meth:`serve` once the queue is empty and
      every slot resolved (the batch/soak mode); False keeps the service
      waiting for new work (the daemon mode),
    * ``poll_s`` — idle-queue poll interval in daemon mode,
    * ``http_host``/``http_port`` — thin HTTP front (``http_port=None``
      disables it; 0 binds an ephemeral port, reported by ``http_address``),
    * ``resilience`` — runner knobs for the embedded
      :class:`~rustpde_mpi_tpu.utils.resilience.ResilientRunner` (fault
      injection, watchdogs, governor); ``run_dir``/``resume`` fields are
      overridden per campaign by the scheduler."""

    run_dir: str = "data/serve"
    slots: int = 8
    max_queue: int = 256
    chunk_steps: int = 256
    # bucket fairness: max requests one campaign visit may claim while
    # OTHER buckets hold queued work (0 = unlimited); with round-robin
    # bucket selection this bounds any bucket's wait to one quantum per
    # competitor instead of a hot bucket's whole backlog
    bucket_quantum: int = 32
    checkpoint_every_s: float | None = 60.0
    request_max_retries: int = 2
    request_dt_backoff: float = 0.5
    default_amp: float = 0.1
    idle_exit: bool = True
    poll_s: float = 0.2
    http_host: str = "127.0.0.1"
    http_port: int | None = None
    resilience: ResilienceConfig | None = None
    # in-scan physics statistics (None = off): arms the stats engine on
    # every DNS campaign ensemble — per-member running averages updated on
    # device, reset when a lane is refilled by a new request, summarized
    # into each done record ("stats": samples, Nu estimators, budget
    # residuals, spectral-tail fractions).  Lane moves across a drain/
    # re-plan restart the per-request averages (documented limitation);
    # the bit-exact durability contract lives on the runner/campaign path.
    stats: StatsConfig | None = None
    # governed campaign dt (None = reactive-only): arms the on-device
    # stability sentinels on every campaign ensemble and gives each bucket
    # a per-bucket DtLadder — a CFL-ceiling catch re-buckets the pinned
    # requests at a lower rung (requeue-WITH-state, journaled
    # `bucket_dt_adjust`) instead of waiting for NaN + reactive retry.
    # The batch-wide StabilityGovernor stays OFF in campaigns: per-request
    # dt is part of the request contract and the bucket key, so the only
    # legal dt response is re-bucketing, never an in-place set_dt.
    stability: StabilityConfig | None = None
    # fleet mode (None = off, the single-replica behavior unchanged —
    # zero extra journal rows or collectives): this SimServer becomes one
    # replica of a fleet over the shared run_dir — it claims buckets via
    # queue-level leases, heartbeats them, persists parked continuations
    # durably, writes its journal/campaigns under replicas/<id>/, and
    # enforces the QoS traffic contract (quotas, priority classes,
    # deadlines, preemption).  Pair with serve/fleet/proxy.py fronts.
    fleet: FleetConfig | None = None
    # embedded fleet autoscaler (None = off, the default: byte-identical
    # serve behavior — zero extra journal rows, zero extra collectives, no
    # controller threads, CI-asserted).  Set (fleet mode, root only) it
    # starts an Autoscaler daemon thread next to the heartbeat thread:
    # pure host-side file IO + subprocess spawns through a local
    # ReplicaLauncher — never a collective.  The controller can equally
    # run standalone (examples/navier_rbc_autoscale.py).
    autoscale: AutoscaleConfig | None = None
    # two-level serving (None = off, the default: byte-identical serve
    # behavior — 10-tuple compat keys everywhere, zero gang journal rows,
    # CI-asserted): carve the device fleet into sub-meshes and serve
    # pencil-sharded flagship buckets as fate-shared GANGS on slices
    # while vmapped buckets keep the remainder.  See SubmeshConfig.
    submesh: SubmeshConfig | None = None
    # warm campaign pool (None = off, the default: byte-identical serve
    # behavior, zero warm-pool journal rows, CI-asserted): a traffic
    # profile — a path to a durable JSON learned from the journal's
    # historical compile_build rows (serve/warmpool.py learn_profile), or
    # an inline list of {"key": [...], "k": int} entries — whose
    # (model kind × grid × K × dt-rung) matrix is AOT-compiled in a
    # background thread at service start and handed to the scheduler warm
    # at bucket-open, so admission-to-first-chunk skips the jit entirely.
    warm_profile: object | None = None
    # admission canonicalization (None = off, the default: requests keep
    # their exact dt and the configured slot count).  See CanonicalConfig.
    canonicalize: CanonicalConfig | None = None
    # end-to-end integrity (None = off): arms the on-device state digest +
    # shadow-audit layer (integrity/) on every campaign ensemble — silent
    # bit flips are caught by the runner's digest audits, contained by
    # in-memory rollback, charged to the quarantine ledger
    # (<run_dir>/quarantine.json), and a quarantined device is excluded
    # from the next campaign's sub-mesh carve.  Done records carry each
    # member's final state digest so the fleet proxy's cross-replica
    # voting can compare double-assigned requests bit-for-bit.
    integrity: IntegrityConfig | None = None


@dataclass
class NavierConfig:
    """Configuration dataclass for the Navier models (SURVEY.md S5: the
    reference passes bare constructor arguments and mutates public fields,
    navier.rs:229-233; this names the same vocabulary in one object).

    Use with ``Navier2D.from_config(cfg)`` / ``Navier2DAdjoint.from_config``.
    """

    nx: int = 129
    ny: int = 129
    ra: float = 1e7
    pr: float = 1.0
    dt: float = 2e-3
    aspect: float = 1.0
    bc: str = "rbc"  # "rbc" | "hc"
    periodic: bool = False
    # post-construction knobs (public-field mutation in the reference)
    write_intervall: float | None = None
    init_random_amp: float | None = 0.1
    params: dict = field(default_factory=dict)  # extra params recorded to h5
    # member count for NavierEnsemble.from_config (1 = plain single run);
    # members share the operator constants and differ by IC seed
    ensemble: int = 1
    # resilience-harness knobs (None = run without the harness; see
    # ResilienceConfig / utils/resilience.ResilientRunner)
    resilience: ResilienceConfig | None = None
    # stability-sentinel knobs (None = plain stepping; see StabilityConfig /
    # utils/governor.py) — from_config calls model.set_stability(stability)
    stability: StabilityConfig | None = None
    # in-scan physics-statistics knobs (None = off unless RUSTPDE_STATS=1;
    # see StatsConfig / models/stats.py) — from_config calls
    # model.set_stats(stats)
    stats: StatsConfig | None = None
    # scenario step modifiers (None = plain physics; a
    # workloads.modifiers.ScenarioConfig or equivalent dict: rotating-frame
    # coriolis rate, passive_scalar, scalar_kappa) — baked into the step
    # and signed into compat_key
    scenario: object | None = None

    def ctor_args(self) -> tuple:
        return (self.nx, self.ny, self.ra, self.pr, self.dt, self.aspect, self.bc)
