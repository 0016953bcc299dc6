"""Run the full test suite (fast + slow tiers) and record the result in
``TESTS.json`` at the repo root (VERDICT r4 weak #6: the slow tier —
multiprocess, examples, production-shape mesh checks — must leave a recorded
cadence, not just an on-demand env knob).

Usage:  python scripts/record_tests.py            # full suite (RUSTPDE_SLOW=1)
        python scripts/record_tests.py --fast     # fast tier only

Per-test durations (``--durations``-style) are parsed from every run and
recorded in TESTS.json, and the FAST tier enforces a per-test wall budget
(``RUSTPDE_TEST_BUDGET_S``, default 45 s per test call — the slowest
tier-1 test sits at ~20 s, so the gate only trips on a genuine 2x+
regression, not scheduler noise on a contended box): a tier-1 test
that outgrows its budget fails the run (rc=3) the PR it regresses, instead
of silently eating the suite's 870 s clock until the whole tier times out
(the rc=124-at-HEAD failure mode this repo has already hit once).
"""

import argparse
import datetime
import json
import os
import re
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="skip the slow tier")
    args = ap.parse_args()

    env = dict(os.environ)
    if not args.fast:
        env["RUSTPDE_SLOW"] = "1"
    tier = "fast" if args.fast else "full (RUSTPDE_SLOW=1)"
    tier_key = "fast" if args.fast else "full"
    budget_s = float(os.environ.get("RUSTPDE_TEST_BUDGET_S", "45"))
    timeout_s = 7200
    t0 = time.time()
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/", "-q", "--durations=0"],
            cwd=_REPO,
            env=env,
            capture_output=True,
            text=True,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired as exc:
        # a hung suite must still leave a TESTS.json entry: record the
        # timeout (rc=124, the coreutils convention) before exiting nonzero
        out = exc.stdout
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        record = {
            "tier": tier,
            "summary": f"timeout: suite exceeded {timeout_s}s",
            "passed": 0,
            "failed": 0,
            "skipped": 0,
            "dots_passed": _dots_passed(out or ""),  # how far the run got
            "wall_s": round(time.time() - t0, 1),
            "returncode": 124,
            "date": _utc_now(),
        }
        _persist(record, tier_key)
        print(json.dumps(record))
        sys.stderr.write((out or "")[-4000:])
        return 124
    wall = time.time() - t0
    tail = (proc.stdout or "").strip().splitlines()[-1:] or [""]
    summary = tail[0]
    # normalize "errors" -> "error" so the plural pytest summary counts too
    counts = {kind.rstrip("s") if kind.startswith("error") else kind: int(num)
              for num, kind in
              re.findall(r"(\d+) (passed|failed|skipped|errors?)", summary)}
    record = {
        "tier": tier,
        "summary": summary,
        "passed": counts.get("passed", 0),
        "failed": counts.get("failed", 0) + counts.get("error", 0),
        "skipped": counts.get("skipped", 0),
        # the tier-1 driver's own progress metric (ROADMAP "Tier-1 verify"
        # counts '.' chars on the -q progress lines as DOTS_PASSED): record
        # it per run so an IO/test-duration regression that changes how far
        # the suite gets is visible across PRs even when the summary line
        # is missing (hang/kill)
        "dots_passed": _dots_passed(proc.stdout or ""),
        # per-test duration profile (the 15 slowest call phases) + budget
        # verdict: tier-1 regressions are caught per-PR, not when the whole
        # suite first blows its 870 s clock
        "durations": dict(_durations(proc.stdout or "")[:15]),
        "budget_s": budget_s,
        "over_budget": _over_budget(proc.stdout or "", budget_s),
        "wall_s": round(wall, 1),
        "returncode": proc.returncode,
        # sharded-checkpoint IO counters from the last recorded
        # shardedio129 bench row (shard count, bytes/host, gate flags) —
        # the durability harness's footprint rides the test record so a
        # shard-layout regression is visible across PRs
        "sharded_io": _sharded_io_counters(),
        # multihost-serve counters from the serve129 row's 2-proc CPU leg
        # (drain/replan/dt-adjust trajectory of the root-coordinated
        # scheduler) — the multihost serving path gets the same tracked
        # record the two-phase writer has
        "serve_mp": _serve_mp_counters(),
        # HA-fleet counters from the serve129 fleet leg (replicas
        # spawned, leases broken, preemptions, zero-lost flag) — the
        # replicated front door gets the same tracked record
        "fleet": _fleet_counters(),
        # autoscaling-controller counters from the autoscale129 chaos
        # soak (decisions, spawn/retire counts, preemptions, admission
        # p99, loss gates) — the control loop gets the same tracked
        # record the fleet it drives has
        "autoscale": _autoscale_counters(),
        # gang-scheduled sub-mesh serving counters from the
        # serve_submesh129 chaos pair (gang formations, typed member
        # losses, reclaim/requeue trajectory, solo-parity and co-resident
        # latency gates) — two-level serving gets the same tracked record
        # the flat multihost scheduler has
        "gang_serve": _gang_serve_counters(),
        # cold-start elimination counters from the coldstart129 legs
        # (cache/warm-pool/canonicalization TTFC + restart walls and
        # their gates) — the serving stack's p99-compile story gets the
        # same tracked record its chaos legs have
        "coldstart": _coldstart_counters(),
        # SDC-defense counters from the integrity129 row (digest-stream
        # overhead, bit-equal trajectory, injected-bitflip caught/rolled-
        # back gates) — the integrity layer gets the same tracked record
        # its chaos siblings have
        "integrity": _integrity_counters(),
        # per-model solo-vs-ensemble parity deltas (workloads satellite):
        # recorded into PARITY.json too, so cross-model vmap/scan drift
        # shows up per-PR next to the Nu-parity numbers
        "workloads": _workloads_parity(),
        # fused-Pallas-vs-dense convection parity per layout (max rel diff,
        # interpreter mode) — merged into PARITY.json under "pallas_conv"
        # so kernel drift is tracked per-PR like the Nu trajectories
        "pallas_conv": _pallas_conv_parity(),
        # fused-step (Helmholtz/Poisson solve megakernel) vs dense solver
        # chain, 5-step trajectory parity per layout — merged into
        # PARITY.json under "pallas_step" next to the conv kernel trend
        "pallas_step": _pallas_step_parity(),
        # in-scan stats engine vs the eager legacy accumulator (max rel
        # diff per accumulated field) — merged into PARITY.json under
        # "stats" so accumulator drift is tracked per-PR too
        "stats": _stats_parity(),
        # telemetry inventory (METRICS.json written alongside): the metric
        # names an instrumented run registers — a per-PR record of the
        # observable vocabulary, like the journal schema rows
        "metrics": _metrics_snapshot(),
        # static-analysis payload (LINT.json written alongside): rule ->
        # count for both lint layers + baseline size, with a delta gate —
        # NEW findings (or stale baseline entries) fail the record run
        "lint": _lint_payload(),
        # perf-trend payload (TREND.json written alongside): per-config
        # BENCH_r*/BENCH_FULL trajectories with a noise-band regression
        # gate — an un-acked regression fails the record run via rc=5
        "trend": _trend_payload(),
        "date": _utc_now(),
    }
    _persist(record, tier_key)
    print(json.dumps(record))
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout[-4000:])
        return proc.returncode
    lint = record["lint"] or {}
    if lint.get("clean") is False:
        sys.stderr.write(
            "lint baseline-delta gate: new findings "
            f"{lint.get('counts')} (stale baseline: {lint.get('stale')}) — "
            "run scripts/lint.py\n"
        )
        return 4
    trend = record["trend"] or {}
    if trend.get("clean") is False:
        sys.stderr.write(
            "perf-trend gate: un-acked regressions "
            f"{trend.get('regressions_unacked')} — investigate, or ack with "
            "a written reason: scripts/bench_trend.py --ack <config> "
            "--reason '...'\n"
        )
        return 5
    # the budget gate applies to the FAST (= tier-1) selection only: slow-
    # tier tests (multiprocess spawns, soaks) legitimately run for minutes
    if args.fast and record["over_budget"]:
        sys.stderr.write(
            f"tier-1 per-test budget ({budget_s:.0f}s) exceeded by: "
            f"{record['over_budget']}\n"
        )
        return 3
    return 0


_DURATION_LINE = re.compile(
    r"^\s*([0-9]+\.[0-9]+)s\s+(call|setup|teardown)\s+(\S+)\s*$"
)


def _durations(out: str) -> list:
    """``[(testid, seconds), ...]`` slowest-first from pytest's
    ``--durations=0`` report (call phases only: setup/teardown time is
    fixture-shared and double-counts across tests)."""
    found = []
    for line in out.splitlines():
        m = _DURATION_LINE.match(line)
        if m and m.group(2) == "call":
            found.append((m.group(3), float(m.group(1))))
    found.sort(key=lambda kv: -kv[1])
    return found


def _over_budget(out: str, budget_s: float) -> list:
    """Test ids whose call phase exceeded the per-test budget."""
    return [tid for tid, s in _durations(out) if s > budget_s]


def _dots_passed(out: str) -> int:
    """Count pass-dots on pytest -q progress lines — the same
    ``^[.FEsx]+( *\\[ *[0-9]+%\\])?$`` line shape (and dot count) the
    ROADMAP tier-1 verify greps as DOTS_PASSED."""
    progress = re.compile(r"^[.FEsx]+( *\[ *[0-9]+%\])?$")
    return sum(
        line.count(".")
        for line in out.splitlines()
        if progress.match(line.strip())
    )


def _sharded_io_counters() -> dict | None:
    """Shard/bytes counters from BENCH_FULL.json's ``shardedio129`` row
    (None when the config was never benched on this platform)."""
    try:
        with open(os.path.join(_REPO, "BENCH_FULL.json")) as f:
            row = json.load(f)["results"]["shardedio129"]
        return {
            "shards": row.get("shards"),
            "bytes_host": row.get("bytes_host"),
            "bytes_total": row.get("bytes_total"),
            "manifest_verify_ok": row.get("manifest_verify_ok"),
            "cross_topology_restore_equal": row.get(
                "cross_topology_restore_equal"
            ),
        }
    except (OSError, ValueError, KeyError):
        return None


def _serve_mp_counters() -> dict | None:
    """Drain/replan/dt-adjust counters from BENCH_FULL.json's ``serve129``
    2-process leg (None when the config was never benched — or predates
    the multihost scheduler)."""
    try:
        with open(os.path.join(_REPO, "BENCH_FULL.json")) as f:
            row = json.load(f)["results"]["serve129"]
        mp = row.get("multiprocess")
        if not isinstance(mp, dict):
            return None
        return {
            key: mp.get(key)
            for key in (
                "nproc",
                "completed",
                "drains",
                "requeued",
                "replans",
                "dt_adjusts",
                "restored_mid_trajectory",
                "zero_lost",
                "error",
            )
            if key in mp
        }
    except (OSError, ValueError, KeyError):
        return None


def _fleet_counters() -> dict | None:
    """HA-fleet counters from BENCH_FULL.json's ``serve129`` fleet leg
    (proxy + 2 leased replicas, replica SIGKILL mid-campaign): replicas
    spawned, leases broken, preemptions, break->reclaim latency and the
    zero-lost / reclaimed-with-state flags.  None when the config was
    never benched — or predates the fleet layer."""
    try:
        with open(os.path.join(_REPO, "BENCH_FULL.json")) as f:
            row = json.load(f)["results"]["serve129"]
        fleet = row.get("fleet")
        if not isinstance(fleet, dict):
            return None
        return {
            key: fleet.get(key)
            for key in (
                "replicas",
                "proxies",
                "requests",
                "leases_broken",
                "preemptions",
                "resumed_mid_flight",
                "lease_break_to_reclaim_s",
                "zero_lost",
                "reclaimed_with_state",
                "error",
            )
            if key in fleet
        }
    except (OSError, ValueError, KeyError):
        return None


def _autoscale_counters() -> dict | None:
    """Controller counters from BENCH_FULL.json's ``autoscale129`` row
    (chaos soak: Poisson notice-SIGTERM/SIGKILL preemptions against an
    autoscaled fleet): decisions taken, replicas spawned/retired,
    preemption mix, admission p99 and the zero-lost /
    reclaimed-with-state / SLO gates.  None when the config was never
    benched — or predates the autoscaler."""
    try:
        with open(os.path.join(_REPO, "BENCH_FULL.json")) as f:
            row = json.load(f)["results"]["autoscale129"]
        return {
            key: row.get(key)
            for key in (
                "requests",
                "decisions",
                "spawned",
                "retired",
                "preempts_notice",
                "preempts_kill",
                "resumed_mid_flight",
                "admission_p50_s",
                "admission_p99_s",
                "zero_lost",
                "reclaimed_with_state",
                "slo_ok",
                "error",
            )
            if key in row
        }
    except (OSError, ValueError, KeyError):
        return None


def _coldstart_counters() -> dict | None:
    """Cold-start counters from BENCH_FULL.json's ``coldstart129`` row
    (persistent compile cache + warm campaign pool + admission
    canonicalization legs): never-seen-key TTFC and restart-to-first-
    result cold vs warm, the zero-jit warm admission / recompile-flat /
    canonicalization-parity gates.  None when the config was never
    benched — or predates the warm pool."""
    try:
        with open(os.path.join(_REPO, "BENCH_FULL.json")) as f:
            row = json.load(f)["results"]["coldstart129"]
        return {
            key: row.get(key)
            for key in (
                "ttfc_cold_s",
                "ttfc_warm_s",
                "restart_to_first_result_cold_s",
                "restart_to_first_result_prime_s",
                "restart_to_first_result_warm_s",
                "warm_pool_hits",
                "warm_leg_compile_builds",
                "recompiles",
                "canonicalized_parity_rel",
                "parity_rtol",
                "zero_jit_warm",
                "ttfc_improved",
                "restart_improved",
                "recompile_flat",
                "parity_ok",
                "error",
            )
            if key in row
        }
    except (OSError, ValueError, KeyError):
        return None


def _gang_serve_counters() -> dict | None:
    """Two-level serving counters from BENCH_FULL.json's
    ``serve_submesh129`` row (clean baseline + gang-kill chaos pair on
    the 2-process sub-mesh harness): gang formations, typed member
    losses, the reclaim trajectory and the zero-lost / solo-parity /
    co-resident-latency gates.  None when the config was never benched —
    or predates gang scheduling."""
    try:
        with open(os.path.join(_REPO, "BENCH_FULL.json")) as f:
            row = json.load(f)["results"]["serve_submesh129"]
        out = {
            key: row.get(key)
            for key in (
                "requests_gang",
                "requests_vmapped",
                "coresident_p99_factor",
                "solo_rel_err_max",
                "zero_lost",
                "gang_killed",
                "gang_reclaimed",
                "solo_ok",
                "coresident_ok",
                "error",
            )
            if key in row
        }
        chaos = row.get("chaos")
        if isinstance(chaos, dict):
            out["gang_formed"] = chaos.get("gang_formed")
            out["gang_member_lost"] = chaos.get("gang_member_lost")
            out["restored_mid_trajectory"] = chaos.get(
                "restored_mid_trajectory"
            )
        return out
    except (OSError, ValueError, KeyError):
        return None


def _integrity_counters() -> dict | None:
    """SDC-defense counters from BENCH_FULL.json's ``integrity129`` row
    (digests-on vs off matched windows + the injected-bitflip detection
    pair): overhead factor, bit-equal flags and the caught/rolled-back
    gate.  None when the config was never benched — or predates the
    integrity layer."""
    try:
        with open(os.path.join(_REPO, "BENCH_FULL.json")) as f:
            row = json.load(f)["results"]["integrity129"]
        return {
            key: row.get(key)
            for key in (
                "integrity_overhead_x",
                "integrity_overhead_ok",
                "integrity_bit_equal",
                "sdc_caught",
                "sdc_bit_equal",
                "error",
            )
            if key in row
        }
    except (OSError, ValueError, KeyError):
        return None


_WORKLOADS_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %(repo)r)
import jax
from rustpde_mpi_tpu.workloads import solo_ensemble_parity
print("WORKLOADS_JSON " + json.dumps(solo_ensemble_parity(steps=6)))
"""


def _parity_probe(child_src: str, marker: str, key: str, value_key: str) -> dict:
    """Shared harness behind every PARITY.json probe: run ``child_src`` as
    a CPU child, parse the ``marker``-prefixed JSON line, and atomically
    merge the payload under ``key`` next to the Nu-parity trajectories.
    Best-effort: a failure records the error string instead of killing the
    test record."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", child_src % {"repo": _REPO}],
            capture_output=True,
            text=True,
            timeout=600,
            cwd=_REPO,
        )
        line = next(
            ln for ln in proc.stdout.splitlines() if ln.startswith(marker)
        )
        values = json.loads(line[len(marker):])
    except Exception as exc:  # noqa: BLE001 — recording must not fail the run
        return {"error": f"{type(exc).__name__}: {exc}"}
    payload = {value_key: values, "date": _utc_now()}
    parity_path = os.path.join(_REPO, "PARITY.json")
    try:
        with open(parity_path) as f:
            parity = json.load(f)
    except (OSError, ValueError):
        parity = {}
    parity[key] = payload
    tmp = f"{parity_path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(parity, f, indent=1)
    os.replace(tmp, parity_path)
    return payload


def _workloads_parity() -> dict | None:
    """Per-model-kind solo-vs-ensemble parity deltas (max relative state
    deviation of a K=2 vmapped campaign vs member-wise solo runs, per
    registered model kind), merged into PARITY.json under ``"workloads"``."""
    return _parity_probe(_WORKLOADS_CHILD, "WORKLOADS_JSON ", "workloads", "deltas")


_PALLAS_CONV_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("RUSTPDE_X64", "1")
sys.path.insert(0, %(repo)r)
import numpy as np
import jax
import jax.numpy as jnp
from rustpde_mpi_tpu.bases import (
    Space2, cheb_dirichlet, chebyshev, fourier_r2c, fourier_r2c_split,
)
from rustpde_mpi_tpu.ops.pallas_conv import FusedConv

def delta(sp, fs, seed=0):
    fc = FusedConv(sp, fs, (1.0, 1.0))
    rng = np.random.default_rng(seed)
    nx, ny = sp.shape_physical
    ux = jnp.asarray(rng.standard_normal((nx, ny)))
    uy = jnp.asarray(rng.standard_normal((nx, ny)))
    vhat = sp.forward(jnp.asarray(rng.standard_normal((nx, ny))))
    ref = np.asarray(fc.reference(ux, uy, vhat))
    out = np.asarray(fc.apply(ux, uy, vhat))
    return float(np.abs(out - ref).max() / (np.abs(ref).max() or 1.0))

os.environ["RUSTPDE_SEP"] = "1"
deltas = {
    "confined_sep": delta(
        Space2(cheb_dirichlet(33), cheb_dirichlet(33), method="matmul", sep=True),
        Space2(chebyshev(33), chebyshev(33), method="matmul", sep=True),
    ),
    "periodic_complex": delta(
        Space2(fourier_r2c(16), cheb_dirichlet(17)),
        Space2(fourier_r2c(16), chebyshev(17)),
    ),
    "split_sep": delta(
        Space2(fourier_r2c_split(16), cheb_dirichlet(17), method="matmul"),
        Space2(fourier_r2c_split(16), chebyshev(17), method="matmul"),
    ),
}
print("PALLAS_CONV_JSON " + json.dumps(deltas))
"""


def _pallas_conv_parity() -> dict | None:
    """Max relative dense-vs-Pallas deviation of the fused convection chain
    per layout (CPU interpreter mode, f64), merged into PARITY.json under
    ``"pallas_conv"``."""
    return _parity_probe(
        _PALLAS_CONV_CHILD, "PALLAS_CONV_JSON ", "pallas_conv", "max_rel_diff"
    )


_PALLAS_STEP_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("RUSTPDE_X64", "1")
sys.path.insert(0, %(repo)r)
import numpy as np
import jax
import rustpde_mpi_tpu as rp

def build(periodic, nx, ny, kernel):
    os.environ["RUSTPDE_STEP_KERNEL"] = kernel
    m = rp.Navier2D(nx, ny, 1e4, 1.0, 5e-3, 1.0, "rbc", periodic=periodic)
    m.set_velocity(0.1, 1.0, 1.0)
    m.set_temperature(0.1, 1.0, 1.0)
    return m

def delta(periodic, nx, ny, env=()):
    for k, v in env:
        os.environ[k] = v
    try:
        d = build(periodic, nx, ny, "dense")
        p = build(periodic, nx, ny, "pallas")
        assert p._step_impl is not None
        d.update_n(5)
        p.update_n(5)
        # per-leaf deviations floored by the physical-field scale (the
        # pseudo-pressure is ~zero at near-incompressibility: its own max
        # is roundoff noise, not a meaningful denominator)
        scale0 = max(
            float(np.abs(np.asarray(x)).max())
            for x in (d.state.temp, d.state.velx, d.state.vely)
        )
        rel = 0.0
        for a, b in zip(p.state, d.state):
            a, b = np.asarray(a), np.asarray(b)
            den = max(float(np.abs(b).max()), scale0, 1e-30)
            rel = max(rel, float(np.abs(a - b).max() / den))
        return rel
    finally:
        for k, _ in env:
            os.environ.pop(k, None)
        os.environ.pop("RUSTPDE_STEP_KERNEL", None)

deltas = {
    "confined": delta(False, 17, 17),
    "periodic_complex": delta(True, 16, 17),
    "confined_sep": delta(False, 33, 33, (("RUSTPDE_FORCE_TPU_PATH", "1"),)),
    "split_sep": delta(
        True, 16, 17,
        (("RUSTPDE_FORCE_TPU_PATH", "1"), ("RUSTPDE_SEP", "1")),
    ),
}
print("PALLAS_STEP_JSON " + json.dumps(deltas))
"""


def _pallas_step_parity() -> dict | None:
    """Max relative dense-vs-Pallas deviation of the fused solve/projection
    step (5-step trajectory, ops/pallas_step.py) per layout, floored by the
    physical-field scale — merged into PARITY.json under ``"pallas_step"``."""
    return _parity_probe(
        _PALLAS_STEP_CHILD, "PALLAS_STEP_JSON ", "pallas_step", "max_rel_diff"
    )


_STATS_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %(repo)r)
import numpy as np
import jax
from rustpde_mpi_tpu import Navier2D, Statistics
from rustpde_mpi_tpu.config import StatsConfig

def build():
    m = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
    m.set_velocity(0.1, 1.0, 1.0)
    m.set_temperature(0.1, 1.0, 1.0)
    return m

m = build()
m.set_stats(StatsConfig(stride=3))
m.update_n(12)
twin = build()
legacy = Statistics(twin, 0.01, 1.0)
for _ in range(4):
    twin.update_n(3)
    legacy.update(twin)
n = float(np.asarray(m.stats_state.samples).reshape(-1)[0])
deltas = {}
for eng, leg in (
    ("t_sum", "t_avg"), ("ux_sum", "ux_avg"),
    ("uy_sum", "uy_avg"), ("nusselt_sum", "nusselt"),
):
    a = np.asarray(getattr(m.stats_state, eng)) / n
    b = np.asarray(getattr(legacy, leg))
    deltas[eng[:-4]] = float(np.abs(a - b).max() / (np.abs(b).max() or 1.0))
print("STATS_JSON " + json.dumps(deltas))
"""


def _stats_parity() -> dict | None:
    """Engine-vs-eager-legacy accumulator parity (max relative deviation
    of the running averages over a matched sampled trajectory), merged
    into PARITY.json under ``"stats"``."""
    return _parity_probe(_STATS_CHILD, "STATS_JSON ", "stats", "max_rel_diff")


_METRICS_CHILD = r"""
import json, os, sys, tempfile
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("RUSTPDE_X64", "1")
sys.path.insert(0, %(repo)r)
import jax
from rustpde_mpi_tpu import Navier2D, ResilientRunner, telemetry
from rustpde_mpi_tpu.config import StabilityConfig

d = tempfile.mkdtemp()
m = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
m.init_random(0.1, seed=0)
r = ResilientRunner(m, max_time=0.08, run_dir=os.path.join(d, "run"),
                    checkpoint_every_s=None, max_chunk_steps=4,
                    stability=StabilityConfig())
r.run()
print("METRICS_JSON " + json.dumps(telemetry.snapshot()))
"""


def _metrics_snapshot() -> dict | None:
    """Snapshot the telemetry registry of a tiny instrumented governed run
    (CPU child) into METRICS.json next to TESTS.json — the per-PR record
    of the live metric vocabulary (names, kinds, label sets), like the
    journal schema table but machine-readable.  Best-effort: a failure
    records the error string instead of killing the test record."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _METRICS_CHILD % {"repo": _REPO}],
            capture_output=True,
            text=True,
            timeout=600,
            cwd=_REPO,
        )
        line = next(
            ln for ln in proc.stdout.splitlines()
            if ln.startswith("METRICS_JSON ")
        )
        snap = json.loads(line[len("METRICS_JSON "):])
    except Exception as exc:  # noqa: BLE001 — recording must not fail the run
        return {"error": f"{type(exc).__name__}: {exc}"}
    payload = {
        "names": {
            name: fam.get("kind", "?") for name, fam in sorted(snap.items())
        },
        "snapshot": snap,
        "date": _utc_now(),
    }
    path = os.path.join(_REPO, "METRICS.json")
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)
    # the TESTS.json row carries the compact inventory, not the full dump
    return {"names": payload["names"], "date": payload["date"]}


def _lint_payload() -> dict | None:
    """Run ``scripts/lint.py --json`` and persist the rule->count payload
    of both lint layers as LINT.json (project RPD rules + the curated
    GEN ruff-subset, engine recorded), with the baseline counts alongside
    so the delta is visible per-PR.  ``clean`` False (new findings or a
    stale baseline entry) fails the record run via rc=4.  Best-effort on
    infrastructure errors: the error string is recorded instead."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "scripts", "lint.py"), "--json"],
            capture_output=True,
            text=True,
            timeout=600,
            cwd=_REPO,
        )
        data = json.loads(proc.stdout)
    except Exception as exc:  # noqa: BLE001 — recording must not fail the run
        return {"error": f"{type(exc).__name__}: {exc}"}
    payload = {
        "engine": data.get("engine"),
        "files": data.get("files"),
        "counts": data.get("counts", {}),
        "baselined_counts": data.get("baselined_counts", {}),
        "suppressed": data.get("suppressed", 0),
        "stale": len(data.get("stale_baseline", [])),
        "new": len(data.get("new", [])),
        "clean": proc.returncode == 0,
        "date": _utc_now(),
    }
    path = os.path.join(_REPO, "LINT.json")
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)
    return payload


def _trend_payload() -> dict | None:
    """Run ``scripts/bench_trend.py --json --gate`` (writes TREND.json at
    the repo root, like LINT.json) and return the compact verdict.
    ``clean`` False — an un-acked perf regression against the rolling best
    — fails the record run via rc=5.  Best-effort on infrastructure
    errors: the error string is recorded instead."""
    try:
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(_REPO, "scripts", "bench_trend.py"),
                "--json",
                "--gate",
            ],
            capture_output=True,
            text=True,
            timeout=120,
            cwd=_REPO,
        )
        data = json.loads(proc.stdout)
    except Exception as exc:  # noqa: BLE001 — recording must not fail the run
        return {"error": f"{type(exc).__name__}: {exc}"}
    return {
        "band": data.get("band"),
        "configs": len(data.get("configs", {})),
        "regressions": data.get("regressions", []),
        "regressions_unacked": data.get("regressions_unacked", []),
        "clean": proc.returncode == 0,
        "date": _utc_now(),
    }


def _utc_now() -> str:
    return datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y-%m-%d %H:%M UTC"
    )


def _persist(record: dict, tier_key: str) -> None:
    """Append ``record`` to TESTS.json, keeping SEPARATE fast-tier and
    full-tier sections (``{"fast": {latest, history}, "full": {...}}``): a
    stale full-tier ``latest`` used to shadow every later fast-tier run,
    so a tier-1 regression was invisible in the record.  The legacy
    top-level ``latest`` stays as "most recent run of any tier" for old
    readers; legacy flat histories are migrated by their tier string."""
    path = os.path.join(_REPO, "TESTS.json")
    try:
        with open(path) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        prev = {}
    tiers = {}
    for key in ("fast", "full"):
        section = prev.get(key)
        tiers[key] = dict(section) if isinstance(section, dict) else {}
        tiers[key].setdefault("history", [])
    # one-time migration of the legacy flat history (entries carry a human
    # tier string: "fast" or "full (RUSTPDE_SLOW=1)")
    for entry in prev.get("history", []):
        key = "fast" if str(entry.get("tier", "")).startswith("fast") else "full"
        if entry not in tiers[key]["history"]:
            tiers[key]["history"].append(entry)
    tiers[tier_key]["latest"] = record
    tiers[tier_key]["history"] = (tiers[tier_key]["history"] + [record])[-10:]
    for key in ("fast", "full"):
        tiers[key].setdefault("latest", None)
    with open(path, "w") as f:
        json.dump({"latest": record, **tiers}, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
