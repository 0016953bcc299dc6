"""Cold-start elimination tests (PR 19): the persistent compile cache
arming contract, admission canonicalization (dt ladder snap + slot
rounding + result parity), warm campaign pool accounting, AOT bucket
executables, and cross-process compile-cache reuse.

The fast tier drives the WarmPool directly with a stub build callback and
the scheduler's canonicalization hooks on the shared 17^2 jit shapes; the
subprocess cache-reuse test times ONLY the jit compile inside each child
(imports excluded) with a deliberately lenient gate, and the full
replica-boots-warm soak rides the slow tier.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from rustpde_mpi_tpu import config
from rustpde_mpi_tpu.config import CanonicalConfig, ServeConfig
from rustpde_mpi_tpu.serve import SimServer
from rustpde_mpi_tpu.serve.warmpool import (
    WarmPool,
    freeze_key,
    learn_profile,
    load_profile,
    save_profile,
)
from rustpde_mpi_tpu.telemetry import compile_log
from rustpde_mpi_tpu.utils.journal import read_journal

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REQ = dict(ra=1e4, pr=1.0, nx=17, ny=17, dt=0.01, horizon=0.1, bc="rbc")

_CACHE_VARS = (
    "JAX_COMPILATION_CACHE_DIR",
    "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
    "RUSTPDE_COMPILE_CACHE",
)


@pytest.fixture
def cache_env():
    """Snapshot/restore the cache arming state: the env vars, the module
    idempotence latch, and jax's own cache-dir config — so these tests
    can arm/disarm freely without leaking into the rest of the tier."""
    import jax

    saved = {name: os.environ.get(name) for name in _CACHE_VARS}
    saved_latch = config._cache_armed
    saved_jax = jax.config.jax_compilation_cache_dir
    yield
    for name, val in saved.items():
        if val is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = val
    config._cache_armed = saved_latch
    jax.config.update("jax_compilation_cache_dir", saved_jax)


def _cfg(tmp_path, **kw):
    kw.setdefault("run_dir", str(tmp_path / "serve"))
    kw.setdefault("slots", 2)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("checkpoint_every_s", None)
    kw.setdefault("http_port", None)
    return ServeConfig(**kw)


# -- compile cache arming -----------------------------------------------------


def test_ensure_compile_cache_arms_once(tmp_path, cache_env):
    config._cache_armed = None
    os.environ.pop("RUSTPDE_COMPILE_CACHE", None)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    first = config.ensure_compile_cache()
    # the directory is placed from OUTSIDE: the variable, when set, IS the
    # directory, and arming leaves it exactly as it found it
    assert first == str(tmp_path / "cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == first
    # idempotent: the second call returns the latched path without
    # re-reading the environment (a changed dir mid-process must not re-arm)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "elsewhere")
    assert config.ensure_compile_cache() == first
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path / "elsewhere")


def test_ensure_compile_cache_knob_off_is_inert(tmp_path, cache_env):
    config._cache_armed = None
    os.environ["RUSTPDE_COMPILE_CACHE"] = "0"
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert config.ensure_compile_cache() is None
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
    assert config._cache_armed is None


def test_compile_cache_env_snapshot(tmp_path, cache_env):
    config._cache_armed = None
    os.environ.pop("RUSTPDE_COMPILE_CACHE", None)
    # unset: the repo-fixed default, and arming exports nothing
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert config.ensure_compile_cache() == os.path.join(_REPO, ".jax_cache")
    assert "JAX_COMPILATION_CACHE_DIR" not in config.compile_cache_env()
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
    # set: the snapshot carries it so a child resolves the same directory
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    os.environ["RUSTPDE_COMPILE_CACHE"] = "1"
    env = config.compile_cache_env()
    assert env["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path / "cache")
    assert env["RUSTPDE_COMPILE_CACHE"] == "1"


def test_launcher_seeds_cache_env_into_custom_snapshot(tmp_path, cache_env):
    from rustpde_mpi_tpu.serve.fleet.launcher import LocalProcessLauncher

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    # a custom env snapshot missing the cache variables gets them seeded
    # from this process's environment, so every spawned replica shares the
    # fleet cache; an explicit value in the snapshot wins (setdefault)
    launcher = LocalProcessLauncher(
        str(tmp_path / "fleet"),
        env={"PATH": os.environ.get("PATH", ""),
             "RUSTPDE_COMPILE_CACHE": "0"},
    )
    assert launcher.env["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path / "cache")
    assert launcher.env["RUSTPDE_COMPILE_CACHE"] == "0"


# -- admission canonicalization -----------------------------------------------


def test_canonicalize_snaps_dt_preserving_horizon(tmp_path):
    srv = SimServer(
        _cfg(tmp_path, canonicalize=CanonicalConfig(dt_anchor=1e-2))
    )
    req = srv.submit({**_REQ, "dt": 9e-3, "horizon": 0.08})
    # snapped onto rung 0 EXACTLY (the ladder float, not an approximation)
    assert req.dt == 1e-2
    # steps re-derive from horizon/dt: same physical end time, fewer steps
    assert req.steps == 8
    rows = [
        r
        for r in read_journal(os.path.join(srv.cfg.run_dir, "journal.jsonl"))
        if r.get("event") == "request_canonicalized"
    ]
    assert len(rows) == 1
    assert rows[0]["dt_from"] == 9e-3 and rows[0]["dt_to"] == 1e-2
    assert rows[0]["rung"] == 0


def test_canonicalize_co_buckets_near_rung_requests(tmp_path):
    srv = SimServer(
        _cfg(tmp_path, canonicalize=CanonicalConfig(dt_anchor=1e-2))
    )
    a = srv.submit({**_REQ, "dt": 1e-2})
    b = srv.submit({**_REQ, "dt": 9e-3})
    assert a.compat_key == b.compat_key


def test_canonicalize_on_rung_dt_untouched(tmp_path):
    srv = SimServer(
        _cfg(tmp_path, canonicalize=CanonicalConfig(dt_anchor=1e-2))
    )
    req = srv.submit({**_REQ, "dt": 1e-2})
    assert req.dt == 1e-2
    events = [
        r.get("event")
        for r in read_journal(os.path.join(srv.cfg.run_dir, "journal.jsonl"))
    ]
    assert "request_canonicalized" not in events


def test_canonicalize_shift_bound_keeps_exact_dt(tmp_path):
    # 3e-3 would snap to the 2.5e-3 rung (-17%), beyond a 0.1 bound: the
    # request keeps its exact dt and pays its own compile
    srv = SimServer(
        _cfg(
            tmp_path,
            canonicalize=CanonicalConfig(dt_anchor=1e-2, max_rel_dt_shift=0.1),
        )
    )
    req = srv.submit({**_REQ, "dt": 3e-3})
    assert req.dt == 3e-3


def test_canonicalize_off_is_inert(tmp_path):
    srv = SimServer(_cfg(tmp_path))
    req = srv.submit({**_REQ, "dt": 9e-3})
    assert req.dt == 9e-3
    assert srv._canon_ladder is None


def test_canonical_k_rounds_up_to_pool_size(tmp_path):
    canon = CanonicalConfig(slot_sizes=(2, 4, 8))
    assert SimServer(
        _cfg(tmp_path / "a", slots=3, canonicalize=canon)
    )._canonical_k() == 4
    # above every pool size: the largest pool wins (lanes are bounded)
    assert SimServer(
        _cfg(tmp_path / "b", slots=16, canonicalize=canon)
    )._canonical_k() == 8
    assert SimServer(_cfg(tmp_path / "c", slots=3))._canonical_k() == 3


# -- canonicalized-vs-direct parity -------------------------------------------


def _serve_one(tmp_path, name, dt, canonicalize):
    srv = SimServer(
        _cfg(tmp_path / name, canonicalize=canonicalize, slots=1)
    )
    req = srv.submit({**_REQ, "dt": dt, "horizon": 0.08, "seed": 3})
    srv.serve()
    return srv.result(req.id)


def test_canonicalized_parity_within_documented_rtol(tmp_path):
    """The canonicalization contract's physics half: a dt snapped onto
    the ladder reaches the same horizon with observables within
    ``CanonicalConfig.rtol`` of the exact-dt run."""
    canon = CanonicalConfig(dt_anchor=1e-2)
    direct = _serve_one(tmp_path, "direct", 9e-3, None)
    snapped = _serve_one(tmp_path, "snapped", 9e-3, canon)
    assert direct is not None and snapped is not None
    scale = max(abs(direct["nu"]), 1e-12)
    assert abs(snapped["nu"] - direct["nu"]) / scale <= canon.rtol


# -- warm pool ----------------------------------------------------------------


class _FakeEns:
    def __init__(self, k):
        self.k = k


def _key(tag="dns", nx=17):
    return (tag, nx, nx, 1e4, 1.0, 1e-2, 1.0, "rbc", False, ())


def test_freeze_key_normalizes_json_round_trip():
    key = _key()
    thawed = json.loads(json.dumps(list(key)))
    assert freeze_key(thawed) == key
    assert compile_log.key_tag(freeze_key(thawed)) == compile_log.key_tag(key)


def test_warm_pool_hit_miss_eviction_accounting():
    built = []

    def build(key, k):
        built.append(key)
        return object(), _FakeEns(k or 2), 1

    rows = []
    pool = WarmPool(
        [{"key": _key(), "k": 2}], build, journal=rows.append, max_entries=2
    )
    pool.start()
    assert pool.wait(timeout=10)
    assert pool.counts()["built"] == 1 and pool.counts()["pooled"] == 1

    # hit: ownership transfers, so the same key misses the second time
    got = pool.take(_key(), 2)
    assert got is not None and got[1].k == 2
    assert pool.take(_key(), 2) is None
    # unknown key: plain miss
    assert pool.take(_key(nx=33)) is None
    counts = pool.counts()
    assert counts["hits"] == 1 and counts["misses"] == 2
    events = [r["event"] for r in rows]
    assert events.count("aot_build") == 1
    assert events.count("warm_pool_hit") == 1
    assert events.count("warm_pool_miss") == 2


def test_warm_pool_k_mismatch_is_miss_and_eviction():
    pool = WarmPool([], lambda key, k: None)
    pool.put(_key(), object(), _FakeEns(2))
    assert pool.take(_key(), 4) is None
    counts = pool.counts()
    assert counts["misses"] == 1 and counts["evictions"] == 1
    assert counts["pooled"] == 0


def test_warm_pool_capacity_eviction_is_fifo():
    rows = []
    pool = WarmPool([], lambda key, k: None, journal=rows.append, max_entries=1)
    pool.put(_key(nx=17), object(), _FakeEns(2))
    pool.put(_key(nx=33), object(), _FakeEns(2))
    assert pool.counts() == {
        "hits": 0, "misses": 0, "evictions": 1, "built": 0,
        "build_errors": 0, "pooled": 1,
    }
    assert pool.take(_key(nx=33)) is not None  # newest survived
    (evict,) = [r for r in rows if r["event"] == "warm_pool_evict"]
    assert evict["reason"] == "capacity"


def test_warm_pool_take_waits_for_in_flight_build():
    """The race the wait kills: a campaign opening before the background
    builder finishes must BLOCK on the in-flight entry (the build started
    earlier, so waiting beats a duplicate inline compile), not record a
    miss and cold-build the same key twice."""
    release = threading.Event()

    def build(key, k):
        release.wait(10)
        return object(), _FakeEns(k or 2), 1

    pool = WarmPool([{"key": _key(), "k": 2}], build)
    pool.start()
    got = {}

    def taker():
        got["entry"] = pool.take(_key(), 2)

    t = threading.Thread(target=taker)
    t.start()
    t.join(0.2)
    assert t.is_alive(), "take() returned before the in-flight build finished"
    release.set()
    t.join(10)
    assert not t.is_alive() and got["entry"] is not None
    assert pool.counts()["hits"] == 1 and pool.counts()["misses"] == 0


def test_warm_pool_stop_unblocks_waiters_and_skips_entries():
    def build(key, k):
        time.sleep(0.05)
        return object(), _FakeEns(2), 1

    pool = WarmPool([{"key": _key(nx=n)} for n in (17, 33, 65)], build)
    pool.stop()  # stop BEFORE start: every entry skipped, no waiter hangs
    pool.start()
    assert pool.wait(timeout=10)
    assert pool.take(_key(nx=65)) is None  # miss, but instant — not a hang


def test_warm_pool_build_error_accounted_not_fatal():
    def build(key, k):
        if key[1] == 17:
            raise RuntimeError("boom")
        return object(), _FakeEns(2), 1

    rows = []
    pool = WarmPool(
        [{"key": _key(nx=17)}, {"key": _key(nx=33)}], build, journal=rows.append
    )
    pool.start()
    assert pool.wait(timeout=10)
    counts = pool.counts()
    assert counts["build_errors"] == 1 and counts["built"] == 1
    errs = [r for r in rows if r["event"] == "warm_pool_error"]
    assert len(errs) == 1 and "boom" in errs[0]["error"]


# -- profiles -----------------------------------------------------------------


def test_profile_load_save_round_trip(tmp_path):
    path = str(tmp_path / "profile.json")
    save_profile(path, [{"key": _key(), "k": 4}])
    entries = load_profile(path)
    assert entries == [{"key": _key(), "k": 4}]
    # inline lists pass through with the same normalization
    assert load_profile([{"key": list(_key()), "k": "4"}]) == [
        {"key": _key(), "k": 4}
    ]
    # missing/corrupt files must not stop the service from booting
    assert load_profile(str(tmp_path / "nope.json")) == []
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert load_profile(str(bad)) == []
    assert load_profile(None) == []


def test_learn_profile_ranks_by_build_count_and_skips_aot(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    rows = (
        [{"event": "compile_build", "key": list(_key(nx=17)), "k": 2,
          "phase": "build"}] * 3
        + [{"event": "compile_build", "key": list(_key(nx=33)), "k": 4,
            "phase": "build"}]
        # the pool must not learn from its own background builds
        + [{"event": "compile_build", "key": list(_key(nx=65)),
            "phase": "aot"}] * 9
        + [{"event": "request_done", "id": "x"}]
    )
    with open(path, "w") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")
    entries = learn_profile(path)
    assert [e["key"][1] for e in entries] == [17, 33]
    assert entries[0]["k"] == 2 and entries[1]["k"] == 4
    assert learn_profile(str(tmp_path / "missing.jsonl")) == []


# -- warm serve end to end ----------------------------------------------------


def test_warm_pool_serve_hits_with_zero_jit_builds(tmp_path):
    """The acceptance gate at test scale: with the key's campaign
    prebuilt from the profile, admission -> first chunk crosses ZERO
    compile_build rows — the warm takeover skips the jit entirely — and
    an off-rung request canonicalizes into the same warm bucket."""
    profile = [{"key": list(_REQ_KEY), "k": 2}]
    srv = SimServer(
        _cfg(
            tmp_path,
            chunk_steps=8,
            warm_profile=profile,
            canonicalize=CanonicalConfig(dt_anchor=1e-2, slot_sizes=(2,)),
        )
    )
    for seed, dt in enumerate([1e-2, 9e-3]):
        srv.submit({**_REQ, "dt": dt, "horizon": 0.08, "seed": seed})
    summary = srv.serve()
    assert summary["completed"] == 2
    events = {}
    for row in read_journal(os.path.join(srv.cfg.run_dir, "journal.jsonl")):
        events[row.get("event")] = events.get(row.get("event"), 0) + 1
    assert events.get("warm_pool_hit") == 1
    assert events.get("aot_build") == 1
    assert events.get("request_canonicalized") == 1
    assert "compile_build" not in events, "warm campaign still jit-built"


_REQ_KEY = ("dns", 17, 17, 1e4, 1.0, 1e-2, 1.0, "rbc", False, ())


def test_warm_pool_off_no_thread_no_rows(tmp_path):
    srv = SimServer(_cfg(tmp_path, chunk_steps=8))
    srv.submit({**_REQ, "horizon": 0.04})
    srv.serve()
    assert srv._warm is None
    events = [
        r.get("event")
        for r in read_journal(os.path.join(srv.cfg.run_dir, "journal.jsonl"))
    ]
    assert not any(
        e and (e.startswith("warm_pool") or e == "aot_build") for e in events
    )
    assert "compile_build" in events  # the cold path still journals builds


# -- cross-process persistent cache reuse -------------------------------------

_CHILD_COMPILE = r"""
import json, os, sys, time
import jax
import jax.numpy as jnp

def step(x):
    for _ in range(8):
        x = jnp.fft.rfft2(jnp.tanh(jnp.fft.irfft2(x, s=(48, 48))))
    return x

x = jnp.ones((48, 25), dtype=jnp.complex64)
fn = jax.jit(step)
t0 = time.perf_counter()
fn.lower(x).compile()
print(json.dumps({"compile_s": time.perf_counter() - t0}))
"""


def test_cross_process_cache_reuse(tmp_path):
    """Second process's compile of the SAME function deserializes from
    the persistent cache dir instead of recompiling.  The gate is
    deliberately lenient (CI timing noise): the cache dir must be
    populated by the first child, and the second child's compile must
    not be slower — with a real speedup asserted only when the cold
    compile was slow enough to measure."""
    cache = str(tmp_path / "cache")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": cache,
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    }

    def run():
        out = subprocess.run(
            [sys.executable, "-c", _CHILD_COMPILE],
            env=env, capture_output=True, text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])["compile_s"]

    cold = run()
    assert os.listdir(cache), "first compile left the cache dir empty"
    warm = run()
    assert warm <= cold * 1.1 + 0.05
    if cold > 1.0:
        assert warm <= cold * 0.8


# -- replica boots warm (slow tier) -------------------------------------------


@pytest.mark.slow
def test_restarted_server_boots_warm_from_shared_cache(tmp_path):
    """Restart-to-first-result with a shared persistent cache: the second
    server process (fresh run_dir, same cache dir) rebuilds its campaign
    against serialized executables — its jit-build wall collapses vs the
    cold first boot.  This is the autoscaled-replica contract: a scale-out
    spawn inherits JAX_COMPILATION_CACHE_DIR through the launcher env and
    pays deserialization, not compilation."""
    cache = str(tmp_path / "cache")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "RUSTPDE_COMPILE_CACHE": "1",
        "JAX_COMPILATION_CACHE_DIR": cache,
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    }

    def boot(name):
        run_dir = str(tmp_path / name)
        out = subprocess.run(
            [
                sys.executable,
                os.path.join(_REPO, "examples", "navier_rbc_serve.py"),
                "--quick", "--requests", "1", "--slots", "1",
                "--horizon", "0.04", "--run-dir", run_dir,
            ],
            env=env, capture_output=True, text=True, timeout=600, cwd=_REPO,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        walls = [
            float(r.get("wall_s", 0.0))
            for r in read_journal(os.path.join(run_dir, "journal.jsonl"))
            if r.get("event") == "compile_build" and r.get("phase") == "build"
        ]
        assert walls, "no compile_build rows journaled"
        return sum(walls)

    cold = boot("first")
    warm = boot("second")
    assert os.listdir(cache)
    assert warm < cold, f"warm boot not faster: {warm:.2f}s vs {cold:.2f}s"
    if cold > 2.0:
        assert warm <= cold * 0.7
