"""Runtime collective-sequence sanitizer (``RUSTPDE_SANITIZE=1``).

The multihost correctness contract (README "Multihost campaigns") is that
EVERY host executes the identical sequence of collectives — each scheduling
decision root-computed and broadcast before any collective dispatch.  The
reference gets this for free from MPI's rigid call structure; our port
re-derives it by hand, and the repo's own history shows the failure mode:
a drain check evaluated outside the root plan left one host's collectives
out of phase (PR 10 review), and the symptom of any such desync is a
SILENT fleet wedge — every host blocked in a collective its peers never
entered, diagnosed only by a watchdog stack dump long after the divergent
decision ran.

With the sanitizer armed, every collective entry point in
:mod:`~rustpde_mpi_tpu.parallel.multihost` (``broadcast``,
``broadcast_obj`` via its inner broadcasts, ``allgather_host``,
``sync_hosts``, ``root_decides``) records ``(seq, kind, tag, call site,
payload-schema digest)`` into a bounded per-host ring plus a running
sha256 over the full history.  Every ``RUSTPDE_SANITIZE_CADENCE``
executed collectives, a fixed-shape hash compare rides one extra
``allgather_host`` — the trigger counts EXECUTED collectives, which stay
in lockstep across hosts at the transport level even when one host skipped
a call, so the verification exchange always pairs with itself.  On a hash
mismatch the hosts exchange their rings and every host raises a typed
:class:`CollectiveDesyncError` naming the FIRST divergent call site (and
dumps the telemetry flight recorder), turning the silent wedge into an
immediate, located diagnosis within one cadence.

Overhead contract: ``RUSTPDE_SANITIZE`` unset/0 costs one module-bool
branch per collective and records nothing — runs are bit-identical (the
sanitizer is host-side only and never touches traced programs; armed runs
are bit-identical too: tests/test_sanitizer.py).  Armed, each
record is a frame walk + sha256 update — microseconds against the
milliseconds any real collective costs.

Injection (tests): ``RUSTPDE_SANITIZE_INJECT=skip_broadcast@<n>[:host<p>]``
makes the scoped host SKIP its ``<n>``-th broadcast entirely (no record,
no collective) — the exact shape of the PR-10 bug — so the 2-process test
can assert both hosts raise within one cadence.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import threading
from collections import deque

import numpy as np

from ..config import env_get

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SKIP_FILES = (os.sep + "multihost.py", os.sep + "sanitizer.py")


class CollectiveDesyncError(RuntimeError):
    """The cross-host collective sequences diverged.  ``seq`` is the global
    index of the first divergent record, ``sites`` maps process index ->
    that host's record at ``seq`` (or None where the host has no record —
    e.g. it skipped the call), ``site`` is the first divergent call site
    as a ``file:line`` string (the majority/root form, for log grepping)."""

    def __init__(self, message: str, seq: int | None = None,
                 sites: dict | None = None, site: str | None = None):
        super().__init__(message)
        self.seq = seq
        self.sites = sites or {}
        self.site = site


class _InjectPlan:
    """Parsed ``RUSTPDE_SANITIZE_INJECT`` spec (strict, like utils/faults)."""

    EXPECTED = "skip_broadcast@<n>[:host<p>]"

    def __init__(self, call: int, host: int | None):
        self.call = call
        self.host = host
        self.seen = 0

    @classmethod
    def from_spec(cls, spec: str | None) -> "_InjectPlan | None":
        if not spec:
            return None
        kind, sep, rest = spec.partition("@")
        if kind != "skip_broadcast" or not sep:
            raise ValueError(
                f"bad RUSTPDE_SANITIZE_INJECT {spec!r}: expected {cls.EXPECTED}"
            )
        at, hsep, host = rest.partition(":")
        if not at.isdigit():
            raise ValueError(
                f"bad RUSTPDE_SANITIZE_INJECT {spec!r}: bad call index {at!r}"
            )
        hostidx = None
        if hsep:
            if not host.startswith("host") or not host[4:].isdigit():
                raise ValueError(
                    f"bad RUSTPDE_SANITIZE_INJECT {spec!r}: bad host scope {host!r}"
                )
            hostidx = int(host[4:])
        return cls(int(at), hostidx)


class _State:
    def __init__(self):
        self.lock = threading.RLock()
        self.reload()

    def reload(self):
        self.enabled = env_get("RUSTPDE_SANITIZE", "0") == "1"
        self.cadence = max(1, int(env_get("RUSTPDE_SANITIZE_CADENCE", "32") or 32))
        capacity = max(8, int(env_get("RUSTPDE_SANITIZE_RING", "256") or 256))
        self.ring: deque = deque(maxlen=capacity)
        self.seq = 0
        self.hash = hashlib.sha256()
        # the verification trigger counts EXECUTED collectives (paired 1:1
        # across hosts at the transport level), NOT ring records: a
        # root_decides record carries intent without its own transport
        # slot, so record counts may skew across hosts after a skipped
        # call while executed counts cannot
        self.executed = 0
        self.last_verify_exec = 0
        self.in_verify = False
        self.run_dir: str | None = None
        self.records = 0
        self.verifies = 0
        self.desyncs = 0
        self.inject = _InjectPlan.from_spec(env_get("RUSTPDE_SANITIZE_INJECT"))


_STATE = _State()


def enabled() -> bool:
    return _STATE.enabled


def set_enabled(flag: bool) -> None:
    """Arm/disarm in-process (``RUSTPDE_SANITIZE`` env default; tests toggle
    this)."""
    _STATE.enabled = bool(flag)


def reset() -> None:
    """Re-read every knob and clear the ring/counters (tests, and fresh
    service incarnations that want a clean sequence history)."""
    _STATE.reload()


def set_run_dir(path: str | None) -> None:
    """Where a desync trip dumps the telemetry flight record (the runner /
    serve session arms this alongside its own incident dumps)."""
    _STATE.run_dir = path


def stats() -> dict:
    """Host-local counters: records, verifies, desyncs, seq."""
    return {
        "enabled": _STATE.enabled,
        "records": _STATE.records,
        "executed": _STATE.executed,
        "verifies": _STATE.verifies,
        "desyncs": _STATE.desyncs,
        "seq": _STATE.seq,
        "cadence": _STATE.cadence,
    }


def np_schema(value) -> str:
    """Payload-schema digest of a small host value: dtype + shape (host-
    invariant when the fleet is in sync — values may differ, shapes not)."""
    try:
        a = np.asarray(value)
        return f"{a.dtype}{list(a.shape)}"
    except Exception:
        return type(value).__name__


def _call_site() -> str:
    """First stack frame outside multihost.py/sanitizer.py, repo-relative
    (hosts run the same tree, so sites are host-invariant)."""
    frame = sys._getframe(1)
    while frame is not None:
        fname = frame.f_code.co_filename
        if not fname.endswith(_SKIP_FILES):
            try:
                rel = os.path.relpath(fname, _REPO_ROOT)
            except ValueError:
                rel = fname
            if not rel.startswith(".."):
                fname = rel
            return f"{fname}:{frame.f_lineno}"
        frame = frame.f_back
    return "<unknown>"


def skip_broadcast_injected() -> bool:
    """True when the armed injection plan says THIS broadcast call must be
    skipped on THIS host (no record, no collective — the PR-10 bug shape)."""
    plan = _STATE.inject
    if plan is None:
        return False
    with _STATE.lock:
        plan.seen += 1
        if plan.seen != plan.call:
            return False
    if plan.host is None:
        return True
    try:
        import jax

        return int(jax.process_index()) == plan.host
    except Exception:
        return plan.host == 0


def record(kind: str, tag: str = "", payload=None) -> None:
    """Append one collective record (kind, tag, call site, payload schema)
    to the ring + running hash.  No-op when disarmed or inside the
    verification exchange itself — the payload-schema digest is computed
    lazily AFTER the enabled gate, so the disarmed cost at every
    collective entry stays one function call + one branch."""
    st = _STATE
    if not st.enabled or st.in_verify:
        return
    schema = np_schema(payload) if payload is not None else ""
    site = _call_site()
    with st.lock:
        st.seq += 1
        st.records += 1
        entry = {"seq": st.seq, "kind": kind, "tag": tag, "site": site,
                 "schema": schema}
        st.ring.append(entry)
        st.hash.update(
            f"{st.seq}|{kind}|{tag}|{site}|{schema}".encode("utf-8", "replace")
        )


def _hash_words() -> tuple[int, int]:
    digest = _STATE.hash.digest()
    return (
        int.from_bytes(digest[:8], "big"),
        int.from_bytes(digest[8:16], "big"),
    )


def _gather(value):
    """Verification exchange: one allgather_host, optionally under the
    ``RUSTPDE_SYNC_TIMEOUT_S`` watchdog (a peer that died mid-window must
    become a structured DispatchHang, not a wedge)."""
    from . import multihost

    timeout = float(env_get("RUSTPDE_SYNC_TIMEOUT_S", "0") or 0.0)
    if timeout <= 0:
        return multihost.allgather_host(value)
    from ..utils.resilience import call_with_watchdog

    return call_with_watchdog(
        lambda: multihost.allgather_host(value), timeout, label="sanitizer_verify"
    )


def maybe_verify() -> None:
    """Cadenced cross-host sequence verification, called by multihost after
    each EXECUTED collective.  Executed collectives pair 1:1 across hosts
    at the transport level, so every host crosses the cadence threshold
    after the SAME paired collective and the verification exchange pairs
    with itself — even when the recorded sequences already diverged."""
    st = _STATE
    if not st.enabled or st.in_verify:
        return
    st.executed += 1
    if st.executed - st.last_verify_exec < st.cadence:
        return
    verify()


def verify() -> None:
    """One verification round: fixed-shape hash compare; on mismatch,
    exchange rings, locate the first divergent record, dump the flight
    recorder and raise :class:`CollectiveDesyncError` on EVERY host."""
    st = _STATE
    if not st.enabled or st.in_verify:
        return
    import jax

    if jax.process_count() == 1:
        st.last_verify_exec = st.executed
        return
    st.in_verify = True
    try:
        st.verifies += 1
        st.last_verify_exec = st.executed
        h0, h1 = _hash_words()
        rows = np.asarray(_gather(np.array([st.seq, h0, h1], dtype=np.uint64)))
        if bool((rows == rows[0]).all()):
            return
        st.desyncs += 1
        _raise_desync(rows)
    finally:
        st.in_verify = False


def _raise_desync(rows) -> None:
    """Rings ride a second (length-padded) exchange; every host runs the
    identical comparison on the identical gathered rings, so every host
    raises the same first-divergence diagnosis."""
    payload = json.dumps(list(_STATE.ring)).encode("utf-8")
    lengths = np.asarray(_gather(np.int64(len(payload)))).reshape(-1)
    width = int(lengths.max())
    buf = np.zeros(width, dtype=np.uint8)
    buf[: len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    gathered = np.asarray(_gather(buf)).astype(np.uint8)
    rings: dict[int, dict[int, dict]] = {}
    for proc in range(gathered.shape[0]):
        raw = gathered[proc, : int(lengths[proc])].tobytes().decode("utf-8")
        rings[proc] = {e["seq"]: e for e in json.loads(raw)}
    # compare only the COMMON seq window: ring eviction points differ when
    # hosts recorded different amounts, and a seq present on one host only
    # because the other evicted it is a window artifact, not a divergence
    lo = max((min(r) for r in rings.values() if r), default=0)
    all_seqs = sorted(
        s for s in set().union(*[set(r) for r in rings.values()]) if s >= lo
    )
    first_seq, sites = None, {}
    for seq in all_seqs:
        entries = {p: rings[p].get(seq) for p in rings}
        keys = {
            p: (e["kind"], e["tag"], e["site"], e["schema"]) if e else None
            for p, e in entries.items()
        }
        if len(set(keys.values())) > 1:
            first_seq, sites = seq, entries
            break
    if first_seq is None:
        counts = ", ".join(f"host{int(p)}: seq={int(rows[p][0])}" for p in range(len(rows)))
        message = (
            "collective sequences diverged BEFORE the ring window "
            f"({counts}); raise RUSTPDE_SANITIZE_RING or lower "
            "RUSTPDE_SANITIZE_CADENCE to catch the first divergent call"
        )
        site = None
    else:
        parts = []
        for p in sorted(sites):
            e = sites[p]
            parts.append(
                f"host{p}: {e['kind']}[{e['tag']}] at {e['site']} ({e['schema']})"
                if e
                else f"host{p}: <no collective recorded at seq {first_seq}>"
            )
        site = next((e["site"] for e in sites.values() if e), None)
        message = (
            f"collective sequence desync at global call #{first_seq}: "
            + "; ".join(parts)
            + " — a host-local decision reached a collective without going "
            "through root_decides/broadcast_obj (see README 'Static "
            "analysis & sanitizer')"
        )
    try:
        from ..telemetry import tracing

        tracing.instant("collective_desync", seq=first_seq, site=site)
        # dump only into an armed run_dir (the runner/serve session wires
        # set_run_dir): bare multihost usage must not litter the cwd
        if _STATE.run_dir:
            tracing.dump_flight_record(
                _STATE.run_dir, "collective_desync",
                extra={"seq": first_seq, "site": site},
            )
    except Exception:
        pass
    raise CollectiveDesyncError(message, seq=first_seq, sites=sites, site=site)
