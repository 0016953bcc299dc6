"""Two-level serving units (parallel/submesh.py + serve/fleet/gang.py):
sub-mesh canonicalization and carving, gang-lease fate sharing — all-or-
nothing formation, the break-vs-member-renew race (exactly one winner,
tokens monotonic across gang generations), the fate-shared stale sweep —
partial-gang heartbeat aggregation, typed sub-mesh admission, gang fault
scoping, and the CI guard that the default (``submesh=None``) service
emits not one gang journal row and keeps today's bare bucket keys.

The 2-process gang campaign itself (formation, SIGKILL containment,
loss-free reclaim) runs in tests/test_multiprocess.py's slow tier.
"""

import os
import time

import pytest

from rustpde_mpi_tpu.config import ServeConfig, SubmeshConfig
from rustpde_mpi_tpu.parallel import submesh as sm
from rustpde_mpi_tpu.serve import SimRequest, SimServer
from rustpde_mpi_tpu.serve.fleet import gang as gg
from rustpde_mpi_tpu.serve.fleet import qos as qos
from rustpde_mpi_tpu.serve.fleet.lease import LeaseLost, LeaseManager, bucket_tag
from rustpde_mpi_tpu.serve.fleet.proxy import (
    read_replica_status,
    write_replica_heartbeat,
)
from rustpde_mpi_tpu.serve.request import AdmissionError, RequestError
from rustpde_mpi_tpu.utils.faults import FaultPlan, FaultSpecError
from rustpde_mpi_tpu.utils.journal import read_journal

pytest.importorskip("h5py")

_KEY = ("rbc", 34, 34, "1.0e4", "1.0", "0.01", 0, "f64", "none", "base", 2)


class _Dev:
    """CPU test double for a jax device: only process_index matters."""

    def __init__(self, pid):
        self.process_index = pid

    def __repr__(self):
        return f"dev(p{self.process_index})"


# -- canonicalization (pure, proxy-side) --------------------------------------


def test_grid_fits_divisibility_rule():
    assert sm.grid_fits(17, 17, 1)  # shape 1 always fits (unsharded)
    assert sm.grid_fits(34, 34, 2)  # full extent divides
    assert sm.grid_fits(130, 130, 4)  # interior (n-2) divides
    assert not sm.grid_fits(33, 33, 2)  # neither 33 nor 31 divides
    assert not sm.grid_fits(34, 33, 2)  # both dims must fit


def test_shape_for_stamps_smallest_fitting_shape():
    cfg = SubmeshConfig(shapes=(4, 2), shard_min_nx=34)
    assert sm.shape_for(17, 17, cfg) == 0  # below threshold: vmapped
    assert sm.shape_for(34, 34, cfg) == 2  # smallest fitting, not 4
    assert sm.shape_for(132, 132, cfg) == 2  # deterministic across fronts
    assert sm.shape_for(35, 35, cfg) == -1  # must shard, nothing fits


def test_serve_key_stamp_roundtrip_and_default_identity():
    bare = _KEY[:10]
    assert sm.serve_key(bare, 0) == bare  # submesh off: byte-identical
    stamped = sm.serve_key(bare, 2)
    assert len(stamped) == 11 and stamped[10] == 2
    assert sm.model_key(stamped) == bare
    assert sm.key_shape(stamped) == 2
    assert sm.key_shape(bare) == 0


# -- carving (device binding, replica-side) -----------------------------------


def test_carve_interleaves_processes_and_keeps_devices_disjoint():
    # 2 processes x 4 local devices; one 4-gang + default remainder
    devs = [_Dev(p) for p in (0, 0, 0, 0, 1, 1, 1, 1)]
    plan = sm.carve(devs, shapes=(4,), nproc=2)
    (gangsm,) = plan.submeshes
    assert gangsm.shape == 4 and plan.default.shape == 4
    # every sub-mesh takes equal devices from every process (no process
    # is ever absent from a sub-mesh collective)
    for slice_ in (gangsm.devices, plan.default.devices):
        procs = [d.process_index for d in slice_]
        assert procs.count(0) == procs.count(1) == 2
    assert set(gangsm.devices).isdisjoint(plan.default.devices)


def test_carve_drops_unfittable_and_non_process_aligned_shapes():
    devs = [_Dev(p) for p in (0, 1)]
    # 3 is not a multiple of nproc=2, 8 exceeds the fleet: both dropped
    plan = sm.carve(devs, shapes=(8, 3, 2), nproc=2)
    assert [s.shape for s in plan.submeshes] == [2]
    assert plan.default is None  # nothing left over


def test_place_exact_then_elastic_replan_then_unplaceable():
    devs = [_Dev(0) for _ in range(6)]
    plan = sm.carve(devs, shapes=(4, 2), nproc=1)
    exact, replanned = plan.place(36, 36, 4)
    assert exact.shape == 4 and replanned is False
    # the stamp names a shape the carve no longer has: largest still-
    # fitting sub-mesh, reported as a replan (journaled gang_replanned)
    shrunk = sm.carve(devs[:2], shapes=(2,), nproc=1)
    moved, replanned = shrunk.place(36, 36, 4)
    assert moved.shape == 2 and replanned is True
    nowhere, replanned = shrunk.place(35, 35, 4)
    assert nowhere is None and replanned is False


# -- gang leases: fate-shared formation / break / sweep -----------------------


def test_gang_formation_is_all_or_nothing(tmp_path):
    root = str(tmp_path / "leases")
    mgr = LeaseManager(root, "replica-a", ttl_s=5.0)
    intruder = LeaseManager(root, "intruder", ttl_s=5.0)
    held = intruder.claim(gg.member_key(_KEY, 1))
    assert held is not None
    # member 1 is taken: the whole formation rolls back — no group lease,
    # no member-0 lease left holding capacity
    assert gg.GangLease.form(mgr, _KEY, 2) is None
    holders = mgr.holders()
    assert bucket_tag(gg.gang_key(_KEY)) not in holders
    assert bucket_tag(gg.member_key(_KEY, 0)) not in holders
    held.release()
    g = gg.GangLease.form(mgr, _KEY, 2)
    assert g is not None and len(g.members) == 2
    # the rolled-back claims escrowed their tokens: generation advanced
    assert g.generation >= 2
    g.release()


def test_gang_break_vs_member_renew_race_one_winner_tokens_monotonic(tmp_path):
    """The satellite race: a survivor breaks the gang while a member is
    mid-renew.  Exactly one side wins (the group-lease rename is the
    linearization point), the loser fences typed, and after re-formation
    every token — group generation and each member's — is strictly newer
    than anything the dead gang ever held."""
    root = str(tmp_path / "leases")
    holder = LeaseManager(root, "holder", ttl_s=0.1)
    survivor = LeaseManager(root, "survivor", ttl_s=0.1)
    peer = LeaseManager(root, "peer", ttl_s=0.1)
    g1 = gg.GangLease.form(holder, _KEY, 2)
    assert g1 is not None
    gen1 = g1.generation
    member_tokens1 = [m.token for m in g1.members]
    g1.renew_member(0)  # pre-race: renew under the gang's authority works

    broken = gg.break_gang(survivor, _KEY, 2)
    assert broken is not None and broken["owner"] == "holder"
    # exactly one break winner: the racing peer loses cleanly
    assert gg.break_gang(peer, _KEY, 2) is None
    # the holder's in-flight member renew fences instead of writing
    with pytest.raises(LeaseLost):
        g1.renew_member(0)
    with pytest.raises(LeaseLost):
        g1.renew()
    with pytest.raises(LeaseLost):
        g1.guard()

    g2 = gg.GangLease.form(survivor, _KEY, 2)
    assert g2 is not None
    assert g2.generation > gen1
    for new, old in zip((m.token for m in g2.members), member_tokens1):
        assert new > old  # member escrows advanced through the break
    g2.release()


def test_stale_gang_sweep_breaks_group_and_members_not_buckets(tmp_path):
    root = str(tmp_path / "leases")
    holder = LeaseManager(root, "holder", ttl_s=0.08)
    survivor = LeaseManager(root, "survivor", ttl_s=0.08)
    g = gg.GangLease.form(holder, _KEY, 2)
    assert g is not None
    plain = holder.claim(("bucket",) + _KEY)  # ordinary bucket lease
    assert plain is not None
    assert gg.stale_gangs(survivor) == []  # first pass opens the window
    time.sleep(0.12)  # the gang stops heartbeating
    (rec,) = gg.stale_gangs(survivor)
    assert rec["owner"] == "holder"
    holders = survivor.holders()
    # fate-shared: group AND every member lease are gone together...
    assert bucket_tag(gg.gang_key(_KEY)) not in holders
    for i in range(2):
        assert bucket_tag(gg.member_key(_KEY, i)) not in holders
    # ...but the ordinary bucket lease is not the gang sweep's business
    assert bucket_tag(("bucket",) + _KEY) in holders


# -- partial-gang heartbeats --------------------------------------------------


def test_replica_status_aggregates_partial_gang_heartbeats(tmp_path):
    """When only SOME gang members still heartbeat, the aggregation shows
    the sick gang instead of silently forgetting the dead member: the
    fresh member reports its gang, the missing one surfaces stale."""
    run_dir = str(tmp_path / "fleet")
    write_replica_heartbeat(
        run_dir, "gang0-m0", {"gang": 0, "member": 0, "slots": [1, 2]}
    )
    write_replica_heartbeat(
        run_dir, "gang0-m1", {"gang": 0, "member": 1, "slots": [1, 2]}
    )
    # member 1's writer died: its file stops being rewritten
    old = time.time() - 60.0
    os.utime(os.path.join(run_dir, "replicas", "gang0-m1.json"), (old, old))
    status = read_replica_status(run_dir, ttl_s=5.0)
    by_id = {r["replica"]: r for r in status}
    assert by_id["gang0-m0"]["stale"] is False
    assert by_id["gang0-m0"]["gang"] == 0
    assert by_id["gang0-m1"]["stale"] is True  # visible, not forgotten
    fresh = [r for r in status if not r["stale"]]
    assert len(fresh) == 1  # the gang is NOT quorate: 1 of 2 members


# -- sub-mesh admission (typed rejects at the door) ---------------------------


def _req(nx, ny):
    return SimRequest(
        ra=1e4, pr=1.0, nx=nx, ny=ny, dt=0.01, horizon=0.1, bc="rbc"
    )


def test_admit_submesh_stamps_rejects_and_passes_through():
    cfg = SubmeshConfig(shapes=(2,), shard_min_nx=34, max_pending=2)
    # feature off: byte-identical pass-through
    small = _req(17, 17)
    assert qos.admit_submesh(small, 0, None) is small
    # vmapped traffic below the threshold: unstamped
    assert qos.admit_submesh(small, 0, cfg).submesh == 0
    # sharded traffic: stamped with the canonical shape
    stamped = qos.admit_submesh(_req(34, 34), 0, cfg)
    assert stamped.submesh == 2
    assert len(stamped.compat_key) == 11 and stamped.compat_key[10] == 2
    # permanent mismatch: typed 400 at POST, not a durable poison pill
    with pytest.raises(RequestError) as exc:
        qos.admit_submesh(_req(35, 35), 0, cfg)
    assert exc.value.reason == "no_submesh"
    # transient sharded backlog: 429 with queue-depth-derived Retry-After
    with pytest.raises(AdmissionError) as exc:
        qos.admit_submesh(_req(34, 34), 2, cfg)
    assert exc.value.reason == "capacity"
    assert exc.value.retry_after_s >= 2.0


# -- gang fault scoping -------------------------------------------------------


def test_fault_plan_gang_scope_parsing_and_binding():
    plan = FaultPlan.from_spec("kill@5:gang0member1")
    assert (plan.kind, plan.step) == ("kill", 5)
    assert (plan.gang, plan.member) == (0, 1)
    assert plan.scoped_here() is False  # no gang campaign bound
    plan.bind_gang(0, 1)
    assert plan.scoped_here() is True
    plan.bind_gang(0, 0)  # right gang, wrong member
    assert plan.scoped_here() is False
    plan.bind_gang(None, None)  # campaign closed: never acts again
    assert plan.scoped_here() is False
    # gang-wide scope (no member): every bound member of gang 2 acts
    wide = FaultPlan.from_spec("nan@3:gang2")
    wide.bind_gang(2, 1)
    assert wide.scoped_here() is True


@pytest.mark.parametrize(
    "spec",
    [
        "kill@5:gang",  # missing index
        "kill@5:gangXmember1",  # non-numeric gang
        "kill@5:gang0member",  # member keyword without index
        "kill@5:gang0memberX",  # non-numeric member
        "kill@5:gang0extra",  # trailing junk
    ],
)
def test_fault_plan_gang_scope_malformed_raise_typed(spec):
    with pytest.raises(FaultSpecError):
        FaultPlan.from_spec(spec)


# -- default config: byte-identical to today ----------------------------------


def test_default_config_serves_without_any_gang_rows(tmp_path):
    """The acceptance guard: ``ServeConfig.submesh=None`` (the default)
    must be byte-identical to the pre-gang service — bare 10-tuple
    bucket keys, zero gang/submesh journal rows, no gang counters."""
    cfg = ServeConfig(
        run_dir=str(tmp_path / "serve"),
        slots=2,
        chunk_steps=4,
        checkpoint_every_s=None,
        http_port=None,
    )
    assert cfg.submesh is None
    srv = SimServer(cfg)
    req = srv.submit(
        dict(ra=1e4, pr=1.0, nx=17, ny=17, dt=0.01, horizon=0.05, bc="rbc")
    )
    assert len(req.compat_key) == 10  # bare key: no stamp element
    summary = srv.serve()
    assert summary["completed"] == 1 and summary["failed"] == 0
    events = read_journal(os.path.join(cfg.run_dir, "journal.jsonl"))
    gangish = [
        e["event"]
        for e in events
        if e["event"].startswith(("gang_", "submesh_"))
    ]
    assert gangish == []
    assert "gangs" not in srv.stats()
