"""Fleet serving: stateless proxies + replica SimServers over one queue.

One shared ``--run-dir`` holds the durable queue, the bucket leases, the
parked continuations and the per-replica journals; any number of proxy
and replica processes attach to it.  Kill any one of them — proxies are
stateless, replicas are leased — and the fleet keeps serving.

Start a proxy (prints its bound address as a JSON line)::

    python examples/navier_rbc_fleet.py --proxy --http-port 0 --run-dir data/fleet

Start two replicas (each is one SimServer in fleet mode)::

    python examples/navier_rbc_fleet.py --replica --replica-id rA --run-dir data/fleet
    python examples/navier_rbc_fleet.py --replica --replica-id rB --run-dir data/fleet

Submit mixed-priority traffic through the proxy::

    curl -X POST localhost:<port>/requests -d '{"ra":1e4,"nx":17,"ny":17,
      "dt":0.01,"horizon":0.2,"priority":"interactive","deadline_s":30}'
    curl localhost:<port>/stats      # queue + leases + replica heartbeats

SIGTERM drains a replica gracefully; SIGKILL exercises the lease-break
path (survivors re-claim the dead replica's requests and resume them
mid-flight from the durable parked state).
"""

import argparse
import json
import os
import signal
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from rustpde_mpi_tpu.config import FleetConfig, ServeConfig  # noqa: E402


def run_proxy(args) -> int:
    from rustpde_mpi_tpu.serve.fleet.proxy import FleetProxy

    fleet = FleetConfig(
        lease_ttl_s=args.lease_ttl_s, default_quota=args.quota
    )
    proxy = FleetProxy(
        args.run_dir,
        port=args.http_port or 0,
        max_queue=args.max_queue,
        fleet=fleet,
    )
    proxy.start()
    # a driver parses this line for the ephemeral port
    print(json.dumps({"proxy": proxy.proxy_id, "address": list(proxy.address)}),
          flush=True)
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    proxy.stop()
    print(json.dumps({"outcome": "stopped", **proxy.stats()}), flush=True)
    return 0


def run_replica(args) -> int:
    from rustpde_mpi_tpu.serve import SimServer

    fleet = FleetConfig(
        replica_id=args.replica_id,
        lease_ttl_s=args.lease_ttl_s,
        heartbeat_s=args.heartbeat_s,
        default_quota=args.quota,
        preempt_slack_s=args.preempt_slack_s,
    )
    cfg = ServeConfig(
        run_dir=args.run_dir,
        slots=args.slots,
        max_queue=args.max_queue,
        chunk_steps=args.chunk_steps,
        checkpoint_every_s=args.ckpt_every_s,
        idle_exit=not args.daemon,
        poll_s=0.1,
        http_port=None,  # the proxy tier is the front door
        fleet=fleet,
    )
    server = SimServer(cfg, fault=args.fault)
    summary = server.serve()
    print(json.dumps(summary), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--proxy", action="store_true")
    mode.add_argument("--replica", action="store_true")
    ap.add_argument("--run-dir", default="data/fleet")
    ap.add_argument("--replica-id", default="")
    ap.add_argument("--http-port", type=int, default=0)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--chunk-steps", type=int, default=8)
    ap.add_argument("--max-queue", type=int, default=512)
    ap.add_argument("--ckpt-every-s", type=float, default=30.0)
    ap.add_argument("--lease-ttl-s", type=float, default=None)
    ap.add_argument("--heartbeat-s", type=float, default=None)
    ap.add_argument("--quota", type=int, default=None)
    ap.add_argument("--preempt-slack-s", type=float, default=30.0)
    ap.add_argument("--daemon", action="store_true",
                    help="keep serving after the queue drains (replicas)")
    ap.add_argument("--fault", default=None,
                    help="nan@<step> | spike@<step> | kill@<step> | slow@<step>")
    args = ap.parse_args()
    return run_proxy(args) if args.proxy else run_replica(args)


if __name__ == "__main__":
    sys.exit(main())
