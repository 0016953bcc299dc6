"""Shared 2-process CPU cluster spawner for tests/mp_worker.py.

One copy of the spawn recipe (port allocation, CPU/virtual-device env,
worker argv order, sequential communicate) for every test that needs a
real 2-controller cluster.  Deliberately imports no jax: the parent must
not have its platform touched.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def spawn_cluster(
    out_dir: str,
    mode: str | None = None,
    nproc: int = 2,
    env_extra: dict | None = None,
    timeout: float = 600,
    check: bool = True,
):
    """Run ``nproc`` mp_worker.py processes as one jax.distributed cluster.

    Returns ``[(returncode, stdout, stderr), ...]`` in rank order, or
    ``None`` when the spawn timed out (workers killed — callers decide
    whether that skips or fails).  ``check=True`` asserts every rank
    exited 0; pass ``check=False`` for fault-injection runs that expect
    specific nonzero codes and assert on the returned list."""
    port = free_port()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        RUSTPDE_X64="1",
        **(env_extra or {}),
    )
    argv_tail = [out_dir] + ([mode] if mode else [])
    procs = [
        subprocess.Popen(
            [
                sys.executable,
                os.path.join(_REPO, "tests", "mp_worker.py"),
                str(port),
                str(i),
                str(nproc),
                *argv_tail,
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
            cwd=_REPO,
        )
        for i in range(nproc)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        return None
    if check:
        for rc, out, err in outs:
            assert rc == 0, f"worker failed (rc={rc}):\n{err[-3000:]}"
    return outs
