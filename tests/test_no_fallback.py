"""No fallback that hides the device (PR 21): the places that used to carry
on quietly when the platform, the profiler or the chip was not what they
assumed now say so."""

import os
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

from rustpde_mpi_tpu import config
from rustpde_mpi_tpu.ops import pallas_common
from rustpde_mpi_tpu.parallel import mesh as pmesh
from rustpde_mpi_tpu.parallel import multihost
from rustpde_mpi_tpu.utils import profiling


def test_unknown_platform_is_not_tpu_like(monkeypatch):
    monkeypatch.delenv("RUSTPDE_FORCE_TPU_PATH", raising=False)
    for platform, want in (("tpu", True), ("cpu", False), ("gpu", False)):
        monkeypatch.setattr(config, "default_platform", lambda p=platform: p)
        assert config.is_tpu_like() is want
    monkeypatch.setattr(config, "default_platform", lambda: "mystery")
    with pytest.raises(config.UnknownPlatformError, match="mystery"):
        config.is_tpu_like()
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")  # the forced test path
    assert config.is_tpu_like() is True


class _Dev:
    def __init__(self, platform):
        self.platform = platform


def test_pallas_interprets_only_on_the_cpu(monkeypatch):
    assert pallas_common.resolve_interpret(None) is True  # this suite: cpu
    assert pallas_common.resolve_interpret(True) is True
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu")])
    assert pallas_common.resolve_interpret(None) is False
    with pytest.raises(pallas_common.PallasCompileRefused, match="interpret=True"):
        pallas_common.resolve_interpret(True)
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("gpu")])
    with pytest.raises(pallas_common.PallasCompileRefused, match="'gpu'"):
        pallas_common.resolve_interpret(None)


def test_vmem_limit_is_sized_from_the_blocks_and_capped(monkeypatch):
    from types import SimpleNamespace

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(
        pltpu, "get_tpu_info", lambda: SimpleNamespace(vmem_capacity_bytes=128 << 20)
    )
    specs = [pl.BlockSpec((256, 1024), lambda i, j: (i, 0))] * 2
    params = pallas_common.compiler_params(specs, 256 * 1024, np.float32)
    # 1.5 * (2 blocks double-buffered + one scratch) * 4 bytes + 4 MiB
    assert params.vmem_limit_bytes == int(1.5 * 5 * 256 * 1024 * 4) + (4 << 20)
    huge = [pl.BlockSpec((8192, 8192), lambda i, j: (0, 0))]
    capped = pallas_common.compiler_params(huge, 0, np.float32)
    assert capped.vmem_limit_bytes == int(0.85 * (128 << 20))


def test_a_refused_kernel_is_one_typed_error():
    def boom(x):
        raise ValueError("Mosaic failed: scoped vmem exceeded")

    example = jax.ShapeDtypeStruct((8, 128), np.float32)
    with pytest.raises(pallas_common.PallasCompileRefused) as err:
        pallas_common.require_native_compile("SOME_KNOB=pallas", "k", boom, example)
    assert "SOME_KNOB=pallas" in str(err.value) and "scoped vmem" in str(err.value)


def test_a_refused_ring_transpose_is_typed_at_build(monkeypatch):
    from rustpde_mpi_tpu.parallel import decomp

    monkeypatch.setattr(decomp, "_pallas_ring_available", lambda: True)
    dec = decomp.Decomp2d((16, 16), mesh=pmesh.make_mesh(jax.devices()[:4]))
    arr = np.ones((16, 16), np.float32)
    with pytest.raises(pallas_common.PallasCompileRefused, match="RUSTPDE_TRANSPOSE=ring"):
        dec.transpose_x_to_y(arr, method="ring")
    np.testing.assert_array_equal(dec.transpose_x_to_y(arr, method="alltoall"), arr)


def test_trace_raises_when_the_profiler_will_not_start(monkeypatch, tmp_path):
    def refuse(logdir):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler busy"):
        with profiling.trace(str(tmp_path)):
            pass


def test_no_cluster_means_no_distributed_probe(monkeypatch):
    for var in ("JAX_COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS",
                "TPU_WORKER_HOSTNAMES", "SLURM_STEP_NUM_TASKS", "OMPI_COMM_WORLD_SIZE"):
        monkeypatch.delenv(var, raising=False)

    def must_not_run(**_):
        raise AssertionError("auto-detect probe ran on a plain single host")

    monkeypatch.setattr(jax.distributed, "initialize", must_not_run)
    assert multihost.initialize_distributed() is False


def test_nondivisible_pencil_is_replicated_over_the_mesh_and_says_so():
    mesh = pmesh.make_mesh(jax.devices()[:4])
    arr = np.ones((129, 131), np.float32)  # 16.9k elements, 131 % 4 != 0
    with pmesh.use_mesh(mesh):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            placed = pmesh.device_put(arr, pmesh.SPEC)
            even = pmesh.device_put(np.ones((129, 132), np.float32), pmesh.SPEC)
    assert [w.category for w in caught] == [pmesh.ReplicatedPencilWarning]
    assert "REPLICATED" in str(caught[0].message)
    # committed on every device of the mesh — never parked on device 0
    assert placed.sharding.device_set == set(mesh.devices.flat)
    assert placed.sharding.is_fully_replicated and placed.committed
    assert not even.sharding.is_fully_replicated
    assert {s.data.shape for s in even.addressable_shards} == {(129, 33)}


def test_the_benchmark_has_no_cpu_mode():
    """The one benchmark there is: a cell that finds no TPU exits 3 before
    any model is built and prints no result line, so a CPU reading can never
    stand under a device metric's name."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "rbc513_f32.solo",
         "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, cwd=repo, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 3, proc.stderr[-1000:]
    assert proc.stdout.strip() == ""
    assert "There is no CPU mode" in proc.stderr
