"""CI coverage for the execution paths the real TPU chip uses.

CI runs on CPU (tests/conftest.py), where the defaults are FFT transforms +
banded-scan solvers; on a TPU the model instead runs matmul transforms
+ DenseSolver ADI + FastDiag Poisson (no complex dtypes, no FFT).  These
tests force that path via RUSTPDE_FORCE_TPU_PATH and assert it produces the
same physics as the default path — so a TPU-only numerical bug cannot hide
behind CPU-only CI (VERDICT r1 weak #4).
"""

import numpy as np
import pytest

from rustpde_mpi_tpu import Navier2D, Space2, cheb_dirichlet, cheb_neumann
from rustpde_mpi_tpu.solver import HholtzAdi, Poisson


@pytest.fixture()
def tpu_path(monkeypatch):
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    yield
    monkeypatch.delenv("RUSTPDE_FORCE_TPU_PATH", raising=False)


def test_forced_path_selects_tpu_defaults(tpu_path):
    from rustpde_mpi_tpu import config
    from rustpde_mpi_tpu.solver import FastDiag, default_method

    assert config.is_tpu_like()
    assert default_method() == "dense"
    space = Space2(cheb_dirichlet(9), cheb_dirichlet(9))
    assert space.method == "matmul"
    solver = Poisson(space, (1.0, 1.0))
    assert isinstance(solver._solver, FastDiag)


def test_matmul_transforms_match_fft(tpu_path):
    space_tpu = Space2(cheb_dirichlet(17), cheb_neumann(17))
    assert space_tpu.method == "matmul"
    space_fft = Space2(cheb_dirichlet(17), cheb_neumann(17), method="fft")
    rng = np.random.default_rng(3)
    v = rng.standard_normal((17, 17))
    a = space_tpu.forward(v)
    b = np.asarray(space_fft.forward(v))
    # the TPU matmul path stores spectral axes parity-separated (ops/folded
    # sep layout); compare in the natural order via the IO-boundary helper
    np.testing.assert_allclose(space_tpu.spectral_to_natural(a), b, atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(space_tpu.backward(a)), np.asarray(space_fft.backward(b)), atol=1e-12
    )


def test_model_tpu_path_matches_default_path(tpu_path, monkeypatch):
    """Full confined model: 30 steps on the forced TPU path vs the CPU
    default path — observables and fields must agree to spectral accuracy."""

    def build():
        model = Navier2D(25, 25, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
        model.set_velocity(0.1, 1.0, 1.0)
        model.set_temperature(0.1, 1.0, 1.0)
        return model

    tpu_model = build()
    assert tpu_model.field_space.method == "matmul"
    monkeypatch.delenv("RUSTPDE_FORCE_TPU_PATH")
    cpu_model = build()
    assert cpu_model.field_space.method == "fft"

    tpu_model.update_n(30)
    cpu_model.update_n(30)
    spaces = ("temp_space", "velx_space", "vely_space", "pres_space", "pseu_space")
    for sp_name, a, b in zip(spaces, tpu_model.state, cpu_model.state):
        space = getattr(tpu_model, sp_name)
        np.testing.assert_allclose(
            space.spectral_to_natural(a), np.asarray(b), atol=1e-9, err_msg=sp_name
        )
    for va, vb in zip(tpu_model.get_observables(), cpu_model.get_observables()):
        assert va == pytest.approx(vb, rel=1e-8, abs=1e-10)


def test_dense_adi_matches_banded():
    space = Space2(cheb_dirichlet(33), cheb_dirichlet(33))
    rng = np.random.default_rng(7)
    rhs = rng.standard_normal((33, 33))
    x_banded = np.asarray(HholtzAdi(space, (0.1, 0.1), method="banded").solve(rhs))
    x_dense = np.asarray(HholtzAdi(space, (0.1, 0.1), method="dense").solve(rhs))
    np.testing.assert_allclose(x_dense, x_banded, atol=1e-11)


def test_periodic_model_tpu_split_path_matches_complex(tpu_path, monkeypatch):
    """Horizontally-periodic model on the forced TPU path (split Re/Im
    Fourier + matmul transforms) vs the CPU complex-FFT path."""

    def build():
        model = Navier2D(16, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=True)
        model.set_velocity(0.1, 1.0, 1.0)
        model.set_temperature(0.1, 1.0, 1.0)
        return model

    tpu_model = build()
    assert tpu_model.temp_space.base_x.kind.is_split
    monkeypatch.delenv("RUSTPDE_FORCE_TPU_PATH")
    cpu_model = build()
    assert not cpu_model.temp_space.base_x.kind.is_split

    tpu_model.update_n(20)
    cpu_model.update_n(20)
    np.testing.assert_allclose(
        tpu_model.get_field("temp"), cpu_model.get_field("temp"), atol=1e-9
    )
    for va, vb in zip(tpu_model.get_observables(), cpu_model.get_observables()):
        assert va == pytest.approx(vb, rel=1e-8, abs=1e-10)


def test_swift_hohenberg_tpu_matmul_path(tpu_path, monkeypatch):
    """SH2D biperiodic space auto-selects matmul under the forced TPU path
    and reproduces the FFT-path trajectory."""
    from rustpde_mpi_tpu import SwiftHohenberg2D

    tpu_model = SwiftHohenberg2D(16, 16, r=0.3, dt=0.02, length=6.0)
    assert tpu_model.space.method == "matmul"
    monkeypatch.delenv("RUSTPDE_FORCE_TPU_PATH")
    cpu_model = SwiftHohenberg2D(16, 16, r=0.3, dt=0.02, length=6.0)
    assert cpu_model.space.method == "fft"
    tpu_model.update_n(50)
    cpu_model.update_n(50)
    np.testing.assert_allclose(
        tpu_model.theta_physical(), cpu_model.theta_physical(), atol=1e-10
    )


def test_penalization_tpu_path_matches_default(tpu_path, monkeypatch):
    """Brinkman penalization on the forced TPU path == default path."""
    from rustpde_mpi_tpu.models.solid_masks import solid_cylinder_inner

    def build():
        model = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
        x, y = model.x
        mask, value = solid_cylinder_inner(x, y, 0.0, 0.0, 0.3)
        model.set_solid(mask, value)
        model.set_velocity(0.1, 1.0, 1.0)
        return model

    tpu_model = build()
    monkeypatch.delenv("RUSTPDE_FORCE_TPU_PATH")
    cpu_model = build()
    tpu_model.update_n(20)
    cpu_model.update_n(20)
    np.testing.assert_allclose(
        tpu_model.get_field("velx"), cpu_model.get_field("velx"), atol=1e-10
    )


@pytest.mark.slow
def test_f64_hybrid_tracks_full_f64():
    """RUSTPDE_F64_HYBRID=1 (f32 convection transforms feeding f64 solves,
    SURVEY S7 hybrid): state stays f64 and a 50-step trajectory tracks the
    all-f64 one at f32-roundoff level.  Subprocesses: the sep-operator cache
    is keyed per-process by the build-time env."""
    import json
    import os
    import subprocess
    import sys

    code = (
        "import os, jax\n"
        "import json\n"
        "from rustpde_mpi_tpu import Navier2D\n"
        "m = Navier2D.new_confined(33, 33, 1e5, 1.0, 5e-3, 1.0, 'rbc')\n"
        "assert all(m.temp_space.sep)\n"
        "assert str(m.state.temp.dtype) == 'float64'\n"
        "m.set_velocity(0.1, 2.0, 2.0); m.set_temperature(0.1, 2.0, 2.0)\n"
        "m.update_n(50)\n"
        "assert str(m.state.temp.dtype) == 'float64'\n"
        "print(json.dumps(list(m.get_observables())))\n"
    )
    obs = {}
    for hybrid in ("0", "1"):
        env = dict(
            os.environ,
            RUSTPDE_X64="1",
            RUSTPDE_FORCE_TPU_PATH="1",
            RUSTPDE_F64_HYBRID=hybrid,
            JAX_PLATFORMS="cpu",
        )
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, env=env, timeout=600,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        obs[hybrid] = json.loads(out.stdout.strip().splitlines()[-1])
    nu64, nuh = obs["0"][0], obs["1"][0]
    assert abs(nuh - nu64) / abs(nu64) < 1e-4, (obs["0"], obs["1"])
    # Re and |div| also agree; the hybrid must not degrade divergence control
    assert abs(obs["1"][2] - obs["0"][2]) / abs(obs["0"][2]) < 1e-4
    assert obs["1"][3] < 2 * max(obs["0"][3], 1e-12)


@pytest.mark.slow
def test_f64_hybrid_sharded_matches_serial():
    """The f64 hybrid under the 8-device pencil mesh == serial hybrid: the
    f32-cast convection operators must partition cleanly under GSPMD (real
    multichip would run exactly this combination)."""
    import os
    import re
    import subprocess
    import sys

    code = (
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "from rustpde_mpi_tpu import Navier2D\n"
        "from rustpde_mpi_tpu.parallel.mesh import AXIS\n"
        "def build(mesh):\n"
        "    m = Navier2D(17, 16, 1e4, 1.0, 1e-2, 1.0, 'rbc', periodic=False, mesh=mesh)\n"
        "    m.set_velocity(0.1, 1.0, 1.0)\n"
        "    m.set_temperature(0.1, 1.0, 1.0)\n"
        "    return m\n"
        "serial = build(None)\n"
        "mesh = Mesh(np.array(jax.devices()[:8]), (AXIS,))\n"
        "sharded = build(mesh)\n"
        "serial.update_n(6)\n"
        "sharded.update_n(6)\n"
        "# f32 GEMM segments reassociate differently under partitioning; the\n"
        "# agreement bar is f32 roundoff (observed ~2e-11), not bitwise\n"
        "np.testing.assert_allclose(np.asarray(sharded.state.temp),\n"
        "                           np.asarray(serial.state.temp), atol=1e-9)\n"
        "print('OK', serial.eval_nu())\n"
    )
    env = dict(
        os.environ,
        RUSTPDE_X64="1",
        RUSTPDE_FORCE_TPU_PATH="1",
        RUSTPDE_F64_HYBRID="1",
        JAX_PLATFORMS="cpu",
    )
    env["XLA_FLAGS"] = (
        re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            env.get("XLA_FLAGS", ""),
        ).strip()
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
