"""chip_smoke.py contract, as far as a CPU can show it: no chip is a fast
non-zero exit that names the platform and prints no result, the parent
holds no backend, the leg functions run (at 17^2) and gate what they say
they gate, and the compile cache is placed from outside."""

import json
import os
import subprocess
import sys

import jax
import pytest

from rustpde_mpi_tpu import Navier2D, config

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
import chip_smoke  # noqa: E402

_CFG17 = {
    "nx": 17, "ny": 17, "ra": 1e4, "pr": 1.0, "dt": 0.01, "aspect": 1.0,
    "bc": "rbc", "amp": 0.01, "sample_every": 4,
}


@pytest.fixture(scope="module")
def meter():
    return chip_smoke.CompileMeter()


def test_no_chip_fails_fast_and_parent_stays_off_jax():
    code = (
        "import sys, chip_smoke\n"
        "rc = chip_smoke.main()\n"
        "print('PARENT', rc, 'jax' in sys.modules, 'rustpde_mpi_tpu' in sys.modules)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=_REPO, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    # the only stdout line is this test's own probe: no result was printed
    assert proc.stdout.split() == ["PARENT", "3", "False", "False"]
    assert "platform is 'cpu'" in proc.stderr and "not 'tpu'" in proc.stderr


@pytest.mark.parametrize("passed", [True, False])
def test_last_stdout_line_is_exactly_the_verdict(monkeypatch, tmp_path, capsys, passed):
    # the driver parses the last line and refuses any key beyond these
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    def child(precision, deadline):
        legs = {"parity": {"passed": passed}}
        if precision == "f32":
            legs["mesh"] = {"skipped": "1 device"}
        return {"precision": precision, "device": device, "versions": {},
                "compile_cache_dir": "x", "legs": legs,
                "rematerialization_on_stderr": False}

    monkeypatch.setattr(chip_smoke, "_run_child", child)
    monkeypatch.setattr(chip_smoke, "_OUT", str(tmp_path))
    assert chip_smoke.main() == (0 if passed else 1)
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": passed, "device": device}
    report = json.loads(lines[-2])["report"]
    assert report["mesh"] == {"skipped": "1 device"} and "f64" in report


def test_parity_leg_gates_on_the_reference(meter):
    # reference rows from the per-step entry point; the leg runs update_n
    ref = Navier2D(17, 17, 1e4, 1.0, 0.01, 1.0, "rbc", periodic=False)
    ref.init_random(_CFG17["amp"], seed=0)
    gold = []
    for k in (1, 2):
        for _ in range(_CFG17["sample_every"]):
            ref.update()
        gold.append({"time": 0.04 * k, "nu": ref.get_observables()[0]})
    out = chip_smoke.leg_parity(meter, "cpu", _CFG17, gold, rtol=1e-9)
    assert out["passed"], out
    assert out["compiled"] + out["cache_loads"] > 0  # the meter saw this leg's jits
    off = [dict(g, nu=g["nu"] * (1 + 1e-6)) for g in gold]
    assert not chip_smoke.leg_parity(meter, "cpu", _CFG17, off, rtol=1e-9)["passed"]
    # the residence gate every leg ANDs in
    assert chip_smoke._on_platform(ref.state, "cpu")
    assert not chip_smoke._on_platform(ref.state, "tpu")


def test_served_leg_drains_clean(meter, tmp_path):
    out = chip_smoke.leg_served(
        meter, "cpu", str(tmp_path / "serve"), nx=17, ny=17, dt=0.01,
        ras=(1e4,), per_ra=3, base_steps=8, step_stride=4, jitter_steps=2,
        solo_checks=1, nu_rtol=1e-6,
    )
    assert out["passed"], out
    assert out["queue"] == {"queued": 0, "running": 0, "done": 3, "failed": 0}
    assert out["campaign_devices"] == ["cpu:0"]
    assert out["unclean_journal_events"] == []


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    saved = (config._cache_armed, jax.config.jax_compilation_cache_dir)
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert config.compile_cache_dir() == os.path.join(_REPO, ".jax_cache")
        assert config.host_cache_dir() == os.path.join(_REPO, ".jax_cache", "host")
        assert config.enable_compilation_cache() == os.path.join(_REPO, ".jax_cache")
        assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = dict(os.environ)
        assert config.enable_compilation_cache() == str(tmp_path)
        assert config.host_cache_dir() == str(tmp_path / "host")
        assert dict(os.environ) == before  # arming leaves it as it found it
    finally:
        config._cache_armed = saved[0]
        jax.config.update("jax_compilation_cache_dir", saved[1])
