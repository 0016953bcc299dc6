"""Perf-trend gating (scripts/bench_trend.py): BENCH history parsing, the
noise-band regression verdict, the ack workflow, and the checked-in repo
history producing a clean TREND.json."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "bench_trend.py")


def _run(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, SCRIPT, *args],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=cwd,
    )


def _round_file(path, configs, rc=0, flagship=None):
    parsed = {"configs": configs}
    if flagship is not None:
        parsed.update(
            {"metric": "timesteps/sec", "value": flagship, "unit": "steps/s"}
        )
    with open(path, "w") as fh:
        json.dump({"n": 1, "cmd": "bench", "rc": rc, "tail": "", "parsed": parsed}, fh)


def _fake_history(tmp_path, r03_rate):
    """Three rounds of one config; r03 carries the rate under test."""
    for i, rate in enumerate([100.0, 104.0, r03_rate], start=1):
        _round_file(
            str(tmp_path / f"BENCH_r{i:02d}.json"),
            {"rbc129": {"steps_per_sec": rate, "finite": True}},
        )


def test_trend_clean_history_no_regression(tmp_path):
    _fake_history(tmp_path, r03_rate=98.0)  # within the 30% band
    out = str(tmp_path / "TREND.json")
    proc = _run(["--repo", str(tmp_path), "--out", out, "--json", "--gate"])
    assert proc.returncode == 0, proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["regressions"] == [] and payload["regressions_unacked"] == []
    cfg = payload["configs"]["rbc129"]
    assert cfg["rolling_best"] == 104.0 and cfg["latest"] == 98.0
    assert [p["label"] for p in cfg["points"]] == ["r01", "r02", "r03"]
    # the artifact landed
    assert json.load(open(out))["configs"]["rbc129"]["regressed"] is False


def test_trend_flags_synthetic_regression_and_ack_clears_it(tmp_path):
    _fake_history(tmp_path, r03_rate=40.0)  # 62% below the rolling best
    out = str(tmp_path / "TREND.json")
    proc = _run(["--repo", str(tmp_path), "--out", out, "--json", "--gate"])
    assert proc.returncode == 5, (proc.stdout, proc.stderr)
    payload = json.loads(proc.stdout)
    assert payload["regressions_unacked"] == ["rbc129"]
    assert payload["configs"]["rbc129"]["ratio"] < 0.7

    # an ack without a written reason is refused
    proc = _run(["--repo", str(tmp_path), "--out", out, "--ack", "rbc129"])
    assert proc.returncode == 2

    # acked with a reason: the gate passes, the ack is recorded in TREND.json
    proc = _run(
        ["--repo", str(tmp_path), "--out", out, "--json", "--gate",
         "--ack", "rbc129", "--reason", "shared-host slowdown, tracked upstream"]
    )
    assert proc.returncode == 0, proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["regressions"] == ["rbc129"]
    assert payload["regressions_unacked"] == []
    assert payload["acks"]["rbc129"]["reason"].startswith("shared-host slowdown")

    # the ack persists across runs (it lives inside TREND.json)...
    proc = _run(["--repo", str(tmp_path), "--out", out, "--json", "--gate"])
    assert proc.returncode == 0

    # ... but pins (config, label, VALUE): a re-captured point at the SAME
    # label with a different (worse) value re-fires — BENCH_FULL's label
    # is always "full", so a label-only pin would silence that config
    # forever after one ack
    _round_file(
        str(tmp_path / "BENCH_r03.json"),
        {"rbc129": {"steps_per_sec": 10.0, "finite": True}},
    )
    proc = _run(["--repo", str(tmp_path), "--out", out, "--json", "--gate"])
    assert proc.returncode == 5
    assert json.loads(proc.stdout)["regressions_unacked"] == ["rbc129"]

    # restore the acked capture, then a FURTHER round regressing re-fires
    _round_file(
        str(tmp_path / "BENCH_r03.json"),
        {"rbc129": {"steps_per_sec": 40.0, "finite": True}},
    )
    _round_file(
        str(tmp_path / "BENCH_r04.json"),
        {"rbc129": {"steps_per_sec": 20.0, "finite": True}},
    )
    proc = _run(["--repo", str(tmp_path), "--out", out, "--json", "--gate"])
    assert proc.returncode == 5
    assert json.loads(proc.stdout)["regressions_unacked"] == ["rbc129"]


def test_trend_skips_unparseable_rounds_and_stale_rows(tmp_path):
    _round_file(
        str(tmp_path / "BENCH_r01.json"),
        {
            "rbc129": {"steps_per_sec": 100.0},
            "old": {"steps_per_sec": 50.0, "stale": True},
        },
    )
    # an rc!=0 round with no recoverable JSON is skipped, not fatal
    with open(str(tmp_path / "BENCH_r02.json"), "w") as fh:
        json.dump({"n": 2, "rc": 1, "tail": "Traceback ...", "parsed": None}, fh)
    proc = _run(
        ["--repo", str(tmp_path), "--out", str(tmp_path / "TREND.json"),
         "--json"]
    )
    assert proc.returncode == 0, proc.stderr
    payload = json.loads(proc.stdout)
    assert "old" not in payload["configs"]  # stale rows excluded
    assert [p["label"] for p in payload["configs"]["rbc129"]["points"]] == ["r01"]


def test_trend_recovers_final_json_line_from_tail(tmp_path):
    tail = 'noise\n{"metric": "x", "value": 42.0, "unit": "steps/s"}\n'
    with open(str(tmp_path / "BENCH_r01.json"), "w") as fh:
        json.dump({"n": 1, "rc": 0, "tail": tail, "parsed": None}, fh)
    proc = _run(
        ["--repo", str(tmp_path), "--out", str(tmp_path / "TREND.json"),
         "--json"]
    )
    payload = json.loads(proc.stdout)
    assert payload["configs"]["flagship"]["latest"] == 42.0


def test_trend_real_repo_history_parses_clean(tmp_path):
    """Whatever bench history the checkout holds — none is committed since
    PR 21 (the driver's PERF_LEDGER.jsonl is the record; bench.py's
    BENCH_FULL.json is a run-time artifact) — parses clean into a
    TREND.json (written to a scratch path) with no un-acked regression."""
    out = str(tmp_path / "TREND.json")
    proc = _run(["--json", "--out", out])
    assert proc.returncode == 0, proc.stderr
    payload = json.loads(proc.stdout)
    assert isinstance(payload["configs"], dict)
    assert payload["regressions_unacked"] == []
    assert os.path.exists(out)
