"""Parse the BENCH_r*.json / BENCH_FULL.json history into per-config metric
trajectories and gate on regressions — the piece that turns the pile of
bench round files into a machine-checked trend instead of archaeology.

Usage::

    python scripts/bench_trend.py                 # write TREND.json, report
    python scripts/bench_trend.py --json          # machine payload on stdout
    python scripts/bench_trend.py --gate          # rc=5 on un-acked regression
    python scripts/bench_trend.py --ack rbc1025 --reason "shared host, \\
        tracked in ROADMAP"                       # accept the latest point

How it reads the history:

* every ``BENCH_r*.json`` round file carries the driver's ``parsed`` final
  JSON line (flagship ``value`` + optional per-config ``configs`` rows); a
  round whose ``parsed`` is null is re-parsed from the recorded ``tail``
  and skipped when unrecoverable (rc!=0 rounds),
* ``BENCH_FULL.json`` (``results`` per config) is the newest point,
* per config the primary metric is ``member_steps_per_sec`` (serve rows)
  else ``steps_per_sec`` else the flagship ``value``; rows marked
  ``stale`` (budget-starved carry-overs) are excluded.

The gate: a config REGRESSES when its newest point falls below
``(1 - band) * rolling_best`` of all earlier points (band from
``RUSTPDE_TREND_BAND``, default 0.3 — wider than the round-to-round
spread of the recorded history).  Regressions must be ACKED with a
written reason (``--ack``) to pass the gate; acks pin (config, round,
MEASURED VALUE) — a later round, or a re-captured point at a different
value (BENCH_FULL's label never changes), re-fires the gate.
``scripts/record_tests.py`` runs this with ``--gate`` and fails the
record run (rc=5) on an un-acked regression, the same way LINT.json
already gates.
"""

import argparse
import datetime
import glob
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: preference order for a config row's primary metric
_PRIMARY = ("member_steps_per_sec", "steps_per_sec")


def _primary_metric(row: dict):
    for name in _PRIMARY:
        v = row.get(name)
        if isinstance(v, (int, float)) and v > 0:
            return name, float(v)
    return None, None


def _last_json_line(text: str):
    """Best-effort recovery of the driver's final JSON line from a recorded
    ``tail`` (the round file truncates output from the FRONT, so the final
    line is usually intact)."""
    for line in reversed((text or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return None


def _round_configs(parsed: dict) -> dict:
    """``{config: {"metric", "value"}}`` from one round's parsed payload."""
    out = {}
    if isinstance(parsed.get("value"), (int, float)):
        out["flagship"] = {
            "metric": parsed.get("unit", "steps/s"),
            "value": float(parsed["value"]),
        }
    for name, row in (parsed.get("configs") or {}).items():
        if not isinstance(row, dict) or row.get("stale"):
            continue
        metric, value = _primary_metric(row)
        if metric is not None:
            out[name] = {"metric": metric, "value": value}
    return out


def collect_history(repo: str = _REPO) -> list:
    """Ordered ``[(label, {config: {"metric","value"}}), ...]``: the
    BENCH_rNN rounds by number, then BENCH_FULL as the newest point."""
    points = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        label = os.path.splitext(os.path.basename(path))[0].replace("BENCH_", "")
        try:
            with open(path, encoding="utf-8") as fh:
                data = json.load(fh)
        except (OSError, ValueError):
            continue
        parsed = data.get("parsed")
        if not isinstance(parsed, dict):
            parsed = _last_json_line(data.get("tail", ""))
        if not isinstance(parsed, dict):
            continue  # unrecoverable round (rc!=0, torn tail)
        configs = _round_configs(parsed)
        if configs:
            points.append((label, configs))
    full_path = os.path.join(repo, "BENCH_FULL.json")
    try:
        with open(full_path, encoding="utf-8") as fh:
            results = json.load(fh).get("results", {})
    except (OSError, ValueError):
        results = {}
    configs = {}
    for name, row in results.items():
        if not isinstance(row, dict) or row.get("stale"):
            continue
        metric, value = _primary_metric(row)
        if metric is not None:
            configs[name] = {"metric": metric, "value": value}
    if configs:
        points.append(("full", configs))
    return points


def compute_trend(points: list, band: float, acks: dict | None = None) -> dict:
    """The TREND.json payload: per-config trajectory, rolling best, the
    regression verdict against the noise band, and ack status."""
    acks = acks or {}
    by_config: dict[str, list] = {}
    for label, configs in points:
        for name, entry in configs.items():
            by_config.setdefault(name, []).append(
                {"label": label, "value": entry["value"], "metric": entry["metric"]}
            )
    trend = {}
    regressions, unacked = [], []
    for name, series in sorted(by_config.items()):
        latest = series[-1]
        earlier = [p["value"] for p in series[:-1]]
        best = max(earlier) if earlier else latest["value"]
        ratio = latest["value"] / best if best > 0 else 1.0
        regressed = len(series) >= 2 and latest["value"] < (1.0 - band) * best
        ack = acks.get(name)
        # an ack pins (config, round, MEASURED VALUE): BENCH_FULL's label
        # is always "full", so without the value fingerprint one ack there
        # would silence every future regression of that config forever — a
        # re-captured point with a different value must re-fire the gate
        acked = bool(
            regressed
            and ack
            and ack.get("label") == latest["label"]
            and ack.get("value") is not None
            and abs(latest["value"] - ack["value"])
            <= 1e-9 * max(abs(latest["value"]), abs(ack["value"]), 1e-30)
        )
        trend[name] = {
            "points": series,
            "metric": latest["metric"],
            "rolling_best": best,
            "latest": latest["value"],
            "latest_label": latest["label"],
            "ratio": round(ratio, 4),
            "regressed": regressed,
            "acked": acked,
            **({"ack": ack} if acked else {}),
        }
        if regressed:
            regressions.append(name)
            if not acked:
                unacked.append(name)
    return {
        "band": band,
        "configs": trend,
        "regressions": regressions,
        "regressions_unacked": unacked,
        "acks": acks,
        "date": datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y-%m-%d %H:%M UTC"
        ),
    }


def _load_acks(out_path: str) -> dict:
    """Acks persist inside TREND.json itself — one artifact, no side file."""
    try:
        with open(out_path, encoding="utf-8") as fh:
            return json.load(fh).get("acks", {}) or {}
    except (OSError, ValueError):
        return {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--repo", default=_REPO, help="repo root to scan")
    ap.add_argument("--out", default=None, help="output path (default <repo>/TREND.json)")
    ap.add_argument("--band", type=float, default=None,
                    help="noise band (default RUSTPDE_TREND_BAND or 0.3)")
    ap.add_argument("--json", action="store_true", help="print the payload")
    ap.add_argument("--gate", action="store_true",
                    help="exit 5 when an un-acked regression is present")
    ap.add_argument("--ack", default=None, metavar="CONFIG",
                    help="ack CONFIG's latest point as accepted")
    ap.add_argument("--reason", default=None,
                    help="written reason for --ack (required with it)")
    args = ap.parse_args(argv)

    out_path = args.out or os.path.join(args.repo, "TREND.json")
    band = args.band
    if band is None:
        band = float(os.environ.get("RUSTPDE_TREND_BAND", "0.3") or 0.3)

    acks = _load_acks(out_path)
    points = collect_history(args.repo)
    payload = compute_trend(points, band, acks)

    if args.ack:
        if not args.reason:
            print("--ack requires --reason <written why>", file=sys.stderr)
            return 2
        cfg = payload["configs"].get(args.ack)
        if cfg is None:
            print(f"unknown config {args.ack!r}; known: "
                  f"{sorted(payload['configs'])}", file=sys.stderr)
            return 2
        acks[args.ack] = {
            "label": cfg["latest_label"],
            "value": cfg["latest"],
            "reason": args.reason,
            "date": payload["date"],
        }
        payload = compute_trend(points, band, acks)

    tmp = f"{out_path}.{os.getpid()}.tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=1)
        fh.write("\n")
    os.replace(tmp, out_path)

    if args.json:
        print(json.dumps(payload))
    else:
        for name, cfg in payload["configs"].items():
            flag = ""
            if cfg["regressed"]:
                flag = " ACKED" if cfg["acked"] else " REGRESSED"
            print(
                f"{name:24s} {cfg['latest']:>12.3f} {cfg['metric']:<22s}"
                f" best {cfg['rolling_best']:>12.3f} ratio {cfg['ratio']:.3f}"
                f"{flag}"
            )
        if payload["regressions_unacked"]:
            print(f"UN-ACKED regressions: {payload['regressions_unacked']}")
    if args.gate and payload["regressions_unacked"]:
        return 5
    return 0


if __name__ == "__main__":
    sys.exit(main())
