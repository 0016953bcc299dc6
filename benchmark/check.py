"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference (``reference.py``).  Each number compared comes
back as ``name -> (value, limit)``; the limits sit in the cell's traffic file
under ``check`` and were set from chip readings (PERF.md, section 2)."""

from __future__ import annotations

import numpy as np

from .reference import Reference

FIELDS = ("temp", "velx", "vely")


def reference_for(cfg: dict) -> Reference:
    g, ph = cfg["grid"], cfg["physics"]
    return Reference(g["nx"], g["ny"], ph["ra"], ph["pr"], ph["dt"], ph["aspect"])


def field_gaps(program: dict, reference: dict) -> dict:
    """||program - reference|| / ||reference|| of each physical field."""
    out = {}
    for k in FIELDS:
        a, b = np.asarray(program[k], np.float64), np.asarray(reference[k], np.float64)
        out[k] = float(np.linalg.norm(a - b) / np.linalg.norm(b)) if np.isfinite(a).all() else float("inf")
    return out


def reference_fields(ref: Reference, initial: dict, steps: int, mode: str = "f32") -> dict:
    """The reference's fields ``steps`` steps after the physical values
    ``initial`` (temp, velx, vely)."""
    out = ref.run(ref.initial_state(initial), steps, mode)
    return {k: ref.backward(k, out[i]) for i, k in enumerate(FIELDS)}


def compare_fields(program: dict, reference: dict, limits: dict) -> dict:
    gaps = field_gaps(program, reference)
    return {f"{k}_rel": (gaps[k], float(limits[f"{k}_rel"])) for k in FIELDS}
