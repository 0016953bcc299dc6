"""Per-model solo-vs-ensemble parity deltas (the cross-model drift probe).

One tiny campaign per registered model kind: K=2 members stepped as a
vmapped ensemble vs the same trajectories stepped solo, with the maximum
relative state-leaf deviation reported per kind.  tests/test_workloads.py
holds every kind under 1e-9, so a vmap/scan/refactor regression in ANY
model's batched path fails the PR that made it — not months later in a
campaign.
"""

from __future__ import annotations

import numpy as np

#: tiny shapes: parity is about code paths, not physics
_DEFAULTS = dict(nx=17, ny=17, ra=1e4, pr=1.0, aspect=1.0, bc="rbc")


def _build(kind: str, dt: float):
    from .registry import build_model

    return build_model(
        kind,
        _DEFAULTS["nx"],
        _DEFAULTS["ny"],
        _DEFAULTS["ra"],
        _DEFAULTS["pr"],
        dt,
        _DEFAULTS["aspect"],
        _DEFAULTS["bc"],
        False,
    )


def _seed(model, kind: str, seed: int) -> None:
    if kind == "adjoint":
        model.set_temperature(0.3 + 0.1 * seed, 1.0, 1.0)
        model.set_velocity(0.3 + 0.1 * seed, 1.0, 1.0)
    else:
        model.init_random(1e-2, seed=seed)


def solo_ensemble_parity(kinds=("dns", "lnse", "adjoint"), steps: int = 8) -> dict:
    """``{kind: {"max_rel_diff", "steps", "k"}}`` — max relative deviation
    of every state leaf between a K=2 vmapped ensemble and the member-wise
    solo runs after ``steps`` steps (identical ICs, identical dt)."""
    from ..models.ensemble import NavierEnsemble

    out = {}
    for kind in kinds:
        dt = 5e-3 if kind == "adjoint" else 1e-2
        model = _build(kind, dt)
        members = []
        for seed in (0, 1):
            _seed(model, kind, seed)
            members.append(model.state)
        ens = NavierEnsemble(model, members)
        ens.update_n(steps)
        worst = 0.0
        for i, seed in enumerate((0, 1)):
            # fresh model per member: seeding only rewrites the IC fields,
            # and a reused model would leak the previous run's pres/pseu
            solo = _build(kind, dt)
            _seed(solo, kind, seed)
            solo.update_n(steps)
            for got, want in zip(ens.member_state(i), solo.state):
                got = np.asarray(got)
                want = np.asarray(want)
                scale = float(np.max(np.abs(want)))
                if scale == 0.0 or not np.isfinite(scale):
                    continue
                worst = max(worst, float(np.max(np.abs(got - want))) / scale)
        out[kind] = {"max_rel_diff": worst, "steps": int(steps), "k": 2}
    return out
