"""On-device physics-statistics engine: in-scan turbulence statistics,
spectral-health sentinels, and budget-closure drift detection.

The reference port's :class:`~rustpde_mpi_tpu.models.statistics.Statistics`
is an eager host-side numpy accumulator — single-model only, synchronous in
the IO callback, invisible to ensembles/sharded meshes/serve, and its
running averages silently restart from zero after every crash.  This module
is the production replacement: a :class:`StatsState` pytree of running sums
carried *through the scanned step chunk* alongside the model state —

* updated ON DEVICE at a configured ``stride`` (a handful of extra
  syntheses per sample, ~1/stride amortized overhead),
* vmapped per ensemble member and pencil-sharded under a mesh (the
  accumulation is a pure function of one member state, so the batch axis
  and GSPMD propagation come for free),
* registered in the models' ``snapshot_state_items`` so long-horizon
  averages ride the two-phase sharded checkpoints (and the gathered
  single-file format) and survive kill/resume BIT-exactly,
* read, never fed back: the state trajectory is bit-identical stats-on vs
  stats-off (CI-asserted — the same contract the PR-3 sentinels and PR-8
  telemetry ship under).

What is accumulated (per member):

* the legacy-parity set — running spectral-space sums of T (ortho, no BC
  lift), ux, uy, and the pointwise Nusselt field (with lift, dealiased) —
  the engine matches the eager legacy accumulator to fp tolerance
  (tests/test_stats.py), and :func:`export_stats` writes the reference
  ``statistics.h5`` layout plus engine extras,
* x-averaged profiles: mean T, second moments of T/ux/uy (RMS profiles),
  convective flux ``uy*T``,
* per-axis energy-spectrum accumulators for T/ux/uy (the under-resolution
  detector's raw material),
* budget scalars: plate-flux Nu, volume Nu, the exact-relation flux Nu
  ``1 + <uy*T>*2*sy/ka``, kinetic energy (first/last sample + running sum),
  buoyancy production ``<uy*T>`` and viscous dissipation.

On top of the accumulators, :data:`HEALTH_NAMES` scalars are compiled as a
separate jitted readout (streamed through the existing observable-future
plumbing, exported as telemetry gauges, journal-typed by the runner):
spectral-tail energy fraction per field/axis, thermal/viscous boundary-layer
point counts, and budget-closure residuals (kinetic-energy balance;
Nu-consistency between the plate-flux, volume and flux estimators) — the
physics-invariant drift detectors the f64 precision ladder and the Pallas
A/B flips gate on.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from .. import config


class StatsState(NamedTuple):
    """Running-sum pytree carried through the scan (one member's leaves;
    ensembles stack a leading K axis on every leaf).  Scalars are shape
    ``(1,)`` so the sharded checkpoint writer's slab addressing covers
    them like any other dataset."""

    # legacy-parity spectral running sums (ortho field-space layout)
    t_sum: object      # T composite->ortho, NO BC lift (statistics.rs t_avg)
    ux_sum: object
    uy_sum: object
    nusselt_sum: object  # pointwise Nusselt field (with lift, dealiased)
    # per-axis energy-spectrum sums, rows (T, ux, uy)
    spec_x: object     # (3, x-rows)  |coeff|^2 summed over the y axis
    spec_y: object     # (3, ny_spec) |coeff|^2 summed over the other axes
    # x-averaged physical profiles (ny,)
    t_prof_sum: object    # mean T (WITH lift: the physical temperature)
    t2_prof_sum: object   # second moments -> RMS profiles
    ux2_prof_sum: object
    uy2_prof_sum: object
    flux_prof_sum: object  # uy * T convective-flux profile
    # budget scalars, shape (1,)
    nu_plate_sum: object   # plate-flux Nu per sample
    nuvol_sum: object      # volume Nu per sample (the eval_nuvol integrand)
    flux_vol_sum: object   # <uy*T> * 2*sy/ka  (Nu_flux = 1 + avg of this)
    ke_sum: object         # volume-avg kinetic energy
    buoy_sum: object       # buoyancy production <uy*T>
    diss_sum: object       # viscous dissipation nu*<|grad u|^2>
    ke_first: object       # KE at the first sample (dKE/dt window anchor)
    ke_last: object        # KE at the newest sample
    # window span in SIM time, accumulated per sample at that sample's OWN
    # stride*dt (the accumulator is rebuilt per governor dt rung, so a
    # ladder move mid-window keeps the dKE/dt span exact — reconstructing
    # it from the current dt would mis-scale old-rung samples)
    span_sum: object       # sum of stride*dt over the samples
    span_first: object     # span_sum at the first sample (elapsed anchor)
    samples: object        # sample count (real dtype; exact far past any run)


#: the compiled health readout's scalar vocabulary, in order
#: (:meth:`StatsEngine.health_fn` returns exactly these)
HEALTH_NAMES = (
    "tail_t_x",
    "tail_t_y",
    "tail_ux_x",
    "tail_ux_y",
    "tail_uy_x",
    "tail_uy_y",
    "bl_thermal_pts",
    "bl_visc_pts",
    "ke_residual",
    "nu_residual",
    "nu_plate_avg",
    "nu_flux_avg",
    "samples",
)


# typed replacements for the legacy statistics flow's silent failure paths:
# event name -> (telemetry counter, help)
_EVENT_COUNTERS = {
    "stats_mismatch": (
        "stats_mismatch_total",
        "legacy statistics time-mismatch rejections (averages NOT updated)",
    ),
    "stats_write_failed": (
        "stats_write_failed_total",
        "statistics.h5 write failures (averages survive in memory only)",
    ),
}


def report_stats_event(model, event: dict) -> None:
    """Surface a statistics-flow failure as a telemetry counter + (when the
    model carries an attached ``journal_writer`` — the resilient runner
    wires its own during a session) a typed journal event, so a production
    run can't lose its averages invisibly behind a swallowed ``print``."""
    from ..telemetry import metrics as _tm

    counter = _EVENT_COUNTERS.get(event.get("event"))
    if counter is not None:
        _tm.counter(*counter).inc()
    writer = getattr(model, "journal_writer", None)
    if writer is not None:
        writer.append(dict(event))


class StatsEngine:
    """Builder of the compiled stats machinery for ONE model (dns only —
    the accumulators read temp/velx/vely through the DNS spaces).

    The engine owns the *math*: :meth:`sample_fn` (one state's contribution
    as a StatsState), :meth:`accum_fn` (fold a sample into the running
    sums), :meth:`health_fn` (the :data:`HEALTH_NAMES` readout) and
    :meth:`init_state` (zeros).  The *threading* — hoisting these into the
    scanned chunk with the stride cond, vmapping them per member, carrying
    the state through checkpoints — lives in
    :class:`~rustpde_mpi_tpu.models.campaign.CampaignModelBase` and
    :class:`~rustpde_mpi_tpu.models.ensemble.NavierEnsemble`, exactly where
    the step's own machinery lives."""

    def __init__(self, model, cfg=None):
        if getattr(model, "MODEL_KIND", "") != "dns":
            raise TypeError(
                "the stats engine reads DNS fields (temp/velx/vely); model "
                f"kind {getattr(model, 'MODEL_KIND', '?')!r} is not supported"
            )
        self.model = model
        self.cfg = cfg
        stride = getattr(cfg, "stride", None)
        if stride is None:
            stride = int(config.env_get("RUSTPDE_STATS_STRIDE", "16"))
        self.stride = max(1, int(stride))
        tail_warn = getattr(cfg, "tail_warn", None)
        if tail_warn is None:
            tail_warn = float(config.env_get("RUSTPDE_STATS_TAIL_WARN", "1e-3"))
        self.tail_warn = float(tail_warn)
        budget_warn = getattr(cfg, "budget_warn", None)
        if budget_warn is None:
            budget_warn = float(
                config.env_get("RUSTPDE_STATS_BUDGET_WARN", "0.5")
            )
        self.budget_warn = float(budget_warn)
        self._example = None  # ShapeDtypeStruct pytree, computed lazily

    # -- compiled pieces -----------------------------------------------------

    def sample_fn(self):
        """One state's StatsState contribution (``samples == 1``): the pure
        function the accumulator and the zero-state shapes derive from.
        Every ingredient mirrors the eager legacy accumulator
        (models/statistics.py) and the fused observables
        (models/navier._make_observables) so the engine-vs-legacy parity
        holds at fp tolerance by construction."""
        import jax.numpy as jnp

        m = self.model
        sp_t, sp_u, sp_v = m.temp_space, m.velx_space, m.vely_space
        sp_f = m.field_space
        scale = m.scale
        nu = m.params["nu"]
        ka = m.params["ka"]
        tb = m.tempbc_ortho
        mask = m._dealias
        w0, w1 = m._w0, m._w1
        rdt = config.real_dtype()
        # this rung's per-sample time span (the entry points — and so this
        # sample fn — are rebuilt per dt rung via the _DT_ARTIFACTS cache)
        stride_dt = float(self.stride) * float(m.dt)

        def avg_x(v):
            return jnp.sum(v * w0[:, None], axis=0)

        def avg(v):
            return jnp.sum(v * w0[:, None] * w1[None, :])

        from ..bases import BaseKind

        def spec_fns(space):
            """Per-axis (fold_x, fold_y) mapping stored-row energies to
            NATURAL ascending-mode order, so ``tails()``'s "top third of
            rows" really is the high-wavenumber tail on every layout:
            split-Fourier stores [Re | Im] half-blocks (fold per mode),
            sep axes store the parity permutation (invert it), c2c FFT
            order puts high |k| mid-array (reorder); plain Chebyshev and
            r2c storage is already ascending."""
            from ..ops.folded import parity_perm

            def fold(axis):
                base = space.bases[axis]
                if getattr(base.kind, "is_split", False):
                    mc = base.m_complex
                    return lambda e: e[:mc] + e[mc:]
                if space.sep[axis]:
                    return lambda e: e[np.argsort(parity_perm(e.shape[0]))]
                if base.kind == BaseKind.FOURIER_C2C:
                    return lambda e: e[
                        np.argsort(
                            np.abs(np.fft.fftfreq(e.shape[0])), kind="stable"
                        )
                    ]
                return lambda e: e

            return fold(0), fold(1)

        folds = {sp: spec_fns(sp) for sp in (sp_t, sp_u, sp_v)}

        def spec_pair(c, space):
            """Per-axis energy of one spectral array in natural mode order:
            (x-modes, y-modes)."""
            e = jnp.abs(c) ** 2
            fx, fy = folds[space]
            sx = fx(jnp.sum(e, axis=-1))
            sy = fy(jnp.sum(e, axis=0))
            return sx.astype(rdt), sy.astype(rdt)

        def s1(v):
            return jnp.reshape(v, (1,)).astype(rdt)

        def sample(state):
            that_h = sp_t.to_ortho(state.temp)
            uxhat = sp_u.to_ortho(state.velx)
            uyhat = sp_v.to_ortho(state.vely)
            that = that_h + tb  # full physical temperature (with BC lift)
            temp_p = sp_f.backward_ortho(that)
            ux_p = sp_u.backward(state.velx)
            uy_p = sp_v.backward(state.vely)
            # physical dT/dy, shared by the plate-flux Nu, the volume Nu
            # and the pointwise Nusselt field (statistics.rs:246-270)
            dtdy_p = sp_f.backward_gradient(that, (0, 1), None)
            dtdz = dtdy_p / (-scale[1])
            nusselt_v = (dtdz + uy_p * temp_p / ka) * 2.0 * scale[1]
            nusselt = sp_f.forward(nusselt_v) * mask
            tx, ty = spec_pair(that_h, sp_t)
            uxx, uxy = spec_pair(uxhat, sp_u)
            uyx, uyy = spec_pair(uyhat, sp_v)
            x_avg = avg_x(dtdy_p) * (-2.0 / scale[1])
            nu_plate = 0.5 * (x_avg[0] + x_avg[-1])
            flux = uy_p * temp_p
            ke = 0.5 * avg(ux_p**2 + uy_p**2)
            # viscous dissipation nu * <|grad u|^2> (KE-balance sink)
            duxdx = sp_u.backward_gradient(state.velx, (1, 0), scale)
            duxdy = sp_u.backward_gradient(state.velx, (0, 1), scale)
            duydx = sp_v.backward_gradient(state.vely, (1, 0), scale)
            duydy = sp_v.backward_gradient(state.vely, (0, 1), scale)
            diss = nu * avg(duxdx**2 + duxdy**2 + duydx**2 + duydy**2)
            return StatsState(
                t_sum=that_h,
                ux_sum=uxhat,
                uy_sum=uyhat,
                nusselt_sum=nusselt,
                spec_x=jnp.stack([tx, uxx, uyx]),
                spec_y=jnp.stack([ty, uxy, uyy]),
                t_prof_sum=avg_x(temp_p).astype(rdt),
                t2_prof_sum=avg_x(temp_p**2).astype(rdt),
                ux2_prof_sum=avg_x(ux_p**2).astype(rdt),
                uy2_prof_sum=avg_x(uy_p**2).astype(rdt),
                flux_prof_sum=avg_x(flux).astype(rdt),
                nu_plate_sum=s1(nu_plate),
                nuvol_sum=s1(avg(nusselt_v)),
                flux_vol_sum=s1(avg(flux) * 2.0 * scale[1] / ka),
                ke_sum=s1(ke),
                buoy_sum=s1(avg(flux)),
                diss_sum=s1(diss),
                ke_first=s1(ke),
                ke_last=s1(ke),
                span_sum=jnp.full((1,), stride_dt, rdt),
                span_first=jnp.full((1,), stride_dt, rdt),
                samples=jnp.ones((1,), rdt),
            )

        return sample

    def accum_fn(self):
        """``(stats_state, state) -> stats_state`` — fold one sample in.
        Running sums add; ``ke_first`` keeps the first sample's value and
        ``ke_last`` the newest (the dKE/dt window anchors)."""
        import jax
        import jax.numpy as jnp

        sample = self.sample_fn()

        def accum(ss, state):
            c = sample(state)
            out = jax.tree.map(jnp.add, ss, c)
            return out._replace(
                ke_first=jnp.where(ss.samples > 0, ss.ke_first, c.ke_first),
                ke_last=c.ke_last,
                span_first=jnp.where(
                    ss.samples > 0, ss.span_first, out.span_sum
                ),
            )

        return accum

    def health_fn(self):
        """``stats_state ->`` the :data:`HEALTH_NAMES` scalars — a cheap
        compiled readout over the running sums (no field transforms), so it
        can stream through an observable future at every chunk boundary."""
        import jax.numpy as jnp

        m = self.model
        rdt = config.real_dtype()
        ys = np.asarray(m.field_space.bases[1].points, dtype=np.float64)
        ys = ys * m.scale[1]
        # distance from the nearest plate, per y grid point (ordering-proof)
        dist = np.minimum(ys - ys.min(), ys.max() - ys)
        dist_dev = jnp.asarray(dist, dtype=rdt)
        dy0 = abs(ys[1] - ys[0])
        dy1 = abs(ys[-1] - ys[-2])

        def tails(spec):
            """Energy fraction in the top third of the stored rows, rows
            (T, ux, uy).  A well-resolved spectral run keeps this tiny;
            energy piling at the dealias cut reads as under-resolution."""
            tot = jnp.sum(spec, axis=-1)
            cut = (2 * int(spec.shape[-1])) // 3
            t = jnp.sum(spec[:, cut:], axis=-1) / jnp.maximum(tot, 1e-300)
            return jnp.where(tot > 0, t, 0.0)

        def health(ss):
            n = jnp.maximum(ss.samples[0], 1.0)
            has = ss.samples[0] > 0
            tx = tails(ss.spec_x)
            ty = tails(ss.spec_y)
            t_prof = ss.t_prof_sum / n
            # thermal BL thickness from the mean-profile wall slope:
            # delta_T = (dT/2) / |dT/dy|_wall, grid points within it counted
            slope = 0.5 * (
                jnp.abs(t_prof[1] - t_prof[0]) / dy0
                + jnp.abs(t_prof[-1] - t_prof[-2]) / dy1
            )
            d_temp = jnp.abs(t_prof[-1] - t_prof[0])
            delta_t = 0.5 * d_temp / jnp.maximum(slope, 1e-300)
            bl_thermal = jnp.sum((dist_dev < delta_t).astype(rdt))
            # viscous BL: distance of the horizontal-velocity-RMS peak from
            # the nearest plate (the standard delta_u definition)
            ux_rms = jnp.sqrt(jnp.maximum(ss.ux2_prof_sum / n, 0.0))
            delta_u = dist_dev[jnp.argmax(ux_rms)]
            bl_visc = jnp.sum((dist_dev < delta_u).astype(rdt))
            # budget closures
            nu_plate = ss.nu_plate_sum[0] / n
            nu_flux = 1.0 + ss.flux_vol_sum[0] / n
            nu_resid = jnp.abs(nu_plate - nu_flux) / jnp.maximum(
                jnp.abs(nu_flux), 1.0
            )
            prod = ss.buoy_sum[0] / n
            dis = ss.diss_sum[0] / n
            # elapsed sim time first->last sample, exact across governor
            # dt-rung moves (each sample accumulated its own stride*dt);
            # one sample => span ~0 and dkedt reads 0 (ke_last == ke_first)
            span = jnp.maximum(ss.span_sum[0] - ss.span_first[0], 1e-300)
            dkedt = (ss.ke_last[0] - ss.ke_first[0]) / span
            ke_resid = jnp.abs(prod - dis - dkedt) / jnp.maximum(
                jnp.maximum(jnp.abs(prod), jnp.abs(dis)), 1e-9
            )

            def z(v):
                return jnp.where(has, v, jnp.zeros_like(v))

            return (
                z(tx[0]), z(ty[0]),
                z(tx[1]), z(ty[1]),
                z(tx[2]), z(ty[2]),
                z(bl_thermal), z(bl_visc),
                z(ke_resid), z(nu_resid),
                z(nu_plate), z(nu_flux),
                ss.samples[0],
            )

        return health

    # -- state construction ---------------------------------------------------

    def state_example(self):
        """ShapeDtypeStruct pytree of one member's StatsState."""
        import jax

        if self._example is None:
            self._example = jax.eval_shape(
                self.sample_fn(), self.model._state_example()
            )
        return self._example

    def init_state(self, k: int | None = None):
        """Zeroed StatsState (``k`` adds a leading member axis)."""
        import jax
        import jax.numpy as jnp

        ex = self.state_example()

        def zeros(leaf):
            shape = leaf.shape if k is None else (int(k),) + tuple(leaf.shape)
            return jnp.zeros(shape, dtype=leaf.dtype)

        return jax.tree.map(zeros, ex)

    def host_items(self, stats_state, tick) -> list:
        """``(h5path, numpy array, "raw")`` rows the GATHERED snapshot
        format appends for the stats leaves (exact dtypes — the restore is
        bit-equal).  Gathered writers require fully-addressable state, the
        same contract the baselined state writers carry."""
        items = [
            (f"stats_state/{name}", np.asarray(getattr(stats_state, name)), "raw")
            for name in stats_state._fields
        ]
        items.append(("stats_state/tick", np.asarray(tick), "raw"))
        return items

    def split_restored(self, updates: dict) -> dict:
        """Pull this engine's leaf entries (+ ``tick``) out of a restore
        ``updates`` dict (mutated in place); the remainder is the caller's
        state leaves.  Feed the result to :meth:`restore_state`."""
        names = self.state_example()._fields + ("tick",)
        return {n: updates.pop(n) for n in names if n in updates}

    def restore_state(self, data: dict | None, k: int | None = None):
        """``(stats_state, tick)`` from a restore dict (leaf names +
        ``tick``) — the ONE implementation behind every gathered/sharded
        restore path.  ``None``/missing leaves reset to zero: a checkpoint
        written before the engine was armed restarts the averaging window
        instead of failing the restore."""
        import jax.numpy as jnp

        init = self.init_state(k=k)
        zero_tick = jnp.zeros((1,), jnp.int32)
        if not data:
            return init, zero_tick
        for name in init._fields:
            arr = data.get(name)
            want = tuple(getattr(init, name).shape)
            if arr is not None and tuple(np.shape(arr)) != want:
                # resolution-elastic gathered restart: the STATE leaves
                # interpolate onto the new grid, but running sums on the
                # old spectrum cannot — restart the averaging window
                # instead of handing the stats chunk a shape mismatch
                print(
                    f"restored stats leaf {name!r} has shape "
                    f"{tuple(np.shape(arr))} != {want}; running averages "
                    "restart from zero"
                )
                return init, zero_tick
        fields = {}
        for name in init._fields:
            arr = data.get(name)
            fields[name] = (
                jnp.asarray(arr, dtype=getattr(init, name).dtype)
                if arr is not None
                else getattr(init, name)
            )
        tick = data.get("tick")
        if tick is not None:
            tick = jnp.asarray(
                np.asarray(tick),  # lint-ok: RPD005 tick is a replicated (1,) leaf
                jnp.int32,
            ).reshape((1,))
        return type(init)(**fields), tick if tick is not None else zero_tick


# -- host-side export ---------------------------------------------------------


def _averages(host: StatsState) -> dict:
    """Host-side running averages from a fetched (numpy) StatsState."""
    n = max(float(np.asarray(host.samples).reshape(-1)[0]), 1.0)
    out = {"samples": int(np.asarray(host.samples).reshape(-1)[0])}
    for name in ("t_sum", "ux_sum", "uy_sum", "nusselt_sum"):
        out[name[:-4] + "_avg"] = np.asarray(getattr(host, name)) / n
    out["t_prof"] = np.asarray(host.t_prof_sum) / n
    out["t_rms"] = np.sqrt(
        np.maximum(np.asarray(host.t2_prof_sum) / n - out["t_prof"] ** 2, 0.0)
    )
    out["ux_rms"] = np.sqrt(np.maximum(np.asarray(host.ux2_prof_sum) / n, 0.0))
    out["uy_rms"] = np.sqrt(np.maximum(np.asarray(host.uy2_prof_sum) / n, 0.0))
    out["flux_prof"] = np.asarray(host.flux_prof_sum) / n
    out["spec_x"] = np.asarray(host.spec_x) / n
    out["spec_y"] = np.asarray(host.spec_y) / n
    return out


def _write_member(h5, prefix: str, model, host: StatsState, tot_time: float) -> None:
    """One member's engine export: the legacy ``statistics.h5`` group
    layout (``{temp,ux,uy,nusselt}/{x,dx,y,dy,v,vhat}`` + counters/params,
    statistics.rs:140-158 — so the reference readers keep working) plus the
    engine extras under ``profiles/`` and ``spectra/``.  ``tot_time`` comes
    from the RUNNING object (an ensemble advances its own clock; the
    template model's never moves)."""
    from ..field import grid_deltas
    from ..utils.checkpoint import write_field

    avgs = _averages(host)
    sp = model.field_space
    xs = [b.points * s for b, s in zip(sp.bases, model.scale)]
    dxs = [
        grid_deltas(b.points, b.is_periodic) * s
        for b, s in zip(sp.bases, model.scale)
    ]
    import jax.numpy as jnp

    root = h5.require_group(prefix) if prefix else h5
    for varname, key in (
        ("temp", "t_avg"),
        ("ux", "ux_avg"),
        ("uy", "uy_avg"),
        ("nusselt", "nusselt_avg"),
    ):
        vhat = jnp.asarray(avgs[key], dtype=sp.spectral_dtype())
        write_field(root, varname, sp, vhat, xs, dxs)
    for key, value in (
        ("tot_time", float(tot_time)),
        # accumulated per sample at that sample's own stride*dt — exact
        # across governor dt-rung moves (a current-dt reconstruction would
        # misreport windows that crossed a ladder move)
        ("avg_time", float(np.asarray(host.span_sum).reshape(-1)[0])),
        ("num_save", float(avgs["samples"])),
    ):
        if key in root:
            del root[key]
        root.create_dataset(key, data=value)
    for key, value in model.params.items():
        if key in root:
            del root[key]
        root.create_dataset(key, data=float(value))
    prof = root.require_group("profiles")
    for key, data in (
        ("y", xs[1]),
        ("t_mean", avgs["t_prof"]),
        ("t_rms", avgs["t_rms"]),
        ("ux_rms", avgs["ux_rms"]),
        ("uy_rms", avgs["uy_rms"]),
        ("flux", avgs["flux_prof"]),
    ):
        if key in prof:
            del prof[key]
        prof.create_dataset(key, data=np.asarray(data, dtype=np.float64))
    spec = root.require_group("spectra")
    for key, data in (("x", avgs["spec_x"]), ("y", avgs["spec_y"])):
        if key in spec:
            del spec[key]
        spec.create_dataset(key, data=np.asarray(data, dtype=np.float64))


def export_stats(pde, filename: str) -> None:
    """Write the engine's running averages to HDF5.

    A single model exports the legacy root layout (readable by every
    ``statistics.h5`` consumer) + ``profiles``/``spectra`` groups; an
    ensemble exports per-member groups ``member{i}/...`` (same inner
    layout) with a root ``members`` scalar.  ``plot/plot_statistics.py``
    reads both."""
    import os

    import h5py
    import jax

    if not getattr(pde, "stats_armed", False):
        raise RuntimeError("export_stats needs an armed stats engine (set_stats)")
    os.makedirs(os.path.dirname(filename) or ".", exist_ok=True)
    is_ens = hasattr(pde, "member_state")
    model = pde.model if is_ens else pde
    host = jax.tree.map(np.asarray, pde.stats_state)
    with h5py.File(filename, "a") as h5:
        h5.attrs["stats_engine"] = 1
        h5.attrs["stride"] = int(model.stats_engine.stride)
        if is_ens:
            if "members" in h5:
                del h5["members"]
            h5.create_dataset("members", data=int(pde.k))
            for i in range(pde.k):
                member = jax.tree.map(lambda x, i=i: x[i], host)
                _write_member(h5, f"member{i}", model, member, pde.get_time())
        else:
            _write_member(h5, "", model, host, pde.get_time())
