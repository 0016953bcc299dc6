"""Time-integration driver.

Rebuild of the reference's ``Integrate`` trait + ``integrate`` free function
(/root/reference/src/lib.rs:167-219).  The loop semantics (save-window test,
three stop criteria) are preserved; models may additionally advance many
steps per host round-trip via ``lax.scan`` inside their ``update`` (the
TPU-friendly path) — the driver only sees wall-clock-relevant boundaries.

The driver returns a status string and accepts two hooks (both default to
the plain behavior) so a supervising harness — the resilient runner in
``utils/resilience.py`` — can wrap dispatches and act at chunk boundaries
without forking the loop:

* ``dispatch(pde, n)`` replaces the raw ``pde.update_n(n)`` / ``pde.update()``
  call (watchdog deadlines, fault injection),
* ``on_chunk(pde)`` runs after each chunk's callback/exit checks; returning
  truthy stops the loop with status ``"stopped"`` (checkpoint cadence,
  preemption signals).

Statuses: ``"time_limit"`` | ``"timestep_limit"`` | ``"break"`` (the model's
``exit()`` fired, e.g. NaN divergence) | ``"stopped"`` (``on_chunk`` asked).
"""

from __future__ import annotations

import math

MAX_TIMESTEP = 10_000_000


def _next_boundary(t: float, dt: float, save_intervall: float) -> float:
    """First absolute save boundary ``k * save_intervall`` strictly after
    ``t`` (half-dt tolerance, so a time that just landed on a boundary
    targets the following one).  Working with the integer boundary index
    keeps the save-window test exact at large ``t``, where the legacy
    ``t % save_intervall`` form has lost the float resolution to place a
    half-dt window reliably."""
    return (math.floor((t + dt / 2.0) / save_intervall) + 1) * save_intervall


class Integrate:
    """Duck-typed protocol: update(), get_time(), get_dt(), callback(), exit()."""

    def update(self) -> None:
        raise NotImplementedError

    def get_time(self) -> float:
        raise NotImplementedError

    def get_dt(self) -> float:
        raise NotImplementedError

    def callback(self) -> None:
        pass

    def exit(self) -> bool:
        return False


def integrate(
    pde,
    max_time: float,
    save_intervall: float | None = None,
    *,
    dispatch=None,
    on_chunk=None,
    overlap: bool | None = None,
) -> str:
    """Advance ``pde`` until ``max_time``; invoke ``pde.callback()`` whenever
    the time lands inside a half-dt window around a save interval.  Returns
    the stop status (module docstring).

    Models exposing ``update_n`` (the jitted ``lax.scan`` fast path) advance
    whole save intervals per device dispatch — essential on TPU, where a
    129^2 step is tens of microseconds of device work and a per-step host
    dispatch would dominate.  Stop criteria are then evaluated at
    interval boundaries instead of every step (same observable behavior: the
    reference only *acts* on them via prints/saves at those boundaries).

    Batched models degrade gracefully under this driver: a
    :class:`~rustpde_mpi_tpu.models.ensemble.NavierEnsemble` freezes
    individual diverged members inside its chunked step (per-member finite
    mask) and its ``exit()`` fires only once EVERY member is dead, so the
    loop keeps advancing the surviving members.

    ``overlap`` (chunked path only) opts into **dispatch double-buffering**:
    the per-boundary break check rides an ``exit_future`` instead of a
    blocking ``pde.exit()``, so the next chunk is enqueued before the
    previous one's break flag is fetched — the host never fences the device
    queue at a boundary.  Divergence is then detected at most ONE chunk
    late (the in-scan early-exit has already frozen the state, so the extra
    chunk is near-free identity work), and the final state is always
    resolved exactly before a ``"time_limit"`` return.  ``None`` defers to
    the model's ``io_overlap`` attribute."""
    if hasattr(pde, "update_n"):
        return _integrate_chunked(
            pde, max_time, save_intervall, dispatch, on_chunk, overlap
        )
    timestep = 0
    eps_dt = pde.get_dt() * 1e-4
    boundary = None
    if save_intervall is not None:
        boundary = _next_boundary(pde.get_time(), pde.get_dt(), save_intervall)
    while True:
        if dispatch is not None:
            dispatch(pde, 1)
        else:
            pde.update()
        timestep += 1

        if save_intervall is not None:
            t, dt = pde.get_time(), pde.get_dt()
            if t > boundary - dt / 2.0:
                # inside the half-dt window around the tracked boundary —
                # exact at large t (no modulo); past it (a dt change skipped
                # across), just re-aim at the next boundary
                if t < boundary + dt / 2.0:
                    pde.callback()
                boundary = _next_boundary(t, dt, save_intervall)

        if pde.get_time() + eps_dt >= max_time:
            print(f"time limit reached: {pde.get_time()}")
            return "time_limit"
        if timestep >= MAX_TIMESTEP:
            print(f"timestep limit reached: {timestep}")
            return "timestep_limit"
        if pde.exit():
            print("break criteria triggered")
            return "break"
        if on_chunk is not None and on_chunk(pde):
            return "stopped"


def _integrate_chunked(
    pde,
    max_time: float,
    save_intervall: float | None,
    dispatch=None,
    on_chunk=None,
    overlap: bool | None = None,
) -> str:
    """Chunked driver: one ``update_n`` dispatch per save interval.

    Each chunk aims at the next *absolute* save boundary (k * save_intervall)
    so callback times never drift, and the callback only fires when the time
    actually lands in the reference's half-dt save window.

    With ``overlap`` the break check is double-buffered (see
    :func:`integrate`): each boundary enqueues a fresh ``exit_future`` and
    blocks — if at all — only on the PREVIOUS boundary's future, whose
    device work was queued ahead of the chunk just dispatched and is
    therefore already complete.  NaN persistence makes the one-chunk lag
    safe: a frozen-NaN state (or an all-dead ensemble, or a latched
    sentinel catch) still reads as a break at the next boundary."""
    if overlap is None:
        overlap = bool(getattr(pde, "io_overlap", False))
    overlap = overlap and hasattr(pde, "exit_future")
    pending = None  # the previous boundary's unresolved exit_future
    dispatched = False  # any chunk run (guards the final exact resolve)

    def break_hit() -> bool:
        """Overlapped break check: resolves the newest future when it is
        already done (latch/fast device — exact, zero lag), else trades
        exactness for overlap by resolving the previous boundary's."""
        nonlocal pending
        fut = pde.exit_future()
        if fut.ready():
            pending = None
            return bool(fut.result())
        hit = bool(pending.result()) if pending is not None else False
        pending = fut
        return hit

    timestep = 0
    while True:
        # re-read dt every chunk: a supervising on_chunk/retry harness may
        # have shrunk it (set_dt) since the last boundary
        dt = pde.get_dt()
        eps_dt = dt * 1e-4
        t = pde.get_time()
        if t + eps_dt >= max_time:
            break
        boundary = None
        if save_intervall is not None:
            boundary = _next_boundary(t, dt, save_intervall)
            target = min(boundary, max_time)
        else:
            target = max_time
        n = max(1, round((target - t) / dt))
        n = min(n, MAX_TIMESTEP - timestep)
        if dispatch is not None:
            dispatch(pde, n)
        else:
            pde.update_n(n)
        timestep += n
        dispatched = True
        if boundary is not None:
            # the chunk aimed at one absolute boundary; fire the callback
            # only when the time actually landed in its half-dt window (a
            # governed/preempted dispatch may have advanced less) — exact at
            # large t, unlike the legacy ``t % save_intervall`` test
            if abs(pde.get_time() - boundary) < dt / 2.0:
                pde.callback()
        if timestep >= MAX_TIMESTEP:
            print(f"timestep limit reached: {timestep}")
            return "timestep_limit"
        if break_hit() if overlap else pde.exit():
            print("break criteria triggered")
            return "break"
        if pde.get_time() + eps_dt >= max_time:
            break  # completed: the time limit beats a late stop request
        if on_chunk is not None and on_chunk(pde):
            return "stopped"
    if overlap and dispatched and bool(pde.exit_future().result()):
        # the FINAL state must be judged exactly: a NaN arriving in the last
        # chunk still reports "break", matching the blocking driver
        print("break criteria triggered")
        return "break"
    print(f"time limit reached: {pde.get_time()}")
    return "time_limit"
