"""Swift–Hohenberg pattern-formation models (1-D and 2-D, periodic).

TPU rebuild of the reference's user-level "bring your own PDE" demos
(/root/reference/examples/swift_hohenberg_1d.rs, swift_hohenberg_2d.rs):

    du/dt = [r - (lap + 1)^2] u - u^3

integrated with the reference's IMEX scheme — the stiff linear operator
``(lap+1)^2 - r`` implicit (it is diagonal in Fourier space, so the implicit
solve is one elementwise divide), the cubic nonlinearity explicit:

    u_{n+1} = (u_n - dt * F[(F^-1 u_n)^3]) / (1 + dt*((1 - K^2)^2 - r))

with K^2 = (kx/Lx)^2 + (ky/Ly)^2.  The whole step is transforms + an
elementwise divide — on TPU that is MXU matmul transforms over the split
Re/Im representation (bases.Space1 / bases.BiPeriodicSpace2); there is no
complex arithmetic anywhere on that backend.

Reference-parity details kept: the 1-D model dealiases the cubic term and
does not pin the mean mode; the 2-D model pins the (0,0) mode and enforces
Hermitian symmetry of the ky=0 column each step (without which the implicit
update drifts unstable — swift_hohenberg_2d.rs enforce_hermitian_symmetry).

Both are campaign models (models/campaign.py): a one-leaf ``state`` (the leaf
is ``temp``, as the source's snapshot group is ``temp/``), the step and the
observables hoisted through ``CampaignModelBase._compile_entry_points``, and
``update_n`` / ``set_dt`` / the snapshot surface from the base.  What a scalar
PDE with one field, no velocity and no pressure gives where the base was grown
on a DNS:

* observables ``(norm, energy, amp, mean)``: the source's ``|F|``, the domain
  mean of theta^2, max |theta| and |(0) mode| (which the 2-D pin holds at 0);
  ``mean`` is the NaN detector (index 3), a NaN anywhere in the state shows in it;
* stability sentinels ``(0, energy, mean)`` in the base's ``(cfl, ke, div)``
  slots: nothing advects, so no rate can pass ``max_cfl`` and a sentinel chunk
  stops on a non-finite state only; the energy's growth and the mean are
  tracked as the DNS's kinetic energy and divergence are;
* no mesh (``BiPeriodicSpace2`` has no pencil form), no scenario, and the
  statistics engine refuses it (models/stats.py reads DNS fields).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import config
from ..bases import BiPeriodicSpace2, Space1, fourier_r2c
from ..telemetry import tracing as _tr
from ..utils.integrate import Integrate
from .campaign import _LAYER, CampaignModelBase


def _h5():
    import h5py

    return h5py


class SwiftState(NamedTuple):
    """theta's spectral coefficients (the source's snapshot group ``temp/``)."""

    temp: jax.Array


class _SwiftHohenbergBase(CampaignModelBase, Integrate):
    """What the two models share: the IMEX step, the observables, the dt
    artifact (the diagonal implicit operator) and the source's snapshot IO.
    A subclass names its space, its wavenumbers and what it does to the cubic
    term's spectrum and to the new state."""

    MODEL_KIND = "swift"
    observable_names = ("norm", "energy", "amp", "mean")
    _DT_ARTIFACTS = ("_matl",) + CampaignModelBase._DT_ARTIFACTS
    mesh = None

    def _setup(self, space, shape, r: float, dt: float, length: float) -> None:
        self.space = space
        self.nx, self.ny = shape
        self.r, self.dt, self.length = float(r), float(dt), float(length)
        self.params = {"r": self.r, "length": self.length}
        self.scale = (self.length,) * len(space.coords())
        self.x = [p * self.length for p in space.coords()]
        self.write_intervall: float | None = None
        self._init_campaign()
        # the model's own writes and reads of the physical field (set_theta,
        # theta_physical), one program each and not a launch per operator
        self._to_spectral = jax.jit(space.forward)
        self._to_physical = jax.jit(space.backward)
        self._matl = self._build_matl()
        self.state = SwiftState(space.ndarray_spectral())
        self._compile_entry_points()

    # -- per subclass ---------------------------------------------------------

    def _k2(self) -> np.ndarray:
        """|k|^2 over the spectral shape (host)."""
        raise NotImplementedError

    def _analysis(self):
        """Physical cubic term -> its spectrum (the 1-D model dealiases)."""
        return self.space.forward

    def _symmetry(self):
        """What the step does to the new spectrum last (the 2-D model's pin
        and Hermitian projection)."""
        return lambda theta: theta

    def _mean_mode(self, theta):
        """|c_0|, the modulus of the constant mode."""
        raise NotImplementedError

    # -- the state as the examples and tests read it ---------------------------

    @property
    def theta(self):
        return self.state.temp

    @theta.setter
    def theta(self, value) -> None:
        self.state = SwiftState(value)

    def set_theta(self, values: np.ndarray) -> None:
        with _tr.span("model.set_field", layer=_LAYER, fields=("temp",)):
            self.theta = self._to_spectral(jnp.asarray(values, dtype=config.real_dtype()))

    def theta_physical(self) -> np.ndarray:
        with _tr.span("model.get_field", layer=_LAYER, fields=("temp",)):
            return np.asarray(self._to_physical(self.theta))

    # -- physics hooks (models/campaign.py) -------------------------------------

    def _build_matl(self):
        matl = 1.0 + self.dt * ((1.0 - self._k2()) ** 2 - self.r)
        return jnp.asarray(matl, dtype=config.real_dtype())

    def _rebuild_dt_artifacts(self) -> None:
        self._matl = self._build_matl()
        self._compile_entry_points()

    def _state_example(self):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.state)

    def _make_step(self, with_sentinels: bool = False):
        space, dt, matl = self.space, self.dt, self._matl
        analysis, symmetry, mean_mode = self._analysis(), self._symmetry(), self._mean_mode
        stage = jax.named_scope  # metadata only: names the stage in the compiled text

        def step(state: SwiftState):
            theta = state.temp
            with stage("synthesis"):
                v = space.backward(theta)
            with stage("cubic"):
                cube = v * v * v
            with stage("analysis"):
                cubic = analysis(cube)
            with stage("implicit"):
                out = (theta - dt * cubic) / matl
            with stage("symmetry"):
                out = symmetry(out)
            if with_sentinels:
                with stage("sentinels"):
                    zero = jnp.zeros((), v.dtype)
                    return SwiftState(out), (zero, jnp.mean(v * v), mean_mode(out))
            return SwiftState(out)

        return step

    def _make_observables(self):
        """``(norm, energy, amp, mean)``: |F| is the coefficient-space L2 norm
        over the complex mode count (the reference's norm_l2_c64 diagnostic;
        the split Re/Im representation stores |c|^2 as re^2 + im^2 across its
        two blocks, so the value is backend-independent)."""
        space, norm_len, mean_mode = self.space, self._norm_len, self._mean_mode

        def observables(state: SwiftState):
            theta = state.temp
            v = space.backward(theta)
            norm = jnp.sqrt(jnp.sum(jnp.abs(theta) ** 2)) / norm_len
            # a NaN or an infinity anywhere in the state shows in ``norm``;
            # the pin would hide it from the constant mode alone
            return norm, jnp.mean(v * v), jnp.max(jnp.abs(v)), mean_mode(theta) + 0.0 * norm

        return observables

    def _compat_fields(self) -> tuple:
        return (
            self.nx, self.ny, self.r, self.dt, self.length,
            np.dtype(config.real_dtype()).name,
        )

    # -- diagnostics and IO ----------------------------------------------------

    def norm(self) -> float:
        """|F| (observable 0)."""
        return self.get_observables()[0]

    def pattern_energy(self) -> float:
        """Domain-averaged theta^2 — the pattern-amplitude trace of the
        upstream example (examples/swift_hohenberg_2d.rs)."""
        return self.get_observables()[1]

    def callback(self) -> None:
        import os

        print(f"Time = {self.time:6.2e}")
        os.makedirs("data", exist_ok=True)
        fname = f"data/flow{self.time:0>8.2f}.h5"
        self.write(fname)
        print(f"|F| = {self.norm():6.2e}")

    def write(self, filename: str) -> None:
        """Snapshot in the reference layout: ``temp/{v,vhat,x,dx,...}`` +
        scalars time/dt/r (swift_hohenberg_2d.rs _write)."""
        try:
            self._write(filename)
            print(f" ==> {filename}")
        except OSError as exc:
            print(f"Error while writing file {filename}: {exc}")

    def _write(self, filename: str) -> None:
        from ..field import grid_deltas

        with _h5().File(filename, "w") as f:
            g = f.create_group("temp")
            g.create_dataset("v", data=self.theta_physical())
            vc = self.space.vhat_as_complex(self.theta)
            if np.iscomplexobj(vc):
                g.create_dataset("vhat_re", data=vc.real)
                g.create_dataset("vhat_im", data=vc.imag)
            else:
                g.create_dataset("vhat", data=vc)
            for name, arr in zip("xy", self.x):
                g.create_dataset(name, data=arr)
                g.create_dataset("d" + name, data=grid_deltas(arr, True))
            f.create_dataset("time", data=self.time)
            f.create_dataset("dt", data=self.dt)
            f.create_dataset("r", data=self.r)

    def read(self, filename: str) -> None:
        with _h5().File(filename, "r") as f:
            g = f["temp"]
            if "vhat_re" in g:
                vhat_c = np.asarray(g["vhat_re"]) + 1j * np.asarray(g["vhat_im"])
            else:
                vhat_c = np.asarray(g["vhat"])
            s = self.space.vhat_from_complex(vhat_c)
            dtype = (
                config.complex_dtype()
                if np.iscomplexobj(s)
                else config.real_dtype()
            )
            self.theta = jnp.asarray(s, dtype=dtype)
            self.time = float(np.asarray(f["time"]))


class SwiftHohenberg1D(_SwiftHohenbergBase):
    """1-D Swift–Hohenberg on a periodic domain of length ``2*pi*length``
    (/root/reference/examples/swift_hohenberg_1d.rs)."""

    def __init__(self, nx: int, r: float, dt: float, length: float):
        with self._build_span(nx, 1, None):
            space = Space1(fourier_r2c(nx))
            self._dealias = jnp.asarray(space.dealias_mask(), dtype=config.real_dtype())
            # complex mode count (the split representation has 2x real rows)
            base = space.base
            self._norm_len = base.m_complex if base.kind.is_split else base.m
            self._setup(space, (nx, 1), r, dt, length)
            self.init_cos(1e-5)

    def _k2(self) -> np.ndarray:
        return (self.space.base.wavenumbers / self.length) ** 2

    def _analysis(self):
        space, mask = self.space, self._dealias
        return lambda cube: space.forward(cube) * mask

    def _mean_mode(self, theta):
        base = self.space.base
        if base.kind.is_split:
            return jnp.hypot(theta[0], theta[base.m_complex])
        return jnp.abs(theta[0])

    def init_cos(self, c: float) -> None:
        """One-cosine disturbance over the domain span (reference init_cos)."""
        x = self.x[0]
        span = x[-1] - x[0]
        v = c * np.cos((x - x[0]) / span * 2.0 * np.pi)
        self.set_theta(v)

    def init_random(self, c: float, seed: int = 0) -> None:
        rng = np.random.default_rng(seed)
        self.set_theta(rng.uniform(-c, c, size=self.nx))


class SwiftHohenberg2D(_SwiftHohenbergBase):
    """2-D Swift–Hohenberg on a doubly-periodic square of side
    ``2*pi*length`` (the upstream example
    /root/reference/examples/swift_hohenberg_2d.rs)."""

    def __init__(self, nx: int, ny: int, r: float, dt: float, length: float):
        with self._build_span(nx, ny, None):
            space = BiPeriodicSpace2(nx, ny)
            self._norm_len = nx * space.my
            self._setup(space, (nx, ny), r, dt, length)
            self.init_random(1e-1)

    def _k2(self) -> np.ndarray:
        kx = self.space.kx / self.length
        ky = self.space.ky / self.length
        return kx[:, None] ** 2 + ky[None, :] ** 2

    def _symmetry(self):
        space = self.space
        return lambda theta: space.enforce_hermitian_x(space.pin_zero_mode(theta))

    def _mean_mode(self, theta):
        return jnp.hypot(theta[0, 0, 0], theta[1, 0, 0])

    def init_random(self, c: float, seed: int = 0) -> None:
        rng = np.random.default_rng(seed)
        self.set_theta(rng.uniform(-c, c, size=(self.nx, self.ny)))

    def init_cos(self, c: float, kx: float, ky: float) -> None:
        x, y = self.x
        sx, sy = x[-1] - x[0], y[-1] - y[0]
        v = (
            c
            * np.cos((x[:, None] - x[0]) / sx * kx * np.pi)
            * np.cos((y[None, :] - y[0]) / sy * ky * np.pi)
        )
        self.set_theta(v)
