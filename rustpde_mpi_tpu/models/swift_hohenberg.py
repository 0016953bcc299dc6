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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import config
from ..bases import BiPeriodicSpace2, Space1, fourier_r2c
from ..utils.integrate import Integrate


def _h5():
    import h5py

    return h5py


class _SwiftHohenbergBase(Integrate):
    """Shared driver plumbing (time bookkeeping, scanned update_n, IO)."""

    def __init__(self, r: float, dt: float):
        self.r = r
        self.dt = dt
        self.time = 0.0
        self.write_intervall: float | None = None

    def _compile(self):
        from ..utils.jit import hoist_constants

        step = self._make_step()
        converted, consts = hoist_constants(step, self.theta)
        self._consts = consts

        @jax.jit
        def step_1(consts, theta):
            return converted(consts, theta)

        from functools import partial

        @partial(jax.jit, static_argnums=2)
        def step_n(consts, theta, n):
            return jax.lax.scan(
                lambda th, _: (converted(consts, th), None), theta, None, length=n
            )[0]

        self._step_1 = lambda th: step_1(self._consts, th)
        self._step_n = lambda th, n: step_n(self._consts, th, n)

    def update(self) -> None:
        self.theta = self._step_1(self.theta)
        self.time += self.dt

    def update_n(self, n: int) -> None:
        from ..utils.jit import run_scanned

        self.theta = run_scanned(self._step_n, self.theta, n)
        self.time += n * self.dt

    def get_time(self) -> float:
        return self.time

    def get_dt(self) -> float:
        return self.dt

    def norm(self) -> float:
        """|F|: coefficient-space L2 norm / complex mode count (the
        reference's norm_l2_c64 diagnostic, swift_hohenberg_2d.rs).  The
        split Re/Im representation stores |c|^2 as re^2 + im^2 across its two
        blocks, so the value is backend-independent."""
        a = np.asarray(self.theta)
        return float(np.sqrt(np.sum(np.abs(a) ** 2)) / self._norm_len)

    def exit(self) -> bool:
        return bool(np.any(np.isnan(np.asarray(self.theta))))

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
        super().__init__(r, dt)
        self.nx = nx
        self.space = Space1(fourier_r2c(nx))
        self.scale = (float(length),)
        self.x = [self.space.base.points * length]
        k = self.space.base.wavenumbers / length
        matl = 1.0 + dt * ((1.0 - k**2) ** 2 - r)
        self._matl = jnp.asarray(matl, dtype=config.real_dtype())
        self._dealias = jnp.asarray(
            self.space.dealias_mask(), dtype=config.real_dtype()
        )
        self.theta = self.space.ndarray_spectral()
        # complex mode count (the split representation has 2x real rows)
        base = self.space.base
        self._norm_len = base.m_complex if base.kind.is_split else base.m
        self.init_cos(1e-5)
        self._compile()

    def init_cos(self, c: float) -> None:
        """One-cosine disturbance over the domain span (reference init_cos)."""
        x = self.x[0]
        span = x[-1] - x[0]
        v = c * np.cos((x - x[0]) / span * 2.0 * np.pi)
        self.set_theta(v)

    def init_random(self, c: float, seed: int = 0) -> None:
        rng = np.random.default_rng(seed)
        self.set_theta(rng.uniform(-c, c, size=self.nx))

    def set_theta(self, values: np.ndarray) -> None:
        self.theta = self.space.forward(
            jnp.asarray(values, dtype=config.real_dtype())
        )

    def theta_physical(self) -> np.ndarray:
        return np.asarray(self.space.backward(self.theta))

    def _make_step(self):
        space, dt = self.space, self.dt
        matl, mask = self._matl, self._dealias

        def step(theta):
            v = space.backward(theta)
            cubic = space.forward(v * v * v) * mask
            return (theta - dt * cubic) / matl

        return step

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
            g.create_dataset("x", data=self.x[0])
            g.create_dataset("dx", data=grid_deltas(self.x[0], True))
            f.create_dataset("time", data=self.time)
            f.create_dataset("dt", data=self.dt)
            f.create_dataset("r", data=self.r)


class SwiftHohenberg2D(_SwiftHohenbergBase):
    """2-D Swift–Hohenberg on a doubly-periodic square of side
    ``2*pi*length`` (the upstream example
    /root/reference/examples/swift_hohenberg_2d.rs)."""

    def __init__(self, nx: int, ny: int, r: float, dt: float, length: float):
        super().__init__(r, dt)
        self.nx, self.ny = nx, ny
        self.space = BiPeriodicSpace2(nx, ny)
        self.scale = (float(length), float(length))
        self.x = [p * length for p in self.space.coords()]
        kx = self.space.kx / length
        ky = self.space.ky / length
        k2 = kx[:, None] ** 2 + ky[None, :] ** 2
        matl = 1.0 + dt * ((1.0 - k2) ** 2 - r)
        self._matl = jnp.asarray(matl, dtype=config.real_dtype())
        self.theta = self.space.ndarray_spectral()
        self._norm_len = nx * self.space.my
        self.init_random(1e-1)
        self._compile()

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

    def set_theta(self, values: np.ndarray) -> None:
        self.theta = self.space.forward(
            jnp.asarray(values, dtype=config.real_dtype())
        )

    def theta_physical(self) -> np.ndarray:
        return np.asarray(self.space.backward(self.theta))

    def _make_step(self):
        space, dt = self.space, self.dt
        matl = self._matl

        def step(theta):
            v = space.backward(theta)
            cubic = space.forward(v * v * v)
            out = (theta - dt * cubic) / matl
            out = space.pin_zero_mode(out)
            return space.enforce_hermitian_x(out)

        return step

    def pattern_energy(self) -> float:
        """Domain-averaged theta^2 — the pattern-amplitude trace of the
        upstream example (examples/swift_hohenberg_2d.rs)."""
        v = self.theta_physical()
        return float(np.mean(v**2))

    def _write(self, filename: str) -> None:
        from ..field import grid_deltas

        with _h5().File(filename, "w") as f:
            g = f.create_group("temp")
            g.create_dataset("v", data=self.theta_physical())
            vc = self.space.vhat_as_complex(self.theta)
            g.create_dataset("vhat_re", data=vc.real)
            g.create_dataset("vhat_im", data=vc.imag)
            for name, arr in (("x", self.x[0]), ("y", self.x[1])):
                g.create_dataset(name, data=arr)
                g.create_dataset("d" + name, data=grid_deltas(arr, True))
            f.create_dataset("time", data=self.time)
            f.create_dataset("dt", data=self.dt)
            f.create_dataset("r", data=self.r)
