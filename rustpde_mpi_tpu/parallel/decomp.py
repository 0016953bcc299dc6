"""Explicit pencil decomposition + collectives (the MPI-parity surface).

The models shard through GSPMD constraints (parallel/mesh.py) and never call
these directly — XLA places the all-to-alls.  This module provides the
*explicit* counterpart of the reference's distributed API for user code and
custom kernels: funspace's ``Decomp2d`` bookkeeping with its
``transpose_x_to_y``/``transpose_y_to_x`` repartitions as
``shard_map`` + ``jax.lax.all_to_all`` over the ICI mesh, and the collectives
the reference re-exports (``all_gather_sum``, ``broadcast_scalar``,
gather/scatter to root) — SURVEY.md S2.2 (/root/reference/src/mpi/mod.rs:2-12,
src/field_mpi.rs:455-477).

Pencil convention (reference field_mpi.rs:71-88):

* **y-pencil**: axis 0 (x) distributed, axis 1 contiguous — physical data.
* **x-pencil**: axis 1 (y) distributed, axis 0 contiguous — spectral data.

The explicit transposes accept arbitrary (odd) extents — the equal-tile
all_to_all runs on a zero-padded shape and the pad is sliced away — so the
MPI-parity surface expresses the production grids (129/1025/2049) just like
funspace's transpose_x_to_y.  The GSPMD constraint path in the models
remains the execution path for the physics.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .mesh import AXIS, PHYS, SPEC, make_mesh  # noqa: F401  (re-exported)
from ..config import env_get

def _smap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


@dataclass(frozen=True)
class Pencil:
    """One rank's slab of one pencil orientation (reference ``Decomp2d``
    pencils expose st/en/sz, src/field_mpi.rs:128-135)."""

    st: tuple[int, int]  # global start index per axis (inclusive)
    en: tuple[int, int]  # global end index per axis (inclusive)
    sz: tuple[int, int]  # local shape
    dist_axis: int  # which axis is distributed

    @property
    def axis_contig(self) -> int:
        """The undivided axis (field_mpi/average.rs:50)."""
        return 1 - self.dist_axis


def _split(n: int, nprocs: int, rank: int) -> tuple[int, int]:
    """Balanced contiguous split: first (n % nprocs) ranks get one extra."""
    base, extra = divmod(n, nprocs)
    st = rank * base + min(rank, extra)
    sz = base + (1 if rank < extra else 0)
    return st, sz


class Decomp2d:
    """Pencil bookkeeping + explicit repartitions over a 1-D device mesh.

    ``x_pencil(rank)`` / ``y_pencil(rank)`` give each rank's slab exactly as
    the reference's decomp object does; ``transpose_x_to_y`` /
    ``transpose_y_to_x`` are the all-to-all repartitions (jittable,
    differentiable, runnable inside other shard_mapped code via the
    ``*_local`` variants).
    """

    def __init__(self, global_shape: tuple[int, int], mesh: Mesh | None = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.nprocs = self.mesh.shape[AXIS]
        self.global_shape = tuple(global_shape)

    # -- bookkeeping ---------------------------------------------------------

    def _pencil(self, rank: int, dist_axis: int) -> Pencil:
        n0, n1 = self.global_shape
        if dist_axis == 0:
            st0, sz0 = _split(n0, self.nprocs, rank)
            return Pencil((st0, 0), (st0 + sz0 - 1, n1 - 1), (sz0, n1), 0)
        st1, sz1 = _split(n1, self.nprocs, rank)
        return Pencil((0, st1), (n0 - 1, st1 + sz1 - 1), (n0, sz1), 1)

    def y_pencil(self, rank: int) -> Pencil:
        """Axis 0 distributed (physical-data layout)."""
        return self._pencil(rank, 0)

    def x_pencil(self, rank: int) -> Pencil:
        """Axis 1 distributed (spectral-data layout)."""
        return self._pencil(rank, 1)

    # -- explicit repartitions ----------------------------------------------

    def _pad(self, arr):
        """Zero-pad both extents up to the next mesh multiple so the tiled
        all_to_all exchanges equal blocks; the flagship grids are odd
        (129/1025/2049 — funspace's transpose_x_to_y takes any extent,
        SURVEY.md S2.2, and so does this).  The pad rows/cols ride the
        collective and are sliced away by the caller."""
        n0, n1 = self.global_shape
        p0 = (-n0) % self.nprocs
        p1 = (-n1) % self.nprocs
        if p0 or p1:
            arr = jnp.pad(arr, ((0, p0), (0, p1)))
        return arr

    @staticmethod
    def transpose_x_to_y_local(block):
        """Inside-shard_map body: x-pencil block (n0, n1/P) -> y-pencil
        block (n0/P, n1) (funspace transpose_x_to_y)."""
        return jax.lax.all_to_all(block, AXIS, split_axis=0, concat_axis=1, tiled=True)

    @staticmethod
    def transpose_y_to_x_local(block):
        """Inside-shard_map body: y-pencil block (n0/P, n1) -> x-pencil
        block (n0, n1/P)."""
        return jax.lax.all_to_all(block, AXIS, split_axis=1, concat_axis=0, tiled=True)

    def transpose_x_to_y(self, arr, method: str | None = None):
        """Global-view repartition: axis-1-sharded -> axis-0-sharded.
        Any extents (pad-and-slice around the equal-tile exchange);
        ``method``: None = the RUSTPDE_TRANSPOSE default, "alltoall" |
        "ring" (see :func:`make_transpose_local`)."""
        n0, n1 = self.global_shape
        fn = _smap(
            make_transpose_local(self.nprocs, True, method, self.mesh),
            self.mesh,
            in_specs=PartitionSpec(*SPEC),
            out_specs=PartitionSpec(*PHYS),
        )
        return fn(self._pad(arr))[:n0, :n1]

    def transpose_y_to_x(self, arr, method: str | None = None):
        n0, n1 = self.global_shape
        fn = _smap(
            make_transpose_local(self.nprocs, False, method, self.mesh),
            self.mesh,
            in_specs=PartitionSpec(*PHYS),
            out_specs=PartitionSpec(*SPEC),
        )
        return fn(self._pad(arr))[:n0, :n1]

    # -- placement helpers ---------------------------------------------------

    def place_y_pencil(self, arr):
        return jax.device_put(
            jnp.asarray(arr), NamedSharding(self.mesh, PartitionSpec(*PHYS))
        )

    def place_x_pencil(self, arr):
        return jax.device_put(
            jnp.asarray(arr), NamedSharding(self.mesh, PartitionSpec(*SPEC))
        )


# ---------------------------------------------------------------------------
# explicit ring transposes (the SNIPPETS [1]/[2] remote-copy pattern)
# ---------------------------------------------------------------------------
#
# ``jax.lax.all_to_all`` leaves the collective's placement and scheduling to
# the compiler, which serializes the pencil flip behind the surrounding
# GEMMs.  The ring path expresses the same repartition as P-1 explicit
# shift-permute steps INSIDE the shard_map region, so each step's chunk
# exchange can overlap with per-pencil transform compute instead of waiting
# for a compiler-placed fused collective:
#
# * off-TPU (and for CI equivalence): ``lax.ppermute`` shift rounds —
#   semantically identical data movement, testable on the virtual CPU mesh;
# * on TPU: a Pallas kernel pushing each chunk straight into the destination
#   device's output slab with ``pltpu.make_async_remote_copy`` (direct ICI
#   RDMA, one DMA per ring step, no intermediate staging buffer).  On 4 v5e
#   chips under jax 0.9.0 Mosaic REFUSES it ("collective_id has to be
#   unspecified or None when not using a custom barrier", PR 21): the kernel
#   needs a barrier-semaphore handshake before its remote copies.  Until it
#   has one, selecting it raises ``PallasCompileRefused`` at build
#   (``make_transpose_local``); RUSTPDE_RING_IMPL=ppermute is the ring that
#   runs there.
#
# Selection: RUSTPDE_TRANSPOSE=alltoall (default) | ring, plus the
# per-call ``method=`` override; RUSTPDE_RING_IMPL=ppermute pins the
# ppermute form on TPU (A/B of the DMA kernel vs XLA's collective-permute).


def transpose_method() -> str:
    """The RUSTPDE_TRANSPOSE knob (default ``alltoall``) — selection stays
    measurement-driven like solver.default_method; no cell has timed the
    ring form yet (ROADMAP Queue 1)."""
    return env_get("RUSTPDE_TRANSPOSE", "alltoall")


def _pallas_ring_available() -> bool:
    return (
        jax.devices()[0].platform == "tpu"
        and env_get("RUSTPDE_RING_IMPL", "pallas") != "ppermute"
    )


def make_transpose_local(
    nprocs: int, x_to_y: bool, method: str | None = None, mesh: Mesh | None = None
):
    """Inside-shard_map transpose body for an equal-tile pencil flip.

    ``x_to_y``: (n0, n1/P) -> (n0/P, n1) (spectral x-pencil to physical
    y-pencil); else the inverse.  The returned callable is what the manual-
    sharding conv region and the Decomp2d global-view transposes dispatch.
    With ``mesh`` given, selecting the Pallas remote-copy ring compiles it
    for that mesh NOW, so a compiler refusal is one typed
    ``PallasCompileRefused`` at build naming the knob — never a failure
    deep inside the step's trace, and never a quiet switch to all_to_all."""
    if method is None:
        method = transpose_method()
    if method not in ("alltoall", "ring"):
        raise ValueError(f"unknown transpose method {method!r}")
    if method == "alltoall":
        fn = Decomp2d.transpose_x_to_y_local if x_to_y else Decomp2d.transpose_y_to_x_local
    elif not _pallas_ring_available():
        fn = functools.partial(_ring_transpose_ppermute, nprocs=nprocs, x_to_y=x_to_y)
    else:
        fn = functools.partial(_ring_transpose_pallas, nprocs=nprocs, x_to_y=x_to_y)
        if mesh is not None:
            from ..ops.pallas_common import require_native_compile

            src, dst = (SPEC, PHYS) if x_to_y else (PHYS, SPEC)
            require_native_compile(
                "RUSTPDE_TRANSPOSE=ring (RUSTPDE_RING_IMPL=pallas; ppermute runs "
                "the same ring through XLA's collective-permute)",
                "ring_transpose",
                _smap(fn, mesh, PartitionSpec(*src), PartitionSpec(*dst)),
                jax.ShapeDtypeStruct((8 * nprocs, 128 * nprocs), jnp.float32),
            )
    return _scoped(fn, x_to_y)


def _scoped(exchange, x_to_y: bool):
    """``exchange`` under the trace name of its direction (instruction
    metadata only): every body :func:`make_transpose_local` hands out."""
    name = "transpose_x_to_y" if x_to_y else "transpose_y_to_x"

    def named(block):
        with jax.named_scope(name):
            return exchange(block)

    return named


def sent_bytes(blocks, nprocs: int, itemsize: int) -> int:
    """Bytes ONE device sends for the equal-tile exchanges of the local
    ``blocks`` (rows, cols): of each block a device keeps its own tile and
    sends the other ``nprocs - 1``."""
    return sum(r * c // nprocs * (nprocs - 1) for r, c in blocks) * itemsize


def _ring_transpose_ppermute(block, *, nprocs: int, x_to_y: bool):
    """Shift-permute ring form of the tiled all_to_all: at step s every
    device sends the chunk destined s ranks ahead and receives from s ranks
    behind, placing it at the sender's slot — P-1 uniform shifts, the exact
    data movement of the TPU remote-copy kernel, testable on any backend."""
    me = jax.lax.axis_index(AXIS)
    if x_to_y:
        c = block.shape[0] // nprocs
        w = block.shape[1]
        out = jnp.zeros((c, w * nprocs), dtype=block.dtype)
        take = lambda t: jax.lax.dynamic_slice_in_dim(block, t * c, c, axis=0)
        put = lambda o, chunk, r: jax.lax.dynamic_update_slice_in_dim(
            o, chunk, r * w, axis=1
        )
    else:
        c = block.shape[1] // nprocs
        h = block.shape[0]
        out = jnp.zeros((h * nprocs, c), dtype=block.dtype)
        take = lambda t: jax.lax.dynamic_slice_in_dim(block, t * c, c, axis=1)
        put = lambda o, chunk, r: jax.lax.dynamic_update_slice_in_dim(
            o, chunk, r * h, axis=0
        )
    out = put(out, take(me), me)  # own diagonal chunk, no exchange
    for shift in range(1, nprocs):
        perm = [(d, (d + shift) % nprocs) for d in range(nprocs)]
        recv = jax.lax.ppermute(take((me + shift) % nprocs), AXIS, perm)
        out = put(out, recv, (me - shift) % nprocs)
    return out


def _ring_transpose_kernel(in_ref, out_ref, send_sem, recv_sem, local_sem,
                           *, nprocs: int, x_to_y: bool):
    """Direct-DMA transpose: each ring step pushes one chunk into the
    destination device's output slab at the SENDER's slot
    (``pltpu.make_async_remote_copy``, SNIPPETS [1]/[2]).  Every step is a
    uniform shift, so each device's per-step wait() pairs its send with the
    matching inbound DMA; the own-rank diagonal chunk is a local async
    copy overlapped with the first remote step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    me = jax.lax.axis_index(AXIS)
    if x_to_y:
        c = in_ref.shape[0] // nprocs
        w = out_ref.shape[1] // nprocs
        src_at = lambda t: in_ref.at[pl.ds(t * c, c), :]
        dst_at = lambda r: out_ref.at[:, pl.ds(r * w, w)]
    else:
        c = in_ref.shape[1] // nprocs
        h = out_ref.shape[0] // nprocs
        src_at = lambda t: in_ref.at[:, pl.ds(t * c, c)]
        dst_at = lambda r: out_ref.at[pl.ds(r * h, h), :]
    local = pltpu.make_async_copy(src_at(me), dst_at(me), local_sem)
    local.start()
    for shift in range(1, nprocs):
        dst = (me + shift) % nprocs
        rdma = pltpu.make_async_remote_copy(
            src_ref=src_at(dst),
            dst_ref=dst_at(me),
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=(dst,),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        rdma.wait()
    local.wait()


# Each traced ring-transpose call draws a FRESH collective id: two
# independent transposes in one program (ShardedConv's t1/t0 pair) may be
# scheduled in different relative orders per device, and sharing one
# barrier-semaphore id across concurrent non-identical collectives
# mismatches the send/recv pairing (hang or corrupted chunks).  The counter
# is deterministic because every process traces the same program in the
# same order, so all devices agree on each call site's id.
import itertools

_RING_COLLECTIVE_IDS = itertools.count(16)


def _ring_transpose_pallas(block, *, nprocs: int, x_to_y: bool):
    """TPU entry for the remote-copy ring (inside shard_map; HBM-resident
    refs, the DMAs stream chunks without a VMEM round-trip)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if x_to_y:
        out_shape = (block.shape[0] // nprocs, block.shape[1] * nprocs)
    else:
        out_shape = (block.shape[0] * nprocs, block.shape[1] // nprocs)
    return pl.pallas_call(
        functools.partial(_ring_transpose_kernel, nprocs=nprocs, x_to_y=x_to_y),
        # inside shard_map the result varies over the same mesh axes as the
        # block it was cut from
        out_shape=jax.ShapeDtypeStruct(
            out_shape, block.dtype, vma=jax.typeof(block).vma
        ),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 3,
        compiler_params=pltpu.CompilerParams(
            collective_id=next(_RING_COLLECTIVE_IDS)
        ),
        name="ring_transpose",
    )(block)


# ---------------------------------------------------------------------------
# manual-partitioned convection chain (the GSPMD split-sep bypass)
# ---------------------------------------------------------------------------


class ShardedConv:
    """The convection-transform chain as ONE ``shard_map`` region: per-pencil
    transform GEMMs on the locally-full axis with explicit pencil transposes
    (all_to_all or the remote-copy ring, RUSTPDE_TRANSPOSE) between them —
    manual partitioning instead of GSPMD propagation.

    This is the sharded sibling of ops/pallas_conv.FusedConv, built from the
    same ``Base.axis_operator`` dense matrices, and the mechanism that
    retires the per-stage eager fallback on the split-sep periodic layout:
    the upstream GSPMD miscompile lives in the compiler's partitioning of
    the fused transform graph, and a shard_map region is opaque to that
    propagation — inside it, every collective is placed BY HAND, so the
    fused step compiles correctly under an active mesh (de-xfailed in
    tests/test_parallel.py; ``RUSTPDE_FORCE_FUSED_GSPMD=1`` keeps a pinned
    sibling tracking the upstream bug).

    Unlike the dealiased-forward row-drop of the Pallas kernel, the dead
    2/3-rule rows stay zeroed in the forward matrices here — uniform tile
    shapes keep the equal-tile transposes trivial; the flop cost of the
    zero rows is the price of the manual layout until the ring+kernel
    fusion lands on-chip."""

    def __init__(self, space_in, field_space, scale, mesh: Mesh):
        from .. import config

        self.mesh = mesh
        self.nprocs = int(mesh.shape[AXIS])
        P = self.nprocs
        bx_in, by_in = space_in.bases
        fx_b, fy_b = field_space.bases
        if bx_in.spectral_is_complex or fx_b.spectral_is_complex:
            raise ValueError(
                "ShardedConv expects the split Re/Im x-representation "
                "(the layout real multichip meshes run)"
            )
        gx1 = bx_in.axis_operator(("bwd_grad", 1), sep=space_in.sep[0]).matrix
        gx0 = bx_in.axis_operator("bwd", sep=space_in.sep[0]).matrix
        gy1 = by_in.axis_operator(("bwd_grad", 1), sep=space_in.sep[1]).matrix
        gy0 = by_in.axis_operator("bwd", sep=space_in.sep[1]).matrix
        fxm = fx_b.axis_operator("fwd_cut", sep=field_space.sep[0]).matrix
        fym = fy_b.axis_operator("fwd_cut", sep=field_space.sep[1]).matrix
        gx1 = gx1 / float(scale[0])
        gy1 = gy1 / float(scale[1])

        self.nx, self.ny = space_in.shape_physical
        self.mx, self.my = gx0.shape[1], gy0.shape[1]
        self.mxf, self.myf = fxm.shape[0], fym.shape[0]
        self.nxp = -(-self.nx // P) * P
        self.myp = -(-self.my // P) * P
        self.myfp = -(-self.myf // P) * P
        from ..ops.folded import pad_dense as pad

        rdt = config.real_dtype()
        with jax.ensure_compile_time_eval():
            self._gx1 = jnp.asarray(pad(gx1, self.nxp, self.mx), dtype=rdt)
            self._gx0 = jnp.asarray(pad(gx0, self.nxp, self.mx), dtype=rdt)
            self._gy0t = jnp.asarray(pad(gy0.T, self.myp, self.ny), dtype=rdt)
            self._gy1t = jnp.asarray(pad(gy1.T, self.myp, self.ny), dtype=rdt)
            self._fx = jnp.asarray(pad(fxm, self.mxf, self.nxp), dtype=rdt)
            self._fyt = jnp.asarray(pad(fym.T, self.ny, self.myfp), dtype=rdt)

        x2y = make_transpose_local(P, x_to_y=True, mesh=mesh)
        y2x = make_transpose_local(P, x_to_y=False, mesh=mesh)
        # local blocks one apply exchanges (t1 and t0 out, fy back)
        self.exchanges = (
            (self.nxp, self.myp // P), (self.nxp, self.myp // P), (self.nxp // P, self.myfp),
        )

        def region(gx1m, gx0m, gy0tm, gy1tm, fxm_, fytm, vb, uxb, uyb, bdxb, bdyb):
            # spectral x-pencil: x-axis locally full — synthesis(-of-d/dx)
            t1 = gx1m @ vb
            t0 = gx0m @ vb
            # pencil flip, then the y syntheses on the locally-full y axis
            dvdx = x2y(t1) @ gy0tm
            dvdy = x2y(t0) @ gy1tm
            total = uxb * (dvdx + bdxb) + uyb * (dvdy + bdyb)
            # dealiased forward: y first (local), flip back, then x
            fy = total @ fytm
            return fxm_ @ y2x(fy)

        rep = PartitionSpec()
        self._region = _smap(
            region,
            mesh,
            in_specs=(rep,) * 6
            + (PartitionSpec(*SPEC),)
            + (PartitionSpec(*PHYS),) * 4,
            out_specs=PartitionSpec(*SPEC),
        )

    def apply(self, ux, uy, vhat, bc_dx=None, bc_dy=None):
        """Global-view conv: (ux, uy) physical y-pencils, ``vhat`` spectral
        x-pencil -> dealiased spectral x-pencil (zeros in the dead rows),
        identical in value to the unfused serial chain."""
        padp = ((0, self.nxp - self.nx), (0, 0))
        pads = ((0, 0), (0, self.myp - self.my))
        z = jnp.zeros_like(ux) if bc_dx is None else bc_dx
        z2 = jnp.zeros_like(uy) if bc_dy is None else bc_dy
        with jax.named_scope("sharded_conv"):
            out = self._region(
                self._gx1, self._gx0, self._gy0t, self._gy1t, self._fx, self._fyt,
                jnp.pad(vhat, pads),
                jnp.pad(ux, padp), jnp.pad(uy, padp),
                jnp.pad(z, padp), jnp.pad(z2, padp),
            )
            return out[:, : self.myf]


class ShardedSynthesis:
    """Manual-partitioned 2-D backward synthesis (spectral x-pencil ->
    physical y-pencil): the convection-velocity transforms of the manual
    split-sep step, same shard_map + explicit-transpose structure as
    :class:`ShardedConv` and built from the same ``axis_operator``
    matrices."""

    def __init__(self, space, scale_unused, mesh: Mesh):
        from .. import config

        del scale_unused
        self.mesh = mesh
        P = self.nprocs = int(mesh.shape[AXIS])
        bx_in, by_in = space.bases
        gx0 = bx_in.axis_operator("bwd", sep=space.sep[0]).matrix
        gy0 = by_in.axis_operator("bwd", sep=space.sep[1]).matrix
        self.nx, self.ny = space.shape_physical
        self.mx, self.my = gx0.shape[1], gy0.shape[1]
        self.nxp = -(-self.nx // P) * P
        self.myp = -(-self.my // P) * P
        from ..ops.folded import pad_dense as pad

        rdt = config.real_dtype()
        with jax.ensure_compile_time_eval():
            self._gx0 = jnp.asarray(pad(gx0, self.nxp, self.mx), dtype=rdt)
            self._gy0t = jnp.asarray(pad(gy0.T, self.myp, self.ny), dtype=rdt)
        x2y = make_transpose_local(P, x_to_y=True, mesh=mesh)
        self.exchanges = ((self.nxp, self.myp // P),)

        def region(gx0m, gy0tm, vb):
            return x2y(gx0m @ vb) @ gy0tm

        rep = PartitionSpec()
        self._region = _smap(
            region,
            mesh,
            in_specs=(rep, rep, PartitionSpec(*SPEC)),
            out_specs=PartitionSpec(*PHYS),
        )

    def apply(self, vhat):
        with jax.named_scope("sharded_synthesis"):
            out = self._region(
                self._gx0, self._gy0t,
                jnp.pad(vhat, ((0, 0), (0, self.myp - self.my))),
            )
            return out[: self.nx, :]


class ShardedPoisson:
    """The pressure-Poisson fast-diagonalisation solve as one manual
    shard_map region — THE stage the GSPMD miscompile localizes to.

    Bisection on the 8-device CPU mesh (every other stage toggled between
    GSPMD and manual regions, 8-step trajectories vs serial): with the
    whole step under GSPMD the split-sep periodic layout diverges from
    step 1 (div_norm 0.42); making conv/syntheses/gradients/orthos manual
    leaves the error unchanged (pres 0.177); making ONLY this solve manual
    drops the full-step error to ~1.6e-15.  The fused FastDiag on the
    split-Fourier axis (modal identity on axis 0, eigendecomposed GEMMs on
    axis 1, 2-D modal denominator) is what XLA's SPMD propagation
    mispartitions when fused with its neighbors.

    Structure (x-pencil in/out, all collectives hand-placed): transpose to
    the y-pencil, ``fwd1`` eigen-map on the locally-full y axis, divide by
    the lane-sharded modal denominator, ``bwd1`` back, transpose to the
    x-pencil.  The Fourier axis-0 maps are identity (asserted)."""

    def __init__(self, solver, space, mesh: Mesh):
        from .. import config
        from ..solver import FastDiag

        fd = getattr(solver, "_solver", solver)
        if not isinstance(fd, FastDiag) or fd.fwd[0] is not None or fd.bwd[0] is not None:
            raise ValueError(
                "ShardedPoisson wraps the fast-diagonalisation solver with a "
                "modal (Fourier) axis 0 — the split-sep periodic layout"
            )
        self._fwd1, self._bwd1 = fd.fwd[1], fd.bwd[1]
        P = self.nprocs = int(mesh.shape[AXIS])
        self.mx = space.shape_spectral[0]
        self.my_in = space.bases[1].n  # ortho rhs rows along y
        self.my_out = space.shape_spectral[1]
        self.mxp = -(-self.mx // P) * P
        self.myip = -(-self.my_in // P) * P
        self.myop = -(-self.my_out // P) * P
        denom = np.ones((self.mxp, np.asarray(fd.denom).shape[1]))
        denom[: self.mx] = np.asarray(fd.denom)  # pad lanes divide by 1
        rdt = config.real_dtype()
        with jax.ensure_compile_time_eval():
            self._denom = jnp.asarray(denom, dtype=rdt)
        x2y = make_transpose_local(P, x_to_y=True, mesh=mesh)
        y2x = make_transpose_local(P, x_to_y=False, mesh=mesh)
        my_in, myop = self.my_in, self.myop
        fwd1, bwd1 = self._fwd1, self._bwd1
        self.exchanges = ((self.mxp, self.myip // P), (self.mxp // P, self.myop))

        def region(denom_blk, rhs_blk):
            t = x2y(rhs_blk)[:, :my_in]
            if fwd1 is not None:
                t = fwd1.apply(t, 1)
            t = t / denom_blk.astype(t.dtype)
            if bwd1 is not None:
                t = bwd1.apply(t, 1)
            t = jnp.pad(t, ((0, 0), (0, myop - t.shape[1])))
            return y2x(t)

        self._region = _smap(
            region,
            mesh,
            in_specs=(PartitionSpec(AXIS), PartitionSpec(*SPEC)),
            out_specs=PartitionSpec(*SPEC),
        )

    def solve(self, rhs):
        with jax.named_scope("sharded_poisson"):
            out = self._region(
                self._denom,
                jnp.pad(rhs, ((0, self.mxp - self.mx), (0, self.myip - rhs.shape[1]))),
            )
            return out[: self.mx, : self.my_out]


# ---------------------------------------------------------------------------
# collectives (reference src/mpi/mod.rs re-exports)
# ---------------------------------------------------------------------------


def all_gather_sum(arr, mesh: Mesh | None = None, spec=PHYS):
    """Sum a sharded array's per-rank contributions so every rank holds the
    global sum — the reference's ``all_gather_sum``
    (/root/reference/src/navier_stokes_mpi/functions.rs:137-139).  ``arr`` is
    the global view sharded by ``spec``; the result is fully replicated."""
    mesh = mesh if mesh is not None else make_mesh()

    def body(block):
        return jax.lax.psum(jnp.sum(block), AXIS)

    fn = _smap(
        body, mesh, in_specs=PartitionSpec(*spec), out_specs=PartitionSpec()
    )
    return fn(arr)


def broadcast_scalar(value, mesh: Mesh | None = None):
    """Root rank's value to all ranks (reference ``broadcast_scalar``; under
    the single-controller model every process already holds host scalars, so
    this is the in-mesh form: rank 0's lane wins)."""
    mesh = mesh if mesh is not None else make_mesh()
    nprocs = mesh.shape[AXIS]

    def body(vals):  # vals: (1,) per rank
        mine = jnp.where(jax.lax.axis_index(AXIS) == 0, vals[0], 0.0)
        return jnp.full((1,), jax.lax.psum(mine, AXIS))

    per_rank = jnp.asarray(value, dtype=jnp.result_type(value, 0.0)).reshape(())
    stacked = jnp.broadcast_to(per_rank, (nprocs,))
    fn = _smap(body, mesh, in_specs=PartitionSpec(AXIS), out_specs=PartitionSpec(AXIS))
    return fn(stacked)[0]


def gather_root(arr) -> np.ndarray:
    """Full global array on the host — the reference's gather-to-root IO path
    (/root/reference/src/field_mpi/io.rs:45-70).  Under JAX's
    single-controller model this is one device-to-host fetch; across real
    multi-host meshes use jax.experimental.multihost_utils instead."""
    return np.asarray(arr)


def scatter_root(values, decomp: Decomp2d, pencil: str = "y"):
    """Host array -> pencil-sharded device array (reference scatter,
    field_mpi.rs:359-453)."""
    if pencil == "y":
        return decomp.place_y_pencil(values)
    return decomp.place_x_pencil(values)
