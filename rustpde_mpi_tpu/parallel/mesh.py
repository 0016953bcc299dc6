"""Device mesh + pencil-sharding layer.

TPU rebuild of the reference's distributed backend (funspace::spaces_mpi /
Decomp2d, SURVEY.md S2.2-S2.3): a 1-D device mesh over which 2-D fields are
pencil-decomposed.  The reference's convention is kept exactly on a confined
(Chebyshev x Chebyshev) space —

* **physical** data in y-pencils: axis 0 (x) distributed, P("p", None)
* **spectral** data in x-pencils: axis 1 (y) distributed, P(None, "p")

and turned round on a Fourier x Chebyshev one, whose every spectral
x-operator but the odd derivative of the split layout is a diagonal: its
spectral arrays rest as y-pencils and its physical ones as x-pencils
(``bases.Space2.rest``).  Instead of hand-written MPI all-to-alls
(/root/reference/src/field_mpi.rs:455-477) the repartitions are expressed as
``jax.lax.with_sharding_constraint`` at the pencil-flip points inside
transforms and solvers; XLA GSPMD inserts the all-to-all collectives and
overlaps them with compute.  The rule: an operator that needs a whole axis
runs where that axis is local, and the layout is stated at the operator, not
left to propagation (bases.Space2, solver.py).  One code path serves serial
and sharded execution: with no active mesh every constraint is a no-op, so
the physics layer (models/navier.py) is written once — the reference's
duplicated navier_stokes vs navier_stokes_mpi modules collapse into one.
"""

from __future__ import annotations

import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXIS = "p"

_ACTIVE: Mesh | None = None

# pencil specs (reference convention, /root/reference/src/field_mpi.rs:71-88)
PHYS = (AXIS, None)  # y-pencil: x distributed
SPEC = (None, AXIS)  # x-pencil: y distributed
LOCAL = (SPEC, PHYS)  # LOCAL[axis]: the pencil in which ``axis`` is whole on a device


def make_mesh(devices=None) -> Mesh:
    """1-D mesh over all (or the given) devices."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.array(devices), (AXIS,))


def set_mesh(mesh: Mesh | None) -> None:
    """Install ``mesh`` as the active pencil mesh (None disables sharding)."""
    global _ACTIVE
    _ACTIVE = mesh


def active_mesh() -> Mesh | None:
    return _ACTIVE


class use_mesh:
    """Context manager scoping an active mesh."""

    def __init__(self, mesh: Mesh | None):
        self.mesh = mesh
        self.prev: Mesh | None = None

    def __enter__(self):
        self.prev = active_mesh()
        set_mesh(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        set_mesh(self.prev)
        return False


def pencil_sharding(mesh: Mesh, spec: tuple, ndim: int | None = None) -> NamedSharding:
    """NamedSharding for ``spec`` on an EXPLICIT mesh.  When ``ndim``
    exceeds the spec rank the spec applies to the *trailing* dims (leading
    dims are replicated batch).  This is the active-mesh-free form — the
    sharded-checkpoint restore (utils/checkpoint.read_sharded_snapshot)
    builds target layouts for meshes that are not installed as the active
    pencil mesh."""
    if ndim is not None and ndim > len(spec):
        spec = (None,) * (ndim - len(spec)) + tuple(spec)
    return NamedSharding(mesh, PartitionSpec(*spec))


def sharding(spec: tuple, ndim: int | None = None) -> NamedSharding | None:
    """NamedSharding for ``spec`` on the ACTIVE mesh; when ``ndim`` exceeds
    the spec rank the spec applies to the *trailing* dims (leading dims are
    replicated batch — the stacked-field transforms in models/navier.py)."""
    mesh = active_mesh()
    if mesh is None:
        return None
    return pencil_sharding(mesh, spec, ndim)


# Arrays whose sharded dim does not divide the mesh are PLACED fully
# replicated over the whole mesh, and a warning says so.  JAX cannot hold an
# unevenly sharded array between dispatches: device_put rejects the layout,
# and a jit output whose constraint does not divide comes back replicated
# (the pencils are cut, padded, INSIDE the step — measured on 4 devices at
# 1025^2: f32[257,1025] dots, all-gathers at the flips, replicated result).
# Committing the replicated layout from construction is therefore the steady
# state made explicit: no leaf of the STATE sits uncommitted on device 0, and
# no executable has to repartition a leftover compiler-chosen partial sharding
# (the "[SPMD] Involuntary full rematerialization" a 17^2 `pres` on 8 devices
# used to trigger on every dispatch).  The same holds of the hoisted operator
# CONSTANTS a meshed model's programs take as arguments only since they go
# through ``replicate`` where they are hoisted (CampaignModelBase._hoist):
# until then ``hoist_constants`` left them uncommitted on device 0 and every
# dispatch laid them out over the mesh anew (49 ms a dispatch at 1024 x 1025
# on four chips).  Distributing such a state, or the operators, for real needs
# padded storage (1023 -> 1024 columns); ROADMAP Queue 2 A2.


def constrain(x, spec: tuple):
    """Pin ``x`` to a pencil layout inside a jitted computation; no-op without
    an active mesh.  This is the TPU equivalent of the reference's
    transpose_x_to_y/transpose_y_to_x calls — the collective itself is left
    to XLA.  Outside a trace (eager setup code) it becomes a resharding.
    Arrays with more dims than the spec treat the extra leading dims as
    replicated batch.

    NOTE an operator that needs a whole axis (a transform, a solve, a
    Chebyshev derivative, a composite cast) states the layout in which that
    axis is local on its own input: left to propagation, GSPMD runs it along
    the sharded axis and sums whole fields over the devices.

    NOTE in-jit constraints deliberately do NOT take the replicated pin of
    ``device_put``: inside a jit a non-divisible constraint pads, and the
    pencil-flip constraint pattern inside the transforms is what the
    serial==sharded 1e-12 equality tests validate.  Only EAGER placement
    (``device_put``) canonicalizes."""
    s = sharding(spec, np.ndim(x))
    if s is None:
        return x
    if _is_tracer(x):
        return jax.lax.with_sharding_constraint(x, s)
    return device_put(x, spec)


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


# The flips a program states, counted where it is traced: ``flips()`` opens a
# tally for the calling thread, and every ``flip`` traced under an active mesh
# while it is open adds one flip and the bytes one device sends for it.  The
# compiler places the all-to-alls, so the count is what the program asks for,
# once per trace; a dispatch pays nothing for it.
_TALLY = threading.local()


class flips:
    """Context manager: ``{"flips", "bytes"}`` of the ``flip`` calls this
    thread traces while it is open (nested tallies count apart)."""

    def __enter__(self):
        self.prev = getattr(_TALLY, "open", None)
        _TALLY.open = {"flips": 0, "bytes": 0}
        return _TALLY.open

    def __exit__(self, *exc):
        _TALLY.open = self.prev
        return False


def flip(x, spec: tuple, at: tuple | None = None):
    """``constrain(x, spec)`` for an ``x`` stated in the pencil ``at`` (None:
    in ``spec`` already).  Where the two differ this is a pencil flip, and an
    open tally (``flips``) counts it with the bytes one device sends, its
    tiles of ``x`` that other devices hold (the sharded extents padded to the
    mesh, as the all-to-all moves them), at ``x``'s own itemsize."""
    out = constrain(x, spec)
    tally, mesh = getattr(_TALLY, "open", None), active_mesh()
    if tally is not None and mesh is not None and at not in (None, spec) and _is_tracer(x):
        p = int(mesh.size)
        *batch, n0, n1 = np.shape(x)
        tile = int(np.prod(batch, dtype=np.int64)) * (-(-n0 // p)) * (-(-n1 // p))
        tally["flips"] += 1
        tally["bytes"] += tile * (p - 1) * np.dtype(x.dtype).itemsize
    return out


class ReplicatedPencilWarning(UserWarning):
    """A pencil-sharded placement fell back to full replication because the
    sharded extent does not divide the mesh (see ``device_put``)."""


def device_put(x, spec: tuple):
    """Place an array in pencil layout (host->device with sharding).

    Spectral grid sizes are typically odd (129, 1025, ...), so sharded dims
    are often not divisible by the mesh, which explicit placement rejects.
    Such an array is committed REPLICATED over the whole mesh instead (see
    the note above) with a one-time warning per shape."""
    mesh = active_mesh()
    if mesh is None:
        return x
    import jax.numpy as jnp

    arr = jnp.asarray(x)
    s = sharding(spec, arr.ndim)
    if divides(arr.shape, s):
        return jax.device_put(arr, s)
    import warnings

    warnings.warn(
        f"pencil layout {tuple(s.spec)} does not divide shape {arr.shape} "
        f"over {mesh.size} devices: the array is kept REPLICATED on every "
        "device between dispatches (pencils are cut inside the step)",
        ReplicatedPencilWarning,
        stacklevel=2,
    )
    return jax.device_put(
        arr, NamedSharding(mesh, PartitionSpec(*([None] * arr.ndim)))
    )


def divides(shape: tuple, s: NamedSharding) -> bool:
    """Whether every sharded extent of ``shape`` divides its mesh axis (one
    source of truth for the leading-batch padding: the padded spec is read
    back off the sharding itself)."""
    return all(
        sp is None or shape[i] % s.mesh.shape[sp] == 0 for i, sp in enumerate(s.spec)
    )


def settle(x, spec: tuple):
    """``device_put``'s rule inside a jitted computation, for what a program
    hands back: the pencil layout where the extent divides the mesh, else
    whole on every device.  That is the layout ``device_put`` gave the
    argument, so a chunk's state comes back laid out as it went in, and the
    next dispatch (or an executable built ahead of it, which refuses any other
    layout) finds it where it expects it.  Left to the compiler, a result
    whose constraint does not divide comes back in whatever layout of the
    mesh does: replicated for the odd extents of a Chebyshev axis, but cut in
    two for the 1026 rows of a split Fourier axis on four devices.  No-op
    without an active mesh, and for anything below rank 2 (a scalar leaf)."""
    if active_mesh() is None or np.ndim(x) < len(spec):
        return x
    s = sharding(spec, np.ndim(x))
    if not divides(np.shape(x), s):
        s = NamedSharding(s.mesh, PartitionSpec())
    return jax.lax.with_sharding_constraint(x, s)


def _on(leaf, devices: set) -> bool:
    placed = getattr(leaf, "sharding", None)
    return placed is not None and placed.device_set == devices


def unplaced(tree, mesh: Mesh | None) -> int:
    """How many leaves of ``tree`` are not laid out over exactly ``mesh``'s
    devices: each costs a dispatch over the mesh a placement of its own,
    every time.  0 without a mesh."""
    if mesh is None:
        return 0
    devices = set(mesh.devices.flat)
    return sum(not _on(leaf, devices) for leaf in jax.tree.leaves(tree))


def replicate(tree):
    """Commit every leaf of ``tree`` whole to every device of the active mesh
    (``PartitionSpec()``), once.  A mesh program is compiled for the layout
    its committed arguments have, so every dispatch finds such a leaf where
    the executable wants it and moves nothing; an uncommitted one (whatever
    ``jnp.asarray`` made, on device 0) is laid out over the mesh anew by
    every dispatch that takes it.  A leaf already laid out over the mesh's
    devices, whatever its spec, is kept.  Without an active mesh the argument
    comes back as it is (the same objects), as from ``constrain`` and
    ``device_put``."""
    mesh = active_mesh()
    if mesh is None:
        return tree
    devices = set(mesh.devices.flat)
    whole = NamedSharding(mesh, PartitionSpec())
    return jax.tree.map(
        lambda leaf: leaf if _on(leaf, devices) else jax.device_put(leaf, whole), tree
    )
