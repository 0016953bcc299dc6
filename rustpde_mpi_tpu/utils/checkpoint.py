"""HDF5 checkpoint/restart with the reference's snapshot layout.

Rebuild of /root/reference/src/navier_stokes/navier_io.rs + src/field/io.rs +
src/io/read_write_hdf5.rs:

* per-variable groups ``{var}/{x,dx,y,dy,v,vhat}`` with variables named
  ``ux, uy, temp, pres`` (+ ``tempbc``); complex spectral data stored as
  ``vhat_re``/``vhat_im`` dataset pairs
  (/root/reference/src/io/read_write_hdf5.rs:171-188),
* scalars ``time`` + physics params at the file root,
* restart restores spectral coefficients, supporting **resolution change via
  spectral truncation/zero-padding** with r2c Nyquist-mode bookkeeping (no
  Fourier renormalization — see :func:`interpolate_2d`; the reference's
  (new-1)/(old-1) factor compensates its unnormalized rustfft convention,
  /root/reference/src/field/io.rs:151-176).

One deliberate fix over the reference: the reference writes the coordinate
array into both the ``x`` and ``dx`` datasets (field/io.rs:96-99); here ``dx``
holds the actual grid deltas.  Readers that only consume ``x``/``y``/``v``
(the plot/ scripts, xmf generator) see identical layout.

Durability (utils/resilience.py rides on these guarantees):

* every snapshot writer is **atomic**: the file is written to
  ``<name>.<pid>.tmp``, flushed + fsynced, then ``os.replace``d over the
  target — a crash/preemption mid-write can never truncate a previously
  valid checkpoint,
* files are stamped with root attrs ``digest`` (sha256 over every dataset's
  path/shape/dtype/bytes), ``schema``, ``step`` and ``time``; readers verify
  the digest before restoring state,
* malformed/truncated files surface as :class:`CheckpointError` naming the
  file and the missing group/dataset (instead of a bare ``KeyError`` /
  h5py ``OSError``), which is what :func:`latest_checkpoint`'s
  skip-corrupt-files logic catches,
* :func:`rotate_checkpoints` keeps a rolling retention window (and removes a
  sharded checkpoint's whole shard set with its manifest).

Distributed (multihost) checkpoints — the sharded two-phase layer:

The writers above fetch the FULL state through ``np.asarray``, which needs
every shard addressable from one process — true on single-controller meshes
but impossible on a real multi-controller pencil mesh.  The sharded layer
(the analog of the reference's rank-parallel IO pair io_mpi_sequ.rs /
io_mpi.rs) checkpoints through every process at once:

* each process serializes only its **addressable shards** to a per-host
  shard file ``<ckpt>.h5.shard<p>`` (atomic tmp+fsync+replace, per-shard
  sha256 digest computed write-side from the in-memory slabs; slab offsets
  are encoded in the dataset names so the digest covers placement),
* commit is **two-phase**: all hosts write+fsync shards, barrier
  (``sync_hosts``), digests ride one small allgather, then ROOT atomically
  writes the manifest ``<ckpt>.h5`` — global shapes/dtypes, mesh topology,
  shard->file map with digests, step/time/dt root attrs.  **Manifest
  presence IS the commit marker**: a crash or single-host kill anywhere in
  the sequence leaves the previous checkpoint fully valid (the shard files
  of the aborted attempt are orphans the rotation sweep collects),
* :func:`verify_snapshot` / :func:`latest_checkpoint` validate manifests
  end-to-end — any missing/corrupt shard rejects the WHOLE checkpoint and
  resume falls back to the previous one,
* restore is **topology-elastic** (:func:`read_sharded_snapshot`): a
  checkpoint written under any mesh/host count restores onto a different
  mesh shape, host count or a plain serial model — each host assembles only
  the slabs its own devices need (``jax.make_array_from_single_device_arrays``)
  and the restored state is bit-equal to the writer's.  Shard files store
  the raw device-layout state (exact dtypes, complex split into _re/_im),
  so the roundtrip is exact; resolution change stays with the gathered
  writers (:func:`write_snapshot`), which remain the plot/export format.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from contextlib import ExitStack, contextmanager

import numpy as np

from ..bases import BaseKind, Space2

from ..config import env_get
from . import fsutil
from .fsutil import fsync_dir
from ..field import grid_deltas

_VARS = (("ux", "velx"), ("uy", "vely"), ("temp", "temp"), ("pres", "pres"))

#: bump when the on-disk layout changes incompatibly; readers accept files
#: without the attr (pre-resilience snapshots) unchanged
SCHEMA_VERSION = 1

_CKPT_PREFIX = "ckpt_"
_CKPT_SUFFIX = ".h5"


class CheckpointError(RuntimeError):
    """A checkpoint file is malformed, truncated or corrupt.

    Carries the offending ``filename`` and a cause message naming the missing
    group/dataset or the failed integrity check, so restart logic
    (utils/resilience.latest_checkpoint skip path, Navier2D.read_unwrap) has
    one typed error to catch instead of bare ``KeyError``/``OSError``.
    """

    def __init__(self, filename: str, message: str):
        super().__init__(f"{filename}: {message}")
        self.filename = filename


def _digest_update(digest, name: str, data: np.ndarray) -> None:
    digest.update(name.encode("utf-8") + b"\0")
    digest.update(str(data.dtype).encode() + b"\0")
    digest.update(str(data.shape).encode() + b"\0")
    digest.update(data.tobytes())


def content_digest(h5) -> str:
    """sha256 over every dataset (path + shape + dtype + raw bytes, visited
    in sorted path order).  Root *attrs* are deliberately excluded so the
    digest can be stored as one."""
    import h5py

    paths: list[str] = []

    def visit(name, obj):
        if isinstance(obj, h5py.Dataset):
            paths.append(name)

    h5.visititems(visit)
    digest = hashlib.sha256()
    for name in sorted(paths):
        _digest_update(digest, name, np.ascontiguousarray(h5[name][()]))
    return digest.hexdigest()


def _attrs_of(h5) -> dict:
    return {
        key: (val.decode() if isinstance(val, bytes) else val)
        for key, val in h5.attrs.items()
    }


def _verify_open_file(h5, filename: str) -> dict:
    """Digest-check an open file; returns its root attrs (digest-less files
    — pre-resilience snapshots — pass through unverified)."""
    attrs = _attrs_of(h5)
    stored = attrs.get("digest")
    if stored is not None and content_digest(h5) != stored:
        raise CheckpointError(
            filename,
            "content digest mismatch (bit rot or a partially copied file)",
        )
    return attrs


@contextmanager
def _open_checkpoint(filename: str):
    """Open a snapshot for reading with the error contract every reader
    shares: h5py's bare ``OSError`` (truncated/partial/not-HDF5) and any
    unhandled ``KeyError`` (missing root dataset) surface as
    :class:`CheckpointError` naming the file."""
    import h5py

    try:
        with h5py.File(filename, "r") as h5:
            yield h5
    except CheckpointError:
        raise
    except KeyError as exc:
        raise CheckpointError(
            filename, f"missing root dataset {exc.args[0]!r}"
        ) from exc
    except OSError as exc:
        raise CheckpointError(
            filename,
            f"unreadable HDF5 file (likely a truncated/partial write): {exc}",
        ) from exc


def read_attrs(filename: str) -> dict:
    """Root attrs of a snapshot WITHOUT the digest pass (cheap metadata
    lookup for files something else already verified — resume/rollback use
    this after :func:`latest_checkpoint` has digest-checked the file)."""
    with _open_checkpoint(filename) as h5:
        return _attrs_of(h5)


def verify_snapshot(filename: str) -> dict:
    """Open + digest-verify a snapshot; returns its root attrs.

    For a sharded-checkpoint MANIFEST the verification is end-to-end: the
    manifest's own digest first, then every shard file in its shard map —
    existence, readability, and content digest against both the manifest's
    recorded value and the shard's own stamp.  ANY missing/corrupt shard
    rejects the whole checkpoint (``latest_checkpoint`` then falls back).

    Raises :class:`CheckpointError` when the file is unreadable (truncated
    write, not HDF5) or its content hash does not match the stored digest."""
    with _open_checkpoint(filename) as h5:
        attrs = _verify_open_file(h5, filename)
        meta = _read_manifest_meta(h5, filename) if attrs.get("sharded") else None
    if meta is not None:
        _verify_shard_set(filename, meta)
    return attrs


def read_root_data(filename: str) -> dict:
    """Root-level (replicated, digest-covered) datasets of a snapshot as
    numpy arrays, WITHOUT assembling any state — the cheap metadata peek
    the serve scheduler uses to learn a checkpoint's slot geometry
    (``members``, ``serve_slots``) before deciding how to size the fleet.
    For a sharded manifest these are the manifest-root datasets; for a
    gathered snapshot, the root datasets next to the state groups."""
    out: dict[str, np.ndarray] = {}
    with _open_checkpoint(filename) as h5:
        for name, obj in h5.items():
            if name != _MANIFEST_DS and hasattr(obj, "shape"):
                out[name] = np.asarray(obj)
    return out


@dataclasses.dataclass
class HostSnapshot:
    """A snapshot fully fetched to host memory, not yet on disk.

    ``datasets`` is an ordered list of ``(h5path, array, kind)`` where
    ``kind`` is ``"field"`` (written through :func:`_write_array`: float64
    cast, complex split into ``_re``/``_im``) or ``"raw"`` (stored with the
    array's exact dtype — counters, masks, scalars).  The object is
    device-free: building one (:func:`snapshot_to_host` /
    :func:`ensemble_snapshot_to_host`) is the only part of a checkpoint
    that needs the model, so serialization + digest + fsync can run on a
    background thread (utils/io_pipeline.AsyncCheckpointWriter) while the
    device steps the next chunk."""

    datasets: list
    step: int | None = None
    time: float | None = None
    dt: float | None = None

    @property
    def nbytes(self) -> int:
        return sum(int(np.asarray(d).nbytes) for _, d, _ in self.datasets)


def _stored_arrays(path: str, data, kind: str):
    """The ``(name, array)`` pairs exactly as the writers lay them down on
    disk — the complex split and float64 cast :func:`_write_array` applies
    for ``"field"`` entries, the identity for ``"raw"`` ones."""
    if kind != "field":
        return [(path, np.ascontiguousarray(data))]
    if np.iscomplexobj(data):
        return [
            (
                f"{path}_re",
                np.asarray(np.ascontiguousarray(data.real), dtype=np.float64),
            ),
            (
                f"{path}_im",
                np.asarray(np.ascontiguousarray(data.imag), dtype=np.float64),
            ),
        ]
    return [(path, np.asarray(data, dtype=np.float64))]


def snapshot_digest(datasets) -> str:
    """The :func:`content_digest` a file holding ``datasets`` will have,
    computed from the in-memory arrays — so the write path never re-reads
    the file it just wrote (for multi-GB snapshots the read-back pass
    doubled checkpoint IO).  Byte-for-byte the same hash: the stored forms
    (:func:`_stored_arrays`) are hashed in the same sorted-path order
    ``content_digest`` visits, and a roundtrip is CI-asserted
    (tests/test_io_pipeline.py)."""
    expanded = []
    for path, data, kind in datasets:
        expanded.extend(_stored_arrays(path, data, kind))
    digest = hashlib.sha256()
    for name, arr in sorted(expanded, key=lambda kv: kv[0]):
        _digest_update(digest, name, np.ascontiguousarray(arr))
    return digest.hexdigest()


def _atomic_h5_write(
    filename: str,
    body,
    step: int | None = None,
    time: float | None = None,
    dt: float | None = None,
    digest_items=None,
    digest: str | None = None,
) -> None:
    """Write an HDF5 file atomically: ``body(h5)`` fills a ``.tmp`` sibling,
    root attrs (schema/step/time + content digest) are stamped, the file is
    flushed + fsynced, then ``os.replace``d over the target (and the
    directory fsynced) — no code path can leave a truncated file where a
    previously valid checkpoint existed.

    ``digest_items`` (a :class:`HostSnapshot` ``datasets`` list) lets the
    digest be computed from the in-memory arrays instead of re-reading
    every dataset back out of the file just written; ``digest`` accepts an
    already-computed value (the sharded writer hashes its slabs once and
    reuses the hash for the manifest's shard map)."""
    import h5py

    dirname = os.path.dirname(filename) or "."
    os.makedirs(dirname, exist_ok=True)
    tmp = f"{filename}.{os.getpid()}.tmp"
    try:
        with h5py.File(tmp, "w") as h5:
            body(h5)
            h5.attrs["schema"] = SCHEMA_VERSION
            if step is not None:
                h5.attrs["step"] = int(step)
            if time is not None:
                h5.attrs["time"] = float(time)
            if dt is not None:
                # the step size the run was using — resume restores it so a
                # backed-off dt survives preemption (utils/resilience.py)
                h5.attrs["dt"] = float(dt)
            if digest is None:
                digest = (
                    snapshot_digest(digest_items)
                    if digest_items is not None
                    else content_digest(h5)
                )
            h5.attrs["digest"] = digest
            h5.flush()
        fd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, filename)
        # strict: a failed dirsync must fail the write (the two-phase
        # commit would otherwise report a checkpoint committed whose
        # dirent can roll back across power loss)
        fsync_dir(dirname, strict=True)
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def checkpoint_path(run_dir: str, step: int) -> str:
    """Canonical rolling-checkpoint name: ``<run_dir>/ckpt_<step:010d>.h5``
    (name-sortable by step)."""
    return os.path.join(run_dir, f"{_CKPT_PREFIX}{int(step):010d}{_CKPT_SUFFIX}")


def checkpoint_files(run_dir: str) -> list[str]:
    """All rolling checkpoints in ``run_dir``, oldest first (by step-encoded
    name); ``.tmp`` leftovers from interrupted writes are excluded."""
    try:
        names = os.listdir(run_dir)
    except OSError:
        return []
    return [
        os.path.join(run_dir, n)
        for n in sorted(names)
        if n.startswith(_CKPT_PREFIX) and n.endswith(_CKPT_SUFFIX)
    ]


def latest_checkpoint(run_dir: str) -> str | None:
    """Newest checkpoint in ``run_dir`` that passes digest verification.

    Corrupt/partial files (a crash mid-copy, bit rot) are skipped with a
    warning — resume logic falls back to the previous valid checkpoint
    instead of dying on the newest file."""
    for path in reversed(checkpoint_files(run_dir)):
        try:
            verify_snapshot(path)
        except CheckpointError as exc:
            print(f"skipping corrupt checkpoint: {exc}")
            continue
        return path
    return None


def shard_path(manifest: str, index: int) -> str:
    """Per-host shard file of a sharded checkpoint: ``<manifest>.shard<p>``.
    The suffix keeps shards out of :func:`checkpoint_files`' ``.h5`` listing
    — only the manifest (the commit marker) is ever a resume candidate."""
    return f"{manifest}.shard{int(index)}"


def checkpoint_shard_files(manifest: str) -> list[str]:
    """Every shard file belonging to ``manifest`` (committed or orphaned)."""
    dirname = os.path.dirname(manifest) or "."
    base = os.path.basename(manifest) + ".shard"
    try:
        names = os.listdir(dirname)
    except OSError:
        return []
    return [os.path.join(dirname, n) for n in sorted(names) if n.startswith(base)]


def remove_checkpoint(manifest: str) -> None:
    """Remove one checkpoint atomically with respect to validity: the
    MANIFEST goes first (after which the checkpoint is uncommitted — a crash
    mid-removal can only leave harmless orphan shards, never a manifest
    pointing at deleted shards), then the shard set."""
    for path in [manifest, *checkpoint_shard_files(manifest)]:
        try:
            os.remove(path)
        except OSError:
            pass


def rotate_checkpoints(run_dir: str, keep: int) -> list[str]:
    """Prune the rolling window to the newest ``keep`` checkpoints; returns
    the removed manifest paths.  ``keep <= 0`` disables retention.

    Sharded checkpoints are removed as a unit (:func:`remove_checkpoint`:
    manifest first, then shards), and ORPHAN shard sets — shard files whose
    manifest never landed, i.e. a two-phase commit that died between shard
    fsync and manifest write, or a corrupt manifest a previous rotation
    removed — are swept once their step falls below the retention window
    (orphans at or above the oldest kept step may be an in-flight write on
    a peer host and are left alone)."""
    removed = []
    if keep <= 0:
        return removed
    files = checkpoint_files(run_dir)
    for path in files[:-keep] if len(files) > keep else []:
        remove_checkpoint(path)
        removed.append(path)
    kept = checkpoint_files(run_dir)
    if kept:
        oldest_kept = os.path.basename(kept[0])
        try:
            names = os.listdir(run_dir)
        except OSError:
            names = []
        for name in names:
            stem, sep, _ = name.partition(_CKPT_SUFFIX + ".shard")
            if not sep:
                continue
            manifest = stem + _CKPT_SUFFIX
            if manifest < oldest_kept and manifest not in names:
                try:
                    os.remove(os.path.join(run_dir, name))
                except OSError:
                    pass
    return removed


def _write_array(group, name: str, data: np.ndarray) -> None:
    if np.iscomplexobj(data):
        _write_array(group, f"{name}_re", np.ascontiguousarray(data.real))
        _write_array(group, f"{name}_im", np.ascontiguousarray(data.imag))
        return
    if name in group:
        del group[name]
    group.create_dataset(name, data=np.asarray(data, dtype=np.float64))


def _missing(group, name: str) -> CheckpointError:
    filename = getattr(getattr(group, "file", None), "filename", "<h5>")
    where = f"{group.name.rstrip('/')}/{name}"
    return CheckpointError(
        filename,
        f"missing group/dataset {where!r} — truncated write or a file that "
        "is not a snapshot in this layout",
    )


def _read_array(group, name: str, is_complex: bool) -> np.ndarray:
    try:
        if is_complex:
            return np.asarray(group[f"{name}_re"]) + 1j * np.asarray(
                group[f"{name}_im"]
            )
        return np.asarray(group[name])
    except KeyError as exc:
        raise _missing(group, f"{name}_re/_im" if is_complex else name) from exc


def interpolate_2d(
    old: np.ndarray,
    new_shape: tuple[int, int],
    kind_x: BaseKind,
    old_nx: int | None = None,
    new_nx: int | None = None,
) -> np.ndarray:
    """Spectral interpolation on resolution change: truncate / zero-pad the
    coefficient array (/root/reference/src/field/io.rs:151-176).

    Unlike the reference, no global Fourier renormalization is applied: the
    reference's rustfft forward is unnormalized (coefficients scale with n),
    while this repo's r2c forward is amplitude-normalized (rfft/n), so
    coefficients are grid-size independent.  What the r2c axis does need is
    the Nyquist-mode bookkeeping (``old_nx``/``new_nx`` are the physical grid
    sizes): an even-grid Nyquist coefficient represents cos(Nx) counted once,
    so when it becomes a regular +k mode of the new grid it must be halved,
    and when a regular +k/-k pair lands on the new grid's Nyquist it folds to
    double the real part.  This covers resolution changes that keep the
    spectral shape but flip grid parity (e.g. nx 16 -> 17)."""
    new = np.zeros(new_shape, dtype=old.dtype)
    s0 = min(old.shape[0], new_shape[0])
    s1 = min(old.shape[1], new_shape[1])
    new[:s0, :s1] = old[:s0, :s1]
    if kind_x == BaseKind.FOURIER_R2C:
        if old_nx is None:
            import warnings

            warnings.warn(
                "r2c restart interpolation without the source grid size "
                "(missing 'x' dataset): assuming an even source grid for "
                "Nyquist-mode bookkeeping",
                stacklevel=2,
            )
            old_nx = 2 * (old.shape[0] - 1)
        old_nyq = old.shape[0] - 1 if old_nx % 2 == 0 else None
        new_nyq = (
            new_shape[0] - 1 if new_nx is not None and new_nx % 2 == 0 else None
        )
        if old_nyq is not None and old_nyq < s0 and old_nyq != new_nyq:
            new[old_nyq, :] *= 0.5  # old Nyquist -> regular +k mode
        if new_nyq is not None and new_nyq < s0 and new_nyq != old_nyq:
            new[new_nyq, :] = 2.0 * new[new_nyq, :].real  # +-k fold onto Nyquist
    return new


def write_field(h5, varname: str, space: Space2, vhat, x, dx) -> None:
    """Write one field group in the reference layout.  Split-Fourier spaces
    store their coefficients in the complex convention (vhat_re/vhat_im), so
    files are layout-identical across backends."""
    grp = h5.require_group(varname)
    _write_array(grp, "x", x[0])
    _write_array(grp, "dx", dx[0])
    _write_array(grp, "y", x[1])
    _write_array(grp, "dy", dx[1])
    _write_array(grp, "v", np.asarray(space.backward(vhat)))
    _write_array(grp, "vhat", space.vhat_as_complex(vhat))


def read_field_vhat(h5, varname: str, space: Space2) -> np.ndarray:
    """Read one field's spectral coefficients, interpolating on mismatch.

    Files always carry the complex convention for periodic axes; a split
    target space converts after the (complex-domain) interpolation.

    A missing group/dataset raises :class:`CheckpointError` naming the file
    and what was expected (the corrupt-checkpoint skip logic catches it)."""
    try:
        grp = h5[varname]
    except KeyError as exc:
        raise _missing(h5, varname) from exc
    split = space.bases[0].kind.is_split
    is_complex = space.spectral_is_complex or split
    data = _read_array(grp, "vhat", is_complex)
    old_nx = grp["x"].shape[0] if "x" in grp else None
    if split:
        target_shape = (space.bases[0].m_complex, space.bases[1].m)
        kind_x = BaseKind.FOURIER_R2C
    else:
        target_shape = space.shape_spectral
        kind_x = space.base_kind(0)
    # interpolate on shape mismatch, and also when the shapes agree but the
    # r2c grid parity changed (nx 16 -> 17 keeps m = 9 yet re-types the
    # Nyquist row)
    parity_flip = (
        kind_x == BaseKind.FOURIER_R2C
        and old_nx is not None
        and old_nx % 2 != space.shape_physical[0] % 2
    )
    if data.shape != target_shape or parity_flip:
        data = interpolate_2d(
            data,
            target_shape,
            kind_x,
            old_nx=old_nx,
            new_nx=space.shape_physical[0],
        )
    # vhat_from_complex is also the sep-layout boundary (Space2 stores
    # Chebyshev spectral axes parity-permuted on the TPU path), so it must
    # run for non-split spaces too — h5 files always hold natural order
    return space.vhat_from_complex(data)


def _model_coords(model):
    xs = model.x  # scaled coords the model already derived
    dxs = [
        grid_deltas(b.points, b.is_periodic) * s
        for b, s in zip(model.field_space.bases, model.scale)
    ]
    return xs, dxs


def _field_host_datasets(path: str, space, vhat, v_phys, x, dx) -> list:
    """Host dataset list for one variable group — exactly the layout
    :func:`write_field` lays down (``v_phys`` is the already-dispatched
    physical field; ``vhat_as_complex`` fetches the coefficients)."""
    return [
        (f"{path}/x", np.asarray(x[0]), "field"),
        (f"{path}/dx", np.asarray(dx[0]), "field"),
        (f"{path}/y", np.asarray(x[1]), "field"),
        (f"{path}/dy", np.asarray(dx[1]), "field"),
        (f"{path}/v", np.asarray(v_phys), "field"),
        (f"{path}/vhat", space.vhat_as_complex(vhat), "field"),
    ]


def snapshot_to_host(model, step: int | None = None) -> HostSnapshot:
    """Fetch a flow snapshot into host memory WITHOUT touching disk.

    The one device sync a checkpoint inherently needs: every backward
    transform is dispatched first (the device pipelines them), then the
    results are fetched.  The returned :class:`HostSnapshot` feeds
    :func:`write_host_snapshot` — synchronously (:func:`write_snapshot`) or
    on the io_pipeline worker, off the dispatch critical path."""
    xs, dxs = _model_coords(model)
    datasets: list = []
    model_vars = getattr(model, "snapshot_vars", _VARS)
    with model._scope():
        phys = {
            attr: getattr(model, f"{attr}_space").backward(
                getattr(model.state, attr)
            )
            for _, attr in model_vars
        }
        tempbc = getattr(model, "tempbc_ortho", None)
        phys_bc = model.field_space.backward(tempbc) if tempbc is not None else None
        for varname, attr in model_vars:
            space = getattr(model, f"{attr}_space")
            datasets += _field_host_datasets(
                varname, space, getattr(model.state, attr), phys[attr], xs, dxs
            )
        if tempbc is not None:
            datasets += _field_host_datasets(
                "tempbc", model.field_space, tempbc, phys_bc, xs, dxs
            )
    datasets.append(("time", np.asarray(float(model.time), dtype=np.float64), "raw"))
    for key, value in model.params.items():
        datasets.append((key, np.asarray(float(value), dtype=np.float64), "raw"))
    # armed in-scan stats (models/stats.py): running sums + sample tick as
    # exact-dtype raw datasets, so a resume restores the averages bit-equal
    stats_items = getattr(model, "stats_host_items", None)
    if stats_items is not None:
        with model._scope():
            datasets.extend(stats_items())
    return HostSnapshot(
        datasets=datasets, step=step, time=float(model.time), dt=float(model.dt)
    )


def ensemble_snapshot_to_host(ens, step: int | None = None) -> HostSnapshot:
    """Ensemble analogue of :func:`snapshot_to_host`: per-member groups plus
    the root-level bookkeeping (``time``/``members``/``alive``/
    ``steps_done``/params), all fetched to host in one pass."""
    model = ens.model
    xs, dxs = _model_coords(model)
    datasets: list = []
    model_vars = getattr(model, "snapshot_vars", _VARS)
    with model._scope():
        phys = {
            attr: [
                getattr(model, f"{attr}_space").backward(
                    getattr(ens.state, attr)[i]
                )
                for i in range(ens.k)
            ]
            for _, attr in model_vars
        }
        tempbc = getattr(model, "tempbc_ortho", None)
        phys_bc = model.field_space.backward(tempbc) if tempbc is not None else None
        for i in range(ens.k):
            for varname, attr in model_vars:
                space = getattr(model, f"{attr}_space")
                datasets += _field_host_datasets(
                    f"member{i}/{varname}",
                    space,
                    getattr(ens.state, attr)[i],
                    phys[attr][i],
                    xs,
                    dxs,
                )
        if tempbc is not None:
            datasets += _field_host_datasets(
                "tempbc", model.field_space, tempbc, phys_bc, xs, dxs
            )
        alive = np.asarray(ens.mask).astype(np.int8)
        steps_done = np.asarray(ens.steps_done, dtype=np.int64)
    datasets.append(("time", np.asarray(float(ens.time), dtype=np.float64), "raw"))
    datasets.append(("members", np.asarray(int(ens.k), dtype=np.int64), "raw"))
    datasets.append(("alive", alive, "raw"))
    datasets.append(("steps_done", steps_done, "raw"))
    for key, value in model.params.items():
        datasets.append((key, np.asarray(float(value), dtype=np.float64), "raw"))
    stats_items = getattr(ens, "stats_host_items", None)
    if stats_items is not None:
        with model._scope():
            datasets.extend(stats_items())
    return HostSnapshot(
        datasets=datasets, step=step, time=float(ens.time), dt=float(ens.dt)
    )


def write_host_snapshot(snap: HostSnapshot, filename: str) -> None:
    """Serialize a :class:`HostSnapshot`: atomic, digest-stamped (from the
    in-memory arrays — no read-back pass), layout-identical to the legacy
    in-place writers.  Pure host-side work — safe on a background thread."""

    def body(h5):
        for path, data, kind in snap.datasets:
            gpath, _, name = path.rpartition("/")
            grp = h5.require_group(gpath) if gpath else h5
            if kind == "field":
                _write_array(grp, name, data)
            else:
                if name in grp:
                    del grp[name]
                grp.create_dataset(name, data=data)

    _atomic_h5_write(
        filename,
        body,
        step=snap.step,
        time=snap.time,
        dt=snap.dt,
        digest_items=snap.datasets,
    )


def write_snapshot(model, filename: str, step: int | None = None) -> None:
    """Write a flow snapshot (/root/reference/src/navier_stokes/navier_io.rs:44-62).

    Atomic (tmp + fsync + ``os.replace``) and digest-stamped; ``step`` is an
    optional run-step counter recorded as a root attr for resume logic.
    Implemented as fetch-then-serialize (:func:`snapshot_to_host` +
    :func:`write_host_snapshot`) so the synchronous and background-writer
    paths are ONE code path producing bit-identical files."""
    write_host_snapshot(snapshot_to_host(model, step=step), filename)


def write_ensemble_snapshot(ens, filename: str, step: int | None = None) -> None:
    """Write a K-member ensemble snapshot: groups ``member{i}`` each holding
    the reference single-run variable layout (:func:`write_field`), plus
    root-level ensemble bookkeeping — ``time``, ``members``, per-member
    ``alive`` mask and ``steps_done`` counters, physics params, and the
    shared ``tempbc`` lift field (written once, members share it).  Atomic
    and digest-stamped like :func:`write_snapshot`."""
    write_host_snapshot(ensemble_snapshot_to_host(ens, step=step), filename)


def read_ensemble_snapshot(ens, filename: str) -> None:
    """Restore an ensemble snapshot written by :func:`write_ensemble_snapshot`.

    Member count may differ from the target ensemble's — the state, mask and
    counters are rebuilt at the file's K.  Each member goes through
    :func:`read_field_vhat`, so per-member resolution interpolation works
    exactly like the single-run restart path.  ``pseu`` (the pressure
    increment, not stored — reference layout) restarts at zero.  A sharded
    manifest dispatches to :func:`read_sharded_snapshot` (same-K, exact)."""
    import jax
    import jax.numpy as jnp

    if is_sharded_checkpoint(filename):
        read_sharded_snapshot(ens, filename)
        return
    model = ens.model
    model_vars = getattr(model, "snapshot_vars", _VARS)
    state_cls = type(model.state)
    with _open_checkpoint(filename) as h5:
        _verify_open_file(h5, filename)
        k = int(np.asarray(h5["members"]))
        members = []
        for i in range(k):
            try:
                grp = h5[f"member{i}"]
            except KeyError as exc:
                raise _missing(h5, f"member{i}") from exc
            updates = {}
            for varname, attr in model_vars:
                space = getattr(model, f"{attr}_space")
                vhat = read_field_vhat(grp, varname, space)
                updates[attr] = jnp.asarray(vhat, dtype=space.spectral_dtype())
            for name in state_cls._fields:
                # leaves the gathered layout does not carry (``pseu``, the
                # reference layout; auxiliary campaign leaves) restart via
                # the model's fill rule (default zero) — the gathered format
                # is restart-equivalent, the sharded manifest is bit-exact
                if name not in updates:
                    like = getattr(model.state, name)
                    fill = getattr(model, "restart_fill", None)
                    updates[name] = (
                        fill(name, like) if fill else jnp.zeros_like(like)
                    )
            members.append(state_cls(**updates))
        with model._scope():
            ens.state = jax.tree.map(lambda *xs: jnp.stack(xs), *members)
            ens.k = k
            ens.mask = jnp.asarray(np.asarray(h5["alive"], dtype=bool))
            ens.steps_done = jnp.asarray(
                np.asarray(h5["steps_done"]), dtype=jnp.int32
            )
        ens.time = float(np.asarray(h5["time"]))
        _restore_stats(ens, h5)
    ens._obs_cache = None
    print(f" <== {filename} ({k} members)")


def _read_stats_group(h5) -> dict | None:
    """The ``stats_state/`` raw datasets of a gathered snapshot (None when
    the file predates the stats engine — restores then reset the averaging
    window instead of failing)."""
    if "stats_state" not in h5:
        return None
    grp = h5["stats_state"]
    return {name: np.asarray(grp[name]) for name in grp}


def _restore_stats(pde, h5) -> None:
    """Install a gathered snapshot's stats leaves on a stats-armed model/
    ensemble (no-op otherwise)."""
    if not getattr(pde, "stats_armed", False):
        return
    pde.apply_restored_stats(_read_stats_group(h5))


def read_snapshot(model, filename: str) -> None:
    """Restore a flow snapshot: spectral coefficients + time
    (/root/reference/src/navier_stokes/navier_io.rs:21-29).  Digest-verified
    when the file carries one; malformed files raise
    :class:`CheckpointError`.  A sharded-checkpoint manifest dispatches to
    the topology-elastic :func:`read_sharded_snapshot`."""
    import jax.numpy as jnp

    if is_sharded_checkpoint(filename):
        read_sharded_snapshot(model, filename)
        return
    base_vars = {attr for _, attr in _VARS}
    with _open_checkpoint(filename) as h5:
        _verify_open_file(h5, filename)
        updates = {}
        for varname, attr in getattr(model, "snapshot_vars", _VARS):
            space = getattr(model, f"{attr}_space")
            if varname not in h5 and attr not in base_vars:
                # scenario-extended leaf absent from an older snapshot:
                # restart it via the model's fill rule (the write side
                # stores it — snapshot_to_host uses the same var list)
                fill = getattr(model, "restart_fill", None)
                like = getattr(model.state, attr)
                updates[attr] = (
                    fill(attr, like) if fill else jnp.zeros_like(like)
                )
                continue
            vhat = read_field_vhat(h5, varname, space)
            updates[attr] = jnp.asarray(vhat, dtype=space.spectral_dtype())
        model.state = model.state._replace(**updates)
        model.time = float(np.asarray(h5["time"]))
        _restore_stats(model, h5)
    print(f" <== {filename}")


# ---------------------------------------------------------------------------
# sharded two-phase checkpoints (multihost-grade durability)
# ---------------------------------------------------------------------------

#: root dataset holding the manifest's JSON metadata (dataset, not attr, so
#: the manifest's own content digest covers it)
_MANIFEST_DS = "sharded_manifest"


def is_sharded_checkpoint(filename: str) -> bool:
    """True when ``filename`` is a sharded-checkpoint manifest (cheap attr
    sniff, no digest pass)."""
    try:
        return bool(read_attrs(filename).get("sharded"))
    except CheckpointError:
        return False


def _process_index() -> int:
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


def _process_count() -> int:
    try:
        import jax

        return int(jax.process_count())
    except Exception:
        return 1


def _shard_crash_hook(point: str, step) -> None:
    """Deterministic crash injection inside the two-phase commit window
    (tests/test_multiprocess.py proves single-host-death recovery with it).

    ``RUSTPDE_SHARD_CRASH=<point>@<step>[:host<p>]`` hard-kills
    (``os._exit(9)``) the matching process when the writer reaches
    ``point`` for the checkpoint at ``step``:

    * ``after_shard``     — the host's shard file is fsynced and in place,
      the barrier/manifest commit has NOT run: the canonical "host dies
      between shard fsync and manifest commit" window,
    * ``before_manifest`` — root passed the barrier + digest exchange but
      has not written the manifest: the commit marker is missing even
      though EVERY shard landed.

    Parsing is STRICT (utils/faults.parse_shard_crash_spec): a malformed
    spec raises a typed FaultSpecError rather than silently never firing —
    a chaos test that isn't injecting is worse than none.  The harness
    constructors validate the env at startup too (faults.validate_fault_env),
    so the raise normally lands before any stepping."""
    from .faults import parse_shard_crash_spec

    plan = parse_shard_crash_spec(env_get("RUSTPDE_SHARD_CRASH"))
    if plan is None or step is None:
        return
    want, at, host = plan
    if want != point or at != int(step):
        return
    if host is not None and _process_index() != host:
        return
    os._exit(9)


def _normalize_index(idx, shape) -> tuple:
    """A shard's ``index`` (tuple of slices) as ``((start, stop), ...)``."""
    out = []
    for sl, n in zip(idx, shape):
        start, stop, _ = sl.indices(n)
        out.append((int(start), int(stop)))
    return tuple(out)


def _owned_slabs(arr, proc: int) -> list:
    """The slabs of ``arr`` THIS process must serialize: each distinct shard
    index is owned by the lowest-id device holding it (so replicated or
    partially-replicated arrays are written exactly once across the whole
    job), and this process writes the slabs whose owner is local.  Returns
    ``[(offset_tuple, numpy_slab), ...]`` (device->host fetch happens
    here)."""
    import jax

    if not isinstance(arr, jax.Array):
        data = np.asarray(arr)
        return [((0,) * data.ndim, data)] if proc == 0 else []
    try:
        imap = arr.sharding.devices_indices_map(arr.shape)
    except Exception:
        # no global placement metadata (single-device array): process 0 owns
        return [((0,) * arr.ndim, np.asarray(arr))] if proc == 0 else []
    owners: dict[tuple, object] = {}
    for dev, idx in imap.items():
        key = _normalize_index(idx, arr.shape)
        prev = owners.get(key)
        if prev is None or dev.id < prev.id:
            owners[key] = dev
    local = {
        _normalize_index(s.index, arr.shape): s.data
        for s in arr.addressable_shards
    }
    slabs = []
    for key, dev in sorted(owners.items()):
        if dev.process_index != proc:
            continue
        offset = tuple(start for start, _ in key)
        slabs.append((offset, np.ascontiguousarray(np.asarray(local[key]))))
    return slabs


def _storage_names(name: str, dtype) -> list[str]:
    """On-disk dataset names for one logical array: complex data splits into
    ``_re``/``_im`` float pairs (the repo-wide HDF5 convention), real data
    keeps its exact dtype under its own name."""
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        return [f"{name}_re", f"{name}_im"]
    return [name]


def _slab_ds_name(storage: str, offset: tuple) -> str:
    """Slab dataset path inside a shard file.  The offset is encoded in the
    NAME so the shard's content digest covers placement, not just bytes."""
    return f"{storage}/slab_" + "_".join(str(int(o)) for o in offset)


def _slab_offset_of(dsname: str) -> tuple | None:
    base = dsname.rsplit("/", 1)[-1]
    if not base.startswith("slab_"):
        return None
    try:
        return tuple(int(p) for p in base[len("slab_"):].split("_"))
    except ValueError:
        return None


@dataclasses.dataclass
class ShardSnapshot:
    """One process's share of a sharded checkpoint, fully fetched to host.

    ``slabs`` is ``[(storage_path, offset, numpy_array), ...]`` — only this
    host's owned slabs; ``root_datasets`` is the replicated manifest-side
    data (time, params, ensemble bookkeeping — HostSnapshot-style tuples);
    ``meta`` carries the global dataset catalog + mesh topology the root
    embeds in the manifest.  Like :class:`HostSnapshot`, the object is
    device-free: :func:`write_shard_file` (serialize + digest + fsync) can
    run on a background worker while the device steps on — the multihost
    re-enable of the PR-4 overlapped write path."""

    shard_index: int
    shard_count: int
    slabs: list
    root_datasets: list
    meta: dict
    step: int | None = None
    time: float | None = None
    dt: float | None = None
    digest: str | None = None  # set once the shard file is on disk

    @property
    def nbytes(self) -> int:
        return sum(int(arr.nbytes) for _, _, arr in self.slabs)


def sharded_snapshot_to_host(pde, step: int | None = None) -> ShardSnapshot:
    """Fetch THIS process's shard of a model/ensemble snapshot to host
    memory (the one device sync a checkpoint needs — only addressable
    shards move, never the global state).  Collective-free: every process
    calls it independently."""
    proc = _process_index()
    datasets_meta: dict[str, dict] = {}
    slabs: list = []
    for name, arr in pde.snapshot_state_items():
        dtype = np.dtype(arr.dtype)
        storage = _storage_names(name, dtype)
        datasets_meta[name] = {
            "shape": [int(s) for s in arr.shape],
            "dtype": str(dtype),
            "storage": storage,
        }
        for offset, block in _owned_slabs(arr, proc):
            if len(storage) == 2:
                slabs.append((storage[0], offset, np.ascontiguousarray(block.real)))
                slabs.append((storage[1], offset, np.ascontiguousarray(block.imag)))
            else:
                slabs.append((storage[0], offset, block))
    mesh = getattr(pde, "mesh", None)
    if mesh is None and hasattr(pde, "model"):
        mesh = getattr(pde.model, "mesh", None)
    meta = {
        "datasets": datasets_meta,
        "mesh": {
            "process_count": _process_count(),
            "devices": int(np.prod(mesh.devices.shape)) if mesh is not None else 1,
            "axes": list(mesh.axis_names) if mesh is not None else [],
        },
    }
    return ShardSnapshot(
        shard_index=proc,
        shard_count=_process_count(),
        slabs=slabs,
        root_datasets=pde.snapshot_root_items(),
        meta=meta,
        step=step,
        time=float(pde.get_time()),
        dt=float(pde.get_dt()),
    )


def write_shard_file(snap: ShardSnapshot, manifest: str) -> str:
    """Phase one for one host: serialize ``snap``'s slabs to the shard file
    of ``manifest``, atomic and digest-stamped (hash computed from the
    in-memory slabs, no read-back).  Pure host-side work — safe on the
    io_pipeline worker.  Sets ``snap.digest`` and returns the shard path."""
    filename = shard_path(manifest, snap.shard_index)
    items = [
        (_slab_ds_name(storage, offset), arr, "raw")
        for storage, offset, arr in snap.slabs
    ]
    digest = snapshot_digest(items)

    def body(h5):
        for dspath, arr, _ in items:
            gpath, _, dname = dspath.rpartition("/")
            grp = h5.require_group(gpath) if gpath else h5
            grp.create_dataset(dname, data=arr)
        h5.attrs["shard_index"] = int(snap.shard_index)
        h5.attrs["shard_count"] = int(snap.shard_count)

    _atomic_h5_write(
        filename, body, step=snap.step, time=snap.time, dt=snap.dt, digest=digest
    )
    snap.digest = digest
    _shard_crash_hook("after_shard", snap.step)
    return filename


def _pack_shard_report(snap: ShardSnapshot, ok: bool) -> np.ndarray:
    """(digest, nbytes, ok) as a fixed-size uint8 row for the allgather."""
    buf = np.zeros(41, np.uint8)
    if snap.digest is not None:
        buf[:32] = np.frombuffer(bytes.fromhex(snap.digest), np.uint8)
    buf[32:40] = np.frombuffer(np.int64(snap.nbytes).tobytes(), np.uint8)
    buf[40] = 1 if (ok and snap.digest is not None) else 0
    return buf


def commit_sharded_snapshot(
    snap: ShardSnapshot, manifest: str, local_ok: bool = True
) -> dict:
    """Phase two (collective — every process must call it at the same
    point): barrier so every shard is durably on disk, exchange per-shard
    digests + byte counts + ok flags in one small allgather, then ROOT
    atomically writes the manifest — whose presence commits the checkpoint.
    A second barrier keeps any host from acting on the new checkpoint
    (rotation, resume scans) before the commit marker exists.

    Returns ``{"ok", "shards", "bytes_host", "bytes_total", "barrier_s"}``;
    ``ok=False`` (some host failed its shard write) means NO manifest was
    written and the previous checkpoint is still the newest valid one —
    the caller decides whether that is fatal."""
    import time as _time

    from ..parallel import multihost

    t0 = _time.monotonic()
    multihost.sync_hosts("rustpde-ckpt-shards")
    barrier_s = _time.monotonic() - t0
    reports = multihost.allgather_host(_pack_shard_report(snap, local_ok))
    reports = np.atleast_2d(np.asarray(reports, np.uint8))
    oks = [bool(row[40]) for row in reports]
    digests = [bytes(row[:32]).hex() for row in reports]
    nbytes = [int(np.frombuffer(bytes(row[32:40]), np.int64)[0]) for row in reports]
    # the abort decision derives ONLY from the allgathered ok flags —
    # fleet-agreed data, so every host takes the same branch into the
    # abort barrier (lint RPD001 checks exactly this property)
    ok_all = all(oks)
    stats = {
        "ok": ok_all,
        "shards": int(snap.shard_count),
        "bytes_host": int(snap.nbytes),
        "bytes_total": int(sum(nbytes)),
        "barrier_s": round(barrier_s, 3),
    }
    if not ok_all:
        multihost.sync_hosts("rustpde-ckpt-abort")
        return stats
    if _process_index() == 0:
        _shard_crash_hook("before_manifest", snap.step)
        meta = dict(snap.meta)
        meta["shards"] = [
            {
                "file": os.path.basename(shard_path(manifest, i)),
                "process": i,
                "digest": digests[i],
                "nbytes": nbytes[i],
            }
            for i in range(snap.shard_count)
        ]

        def body(h5):
            for path, data, kind in snap.root_datasets:
                gpath, _, name = path.rpartition("/")
                grp = h5.require_group(gpath) if gpath else h5
                if kind == "field":
                    _write_array(grp, name, data)
                else:
                    grp.create_dataset(name, data=data)
            h5.create_dataset(
                _MANIFEST_DS, data=np.bytes_(json.dumps(meta, sort_keys=True))
            )
            h5.attrs["sharded"] = int(snap.shard_count)

        _atomic_h5_write(manifest, body, step=snap.step, time=snap.time, dt=snap.dt)
    multihost.sync_hosts("rustpde-ckpt-commit")
    return stats


def write_sharded_snapshot(pde, filename: str, step: int | None = None) -> dict:
    """Blocking collective sharded checkpoint: fetch this host's slabs,
    write+fsync the shard file, then run the two-phase commit.  Raises
    ``CheckpointError`` on every host when ANY host's shard write failed
    (no manifest is written, so the previous checkpoint stays newest-valid).
    Returns the commit stats dict."""
    snap = sharded_snapshot_to_host(pde, step=step)
    local_error: Exception | None = None
    try:
        write_shard_file(snap, filename)
    except Exception as exc:
        local_error = exc
    stats = commit_sharded_snapshot(snap, filename, local_ok=local_error is None)
    if not stats["ok"]:
        # chain the local cause when THIS host failed; peers raise without
        # one (their shard landed — the abort came from the allgather)
        raise CheckpointError(
            filename,
            "sharded checkpoint aborted: a host failed its shard write "
            "(no manifest committed; the previous checkpoint is intact)"
            + (f"; local cause: {local_error}" if local_error else ""),
        ) from local_error
    return stats


def _read_manifest_meta(h5, filename: str) -> dict:
    try:
        raw = h5[_MANIFEST_DS][()]
    except KeyError as exc:
        raise _missing(h5, _MANIFEST_DS) from exc
    if isinstance(raw, np.ndarray):
        raw = raw.item()
    if isinstance(raw, bytes):
        raw = raw.decode("utf-8")
    try:
        return json.loads(raw)
    except ValueError as exc:
        raise CheckpointError(filename, f"unparseable manifest JSON: {exc}") from exc


def _verify_shard_set(manifest: str, meta: dict, full: bool = True) -> None:
    """Verify every shard named by ``meta`` against its recorded digest.

    ``full=False`` is the cheap cross-check (existence + the shard's own
    digest stamp against the manifest's record, no re-hash of the data) —
    used by NON-ROOT hosts at restore time so a multihost resume reads the
    checkpoint ~2x instead of (N+1)x: root's :func:`verify_snapshot` /
    ``latest_checkpoint`` scan has already re-hashed every shard end-to-end
    before the step number is broadcast."""
    dirname = os.path.dirname(manifest) or "."
    for entry in meta.get("shards", []):
        path = os.path.join(dirname, entry["file"])
        if not os.path.exists(path):
            raise CheckpointError(
                manifest,
                f"missing shard file {entry['file']!r} — the shard set is "
                "incomplete (partial copy or deleted shard)",
            )
        with _open_checkpoint(path) as sh5:
            attrs = _attrs_of(sh5)
            bad = attrs.get("digest") != entry["digest"]
            if not bad and full:
                bad = content_digest(sh5) != entry["digest"]
            if bad:
                raise CheckpointError(
                    manifest,
                    f"shard {entry['file']!r} digest mismatch (bit rot or a "
                    "partially copied shard)",
                )


class _SlabCatalog:
    """Every slab of one verified shard set, indexed by storage path, with
    the owning h5 handles kept open for region reads."""

    def __init__(self, stack: ExitStack, manifest: str, meta: dict):
        import h5py

        self.slabs: dict[str, list] = {}
        dirname = os.path.dirname(manifest) or "."
        for entry in meta.get("shards", []):
            path = os.path.join(dirname, entry["file"])
            try:
                h5 = stack.enter_context(h5py.File(path, "r"))
            except OSError as exc:
                raise CheckpointError(manifest, f"unreadable shard: {exc}") from exc

            def visit(name, obj, h5=h5):
                if not isinstance(obj, h5py.Dataset):
                    return
                offset = _slab_offset_of(name)
                if offset is None:
                    return
                storage = name.rsplit("/", 1)[0]
                self.slabs.setdefault(storage, []).append(
                    (h5, name, offset, tuple(obj.shape))
                )

            h5.visititems(visit)

    def read_region(self, manifest: str, storage: str, region, dtype):
        """Assemble the rectangular ``region`` (tuple of (start, stop)) of
        global dataset ``storage`` from whichever slabs intersect it; only
        the intersecting slab bytes are read.  Incomplete coverage raises
        :class:`CheckpointError` (a shard set from a different layout)."""
        starts = [s for s, _ in region]
        sizes = [e - s for s, e in region]
        out = np.zeros(sizes, dtype=np.dtype(dtype))
        filled = np.zeros(sizes, dtype=bool)
        for h5, dsname, offset, sshape in self.slabs.get(storage, []):
            src_sel, dst_sel = [], []
            empty = False
            for (rs, re_), so, sn in zip(region, offset, sshape):
                lo, hi = max(rs, so), min(re_, so + sn)
                if lo >= hi:
                    empty = True
                    break
                src_sel.append(slice(lo - so, hi - so))
                dst_sel.append(slice(lo - rs, hi - rs))
            if empty:
                continue
            out[tuple(dst_sel)] = h5[dsname][tuple(src_sel)]
            filled[tuple(dst_sel)] = True
        if not filled.all():
            raise CheckpointError(
                manifest,
                f"shard set does not cover dataset {storage!r} region "
                f"{[(s, s + n) for s, n in zip(starts, sizes)]}",
            )
        return out

    def read_logical(self, manifest: str, name: str, dmeta: dict, region):
        """One logical dataset's region, re/im-merged back to its dtype."""
        dtype = np.dtype(dmeta["dtype"])
        storage = dmeta["storage"]
        if len(storage) == 2:
            fdt = np.zeros(0, dtype).real.dtype
            re_ = self.read_region(manifest, storage[0], region, fdt)
            im = self.read_region(manifest, storage[1], region, fdt)
            return (re_ + 1j * im).astype(dtype, copy=False)
        return self.read_region(manifest, storage[0], region, dtype)


def _target_region(idx, shape) -> tuple:
    return _normalize_index(idx, shape)


def _resting_spec(pde) -> tuple:
    """The pencil layout ``pde``'s spectral state rests in (``Space2.rest``
    of the model's spaces, an ensemble's through its template model); the
    x-pencil for a model that names no space."""
    from ..parallel.mesh import SPEC

    space = getattr(getattr(pde, "model", pde), "temp_space", None)
    return SPEC if space is None else space.rest


def read_sharded_snapshot(pde, filename: str) -> None:
    """Topology-elastic restore of a sharded checkpoint onto ``pde``.

    The writer's mesh shape, host count and device order are IRRELEVANT:
    each process assembles, for every state leaf, exactly the slab regions
    its own devices need under the TARGET layout — per-device buffers are
    built with :func:`jax.make_array_from_single_device_arrays` on a mesh
    (a serial model just gets the assembled global array) — so a checkpoint
    written under mesh ``(2,)`` restores onto serial, a 4-device mesh, a
    reversed-order mesh or a different host count, bit-equal to the
    writer's state.  Resolution/dtype changes are rejected with
    :class:`CheckpointError` (use the gathered snapshot format for
    spectral interpolation)."""
    import jax
    import jax.numpy as jnp

    from ..parallel.mesh import divides, pencil_sharding

    with _open_checkpoint(filename) as h5:
        attrs = _verify_open_file(h5, filename)
        if not attrs.get("sharded"):
            raise CheckpointError(filename, "not a sharded-checkpoint manifest")
        meta = _read_manifest_meta(h5, filename)
        root: dict[str, np.ndarray] = {}
        for name, obj in h5.items():
            if name != _MANIFEST_DS and hasattr(obj, "shape"):
                root[name] = np.asarray(obj)
    if hasattr(pde, "k") and "members" in root:
        # member-count mismatch gets ITS message, not the per-leaf shape
        # gate's interpolation advice (which would be wrong here)
        k = int(np.asarray(root["members"]))
        if k != int(pde.k):
            raise CheckpointError(
                filename,
                f"checkpoint holds {k} members but the ensemble has "
                f"{pde.k}; sharded restore is K-fixed (the gathered "
                "per-member format is the K-elastic one)",
            )
    # root re-hashes the full shard set; peers run the cheap digest-attr
    # cross-check — a multihost resume then costs ~2x the checkpoint bytes
    # in shared-storage reads, not (N+1)x (root already verified end-to-end
    # at selection time, and the assembly below reads only needed slabs)
    _verify_shard_set(filename, meta, full=_process_index() == 0)

    mesh = getattr(pde, "mesh", None)
    if mesh is None and hasattr(pde, "model"):
        mesh = getattr(pde.model, "mesh", None)
    scope = pde.model._scope if hasattr(pde, "model") else pde._scope

    updates: dict[str, object] = {}
    with ExitStack() as stack:
        catalog = _SlabCatalog(stack, filename, meta)
        for name, arr in pde.snapshot_state_items():
            dmeta = meta["datasets"].get(name)
            if dmeta is None:
                if name.startswith("stats/"):
                    # checkpoint written before the stats engine was armed:
                    # the averaging window restarts (apply_restored_state
                    # zero-fills the absent leaves) — the STATE restore
                    # stays bit-exact either way
                    print(
                        f"sharded checkpoint lacks {name!r}; running "
                        "averages restart from zero"
                    )
                    continue
                raise CheckpointError(filename, f"manifest lacks dataset {name!r}")
            if tuple(dmeta["shape"]) != tuple(arr.shape):
                raise CheckpointError(
                    filename,
                    f"{name}: checkpoint shape {tuple(dmeta['shape'])} != model "
                    f"shape {tuple(arr.shape)} — sharded restore is topology-"
                    "elastic but resolution-fixed (use the gathered format "
                    "to interpolate)",
                )
            if str(np.dtype(dmeta["dtype"])) != str(np.dtype(arr.dtype)):
                raise CheckpointError(
                    filename,
                    f"{name}: checkpoint dtype {dmeta['dtype']} != model dtype "
                    f"{arr.dtype} (precision mode mismatch)",
                )
            leaf = name.rsplit("/", 1)[-1]
            if mesh is None:
                full = catalog.read_logical(
                    filename, name, dmeta, tuple((0, n) for n in arr.shape)
                )
                updates[leaf] = jnp.asarray(full)
                continue
            target = pencil_sharding(mesh, _resting_spec(pde), ndim=len(arr.shape))
            # explicit placement rejects non-divisible sharded dims (the odd
            # spectral sizes); GSPMD's constraint path rounds those to
            # replicated, so the restore target mirrors that rule — the
            # restored leaf then matches the layout the stepped model holds
            if not divides(arr.shape, target):
                target = pencil_sharding(mesh, (None,) * len(arr.shape))
            idx_map = target.addressable_devices_indices_map(tuple(arr.shape))
            buffers = []
            for dev, idx in idx_map.items():
                region = _target_region(idx, arr.shape)
                block = catalog.read_logical(filename, name, dmeta, region)
                buffers.append(jax.device_put(block, dev))
            updates[leaf] = jax.make_array_from_single_device_arrays(
                tuple(arr.shape), target, buffers
            )
    with scope():
        pde.apply_restored_state(updates, attrs, root)
    print(f" <== {filename} (sharded, {int(attrs['sharded'])} shard(s))")


# -- durable parked continuations (serve/fleet) -------------------------------
#
# A parked mid-flight member state (elastic shrink, proactive dt
# re-bucket, QoS preemption) was process-local in PR 10: a replica death
# before the park was re-claimed restarted that request from step 0.  The
# fleet layer persists each park as a per-request continuation dir,
# two-phase like every other durable write in this file:
#
#     parked/<request-id>/shard_00000.h5   per-process state slabs,
#                                          digest-stamped, atomic
#     parked/<request-id>/manifest.json    the COMMIT MARKER (atomic
#                                          rename + dirsync): a crash
#                                          mid-write leaves shards with
#                                          no manifest = no continuation
#
# so ANY replica that later claims the request resumes the trajectory
# mid-flight from durable state instead of restarting.

CONTINUATION_MANIFEST = "manifest.json"


def continuation_dir(run_dir: str, request_id: str) -> str:
    """``<run_dir>/parked/<id>`` — one continuation dir per request."""
    return os.path.join(run_dir, "parked", str(request_id))


def continuation_exists(cont_dir: str) -> bool:
    """True when a COMMITTED continuation is present (manifest = marker)."""
    return os.path.exists(os.path.join(cont_dir, CONTINUATION_MANIFEST))


def continuation_meta(cont_dir: str) -> tuple[int, float] | None:
    """``(base_steps, time_base)`` of a committed continuation — the
    host-side progress accounting a scheduler plan needs BEFORE deciding
    to restore the (much larger) state shards; None when no committed
    continuation exists."""
    try:
        with open(
            os.path.join(cont_dir, CONTINUATION_MANIFEST), encoding="utf-8"
        ) as fh:
            record = json.load(fh)
        return int(record["base"]), float(record["time_base"])
    except (OSError, ValueError, KeyError):
        return None


def continuation_record(cont_dir: str) -> dict | None:
    """The full committed-continuation manifest record (progress, shard
    table, writer-supplied ``meta`` — request id, dt, and the sub-mesh
    stamp a gang park carries), host-side JSON only; None when no
    committed continuation exists.  The gang recovery path reads this to
    verify a parked SHARDED state's topology (``meta.submesh``,
    ``len(shards)``) matches the bucket re-forming over it, and the
    chaos-soak gates assert reclaimed-with-state through it."""
    try:
        with open(
            os.path.join(cont_dir, CONTINUATION_MANIFEST), encoding="utf-8"
        ) as fh:
            record = json.load(fh)
    except (OSError, ValueError):
        return None
    if "base" not in record or "time_base" not in record:
        return None
    return record


def write_continuation(
    cont_dir: str, state, *, base: int, time_base: float, meta: dict | None = None
) -> str:
    """Persist one parked member state, two-phase (collective on a
    multi-process runtime — every host calls this together, like the
    sharded checkpoint writer it mirrors): each process writes its
    host-local state slabs to ``shard_<p>.h5`` (fsynced, digest-stamped),
    digests are exchanged, then ROOT atomically writes the manifest whose
    presence commits the continuation.  Raises :class:`CheckpointError`
    on a failed shard write (no manifest is committed)."""
    from ..parallel import multihost

    proc = _process_index()
    nproc = _process_count()
    fields = list(state._fields)
    slabs = {name: multihost.host_local_array(getattr(state, name)) for name in fields}
    items = [(f"state/{name}", arr, "raw") for name, arr in sorted(slabs.items())]
    digest = snapshot_digest(items)
    shard_file = os.path.join(cont_dir, f"shard_{proc:05d}.h5")

    def body(h5):
        grp = h5.require_group("state")
        for name in fields:
            grp.create_dataset(name, data=slabs[name])
        h5.attrs["shard_index"] = int(proc)
        h5.attrs["shard_count"] = int(nproc)

    local_error: Exception | None = None
    try:
        _atomic_h5_write(shard_file, body, step=int(base), digest=digest)
    except Exception as exc:  # noqa: BLE001 — the commit exchange decides
        local_error = exc
    if nproc == 1:
        digests, oks = [digest], [local_error is None]
    else:
        # the allgather doubles as the phase barrier: it completes only
        # after every host's shard write attempt resolved
        rows = multihost.allgather_bytes(
            json.dumps(
                {"digest": digest, "ok": local_error is None}
            ).encode("utf-8")
        )
        parsed = [json.loads(r.decode("utf-8")) for r in rows]
        digests = [p["digest"] for p in parsed]
        oks = [bool(p["ok"]) for p in parsed]
    manifest = os.path.join(cont_dir, CONTINUATION_MANIFEST)
    if not all(oks):
        if nproc > 1:
            multihost.sync_hosts("rustpde-continuation-abort")
        raise CheckpointError(
            manifest,
            "continuation persist aborted: a host failed its shard write "
            "(no manifest committed)"
            + (f"; local cause: {local_error}" if local_error else ""),
        ) from local_error
    if proc == 0:
        record = {
            "schema": SCHEMA_VERSION,
            "base": int(base),
            "time_base": float(time_base),
            "fields": fields,
            "shards": [
                {"file": f"shard_{i:05d}.h5", "digest": d}
                for i, d in enumerate(digests)
            ],
            "meta": dict(meta or {}),
        }
        # the COMMIT marker: strict dirsync — a failed dirsync must
        # report the continuation NOT committed
        fsutil.atomic_write_text(
            manifest, json.dumps(record, sort_keys=True), strict=True
        )
    if nproc > 1:
        multihost.sync_hosts("rustpde-continuation-commit")
    return manifest


def read_continuation(cont_dir: str, template_state):
    """Restore a committed continuation: ``(state, base, time_base)``.

    Each process reads ITS shard (digest-verified end-to-end), checks
    every leaf's shape/dtype against ``template_state`` (a donor member
    state of the claiming ensemble — same compat bucket, so same shapes
    by construction), and on a multi-process runtime reassembles the
    host-local slabs into global arrays with the template leaf's
    sharding.  Raises :class:`CheckpointError` on a missing/uncommitted
    continuation or any verification failure — callers degrade to a
    from-scratch restart, never a torn state."""
    import h5py

    from ..parallel import multihost

    manifest = os.path.join(cont_dir, CONTINUATION_MANIFEST)
    try:
        with open(manifest, encoding="utf-8") as fh:
            record = json.load(fh)
    except (OSError, ValueError) as exc:
        raise CheckpointError(
            manifest, f"no committed continuation: {exc}"
        ) from exc
    fields = list(record.get("fields", ()))
    if fields != list(template_state._fields):
        raise CheckpointError(
            manifest,
            f"continuation fields {fields} != state fields "
            f"{list(template_state._fields)} (model kind changed?)",
        )
    proc = _process_index()
    shards = record.get("shards", [])
    if proc >= len(shards):
        raise CheckpointError(
            manifest,
            f"continuation holds {len(shards)} shard(s) but this is "
            f"process {proc}: written under a different topology",
        )
    path = os.path.join(cont_dir, shards[proc]["file"])
    with _open_checkpoint(path) as h5:
        attrs = _attrs_of(h5)
        if attrs.get("digest") != shards[proc]["digest"]:
            raise CheckpointError(
                manifest, f"shard {shards[proc]['file']!r} digest mismatch"
            )
        if content_digest(h5) != shards[proc]["digest"]:
            raise CheckpointError(
                manifest, f"shard {shards[proc]['file']!r} content mismatch"
            )
        slabs = {name: np.asarray(h5["state"][name]) for name in fields}
    leaves = {}
    for name in fields:
        tmpl = getattr(template_state, name)
        slab = slabs[name]
        if _process_count() == 1:
            if tuple(slab.shape) != tuple(tmpl.shape) or str(
                np.dtype(slab.dtype)
            ) != str(np.dtype(tmpl.dtype)):
                raise CheckpointError(
                    manifest,
                    f"{name}: continuation {slab.shape}/{slab.dtype} != "
                    f"state {tuple(tmpl.shape)}/{tmpl.dtype}",
                )
            leaves[name] = slab
        else:
            leaves[name] = multihost.global_array(slab, tmpl.sharding)
    return (
        type(template_state)(**leaves),
        int(record.get("base", 0)),
        float(record.get("time_base", 0.0)),
    )


def remove_continuation(cont_dir: str) -> None:
    """Retire a consumed continuation: the MANIFEST goes first (atomic
    uncommit — a crash mid-removal leaves shards with no marker, which
    reads as "no continuation", never a torn one), then the shards and
    the dir itself.  Root-only on multi-process runtimes (host-local
    filesystem work; the caller fences)."""
    manifest = os.path.join(cont_dir, CONTINUATION_MANIFEST)
    try:
        os.remove(manifest)
        fsync_dir(cont_dir)
    except OSError:
        pass
    try:
        for name in os.listdir(cont_dir):
            try:
                os.remove(os.path.join(cont_dir, name))
            except OSError:
                pass
        fsync_dir(cont_dir)
        os.rmdir(cont_dir)
        fsync_dir(os.path.dirname(cont_dir) or ".")
    except OSError:
        pass
