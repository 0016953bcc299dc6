"""What the ``periodic1024_f32`` cells rest on, at sizes a CPU can hold:

* the plain reference with a Fourier axis (``benchmark/reference_periodic.py``)
  against the program's own float64 periodic path, and its three-pass control
  against itself;
* the program pencil-decomposed over a virtual CPU mesh against that same
  reference, on the normal path (GSPMD places the collectives) and on the
  manual one (``RUSTPDE_SEP=1``: the ``shard_map`` regions of
  parallel/decomp.py with hand-placed all-to-alls);
* what a meshed model's ``model.update_n`` span counts, against a hand count;
* the scopes of parallel/decomp.py in the chunk's compiled text, and that they
  change nothing else of it;
* ``benchmark/work_periodic.py``'s count against a hand count, and the new
  per-layer readers.
"""

import collections
import contextlib
import importlib.util
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax

from benchmark import check, work, work_periodic
from benchmark.ic_periodic import smooth_periodic_fields
from benchmark.reference import random_fields
from benchmark.reference_periodic import Reference
from rustpde_mpi_tpu import Navier2D, config
from rustpde_mpi_tpu.ops import folded
from rustpde_mpi_tpu.parallel.decomp import Decomp2d
from rustpde_mpi_tpu.parallel.mesh import PHYS, SPEC, make_mesh
from rustpde_mpi_tpu.telemetry import FlightRecorder
from rustpde_mpi_tpu.telemetry import tracing as ttracing

PHYSICS = (1e6, 1.0, 2e-3, 1.0)  # ra, pr, dt, aspect
FIELDS = ("temp", "velx", "vely", "pres")
needs_x64 = pytest.mark.skipif(not config.X64, reason="the reference is pinned in float64")


def gaps(model, ref, out) -> dict:
    got = {k: model.get_field(k) for k in FIELDS}
    want = {k: ref.backward(k, out[i]) for i, k in enumerate(FIELDS)}
    return {k: float(np.linalg.norm(got[k] - want[k]) / np.linalg.norm(want[k])) for k in FIELDS}


def reference_after(nx, ny, fields, steps):
    ref = Reference(nx, ny, *PHYSICS, dtype=np.float64)
    return ref, ref.run(ref.initial_state(fields), steps)


# -- the reference ---------------------------------------------------------------


@needs_x64
@pytest.mark.parametrize("grid", [(16, 17), (32, 33)])
def test_reference_is_pinned_to_the_programs_f64_periodic_path(grid):
    """The program's CPU path in float64 (FFT along x, complex spectra, banded
    solves) and the reference (dense products, its own operators) agree to
    rounding after 10 steps from white noise."""
    nx, ny = grid
    model = Navier2D.new_periodic(nx, ny, *PHYSICS, "rbc")
    model.init_random(0.1, seed=5)
    model.update_n(10)
    ref, out = reference_after(nx, ny, random_fields((nx, ny), 0.1, 5), 10)
    assert max(gaps(model, ref, out).values()) < 1e-9


@needs_x64
def test_three_pass_control_reads_twenty_times_worse_than_float32():
    """Against the float64 trajectory after 128 steps at 32 x 33 the float32
    reference reads 2e-6..1e-5 and its ``bf16_3x`` control 2e-4..6e-4: the
    nearest precision below is told apart by a factor of 20 at least."""
    nx, ny = 32, 33
    ic = smooth_periodic_fields(nx, ny, 2**31 + 3)
    truth = check.reference_fields(Reference(nx, ny, *PHYSICS, dtype=np.float64), ic, 128)
    ref = Reference(nx, ny, *PHYSICS)
    sound = check.field_gaps(check.reference_fields(ref, ic, 128), truth)
    control = check.field_gaps(check.reference_fields(ref, ic, 128, "bf16_3x"), truth)
    for k in check.FIELDS:
        assert sound[k] < 1e-4 and control[k] > 20.0 * sound[k], (k, sound, control)


def test_initial_condition_is_periodic_solenoidal_and_no_slip():
    nx, ny = 32, 33
    ic = smooth_periodic_fields(nx, ny, 7, aspect=1.5)
    ref = Reference(nx, ny, 1e6, 1.0, 2e-3, 1.5, dtype=np.float64)
    _, velx, vely, _, _ = ref.initial_state(ic)
    h = ref._host
    div = 1j * h["k1"][:, None] * (velx @ h["ortho_T"]) + vely @ h["div_yT"]
    assert np.abs(div).max() < 1e-12
    for k in check.FIELDS:
        assert np.abs(ic[k][:, [0, -1]]).max() < 1e-15, k  # the plates
        assert np.abs(ref.backward(k, ref.initial_state(ic)[ref_index(k)]) - ic[k]).max() < 1e-12
    assert smooth_periodic_fields(nx, ny, 7)["temp"].tolist() != \
        smooth_periodic_fields(nx, ny, 8)["temp"].tolist()


def ref_index(name: str) -> int:
    return ("temp", "velx", "vely", "pres", "pseu").index(name)


# -- the program on a mesh, against the same reference ---------------------------


def meshed(monkeypatch, devices: int, path: str, nx=32, ny=33):
    """The periodic model on ``devices`` virtual CPU devices in the layout a
    TPU runs (split Re/Im spectra); ``manual`` forces the mixed-sep layout,
    whose mesh path is the shard_map regions of parallel/decomp.py."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    if path == "manual":
        monkeypatch.setenv("RUSTPDE_SEP", "1")
    mesh = make_mesh(jax.devices()[:devices]) if devices else None
    model = Navier2D.new_periodic(nx, ny, *PHYSICS, "rbc", mesh=mesh)
    assert model.temp_space.bases[0].kind.is_split
    assert (model._manual_poisson is not None) == (path == "manual" and bool(devices))
    # the hand-partitioned program's spaces state no layout of their own
    assert model.velx_space.states_layout == (not (path == "manual" and bool(devices)))
    return model


@needs_x64
@pytest.mark.parametrize("path", ["normal", "manual"])
@pytest.mark.parametrize("devices", [2, 4])
def test_meshed_program_follows_the_reference(monkeypatch, devices, path):
    nx, ny = 32, 33
    model = meshed(monkeypatch, devices, path)
    ic = smooth_periodic_fields(nx, ny, 2**31 + 11)
    for name, values in ic.items():
        model.set_field(name, values)
    model.update_n(10)
    ref, out = reference_after(nx, ny, ic, 10)
    assert max(gaps(model, ref, out).values()) < 1e-9


@needs_x64
@pytest.mark.parametrize("path", ["normal", "manual"])
def test_unmeshed_split_program_follows_the_reference(monkeypatch, path):
    """The layout a TPU runs, on one device: every convection chain goes
    through ``Space2.backward_gradient``'s per-axis loop (x-derivative,
    x-synthesis, y-derivative, y-synthesis), on the natural-order space
    (``normal``) as on the mixed one (``manual``: the y-axis sep)."""
    nx, ny = 32, 33
    model = meshed(monkeypatch, 0, path)
    assert model.mesh is None and model.velx_space.sep == (False, path == "manual")
    ic = smooth_periodic_fields(nx, ny, 2**31 + 11)
    for name, values in ic.items():
        model.set_field(name, values)
    model.update_n(10)
    ref, out = reference_after(nx, ny, ic, 10)
    assert max(gaps(model, ref, out).values()) < 1e-9


@needs_x64
def test_meshed_program_follows_the_unmeshed_one(monkeypatch):
    """Ten steps on four virtual devices against the same program on one, from
    the same seeded state.  On the mesh each synthesis takes y first (where
    the spectral arrays rest), flips, then x, and each analysis the reverse;
    on one device nothing is distributed and x goes first, as it always has
    (``Space2.synthesis_axes``).  The two phases of a transform commute but do
    not round alike, and a distributed product sums its partial rows in
    another order, so the fields agree to rounding and not bit for bit: read
    4.3e-15..2.0e-14 relative (CPU, PR 31); held to the 1e-9 this file holds
    mesh and plain to against the reference."""
    nx, ny = 32, 33
    ic = smooth_periodic_fields(nx, ny, 2**31 + 17)
    fields = {}
    for devices in (4, 0):
        model = meshed(monkeypatch, devices, "normal")
        with model._scope():
            assert model.temp_space.synthesis_axes == ((1, 0) if devices else (0, 1))
        for name, values in ic.items():
            model.set_field(name, values)
        model.update_n(10)
        fields[devices] = {k: model.get_field(k) for k in FIELDS}
    gap = {k: float(np.linalg.norm(fields[4][k] - fields[0][k]) / np.linalg.norm(fields[0][k]))
           for k in FIELDS}
    assert 0.0 < max(gap.values()) < 1e-9, gap


# -- where the meshed step takes its operators ----------------------------------------

COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) "
    r"(all-reduce|all-to-all|all-gather|collective-permute|reduce-scatter)(?:-start)?\((.*)$", re.M)
FLIPS = 15  # the hand count of test_meshed_chunk_sums_no_field_over_the_devices
# a confined 17 x 17 chunk of 4 steps on four devices, as the tree before ISSUE 31 compiled it,
# less the two flips behind the x-synthesis of velx and of vely that ux / uy and the velocity's
# own d/dy share since ISSUE 36 (23 all-to-alls before)
CONFINED_17 = {"all-to-all": 21, "all-gather": 39, "collective-permute": 44, "all-reduce": 3}
# the same chunk in float64 on the TPU path, every product a sliced product whose field is pinned
# with its contracted axis whole (ops/folded.py): 16 more all-to-alls round the products where
# this partitioner gathered and permuted round XLA's own dots, 43 collectives for 107
CONFINED_17_SLICED = {"all-to-all": 37, "all-gather": 5, "all-reduce": 1}


def chunk_text(model, n=4) -> str:
    with model._scope():
        return model._step_n_jit.lower(model._step_consts, model.state, n=n).compile().as_text()


def largest_operand(result: str) -> int:
    """Entries of the largest array of an instruction's result type, which for
    a collective may be a tuple: ``(f32[1,18], f32[8,18])`` -> 144."""
    sizes = [int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
             for dims in re.findall(r"\w+\[([\d,]*)\]", result)]
    return max(sizes)


def collectives(text: str) -> list:
    """``(kind, entries of the largest result, inside the step body)`` of every
    collective of a chunk's compiled text.  Inside the body: the instruction's
    metadata names the scan's loop (the whole-field all-gathers that hand the
    state back whole, once a chunk, carry none)."""
    return [(kind, largest_operand(result), "/while/body/" in rest)
            for result, kind, rest in COLLECTIVE.findall(text)]


def sums_no_field(text: str, nx: int, ny: int, flips: int, gathered_flips=None) -> None:
    """No all-reduce of the chunk moves half a spectral field or more, and the
    all-to-alls are at most ``flips``.  ``gathered_flips``: how many all-gathers
    of half a field or more the step body may hold besides (a partitioner may
    lower a flip of a small array to an all-gather and a slice: then it counts
    as the flip it is, within ``flips``); None where they cannot be counted."""
    half = (nx + 2) * (ny - 2) // 2
    found = collectives(text)
    summed = [size for kind, size, _ in found if kind == "all-reduce"]
    assert max(summed, default=0) < half, summed
    exchanged = sum(kind == "all-to-all" for kind, _, _ in found)
    assert 0 < exchanged <= flips
    if gathered_flips is not None:
        gathered = sum(kind == "all-gather" and size >= half and body for kind, size, body in found)
        assert gathered <= gathered_flips and exchanged + gathered <= flips, (exchanged, gathered)


@pytest.mark.parametrize("grid", [(16, 17), (32, 33)])
def test_meshed_chunk_sums_no_field_over_the_devices(monkeypatch, no_compile_cache, grid):
    """Four devices, extents four does not divide.  On this space (split
    Fourier along x, Chebyshev along y) spectral arrays rest as y-pencils (x
    distributed, y whole on a device: ``Space2.rest``), because every spectral
    x-operator between two transforms is a diagonal but one.  A Chebyshev
    derivative, a composite cast, a Helmholtz or Poisson solve along y is
    taken where it is, with nothing to exchange or to sum: no all-reduce of
    the chunk moves half a spectral field or more (a tuple all-reduce is named
    by its first element, so every element is looked at; scalar reductions,
    the finite check among them, stay).  The flips are the hand count:
    1 a velocity for ``ux`` / ``uy`` AND the d/dx synthesis of its own chain
    (y where it rests, ONE flip, then x with and without the derivative:
    ``Space2.synthesis_first``), 2 a velocity chain besides (the d/dy
    synthesis out, the product back), 3 for the temperature's chain (two
    derivative syntheses out, the product back), and 2 round each of the
    three odd x-derivatives of the split layout, which swaps the Re and Im
    halves of the x extent and is the one spectral operator that needs x
    whole (d/dx of the pressure, of the new velx in the divergence, of the
    pseudo-pressure in the projection); the three Helmholtz solves, the
    Poisson solve, every d/dy, stencil and cast: 0.  2 + 4 + 3 + 6 = 15 (17
    while a velocity's y-synthesis was stated twice, 27 while the arrays
    rested as x-pencils).  The compiler may lower a flip of
    so small an array to an all-gather and a slice, never add one: an
    all-gather of half a field or more inside the step body counts as a flip
    (the lowering a sliced and concatenated sharded axis falls into would add
    some)."""
    nx, ny = grid
    model = meshed(monkeypatch, 4, "normal", nx=nx, ny=ny)
    assert model.temp_space.rest == PHYS
    sums_no_field(chunk_text(model), nx, ny, flips=FLIPS, gathered_flips=FLIPS)


# The chunk compiled for the four chips of a DESCRIBED v5e host: the TPU's
# compiler is installed here and compiles for a chip that is not attached.
# Nothing can be placed on a described device, so the model keeps its arrays
# where they are and the chunk is compiled from shapes.
V5E_CHUNK = """
import sys
import jax
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec
from rustpde_mpi_tpu import Navier2D
from rustpde_mpi_tpu.parallel import mesh as pmesh

nx, ny = (int(a) for a in sys.argv[1:3])
mesh = pmesh.make_mesh(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices)
pmesh.device_put = lambda x, spec: jax.numpy.asarray(x)
pmesh.replicate = lambda tree: tree
model = Navier2D.new_periodic(nx, ny, 1e6, 1.0, 2e-3, 1.0, "rbc", mesh=mesh)
whole = NamedSharding(mesh, PartitionSpec())
shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=whole),
                      (model._step_consts, model.state))
with model._scope():
    sys.stdout.write(model._step_n_jit.lower(*shapes, n=4).compile().as_text())
"""


@pytest.mark.parametrize("grid", [(32, 33), (256, 257)])
def test_the_chips_own_compiler_sums_no_field_either(grid):
    """The same program through the TPU's compiler, which propagates layouts
    its own way: with the flips stated only round the visit to the y-local
    layout, it ran the projection's stencil on the x-pencil and summed the
    halves of its result over the devices (two all-reduces of half a field,
    88 us a step on the chip), where the CPU's partitioner of the case above
    summed nothing.  At 32 x 33 it takes the three analysis flips as
    all-gathers folded into the products that follow, in steps that cannot be
    counted; from 256 x 257 up it makes the program it makes at the cell's
    1024 x 1025 (15 all-to-alls in the step body, no all-gather there and no
    collective-permute), so that size also holds: no all-gather of half a
    spectral field or more inside the loop.  In a process of its own, which
    ends with the compile: the TPU's library is loaded into no test worker,
    and its machine-wide lock is asked for by no one (a compile for a
    described chip touches no chip).  Skipped only where that library is not
    installed."""
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no libtpu installed: no compiler for a described v5e")
    nx, ny = grid
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", RUSTPDE_FORCE_TPU_PATH="1", RUSTPDE_COMPILE_CACHE="0",
               RUSTPDE_X64="0", ALLOW_MULTIPLE_LIBTPU_LOAD="1", PYTHONPATH=repo)  # float32, as the cell
    env.pop("RUSTPDE_SEP", None)
    done = subprocess.run([sys.executable, "-c", V5E_CHUNK, str(nx), str(ny)], env=env, cwd=repo,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    sums_no_field(done.stdout, nx, ny, flips=FLIPS, gathered_flips=0 if nx >= 256 else None)


@pytest.mark.parametrize("products", ["dots", "sliced"])
def test_meshed_confined_chunk_keeps_the_x_pencil_rest(monkeypatch, no_compile_cache, fold_gate, products):
    """The other side of the selection: a confined space (Chebyshev along x,
    dense x-operators) rests as x-pencils, its y-operators between a pair of
    flips, and its chunk on four devices holds the collectives the tree before
    ISSUE 31 compiled for it (counted there at this size, with this jax: the
    chunk's text was the same but for its metadata).  That tree folded every
    transform, so the fold gate of ops/folded.py is pinned below this size
    (with plain products this partitioner takes 8 of the 23 flips as 10
    all-gathers: PERF.md section 7).  ``dots``: every product
    XLA's own dot, as the float32 cells' are; ``sliced``: the float64 step of
    the TPU path, whose sliced products (ops/folded.py) state their
    contracted axis whole on a device, as the transforms do (CONFINED_17_SLICED)."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    if products == "dots":
        monkeypatch.setattr(folded, "_sliced", lambda itemsize: False)
    fold_gate(4)
    model = Navier2D.new_confined(17, 17, *PHYSICS, "rbc", mesh=make_mesh(jax.devices()[:4]))
    assert model.temp_space.rest == SPEC and model.temp_space.synthesis_axes == (0, 1)
    assert (model._step_products["sliced_products"] > 0) == (products == "sliced" and config.X64)
    counts = collections.Counter(kind for kind, _, _ in collectives(chunk_text(model)))
    assert counts == (CONFINED_17_SLICED if model._step_products["sliced_products"] else CONFINED_17), counts


def test_unmeshed_confined_chunk_states_no_layout(monkeypatch, no_compile_cache):
    """Without a mesh every stated flip is its argument: the confined chunk
    (the layout of ``rbc513_f32`` and ``swarm129_f32``) holds no sharding
    annotation and no collective."""
    monkeypatch.setenv("RUSTPDE_FORCE_TPU_PATH", "1")
    model = Navier2D.new_confined(17, 17, *PHYSICS, "rbc")
    assert model.mesh is None and all(model.velx_space.sep)
    with model._scope():
        lowered = model._step_n_jit.lower(model._step_consts, model.state, n=4)
    assert "harding" not in lowered.as_text()  # no constraint was traced: sdy's or mhlo's
    text = lowered.compile().as_text()
    assert "sharding=" not in text and not COLLECTIVE.findall(text)


# What one traced step holds at the sizes from which ops/folded.py's gates make
# the cells' own programs, in float32 as the cells run: in a process of its own,
# because a process has one precision.  Nothing is compiled.
TRACED_PRODUCTS = """
import json
import jax
from rustpde_mpi_tpu import Navier2D
from rustpde_mpi_tpu.parallel.mesh import make_mesh

physics = (1e6, 1.0, 2e-3, 1.0, "rbc")
out = {
    "periodic_mesh4": Navier2D.new_periodic(256, 257, *physics, mesh=make_mesh(jax.devices()[:4])),
    "periodic_solo": Navier2D.new_periodic(256, 257, *physics),
    "confined_solo": Navier2D.new_confined(257, 257, *physics),
}
print(json.dumps({name: model._step_products for name, model in out.items()}))
"""


@pytest.fixture(scope="module")
def traced_products():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", RUSTPDE_FORCE_TPU_PATH="1", RUSTPDE_X64="0",
               PYTHONPATH=repo, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("RUSTPDE_SEP", None)
    done = subprocess.run([sys.executable, "-W", "ignore", "-c", TRACED_PRODUCTS], env=env,
                          cwd=repo, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("model, products",
                         [("periodic_mesh4", 66), ("periodic_solo", 66), ("confined_solo", 76)])
def test_a_velocitys_first_axis_synthesis_is_traced_once(traced_products, model, products):
    """``ux`` / ``uy`` and the derivative synthesis of the velocity's own
    chain along the second axis of ``synthesis_axes`` are finished from ONE
    first-axis partial (d/dx under a mesh, where y goes first; d/dy on one
    device and on a confined space): the step states a parity-folded product,
    two ``dot_general``s, less for each velocity than it did while both
    consumers stated the product themselves (70, 70 and 80 in the tree before
    ISSUE 36, where only the confined step's pair was merged, by the
    compiler).  The span's ``shared_syntheses`` says how many partials have
    two consumers."""
    got = traced_products[model]
    assert (got["f32_products"], got["f64_products"]) == (products, 0)
    assert got["shared_syntheses"] == 2


# -- the span's counters -----------------------------------------------------------


@pytest.fixture
def ring(monkeypatch):
    rec = FlightRecorder(capacity=64)
    monkeypatch.setattr(ttracing, "RECORDER", rec)
    monkeypatch.setattr(ttracing, "_ENABLED", True)
    return rec


def last_update_n(model) -> dict:
    model.update_n(2)
    return ttracing.spans("model.update_n")[-1][-1]


def test_span_counts_the_manual_exchanges(monkeypatch, ring):
    """32 x 33 on 4 devices, mixed-sep layout.  Padded extents: nx 32, split
    modes 34 -> 36, composite y 31 -> 32, ortho y 33 -> 36.  Local blocks a
    step exchanges: a convection chain (32, 8) twice out and (8, 36) back,
    three chains; a synthesis (32, 8), two; the Poisson solve (36, 9) out and
    (9, 32) back: 13 exchanges.  A device keeps a quarter of each block and
    sends three: 3 * (3 * (64 + 64 + 72)) + 2 * (3 * 64) + 3 * (81 + 72)
    = 2643 numbers of 4 or 8 bytes."""
    args = last_update_n(meshed(monkeypatch, 4, "manual"))
    itemsize = 8 if config.X64 else 4
    assert args["devices"] == 4
    assert args["shared_syntheses"] == 0  # the hand-partitioned regions take their inputs whole
    assert args["transposes"] == 13
    assert args["exchange_bytes"] == 2643 * itemsize
    # neither 31, 33 composite nor 33 ortho columns divide by 4: every leaf whole
    assert args["replicated_leaves"] == 5


def test_span_counts_no_exchange_where_the_compiler_places_them(monkeypatch, ring):
    """Where the compiler places the all-to-alls the span counts the flips
    the step states, once where it is traced (``parallel.mesh.flip``): the
    hand count of the chunk's flips, and what they send from one device, 3
    of its 4 tiles of each flipped array (tests/test_periodic_f64_cell.py
    holds both against the partitioner's all-to-alls)."""
    args = last_update_n(meshed(monkeypatch, 4, "normal"))
    itemsize = 8 if config.X64 else 4
    assert (args["devices"], args["transposes"]) == (4, FLIPS)
    assert args["exchange_bytes"] == 3 * (6 * 81 + 3 * 81 + 2 * 81 + 4 * 72) * itemsize
    assert args["shared_syntheses"] == 2  # a partial a velocity, finished twice
    # the state rests as y-pencils: its 34 split rows (17 modes, Re and Im)
    # divide by 2 and not by 4, so on four devices every leaf is whole
    assert args["replicated_leaves"] == 5
    assert last_update_n(meshed(monkeypatch, 2, "normal"))["replicated_leaves"] == 0
    # 30 points: 16 modes, 32 rows, which divide by 4
    assert last_update_n(meshed(monkeypatch, 4, "normal", nx=30))["replicated_leaves"] == 0


def test_span_of_an_unmeshed_model_says_nothing_of_a_mesh(monkeypatch, ring):
    args = last_update_n(meshed(monkeypatch, 0, "normal"))
    assert not {"devices", "transposes", "exchange_bytes", "replicated_leaves",
                "unplaced_args"} & set(args)


# -- the hoisted constants, committed to the mesh where they are hoisted -----------

CONSTS = ("_step_consts", "_obs_consts", "_stats_consts", "_stats_health_consts",
          "_sent_consts", "_dig_consts")


def off_the_mesh(model, names=CONSTS) -> dict:
    """Per list of hoisted constants, the leaves that are not committed to
    exactly the model's devices (a list that is not armed is None and counts
    nothing: the test arms them all)."""
    devices = set(model.mesh.devices.flat)
    return {
        name: sum(not (leaf.committed and leaf.sharding.device_set == devices)
                  for leaf in jax.tree.leaves(getattr(model, name)))
        for name in names
    }


def seeded(model):
    for name, values in smooth_periodic_fields(32, 33, 2**31 + 11).items():
        model.set_field(name, values)
    return model


def unplaced_args() -> int:
    return ttracing.spans("model.update_n")[-1][-1]["unplaced_args"]


def two_launches(model) -> list:
    model.update_n(4)
    model.update_n(4)
    return [np.asarray(leaf) for leaf in jax.tree.leaves(model.state)]


@pytest.mark.parametrize("path", ["normal", "manual"])
@pytest.mark.parametrize("devices", [2, 4])
def test_hoisted_constants_are_committed_to_the_mesh_once(monkeypatch, ring, devices, path):
    """Every constant a meshed model's programs take sits whole on each of the
    mesh's devices from the build on, and again after a ``set_dt`` rebuild:
    a dispatch moves nothing (the transfer guard holds both launches, the
    second of which finds no operand of the first left over), and the span
    counts 0 ``unplaced_args``.  With ``replicate`` patched out (the parent's
    behaviour) the programs take the same values from device 0: the state
    comes out bit-identical on the normal path (to a few ulp on four devices,
    where one unplaced operator changes a fusion), the span counts the leaves
    each dispatch places anew, and the guard refuses the launch."""
    from rustpde_mpi_tpu.config import IntegrityConfig, StabilityConfig, StatsConfig
    from rustpde_mpi_tpu.parallel import mesh as pmesh

    model = seeded(meshed(monkeypatch, devices, path))
    assert off_the_mesh(model, CONSTS[:2]) == {"_step_consts": 0, "_obs_consts": 0}
    with jax.transfer_guard_device_to_device("disallow"):
        placed = two_launches(model)
        model.get_observables()
    assert unplaced_args() == 0

    model.set_integrity(IntegrityConfig())
    model.set_stats(StatsConfig(stride=2))
    model.set_stability(StabilityConfig())
    assert off_the_mesh(model) == dict.fromkeys(CONSTS, 0)
    before = model._step_consts
    model.set_dt(model.dt / 2)
    assert model._step_consts is not before
    assert off_the_mesh(model) == dict.fromkeys(CONSTS, 0)
    # the sentinel chunk with the stats riding its carry: its seven fresh flags
    # and maxima are made eagerly per dispatch (the carry_copy span's ``fresh``)
    # and are no constants, so the guard holds the digest's launch alone
    model.update_n(2)
    with jax.transfer_guard_device_to_device("disallow"):
        model.state_digest_async().result()
        model.stats_health_async().result()
    assert unplaced_args() == 0
    model.set_dt(model.dt * 2)  # a cached rung brings its own count back
    assert model._step_consts is before and model._unplaced_consts == 0

    replicate = pmesh.replicate
    monkeypatch.setattr(pmesh, "replicate", lambda tree: tree)
    bare = seeded(meshed(monkeypatch, devices, path))
    unplaced = off_the_mesh(bare, CONSTS[:2])
    assert unplaced["_step_consts"] > 0 and unplaced["_obs_consts"] > 0
    # left free, the compiler cuts some of the unplaced operators along "p"
    # itself.  On the normal path that moves no number, but for one operator on
    # four devices: the (2, ny) coefficients of ``to_ortho``'s y-stencil.  Placed
    # they are cut once before the loop, unplaced they ride it whole, the stencil
    # is compiled into other fusions and rounds otherwise (read: 24 eps of the
    # state's largest entry after 8 steps).  The manual path keeps the slack it had.
    eps_of_largest = np.finfo(placed[0].dtype).eps * max(np.abs(leaf).max() for leaf in placed)
    slack = {("normal", 2): 0.0, ("normal", 4): 64.0}.get((path, devices), 1e3) * eps_of_largest
    for got, want in zip(two_launches(bare), placed):
        np.testing.assert_allclose(got, want, rtol=0, atol=slack)
    assert unplaced_args() == unplaced["_step_consts"]
    with pytest.raises(Exception, match="Disallowed device-to-device transfer"):
        with jax.transfer_guard_device_to_device("disallow"):
            bare.update_n(4)
    if (path, devices) == ("normal", 4):  # those coefficients alone placed again: bit for bit
        cured = seeded(meshed(monkeypatch, devices, path))
        with cured._scope():
            cured._step_consts = jax.tree.map(
                lambda a: replicate(a) if a.shape == (2, 33) else a, cured._step_consts)
        assert off_the_mesh(cured, CONSTS[:1])["_step_consts"] == unplaced["_step_consts"] - 2
        for got, want in zip(two_launches(cured), placed):
            np.testing.assert_array_equal(got, want)


def test_unmeshed_constants_are_the_arrays_the_hoist_returned(monkeypatch):
    from rustpde_mpi_tpu.utils import jit as ujit

    returned, hoist = [], ujit.hoist_constants

    def recording(fn, *example):
        out = hoist(fn, *example)
        returned.append(out[1])
        return out

    monkeypatch.setattr(ujit, "hoist_constants", recording)
    model = meshed(monkeypatch, 0, "normal")
    assert model._step_consts is returned[0] and model._obs_consts is returned[1]
    assert model._unplaced_consts == 0


# -- the scopes --------------------------------------------------------------------


@contextlib.contextmanager
def _no_scope(name):
    yield


def _strip(text: str) -> str:
    blocks = [b for b in text.split("\n\n") if b.split("\n", 1)[0] not in
              ("FileNames", "FunctionNames", "FileLocations", "StackFrames")]
    return re.sub(r",? ?metadata=\{[^}]*\}", "", "\n\n".join(blocks))


def test_mesh_scopes_are_in_the_chunks_text_and_change_nothing_else(monkeypatch, no_compile_cache):
    def text_of(named: bool) -> str:
        with monkeypatch.context() as mp:
            if not named:
                mp.setattr(jax, "named_scope", _no_scope)
            return chunk_text(meshed(mp, 2, "manual", nx=16, ny=17))

    named, bare = text_of(True), text_of(False)
    assert _strip(named) == _strip(bare)
    scopes = set(re.findall(r'op_name="([^"]*)"', named))
    for want in ("/synthesis/sharded_synthesis/", "/momentum_x/convection/sharded_conv/",
                 "/temperature/convection/sharded_conv/", "/poisson/sharded_poisson/",
                 "transpose_x_to_y", "transpose_y_to_x"):
        assert any(want in s for s in scopes), want
    # every hand-placed exchange says its direction (the compiler adds its own elsewhere)
    exchanges = [ln for ln in named.splitlines() if " all-to-all(" in ln and "/sharded_" in ln]
    assert exchanges and all("/transpose_" in ln for ln in exchanges)
    assert not any("transpose_" in s for s in re.findall(r'op_name="([^"]*)"', bare))


@pytest.mark.parametrize("method", ["alltoall", "ring"])
def test_global_view_transposes_carry_their_direction(no_compile_cache, method):
    decomp = Decomp2d((9, 7), make_mesh(jax.devices()[:2]))
    x = np.arange(63.0).reshape(9, 7)
    for name in ("transpose_x_to_y", "transpose_y_to_x"):
        fn = jax.jit(lambda a, _name=name: getattr(decomp, _name)(a, method=method))
        np.testing.assert_array_equal(np.asarray(fn(x)), x)
        assert f"/{name}/" in fn.lower(x).compile().as_text()


# -- the yardstick's new pieces ------------------------------------------------------


def test_work_count_against_a_hand_count():
    """8 x 9: m = 5 complex modes.  16 Chebyshev products of 2 * 5 * 81 flops
    and 11 Fourier products of 2 * 5 * 8 * 9."""
    w = work_periodic.step_work(8, 9)
    assert w["products"] == 27
    assert w["flops"] == 16 * 810 + 11 * 720 == 20880
    # five m x ny complex spectra in and out; 7 half y-operators, 2 half x-operators
    assert w["bytes"] == 4 * (2 * 5 * 90 + 7 * 40.5 + 2 * 40.0)
    full = work_periodic.step_work(1024, 1025)
    assert full["flops"] == pytest.approx(2.909e10, rel=1e-3)
    assert work.roofline(full, "TPU v5 lite", 1e-3)["bound"] == "compute"


def _read(name, trace, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(trace, run)


def test_roofline_reader_counts_the_mixs_chips():
    run = {"traced_steps": 100, "cfg": {"grid": {"nx": 1024, "ny": 1025}},
           "traffic": {}, "device": {"kind": "TPU v5 lite"}}
    least = work_periodic.step_work(1024, 1025)["flops"] / 197e12
    one = _read("periodic_step_roofline", {"busy_s": 0.1}, run)
    assert one == pytest.approx(100.0 * least / 1e-3)
    four = _read("periodic_step_roofline", {"busy_s": 0.1}, {**run, "traffic": {"mesh": 4}})
    assert four == pytest.approx(one / 4.0)  # four chips could take a quarter of the time
    assert _read("periodic_step_roofline", {"busy_s": 0.1}, {**run, "traced_steps": 0}) is None


def test_collective_share_reader():
    ops = {"all-gather.3 f32[1026,1025]": 0.02, "all-to-all.1 f32[8,36]": 0.01,
           "collective-permute-start.2 f32[4]": 0.005, "all-reduce.9 f32[]": 0.005,
           "fusion.7 f32[1026,1023]": 0.16}
    assert _read("collective_share", {"ops": ops, "busy_s": 0.2}, {}) == pytest.approx(20.0)
    # one device: no collective in the trace reads nothing, never 0
    assert _read("collective_share", {"ops": {"fusion.7 f32[8]": 0.2}, "busy_s": 0.2}, {}) is None


def test_replicated_leaves_reader(ring):
    run = {"traced_dispatches": 2}
    assert _read("replicated_leaves", {}, run) is None
    for leaves in (0, 5, 5):
        ring.add_complete("model.update_n", ring.now_us(), 700.0,
                          {"id": 1, "parent": None, "replicated_leaves": leaves})
    assert _read("replicated_leaves", {}, run) == 5.0
    # the span of an unmeshed model (or of an older commit) has no such count
    ring.add_complete("model.update_n", ring.now_us(), 700.0, {"id": 1, "parent": None})
    assert _read("replicated_leaves", {}, run) is None
