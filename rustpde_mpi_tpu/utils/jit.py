"""Constant-hoisting jit helper.

The spectral framework's jitted programs close over large dense operator
matrices (transforms, solver factorizations).  Tracing embeds those as HLO
literals, which (a) bloats the serialized program to O(n^2) per matrix —
~900 MB at 2049^2 that every compile has to parse, constant-fold, hash for
the persistent cache and carry inside the executable — and (b) re-uploads
them on every recompile, while (c) the ensemble engine needs the SAME
constants shared by every vmapped member.  ``hoist_constants`` converts a closure
into an equivalent function taking the captured constants as explicit
device-resident arguments: trace once with ``make_jaxpr``, then replay the
jaxpr with ``eval_jaxpr`` feeding the constants as parameters.

(`jax.closure_convert` does NOT do this: it only hoists captured *tracers*,
leaving concrete arrays as embedded constants.)
"""

from __future__ import annotations

import collections

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp


def run_scanned(step_n, state, n: int):
    """Advance ``n`` steps through ``step_n(state, bucket)`` in power-of-two
    buckets (plus a single 3-bucket size), so arbitrary ``n`` costs at most
    ~2*log2(n) distinct XLA compilations ever (a direct static-n scan would
    recompile for every new chunk length, e.g. the tail of an integrate
    interval).

    Buckets of size 1 are avoided (except ``n == 1`` itself): XLA fully
    inlines a ``length=1`` scan and re-fuses its body, which perturbs the
    result at the last bit relative to the loop-compiled ``length>=2`` form
    — an odd tail is dispatched as ``2+3`` instead of ``4+1`` so that two
    program variants sharing the step math (the plain and sentinel-armed
    chunks, models/navier.py) stay BIT-identical whenever their schedules
    agree."""
    for bucket in scan_buckets(n):
        state = step_n(state, bucket)
    return state


def scan_buckets(n: int) -> list:
    """The static bucket schedule :func:`run_scanned` dispatches for ``n``
    steps (in order).  Exposed so the warm pool can AOT-compile exactly the
    executables a ``chunk_steps``-sized dispatch will need — one source of
    truth for the decomposition."""
    out = []
    remaining = int(n)
    while remaining > 0:
        if remaining == 3:
            bucket = 3
        else:
            bucket = 1 << (remaining.bit_length() - 1)
            if bucket > 1 and remaining - bucket == 1:
                bucket //= 2  # leave a 3-tail instead of a 1-tail
        out.append(bucket)
        remaining -= bucket
    return out


def hoist_constants(fn, *example):
    """Return ``(converted, consts)`` where ``converted(consts, *args)``
    computes ``fn(*args)`` with every captured constant passed explicitly.

    ``example`` are abstract or concrete sample arguments (pytrees allowed).
    """
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*example)
    # device-resident, deduplicated by object identity
    seen: dict[int, int] = {}
    consts = []
    index = []
    for c in closed.consts:
        key = id(c)
        if key not in seen:
            seen[key] = len(consts)
            consts.append(jnp.asarray(c))
        index.append(seen[key])
    out_tree = jax.tree.structure(out_shape)

    def converted(consts, *args):
        flat_args, _ = jax.tree.flatten(args)
        expanded = [consts[i] for i in index]
        # jax.extend.core is the stable replay API (jax.core.eval_jaxpr is
        # deprecated); ClosedJaxpr accepts runtime tracers as consts, which is
        # exactly the hoisting trick
        replay = jex_core.jaxpr_as_fun(jex_core.ClosedJaxpr(closed.jaxpr, expanded))
        out_flat = replay(*flat_args)
        return jax.tree.unflatten(out_tree, out_flat)

    converted.jaxpr = closed.jaxpr  # what was traced, for whoever counts in it
    return converted, consts


def equations(jaxpr):
    """Every equation of ``jaxpr`` and of every jaxpr its equations carry
    (``pjit``, ``cond``, ``scan``, a custom rule): the traced program, not its
    launches, so the body of a loop comes once."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr holds one
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


def dot_generals_by_operand(jaxpr) -> collections.Counter:
    """``{dtype name: count}`` of the ``dot_general`` equations of ``jaxpr``,
    sub-programs included (:func:`equations`), each by the wider of its two
    operand types: which arithmetic a program's matrix products were traced
    in."""
    return collections.Counter(
        max((v.aval.dtype for v in eqn.invars), key=lambda d: d.itemsize).name
        for eqn in equations(jaxpr)
        if eqn.primitive.name == "dot_general"
    )


def _count(jaxpr, primitive: str) -> int:
    """The equations of ``jaxpr`` called ``primitive``, sub-programs included:
    the traced program, so a scan's length does not multiply the count."""
    return sum(eqn.primitive.name == primitive for eqn in equations(jaxpr))


def reverses(jaxpr) -> int:
    """The ``rev`` equations of ``jaxpr``, sub-programs included: the array
    flips of the parity folds (ops/folded.py), none below their size gate."""
    return _count(jaxpr, "rev")


def gathers(jaxpr) -> int:
    """The ``gather`` equations of ``jaxpr``, sub-programs included: the index
    gathers of a step (the circular folds of the periodic axes' transforms in
    ops/folded.py, the conjugate pairing of the Hermitian projection)."""
    return _count(jaxpr, "gather")
