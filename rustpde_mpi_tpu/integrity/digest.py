"""On-device state digests + the deterministic bitflip mutation.

The digest is a cheap fold over the bit patterns of every state leaf:
bitcast to uint32 words, positionally mixed (so transpositions and
offsetting paired flips cannot cancel in the commutative reductions),
then reduced by BOTH a wraparound sum and an XOR tree, combined with
Knuth multiplicative hashing.  Properties the integrity layer rests on:

* **deterministic** — integer arithmetic only, no rounding: the same
  state yields the same digest on every dispatch, layout, and shard
  partitioning (sum/xor are exact under reordering),
* **layout-invariant** — positions are LOGICAL indices (broadcasted
  iota), so a solo state, the same state as one vmapped ensemble member,
  and the same state pencil-sharded across a mesh all digest equal,
* **read-only** — a pure consumer of the state, like the sentinel
  reductions: trajectories are bit-identical digest-on vs digest-off,
* **single-bit sensitive** — any one flipped bit changes the XOR word
  and the positional mix, so the digest always moves.

This is an SDC *detector*, not a cryptographic MAC: an adversary could
collide it, a random upset practically cannot.

Everything here is traceable (jit / vmap / shard-safe); jax is imported
inside the functions so the module surface stays import-light.
"""

from __future__ import annotations

import numpy as np

#: 2^32 / golden ratio — Knuth's multiplicative-hash constant (odd, so
#: multiplication mod 2^32 is a bijection: no information is shed when
#: folding leaves/words together)
_GOLD = np.uint32(0x9E3779B1)
_KNUTH = np.uint32(2654435761)
#: FNV-1a offset basis — the fold seed
_SEED = np.uint32(0x811C9DC5)


def _leaf_digest(x):
    """uint32 digest of ONE array (any real/complex/bool/int dtype)."""
    import jax.numpy as jnp
    from jax import lax

    from ..parallel.mesh import constrain

    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        return _leaf_digest(jnp.real(x)) * _GOLD + _leaf_digest(jnp.imag(x))
    if x.dtype == jnp.bool_:
        bits = x.astype(jnp.uint32)
    elif x.dtype.itemsize >= 4:
        # same- or double-width bitcast: f64/i64 gain a trailing dim of 2
        # uint32 words, f32/i32 map 1:1 — either way every payload bit
        # lands in exactly one word
        bits = lax.bitcast_convert_type(x, jnp.uint32)
    else:
        bits = lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    if bits.ndim == 0:
        bits = bits[None]
    # positional mix: h(i0,...,ik) folds every logical index in, making
    # the otherwise-commutative reductions position-sensitive
    h = None
    for d in range(bits.ndim):
        i = lax.broadcasted_iota(jnp.uint32, bits.shape, d)
        h = i if h is None else h * jnp.uint32(1000003) + i
    mixed = bits ^ (h * _GOLD)
    # under a mesh: whole on every device first.  No backend sums an xor over
    # devices ("Unsupported reduction computation"), and a leaf whose sharded
    # extent divides the mesh is really cut (parallel/mesh.py)
    mixed = constrain(mixed, (None,) * mixed.ndim)
    axes = tuple(range(mixed.ndim))
    s = jnp.sum(mixed, dtype=jnp.uint32)
    xo = lax.reduce(mixed, jnp.uint32(0), lax.bitwise_xor, axes)
    return xo + s * _KNUTH


def digest_tree(state):
    """uint32 digest of a state pytree (scalar; ``(k,)`` under vmap).

    The per-leaf digests fold sequentially with a bijective multiplier,
    so the combined digest is order-sensitive across leaves (swapping
    velx/vely changes it) while each leaf's own reduction stays
    layout-invariant."""
    import jax
    import jax.numpy as jnp

    d = jnp.uint32(_SEED)
    for leaf in jax.tree_util.tree_leaves(state):
        d = d * _GOLD + _leaf_digest(jnp.asarray(leaf))
    return d


def default_flip_bit(dtype) -> int:
    """The mantissa MSB for the dtype's REAL component: flipping it is
    visibly wrong (O(1) relative error in that coefficient) yet provably
    finite — the exponent and sign are untouched, so no NaN/Inf can be
    minted and the CFL sentinel stays quiet."""
    real = np.empty(0, dtype).real.dtype
    return 51 if real.itemsize == 8 else 22


def flip_one_bit(arr, index: tuple, bit: int):
    """XOR one bit of one element (on device, bitcast — no rounding).

    ``index`` is a full multi-index into ``arr``; complex arrays flip in
    the real component.  Returns a new array (pure)."""
    import jax.numpy as jnp
    from jax import lax

    if jnp.issubdtype(arr.dtype, jnp.complexfloating):
        flipped = flip_one_bit(jnp.real(arr), index, bit)
        return lax.complex(flipped, jnp.imag(arr)).astype(arr.dtype)
    uint = jnp.uint64 if arr.dtype.itemsize == 8 else jnp.uint32
    bits = lax.bitcast_convert_type(arr, uint)
    bits = bits.at[index].set(bits[index] ^ uint(1 << bit))
    return lax.bitcast_convert_type(bits, arr.dtype)


def flip_state_bit(state, step: int, member: int | None = None,
                   col: int | None = None, bit: int | None = None):
    """Deterministically flip one spectral-coefficient bit in a state.

    The target leaf is ``temp`` (first field otherwise), the row is
    hashed from ``step`` (every process computes the same position, so a
    scoped injection stays a consistent collective), ``col`` pins the
    last (pencil) axis — the host-scope hook: the caller picks a column
    owned by the scoped host's devices — and ``member`` restricts the
    flip to one ensemble member's leading-axis slice.  Returns
    ``(new_state, info_dict)``."""
    name = "temp" if hasattr(state, "temp") else state._fields[0]
    arr = getattr(state, name)
    shape = arr.shape[1:] if member is not None else arr.shape
    n_last = int(shape[-1])
    c = int(col) if col is not None else int(step * 40503) % n_last
    idx = [int(step * int(_KNUTH)) % int(n) for n in shape[:-1]] + [c]
    if member is not None:
        idx = [int(member)] + idx
    if bit is None:
        bit = default_flip_bit(arr.dtype)
    flipped = flip_one_bit(arr, tuple(idx), int(bit))
    info = {"leaf": name, "index": tuple(idx), "bit": int(bit),
            "member": member}
    return state._replace(**{name: flipped}), info
