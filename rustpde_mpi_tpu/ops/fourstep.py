"""Four-step (Bailey) FFT factorizations as batched MXU GEMMs.

The reference outsources its transforms to rustfft/rustdct — O(n log n)
recursive FFTs (/root/reference/Cargo.toml:17 via funspace, SURVEY.md S2.2).
A literal radix-2 FFT is the wrong shape for a TPU: log2(n) sequential
stages of tiny butterflies starve the MXU.  The TPU-native equivalent is the
*four-step* factorization n = n1*n2:

    X[k2 + n2*k1] = sum_{j1} w1^{j1 k1} [ w^{j1 k2} sum_{j2} w2^{j2 k2}
                                          x[j1 + n1*j2] ]

i.e. (1) reshape, (2) a length-n2 DFT over all n1*batch lanes, (3) an
elementwise twiddle, (4) a length-n1 DFT — O(n*(n1+n2)) flops instead of the
dense transform's O(n^2).  Complex arithmetic is *blocked into single real
GEMMs*: the cos/sin matrix pair and the Re/Im operand pair are stacked so
each stage is ONE matrix product with a 2x contraction dim — measured faster
on the v5e MXU than the 4-GEMM formulation (half-sized K starves the 128x128
systolic array) and the TPU path keeps every spectrum real anyway.
Real-input (r2c) transforms compute only the k2 half spectrum in stage 2
(Hermitian mirror is a slice+flip) and only k1 <= n1//2 in stage 4;
real-*output* transforms (the DCT cores, the c2r synthesis) drop the
imaginary accumulators of their final stage.

The Chebyshev DCT-I rides the same core: the cosine kernel of size N+1 is
the real part of the length-2N r2c DFT of the even extension, so both the
analysis and the synthesis direction reduce to ``RfftPlan.re`` plus diagonal
pre/post scalings.

Everything here is exact to reassociation; tests pin equality against the
dense transform matrices at 1e-12 (f64).
"""

from __future__ import annotations


import numpy as np

import jax.numpy as jnp

from .. import config

# NOTE: _MODE/_MIN are re-read from the environment on every enabled() call
# (they are cheap lookups), so tests/scripts may toggle RUSTPDE_FOURSTEP*
# after import.  Plans already built into a Base/Space are NOT invalidated —
# transform path selection is construction-time, like every other operator
# choice in the package (rebuild the Space to change it).  config.X64 is
# process-level (jax_enable_x64 at import) and cannot toggle mid-process.
_MODE = config.env_get("RUSTPDE_FOURSTEP", "auto")
# Per-kind auto thresholds on the DFT length, measured on the v5e in f32
# (scripts/bench_transforms.py): below these the
# folded dense GEMM wins (it is one well-shaped MXU op; the factored path's
# smaller-K stages + twiddle/mirror passes only pay off once the dense
# O(n^2) bill is large enough).  Measured ratios dense/fourstep: r2c 0.44x
# @1024 -> 2.1x @2048; c2c 2.0x @1024, 2.9x @2048.  The DCT core never wins
# at the production grid sizes: a batch-1025 microbench showed 1.2x at core
# 4096, but in model context at 2049^2 (batch 2049) the dense pair runs
# 1.13 ms vs 2.22 ms fourstep — so the DCT gate sits above every current
# grid (re-measure before lowering).
_MIN = {
    "dft": int(config.env_get("RUSTPDE_FOURSTEP_MIN", "2048")),
    "c2c": int(config.env_get("RUSTPDE_FOURSTEP_MIN_C2C", "1024")),
    "dct": int(config.env_get("RUSTPDE_FOURSTEP_MIN_DCT", "8192")),
}


def enabled(n: int, kind: str = "dft") -> bool:
    """Whether the four-step path should replace the dense transform GEMM for
    a length-n DFT of the given kind ("dft" = r2c/c2r, "c2c", "dct" — n is
    the *DFT core* length, 2N for a size-(N+1) DCT-I).  ``RUSTPDE_FOURSTEP``:
    "auto" (default; per-kind measured thresholds above), "1" (whenever
    factorable, incl. small sizes — used by tests), "0" (never).

    Auto never engages in x64 mode: measured on the v5e in emulated f64 the
    factored path loses at EVERY size (0.18-0.49x; the non-MXU twiddle/
    mirror/stacking passes emulate far worse than the dense GEMM's extra
    flops cost — same asymmetry as the cumsum derivative)."""
    mode = config.env_get("RUSTPDE_FOURSTEP", _MODE)
    if mode == "0":
        return False
    if mode == "1":
        return viable(n, 4)
    if config.X64:
        return False
    env_min = {
        "dft": config.env_get("RUSTPDE_FOURSTEP_MIN"),
        "c2c": config.env_get("RUSTPDE_FOURSTEP_MIN_C2C"),
        "dct": config.env_get("RUSTPDE_FOURSTEP_MIN_DCT"),
    }.get(kind)
    lo = int(env_min) if env_min else _MIN.get(kind, _MIN["dft"])
    return n >= lo and viable(n)


def default_factors(n: int) -> tuple[int, int]:
    """Split n = n1*n2 with n1 <= n2, n1 as close to sqrt(n) as divisibility
    allows (balanced stages minimize total GEMM flops ~ n*(n1+n2)).
    ``RUSTPDE_FOURSTEP_N1`` forces n1 for hardware tuning."""
    forced = config.env_get("RUSTPDE_FOURSTEP_N1")
    if forced:
        n1 = int(forced)
        if n % n1 == 0:
            a, b = sorted((n1, n // n1))
            return a, b
    n1 = int(np.sqrt(n))
    while n1 > 1 and n % n1 != 0:
        n1 -= 1
    return n1, n // n1


def viable(n: int, min_factor: int = 8) -> bool:
    """A four-step plan only pays off when both stages are real GEMMs."""
    n1, _ = default_factors(n)
    return n1 >= min_factor


def _twiddle(n1: int, n2: int, n: int, transpose: bool = False):
    """cos/sin(2pi j1 k2 / n) tables; (n2, n1) rows k2 (or transposed)."""
    k2 = np.arange(n2)[:, None]
    j1 = np.arange(n1)[None, :]
    ang = 2.0 * np.pi * k2 * j1 / n
    c, s = np.cos(ang), np.sin(ang)
    if transpose:
        return c.T, s.T
    return c, s


class RfftPlan:
    """Real-input forward DFT of length n (batched along the other dims).

    ``split(x)``  -> (2m, ...) stacked [Re; Im] of the *unnormalized* rfft,
    ``re(x)``     -> (m, ...) real part only (the DCT-I core),
    m = n//2 + 1.  ``x`` must already have the transform axis moved to 0.
    """

    def __init__(self, n: int, to_dev, n1: int | None = None):
        self.n = n
        if n1 is None:
            n1, n2 = default_factors(n)
        else:
            n2 = n // n1
        assert n1 * n2 == n
        self.n1, self.n2 = n1, n2
        self.m = n // 2 + 1
        m2 = n2 // 2 + 1
        self.m2 = m2
        h1 = n1 // 2 + 1
        self.h1 = h1
        j2 = np.arange(n2)[None, :]
        k2 = np.arange(m2)[:, None]
        ang2 = 2.0 * np.pi * k2 * j2 / n2
        # stage 2: one (2*m2 x n2) GEMM producing [Re; Im] rows
        self._m2mat = to_dev(np.concatenate([np.cos(ang2), -np.sin(ang2)], axis=0))
        twc, tws = _twiddle(n1, n2, n)
        self._twc = to_dev(twc)  # (n2, n1)
        self._tws = to_dev(tws)
        j1 = np.arange(n1)[None, :]
        k1h = np.arange(h1)[:, None]
        ang1 = 2.0 * np.pi * k1h * j1 / n1
        c1, s1 = np.cos(ang1), np.sin(ang1)
        # stage 4 blocked over the stacked [Zre | Zim] contraction:
        #   Re rows: [ C1 | S1 ],  Im rows: [ -S1 | C1 ]
        self._m4_re = to_dev(np.concatenate([c1, s1], axis=1))  # (h1, 2n1)
        self._m4_full = to_dev(
            np.block([[c1, s1], [-s1, c1]])  # (2h1, 2n1)
        )

    # -- stages ------------------------------------------------------------

    def _stage123(self, x):
        """x: (n, ...) real -> twiddled Z stacked (n2, 2*n1, ...)."""
        n1, n2, m2 = self.n1, self.n2, self.m2
        batch = x.shape[1:]
        a = x.reshape((n2, n1) + batch)  # a[j2, j1] = x[j1 + n1*j2]
        y = jnp.tensordot(self._m2mat, a, axes=([1], [0]))  # (2m2, n1, ...)
        yre, yim = y[:m2], y[m2:]
        # Hermitian mirror to the full k2 range: rows n2-k2 for k2=m2..n2-1
        mir = slice(1, n2 - m2 + 1)
        yre = jnp.concatenate([yre, jnp.flip(yre[mir], 0)], axis=0)
        yim = jnp.concatenate([yim, -jnp.flip(yim[mir], 0)], axis=0)
        shape = (n2, n1) + (1,) * len(batch)
        twc = self._twc.reshape(shape)
        tws = self._tws.reshape(shape)
        # w^{j1 k2} = cos - i sin
        zre = twc * yre + tws * yim
        zim = twc * yim - tws * yre
        return jnp.concatenate([zre, zim], axis=1)  # (n2, 2n1, ...)

    def _finalize(self, block, rows: int):
        """(n2, rows_per_part*?, ...) stage-4 output -> k = k2 + n2*k1 order:
        transposing (n2, h1) to (h1, n2) and flattening C-order lists index
        k1*n2 + k2 = k; slice to m."""
        out = jnp.moveaxis(block, 1, 0)  # (rows, n2, ...)
        return out.reshape((rows * self.n2,) + out.shape[2:])[: self.m]

    def re(self, x):
        """Re(rfft(x)) along axis 0, unnormalized."""
        z = self._stage123(x)
        blk = jnp.einsum("kj,cj...->ck...", self._m4_re, z)  # (n2, h1, ...)
        return self._finalize(blk, self.h1)

    def split(self, x):
        """[Re; Im] of rfft(x) along axis 0, unnormalized (2m rows)."""
        h1 = self.h1
        z = self._stage123(x)
        blk = jnp.einsum("kj,cj...->ck...", self._m4_full, z)  # (n2, 2h1, ...)
        re = self._finalize(blk[:, :h1], h1)
        im = self._finalize(blk[:, h1:], h1)
        return jnp.concatenate([re, im], axis=0)


class IrfftPlan:
    """Real-output inverse DFT: split spectrum [Re; Im] (2m rows) ->
    ``v_j = Re sum_{k=0}^{n-1} chat_k e^{+2pi i jk/n}`` with chat the
    Hermitian extension weighted exactly like
    ops/fourier.split_backward_matrix (normalization is the caller's)."""

    def __init__(self, n: int, to_dev, n1: int | None = None):
        self.n = n
        if n1 is None:
            n1, n2 = default_factors(n)
        else:
            n2 = n // n1
        assert n1 * n2 == n
        self.n1, self.n2 = n1, n2
        self.m = n // 2 + 1
        j1 = np.arange(n1)[:, None]
        k1 = np.arange(n1)[None, :]
        ang1 = 2.0 * np.pi * j1 * k1 / n1
        c1, s1 = np.cos(ang1), np.sin(ang1)
        # stage 2 blocked over stacked [Wre; Wim] (contract k1, sign +):
        #   Gre rows: [ C1 | -S1 ],  Gim rows: [ S1 | C1 ]
        self._m2 = to_dev(np.block([[c1, -s1], [s1, c1]]))  # (2n1, 2n1)
        twc, tws = _twiddle(n1, n2, n, transpose=True)  # (n1, n2)
        self._twc = to_dev(twc)
        self._tws = to_dev(tws)
        j2 = np.arange(n2)[:, None]
        k2 = np.arange(n2)[None, :]
        ang2 = 2.0 * np.pi * j2 * k2 / n2
        # stage 4 real output (sign +): v = [ C2 | -S2 ] @ [Hre; Him]
        self._m4 = to_dev(np.concatenate([np.cos(ang2), -np.sin(ang2)], axis=1))

    def apply(self, s):
        """s: (2m, ...) split spectrum, transform axis already moved to 0."""
        n, n1, n2, m = self.n, self.n1, self.n2, self.m
        batch = s.shape[1:]
        re, im = s[:m], s[m:]
        # Hermitian extension chat[k], k=0..n-1 (interior modes twice)
        mir = slice(1, n - m + 1)
        cre = jnp.concatenate([re, jnp.flip(re[mir], 0)], axis=0)
        cim = jnp.concatenate([im, -jnp.flip(im[mir], 0)], axis=0)
        w = jnp.concatenate(
            [cre.reshape((n1, n2) + batch), cim.reshape((n1, n2) + batch)], axis=0
        )  # (2n1, n2, ...): [Wre; Wim] with W[k1, k2] = chat[n2*k1 + k2]
        g = jnp.tensordot(self._m2, w, axes=([1], [0]))  # (2n1, n2, ...)
        gre, gim = g[:n1], g[n1:]
        shape = (n1, n2) + (1,) * len(batch)
        twc = self._twc.reshape(shape)
        tws = self._tws.reshape(shape)
        hre = twc * gre - tws * gim
        him = twc * gim + tws * gre
        h = jnp.concatenate([hre, him], axis=1)  # (n1, 2n2, ...)
        v = jnp.einsum("mk,jk...->mj...", self._m4, h)  # (n2, n1, ...)
        return v.reshape((n,) + batch)  # (j2, j1) flattens to j1 + n1*j2


class C2cPlan:
    """Complex-to-complex DFT on split re/im planes.

    ``sign=-1`` is the forward convention (e^{-2pi i jk/n}), ``sign=+1`` the
    inverse (no 1/n — normalization is the caller's).  Input and output are
    ``(re, im)`` pairs with the transform axis moved to 0.
    """

    def __init__(self, n: int, to_dev, sign: float, n1: int | None = None):
        self.n = n
        self.sign = float(sign)
        if n1 is None:
            n1, n2 = default_factors(n)
        else:
            n2 = n // n1
        assert n1 * n2 == n
        self.n1, self.n2 = n1, n2
        sg = self.sign
        j2 = np.arange(n2)[None, :]
        k2 = np.arange(n2)[:, None]
        ang2 = 2.0 * np.pi * k2 * j2 / n2
        c2, s2 = np.cos(ang2), sg * np.sin(ang2)
        # stage 2 over stacked [Are; Aim]: Yre = C*Are - sg*S*Aim, etc.
        self._m2 = to_dev(np.block([[c2, -s2], [s2, c2]]))  # (2n2, 2n2)
        twc, tws = _twiddle(n1, n2, n)
        self._twc = to_dev(twc)
        self._tws = to_dev(sg * tws)
        j1 = np.arange(n1)[None, :]
        k1 = np.arange(n1)[:, None]
        ang1 = 2.0 * np.pi * k1 * j1 / n1
        c1, s1 = np.cos(ang1), sg * np.sin(ang1)
        self._m4 = to_dev(np.block([[c1, -s1], [s1, c1]]))  # (2n1, 2n1)

    def apply(self, xre, xim):
        n1, n2 = self.n1, self.n2
        batch = xre.shape[1:]
        a = jnp.concatenate(
            [xre.reshape((n2, n1) + batch), xim.reshape((n2, n1) + batch)], axis=0
        )  # (2n2, n1, ...)
        y = jnp.tensordot(self._m2, a, axes=([1], [0]))  # (2n2, n1, ...)
        yre, yim = y[:n2], y[n2:]
        shape = (n2, n1) + (1,) * len(batch)
        twc = self._twc.reshape(shape)
        tws = self._tws.reshape(shape)
        zre = twc * yre - tws * yim
        zim = twc * yim + tws * yre
        z = jnp.concatenate([zre, zim], axis=1)  # (n2, 2n1, ...)
        b = jnp.einsum("kj,cj...->ck...", self._m4, z)  # (n2, 2n1, ...)
        # (k2, k1) -> k = k2 + n2*k1: transpose then flatten
        bre = jnp.moveaxis(b[:, :n1], 1, 0).reshape((self.n,) + batch)
        bim = jnp.moveaxis(b[:, n1:], 1, 0).reshape((self.n,) + batch)
        return bre, bim


class Dct1Plan:
    """Fast DCT-I cosine-kernel application of size n = N+1 (any N whose
    doubling 2N factors well): ``out_k = sum_j colw_j x_j cos(pi j k / N)``
    with the natural even-extension column weights colw = [1, 2, ..., 2, 1]
    — exactly ``Re(rfft(ext(x)))`` where ext is the length-2N even
    extension.  Both Chebyshev transform directions are diagonal scalings
    around this core (ops/chebyshev.analysis_matrix / synthesis_matrix)."""

    def __init__(self, n: int, to_dev, n1: int | None = None):
        self.n = n
        self.N = n - 1
        self._plan = RfftPlan(2 * self.N, to_dev, n1=n1)

    def apply(self, x):
        """x: (n, ...), transform axis already at 0 -> (n, ...)."""
        ext = jnp.concatenate([x, jnp.flip(x[1:-1], 0)], axis=0)
        return self._plan.re(ext)  # (N+1, ...) = (n, ...)
