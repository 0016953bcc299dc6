"""A/B transform microbench: dense folded GEMM vs four-step plans (VERDICT
r2 #1 'done' criterion).  Slope-timed (the per-dispatch fixed cost cancels).

Usage: RUSTPDE_X64=0 python scripts/bench_transforms.py [--iters 128]
       [--sizes 1024,2048] [--batch 1025] [--n1 0 (auto) | k]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, state, iters):
    import functools

    import jax
    import numpy as np

    def body(c, _):
        return fn(c), None

    @functools.partial(jax.jit, static_argnames=("length",))
    def run(s, length):
        return jax.lax.scan(body, s, None, length=length)[0]

    def once(length):
        out = run(state, length)
        leaf = jax.tree.leaves(out)[0]
        jax.block_until_ready(leaf)

    times = {}
    for length in (iters, 4 * iters):
        once(length)  # compile + warm
        best = float("inf")
        for _ in range(3):  # min-of-3: a one-chip machine shares its host's cores
            t0 = time.perf_counter()
            once(length)
            best = min(best, time.perf_counter() - t0)
        times[length] = best
    return (times[4 * iters] - times[iters]) / (3 * iters) * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=128)
    ap.add_argument("--sizes", default="1024,2048")
    ap.add_argument("--batch", type=int, default=1025)
    ap.add_argument("--n1", type=int, default=0)
    args = ap.parse_args()
    os.environ.setdefault("RUSTPDE_X64", "0")

    import jax.numpy as jnp
    import numpy as np

    from rustpde_mpi_tpu import config
    from rustpde_mpi_tpu.ops import chebyshev as chb
    from rustpde_mpi_tpu.ops import fourier as fou
    from rustpde_mpi_tpu.ops import fourstep
    from rustpde_mpi_tpu.ops.folded import FoldedMatrix

    rdt = config.real_dtype()
    to_dev = lambda m: jnp.asarray(np.asarray(m, dtype=rdt))  # noqa: E731
    rng = np.random.default_rng(0)
    B = args.batch
    n1 = args.n1 or None
    it = args.iters
    print(f"platform={config.default_platform()} dtype={np.dtype(rdt).name} batch={B}")

    for n in (int(s) for s in args.sizes.split(",")):
        v = to_dev(rng.standard_normal((n, B)))
        # --- DCT core of size n+1 (what a Chebyshev axis transform runs) ---
        np1 = n + 1
        vp = to_dev(rng.standard_normal((np1, B)))
        dense_f = FoldedMatrix(chb.analysis_matrix(np1), to_dev)
        t_dense = timeit(lambda a: dense_f.apply(a, 0), vp, it)
        plan = fourstep.Dct1Plan(np1, to_dev, n1=n1)
        t_fast = timeit(plan.apply, vp, it)
        f1, f2 = plan._plan.n1, plan._plan.n2
        print(
            f"DCT n={np1:5d}: dense {t_dense:7.3f} ms  fourstep({f1}x{f2})"
            f" {t_fast:7.3f} ms  ratio {t_dense / max(t_fast, 1e-9):5.2f}x"
        )
        # --- split r2c of size n ---
        dense_s = FoldedMatrix(fou.split_forward_matrix(n), to_dev)
        m = n // 2 + 1

        # slice to the input shape so the timing scan carry is well-typed
        t_dense = timeit(lambda a: dense_s.apply(a, 0)[:n], v, it)
        rplan = fourstep.RfftPlan(n, to_dev, n1=n1)
        t_fast = timeit(lambda a: rplan.split(a)[:n], v, it)
        print(
            f"r2c n={n:5d}: dense {t_dense:7.3f} ms  fourstep({rplan.n1}x{rplan.n2})"
            f" {t_fast:7.3f} ms  ratio {t_dense / max(t_fast, 1e-9):5.2f}x"
        )
        # --- irfft of size n ---
        s2m = to_dev(rng.standard_normal((2 * m, B)))
        dense_b = FoldedMatrix(fou.split_backward_matrix(n), to_dev)
        # pad the (n, B) synthesis back to the (2m, B) carry shape
        t_dense = timeit(
            lambda a: jnp.concatenate(
                [dense_b.apply(a, 0), jnp.zeros_like(a[: 2 * m - n])], 0
            ),
            s2m,
            it,
        )
        iplan = fourstep.IrfftPlan(n, to_dev, n1=n1)
        t_fast = timeit(
            lambda a: jnp.concatenate([iplan.apply(a), jnp.zeros_like(a[: 2 * m - n])], 0),
            s2m,
            it,
        )
        print(
            f"c2r n={n:5d}: dense {t_dense:7.3f} ms  fourstep({iplan.n1}x{iplan.n2})"
            f" {t_fast:7.3f} ms  ratio {t_dense / max(t_fast, 1e-9):5.2f}x"
        )
        # --- c2c of size n (both split planes) ---
        w = to_dev(rng.standard_normal((2, n, B)))
        ccos = FoldedMatrix(fou.dft_cos_matrix(n), to_dev)
        csin = FoldedMatrix(fou.dft_sin_matrix(n), to_dev)

        def dense_c2c(a):
            re = ccos.apply(a[0], 0) + csin.apply(a[1], 0)
            im = ccos.apply(a[1], 0) - csin.apply(a[0], 0)
            return jnp.stack([re, im])

        t_dense = timeit(dense_c2c, w, it)
        cplan = fourstep.C2cPlan(n, to_dev, sign=-1.0, n1=n1)

        def fast_c2c(a):
            re, im = cplan.apply(a[0], a[1])
            return jnp.stack([re, im])

        t_fast = timeit(fast_c2c, w, it)
        print(
            f"c2c n={n:5d}: dense {t_dense:7.3f} ms  fourstep({cplan.n1}x{cplan.n2})"
            f" {t_fast:7.3f} ms  ratio {t_dense / max(t_fast, 1e-9):5.2f}x"
        )


if __name__ == "__main__":
    main()
