"""Component-level timing of the confined Navier2D step (VERDICT r2 #5).

Times each building block of the 1025^2 step in isolation — transforms
(dense vs four-step), derivatives (GEMM vs cumsum), banded applies, ADI and
Poisson solves, and the full step — each as a jitted scan ended by
``block_until_ready``.

Usage:  [RUSTPDE_X64=0] python scripts/profile_step.py [--n 1025] [--iters 50]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, state, iters):
    """Per-iteration ms via a two-point slope: time scans of length iters and
    4*iters and divide the difference — the fixed per-dispatch cost cancels,
    leaving device time."""
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
    ap.add_argument("--n", type=int, default=1025)
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args()

    os.environ.setdefault("RUSTPDE_X64", "0")
    import jax.numpy as jnp
    import numpy as np

    from rustpde_mpi_tpu import Navier2D, config
    from rustpde_mpi_tpu.ops import fourstep

    n = args.n
    it = args.iters
    rdt = config.real_dtype()
    print(f"platform={config.default_platform()} n={n} dtype={np.dtype(rdt).name}")

    model = Navier2D(n, n, 1e9, 1.0, 1e-4, 1.0, "rbc", periodic=False)
    model.init_random(0.1)
    sp_f = model.field_space
    sp_u = model.velx_space
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.standard_normal((n, n)), dtype=rdt)
    rows = []

    def rec(name, ms):
        rows.append((name, ms))
        print(f"{name:42s} {ms:8.3f} ms")

    # full step
    step = model._make_step()
    from rustpde_mpi_tpu.utils.jit import hoist_constants

    step_cc, consts = hoist_constants(step, model.state)
    rec("full step", timeit(lambda s: step_cc(consts, s), model.state, it))

    # transforms: pure-space forward+backward_ortho pair (fast path auto)
    rec(
        "field fwd+bwd_ortho (fast DCT pair)",
        timeit(lambda a: sp_f.backward_ortho(sp_f.forward(a)), v, it),
    )
    base = sp_f.base_x

    def dense_pair(a):
        c = base._fwd_matrix.apply(base._fwd_matrix.apply(a, 0), 1)
        return base._synthesis_dev.apply(base._synthesis_dev.apply(c, 0), 1)

    rec("dense folded DCT pair (2 axes each way)", timeit(dense_pair, v, it))
    if base._dct_plan is not None:

        def fast_pair(a):
            c = base._fast_analysis(base._fast_analysis(a, 0), 1)
            return base._fast_synthesis(base._fast_synthesis(c, 0), 1)

        rec("fourstep DCT pair (2 axes each way)", timeit(fast_pair, v, it))

    # derivative: cumsum vs checker GEMM vs the sep trapezoid strips
    from rustpde_mpi_tpu.ops import transforms as tr

    rec("cheb_derivative cumsum (1 axis)", timeit(lambda a: tr.cheb_derivative(a, 1, 0), v, it))
    gm = base._gradient_dev(1)
    rec("gradient checker GEMM (1 axis)", timeit(lambda a: gm.apply(a, 0), v, it))
    if any(sp_u.sep):
        m_c = sp_u.base_x.m
        gs = sp_u.base_x._sep_dev(("grad", 1))
        vu_g = jnp.asarray(rng.standard_normal((m_c, n)), dtype=rdt)
        rec(
            f"gradient sep ({gs.kind}) (1 axis)",
            timeit(lambda a: gs.apply(a, 0)[:m_c], vu_g, it),
        )
        bg = sp_u.base_x._sep_dev(("bwd_grad", 1))
        rec(
            "bwd_grad fused synthesis-of-derivative (1 axis)",
            timeit(lambda a: bg.apply(a, 0)[:m_c], vu_g, it),
        )
    if all(sp_f.sep):
        rec(
            "forward_dealiased (2 axes, rows dropped)",
            timeit(sp_f.forward_dealiased, v, it),
        )

    # banded apply vs what it replaced (slice keeps the scan carry shape)
    st = sp_u.base_x._stencil_dev
    m_u = sp_u.base_x.m
    vu = jnp.asarray(rng.standard_normal((m_u, n)), dtype=rdt)
    rec(
        f"banded stencil apply ({st.kind})",
        timeit(lambda a: st.apply(a, 0)[:m_u], vu, it),
    )

    # solves: rhs is ortho-space (n rows per axis), solution composite (m) —
    # pad back to the carry shape
    rhs_u = jnp.asarray(rng.standard_normal((n, n)), dtype=rdt)

    def adi(a):
        out = model.solver_velx.solve(a)
        return jnp.pad(out, ((0, n - out.shape[0]), (0, n - out.shape[1])))

    rec("HholtzAdi solve (velx)", timeit(adi, rhs_u, it))

    def poi(a):
        out = model.solver_pres.solve(a)
        return jnp.pad(out, ((0, n - out.shape[0]), (0, n - out.shape[1])))

    rec("Poisson FastDiag solve", timeit(poi, rhs_u, it))

    # raw GEMM reference point: one folded dense transform-sized matmul
    big = base._synthesis_dev
    rec("single dense synthesis GEMM (1 axis)", timeit(lambda a: big.apply(a, 0), v, it))

    full = rows[0][1]
    print(f"\ncomponents sum context: full step = {full:.3f} ms")


if __name__ == "__main__":
    main()
