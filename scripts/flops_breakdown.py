"""Exact per-component dot_general FLOPs of one Navier2D step (trace-only).

Answers "which GEMM family dominates the step" without running anything:
every component is traced with jax.make_jaxpr and its dot_general flops
summed (utils/profiling._jaxpr_dot_flops — the same counter the MFU
estimate uses).  This is the *algebraic* decomposition; wall-time shares
additionally depend on per-op efficiency (f64 emulation factors, GEMM
shapes), which scripts/profile_step.py measures on-chip.

Usage:  [RUSTPDE_X64=1] python scripts/flops_breakdown.py [--n 2049]
        [--periodic] [--nx 1024]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2049)
    ap.add_argument("--nx", type=int, default=None, help="periodic x size")
    ap.add_argument("--periodic", action="store_true")
    args = ap.parse_args()

    # trace on CPU unconditionally: this script counts flops from the
    # jaxpr and never needs the chip (which another process may hold)
    os.environ.setdefault("RUSTPDE_FORCE_TPU_PATH", "1")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from rustpde_mpi_tpu import Navier2D, config
    from rustpde_mpi_tpu.utils.profiling import _jaxpr_dot_flops

    n = args.n
    nx = args.nx or (n - 1 if args.periodic else n)
    model = Navier2D(
        nx, n, 1e9, 1.0, 1e-4 if n <= 1025 else 5e-5, 1.0, "rbc",
        periodic=args.periodic,
    )
    print(
        f"n={nx}x{n} periodic={args.periodic} "
        f"x64={config.X64} sep={model.temp_space.sep}"
    )

    def flops(fn, *ex):
        return _jaxpr_dot_flops(jax.make_jaxpr(fn)(*ex).jaxpr)

    st = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), model.state
    )
    total = flops(model._make_step(), st)

    sp_t, sp_u, sp_v = model.temp_space, model.velx_space, model.vely_space
    sp_f, sp_p, sp_q = model.field_space, model.pres_space, model.pseu_space
    scale = model.scale
    ex = {
        "t": st.temp, "u": st.velx, "v": st.vely, "p": st.pres, "q": st.pseu,
        "phys": jax.ShapeDtypeStruct(sp_f.shape_physical, config.real_dtype()),
        "ortho": jax.ShapeDtypeStruct(
            (sp_f.shape_spectral if not args.periodic else sp_f.shape_spectral),
            config.real_dtype() if not sp_f.spectral_is_complex else sp_f.spectral_dtype(),
        ),
    }

    rows = []

    def rec(name, fl, count=1):
        rows.append((name, fl * count))
        pct = 100.0 * fl * count / total if total else 0.0
        print(f"{name:46s} {fl * count / 1e9:9.2f} GF  {pct:5.1f}%")

    print(f"{'FULL STEP':46s} {total / 1e9:9.2f} GF  100.0%")
    # convection-chain syntheses (the hybrid/fast-key family)
    rec(
        "conv syntheses: 2x backward_fast(vel)",
        flops(lambda a: sp_u.backward_fast(a), ex["u"])
        + flops(lambda a: sp_v.backward_fast(a), ex["v"]),
    )
    bg = 0.0
    for sp, e in ((sp_u, ex["u"]), (sp_v, ex["v"]), (sp_t, ex["t"])):
        for d in ((1, 0), (0, 1)):
            bg += flops(
                lambda a, _sp=sp, _d=d: _sp.backward_gradient(a, _d, scale, fast=True),
                e,
            )
    rec("conv syntheses: 6x backward_gradient", bg)
    try:
        fd = flops(lambda a: sp_f.forward_dealiased(a, fast=True), ex["phys"])
    except ValueError:
        fd = flops(lambda a: sp_f.forward(a), ex["phys"])
    rec("conv forwards: 3x forward_dealiased", fd, 3)
    # implicit solves: rhs lives in the ORTHO (field) space, like the step's
    # to_ortho/conv outputs
    ortho_ex = jax.ShapeDtypeStruct(
        sp_f.shape_spectral,
        config.real_dtype() if not sp_f.spectral_is_complex
        else sp_f.spectral_dtype(),
    )
    so = sum(
        flops(sol.solve, ortho_ex)
        for sol in (model.solver_velx, model.solver_vely, model.solver_temp)
    )
    rec("3x ADI Helmholtz solve", so)
    rec("Poisson solve (pseudo-pressure)", flops(model.solver_pres.solve, ortho_ex))
    # gradients / projection
    g = flops(lambda a: sp_p.gradient(a, (1, 0), scale), ex["p"]) + flops(
        lambda a: sp_p.gradient(a, (0, 1), scale), ex["p"]
    )
    rec("2x pres gradient (rhs)", g)
    g = flops(lambda a: sp_u.gradient(a, (1, 0), scale), ex["u"]) + flops(
        lambda a: sp_v.gradient(a, (0, 1), scale), ex["v"]
    )
    rec("divergence (2 gradients)", g)
    if model._proj_grad is not None:
        gx0, gx1, gy0, gy1 = model._proj_grad
        ax = 0
        rec(
            "projection correction (fused proj-grad)",
            flops(lambda a: gx1.apply(gx0.apply(a, ax), ax + 1), ex["q"])
            + flops(lambda a: gy1.apply(gy0.apply(a, ax), ax + 1), ex["q"]),
        )
    accounted = sum(f for _, f in rows)
    print(
        f"{'(other: stencils, to/from_ortho, obs-free)':46s} "
        f"{(total - accounted) / 1e9:9.2f} GF  {100.0 * (total - accounted) / total:5.1f}%"
    )
    conv = sum(f for name, f in rows if name.startswith("conv"))
    print(
        f"\nconvection-transform family (hybrid/fast-key target): "
        f"{100.0 * conv / total:.1f}% of step dot-flops"
    )


if __name__ == "__main__":
    main()
