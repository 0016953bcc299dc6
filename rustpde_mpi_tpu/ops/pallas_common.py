"""What the three Pallas kernel modules share: where a kernel may run
interpreted, how much VMEM it asks Mosaic for, and the typed error a knob
raises at model build when the TPU compiler will not take the kernel.

A Pallas kernel runs interpreted ONLY on the CPU (the CI parity suite).  On
a TPU it compiles natively or the build fails with
:class:`PallasCompileRefused` quoting the compiler — it never falls back to
the interpreter and never gives way to the dense path behind the user's
back.
"""

from __future__ import annotations

import jax

LANE = 128
SUBLANE = 8

# Mosaic's default scoped-VMEM limit is 16 MiB; the fused kernels hold
# whole-width operands resident and need more.  Ask for what the blocks
# need, never for more than this share of the core's VMEM (the compiler
# keeps internal scratch of its own).
_VMEM_SHARE = 0.85


class PallasCompileRefused(RuntimeError):
    """A Pallas kernel selected by a knob cannot be compiled for this
    device (VMEM residency, a dtype Mosaic does not lower, an unsupported
    platform).  Raised at model build; the message quotes the refusal."""


def resolve_interpret(interpret: bool | None) -> bool:
    """The ``interpret`` flag a kernel class runs with: None picks True on
    the CPU (interpreter parity suite) and False on a TPU (native Mosaic).
    Asking for the interpreter anywhere but the CPU, or for any kernel on a
    platform that is neither, is refused."""
    platform = jax.devices()[0].platform
    if platform not in ("cpu", "tpu"):
        raise PallasCompileRefused(
            f"Pallas kernels here target the TPU (interpreted on the CPU for "
            f"tests); platform {platform!r} has no path"
        )
    if interpret is None:
        return platform == "cpu"
    if interpret and platform != "cpu":
        raise PallasCompileRefused(
            f"interpret=True on platform {platform!r}: kernels compile "
            "natively on a TPU, the interpreter is for the CPU suite only"
        )
    return bool(interpret)


def compiler_params(block_specs, scratch_elems: int, dtype):
    """``pltpu.CompilerParams`` with an explicit ``vmem_limit_bytes`` sized
    from the kernel's own blocks (``block_specs``: every in/out
    ``pl.BlockSpec``; ``scratch_elems``: elements of VMEM scratch): every
    pipelined block is double-buffered, scratch is single, plus half again
    for the body's temporaries."""
    import math

    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    blocks = sum(math.prod(spec.block_shape) for spec in block_specs)
    need = int(1.5 * (2 * blocks + scratch_elems) * np.dtype(dtype).itemsize)
    need += 4 << 20
    cap = int(_VMEM_SHARE * pltpu.get_tpu_info().vmem_capacity_bytes)
    return pltpu.CompilerParams(vmem_limit_bytes=min(need, cap))


def require_native_compile(knob: str, name: str, fn, *example) -> None:
    """Compile ``fn`` for the attached TPU NOW (model build) so that a
    compiler refusal surfaces as one typed error naming the knob that
    selected the kernel, instead of somewhere inside the step's trace.
    ``example`` are ``jax.ShapeDtypeStruct`` arguments."""
    try:
        jax.jit(fn).lower(*example).compile()
    except Exception as exc:  # noqa: BLE001 — re-raised typed, with the cause
        text = " ".join(f"{type(exc).__name__}: {exc}".split())
        raise PallasCompileRefused(
            f"{knob}: the TPU compiler refused kernel {name!r}: {text[:800]}"
        ) from exc
