"""Which implementation a kernel entry point runs — one rule for every op.

A Pallas kernel has three ways to run: compiled by Mosaic (a TPU backend),
interpreted (any backend, for parity tests), or replaced by its pure-XLA
reference.  ``impl=None``/``"auto"`` picks from the one thing the code can
observe, the JAX backend: ``tpu`` compiles the kernel, ``cpu`` (which has
no Mosaic) runs the reference.  Any OTHER backend is an error, not a
quiet trip through the reference: an accelerator that reports an
unexpected platform name must not end up serving the XLA path while the
caller believes the kernel ran.
"""

from __future__ import annotations

from typing import Optional

import jax

IMPLS = ("pallas", "xla", "interpret")


def on_tpu() -> bool:
    """True on a TPU backend, False on CPU, RuntimeError anywhere else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return True
    if backend == "cpu":
        return False
    raise RuntimeError(
        f"backend {backend!r} is neither tpu nor cpu: there is no default "
        "kernel path for it — choose impl/interpret explicitly")


def matmul_operand_dtype():
    """The type a float32 matmul's operands are rounded to by the backend
    itself, or None where it multiplies them as they are.  On a TPU, at
    jax's default matmul precision (``jax_default_matmul_precision`` unset
    or one of its single-pass bfloat16 names), a float32 dot is ONE
    bfloat16 MXU pass: a weight rounded to bfloat16 once gives the same
    products.  A CPU, or a user who asked jax for more (``float32``,
    ``highest``, ``BF16_BF16_F32_X3`` ...), gets None: nothing may be
    rounded ahead."""
    if not on_tpu():
        return None
    if jax.config.jax_default_matmul_precision not in (
            None, "default", "bfloat16", "BF16_BF16_F32"):
        return None
    return jax.numpy.bfloat16


def resolve_impl(impl: Optional[str]) -> str:
    """``auto``/None -> ``pallas`` on TPU, ``xla`` on CPU; an explicit
    ``pallas`` | ``xla`` | ``interpret`` wins."""
    if impl in (None, "auto"):
        return "pallas" if on_tpu() else "xla"
    if impl not in IMPLS:
        raise ValueError(f"impl={impl!r}: expected auto|pallas|xla|interpret")
    return impl
