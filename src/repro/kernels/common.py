"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (MXU 128x128 systolic matmul, VPU 8x128 lanes,
~16 MiB VMEM per core) and are validated on CPU with ``interpret=True``.
Block shapes default to MXU-aligned multiples of 128; wrappers pad
arbitrary shapes up to block multiples and slice the result back.

Interpret mode is resolved from the platform at first use, never at
import: compiled on a TPU backend, interpreted anywhere else.  Asking for
interpret mode while a TPU is the backend is an error — the chip never
runs a kernel through the interpreter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# TPU tiling constants
LANE = 128          # last-dim tile (VREG lane width, MXU edge)
SUBLANE = 8         # second-to-last-dim tile for fp32
MXU = 128

_INTERPRET: list[bool | None] = [None]  # None: follow the platform


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _refuse_interpret_on_tpu() -> None:
    if on_tpu():
        raise RuntimeError(
            "Pallas interpret mode was requested while a TPU is the JAX "
            "backend; kernels run compiled on the chip")


def set_interpret(mode: bool | None) -> bool | None:
    """Test hook: force interpret mode on or off (``None`` restores the
    platform default).  Returns the previous setting for restoring."""
    if mode:
        _refuse_interpret_on_tpu()
    prev, _INTERPRET[0] = _INTERPRET[0], mode
    return prev


def interpret_mode(interpret: bool | None = None) -> bool:
    """A kernel's interpret flag: the explicit ``interpret`` argument, else
    the process setting, else the platform (compiled on TPU only)."""
    if interpret is None:
        interpret = _INTERPRET[0]
    if interpret is None:
        interpret = not on_tpu()
    if interpret:
        _refuse_interpret_on_tpu()
    return bool(interpret)


def recip(c: float) -> np.float32:
    """f32 reciprocal of a static scale.  Kernels and their oracles multiply
    by it rather than divide by the scale: XLA rewrites a division by a
    constant into exactly this multiply, while Mosaic divides, so only the
    explicit multiply gives the compiled kernel and the XLA path the same
    bits."""
    return np.float32(1) / np.float32(c)


def pad_to(x: jax.Array, multiples: tuple[int, ...], value=0) -> jax.Array:
    """Pad trailing dims of ``x`` up to the given multiples."""
    pads = []
    for dim, m in zip(x.shape, multiples):
        if m <= 1:
            pads.append((0, 0))
        else:
            pads.append((0, (-dim) % m))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads, constant_values=value)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pick_block(dim: int, target: int, align: int) -> int:
    """Largest aligned block <= target covering dim (or the padded dim)."""
    if dim <= align:
        return align
    b = min(target, dim)
    return max(align, (b // align) * align)


def requant_block(acc, s1: int, mult: int, s2: int):
    """Traced shift/mul16/shift requantization of an int32 block to the
    int8 range (round-half-up) — the in-kernel form of
    ``core.inumerics.requantize``, shared by every epilogue."""
    if s1 > 0:
        acc = (acc + (1 << (s1 - 1))) >> s1
    acc = jnp.clip(acc, -(1 << 15), (1 << 15) - 1) * mult
    if s2 > 0:
        acc = (acc + (1 << (s2 - 1))) >> s2
    return jnp.clip(acc, -128, 127)
