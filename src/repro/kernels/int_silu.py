"""Integer-only SiLU Pallas kernel (SwiGLU gate non-linearity).

Elementwise I-BERT-style integer sigmoid (shift-exp) times the input, on 2D
blocks; int32 payload in (real = x*scale, int8-range values), int32 payload
out with a static output scale — bit-identical to ``core.inumerics.i_silu``.
The output payload spans ±127*127 (input times a [0, 127] sigmoid payload),
so it stays int32 rather than int8; dequantize with ``silu_out_scale``.

``silu_block`` is the traced core, shared with the fused dual-GEMM gated-MLP
epilogue in ``int8_gemm.py`` (dequant + SiLU(gate) * up without the int32
HBM round trip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import inumerics as inum
from .common import interpret_mode

I32 = jnp.int32


def silu_out_scale(scale: float) -> float:
    """Dequant scale of the int32 SiLU payload (i_silu's scale/127)."""
    return scale / 127.0


def silu_block(q, *, scale: float):
    """Traced int SiLU of one int32 block -> int32 payload (±127*127).

    ``inumerics.i_silu`` is pure int32 jnp (shift-exp sigmoid + integer
    division), so the kernel body IS the oracle — bit-identity by
    construction, the same closed loop as the softmax kernel.
    """
    payload, _ = inum.i_silu(q, scale)
    return payload


def _kernel(x_ref, out_ref, *, scale: float):
    out_ref[...] = silu_block(x_ref[...].astype(I32), scale=scale)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "interpret"))
def int_silu(
    x: jax.Array,
    scale: float,
    bm: int = 8,
    bn: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """SiLU on int payload (real = x*scale); int32 out, scale silu_out_scale."""
    orig_shape = x.shape
    n = orig_shape[-1]
    x2 = x.reshape(-1, n)
    m = x2.shape[0]
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    kernel = functools.partial(_kernel, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), I32),
        interpret=interpret_mode(interpret),
    )(x2.astype(I32))
    return out.reshape(orig_shape)
