"""Integer-only softmax Pallas kernel (the paper's ``sftmx``).

Row-blocked: each grid step owns a (bm, N) slice so the row max/sum are
computed in one VMEM residency (TPU-native replacement for the paper's
two-context split — VMEM holds what the 256 KiB L1 could not, and the grid
schedule is the static microcode).  Arithmetic is bit-identical to
``core.inumerics.i_softmax``: shift-based integer exp (I-BERT 2^z
decomposition) and an integer 127/sum normalization, int8 output.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import interpret_mode

I32 = jnp.int32
_EXP_A, _EXP_B, _EXP_C = 0.35815147, 1.353, 0.344
NEG_INF = -(2 ** 24)


def _exp_consts(scale: float) -> tuple[int, int, int, int]:
    q_ln2 = max(int(math.floor(math.log(2.0) / scale)), 1)
    q_b = int(math.floor(_EXP_B / scale))
    q_c = int(math.floor(_EXP_C / (_EXP_A * scale * scale)))
    # static 14-bit rescale (see inumerics.exp_rescale_shift)
    es = max(0, int(q_b * q_b + q_c).bit_length() - 14)
    return q_ln2, q_b, q_c, es


def _kernel(x_ref, mask_ref, out_ref, *, scale: float, masked: bool):
    q_ln2, q_b, q_c, es = _exp_consts(scale)
    q = x_ref[...].astype(I32)
    if masked:
        q = jnp.where(mask_ref[...] != 0, q, NEG_INF)
    q_max = jnp.max(q, axis=-1, keepdims=True)
    qs = q - q_max
    z = jnp.minimum((-qs) // q_ln2, 30)
    q_p = qs + z * q_ln2
    q_exp = (((q_p + q_b) * (q_p + q_b) + q_c) >> z) >> es
    if masked:
        q_exp = jnp.where(mask_ref[...] != 0, q_exp, 0)
    q_sum = jnp.maximum(jnp.sum(q_exp, axis=-1, keepdims=True), 1)
    out = (q_exp * 127 + (q_sum >> 1)) // q_sum
    out_ref[...] = jnp.clip(out, 0, 127).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "interpret"))
def int_softmax(
    x: jax.Array,
    scale: float,
    mask: jax.Array | None = None,
    bm: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Integer softmax over the last axis.  x: int8/int32 payload [.., M, N].

    Returns int8 probabilities; dequantize with 1/127.
    """
    orig_shape = x.shape
    n = orig_shape[-1]
    x2 = x.reshape(-1, n)
    m = x2.shape[0]
    assert m % bm == 0, f"pad rows to a multiple of {bm} (got {m})"
    masked = mask is not None
    mask2 = (mask.reshape(-1, n).astype(jnp.int8) if masked
             else jnp.zeros((bm, n), jnp.int8))
    kernel = functools.partial(_kernel, scale=scale, masked=masked)
    in_specs = [pl.BlockSpec((bm, n), lambda i: (i, 0))]
    operands = [x2]
    if masked:
        in_specs.append(pl.BlockSpec((bm, n), lambda i: (i, 0)))
        operands.append(mask2)
    else:  # dummy operand keeps the kernel signature uniform
        in_specs.append(pl.BlockSpec((bm, n), lambda i: (0, 0)))
        operands.append(mask2)
    out = pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        interpret=interpret_mode(interpret),
    )(*operands)
    return out.reshape(orig_shape)
