"""Quantize / dequantize Pallas kernels (the paper's ``quant``).

``quantize_rows``: float -> int8 with a per-row absmax scale (one fused
pass: row reduce + scale + round + clip, matching the PTQ activation path).
``requantize_i32``: int32 -> int8 via the shift/mul16/shift scheme — the
exact Table-II ``quant`` kernel (int16/int32 input on the 32-bit operator
path, §IV-A-1).

``pack_int4`` / ``unpack_int4``: the W4A8 weight container — two int4
values per int8 byte along the contraction dim (byte i holds rows 2i and
2i+1 of the weight: low nibble = even row, high nibble = odd row), so a
K-blocked GEMM streams each packed byte exactly once.  These are PTQ- /
host-side helpers (plain jnp, not kernels); the GEMM kernels unpack the
same layout in-register and ``kernels.ref.unpack_int4_ref`` is the
independent oracle both are tested against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.inumerics import RequantParams
from .common import interpret_mode, recip

I32 = jnp.int32


def _quant_kernel(x_ref, out_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8)
    scale = amax * recip(127.0)
    q = jnp.round(x / scale)
    out_ref[...] = jnp.clip(q, -128, 127).astype(jnp.int8)
    scale_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def quantize_rows(x: jax.Array, bm: int = 8, interpret: bool | None = None):
    """float [..., D] -> (int8 [..., D], float32 scales [..., 1])."""
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    m = x2.shape[0]
    assert m % bm == 0, (m, bm)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, d), jnp.int8),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(x2)
    return q.reshape(orig_shape), s.reshape(*orig_shape[:-1], 1)


def _requant_kernel(x_ref, out_ref, *, s1: int, mult: int, s2: int):
    acc = x_ref[...].astype(I32)
    if s1 > 0:
        acc = (acc + (1 << (s1 - 1))) >> s1
    acc = jnp.clip(acc, -(1 << 15), (1 << 15) - 1) * mult
    if s2 > 0:
        acc = (acc + (1 << (s2 - 1))) >> s2
    out_ref[...] = jnp.clip(acc, -128, 127).astype(jnp.int8)


def pack_int4(w4: jax.Array) -> jax.Array:
    """int8 [..., K, N] with values in [-8, 7] -> packed int8 [..., ceil(K/2), N].

    Byte i holds contraction rows 2i (low nibble) and 2i+1 (high nibble).
    Odd K is padded with a zero nibble; ``unpack_int4(packed, k)`` slices
    it back off.  int8 left-shift wraps mod 256, which is exactly the
    nibble placement we want (e.g. -8 << 4 == -128).
    """
    assert w4.dtype == jnp.int8, w4.dtype
    k = w4.shape[-2]
    if k % 2:
        pad = [(0, 0)] * w4.ndim
        pad[-2] = (0, 1)
        w4 = jnp.pad(w4, pad)
    lo = w4[..., 0::2, :]
    hi = w4[..., 1::2, :]
    return jnp.bitwise_or(jnp.left_shift(hi, 4), jnp.bitwise_and(lo, 0xF))


def unpack_int4(packed: jax.Array, k: int) -> jax.Array:
    """packed int8 [..., ceil(K/2), N] -> sign-extended int8 [..., K, N].

    Low nibble: shift up then arithmetic-shift down (sign-extends in two
    vector ops); high nibble: one arithmetic shift.  Interleave restores
    the original row order.  Bit-exact against ``ref.unpack_int4_ref``.
    """
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)
    kp = packed.shape[-2]
    n = packed.shape[-1]
    w = jnp.stack([lo, hi], axis=-2)  # [..., kp, 2, N]
    w = w.reshape(*packed.shape[:-2], 2 * kp, n)
    return w[..., :k, :]


@functools.partial(jax.jit, static_argnames=("params", "bm", "bn", "interpret"))
def requantize_i32(
    x: jax.Array,
    params: RequantParams,
    bm: int = 8,
    bn: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """int32/int16 payload [..., N] -> int8 via shift/mul16/shift."""
    orig_shape = x.shape
    n = orig_shape[-1]
    x2 = x.reshape(-1, n).astype(I32)
    m = x2.shape[0]
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    kernel = functools.partial(
        _requant_kernel, s1=params.s1, mult=params.mult, s2=params.s2)
    out = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        interpret=interpret_mode(interpret),
    )(x2)
    return out.reshape(orig_shape)
