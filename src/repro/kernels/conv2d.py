"""int8 2D convolution Pallas kernel (the paper's ``conv``).

NHWC x HWIO, stride 1, VALID padding — the Table-II benchmark shape
(3x128x128 img, 8 3x3x3 filters) and the vision/audio frontend stubs.
Edge-model images fit VMEM whole, so the grid is (batch, out-channel
blocks) and the kernel unrolls the kh*kw window into C-contraction dots on
the MXU (int8 x int8 -> int32), adding bias and requantizing in-register.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.inumerics import RequantParams
from .common import interpret_mode

I32 = jnp.int32


def _kernel(x_ref, w_ref, b_ref, out_ref, *, kh: int, kw: int,
            s1: int, mult: int, s2: int, requant: bool):
    x = x_ref[...]          # (1, H, W, C) int8
    w = w_ref[...]          # (kh, kw, C, O) int8
    oh = x.shape[1] - kh + 1
    ow = x.shape[2] - kw + 1
    acc = jnp.zeros((oh, ow, w.shape[-1]), I32)
    for i in range(kh):
        for j in range(kw):
            patch = x[0, i:i + oh, j:j + ow, :]  # (oh, ow, C), static slice
            acc += jax.lax.dot_general(
                patch, w[i, j],
                (((2,), (0,)), ((), ())),
                preferred_element_type=I32,
            )
    acc = acc + b_ref[...].astype(I32)
    if requant:
        if s1 > 0:
            acc = (acc + (1 << (s1 - 1))) >> s1
        acc = jnp.clip(acc, -(1 << 15), (1 << 15) - 1) * mult
        if s2 > 0:
            acc = (acc + (1 << (s2 - 1))) >> s2
        out_ref[...] = jnp.clip(acc, -128, 127).astype(jnp.int8)[None]
    else:
        out_ref[...] = acc[None]


@functools.partial(jax.jit, static_argnames=("requant_params", "interpret"))
def int8_conv2d(
    x: jax.Array,
    w: jax.Array,
    bias: jax.Array,
    requant_params: RequantParams | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """x int8 [N,H,W,C], w int8 [kh,kw,C,O], bias int32 [O] -> [N,OH,OW,O]."""
    n, h, ww, c = x.shape
    kh, kw, c2, o = w.shape
    assert c == c2
    oh, ow = h - kh + 1, ww - kw + 1
    requant = requant_params is not None
    s1, mult, s2 = ((requant_params.s1, requant_params.mult, requant_params.s2)
                    if requant else (0, 0, 0))
    kernel = functools.partial(_kernel, kh=kh, kw=kw, s1=s1, mult=mult, s2=s2,
                               requant=requant)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, ww, c), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((kh, kw, c, o), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((o,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, oh, ow, o), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n, oh, ow, o), jnp.int8 if requant else I32),
        interpret=interpret_mode(interpret),
    )(x, w, bias)
