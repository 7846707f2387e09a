"""Integer-only GELU Pallas kernel (the paper's ``gelu``).

Elementwise I-BERT polynomial on 2D blocks; int32 in (pre-activation
accumulator or int8 payload), int8 out with a static output scale —
bit-identical to ``core.inumerics.i_gelu_int8``.

``gelu_block`` is the traced core, shared with the fused GEMM epilogue in
``int8_gemm.py`` (requantize+GELU without the int32 HBM round trip).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import inumerics as inum
from .common import interpret_mode, requant_block

I32 = jnp.int32
_ERF_A, _ERF_B, _ERF_C = -0.2888, -1.769, 1.0


def gelu_requant_params(scale: float) -> inum.RequantParams:
    """The same tight-bound requant params inumerics.i_gelu_int8 derives."""
    s_in = scale / math.sqrt(2.0)
    s_erf = abs(_ERF_A * s_in * s_in)
    s_out_raw = s_erf * scale / 2.0
    acc_bound = int(127 * 2 / s_erf) + 127
    return inum.compute_requant_params(s_out_raw / gelu_out_scale(scale),
                                       acc_bound=acc_bound)


def gelu_block(q, *, scale: float, s1: int, mult: int, s2: int):
    """Traced int GELU of one int32 block -> int8-range int32 values."""
    s_in = scale / math.sqrt(2.0)
    q_b = int(math.floor(_ERF_B / s_in))
    q_c = int(math.floor(_ERF_C / (_ERF_A * s_in * s_in)))
    s_erf = _ERF_A * s_in * s_in
    q_one = int(math.floor(1.0 / s_erf))
    sgn = jnp.sign(q).astype(I32)
    q_abs = jnp.minimum(jnp.abs(q), -q_b)
    q_erf = sgn * ((q_abs + q_b) * (q_abs + q_b) + q_c)
    acc = -(q * (q_erf + q_one))  # negate: s_out < 0 in the raw formula
    return requant_block(acc, s1, mult, s2)


def _kernel(x_ref, out_ref, *, scale: float, s1: int, mult: int, s2: int):
    q = x_ref[...].astype(I32)
    out_ref[...] = gelu_block(q, scale=scale, s1=s1, mult=mult,
                              s2=s2).astype(jnp.int8)


def gelu_out_scale(scale: float) -> float:
    return max(127.0 * scale, 1e-8) / 127.0


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "interpret"))
def int_gelu(
    x: jax.Array,
    scale: float,
    bm: int = 8,
    bn: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """GELU on int payload (real = x*scale); returns int8, scale gelu_out_scale."""
    orig_shape = x.shape
    n = orig_shape[-1]
    x2 = x.reshape(-1, n)
    m = x2.shape[0]
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    p = gelu_requant_params(scale)
    kernel = functools.partial(_kernel, scale=scale, s1=p.s1, mult=p.mult, s2=p.s2)
    out = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        interpret=interpret_mode(interpret),
    )(x2.astype(I32))
    return out.reshape(orig_shape)
