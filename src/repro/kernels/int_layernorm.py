"""Integer-only LayerNorm / RMSNorm Pallas kernel (the paper's ``norm``).

Row-blocked (bm, D) tiles; integer mean/variance with an adaptive pre-shift,
extended-precision integer Newton sqrt, and a 7-fractional-bit normalized
value — bit-identical to ``core.inumerics.i_layernorm``.  The serial divide
chain that dominates the CGRA version (70 MOPS in Table VI) vectorizes onto
the VPU here; the roofline win of the adaptation is measured in benchmarks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import interpret_mode

I32 = jnp.int32
_FRAC = 7  # fractional bits of the normalized value (matches inumerics)


def _isqrt(n: jax.Array, iters: int = 8) -> jax.Array:
    n = jnp.maximum(n, 0)
    bl = 32 - jax.lax.clz(jnp.maximum(n, 1))
    x0 = (jnp.asarray(1, I32) << ((bl + 1) // 2)).astype(I32)

    def body(_, x):
        x = jnp.maximum(x, 1)
        return jnp.minimum(x, (x + n // x) >> 1)

    x = jax.lax.fori_loop(0, iters, body, x0)
    return jnp.where(n == 0, 0, x)


def _kernel(x_ref, g_ref, b_ref, out_ref, *, d: int, rms_only: bool, vshift: int):
    q = x_ref[...].astype(I32)
    if not rms_only:
        s = jnp.sum(q, axis=-1, keepdims=True)
        mean = jnp.where(s >= 0, (s + d // 2) // d, -((-s + d // 2) // d))
        c = q - mean
    else:
        c = q
    c = jnp.clip(c, -255, 255)
    var_sum = jnp.sum((c * c) >> vshift, axis=-1, keepdims=True)
    var = (var_sum // d) << vshift
    std16 = jnp.maximum(_isqrt(var << 8), 1)
    n = (c << (_FRAC + 4)) // std16
    out = n * g_ref[...].astype(I32)
    if not rms_only:
        out = out + (b_ref[...].astype(I32) << _FRAC)
    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("rms_only", "bm", "interpret"))
def int_layernorm(
    x: jax.Array,
    gamma_q: jax.Array,
    beta_q: jax.Array,
    rms_only: bool = False,
    bm: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Integer LayerNorm over the last axis.

    x: int payload [..., D]; gamma_q/beta_q: int8-range payloads [D].
    Returns int32 payload; real value = out * (gb_scale / 2^7).
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    m = x2.shape[0]
    assert m % bm == 0, f"pad rows to a multiple of {bm} (got {m})"
    vshift = max(0, (d - 1).bit_length() - 15)
    kernel = functools.partial(_kernel, d=d, rms_only=rms_only, vshift=vshift)
    out = pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bm, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), I32),
        interpret=interpret_mode(interpret),
    )(x2.astype(I32), gamma_q.astype(I32), beta_q.astype(I32))
    return out.reshape(orig_shape)
