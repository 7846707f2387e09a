"""Fused bf16 flash attention (forward) Pallas kernel.

IO-aware attention for the training/prefill path: Q/K/V stream HBM->VMEM in
MXU-aligned blocks, online softmax keeps the running (max, sum, acc) in VMEM
scratch, and only the final O tile is written back — one HBM pass over K/V
per Q block.  This is the MOB/PE decoupling story at TPU scale: the grid's
async block copies (MOB role) hide HBM latency behind the MXU dots (PE role).

Grid: (batch*heads, num_q_blocks, num_kv_blocks), kv innermost.  Causal
masking skips fully-masked kv blocks via the index map and applies a
triangular mask on the diagonal block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, n_kv: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0].astype(jnp.float32)           # (bk, d)
        v = v_ref[0].astype(jnp.float32)           # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if causal:
            q_idx = qb * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_idx = kb * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_idx >= k_idx, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip kv blocks strictly above the diagonal
        pl.when(kb * bk <= qb * bq + bq - 1)(_body)
    else:
        _body()

    @pl.when(kb == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "bq", "bk", "interpret"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """q [B,H,S,D], k/v [B,Hkv,Skv,D] -> o [B,H,S,D].  GQA via KV repeat."""
    b, h, s, d = q.shape
    _, hkv, skv, _ = k.shape
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    assert s % bq == 0 and skv % bk == 0, (s, skv, bq, bk)
    q3 = q.reshape(b * h, s, d)
    k3 = k.reshape(b * h, skv, d)
    v3 = v.reshape(b * h, skv, d)
    n_kv = skv // bk
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, bq=bq, bk=bk, n_kv=n_kv)
    o = pl.pallas_call(
        kernel,
        grid=(b * h, s // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q3, k3, v3)
    return o.reshape(b, h, s, d)
