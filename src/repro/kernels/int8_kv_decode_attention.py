"""Decode attention over an int8 KV cache (per-token-head scales).

The §Perf cell-C finding: the XLA fallback path materializes f32 copies of
the dequantized cache (5x the ideal 17 GB/step HBM traffic on codeqwen
decode_32k).  This kernel closes that gap on TPU: K/V stream HBM->VMEM as
int8 with their (S, 1) scale vectors, dequantize in-register, and a f32
online softmax accumulates — one int8 pass over the cache per token.

Handles exactly the serving cache layout (`models/attention.init_cache`
int8 mode): ring-buffer `pos_ids` masking (empty slots, causal bound,
sliding window) and GQA via a q-register blocked over query-head groups.

Grid: (B * Hkv, S/bk); the query block (G, D) stays resident; each step
loads (bk, D) int8 K and V tiles + (bk, 1) scales + a (1, bk) row of slot
positions.  The per-sequence query positions ride the scalar-prefetch path
(SMEM), so no block is narrower than the TPU's (8, 128) tiling allows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30


def _kernel(qpos_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, pos_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, window: int, n_kv: int,
            hkv: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(F32)                     # (G, D)
    k = k_ref[0].astype(F32) * ks_ref[0]         # (bk, D) dequant in-register
    v = v_ref[0].astype(F32) * vs_ref[0]
    kpos = pos_ref[0]                            # (1, bk) absolute positions
    qpos = qpos_ref[i // hkv]                    # this sequence's step (SMEM)

    # f32 operands at f32 precision, like the oracle (a TPU dot otherwise
    # rounds f32 operands to bf16)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=HIGHEST,
                            preferred_element_type=F32) * scale  # (G, bk)
    valid = (kpos >= 0) & (kpos <= qpos)
    if window:
        valid &= kpos > (qpos - window)
    s = jnp.where(valid, s, NEG)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=F32)
    m_scr[...] = m_new

    @pl.when(j == n_kv - 1)
    def _emit():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "bk", "interpret"))
def int8_kv_decode_attention(
    q: jax.Array,        # (B, Hq, D) bf16/f32 — one query token per sequence
    k_q: jax.Array,      # (B, S, Hkv, D) int8
    k_s: jax.Array,      # (B, S, Hkv, 1) f32
    v_q: jax.Array,      # (B, S, Hkv, D) int8
    v_s: jax.Array,      # (B, S, Hkv, 1) f32
    pos_ids: jax.Array,  # (B, S) int32, -1 = empty slot
    qpos: jax.Array,     # (B,) int32 current positions
    scale: float | None = None,
    window: int = 0,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    b, hq, d = q.shape
    s, hkv = k_q.shape[1], k_q.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    assert s % bk == 0, (s, bk)
    # (B, Hkv, G, D) query blocks; KV per (B, Hkv): (S, D) + (S, 1) scales
    q4 = q.reshape(b, hkv, g, d).reshape(b * hkv, g, d)
    kq = jnp.transpose(k_q, (0, 2, 1, 3)).reshape(b * hkv, s, d)
    ks = jnp.transpose(k_s, (0, 2, 1, 3)).reshape(b * hkv, s, 1)
    vq = jnp.transpose(v_q, (0, 2, 1, 3)).reshape(b * hkv, s, d)
    vs = jnp.transpose(v_s, (0, 2, 1, 3)).reshape(b * hkv, s, 1)
    # (B, 1, S): a (1, bk) row block spans the full second-minor extent
    pos = pos_ids.reshape(b, 1, s)
    n_kv = s // bk
    kernel = functools.partial(_kernel, scale=scale, window=window, n_kv=n_kv,
                               hkv=hkv)
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * hkv, n_kv),
            in_specs=[
                pl.BlockSpec((1, g, d), lambda i, j, qp: (i, 0, 0)),
                pl.BlockSpec((1, bk, d), lambda i, j, qp: (i, j, 0)),
                pl.BlockSpec((1, bk, 1), lambda i, j, qp: (i, j, 0)),
                pl.BlockSpec((1, bk, d), lambda i, j, qp: (i, j, 0)),
                pl.BlockSpec((1, bk, 1), lambda i, j, qp: (i, j, 0)),
                pl.BlockSpec((1, 1, bk), lambda i, j, qp: (i // hkv, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, g, d), lambda i, j, qp: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), F32),
                pltpu.VMEM((g, 1), F32),
                pltpu.VMEM((g, d), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, d), q.dtype),
        interpret=interpret_mode(interpret),
    )(qpos.astype(jnp.int32), q4, kq, ks, vq, vs, pos)
    return o.reshape(b, hq, d)
