"""Integer-only fused attention (ITA-style), streaming over KV blocks.

The paper's thesis at attention granularity: QK^T on the MXU in int8 with
int32 accumulation, *integer* softmax (I-BERT shift-exp), int8 probability
requantization, and an int8 PV matmul — no float anywhere inside.

A one-pass online integer softmax is not expressible in integer arithmetic
(rescaling by exp(-delta*S) is not a power of two in general), so the kernel
makes two streaming passes over K (max+exp-sum) before the PV pass —
trading one extra K read for exact integer semantics.  Both passes are
BlockSpec grid pipelines, so K/V never resides in VMEM whole.

Pass 1 grid (BH, nq, nk): running row max then exp-sum in VMEM scratch.
Pass 2 grid (BH, nq, nk): int8 probabilities p = e*127/sum, acc += p @ V.

With ``v_scale`` (per-(token, head) V scales, the serving cache layout) the
PV pass dequantizes V in-register — acc_f32 += p * (V_int8 * s_v[token]) —
so the output is EXACT attention over the dequantized int8 inputs: the only
error left in the integer path is input quantization itself.  Without
``v_scale`` the legacy int32-accumulator contract (per-tensor s_v folded by
the caller) is unchanged.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode
from .int_softmax import _exp_consts

I32 = jnp.int32
NEG_INF = -(2 ** 24)


def _qk_block(q_ref, k_ref, *, causal, bq, bk, qb, kb, rshift):
    """int8 QK^T block -> int32 scores, with causal mask."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=I32)             # (bq, bk) int32
    s = s >> rshift                              # fold 1/sqrt(d) power-of-2 part
    if causal:
        q_idx = qb * bq + jax.lax.broadcasted_iota(I32, s.shape, 0)
        k_idx = kb * bk + jax.lax.broadcasted_iota(I32, s.shape, 1)
        s = jnp.where(q_idx >= k_idx, s, NEG_INF)
    return s


def _pass1_kernel(q_ref, k_ref, m_ref, m_scr, *, scale, causal,
                  bq, bk, n_kv, rshift):
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    # first sweep within this block: track the global row max
    s = _qk_block(q_ref, k_ref, causal=causal, bq=bq, bk=bk, qb=qb, kb=kb,
                  rshift=rshift)
    m_scr[...] = jnp.maximum(m_scr[...], jnp.max(s, axis=-1, keepdims=True))

    @pl.when(kb == n_kv - 1)
    def _emit_max():
        m_ref[0] = m_scr[...]


def _pass2_kernel(q_ref, k_ref, m_ref, l_ref, l_scr, *, scale,
                  causal, bq, bk, n_kv, rshift):
    """Second streaming pass: exp-sum with the final max known."""
    qb, kb = pl.program_id(1), pl.program_id(2)
    q_ln2, q_b, q_c, es = _exp_consts(scale)

    @pl.when(kb == 0)
    def _init():
        l_scr[...] = jnp.zeros_like(l_scr)

    s = _qk_block(q_ref, k_ref, causal=causal, bq=bq, bk=bk, qb=qb, kb=kb,
                  rshift=rshift)
    qs = jnp.maximum(s - m_ref[0], NEG_INF)
    z = jnp.clip((-qs) // q_ln2, 0, 30)
    q_p = qs + z * q_ln2
    e = (((q_p + q_b) * (q_p + q_b) + q_c) >> z) >> es
    e = jnp.where(qs <= NEG_INF // 2, 0, e)
    l_scr[...] += jnp.sum(e, axis=-1, keepdims=True)

    @pl.when(kb == n_kv - 1)
    def _emit():
        l_ref[0] = jnp.maximum(l_scr[...], 1)


def _pass3_kernel(q_ref, k_ref, v_ref, m_ref, l_ref, o_ref, acc_ref, *,
                  scale, causal, bq, bk, n_kv, rshift):
    qb, kb = pl.program_id(1), pl.program_id(2)
    q_ln2, q_b, q_c, es = _exp_consts(scale)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = _qk_block(q_ref, k_ref, causal=causal, bq=bq, bk=bk, qb=qb, kb=kb,
                  rshift=rshift)
    qs = jnp.maximum(s - m_ref[0], NEG_INF)
    z = jnp.clip((-qs) // q_ln2, 0, 30)
    q_p = qs + z * q_ln2
    e = (((q_p + q_b) * (q_p + q_b) + q_c) >> z) >> es
    e = jnp.where(qs <= NEG_INF // 2, 0, e)
    l = l_ref[0]
    p = jnp.clip((e * 127 + (l >> 1)) // l, 0, 127).astype(jnp.int8)  # int8 probs
    acc_ref[...] += jax.lax.dot_general(
        p, v_ref[0], (((1,), (0,)), ((), ())), preferred_element_type=I32)

    @pl.when(kb == n_kv - 1)
    def _emit():
        o_ref[0] = acc_ref[...]


def _pass3_pv_kernel(q_ref, k_ref, v_ref, vs_ref, m_ref, l_ref, o_ref,
                     acc_ref, *, scale, causal, bq, bk, n_kv, rshift):
    """PV pass with exact per-(token, head) V dequantization: the int8
    probabilities multiply f32 rows V_int * s_v[token], accumulated in f32.
    Output = acc / 127 — the final attention values, no caller-side scale."""
    qb, kb = pl.program_id(1), pl.program_id(2)
    q_ln2, q_b, q_c, es = _exp_consts(scale)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = _qk_block(q_ref, k_ref, causal=causal, bq=bq, bk=bk, qb=qb, kb=kb,
                  rshift=rshift)
    qs = jnp.maximum(s - m_ref[0], NEG_INF)
    z = jnp.clip((-qs) // q_ln2, 0, 30)
    q_p = qs + z * q_ln2
    e = (((q_p + q_b) * (q_p + q_b) + q_c) >> z) >> es
    e = jnp.where(qs <= NEG_INF // 2, 0, e)
    l = l_ref[0]
    p = jnp.clip((e * 127 + (l >> 1)) // l, 0, 127)           # int32 in [0,127]
    v = v_ref[0].astype(jnp.float32) * vs_ref[0]              # (bk, D) dequant
    acc_ref[...] += jax.lax.dot_general(
        p.astype(jnp.float32), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kb == n_kv - 1)
    def _emit():
        o_ref[0] = acc_ref[...] * (1.0 / 127.0)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "bq", "bk", "interpret"))
def int8_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    scale: float,
    causal: bool = True,
    v_scale: jax.Array | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Integer attention.  q/k/v int8 [B,H,S,D] / [B,Hkv,Skv,D].

    ``scale`` is the real-value scale of one QK^T accumulator unit AFTER the
    power-of-two head-dim fold (s_q * s_k * 2^rshift where rshift =
    log2(sqrt(d)) rounded).  Without ``v_scale``: returns int32 acc
    [B,H,S,D]; real value = acc * (1/127) * s_v (per-tensor s_v is the
    caller's).  With ``v_scale`` [B,Hkv,Skv,1] f32 (per-(token, head)
    scales): the PV pass dequantizes in-register and returns the f32
    attention output directly — exact over the dequantized inputs.
    """
    b, h, s, d = q.shape
    _, hkv, skv, _ = k.shape
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        if v_scale is not None:
            v_scale = jnp.repeat(v_scale, rep, axis=1)
    rshift = max(int(round(math.log2(math.sqrt(d)))), 0)
    assert s % bq == 0 and skv % bk == 0, (s, skv, bq, bk)
    q3 = q.reshape(b * h, s, d)
    k3 = k.reshape(b * h, skv, d)
    v3 = v.reshape(b * h, skv, d)
    vs3 = None if v_scale is None else v_scale.reshape(b * h, skv, 1)
    nq, nk = s // bq, skv // bk
    itp = interpret_mode(interpret)
    common = dict(scale=scale, causal=causal, bq=bq, bk=bk, n_kv=nk,
                  rshift=rshift)

    # pass 1: row max
    m = pl.pallas_call(
        functools.partial(_pass1_kernel, **common),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, 1), I32),
        scratch_shapes=[pltpu.VMEM((bq, 1), I32)],
        interpret=itp,
    )(q3, k3)

    # pass 2: exp-sum under the final max
    l = pl.pallas_call(
        functools.partial(_pass2_kernel, **common),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, 1), I32),
        scratch_shapes=[pltpu.VMEM((bq, 1), I32)],
        interpret=itp,
    )(q3, k3, m)

    if vs3 is not None:
        # pass 3 (exact-dequant variant): f32 acc of p * (V_int8 * s_v)
        o = pl.pallas_call(
            functools.partial(_pass3_pv_kernel, **common),
            grid=(b * h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
                pl.BlockSpec((1, bk, 1), lambda bh, i, j: (bh, j, 0)),
                pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            out_shape=jax.ShapeDtypeStruct((b * h, s, d), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            interpret=itp,
        )(q3, k3, v3, vs3, m, l)
        return o.reshape(b, h, s, d)

    # pass 3: int8 probabilities @ V
    o = pl.pallas_call(
        functools.partial(_pass3_kernel, **common),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), I32),
        scratch_shapes=[pltpu.VMEM((bq, d), I32)],
        interpret=itp,
    )(q3, k3, v3, m, l)
    return o.reshape(b, h, s, d)
