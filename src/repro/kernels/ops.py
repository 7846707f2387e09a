"""Public jit'd entry points for the kernels package.

Call sites (models, serving engine) go through these wrappers, which handle
arbitrary shapes (padding to block multiples) and pick block sizes per
(shape, dtype, backend) via ``kernels.autotune`` (cost-model-seeded table
with an optional measured cache — no hardcoded tiles).

The kernel backend is resolved from the platform at first use, never at
import: "pallas" (compiled kernels) on a TPU, "jnp" (the XLA reference
path in ``ref.py``) anywhere else — e.g. CPU tests and the 512-device
dry-run, where interpret-mode execution would be prohibitive.
``set_backend`` / ``use_backend`` are the hooks tests, reference
comparisons and the trainer use (no kernel defines a VJP, so the
differentiated forward traces the jnp path); the serving engine refuses
to trace its step on a TPU with the jnp path selected
(``check_serving_backend``).

Fused epilogue entry points (``gemm_i8_gelu``, ``gemm_i8_add``,
``gemm_w8a8``, the dual-GEMM ``gated_mlp``/``gated_mlp_w8a8``, and their
packed-int4 W4A8 twins ``gemm_w4a8``/``gated_mlp_w4a8``) keep
the int32 GEMM accumulator in-register instead of round-tripping it
through HBM between the matmul and its consumer; their jnp paths are the
exact unfused compositions, so both backends are bit-identical (the
float ``gated_mlp`` matches to accumulation order, like flash attention).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from ..core.inumerics import RequantParams
from . import autotune, ref
from .common import on_tpu, pad_to
from .conv2d import int8_conv2d
from .flash_attention import flash_attention
from .int8_flash_attention import int8_flash_attention
from .int8_gemm import (dual_gemm_gated, dual_int4_gemm_gated, int4_gemm,
                        int8_gemm)
from .int_gelu import int_gelu, gelu_out_scale  # noqa: F401 (re-export)
from .int_silu import int_silu, silu_out_scale  # noqa: F401 (re-export)
from .int_layernorm import int_layernorm
from .int_softmax import int_softmax
from .quantize import quantize_rows, requantize_i32

_BACKEND: list[str | None] = [None]  # None: follow the platform


def set_backend(name: str | None) -> str | None:
    """Test hook: force "pallas" or "jnp" (``None`` restores the platform
    default).  Returns the previous setting for restoring."""
    if name not in ("pallas", "jnp", None):
        raise ValueError(f"unknown kernel backend {name!r}")
    prev, _BACKEND[0] = _BACKEND[0], name
    return prev


@contextlib.contextmanager
def use_backend(name: str | None):
    """``set_backend(name)`` for the ``with`` block.  The backend is read
    while tracing, so this scopes the programs traced inside it."""
    prev = set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


def backend() -> str:
    if _BACKEND[0] is None:
        return "pallas" if on_tpu() else "jnp"
    return _BACKEND[0]


def _use_pallas() -> bool:
    return backend() == "pallas"


def check_serving_backend() -> None:
    """On a TPU the serving path runs the compiled Pallas kernels; tracing
    it with the jnp reference path selected is an error, not a fallback."""
    if on_tpu() and backend() != "pallas":
        raise RuntimeError(
            "the jnp reference kernel path is selected while a TPU is the "
            "JAX backend; serving on the chip runs the Pallas kernels")


# ---------------------------------------------------------------------------
# GEMM family
# ---------------------------------------------------------------------------


def _gemm_2d(x: jax.Array):
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    return x2, lead, x2.shape[0]


def gemm_i8(x: jax.Array, w: jax.Array, requant: RequantParams | None = None,
            out_dtype=jnp.int32) -> jax.Array:
    """int8 GEMM on arbitrary [..., K] x [K, N]; pads to tuned blocks."""
    x2, lead, m = _gemm_2d(x)
    k, n = w.shape
    if not _use_pallas():
        out = ref.int8_gemm_ref(x2, w, requant, out_dtype)
        return out.reshape(*lead, n)
    bm, bn, bk = autotune.gemm_blocks(m, k, n)
    xp = pad_to(x2, (bm, bk))
    wp = pad_to(w, (bk, bn))
    out = int8_gemm(xp, wp, requant=requant,
                    out_dtype=jnp.int8 if requant is not None else jnp.int32,
                    bm=bm, bn=bn, bk=bk)
    return out[:m, :n].reshape(*lead, n)


def gemm_i8_gelu(x: jax.Array, w: jax.Array, gelu_scale: float) -> jax.Array:
    """Fused ``gemm_i8 -> gelu_i8``: integer GELU of the int32 accumulator
    at a static scale, int8 out (dequant with ``gelu_out_scale``).  The
    int32 intermediate never touches HBM on the pallas path."""
    x2, lead, m = _gemm_2d(x)
    k, n = w.shape
    if not _use_pallas():
        return ref.int8_gemm_gelu_ref(x2, w, gelu_scale).reshape(*lead, n)
    bm, bn, bk = autotune.gemm_blocks(m, k, n)
    out = int8_gemm(pad_to(x2, (bm, bk)), pad_to(w, (bk, bn)),
                    epilogue="requant_gelu", gelu_scale=gelu_scale,
                    bm=bm, bn=bn, bk=bk)
    return out[:m, :n].reshape(*lead, n)


def gemm_i8_add(x: jax.Array, w: jax.Array, requant: RequantParams,
                residual: jax.Array) -> jax.Array:
    """Fused ``requant(gemm_i8) + residual`` with int8 saturation — the
    integer residual-stream form of out-projection + skip connection."""
    x2, lead, m = _gemm_2d(x)
    k, n = w.shape
    r2 = residual.reshape(-1, n)
    if not _use_pallas():
        return ref.int8_gemm_add_ref(x2, w, requant, r2).reshape(*lead, n)
    bm, bn, bk = autotune.gemm_blocks(m, k, n)
    out = int8_gemm(pad_to(x2, (bm, bk)), pad_to(w, (bk, bn)),
                    requant=requant, epilogue="requant_add",
                    residual=pad_to(r2, (bm, bn)), bm=bm, bn=bn, bk=bk)
    return out[:m, :n].reshape(*lead, n)


def gemm_w8a8(x_q: jax.Array, x_scale: jax.Array, w_q: jax.Array,
              w_scale: jax.Array, bias: jax.Array | None = None,
              residual: jax.Array | None = None,
              gelu_scale: float | None = None,
              out_dtype=jnp.bfloat16) -> jax.Array:
    """W8A8 linear with the dequant epilogue fused into the GEMM.

    x_q [..., K] int8 with per-row scales x_scale [..., 1]; w_q [K, N] int8
    with per-col scales w_scale [N].  Returns out_dtype [..., N] — or, with
    ``gelu_scale``, the int8 GELU payload (dequant with gelu_out_scale).
    """
    x2, lead, m = _gemm_2d(x_q)
    k, n = w_q.shape
    xs2 = x_scale.reshape(-1, 1)
    r2 = None if residual is None else residual.reshape(-1, n)
    if not _use_pallas():
        out = ref.gemm_w8a8_ref(x2, xs2, w_q, w_scale, bias=bias,
                                residual=r2, gelu_scale=gelu_scale,
                                out_dtype=out_dtype)
        return out.reshape(*lead, n)
    bm, bn, bk = autotune.gemm_blocks(m, k, n)
    if gelu_scale is not None:
        epi = "scaled_gelu"
    elif r2 is not None:
        epi = "scaled_add"
    else:
        epi = "scaled"
    out = int8_gemm(
        pad_to(x2, (bm, bk)), pad_to(w_q, (bk, bn)),
        epilogue=epi, gelu_scale=gelu_scale,
        x_scale=pad_to(xs2, (bm, 1)),
        w_scale=pad_to(w_scale.reshape(1, n), (1, bn)),
        bias=None if bias is None else pad_to(bias.reshape(1, n), (1, bn)),
        residual=None if r2 is None else pad_to(r2, (bm, bn)),
        out_dtype=out_dtype, bm=bm, bn=bn, bk=bk)
    return out[:m, :n].reshape(*lead, n)


def gated_mlp(x: jax.Array, w_up: jax.Array, w_gate: jax.Array,
              act: str = "silu", compute_dtype=jnp.bfloat16) -> jax.Array:
    """Fused dual-GEMM gated MLP (float): ``act(x @ w_gate) * (x @ w_up)``
    with x streamed once and both accumulators resident — the (T, d_ff)
    gate/up intermediates never touch HBM on the pallas path.  The jnp path
    is the exact unfused model composition."""
    x2, lead, m = _gemm_2d(x)
    k, n = w_up.shape
    if not _use_pallas():
        out = ref.gated_mlp_ref(x2, w_up, w_gate, act, compute_dtype)
        return out.reshape(*lead, n)
    bm, bn, bk = autotune.gated_mlp_blocks(m, k, n, dtype="bf16")
    out = dual_gemm_gated(
        pad_to(x2.astype(compute_dtype), (bm, bk)),
        pad_to(w_up.astype(compute_dtype), (bk, bn)),
        pad_to(w_gate.astype(compute_dtype), (bk, bn)),
        act=act, out_dtype=compute_dtype, bm=bm, bn=bn, bk=bk)
    return out[:m, :n].reshape(*lead, n)


def gated_mlp_w8a8(x_q: jax.Array, x_scale: jax.Array,
                   w_up_q: jax.Array, up_scale: jax.Array,
                   w_gate_q: jax.Array, gate_scale: jax.Array,
                   act: str = "silu", act_scale: float | None = None,
                   out_dtype=jnp.bfloat16) -> jax.Array:
    """Fused W8A8 dual-GEMM gated MLP (SwiGLU/GeGLU up+gate projections).

    x_q [..., K] int8 with per-row scales x_scale [..., 1]; both weights
    [K, N] int8 with per-col scales.  Dequant + integer activation(gate) *
    up run in the GEMM epilogue; bit-identical to the unfused
    ``gemm_w8a8 x2 -> silu_i8/gelu_i8 -> multiply`` composition.
    """
    assert act_scale is not None, "integer gated MLP needs a static act_scale"
    x2, lead, m = _gemm_2d(x_q)
    k, n = w_up_q.shape
    xs2 = x_scale.reshape(-1, 1)
    if not _use_pallas():
        out = ref.gated_mlp_w8a8_ref(x2, xs2, w_up_q, up_scale, w_gate_q,
                                     gate_scale, act=act,
                                     act_scale=act_scale,
                                     out_dtype=out_dtype)
        return out.reshape(*lead, n)
    bm, bn, bk = autotune.gated_mlp_blocks(m, k, n)
    out = dual_gemm_gated(
        pad_to(x2, (bm, bk)),
        pad_to(w_up_q, (bk, bn)), pad_to(w_gate_q, (bk, bn)),
        x_scale=pad_to(xs2, (bm, 1)),
        up_scale=pad_to(up_scale.reshape(1, n), (1, bn)),
        gate_scale=pad_to(gate_scale.reshape(1, n), (1, bn)),
        act=act, act_scale=act_scale, out_dtype=out_dtype,
        bm=bm, bn=bn, bk=bk)
    return out[:m, :n].reshape(*lead, n)


def _w4_group(k: int, qmul: jax.Array) -> int:
    groups = qmul.shape[-2]
    group = k // groups
    assert group * groups == k, (k, qmul.shape)
    return group


def gemm_w4a8(x_q: jax.Array, x_scale: jax.Array, w4: jax.Array,
              qmul: jax.Array, w_scale: jax.Array,
              bias: jax.Array | None = None,
              residual: jax.Array | None = None,
              gelu_scale: float | None = None,
              out_dtype=jnp.bfloat16) -> jax.Array:
    """W4A8 linear: packed-int4 weights, in-kernel nibble unpack + two-level
    group dequant, same fused epilogue family as ``gemm_w8a8``.

    x_q [..., K] int8 with per-row scales x_scale [..., 1]; w4 [K/2, N]
    packed int4 (``quantize.pack_int4`` layout) with int8 group multipliers
    qmul [K/group, N] and per-column scales w_scale [N] (a group's
    effective scale is ``w_scale * qmul``, so the group combine stays in
    int32).  Bit-identical to the unfused unpack -> group-wise int8 GEMM ->
    integer-combine composition (``ref.gemm_w4a8_ref``) on both backends.
    """
    x2, lead, m = _gemm_2d(x_q)
    k = x2.shape[-1]
    n = w4.shape[-1]
    group = _w4_group(k, qmul)
    xs2 = x_scale.reshape(-1, 1)
    r2 = None if residual is None else residual.reshape(-1, n)
    if not _use_pallas():
        out = ref.gemm_w4a8_ref(x2, xs2, w4, qmul, w_scale, bias=bias,
                                residual=r2, gelu_scale=gelu_scale,
                                out_dtype=out_dtype)
        return out.reshape(*lead, n)
    bm, bn, bk = autotune.gemm_w4a8_blocks(m, k, n, group)
    if gelu_scale is not None:
        epi = "scaled_gelu"
    elif r2 is not None:
        epi = "scaled_add"
    else:
        epi = "scaled"
    # zero-padding is exact: padded packed bytes are zero nibbles and their
    # group multipliers are zero, so padded K contributes nothing
    out = int4_gemm(
        pad_to(x2, (bm, bk)), pad_to(w4, (bk // 2, bn)),
        pad_to(qmul, (bk // group, bn)),
        pad_to(w_scale.reshape(1, n), (1, bn)),
        pad_to(xs2, (bm, 1)), group=group,
        epilogue=epi, gelu_scale=gelu_scale,
        bias=None if bias is None else pad_to(bias.reshape(1, n), (1, bn)),
        residual=None if r2 is None else pad_to(r2, (bm, bn)),
        out_dtype=out_dtype, bm=bm, bn=bn, bk=bk)
    return out[:m, :n].reshape(*lead, n)


def gated_mlp_w4a8(x_q: jax.Array, x_scale: jax.Array,
                   up4: jax.Array, up_mul: jax.Array, up_scale: jax.Array,
                   gate4: jax.Array, gate_mul: jax.Array,
                   gate_scale: jax.Array,
                   act: str = "silu", act_scale: float | None = None,
                   out_dtype=jnp.bfloat16) -> jax.Array:
    """Fused W4A8 dual-GEMM gated MLP: two packed-int4 weight streams share
    one A tile; unpack + two-level group dequant + integer activation(gate)
    * up all run in-kernel.  Bit-identical to the unfused ``gemm_w4a8 x2 ->
    silu_i8/gelu_i8 -> multiply`` composition (``ref.gated_mlp_w4a8_ref``).
    """
    assert act_scale is not None, "integer gated MLP needs a static act_scale"
    x2, lead, m = _gemm_2d(x_q)
    k = x2.shape[-1]
    n = up4.shape[-1]
    group = _w4_group(k, up_mul)
    assert gate_mul.shape == up_mul.shape, (gate_mul.shape, up_mul.shape)
    xs2 = x_scale.reshape(-1, 1)
    if not _use_pallas():
        out = ref.gated_mlp_w4a8_ref(x2, xs2, up4, up_mul, up_scale,
                                     gate4, gate_mul, gate_scale, act=act,
                                     act_scale=act_scale,
                                     out_dtype=out_dtype)
        return out.reshape(*lead, n)
    bm, bn, bk = autotune.gatedmlp_w4a8_blocks(m, k, n, group)
    out = dual_int4_gemm_gated(
        pad_to(x2, (bm, bk)),
        pad_to(up4, (bk // 2, bn)), pad_to(up_mul, (bk // group, bn)),
        pad_to(up_scale.reshape(1, n), (1, bn)),
        pad_to(gate4, (bk // 2, bn)), pad_to(gate_mul, (bk // group, bn)),
        pad_to(gate_scale.reshape(1, n), (1, bn)),
        pad_to(xs2, (bm, 1)), group=group,
        act=act, act_scale=act_scale, out_dtype=out_dtype,
        bm=bm, bn=bn, bk=bk)
    return out[:m, :n].reshape(*lead, n)


# ---------------------------------------------------------------------------
# row-wise integer kernels
# ---------------------------------------------------------------------------


def softmax_i8(x: jax.Array, scale: float, mask=None) -> jax.Array:
    if not _use_pallas():
        return ref.int_softmax_ref(x, scale, mask)
    lead = x.shape[:-1]
    n = x.shape[-1]
    x2 = x.reshape(-1, n)
    m = x2.shape[0]
    bm = autotune.rowwise_blocks(m, n)
    xp = pad_to(x2, (bm, 1))
    mp = pad_to(mask.reshape(-1, n), (bm, 1)) if mask is not None else None
    out = int_softmax(xp, scale, mask=mp, bm=bm)
    return out[:m].reshape(*lead, n)


def layernorm_i8(x: jax.Array, gamma_q: jax.Array, beta_q: jax.Array,
                 rms_only: bool = False) -> jax.Array:
    if not _use_pallas():
        return ref.int_layernorm_ref(x, gamma_q, beta_q, rms_only)
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    m = x2.shape[0]
    bm = autotune.rowwise_blocks(m, d)
    xp = pad_to(x2, (bm, 1))
    out = int_layernorm(xp, gamma_q, beta_q, rms_only=rms_only, bm=bm)
    return out[:m].reshape(*lead, d)


def gelu_i8(x: jax.Array, scale: float) -> jax.Array:
    if not _use_pallas():
        return ref.int_gelu_ref(x, scale)
    lead = x.shape[:-1]
    n = x.shape[-1]
    x2 = x.reshape(-1, n)
    m = x2.shape[0]
    bm, bn = autotune.elementwise_blocks(m, n)
    xp = pad_to(x2, (bm, bn))
    out = int_gelu(xp, scale, bm=bm, bn=bn)
    return out[:m, :n].reshape(*lead, n)


def silu_i8(x: jax.Array, scale: float) -> jax.Array:
    """Integer SiLU on int payload (real = x*scale): int32 payload out
    (±127*127 range), dequantize with ``silu_out_scale(scale)``."""
    if not _use_pallas():
        return ref.int_silu_ref(x, scale)
    lead = x.shape[:-1]
    n = x.shape[-1]
    x2 = x.reshape(-1, n)
    m = x2.shape[0]
    bm, bn = autotune.elementwise_blocks(m, n)
    xp = pad_to(x2, (bm, bn))
    out = int_silu(xp, scale, bm=bm, bn=bn)
    return out[:m, :n].reshape(*lead, n)


def quant_rows(x: jax.Array):
    if not _use_pallas():
        return ref.quantize_rows_ref(x)
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    m = x2.shape[0]
    bm = autotune.rowwise_blocks(m, d, dtype="f32")
    xp = pad_to(x2, (bm, 1))
    q, s = quantize_rows(xp, bm=bm)
    return q[:m].reshape(*lead, d), s[:m].reshape(*lead, 1)


def requant(x: jax.Array, params: RequantParams) -> jax.Array:
    if not _use_pallas():
        return ref.requantize_i32_ref(x, params)
    lead = x.shape[:-1]
    n = x.shape[-1]
    x2 = x.reshape(-1, n)
    m = x2.shape[0]
    bm, bn = autotune.elementwise_blocks(m, n)
    xp = pad_to(x2, (bm, bn))
    out = requantize_i32(xp, params, bm=bm, bn=bn)
    return out[:m, :n].reshape(*lead, n)


def conv2d_i8(x, w, bias, requant_params=None):
    if not _use_pallas():
        return ref.int8_conv2d_ref(x, w, bias, requant_params)
    return int8_conv2d(x, w, bias, requant_params)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attention(q, k, v, causal=True, scale=None):
    if not _use_pallas():
        return ref.flash_attention_ref(q, k, v, causal, scale)
    s, skv, d = q.shape[2], k.shape[2], q.shape[3]
    bq, bk = autotune.attention_blocks(s, skv, d)
    return flash_attention(q, k, v, causal=causal, scale=scale, bq=bq, bk=bk)


def attention_i8(q, k, v, scale, causal=True, v_scale=None):
    """Integer attention (int8 QK^T -> i-softmax -> PV).  Without
    ``v_scale``: int32 accumulator out (real value acc/127 * caller's
    per-tensor s_v).  With ``v_scale`` [B,Hkv,Skv,1] f32 per-(token, head)
    scales: exact in-kernel PV dequant, f32 attention output."""
    if not _use_pallas():
        return ref.int8_flash_attention_ref(q, k, v, scale, causal,
                                            v_scale=v_scale)
    s, skv, d = q.shape[2], k.shape[2], q.shape[3]
    if v_scale is not None:
        bq, bk = autotune.attention_pv_blocks(s, skv, d)
    else:
        bq, bk = autotune.attention_blocks(s, skv, d, dtype="int8")
    return int8_flash_attention(q, k, v, scale, causal=causal,
                                v_scale=v_scale, bq=bq, bk=bk)


def decode_attention_int8kv(q, k_q, k_s, v_q, v_s, pos_ids, qpos,
                            scale=None, window=0):
    """Single-token attention over the int8 ring cache (serving hot path:
    reads the cache once as int8, dequantizes in-register)."""
    if not _use_pallas():
        return ref.int8_kv_decode_attention_ref(
            q, k_q, k_s, v_q, v_s, pos_ids, qpos, scale, window)
    from .int8_kv_decode_attention import int8_kv_decode_attention
    s, d = k_q.shape[1], k_q.shape[3]
    g = q.shape[1] // k_q.shape[2]
    bk = autotune.decode_blocks(s, d, g)
    return int8_kv_decode_attention(q, k_q, k_s, v_q, v_s, pos_ids, qpos,
                                    scale=scale, window=window, bk=bk)


def paged_attention_decode(q, pk, pks, pv, pvs, ppos, pt, qpos,
                           scale=None, window=0):
    """Single-token attention over the PAGED KV arena (paged serving hot
    path): the pallas kernel gathers pages HBM->VMEM through the
    scalar-prefetched page table and dequantizes in-register; the jnp path
    materializes the gathered view and runs the dense decode oracle —
    exactly the math of the dense cache path over the same positions
    (``pks``/``pvs`` None = bf16 pages)."""
    if not _use_pallas():
        return ref.paged_decode_attention_ref(
            q, pk, pks, pv, pvs, ppos, pt, qpos, scale, window)
    from .paged_attention import paged_decode_attention
    return paged_decode_attention(q, pk, pks, pv, pvs, ppos, pt, qpos,
                                  scale=scale, window=window)
