"""Pure-jnp oracles for every Pallas kernel (the correctness contracts).

Each ``*_ref`` mirrors one kernel's exact semantics (integer kernels are
bit-exact against these; float kernels match to numerical tolerance).
The integer oracles delegate to ``core.inumerics`` — the same functions the
CGRA simulator executes — closing the loop between the paper-faithful model
and the TPU kernels.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core import inumerics as inum
from .common import recip

I32 = jnp.int32


def int8_gemm_ref(x, w, requant=None, out_dtype=jnp.int32):
    acc = jax.lax.dot_general(
        x.astype(jnp.int8), w.astype(jnp.int8),
        (((1,), (0,)), ((), ())), preferred_element_type=I32)
    if requant is None:
        return acc
    return inum.requantize(acc, requant).astype(jnp.int8)


def int8_gemm_gelu_ref(x, w, gelu_scale):
    """Unfused composition the fused requant+GELU epilogue must match
    bit-for-bit: int32 GEMM accumulator -> integer GELU (requant inside)."""
    acc = int8_gemm_ref(x, w)
    return int_gelu_ref(acc, gelu_scale)


def int8_gemm_add_ref(x, w, requant, residual):
    """Unfused composition of the requant+residual-add epilogue: int32 GEMM
    -> requantize -> saturating int8 residual add."""
    q = inum.requantize(int8_gemm_ref(x, w), requant)
    return jnp.clip(q + residual.astype(I32), -128, 127).astype(jnp.int8)


def gemm_w8a8_ref(x_q, x_scale, w_q, w_scale, bias=None, residual=None,
                  gelu_scale=None, out_dtype=jnp.bfloat16):
    """Unfused W8A8 linear: int8 GEMM -> f32 rescale (-> int GELU | + res).

    Mirrors models.layers.linear_w8a8 (+ the integer ``activation`` /
    residual add that followed it) exactly, including the bf16 cast of the
    residual stream before activation quantization — the fused ``scaled``
    epilogues are bit-identical to this.
    """
    acc = int8_gemm_ref(x_q, w_q)
    h = acc.astype(jnp.float32) * x_scale * w_scale
    if bias is not None:
        h = h + bias
    if gelu_scale is not None:
        h = h.astype(out_dtype).astype(jnp.float32)
        q = jnp.clip(jnp.round(h * recip(gelu_scale)), -128, 127).astype(I32)
        return int_gelu_ref(q, gelu_scale)
    h = h.astype(out_dtype)
    if residual is not None:
        h = h + residual
    return h


def int_silu_ref(x, scale):
    q, _ = inum.i_silu(x.astype(I32), scale)
    return q.astype(I32)


def gated_mlp_ref(x, w_up, w_gate, act="silu", compute_dtype=jnp.bfloat16):
    """Unfused float gated MLP exactly as ``models.layers`` composes it:
    two compute-dtype GEMMs, float activation of the gate, multiply."""
    dims = (((x.ndim - 1,), (0,)), ((), ()))
    xc = x.astype(compute_dtype)
    h = jax.lax.dot_general(xc, w_up.astype(compute_dtype), dims,
                            preferred_element_type=compute_dtype)
    g = jax.lax.dot_general(xc, w_gate.astype(compute_dtype), dims,
                            preferred_element_type=compute_dtype)
    a = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g, approximate=False)
    return a * h


def gated_mlp_w8a8_ref(x_q, x_scale, w_up_q, up_scale, w_gate_q, gate_scale,
                       act="silu", act_scale=None,
                       out_dtype=jnp.bfloat16):
    """Unfused composition the fused dual-GEMM must match bit-for-bit: two
    scaled-dequant W8A8 GEMMs over the same quantized activations ->
    integer activation (i_silu / i_gelu polynomial) of the gate at a static
    scale -> elementwise multiply in the residual-stream dtype."""
    from .int_gelu import gelu_out_scale
    from .int_silu import silu_out_scale
    h = gemm_w8a8_ref(x_q, x_scale, w_up_q, up_scale, out_dtype=out_dtype)
    g = gemm_w8a8_ref(x_q, x_scale, w_gate_q, gate_scale,
                      out_dtype=out_dtype)
    q = jnp.clip(jnp.round(g.astype(jnp.float32) * recip(act_scale)),
                 -128, 127).astype(I32)
    if act == "silu":
        a = (int_silu_ref(q, act_scale).astype(jnp.float32)
             * silu_out_scale(act_scale)).astype(out_dtype)
    else:
        a = (int_gelu_ref(q, act_scale).astype(jnp.float32)
             * gelu_out_scale(act_scale)).astype(out_dtype)
    return a * h


def unpack_int4_ref(packed, k):
    """Oracle for the int4 nibble container: packed int8 [..., ceil(K/2), N]
    -> sign-extended int8 [..., K, N].  Written with modular arithmetic (no
    shifts) so it is independent of ``quantize.unpack_int4``: the low
    nibble is ``((b & 0xF) ^ 8) - 8`` and the high nibble is a floor
    division by 16 (== arithmetic shift)."""
    p = packed.astype(I32)
    lo = jnp.bitwise_xor(jnp.bitwise_and(p, 0xF), 8) - 8
    hi = jnp.floor_divide(p, 16)
    kp, n = packed.shape[-2], packed.shape[-1]
    w = jnp.stack([lo, hi], axis=-2).reshape(*packed.shape[:-2], 2 * kp, n)
    return w[..., :k, :].astype(jnp.int8)


def gemm_w4a8_ref(x_q, x_scale, w4, qmul, w_scale, bias=None, residual=None,
                  gelu_scale=None, out_dtype=jnp.bfloat16):
    """Unfused W4A8 linear: nibble-unpack -> per-group int8xint4 GEMM ->
    INTEGER group combine -> one float rescale (-> int GELU | + res).

    Two-level group scales: a group's effective scale is ``w_scale[n] *
    qmul[g, n]`` (per-column f32 x per-group int8 multiplier).  The group
    combine ``sum_g part_g * qmul_g`` therefore stays in int32 — exact and
    order-independent, so fused and unfused agree bit for bit no matter how
    the compiler reassociates (a direct f32 scale accumulation is NOT
    deterministic: XLA contracts mul+add chains into FMAs and reorders
    them).  Only then does ONE float multiply chain apply ``w_scale *
    x_scale`` — the same epilogue shape as gemm_w8a8_ref.

    The per-group partial GEMM runs in f32: with |x| <= 128 and |w| <= 8 a
    group partial sum is bounded by g * 1024 <= 2^17 for g <= 128 — inside
    f32's 2^24 exact-integer range — so the f32 dot yields EXACTLY the
    int32 GEMM's integers while using the fast float matmul units, and the
    int32 cast back is exact.  ``k * 1024 * 127 < 2^31`` bounds the
    combined accumulator (asserted; both sides would wrap identically past
    it, but the guardrail keeps the math overflow-free).
    """
    k = x_q.shape[-1]
    groups = qmul.shape[-2]
    g = k // groups
    assert g * groups == k and g * 128 * 8 < 2 ** 24, (k, groups)
    assert k * 128 * 8 * 127 < 2 ** 31, k  # int32 combine headroom
    w = unpack_int4_ref(w4, k).astype(jnp.float32)
    xf = x_q.astype(jnp.float32)
    acc = jnp.zeros((*x_q.shape[:-1], w4.shape[-1]), I32)
    for gi in range(groups):
        part = jax.lax.dot_general(
            xf[..., gi * g:(gi + 1) * g], w[gi * g:(gi + 1) * g],
            (((xf.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = acc + part.astype(I32) * qmul[gi].astype(I32)
    h = acc.astype(jnp.float32) * w_scale * x_scale
    if bias is not None:
        h = h + bias
    if gelu_scale is not None:
        h = h.astype(out_dtype).astype(jnp.float32)
        q = jnp.clip(jnp.round(h * recip(gelu_scale)), -128, 127).astype(I32)
        return int_gelu_ref(q, gelu_scale)
    h = h.astype(out_dtype)
    if residual is not None:
        h = h + residual
    return h


def gated_mlp_w4a8_ref(x_q, x_scale, up4, up_mul, up_scale, gate4, gate_mul,
                       gate_scale, act="silu", act_scale=None,
                       out_dtype=jnp.bfloat16):
    """Unfused composition the fused W4A8 dual-GEMM must match bit-for-bit:
    two group-scaled W4A8 GEMMs over the same quantized activations ->
    integer activation of the gate at a static scale -> multiply in the
    residual-stream dtype (exactly gated_mlp_w8a8_ref past the GEMMs)."""
    from .int_gelu import gelu_out_scale
    from .int_silu import silu_out_scale
    h = gemm_w4a8_ref(x_q, x_scale, up4, up_mul, up_scale,
                      out_dtype=out_dtype)
    g = gemm_w4a8_ref(x_q, x_scale, gate4, gate_mul, gate_scale,
                      out_dtype=out_dtype)
    q = jnp.clip(jnp.round(g.astype(jnp.float32) * recip(act_scale)),
                 -128, 127).astype(I32)
    if act == "silu":
        a = (int_silu_ref(q, act_scale).astype(jnp.float32)
             * silu_out_scale(act_scale)).astype(out_dtype)
    else:
        a = (int_gelu_ref(q, act_scale).astype(jnp.float32)
             * gelu_out_scale(act_scale)).astype(out_dtype)
    return a * h


def int_softmax_ref(x, scale, mask=None):
    return inum.i_softmax(x.astype(I32), scale, mask=mask).astype(jnp.int8)


def int_layernorm_ref(x, gamma_q, beta_q, rms_only=False):
    out, _ = inum.i_layernorm(
        x.astype(I32), 1.0, gamma_q.astype(I32), beta_q.astype(I32), 1.0,
        rms_only=rms_only)
    return out


def int_gelu_ref(x, scale):
    q, _ = inum.i_gelu_int8(x.astype(I32), scale)
    return q.astype(jnp.int8)


def quantize_rows_ref(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8)
    scale = amax * recip(127.0)
    q = jnp.clip(jnp.round(x / scale), -128, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def requantize_i32_ref(x, params):
    return inum.requantize(x.astype(I32), params).astype(jnp.int8)


def int8_conv2d_ref(x, w, bias, requant_params=None):
    acc = jax.lax.conv_general_dilated(
        x.astype(I32), w.astype(I32), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=I32)
    acc = acc + bias.astype(I32)
    if requant_params is None:
        return acc
    return inum.requantize(acc, requant_params).astype(jnp.int8)


def flash_attention_ref(q, k, v, causal=True, scale=None):
    b, h, s, d = q.shape
    _, hkv, skv, _ = k.shape
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, skv), bool), k=skv - s)
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p, v.astype(jnp.float32)).astype(q.dtype)


def int8_kv_decode_attention_ref(q, k_q, k_s, v_q, v_s, pos_ids, qpos,
                                 scale=None, window=0):
    """Oracle for kernels.int8_kv_decode_attention (dequant-then-attend)."""
    b, hq, d = q.shape
    hkv = k_q.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    k = (k_q.astype(jnp.float32) * k_s)                 # (B,S,Hkv,D)
    v = (v_q.astype(jnp.float32) * v_s)
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    # f32 contractions at f32 precision (a TPU's default is bf16 passes)
    hi = jax.lax.Precision.HIGHEST
    s_ = jnp.einsum("bhgd,bshd->bhgs", qg, k, precision=hi) * scale
    valid = (pos_ids >= 0) & (pos_ids <= qpos[:, None])
    if window:
        valid &= pos_ids > (qpos[:, None] - window)
    s_ = jnp.where(valid[:, None, None, :], s_, -1e30)
    p = jax.nn.softmax(s_, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v, precision=hi)
    return o.reshape(b, hq, d).astype(q.dtype)


def paged_decode_attention_ref(q, pk, pks, pv, pvs, ppos, pt, qpos,
                               scale=None, window=0):
    """Oracle for kernels.paged_attention: gather the per-lane page view
    through the page table, then dequant-then-attend exactly like the
    dense decode oracle.  ``pks``/``pvs`` None = bf16 pages (no scales)."""
    n_pages, ps = ppos.shape
    ptc = jnp.clip(pt, 0, n_pages - 1)                    # (B, MP)
    b, mp = ptc.shape
    hkv, d = pk.shape[2], pk.shape[3]
    view = lambda a: a[ptc].reshape(b, mp * ps, hkv, -1)
    ones = jnp.ones((n_pages, ps, hkv, 1), jnp.float32)
    pos = ppos[ptc].reshape(b, mp * ps)
    out = int8_kv_decode_attention_ref(
        q, view(pk), view(pks if pks is not None else ones),
        view(pv), view(pvs if pvs is not None else ones),
        pos, qpos, scale=scale, window=window)
    # lanes with NO valid slot (idle: qpos -1 / all-null table / window
    # excluded everything) emit exact zeros, matching the kernel, instead
    # of a masked-uniform mean
    valid = (pos >= 0) & (pos <= qpos[:, None])
    if window:
        valid &= pos > (qpos[:, None] - window)
    live = jnp.any(valid, axis=1)
    return jnp.where(live[:, None, None], out, 0)


def int8_flash_attention_ref(q, k, v, scale, causal=True, v_scale=None,
                             bq=None):
    """Bit-exact integer oracle of kernels.int8_flash_attention.

    With ``v_scale`` (per-(token, head) scales, [B,Hkv,Skv,1] f32) the PV
    contraction runs in f32 over the dequantized V rows and the result is
    the final attention output (acc / 127) — the exact composition the
    fused PV-dequant pass must reproduce.  ``bq`` contracts ``bq`` query
    rows at a time, the kernel's own (bq, Skv) x (Skv, D) product: XLA:CPU
    picks an f32 dot's summation order by the product's shape, so a row's
    sum depends on how many rows share its product.  ``None`` contracts
    all S rows at once.
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
    sc = jnp.einsum("bhsd,bhtd->bhst", q.astype(I32), k.astype(I32)) >> rshift
    if causal:
        cmask = jnp.tril(jnp.ones((s, skv), bool), k=skv - s)
        sc = jnp.where(cmask, sc, -(2 ** 24))
    p = inum.i_softmax(sc, scale)  # int32 payload in [0,127]
    if v_scale is not None:
        vd = v.astype(jnp.float32) * v_scale                  # (B,H,Skv,D)
        nq = 1 if bq is None else s // bq
        pb = p.astype(jnp.float32).reshape(b, h, nq, s // nq, skv)
        # V broadcast over the query blocks makes each block a product of
        # its own (a free block dim would fold them back into one of S rows)
        vb = jnp.broadcast_to(vd[:, :, None], (b, h, nq, skv, d))
        out = jnp.einsum("bhnqt,bhntd->bhnqd", pb, vb).reshape(b, h, s, d)
        return out * (1.0 / 127.0)
    return jnp.einsum("bhst,bhtd->bhsd", p.astype(jnp.int8).astype(I32),
                      v.astype(I32))


def ssd_scan_ref(x, dt, b, c, a, chunk=128):
    """Oracle for kernels.ssd_scan: sequential state-space recurrence
    h_t = exp(dt_t * a) h_{t-1} + dt_t * B_t x_t^T ;  y_t = C_t . h_t"""
    bh, t, p = x.shape
    n = b.shape[-1]

    def per_head(xh, dth, bh_, ch, ah):
        def step(h, inp):
            xt, dtt, bt, ct = inp
            h = jnp.exp(dtt * ah) * h + dtt * bt[:, None] * xt[None, :]
            return h, ct @ h

        h0 = jnp.zeros((n, p), jnp.float32)
        _, ys = jax.lax.scan(step, h0, (xh.astype(jnp.float32),
                                        dth.astype(jnp.float32),
                                        bh_.astype(jnp.float32),
                                        ch.astype(jnp.float32)))
        return ys

    return jax.vmap(per_head)(x, dt, b, c, a[:, 0]).astype(x.dtype)
