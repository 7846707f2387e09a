"""int8 x int8 -> int32 GEMM with fused epilogues.

The paper's ``gemm`` kernel (Table II) adapted to the TPU MXU: int8 operands
stream HBM->VMEM in MXU-aligned blocks (the MOB role: the Pallas pipeline's
async copies mask HBM latency behind compute, §III-B-2), the MXU accumulates
in int32 (the PE 4x fused-MAC role), and the epilogue finishes the tile
in-register — the int32 accumulator NEVER round-trips through HBM:

  none          int32 accumulator out (the original contract)
  requant       int8 out via the shift/mul16/shift scheme (core.inumerics)
  requant_gelu  integer GELU of the accumulator at a static scale — the
                fused form of ``gemm_i8 -> gelu_i8`` (MLP up-projection)
  requant_add   requantize + int8 residual add (attention out-projection
                into an int8 residual stream)
  scaled        f32 dequant epilogue acc * row_scale * col_scale (+bias) —
                the fused form of the W8A8 linear's float rescale
  scaled_gelu   scaled, then integer GELU at a static activation scale
  scaled_add    scaled, then residual add in the output dtype

Grid: (M/bm, N/bn, K/bk), K innermost so the int32 accumulator tile stays
resident in VMEM scratch across the K loop (one write to HBM per (m,n)
tile).  Block sizes come from ``kernels.autotune``.

``dual_gemm_gated`` extends the same structure to the 2-GEMM gated MLP
(SwiGLU/GeGLU): one shared A-tile stream, two weight streams, two resident
accumulators, and a dequant + integer-activation(gate) * up epilogue.

``int4_gemm`` / ``dual_int4_gemm_gated`` are the W4A8 twins: the weight
stream is half-width (two int4 values per byte, ``quantize.pack_int4``
layout) plus a small (K/g, N) int8 group-multiplier stream and a (N,)
per-column f32 scale (two-level scales; see ``layers.quantize_weight_w4``).
Each K block is nibble-unpacked in-register (the packed bytes never widen
in HBM), contracted on the MXU one scale group at a time, and multiplier-
accumulated into a resident INT32 tile: ``acc += part * qmul[g]`` stays
integer, so the group combine is exact and order-independent — XLA's
freedom to FMA-contract or reorder f32 chains cannot perturb it, and the
kernel is bit-identical to the unfused unpack -> int8-GEMM composition
(``ref.gemm_w4a8_ref``) on any backend.  The single float rescale
``acc * w_scale * x_scale`` happens once in the epilogue (a mul-only
chain, same shape as the W8A8 ``scaled`` epilogue).  Headroom:
K/g * (g*128*8) * 127 < 2^31 for every supported shape (asserted at
trace time).  Epilogues reuse the scaled family above.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.inumerics import RequantParams
from .common import interpret_mode, recip, requant_block
from .int_gelu import gelu_block, gelu_out_scale, gelu_requant_params
from .int_silu import silu_block, silu_out_scale

I32 = jnp.int32
F32 = jnp.float32

EPILOGUES = ("none", "requant", "requant_gelu", "requant_add",
             "scaled", "scaled_gelu", "scaled_add")


def _kernel(*refs, n_k: int, epilogue: str, s1: int, mult: int, s2: int,
            gelu_scale: float, g_s1: int, g_mult: int, g_s2: int,
            has_scales: bool, has_bias: bool, has_res: bool, stream_dtype):
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    xs_ref = next(it) if has_scales else None
    ws_ref = next(it) if has_scales else None
    b_ref = next(it) if has_bias else None
    r_ref = next(it) if has_res else None
    out_ref, acc_ref = next(it), next(it)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU: int8 x int8 -> int32
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=I32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        if epilogue == "none":
            out_ref[...] = acc
        elif epilogue == "requant":
            out_ref[...] = requant_block(acc, s1, mult, s2).astype(jnp.int8)
        elif epilogue == "requant_gelu":
            out_ref[...] = gelu_block(
                acc, scale=gelu_scale, s1=g_s1, mult=g_mult,
                s2=g_s2).astype(jnp.int8)
        elif epilogue == "requant_add":
            q = requant_block(acc, s1, mult, s2)
            out_ref[...] = jnp.clip(
                q + r_ref[...].astype(I32), -128, 127).astype(jnp.int8)
        else:  # scaled family: f32 dequant in-register
            h = acc.astype(F32) * xs_ref[...] * ws_ref[...]
            if has_bias:
                h = h + b_ref[...]
            if epilogue == "scaled_gelu":
                # the unfused path quantizes the bf16 residual stream: keep
                # the same grid so fused == unfused bit-for-bit
                h = h.astype(stream_dtype).astype(F32)
                q = jnp.clip(jnp.round(h * recip(gelu_scale)), -128, 127).astype(I32)
                out_ref[...] = gelu_block(
                    q, scale=gelu_scale, s1=g_s1, mult=g_mult,
                    s2=g_s2).astype(jnp.int8)
            else:
                h = h.astype(stream_dtype)
                if epilogue == "scaled_add":
                    h = h + r_ref[...]
                out_ref[...] = h


@functools.partial(
    jax.jit,
    static_argnames=("requant", "out_dtype", "bm", "bn", "bk", "epilogue",
                     "gelu_scale", "interpret"),
)
def int8_gemm(
    x: jax.Array,
    w: jax.Array,
    requant: RequantParams | None = None,
    out_dtype=jnp.int32,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    epilogue: str | None = None,
    gelu_scale: float | None = None,
    x_scale: jax.Array | None = None,   # (M, 1) f32 per-row act scales
    w_scale: jax.Array | None = None,   # (1, N) f32 per-col weight scales
    bias: jax.Array | None = None,      # (1, N) f32
    residual: jax.Array | None = None,  # (M, N) int8 or out_dtype
    interpret: bool | None = None,
) -> jax.Array:
    """x[int8 M,K] @ w[int8 K,N] with the requested fused epilogue."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"pad shapes to block multiples first: {(m, k, n)} vs {(bm, bk, bn)}")
    if epilogue is None:
        epilogue = "requant" if requant is not None else "none"
    assert epilogue in EPILOGUES, epilogue
    stream_dtype = out_dtype  # scaled epilogues: the residual-stream dtype
    if epilogue == "none":
        out_dtype = jnp.int32
    elif epilogue.startswith("requant") or epilogue == "scaled_gelu":
        out_dtype = jnp.int8
    elif epilogue == "scaled_add":
        # standard promotion: a float32 residual widens the output
        out_dtype = jnp.promote_types(stream_dtype, residual.dtype)
    s1 = mult = s2 = 0
    if requant is not None:
        s1, mult, s2 = requant.s1, requant.mult, requant.s2
    g_s1 = g_mult = g_s2 = 0
    if epilogue.endswith("gelu"):
        assert gelu_scale is not None
        gp = gelu_requant_params(gelu_scale)
        g_s1, g_mult, g_s2 = gp.s1, gp.mult, gp.s2
    has_scales = epilogue.startswith("scaled")
    has_bias = bias is not None
    has_res = epilogue.endswith("add")

    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    operands = [x, w]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    if has_scales:
        assert x_scale is not None and w_scale is not None
        operands += [x_scale, w_scale.reshape(1, n)]
        in_specs += [
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ]
    if has_bias:
        operands.append(bias.reshape(1, n))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    if has_res:
        assert residual is not None and residual.shape == (m, n)
        operands.append(residual)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))

    kernel = functools.partial(
        _kernel, n_k=n_k, epilogue=epilogue, s1=s1, mult=mult, s2=s2,
        gelu_scale=0.0 if gelu_scale is None else gelu_scale,
        g_s1=g_s1, g_mult=g_mult, g_s2=g_s2, has_scales=has_scales,
        has_bias=has_bias, has_res=has_res, stream_dtype=stream_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), I32)],
        interpret=interpret_mode(interpret),
    )(*operands)


# ---------------------------------------------------------------------------
# Dual-GEMM gated MLP (SwiGLU / GeGLU): the 2-GEMM fusion the epilogue
# matrix could not express with one weight stream.  The A tile (x) streams
# HBM->VMEM ONCE per grid step and feeds BOTH weight streams; two int32
# (f32 for the float variant) accumulators stay resident in VMEM across the
# K loop, and the epilogue finishes dequant + activation(gate) * up
# in-register — neither the (M, N) up/gate accumulator nor the activated
# gate ever touches HBM.
# ---------------------------------------------------------------------------

GATED_ACTS = ("silu", "gelu")


def _dual_kernel(*refs, n_k: int, act: str, act_scale: float,
                 g_s1: int, g_mult: int, g_s2: int, integer: bool,
                 stream_dtype):
    it = iter(refs)
    x_ref, wu_ref, wg_ref = next(it), next(it), next(it)
    xs_ref = us_ref = gs_ref = None
    if integer:
        xs_ref, us_ref, gs_ref = next(it), next(it), next(it)
    out_ref, acc_u, acc_g = next(it), next(it), next(it)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_u[...] = jnp.zeros_like(acc_u)
        acc_g[...] = jnp.zeros_like(acc_g)

    # the shared A tile: ONE HBM read, two MXU contractions
    x = x_ref[...]
    acc_t = I32 if integer else F32
    acc_u[...] += jax.lax.dot_general(
        x, wu_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=acc_t)
    acc_g[...] += jax.lax.dot_general(
        x, wg_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=acc_t)

    @pl.when(k == n_k - 1)
    def _epilogue():
        if integer:
            # mirror the unfused composition EXACTLY: each GEMM dequantizes
            # through the residual-stream dtype, the gate requantizes at the
            # static activation scale and runs the integer polynomial
            h = (acc_u[...].astype(F32) * xs_ref[...] * us_ref[...]
                 ).astype(stream_dtype)
            g = (acc_g[...].astype(F32) * xs_ref[...] * gs_ref[...]
                 ).astype(stream_dtype).astype(F32)
            q = jnp.clip(jnp.round(g * recip(act_scale)), -128, 127).astype(I32)
            if act == "silu":
                a = (silu_block(q, scale=act_scale).astype(F32)
                     * silu_out_scale(act_scale)).astype(stream_dtype)
            else:
                a = (gelu_block(q, scale=act_scale, s1=g_s1, mult=g_mult,
                                s2=g_s2).astype(F32)
                     * gelu_out_scale(act_scale)).astype(stream_dtype)
            out_ref[...] = a * h
        else:
            g = acc_g[...]
            a = (jax.nn.silu(g) if act == "silu"
                 else jax.nn.gelu(g, approximate=False))
            out_ref[...] = (a * acc_u[...]).astype(stream_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("act", "act_scale", "out_dtype", "bm", "bn", "bk",
                     "interpret"),
)
def dual_gemm_gated(
    x: jax.Array,
    w_up: jax.Array,
    w_gate: jax.Array,
    x_scale: jax.Array | None = None,   # (M, 1) f32 per-row act scales
    up_scale: jax.Array | None = None,  # (1, N) f32 per-col weight scales
    gate_scale: jax.Array | None = None,
    act: str = "silu",
    act_scale: float | None = None,
    out_dtype=jnp.bfloat16,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """activation(x @ w_gate) * (x @ w_up) with both GEMMs fused.

    int8 operands (W8A8): requires the three scale operands plus the static
    ``act_scale``; bit-identical to the unfused scaled-dequant GEMMs ->
    integer activation -> multiply composition.  Float operands: f32
    accumulators, float activation epilogue (matches the unfused
    composition to accumulation order).
    """
    m, k = x.shape
    k2, n = w_up.shape
    assert k == k2 and w_gate.shape == (k, n), (x.shape, w_up.shape,
                                                w_gate.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"pad shapes to block multiples first: {(m, k, n)} vs {(bm, bk, bn)}")
    assert act in GATED_ACTS, act
    integer = x.dtype == jnp.int8
    g_s1 = g_mult = g_s2 = 0
    if integer:
        assert (x_scale is not None and up_scale is not None
                and gate_scale is not None and act_scale is not None)
        if act == "gelu":
            gp = gelu_requant_params(act_scale)
            g_s1, g_mult, g_s2 = gp.s1, gp.mult, gp.s2

    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    operands = [x, w_up, w_gate]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    if integer:
        operands += [x_scale, up_scale.reshape(1, n),
                     gate_scale.reshape(1, n)]
        in_specs += [
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ]

    kernel = functools.partial(
        _dual_kernel, n_k=n_k, act=act,
        act_scale=0.0 if act_scale is None else act_scale,
        g_s1=g_s1, g_mult=g_mult, g_s2=g_s2, integer=integer,
        stream_dtype=out_dtype)
    acc_dtype = I32 if integer else F32
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype),
                        pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret_mode(interpret),
    )(*operands)


# ---------------------------------------------------------------------------
# W4A8: packed-int4 weight stream, in-register nibble unpack, two-level
# group scales (per-column f32 x per-group int8 multiplier).  The group
# combine stays in the int32 accumulator — exact and order-independent, so
# fused == unfused holds bit-for-bit regardless of how the compiler
# reassociates (f32 group-scale accumulation is NOT deterministic under
# XLA's FMA contraction + loop reordering).  Only the ``scaled`` epilogue
# family applies: one float multiply chain past the integer contract,
# exactly the W8A8 epilogue shape.
# ---------------------------------------------------------------------------

W4A8_EPILOGUES = ("scaled", "scaled_gelu", "scaled_add")


def _unpack_block(packed, bk):
    """(bk//2, bn) packed int8 -> (bk, bn) sign-extended int8, in-register.

    Same nibble layout as ``quantize.pack_int4``: low nibble = even K row,
    high nibble = odd K row.  Three VPU ops (two shifts sign-extend the low
    nibble, one the high) plus an interleave.  The shifts run on int32
    lanes: the v5e VPU has no 8-bit shift.
    """
    p = packed.astype(I32)
    lo = jnp.right_shift(jnp.left_shift(p, 28), 28)
    hi = jnp.right_shift(p, 4)
    w = jnp.stack([lo, hi], axis=1).reshape(bk, packed.shape[-1])
    return w.astype(jnp.int8)


def _qmul_blocks(qmul, n_k: int):
    """(K/group, N) group multipliers -> (K/bk, bk/group, N): one K block's
    groups form a full-extent second-minor block, so a block of only a few
    group rows stays legal under the TPU's (8, 128) tiling rule."""
    return qmul.reshape(n_k, qmul.shape[0] // n_k, qmul.shape[1])


def _w4a8_kernel(*refs, n_k: int, epilogue: str, gelu_scale: float,
                 g_s1: int, g_mult: int, g_s2: int, group: int, bk: int,
                 has_bias: bool, has_res: bool, stream_dtype):
    it = iter(refs)
    x_ref, w_ref, qm_ref = next(it), next(it), next(it)
    ws_ref, xs_ref = next(it), next(it)
    b_ref = next(it) if has_bias else None
    r_ref = next(it) if has_res else None
    out_ref, acc_ref = next(it), next(it)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = _unpack_block(w_ref[...], bk)
    qm = qm_ref[0].astype(I32)  # (bk // group, bn) int8 group multipliers
    # one MXU contraction per scale group; the int8 group multiplier folds
    # in WITHOUT leaving the int32 accumulator, so the combine is exact and
    # order-independent — bit-identical to the unfused unpack -> int8-GEMM
    # -> integer-combine reference by construction.
    for gi in range(bk // group):
        part = jax.lax.dot_general(
            x[:, gi * group:(gi + 1) * group],
            w[gi * group:(gi + 1) * group],
            (((1,), (0,)), ((), ())),
            preferred_element_type=I32)
        acc_ref[...] += part * qm[gi]

    @pl.when(k == n_k - 1)
    def _epilogue():
        h = acc_ref[...].astype(F32) * ws_ref[...] * xs_ref[...]
        if has_bias:
            h = h + b_ref[...]
        if epilogue == "scaled_gelu":
            h = h.astype(stream_dtype).astype(F32)
            q = jnp.clip(jnp.round(h * recip(gelu_scale)), -128, 127).astype(I32)
            out_ref[...] = gelu_block(
                q, scale=gelu_scale, s1=g_s1, mult=g_mult,
                s2=g_s2).astype(jnp.int8)
        else:
            h = h.astype(stream_dtype)
            if epilogue == "scaled_add":
                h = h + r_ref[...]
            out_ref[...] = h


@functools.partial(
    jax.jit,
    static_argnames=("group", "out_dtype", "bm", "bn", "bk", "epilogue",
                     "gelu_scale", "interpret"),
)
def int4_gemm(
    x: jax.Array,          # (M, K) int8 activations
    w4: jax.Array,         # (K // 2, N) packed int4 weights
    qmul: jax.Array,       # (K // group, N) int8 group multipliers
    w_scale: jax.Array,    # (N,) f32 per-column scales
    x_scale: jax.Array,    # (M, 1) f32 per-row act scales
    group: int = 64,
    epilogue: str = "scaled",
    gelu_scale: float | None = None,
    bias: jax.Array | None = None,      # (1, N) f32
    residual: jax.Array | None = None,  # (M, N) stream dtype
    out_dtype=jnp.bfloat16,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """x[int8 M,K] @ unpack(w4)[int4 K,N] * two-level scales, fused W4A8."""
    m, k = x.shape
    kp, n = w4.shape
    assert kp * 2 == k, (x.shape, w4.shape)
    assert qmul.shape == (k // group, n), (qmul.shape, k, group, n)
    assert qmul.dtype == jnp.int8 and w_scale.size == n, (qmul.dtype,
                                                          w_scale.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"pad shapes to block multiples first: {(m, k, n)} vs {(bm, bk, bn)}")
    assert bk % group == 0 and bk % 2 == 0, (bk, group)
    assert group * 128 * 8 < 2 ** 24, group  # f32 exact-integer bound
    assert k * 128 * 8 * 127 < 2 ** 31, k    # int32 combine headroom
    assert epilogue in W4A8_EPILOGUES, epilogue
    stream_dtype = out_dtype
    if epilogue == "scaled_gelu":
        out_dtype = jnp.int8
    elif epilogue == "scaled_add":
        out_dtype = jnp.promote_types(stream_dtype, residual.dtype)
    g_s1 = g_mult = g_s2 = 0
    if epilogue == "scaled_gelu":
        assert gelu_scale is not None
        gp = gelu_requant_params(gelu_scale)
        g_s1, g_mult, g_s2 = gp.s1, gp.mult, gp.s2
    has_bias = bias is not None
    has_res = epilogue == "scaled_add"

    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    operands = [x, w4, _qmul_blocks(qmul, n_k), w_scale.reshape(1, n),
                x_scale]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((1, bk // group, bn), lambda i, j, kk: (kk, 0, j)),
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
    ]
    if has_bias:
        operands.append(bias.reshape(1, n))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    if has_res:
        assert residual is not None and residual.shape == (m, n)
        operands.append(residual)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))

    kernel = functools.partial(
        _w4a8_kernel, n_k=n_k, epilogue=epilogue,
        gelu_scale=0.0 if gelu_scale is None else gelu_scale,
        g_s1=g_s1, g_mult=g_mult, g_s2=g_s2, group=group, bk=bk,
        has_bias=has_bias, has_res=has_res, stream_dtype=stream_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), I32)],
        interpret=interpret_mode(interpret),
    )(*operands)


def _dual_w4a8_kernel(*refs, n_k: int, act: str, act_scale: float,
                      g_s1: int, g_mult: int, g_s2: int, group: int,
                      bk: int, stream_dtype):
    it = iter(refs)
    x_ref, wu_ref, wg_ref = next(it), next(it), next(it)
    um_ref, gm_ref = next(it), next(it)
    us_ref, gs_ref, xs_ref = next(it), next(it), next(it)
    out_ref, acc_u, acc_g = next(it), next(it), next(it)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_u[...] = jnp.zeros_like(acc_u)
        acc_g[...] = jnp.zeros_like(acc_g)

    # the shared A tile: ONE HBM read feeds both half-width weight streams
    x = x_ref[...]
    wu = _unpack_block(wu_ref[...], bk)
    wg = _unpack_block(wg_ref[...], bk)
    um, gm = um_ref[0].astype(I32), gm_ref[0].astype(I32)
    for gi in range(bk // group):
        xg = x[:, gi * group:(gi + 1) * group]
        pu = jax.lax.dot_general(
            xg, wu[gi * group:(gi + 1) * group],
            (((1,), (0,)), ((), ())), preferred_element_type=I32)
        pg = jax.lax.dot_general(
            xg, wg[gi * group:(gi + 1) * group],
            (((1,), (0,)), ((), ())), preferred_element_type=I32)
        # the group multiplier folds in WITHOUT leaving int32 — exact and
        # order-independent, same combine as _w4a8_kernel
        acc_u[...] += pu * um[gi]
        acc_g[...] += pg * gm[gi]

    @pl.when(k == n_k - 1)
    def _epilogue():
        # integer contracts done; ONE float multiply chain per stream (the
        # W8A8 dual epilogue shape), then stream-dtype casts, integer gate,
        # multiply.
        h = (acc_u[...].astype(F32) * us_ref[...]
             * xs_ref[...]).astype(stream_dtype)
        g = (acc_g[...].astype(F32) * gs_ref[...]
             * xs_ref[...]).astype(stream_dtype).astype(F32)
        q = jnp.clip(jnp.round(g * recip(act_scale)), -128, 127).astype(I32)
        if act == "silu":
            a = (silu_block(q, scale=act_scale).astype(F32)
                 * silu_out_scale(act_scale)).astype(stream_dtype)
        else:
            a = (gelu_block(q, scale=act_scale, s1=g_s1, mult=g_mult,
                            s2=g_s2).astype(F32)
                 * gelu_out_scale(act_scale)).astype(stream_dtype)
        out_ref[...] = a * h


@functools.partial(
    jax.jit,
    static_argnames=("group", "act", "act_scale", "out_dtype", "bm", "bn",
                     "bk", "interpret"),
)
def dual_int4_gemm_gated(
    x: jax.Array,           # (M, K) int8 activations
    up4: jax.Array,         # (K // 2, N) packed int4 up-proj
    up_mul: jax.Array,      # (K // group, N) int8 group multipliers
    up_scale: jax.Array,    # (N,) f32 per-column scales
    gate4: jax.Array,       # (K // 2, N) packed int4 gate-proj
    gate_mul: jax.Array,    # (K // group, N) int8 group multipliers
    gate_scale: jax.Array,  # (N,) f32 per-column scales
    x_scale: jax.Array,     # (M, 1) f32 per-row act scales
    group: int = 64,
    act: str = "silu",
    act_scale: float | None = None,
    out_dtype=jnp.bfloat16,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """activation(x @ gate4) * (x @ up4), both W4A8 GEMMs fused (shared A)."""
    m, k = x.shape
    kp, n = up4.shape
    assert kp * 2 == k and gate4.shape == (kp, n), (x.shape, up4.shape,
                                                    gate4.shape)
    assert up_mul.shape == (k // group, n), (up_mul.shape, k, group, n)
    assert gate_mul.shape == (k // group, n), gate_mul.shape
    assert up_mul.dtype == jnp.int8 and gate_mul.dtype == jnp.int8
    assert up_scale.size == n and gate_scale.size == n
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"pad shapes to block multiples first: {(m, k, n)} vs {(bm, bk, bn)}")
    assert bk % group == 0 and bk % 2 == 0, (bk, group)
    assert k * 128 * 8 * 127 < 2 ** 31, k    # int32 combine headroom
    assert act in GATED_ACTS and act_scale is not None, (act, act_scale)
    g_s1 = g_mult = g_s2 = 0
    if act == "gelu":
        gp = gelu_requant_params(act_scale)
        g_s1, g_mult, g_s2 = gp.s1, gp.mult, gp.s2

    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    operands = [x, up4, gate4, _qmul_blocks(up_mul, n_k),
                _qmul_blocks(gate_mul, n_k),
                up_scale.reshape(1, n), gate_scale.reshape(1, n), x_scale]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((1, bk // group, bn), lambda i, j, kk: (kk, 0, j)),
        pl.BlockSpec((1, bk // group, bn), lambda i, j, kk: (kk, 0, j)),
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
    ]
    kernel = functools.partial(
        _dual_w4a8_kernel, n_k=n_k, act=act, act_scale=act_scale,
        g_s1=g_s1, g_mult=g_mult, g_s2=g_s2, group=group, bk=bk,
        stream_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), I32),
                        pltpu.VMEM((bm, bn), I32)],
        interpret=interpret_mode(interpret),
    )(*operands)
