"""Gather-based paged decode attention over the paged KV arena.

Extends the ``int8_kv_decode_attention`` design (one int8 pass over the
cache, in-register per-(token, head) dequant, f32 online softmax) to the
PAGED cache layout (``models/attention.init_paged_cache``): K/V live in a
global arena of fixed-size pages and each lane's logical sequence is a
chain of physical page ids in its page table.  The page table rides the
TPU scalar-prefetch path (``pltpu.PrefetchScalarGridSpec``): the KV block
index maps read the NEXT physical page id from SMEM before the grid step
runs, so the kernel's DMA engine gathers pages HBM->VMEM directly — the
per-lane dense view is never materialized in HBM (the XLA fallback in
``models/attention._read_paged`` does materialize it; that copy is exactly
what this kernel removes on the pallas backend).

Dead slots need no special casing: empty/stale slots carry ``ppos`` -1
(the allocator clears pages on free/COW) and unmapped page-table entries
name the null page (id 0, ``ppos`` forever -1), so the ordinary position
mask — the same one the dense decode kernel applies — hides them.

Grid: (B * Hkv, MP); the query block (G, D) stays resident, each step
gathers one (ps, D) K and V page tile + their (ps, 1) scale vectors and
the page's (1, ps) slot positions.  int8 pages carry f32 scales; bf16
pages skip the scale streams.  The lane query positions are a second
scalar-prefetch operand (SMEM) next to the page table.
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


def _kernel(pt_ref, qpos_ref, q_ref, *refs, scale: float, window: int,
            n_pages_grid: int, int8: bool, hkv: int):
    if int8:
        k_ref, ks_ref, v_ref, vs_ref, pos_ref, o_ref = refs[:6]
        m_scr, l_scr, acc_scr = refs[6:]
    else:
        k_ref, v_ref, pos_ref, o_ref = refs[:4]
        m_scr, l_scr, acc_scr = refs[4:]
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(F32)                       # (G, D)
    if int8:
        k = k_ref[0, 0].astype(F32) * ks_ref[0, 0]  # (ps, D) in-register dequant
        v = v_ref[0, 0].astype(F32) * vs_ref[0, 0]
    else:
        k = k_ref[0, 0].astype(F32)
        v = v_ref[0, 0].astype(F32)
    kpos = pos_ref[0]                              # (1, ps) absolute positions
    qpos = qpos_ref[i // hkv]                      # this lane's step (SMEM)

    # f32 operands at f32 precision, like the oracle (a TPU dot otherwise
    # rounds f32 operands to bf16)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=HIGHEST,
                            preferred_element_type=F32) * scale  # (G, ps)
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

    @pl.when(j == n_pages_grid - 1)
    def _emit():
        # a lane with NO valid slot (idle lane, qpos -1, all-null table)
        # emits exact zeros rather than a masked-uniform mean: m never left
        # its NEG init, so the guard costs one compare
        live = (m_scr[...] > NEG * 0.5).astype(F32)
        o_ref[0] = (acc_scr[...] * live
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "interpret"))
def paged_decode_attention(
    q: jax.Array,         # (B, Hq, D) bf16/f32 — one query token per lane
    pk: jax.Array,        # (n_pages, ps, Hkv, D) int8 | bf16 page arena
    pks: jax.Array | None,  # (n_pages, ps, Hkv, 1) f32 scales (int8 pages)
    pv: jax.Array,
    pvs: jax.Array | None,
    ppos: jax.Array,      # (n_pages, ps) int32, -1 = empty slot
    pt: jax.Array,        # (B, MP) int32 page table, 0 = null page
    qpos: jax.Array,      # (B,) int32 current positions (-1 = idle lane)
    scale: float | None = None,
    window: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    b, hq, d = q.shape
    n_pages, ps, hkv = pk.shape[:3]
    mp = pt.shape[1]
    g = hq // hkv
    int8 = pks is not None
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # (B*Hkv, G, D) query blocks; arena re-laid (n_pages, Hkv, ps, D) so one
    # grid step gathers a single head's page tile
    q4 = q.reshape(b, hkv, g, d).reshape(b * hkv, g, d)
    kq = jnp.transpose(pk, (0, 2, 1, 3))
    vq = jnp.transpose(pv, (0, 2, 1, 3))

    page_idx = lambda i, j, pt_ref, qp_ref: (pt_ref[i // hkv, j], i % hkv,
                                             0, 0)
    in_specs = [
        pl.BlockSpec((1, g, d), lambda i, j, pt_ref, qp_ref: (i, 0, 0)),
        pl.BlockSpec((1, 1, ps, d), page_idx),
    ]
    inputs = [q4, kq]
    if int8:
        ks = jnp.transpose(pks, (0, 2, 1, 3))
        vs = jnp.transpose(pvs, (0, 2, 1, 3))
        in_specs.append(pl.BlockSpec((1, 1, ps, 1), page_idx))
        inputs.append(ks)
    in_specs.append(pl.BlockSpec((1, 1, ps, d), page_idx))
    inputs.append(vq)
    if int8:
        in_specs.append(pl.BlockSpec((1, 1, ps, 1), page_idx))
        inputs.append(vs)
    # (n_pages, 1, ps): a page's (1, ps) position row spans the full
    # second-minor extent
    in_specs.append(pl.BlockSpec(
        (1, 1, ps), lambda i, j, pt_ref, qp_ref: (pt_ref[i // hkv, j], 0, 0)))
    inputs.append(ppos.reshape(n_pages, 1, ps))

    kernel = functools.partial(_kernel, scale=scale, window=window,
                               n_pages_grid=mp, int8=int8, hkv=hkv)
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * hkv, mp),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, g, d),
                                   lambda i, j, pt_ref, qp_ref: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), F32),
                pltpu.VMEM((g, 1), F32),
                pltpu.VMEM((g, d), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, d), q.dtype),
        interpret=interpret_mode(interpret),
    )(pt, qpos.astype(jnp.int32), *inputs)
    return o.reshape(b, hq, d)
