"""Block-gather paged decode attention over the paged KV arena.

Extends the ``int8_kv_decode_attention`` design (one int8 pass over the
cache, in-register per-(token, head) dequant, f32 online softmax) to the
PAGED cache layout (``models/attention.init_paged_cache``): K/V live in a
global arena of fixed-size pages and each lane's logical sequence is a
chain of physical page ids in its page table.

Operands are read as ``_write_paged`` stores them: the arenas ``pk``/``pv``
(n_pages, ps, Hkv, D), their f32 scales ``pks``/``pvs`` (n_pages, ps, Hkv,
1) and the slot positions ``ppos`` (n_pages, ps), with no transpose or
reshape in the wrapper.  XLA still lays out what the layer's scatter
leaves for the kernel: each layer makes a lane-padded copy of each f32
scale arena (filed under the scatter's op_name), which only a stored
layout shared by the scatter and the kernel would remove.

Grid: (B, MP / ppb).  Step (b, j) covers ``ppb`` consecutive logical pages
of lane b's table (``autotune.paged_decode_pages`` picks ``ppb`` from the
shapes).  Each page slot p of the block is its own operand of each arena
(the same HBM buffer, passed ``ppb`` times), gathered HBM->VMEM by a
page-sized block whose index map is one read of a scalar-prefetched
(B, MP) table of the page each slot holds (``_block_pages``, a few
integer ops on the page table before the call); Pallas double-buffers
every operand, so block j+1's pages stream in while block j computes.
A page of the stored shape is copied whole, which the tiled layouts of
the int8 (ps, Hkv, D) page and its (ps, Hkv, 1) scales allow only
through such blocks: Mosaic refuses a manual DMA of one page of either
(it cannot slice a dimension narrower than its tile).  A page's
positions come the same way, in the (8, ps) block of ``ppos`` that holds
its row (one int32 sublane tile), so VMEM holds a fixed number of
positions whatever the pool's size.  Every KV head's (G, D) query group
attends its own head of the block.

Dead slots cost nothing.  ``_write_paged`` puts position ``pos`` in
logical page ``pos // ps``, so a page ``t`` with ``t * ps > qpos`` holds no
valid key: its operand keeps the block it last held (Pallas issues no
copy for an unchanged block index), and blocks made only of such pages,
or, with ``window``, wholly at or below ``qpos - window``, do no compute.
An idle lane (``qpos`` -1) computes nothing and emits exact zeros.
Inside a live block the position mask is the dense decode kernel's
(``kpos >= 0 & kpos <= qpos``, plus the window term), so partial pages
and COW-cleared slots stay exact; the rows of pages past ``qpos`` are
masked by their logical index as well and their V rows zeroed, since
their buffers hold another page.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune
from .common import interpret_mode

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
POS_ROWS = autotune.SUBLANE  # rows of ``ppos`` a page's positions come in


def _live_blocks(qpos, *, ps: int, ppb: int, n_blk: int, window: int):
    """Blocks [lo, hi) of a lane's table that hold every position that can
    be valid, and its last logical page at or below ``qpos`` (-1: none).
    Logical page t covers positions [t*ps, (t+1)*ps)."""
    bt = ppb * ps
    hi = jnp.where(qpos >= 0, jnp.minimum(qpos // bt + 1, n_blk), 0)
    lo = jnp.zeros_like(qpos)
    if window:
        lo = jnp.minimum(jnp.maximum(qpos - window + 1, 0) // bt, n_blk - 1)
    return lo, hi, jnp.where(qpos >= 0, qpos // ps, -1)


def _block_pages(pt, qpos, *, ps: int, ppb: int, window: int):
    """(B, MP) physical page that page slot ``t % ppb`` of block
    ``t // ppb`` holds: the table's page while that slot is live, then the
    slot's last live page (the same block index, so Pallas copies
    nothing), or the null page 0 for a slot with no live page in the
    lane.  Blocks before ``lo`` hold block ``lo``'s pages."""
    b, mp = pt.shape
    lo, _, last_page = _live_blocks(qpos[:, None], ps=ps, ppb=ppb,
                                    n_blk=mp // ppb, window=window)
    t = jnp.arange(mp, dtype=jnp.int32)[None, :]
    j, p = t // ppb, t % ppb
    # last block in which slot p is live; below lo if it never is
    j_last = jnp.where(last_page >= p, (last_page - p) // ppb, -1)
    j_eff = jnp.minimum(jnp.maximum(j, lo), j_last)
    held = jnp.take_along_axis(pt, jnp.clip(j_eff * ppb + p, 0, mp - 1),
                               axis=1)
    return jnp.where(j_last >= lo, held, 0).astype(jnp.int32)


def _kernel(pages_ref, qpos_ref, q_ref, *refs, scale: float, window: int,
            ppb: int, ps: int, n_blk: int, hkv: int, int8: bool,
            pos_rows: int):
    n_arenas = 5 if int8 else 3
    pages = [refs[a * ppb:(a + 1) * ppb] for a in range(n_arenas)]
    o_ref, m_scr, l_scr, acc_scr = refs[n_arenas * ppb:]
    k_refs, v_refs, pos_refs = pages[:3]
    ks_refs, vs_refs = pages[3:] if int8 else (None, None)
    b, j = pl.program_id(0), pl.program_id(1)
    qpos = qpos_ref[b]
    lo, hi, last_page = _live_blocks(qpos, ps=ps, ppb=ppb, n_blk=n_blk,
                                     window=window)
    bt = ppb * ps

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((j >= lo) & (j < hi))
    def _block():
        kpos = jnp.concatenate(
            [r[pl.ds(pages_ref[b, j * ppb + p] % pos_rows, 1), :]
             for p, r in enumerate(pos_refs)], axis=1)
        # logical page of each row / column of the block: pages past
        # qpos hold another page's data
        row = j * ppb + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0) // ps
        col = j * ppb + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1) // ps
        valid = (kpos >= 0) & (kpos <= qpos) & (col <= last_page)
        if window:
            valid &= kpos > (qpos - window)
        for h in range(hkv):
            # in-register dequant, page by page, into one (bt, D) block
            def gather(refs, scales):
                tiles = [r[:, h, :].astype(F32) for r in refs]
                if int8:
                    tiles = [t * s[:, h, :] for t, s in zip(tiles, scales)]
                return jnp.concatenate(tiles, axis=0)

            q = q_ref[0, h].astype(F32)                         # (G, D)
            k = gather(k_refs, ks_refs)
            v = jnp.where(row <= last_page, gather(v_refs, vs_refs), 0.0)
            # f32 operands at f32 precision, like the oracle (a TPU dot
            # otherwise rounds f32 operands to bf16)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    precision=HIGHEST,
                                    preferred_element_type=F32) * scale
            s = jnp.where(valid, s, NEG)                        # (G, bt)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p_ = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p_, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p_, v, (((1,), (0,)), ((), ())), precision=HIGHEST,
                preferred_element_type=F32)
            m_scr[h] = m_new

    @pl.when(j == n_blk - 1)
    def _emit():
        # a lane with NO valid slot (idle lane, qpos -1, window past every
        # slot) emits exact zeros rather than a masked-uniform mean: m
        # never left its NEG init, so the guard costs one compare
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
    ppb = autotune.paged_decode_pages(ps, mp, hkv, d, int8=int8)
    n_blk = mp // ppb
    # (B, Hkv, G, D): each KV head's query group is a whole tile
    q4 = q.reshape(b, hkv, g, d)
    qpos = qpos.astype(jnp.int32)
    pages = _block_pages(pt, qpos, ps=ps, ppb=ppb, window=window)
    pos_rows = min(POS_ROWS, n_pages)

    def page_spec(p, arena):
        if arena.ndim == 2:     # ppos: the block of rows holding the page's
            return pl.BlockSpec(
                (pos_rows, ps), lambda i, j, pg_ref, qp_ref:
                (pg_ref[i, j * ppb + p] // pos_rows, 0))
        return pl.BlockSpec(
            (None,) + arena.shape[1:],
            lambda i, j, pg_ref, qp_ref: (pg_ref[i, j * ppb + p], 0, 0, 0))

    arenas = [pk, pv, ppos] + ([pks, pvs] if int8 else [])
    lane_block = pl.BlockSpec((1, hkv, g, d),
                              lambda i, j, pg_ref, qp_ref: (i, 0, 0, 0))
    kernel = functools.partial(_kernel, scale=scale, window=window,
                               ppb=ppb, ps=ps, n_blk=n_blk, hkv=hkv,
                               int8=int8, pos_rows=pos_rows)
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_blk),
            in_specs=[lane_block]
            + [page_spec(p, a) for a in arenas for p in range(ppb)],
            out_specs=lane_block,
            scratch_shapes=[
                pltpu.VMEM((hkv, g, 1), F32),
                pltpu.VMEM((hkv, g, 1), F32),
                pltpu.VMEM((hkv, g, d), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        name="paged_decode_attention",
        interpret=interpret_mode(interpret),
    )(pages, qpos, q4,
      *[a for a in arenas for _ in range(ppb)])
    return o.reshape(b, hq, d)
