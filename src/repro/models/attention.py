"""Attention: GQA/MQA, sliding-window, cross-attention, KV cache (bf16/int8).

One implementation serves train (full causal), prefill (causal + cache
write), decode (single query vs. cache) and cross-attention (static KV from
encoder/vision features).  The KV cache is a uniform ring structure:

    cache = {"k": (B,S,Hkv,D), "v": (B,S,Hkv,D), "pos_ids": (B,S) int32}

``pos_ids`` holds the absolute position stored in each slot (-1 = empty);
sliding-window archs allocate S = window and overwrite slots mod S, full
attention allocates S = max_seq.  Masking always derives from pos_ids, so
full/windowed/ring behavior is one code path.  RoPE is applied at write
time with absolute positions, so ring overwrites need no re-rotation.

In w8a8 mode the cache stores int8 payloads with per-(token,head) scales
(the NX-CGRA thesis applied to serving memory: 2x KV capacity per HBM byte),
and prefill attention runs the integer kernel (int8 QK^T -> i-softmax ->
int8 PV).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..dist.sharding import shard_hint
from ..dist.tp import tp_out_projection, tp_serving_ctx
from ..kernels import autotune, ops
from .config import ArchConfig
from .layers import ExecMode, apply_linear, apply_rope, dense_init

F32 = jnp.float32
NEG = -1e30

# canonical static int8 scale for activations entering integer attention
ATTN_INT_SCALE = 1.0 / 16.0


def init_attn_params(key, cfg: ArchConfig, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, nq * hd),
        "wk": dense_init(ks[1], d, nkv * hd),
        "wv": dense_init(ks[2], d, nkv * hd),
        "wo": dense_init(ks[3], nq * hd, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), F32)
        p["bk"] = jnp.zeros((nkv * hd,), F32)
        p["bv"] = jnp.zeros((nkv * hd,), F32)
    return p


def init_cache(cfg: ArchConfig, batch: int, max_seq: int, *, int8: bool,
               window: int = 0, dtype=jnp.bfloat16) -> dict:
    s = min(window, max_seq) if window else max_seq
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    cache: dict[str, Any] = {
        "pos_ids": jnp.full((batch, s), -1, jnp.int32),
    }
    if int8:
        cache["k"] = jnp.zeros((batch, s, hkv, hd), jnp.int8)
        cache["v"] = jnp.zeros((batch, s, hkv, hd), jnp.int8)
        cache["k_s"] = jnp.ones((batch, s, hkv, 1), F32)
        cache["v_s"] = jnp.ones((batch, s, hkv, 1), F32)
    else:
        cache["k"] = jnp.zeros((batch, s, hkv, hd), dtype)
        cache["v"] = jnp.zeros((batch, s, hkv, hd), dtype)
    return cache


def _quant_kv(x: jax.Array):
    """per-(token, head) symmetric int8."""
    amax = jnp.maximum(jnp.max(jnp.abs(x.astype(F32)), axis=-1, keepdims=True), 1e-8)
    s = amax / 127.0
    return jnp.clip(jnp.round(x.astype(F32) / s), -128, 127).astype(jnp.int8), s


def init_paged_cache(cfg: ArchConfig, batch: int, n_pages: int,
                     page_size: int, pages_per_lane: int, *, int8: bool,
                     dtype=jnp.bfloat16) -> dict:
    """Paged KV arena: ONE physical pool of ``n_pages`` fixed-size pages
    shared by every lane, plus the per-lane page table.

        cache = {"pk"/"pv": (n_pages, ps, Hkv, D),          # page payload
                 "pks"/"pvs": (n_pages, ps, Hkv, 1) f32,    # int8 scales
                 "ppos": (n_pages, ps) int32,               # -1 = empty slot
                 "pt":   (B, max_pages) int32}              # page table

    Page 0 is the permanent null page (``serve/kv_pool.py``): unmapped
    table entries point at it and its ``ppos`` stays -1, so gathers need no
    validity branch.  Logical page j of a lane covers absolute positions
    [j*ps, (j+1)*ps); with ps | max_seq the gathered per-lane view is
    element-for-element the dense ``init_cache`` layout (slot i = position
    i), which is what makes the paged serving path bit-identical to the
    dense one."""
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    cache: dict[str, Any] = {
        "ppos": jnp.full((n_pages, page_size), -1, jnp.int32),
    }
    if int8:
        cache["pk"] = jnp.zeros((n_pages, page_size, hkv, hd), jnp.int8)
        cache["pv"] = jnp.zeros((n_pages, page_size, hkv, hd), jnp.int8)
        cache["pks"] = jnp.ones((n_pages, page_size, hkv, 1), F32)
        cache["pvs"] = jnp.ones((n_pages, page_size, hkv, 1), F32)
    else:
        cache["pk"] = jnp.zeros((n_pages, page_size, hkv, hd), dtype)
        cache["pv"] = jnp.zeros((n_pages, page_size, hkv, hd), dtype)
    cache["pt"] = jnp.zeros((batch, pages_per_lane), jnp.int32)  # all null
    return cache


def _write_paged(cache: dict, k, v, positions):
    """Scatter k/v (B,T,Hkv,D) into the page arena through the page table.

    Slot = (page_table[lane, pos // ps], pos % ps).  Pad tokens (position
    -1) and unmapped/null pages route to an out-of-bounds page index and
    the scatter drops them (jnp ``.at`` default) — the engine guarantees a
    lane-owned page backs every real write (kv_pool.ensure_writable), the
    null-page guard is defense in depth.  There is no full-assign fast
    path: page granularity keeps every write a scatter."""
    npg, ps = cache["ppos"].shape
    pt = cache["pt"]                                        # (B, MP)
    b, t = positions.shape
    logical = jnp.clip(jnp.where(positions >= 0, positions // ps, 0),
                       0, pt.shape[1] - 1)
    phys = jnp.take_along_axis(pt, logical, axis=1)         # (B, T)
    phys = jnp.where((positions >= 0) & (phys > 0), phys, npg)  # OOB -> drop
    slot = jnp.where(positions >= 0, positions % ps, 0)
    pf, sf = phys.reshape(-1), slot.reshape(-1)
    cache = dict(cache)
    if "pks" in cache:
        k_q, k_s = _quant_kv(k)
        v_q, v_s = _quant_kv(v)
        cache["pk"] = cache["pk"].at[pf, sf].set(k_q.reshape(b * t, *k_q.shape[2:]))
        cache["pv"] = cache["pv"].at[pf, sf].set(v_q.reshape(b * t, *v_q.shape[2:]))
        cache["pks"] = cache["pks"].at[pf, sf].set(k_s.reshape(b * t, *k_s.shape[2:]))
        cache["pvs"] = cache["pvs"].at[pf, sf].set(v_s.reshape(b * t, *v_s.shape[2:]))
    else:
        cache["pk"] = cache["pk"].at[pf, sf].set(
            k.astype(cache["pk"].dtype).reshape(b * t, *k.shape[2:]))
        cache["pv"] = cache["pv"].at[pf, sf].set(
            v.astype(cache["pv"].dtype).reshape(b * t, *v.shape[2:]))
    cache["ppos"] = cache["ppos"].at[pf, sf].set(positions.reshape(-1))
    return cache


def _read_paged(cache: dict, dtype):
    """Gather the per-lane dense view (B, MP*ps, Hkv, D) + positions.

    With ps | max_seq this view is element-for-element what ``_read_cache``
    returns for the dense cache (null/empty slots carry pos -1 and are
    masked by position, exactly like dense empty slots), so the attention
    math downstream is unchanged — paging only changes where the bytes
    live."""
    npg, ps = cache["ppos"].shape
    pt = jnp.clip(cache["pt"], 0, npg - 1)                  # (B, MP)
    b, mp = pt.shape
    kpos = cache["ppos"][pt].reshape(b, mp * ps)
    if "pks" in cache:
        k = cache["pk"][pt].astype(F32) * cache["pks"][pt]
        v = cache["pv"][pt].astype(F32) * cache["pvs"][pt]
    else:
        k, v = cache["pk"][pt], cache["pv"][pt]
    shape = (b, mp * ps) + k.shape[3:]
    return k.astype(dtype).reshape(shape), v.astype(dtype).reshape(shape), kpos


_PAGE_KEYS = ("pk", "pv", "pks", "pvs", "ppos")


def _page_axis(cache: dict) -> int:
    """Page axis of a paged cache's leaves: 0 for a single layer's cache
    ((n_pages, ps)), 1 for the engine's period-stacked state leaves
    ((P, n_pages, ps))."""
    return 0 if cache["ppos"].ndim == 2 else 1


def gather_pages(cache: dict, page_ids):
    """Pull whole pages' payloads off the arena — the device side of KV
    swap-OUT.  Returns ``{pk, pv[, pks, pvs], ppos}`` sliced to
    ``page_ids`` along the page axis; pure data movement (no dequant, no
    cast), so a gather → scatter_pages round trip is bit-identical
    whatever physical pages the content comes back to."""
    idx = jnp.asarray(page_ids, jnp.int32)
    ax = _page_axis(cache)
    return {k: jnp.take(cache[k], idx, axis=ax)
            for k in _PAGE_KEYS if k in cache}


def scatter_pages(cache: dict, page_ids, payload: dict) -> dict:
    """Write gathered page payloads back into (possibly DIFFERENT)
    physical pages — the device side of swap-IN page rebind.  Positional
    content travels with the page (``ppos`` is absolute), so only the
    page table needs to name the new physical ids.  Out-of-bounds ids in
    ``page_ids`` are padding: the scatter drops them (jnp ``.at``
    default under jit), letting the engine pad to one static shape."""
    cache = dict(cache)
    ax = _page_axis(cache)
    for k, v in payload.items():
        at = cache[k].at[page_ids] if ax == 0 else cache[k].at[:, page_ids]
        cache[k] = at.set(v)
    return cache


def rollback_cache(cache: dict, keep) -> dict:
    """Speculative-decode KV rewind for the DENSE layout: mark every slot
    holding a position >= the lane's ``keep`` bound as empty again.

    ``keep`` is (B,) int32 — per lane, the first position whose write must
    be withdrawn (rejected draft tokens); lanes with nothing to roll back
    pass a bound above ``max_seq``.  Only ``pos_ids`` is touched: masking
    derives from positions everywhere (``_sdpa`` valid/causal masks, the
    decode kernels), so flipping a slot's pos_id to -1 un-writes it — the
    stale K/V payload is unreadable and the slot is reclaimed by the next
    genuine write at that ring position, exactly as if the rejected token
    had never been fed.  Works on a single layer's (B, S) pos_ids or the
    engine's period-stacked (P, B, S) leaves.
    """
    pos = cache["pos_ids"]
    bound = keep.reshape((1,) * (pos.ndim - 2) + (keep.shape[0], 1))
    return dict(cache, pos_ids=jnp.where(pos >= bound, -1, pos))


def _write_cache(cache: dict, k, v, positions):
    """Write k/v (B,T,Hkv,D) at ring slots positions % S.

    Negative positions are MASKED WRITES: their slot index lands out of
    bounds and the scatter drops them (chunked-prefill pad tokens — the
    serving engine pads chunks to static bucket lengths with position -1).

    Full-length writes (prefill: T == S) assign directly — a scatter here
    makes GSPMD replicate the whole cache + update across the mesh
    (measured 90 GB/step on whisper prefill_32k).  Pad rows still carry
    pos_ids = -1 (empty) on this path, but the assignment erases prior
    slots, so the engine keeps chunk buckets strictly below every cache
    length (see serve/engine.py _chunk_buckets).
    """
    s = cache["k"].shape[1]
    if k.shape[1] == s:
        cache = dict(cache)
        if "k_s" in cache:
            k_q, k_s = _quant_kv(k)
            v_q, v_s = _quant_kv(v)
            cache.update(k=k_q, v=v_q, k_s=k_s, v_s=v_s)
        else:
            cache.update(k=k.astype(cache["k"].dtype),
                         v=v.astype(cache["v"].dtype))
        cache["pos_ids"] = positions
        return cache
    # OOB slot for pad positions -> dropped by the scatter (jnp .at default)
    slots = jnp.where(positions >= 0, positions % s, s)      # (B, T)
    b_idx = jnp.arange(k.shape[0])[:, None]
    if "k_s" in cache:
        k_q, k_s = _quant_kv(k)
        v_q, v_s = _quant_kv(v)
        cache = dict(cache)
        cache["k"] = cache["k"].at[b_idx, slots].set(k_q)
        cache["v"] = cache["v"].at[b_idx, slots].set(v_q)
        cache["k_s"] = cache["k_s"].at[b_idx, slots].set(k_s)
        cache["v_s"] = cache["v_s"].at[b_idx, slots].set(v_s)
    else:
        cache = dict(cache)
        cache["k"] = cache["k"].at[b_idx, slots].set(k.astype(cache["k"].dtype))
        cache["v"] = cache["v"].at[b_idx, slots].set(v.astype(cache["v"].dtype))
    cache["pos_ids"] = cache["pos_ids"].at[b_idx, slots].set(positions)
    return cache


def _read_cache(cache: dict, dtype):
    if "k_s" in cache:
        k = cache["k"].astype(F32) * cache["k_s"]
        v = cache["v"].astype(F32) * cache["v_s"]
        return k.astype(dtype), v.astype(dtype)
    return cache["k"].astype(dtype), cache["v"].astype(dtype)


ATTN_Q_CHUNK = 1024  # query-chunked softmax: streams the S x S score matrix


def _sdpa(q, k, v, qpos, kpos, scale, dtype, *, causal=True, window=0,
          valid=None, chunk=ATTN_Q_CHUNK):
    """Grouped-GQA attention with query chunking.

    q (B,Tq,Hq,D), k/v (B,Tk,Hkv,D); qpos (B,Tq), kpos (B,Tk);
    valid (B,Tk) bool or None.  Masks are built per chunk from positions —
    the (Tq,Tk) score matrix is never materialized beyond a chunk.  The XLA
    analogue of the Pallas flash kernel (which serves the real-TPU path).
    """
    b, tq, hq, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    # KV layout for TP (measured in EXPERIMENTS.md §4A/§4C):
    #  - heads divide the model axis -> shard heads (scores shard cleanly);
    #  - else for prefill/train, repeat KV heads up to the TP degree
    #    (storage unchanged) — otherwise GSPMD ALL-GATHERS the f32 score
    #    tensor (2.4 TB/step on internlm2 train_4k);
    #  - else (decode against a seq-sharded cache, or 56-head Yi where no
    #    integer repeat works) shard the KV SEQUENCE: partial softmax is
    #    collective-cheap, and repeating a seq-sharded cache would
    #    all-to-all the whole cache every layer.
    from ..dist.sharding import axis_env
    env = axis_env()
    tp = env.axes_size(env.tp) if env.active else 1
    kv_hint: tuple | None = None
    if tp > 1:
        if hkv % tp == 0:
            kv_hint = ("dp", "tp", None, None)
        elif tq > 1 and hq % tp == 0 and tp % hkv == 0:
            rep = tp // hkv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            hkv = hkv * rep
            kv_hint = ("dp", "tp", None, None)
        elif tk % tp == 0 and (tq == 1 or valid is not None):
            # serving paths (the cache is already seq-sharded): shard the KV
            # sequence.  NOT for training — the partial-softmax regather
            # costs more than it saves there (measured on yi-34b train)
            kv_hint = ("dp", None, "tp", None)
    g = hq // hkv
    # operands stay bf16 (MXU native); accumulation is f32 via
    # preferred_element_type — halves K/V HBM and boundary traffic vs
    # upcasting the tensors themselves
    kt = jnp.swapaxes(k, 1, 2)                              # (B,Hkv,Tk,D)
    vt = jnp.swapaxes(v, 1, 2)
    if kv_hint is not None:
        kt = shard_hint(kt, *kv_hint)
        vt = shard_hint(vt, *kv_hint)
    # gather the per-key POSITIONS (4-byte/key) before building masks; else
    # GSPMD gathers the computed (Tc, Tk) boolean mask itself (measured
    # 26 GB/step of pred traffic on whisper prefill)
    kpos = shard_hint(kpos, "dp", None)
    if valid is not None:
        valid = shard_hint(valid, "dp", None)

    def chunk_attn(q_c, qpos_c):
        """q_c (B,Tc,Hq,D), qpos_c (B,Tc) -> (B,Tc,Hq,D)"""
        tc = q_c.shape[1]
        qg = q_c.reshape(b, tc, hkv, g, d)
        s = jnp.einsum("bthgd,bhkd->bthgk", qg, kt,
                       preferred_element_type=F32) * scale  # (B,Tc,Hkv,G,Tk)
        m = jnp.ones((b, tc, tk), bool)
        if causal:
            m &= kpos[:, None, :] <= qpos_c[:, :, None]
        if window:
            m &= kpos[:, None, :] > (qpos_c[:, :, None] - window)
        if valid is not None:
            m &= valid[:, None, :]
        s = jnp.where(m[:, :, None, None, :], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bthgk,bhkd->bthgd", p.astype(dtype), vt,
                       preferred_element_type=F32)
        return o.reshape(b, tc, hq, d).astype(dtype)

    if tq <= chunk:
        return chunk_attn(q, qpos)
    pad = (-tq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad)), constant_values=-1)
    nch = q.shape[1] // chunk
    q_ch = jnp.moveaxis(q.reshape(b, nch, chunk, hq, d), 1, 0)
    p_ch = jnp.moveaxis(qpos.reshape(b, nch, chunk), 1, 0)

    def body(_, xs):
        qc, pc = xs
        return None, chunk_attn(qc, pc)

    _, out = jax.lax.scan(jax.checkpoint(body), None, (q_ch, p_ch))
    out = jnp.moveaxis(out, 0, 1).reshape(b, nch * chunk, hq, d)
    return out[:, :tq]


def _int_attention(q, k, v, cfg: ArchConfig, causal: bool, window: int):
    """Integer prefill attention (paper path): static-scale int8 q/k, V in
    int8 with per-(token, head) scales dequantized EXACTLY inside the PV
    pass of the kernel — the only error left vs float attention is the
    input quantization itself."""
    b, s, hq, hd = q.shape
    qi = jnp.clip(jnp.round(q.astype(F32) / ATTN_INT_SCALE), -128, 127).astype(jnp.int8)
    ki = jnp.clip(jnp.round(k.astype(F32) / ATTN_INT_SCALE), -128, 127).astype(jnp.int8)
    vi, v_s = _quant_kv(v)  # per-(token, head) scales
    rshift = max(int(round(math.log2(math.sqrt(hd)))), 0)
    # acc-unit scale after the power-of-two fold; the residual sqrt factor is
    # folded into the integer softmax scale
    sqrt_resid = (2.0 ** rshift) / math.sqrt(hd)
    s_score = ATTN_INT_SCALE * ATTN_INT_SCALE * sqrt_resid
    out = ops.attention_i8(
        jnp.transpose(qi, (0, 2, 1, 3)),
        jnp.transpose(ki, (0, 2, 1, 3)),
        jnp.transpose(vi, (0, 2, 1, 3)),
        scale=s_score, causal=causal,
        v_scale=jnp.transpose(v_s, (0, 2, 1, 3)))       # (B,H,S,D) f32
    return jnp.transpose(out, (0, 2, 1, 3))


def cross_kv_proj(params: dict, kv_source: jax.Array, cfg: ArchConfig,
                  mode: ExecMode) -> tuple[jax.Array, jax.Array]:
    """Project cross-attention K/V from source features (once per request)."""
    b, sv = kv_source.shape[:2]
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    k = apply_linear(kv_source, params["wk"], mode, params.get("bk"))
    v = apply_linear(kv_source, params["wv"], mode, params.get("bv"))
    return k.reshape(b, sv, hkv, hd), v.reshape(b, sv, hkv, hd)


def attention(
    params: dict,
    x: jax.Array,
    cfg: ArchConfig,
    mode: ExecMode,
    positions: jax.Array,              # (B, T) absolute positions
    cache: dict | None = None,
    kv_source: jax.Array | None = None,  # cross-attention source features
    cross_kv: tuple | None = None,       # precomputed (xk, xv) — decode path
    window: int = 0,
    residual: jax.Array | None = None,   # skip input: folded into out-proj
) -> tuple[jax.Array, dict | None]:
    b, t, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cross = kv_source is not None or cross_kv is not None

    with jax.named_scope("attn.qkv"):
        q = apply_linear(x, params["wq"], mode, params.get("bq"),
                         use_hint=(None, "tp"))
        # head counts derive from the PROJECTED widths, not cfg: inside the
        # serving TP shard_map (dist/tp.py) wq/wk/wv are column-sharded and
        # each shard carries n_heads/tp whole heads — cfg would over-reshape
        q = q.reshape(b, t, q.shape[-1] // hd, hd)
        if cross_kv is not None:
            # static cross KV, computed once (precompute_cross_states): the
            # per-decode-step recompute was 87% of vision-90b decode FLOPs
            k = cross_kv[0].astype(x.dtype)
            v = cross_kv[1].astype(x.dtype)
        else:
            src = kv_source if cross else x
            k = apply_linear(src, params["wk"], mode, params.get("bk"),
                             use_hint=(None, "tp"))
            v = apply_linear(src, params["wv"], mode, params.get("bv"),
                             use_hint=(None, "tp"))
            k = k.reshape(b, src.shape[1], k.shape[-1] // hd, hd)
            v = v.reshape(b, src.shape[1], v.shape[-1] // hd, hd)
        # inside the TP region heads take the model axis (seq gathers back)
        q = shard_hint(q, "dp", None, "tp", None)
        k = shard_hint(k, "dp", None, "tp", None)

        if not cross:
            q = apply_rope(q, positions, cfg.rope_theta)
            src_pos = positions
            k = apply_rope(k, src_pos, cfg.rope_theta)

    scale = 1.0 / math.sqrt(hd)
    dtype = x.dtype

    if cross:
        # static KV, no mask (all source positions valid)
        kpos = jnp.zeros((b, k.shape[1]), jnp.int32)
        out = _sdpa(q, k, v, positions, kpos, scale, dtype, causal=False)
    elif cache is not None and "pt" in cache:
        # paged serving path: scatter through the page table, then either
        # the gather-based paged decode kernel (all-decode steady state on
        # the pallas backend, int8 pages) or the XLA gather-then-attend
        # view — the same _sdpa the dense cache path runs, over a view
        # that is element-identical to the dense cache (docs/serving.md)
        with jax.named_scope("attn.kv_write"):
            cache = _write_paged(cache, k, v, positions)
        ps = cache["ppos"].shape[1]
        if "pks" in cache and t == 1 and ops.backend() == "pallas":
            # the scope holds the kernel and the preparation of its
            # operands
            with jax.named_scope("attn.paged_decode"):
                out = ops.paged_attention_decode(
                    q[:, 0], cache["pk"], cache["pks"], cache["pv"],
                    cache["pvs"], cache["ppos"], cache["pt"],
                    positions[:, 0], scale=scale,
                    window=window)[:, None].astype(dtype)
        else:
            with jax.named_scope("attn.gather"):
                kc, vc, kpos = _read_paged(cache, dtype)
                ctx = tp_serving_ctx()
                bq, _ = autotune.paged_blocks(t, ps, kc.shape[1], hd,
                                              arch=cfg.name,
                                              backend=ops.backend(),
                                              hkv=kc.shape[2],
                                              tp=ctx.size if ctx else 1)
                out = _sdpa(q, kc, vc, positions, kpos, scale, dtype,
                            causal=True, window=window, valid=kpos >= 0,
                            chunk=max(bq, 1))
    elif cache is not None:
        with jax.named_scope("attn.kv_write"):
            cache = _write_cache(cache, k, v, positions)
        if "k_s" in cache and t == 1 and ops.backend() == "pallas":
            # serving hot path: fused int8-KV decode kernel (one int8 pass
            # over the cache, in-register dequant — §Perf cell C)
            out = ops.decode_attention_int8kv(
                q[:, 0], cache["k"], cache["k_s"], cache["v"], cache["v_s"],
                cache["pos_ids"], positions[:, 0], scale=scale,
                window=window)[:, None].astype(dtype)
        else:
            kc, vc = _read_cache(cache, dtype)              # (B,S,Hkv,D)
            kpos = cache["pos_ids"]                         # (B,S)
            # mixed-depth packed rows (prefill chunks + decode tokens in
            # one batch): query-block size from the packed autotune family
            # keyed on (budget bucket, arch) — neither the pure-prefill nor
            # the pure-decode table models this shape
            ctx = tp_serving_ctx()
            bq, _ = autotune.packed_blocks(t, kc.shape[1], hd, arch=cfg.name,
                                           backend=ops.backend(),
                                           hkv=kc.shape[2],
                                           tp=ctx.size if ctx else 1)
            out = _sdpa(q, kc, vc, positions, kpos, scale, dtype, causal=True,
                        window=window, valid=kpos >= 0, chunk=max(bq, 1))
    else:
        # training / no-cache prefill
        if mode.integer and window == 0:
            out = _int_attention(q, k, v, cfg, causal=True, window=window)
        elif ops.backend() == "pallas" and window == 0 and t % 8 == 0:
            out = jnp.transpose(
                ops.attention(jnp.transpose(q, (0, 2, 1, 3)),
                              jnp.transpose(k, (0, 2, 1, 3)),
                              jnp.transpose(v, (0, 2, 1, 3)),
                              causal=True, scale=scale), (0, 2, 1, 3))
        else:
            out = _sdpa(q, k, v, positions, positions, scale, dtype,
                        causal=True, window=window)
    out = out.astype(dtype).reshape(b, t, -1)
    # the residual add rides the out-projection (integer path: fused GEMM
    # epilogue — the projection output never round-trips before the skip).
    # Under serving TP this is the collective boundary: ``out`` is
    # head-sharded, wo is replicated, and dist/tp.py rebuilds full rows
    # (barrier all-gather, or the all-to-all token split whose row GEMM
    # consumes each shard's slice as it arrives) before the epilogue.
    with jax.named_scope("attn.out"):
        out = tp_out_projection(
            out, residual,
            lambda h, res: apply_linear(h, params["wo"], mode,
                                        use_hint=("tp", None), residual=res))
    return shard_hint(out, "dp", "sp", None), cache