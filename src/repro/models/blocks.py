"""Block composition: one periodic pattern drives all 10 architectures.

A model is ``n_periods`` repetitions of a P-long block pattern (P = 1 for
dense/MoE, 2/4 for xLSTM, 6 for Zamba2-style hybrids, 5 for the VLM with
its cross-attention cadence).  Per-position parameters are stacked over
periods and the model scans over periods (``lax.scan``), keeping the HLO a
single while loop regardless of depth — essential for 100-layer dry-runs
and for remat.

"shared_attn" (Zamba2) applies a block whose parameters are NOT stacked:
the same weights run at every period — the paper-era trick of amortizing
attention parameters across a Mamba backbone.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..dist.sharding import shard_hint
from ..dist.tp import tp_row_unshard
from .attention import attention, init_attn_params, init_cache, init_paged_cache
from .config import ArchConfig
from .layers import ExecMode, apply_norm, norm_params
from .mlp import init_mlp_params, mlp
from .moe import init_moe_params, moe
from .ssm import (
    _mamba_dims,
    _mlstm_dims,
    init_mamba2_params,
    init_mlstm_params,
    init_slstm_params,
    mamba2,
    mlstm,
    slstm,
)

ATTN_KINDS = {"attn", "attn_swa", "moe", "moe_swa", "shared_attn", "dec"}


# ---------------------------------------------------------------------------
# per-kind init
# ---------------------------------------------------------------------------

def init_block_params(key, kind: str, cfg: ArchConfig) -> dict:
    ks = jax.random.split(key, 4)
    nt = cfg.norm_type
    d = cfg.d_model
    if kind in ("attn", "attn_swa"):
        return {"norm1": norm_params(d, nt), "attn": init_attn_params(ks[0], cfg),
                "norm2": norm_params(d, nt), "mlp": init_mlp_params(ks[1], cfg)}
    if kind in ("moe", "moe_swa"):
        return {"norm1": norm_params(d, nt), "attn": init_attn_params(ks[0], cfg),
                "norm2": norm_params(d, nt), "moe": init_moe_params(ks[1], cfg)}
    if kind == "xattn":
        return {"norm1": norm_params(d, nt),
                "xattn": init_attn_params(ks[0], cfg, cross=True),
                "norm2": norm_params(d, nt), "mlp": init_mlp_params(ks[1], cfg),
                "gate_attn": jnp.zeros((1,), jnp.float32),
                "gate_mlp": jnp.zeros((1,), jnp.float32)}
    if kind == "dec":  # whisper decoder layer: self-attn + cross-attn + mlp
        return {"norm1": norm_params(d, nt), "attn": init_attn_params(ks[0], cfg),
                "norm2": norm_params(d, nt),
                "xattn": init_attn_params(ks[1], cfg, cross=True),
                "norm3": norm_params(d, nt), "mlp": init_mlp_params(ks[2], cfg)}
    if kind == "enc":  # bidirectional encoder layer
        return {"norm1": norm_params(d, nt), "attn": init_attn_params(ks[0], cfg),
                "norm2": norm_params(d, nt), "mlp": init_mlp_params(ks[1], cfg)}
    if kind == "mamba2":
        return {"norm1": norm_params(d, nt), "mamba": init_mamba2_params(ks[0], cfg)}
    if kind == "mlstm":
        return {"norm1": norm_params(d, nt), "mlstm": init_mlstm_params(ks[0], cfg)}
    if kind == "slstm":
        return {"norm1": norm_params(d, nt), "slstm": init_slstm_params(ks[0], cfg)}
    if kind == "shared_attn":
        return {"norm1": norm_params(d, nt), "attn": init_attn_params(ks[0], cfg),
                "norm2": norm_params(d, nt), "mlp": init_mlp_params(ks[1], cfg)}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# per-kind cache/state init
# ---------------------------------------------------------------------------

def _cross_len(cfg: ArchConfig) -> int:
    return (cfg.n_audio_frames if cfg.is_encoder_decoder
            else cfg.n_vision_tokens)


def init_block_state(kind: str, cfg: ArchConfig, batch: int, max_seq: int,
                     int8_kv: bool, dtype, window_slack: int = 0,
                     paged_pages: int = 0, page_size: int = 0) -> dict | None:
    if paged_pages and kind in ("attn", "attn_swa", "moe", "moe_swa",
                                "shared_attn"):
        # paged serving arena (serve/kv_pool.py owns the page bookkeeping);
        # window archs use the same arena — masking derives from positions,
        # the engine caps their LIVE pages at the window instead
        return {"kv": init_paged_cache(cfg, batch, paged_pages, page_size,
                                       -(-max_seq // page_size),
                                       int8=int8_kv, dtype=dtype)}
    if kind in ("xattn", "dec"):
        # cross-attention KV is static per request: precomputed once
        # (models.lm.precompute_cross_states), never per decode step
        sv, hkv, hd = _cross_len(cfg), cfg.n_kv_heads, cfg.head_dim
        st = {"xk": jnp.zeros((batch, sv, hkv, hd), dtype),
              "xv": jnp.zeros((batch, sv, hkv, hd), dtype)}
        if kind == "dec":
            st["kv"] = init_cache(cfg, batch, max_seq, int8=int8_kv, dtype=dtype)
        return st
    if kind in ("attn", "moe", "shared_attn"):
        return {"kv": init_cache(cfg, batch, max_seq, int8=int8_kv, dtype=dtype)}
    if kind in ("attn_swa", "moe_swa"):
        # window_slack: extra ring slots so a prefill chunk's writes never
        # evict keys still inside the window of its earliest query
        return {"kv": init_cache(cfg, batch, max_seq, int8=int8_kv,
                                 window=cfg.sliding_window + window_slack,
                                 dtype=dtype)}
    if kind == "mamba2":
        d_inner, nh, hd, ds = _mamba_dims(cfg)
        conv_ch = d_inner + 2 * ds
        return {"conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), jnp.float32),
                "ssd": jnp.zeros((batch, nh, ds, hd), jnp.float32)}
    if kind == "mlstm":
        _, nh, hd = _mlstm_dims(cfg)
        return {"C": jnp.zeros((batch, nh, hd, hd), jnp.float32),
                "n": jnp.zeros((batch, nh, hd), jnp.float32),
                "m": jnp.full((batch, nh), -1e30, jnp.float32)}
    if kind == "slstm":
        nh = cfg.n_heads
        hd = cfg.d_model // nh
        z = jnp.zeros((batch, nh, hd), jnp.float32)
        return {"h": z, "c": z, "n": jnp.ones_like(z), "m": z}
    if kind == "enc":
        return None
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# per-kind forward
# ---------------------------------------------------------------------------

def block_forward(
    kind: str,
    params: dict,
    x: jax.Array,
    cfg: ArchConfig,
    mode: ExecMode,
    positions: jax.Array,
    state: dict | None = None,
    kv_source: jax.Array | None = None,
    causal: bool = True,
) -> tuple[jax.Array, dict | None]:
    new_state = state
    if kind in ("attn", "attn_swa", "moe", "moe_swa", "shared_attn", "enc"):
        window = cfg.sliding_window if kind in ("attn_swa", "moe_swa") else 0
        # under overlap serving TP the residual stream x is row-sharded
        # (sequence parallel, dist/tp.py): norms run on local rows and
        # tp_row_unshard gathers full rows for the QKV / MLP-in GEMMs
        # (identity everywhere else)
        with jax.named_scope("norm"):
            h = apply_norm(x, params["norm1"], cfg, mode)
        # SP->TP boundary: gather the bf16 norm output (not the f32 norm
        # intermediate GSPMD would otherwise pick — 2x ICI bytes)
        h = shard_hint(h, "dp", None, None)
        h = tp_row_unshard(h, *positions.shape)
        # skip connection folds into the out-projection epilogue
        x, kv = attention(params["attn"], h, cfg, mode, positions,
                          cache=None if state is None else state["kv"],
                          window=window, residual=x)
        if state is not None:
            new_state = dict(state, kv=kv)
        with jax.named_scope("norm"):
            h = apply_norm(x, params["norm2"], cfg, mode)
        h = shard_hint(h, "dp", None, None)
        h = tp_row_unshard(h, *positions.shape)
        with jax.named_scope("mlp"):
            if kind in ("moe", "moe_swa"):
                x = x + moe(params["moe"], h, cfg, mode)
            else:
                x = x + mlp(params["mlp"], h, cfg, mode)
        return x, new_state
    if kind == "xattn":
        ckv = None if state is None else (state["xk"], state["xv"])
        h = apply_norm(x, params["norm1"], cfg, mode)
        a, _ = attention(params["xattn"], h, cfg, mode, positions,
                         kv_source=kv_source, cross_kv=ckv)
        x = x + jnp.tanh(params["gate_attn"]).astype(x.dtype) * a
        h = apply_norm(x, params["norm2"], cfg, mode)
        x = x + jnp.tanh(params["gate_mlp"]).astype(x.dtype) * mlp(
            params["mlp"], h, cfg, mode)
        return x, new_state
    if kind == "dec":
        ckv = None if state is None else (state["xk"], state["xv"])
        h = apply_norm(x, params["norm1"], cfg, mode)
        a, kv = attention(params["attn"], h, cfg, mode, positions,
                          cache=None if state is None else state["kv"])
        x = x + a
        if state is not None:
            new_state = dict(state, kv=kv)
        h = apply_norm(x, params["norm2"], cfg, mode)
        a, _ = attention(params["xattn"], h, cfg, mode, positions,
                         kv_source=kv_source, cross_kv=ckv)
        x = x + a
        h = apply_norm(x, params["norm3"], cfg, mode)
        x = x + mlp(params["mlp"], h, cfg, mode)
        return x, new_state
    if kind == "mamba2":
        h = apply_norm(x, params["norm1"], cfg, mode)
        y, st = mamba2(params["mamba"], h, cfg, mode, state=state)
        return x + y, st
    if kind == "mlstm":
        h = apply_norm(x, params["norm1"], cfg, mode)
        y, st = mlstm(params["mlstm"], h, cfg, mode, state=state)
        return x + y, st
    if kind == "slstm":
        h = apply_norm(x, params["norm1"], cfg, mode)
        y, st = slstm(params["slstm"], h, cfg, mode, state=state)
        return x + y, st
    raise ValueError(kind)
