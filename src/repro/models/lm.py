"""Decoder LM (and VLM backbone): init / forward / loss / prefill / decode.

The layer stack is a ``lax.scan`` over periods (see blocks.py).  Parameters
live in ``params["periods"]`` as a list over pattern positions, each leaf
stacked over ``n_periods``; "shared_attn" blocks live unstacked in
``params["shared"]``.  Caches/recurrent states mirror that layout.

With remat enabled, the scan body is ``jax.checkpoint``-wrapped with the
``dots_with_no_batch_dims_saveable`` policy (save projections, recompute
attention/normalizations) — the standard memory/time point for long-seq
training.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..dist.sharding import shard_hint
from ..dist.tp import tp_row_shard, tp_row_unshard
from .blocks import block_forward, init_block_params, init_block_state
from .config import ArchConfig
from .layers import (
    DEFAULT_DTYPE,
    ExecMode,
    apply_norm,
    embed_init,
    embed_lookup,
    linear,
    norm_params,
)

F32 = jnp.float32


def exec_mode(cfg: ArchConfig) -> ExecMode:
    return ExecMode(precision=cfg.precision, compute_dtype=DEFAULT_DTYPE)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack(*xs):
    return np.stack(xs) if isinstance(xs[0], np.ndarray) else jnp.stack(xs)


def init_params(key, cfg: ArchConfig,
                finish: Callable[[dict], dict] | None = None) -> dict:
    """Random parameters from ``key``.

    ``finish`` maps each freshly initialized block, and the dict of
    top-level leaves, before blocks are stacked over periods — e.g. PTQ
    plus a move to host memory, so that the full float tree never exists
    at once.  Every transform that acts leaf by leaf on a layer gives the
    same tree as applying it to the stacked result."""
    finish = finish or (lambda tree: tree)
    ks = jax.random.split(key, cfg.n_layers + 4)
    pattern = cfg.block_pattern
    periods: list[Any] = []
    shared = None
    for pos, kind in enumerate(pattern):
        if kind == "shared_attn":
            shared = finish(init_block_params(ks[pos], "shared_attn", cfg))
            periods.append(None)  # placeholder; applied from params["shared"]
            continue
        stacked = [
            finish(init_block_params(ks[cfg.period * rep + pos], kind, cfg))
            for rep in range(cfg.n_periods)
        ]
        periods.append(jax.tree.map(_stack, *stacked))
    top = {
        "embed": embed_init(ks[-1], cfg.padded_vocab, cfg.d_model),
        "final_norm": norm_params(cfg.d_model, cfg.norm_type),
    }
    if not cfg.tie_embeddings:
        # stored (d_model, vocab): the lm-head layout ("unembed" spec rule)
        top["unembed"] = embed_init(ks[-2], cfg.padded_vocab, cfg.d_model).T
    params = {**finish(top), "periods": periods}
    if shared is not None:
        params["shared"] = shared
    return params


def init_states(cfg: ArchConfig, batch: int, max_seq: int,
                int8_kv: bool = False, dtype=DEFAULT_DTYPE,
                window_slack: int = 0, paged_pages: int = 0,
                page_size: int = 0) -> list:
    """Stacked per-period states mirroring the params layout.

    ``window_slack`` widens sliding-window ring caches by that many slots
    (chunked prefill: a C-token chunk write must not evict keys still
    inside the window of the chunk's earliest query — see docs/serving.md).
    With ``paged_pages`` > 0, attention KV caches become paged arenas of
    that many ``page_size``-slot pages plus a per-lane page table
    (attention.init_paged_cache; the serving engine owns the allocator).
    """
    states = []
    for kind in cfg.block_pattern:
        st = init_block_state(kind, cfg, batch, max_seq, int8_kv, dtype,
                              window_slack=window_slack,
                              paged_pages=paged_pages, page_size=page_size)
        if st is None:
            states.append(None)
            continue
        if kind == "shared_attn":
            # shared PARAMS but per-layer cache: still stacked over periods
            pass
        states.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_periods,) + x.shape), st))
    return states


def precompute_cross_states(params: dict, cfg: ArchConfig,
                            kv_source: jax.Array, states: list) -> list:
    """Fill the static cross-attention KV in per-period states (once per
    request): decode steps then read state["xk"]/["xv"] instead of
    re-projecting the vision/audio features every token."""
    from .attention import cross_kv_proj
    mode = exec_mode(cfg)
    out = []
    for pos, kind in enumerate(cfg.block_pattern):
        st = states[pos]
        if kind not in ("xattn", "dec") or st is None:
            out.append(st)
            continue

        def proj(period_params):
            return cross_kv_proj(period_params["xattn"], kv_source, cfg, mode)

        xk, xv = jax.vmap(proj)(params["periods"][pos])  # (P, B, Sv, H, D)
        st = dict(st)
        st["xk"] = xk.astype(st["xk"].dtype)
        st["xv"] = xv.astype(st["xv"].dtype)
        out.append(st)
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _period_body(carry, xs, *, cfg: ArchConfig, mode: ExecMode, shared,
                 kv_source, causal: bool):
    x, positions = carry
    period_params, period_states = xs
    new_states = []
    for pos, kind in enumerate(cfg.block_pattern):
        p = shared if kind == "shared_attn" else period_params[pos]
        st = None if period_states is None else period_states[pos]
        x, st = block_forward(kind, p, x, cfg, mode, positions, state=st,
                              kv_source=kv_source, causal=causal)
        new_states.append(st)
    return (x, positions), new_states


def forward(
    params: dict,
    cfg: ArchConfig,
    tokens: jax.Array,                  # (B, T) int32
    positions: jax.Array | None = None,  # (B, T) int32
    states: list | None = None,         # stacked per-period states
    kv_source: jax.Array | None = None,  # vision/encoder features (B, Sv, D)
    embeddings: jax.Array | None = None,  # pre-embedded inputs (frontends)
    logits: bool = True,
) -> tuple[jax.Array, list | None]:
    mode = exec_mode(cfg)
    if embeddings is not None:
        x = embeddings.astype(mode.compute_dtype)
    else:
        with jax.named_scope("embed"):
            x = embed_lookup(tokens, params["embed"], mode.compute_dtype)
    b, t = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    # overlap serving TP: the residual stream runs sequence-parallel
    # between boundaries (dist/tp.py) — enter the row-sharded domain
    # here so every block norm fuses with its local producer (identity
    # outside an overlap TP region)
    x = tp_row_shard(x)

    # pack per-position stacked params/states for the period scan
    xs_params = [params["periods"][i] for i in range(cfg.period)]
    xs_states = states
    body = functools.partial(
        _period_body, cfg=cfg, mode=mode, shared=params.get("shared"),
        kv_source=kv_source, causal=True)
    if cfg.remat:
        # full per-layer recompute (Megatron "full recompute"): the scan
        # carry (B,S,D) is the only live activation per layer.  Selective
        # policies save f32 dot outputs and blow past HBM at 4k x 256 —
        # measured in EXPERIMENTS.md §Perf, where this is a hillclimb axis.
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    (x, _), out_states = jax.lax.scan(
        body, (x, positions),
        (xs_params, xs_states if xs_states is not None else
         [None] * cfg.period))
    with jax.named_scope("norm"):
        x = apply_norm(x, params["final_norm"], cfg, mode)
    x = tp_row_unshard(x, b, t)
    if not logits:
        return x, out_states
    with jax.named_scope("head"):
        unembed = params.get("unembed")
        if unembed is None:
            # tied head: make a vocab-sharded view first (the table itself
            # is d_model-sharded for the gather; without the reshard, the
            # head's grads materialize the full vocab in f32 on every
            # device)
            unembed = shard_hint(params["embed"], "tp", None).T
        from .layers import apply_linear
        lg = apply_linear(x, unembed, ExecMode(cfg.precision, F32))
        if cfg.padded_vocab != cfg.vocab_size:
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            lg = jnp.where(pad_mask, -1e9, lg)
        lg = shard_hint(lg, "dp", None, "tp")
    return lg, out_states


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def lm_loss(params: dict, cfg: ArchConfig, tokens: jax.Array,
            labels: jax.Array, embeddings=None, kv_source=None) -> jax.Array:
    lg, _ = forward(params, cfg, tokens, embeddings=embeddings,
                    kv_source=kv_source)
    return xent_loss(lg, labels)


def xent_loss(lg: jax.Array, labels: jax.Array) -> jax.Array:
    """TP-aware cross entropy: the gold logit is extracted with a one-hot
    contraction (elementwise + reduce over the sharded vocab dim) rather
    than take_along_axis, which would force GSPMD to all-gather the logits
    across the "model" axis."""
    lg = lg.astype(F32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    onehot = jax.nn.one_hot(jnp.maximum(labels, 0), lg.shape[-1], dtype=F32)
    gold = jnp.einsum("bsv,bsv->bs", lg, onehot)
    mask = labels >= 0
    nll = jnp.where(mask, logz - gold, 0.0)
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)
