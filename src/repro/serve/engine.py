"""Serving engine: ONE packed token-budget forward + continuous batching.

``packed_step`` is the single jitted program family the engine dispatches:
every iteration builds one ``(B, T_bucket)`` batch in which each active
lane contributes a contiguous span of tokens — generating lanes 1 token,
prefilling lanes up to their share of the per-iteration ``token_budget`` —
right-padded with position -1 tokens whose KV-cache writes are dropped
(models/attention._write_cache).  Prefill chunks and decode tokens share
the same forward, the same cache writes, and the same lane-masked state
commit (Sarathi-style token packing): decode lanes no longer idle while a
co-resident prompt prefills, and ONE program family — a compile per
(static budget bucket, commit_all) pair — replaces the separate
prefill/decode programs and their dual compile caches.

Mixed per-lane depths are handled in one call: each lane's next-token
logits are gathered at its own last VALID row index, and each lane's
sampling key is folded at its own last fed position, so a request's tokens
are a pure function of (seed, submission id, position) — never of lane
count, co-resident traffic, or scheduling mode.

SELF-SPECULATIVE DECODING (``ServeConfig.spec_k``, serve/draft.py,
docs/serving.md) rides the same program family: a greedy decode lane may
carry its last token plus up to k prompt-lookup draft tokens as one
contiguous span, the verifier reads the greedy argmax at EVERY span row
(causal masking derives from absolute positions, so row j cannot see the
drafted tokens after it — its logits are bit-identical to sequential
decode's), commits the longest draft-matching run plus one corrective
token, and withdraws the rejected positions' KV writes
(``kv_pool.truncate`` clear/copy actions on paged, the
``attention.rollback_cache`` pos_ids rewind on dense).  Output is
bit-identical to vanilla greedy decode for ANY draft content — drafts buy
speed (fewer forwards per committed token), never correctness.  Sampled
engines and tokenwise (recurrent) mode never speculate, so their token
and PRNG streams are untouched by ``spec_k``.

The engine adds host-side continuous batching: a slot-based scheduler
admits queued requests into free batch lanes each iteration (requests
carry their own position counters, so lanes mix sequences at different
depths — the vLLM-style pattern restricted to static shapes).  Bucket
lengths are a small power-of-two set (one compile per bucket, never per
prompt length); sliding-window ring caches are widened by the largest
bucket (init_states ``window_slack``) so a chunk write never evicts
in-window keys.

The FRONT END around that scheduler keeps serving correct and bounded
under any arrival pattern (serve/queue.py, docs/serving.md): ``submit``
validates at the door and feeds a priority ``AdmissionQueue`` whose
optional bound rejects overload with ``QueueFullError`` (explicit
backpressure — never a silent drop, never an allocator crash);
``step`` runs admit → maybe-preempt → pack → forward → commit →
complete.  Under paged-pool memory pressure the maybe-preempt stage
picks a victim lane (lowest priority, then shortest progress), swaps its
KV pages to HOST memory (``kv_pool.swap_out`` + ``gather_pages``), and
resumes it later into fresh physical pages (``swap_in`` +
``scatter_pages``) — a bit-exact round trip, so preempted-then-resumed
requests produce exactly the tokens of an uninterrupted run (greedy and
sampled; tokens are keyed by submission id and position, never by
scheduling).  TTFT/TPOT percentiles, per-request SLO misses, queue
depth, and preemption/swap/rejection counters live in ``stats`` /
``serving_metrics`` — the clock is read only for measurement, never for
scheduling.

Fallback schedules over the SAME program family:

* ``token_budget=0, prefill_chunk>0`` — chunked mode: prefill chunks and
  decode tokens run as two calls per iteration (the pre-packing PR 2
  scheduler, kept for A/B benching).
* both 0 — tokenwise: every lane feeds one token per call, prompts
  token-at-a-time.  Forced for recurrent-state archs (Mamba/xLSTM), whose
  recurrence would consume pad tokens.

Greedy outputs are bit-identical across packed / chunked / tokenwise —
packing is a scheduling change, not a numerical one (enforced by
tests/test_system.py and the scripts/verify.sh equivalence smoke).

Sampling uses PER-LANE PRNG streams keyed by request submission id and
position.  ``warmup()`` requests live in a RESERVED key space (folded at
the top of the uint32 range, ``2^32 - 1 - bucket``) and do not advance the
submission counter, so warming an engine never shifts later requests'
sampled tokens.

In w8a8 mode the KV cache is int8 with per-(token, head) scales.  On the
pallas backend the all-lanes-decoding steady state (bucket 1) still hits
the fused int8-KV decode kernel; mixed-depth buckets read the cache
through the XLA dequant-then-attend path with block sizes from the
``packed`` autotune key family (same numerics contract — masking and
scales from the cache, no approximation; see docs/serving.md).

``paged=True`` replaces the dense per-lane caches with the PAGED KV pool:
one physical arena of fixed-size pages per attention layer, a per-lane
page table, and the refcounted allocator + radix prefix index in
``serve/kv_pool.py``.  Requests whose prompt prefix is already registered
(same system prompt / few-shot header) map the shared physical pages and
SKIP PREFILL for the shared span; divergence inside a page copies-on-
write.  Paging is a memory-layout change only — outputs are bit-identical
to the dense engine (greedy and sampled, all three schedules; enforced by
tests/test_system.py and scripts/paged_equiv_smoke.py).  Recurrent-state
and cross-attention archs keep the dense layout (their per-lane state
leaves need the lane-masked commit that the shared arena deliberately
bypasses).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..models import ArchConfig, forward, init_states, precompute_cross_states
from ..models.attention import gather_pages, rollback_cache, scatter_pages
from . import tracing
from .draft import ngram_propose
from .kv_pool import PagedKVPool, PoolExhaustedError
from .queue import AdmissionQueue, QueueFullError, percentile


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch_lanes: int = 8
    max_seq: int = 2048
    int8_kv: bool = False
    temperature: float = 0.0     # 0 = greedy
    eos_token: int = 1
    token_budget: int = 32       # packed-step tokens per iteration; 0 = off
    prefill_chunk: int = 32      # chunked-mode cap (used when budget = 0)
    seed: int = 0                # base of the per-lane PRNG tree
    paged: bool = False          # paged KV pool + shared-prefix reuse
    page_size: int = 16          # KV page slots (demoted to divide max_seq)
    pool_pages: int = 0          # physical pages; 0 = auto-size
    queue_limit: int = 0         # admission-queue bound; 0 = unbounded
    swap: bool = True            # preempt + swap KV pages under pressure
    spec_k: int = 0              # self-speculative draft tokens per decode
    #                              step (0 = off; greedy engines only —
    #                              sampled engines silently fall back so
    #                              PRNG streams are untouched)
    tp: int = 1                  # serving tensor parallel: shard the packed
    #                              step + KV payloads over a ("tp",) mesh
    #                              (dist/tp.py, docs/sharding.md); 1 = off
    tp_overlap: str = "auto"     # row-GEMM boundary: "barrier" (all-gather
    #                              then full GEMM), "overlap" (all-to-all
    #                              token split so the epilogue consumes
    #                              shards as they arrive), or "auto"
    #                              (kernels.autotune.tp_serving_overlap)


def packed_step(params, cfg: ArchConfig, tokens, positions, states,
                last_idx=None, kv_source=None):
    """The unified forward: (B, T) rows where each lane carries 1..T valid
    tokens (pads at position -1).  Returns each lane's logits at its last
    valid row (``last_idx`` (B,) int32; default: the final row) + states."""
    logits, states = forward(params, cfg, tokens, positions=positions,
                             states=states, kv_source=kv_source)
    if last_idx is None:
        return logits[:, -1], states
    lg = jnp.take_along_axis(logits, last_idx[:, None, None], axis=1)[:, 0]
    return lg, states


def prefill_step(params, cfg: ArchConfig, tokens, positions, states,
                 kv_source=None):
    """Full-row prompt processing: packed_step with every row valid."""
    return packed_step(params, cfg, tokens, positions, states,
                       kv_source=kv_source)


def decode_step(params, cfg: ArchConfig, token, position, states,
                kv_source=None):
    """One token for every lane: packed_step at bucket 1."""
    return packed_step(params, cfg, token, position, states,
                       kv_source=kv_source)


def _masked_commit(old_states, new_states, lane_mask):
    """Keep ``new_states`` only for lanes in ``lane_mask`` (B,) bool.
    State leaves are stacked (P, B, ...)."""
    b = lane_mask.shape[0]

    def sel(new, old):
        m = lane_mask.reshape((1, b) + (1,) * (new.ndim - 2))
        return jnp.where(m, new, old)

    return jax.tree.map(sel, new_states, old_states)


def _paged_states_map(states, fn):
    """Apply ``fn`` to every paged KV cache dict in the state tree."""
    out = []
    for st in states:
        if isinstance(st, dict) and "kv" in st and "ppos" in st["kv"]:
            out.append(dict(st, kv=fn(st["kv"])))
        else:
            out.append(st)
    return out


def _paged_clear(states, mask):
    """Reset ``ppos`` to -1 for every page in ``mask`` (n_pages,) bool —
    a freed page's stale slots must never look valid to its next owner."""
    def clr(kv):
        return dict(kv, ppos=jnp.where(mask[None, :, None], -1, kv["ppos"]))
    return _paged_states_map(states, clr)


def _paged_copy(states, src, dst, keep):
    """Copy page ``src`` into ``dst`` (copy-on-write), keeping the first
    ``keep`` slots' positions valid and clearing the rest: the source may
    carry its owner's tokens beyond the shared span."""
    def cp(kv):
        kv = dict(kv)
        for key in ("pk", "pv", "pks", "pvs"):
            if key in kv:
                kv[key] = kv[key].at[:, dst].set(kv[key][:, src])
        ps = kv["ppos"].shape[-1]
        pos = jnp.where(jnp.arange(ps) < keep, kv["ppos"][:, src], -1)
        kv["ppos"] = kv["ppos"].at[:, dst].set(pos)
        return kv
    return _paged_states_map(states, cp)


def _dense_rollback(states, keep):
    """Withdraw DENSE KV writes at positions >= ``keep`` ((B,) int32,
    huge sentinel = lane untouched) in every dense cache of the state
    tree — the speculative-rejection rewind (attention.rollback_cache).
    Paged caches are skipped: their rewind is the pool's truncate
    actions, applied through the clear/copy machinery instead."""
    out = []
    for st in states:
        if isinstance(st, dict) and "kv" in st and "pos_ids" in st["kv"]:
            out.append(dict(st, kv=rollback_cache(st["kv"], keep)))
        else:
            out.append(st)
    return out


def _paged_swap_in(states, idx, payloads):
    """Scatter swapped-out page payloads back into freshly allocated
    physical pages.  ``idx`` (MP,) int32 is padded with out-of-bounds ids
    (dropped by the scatter) so there is exactly ONE compiled program;
    ``payloads`` carries one payload dict per paged state, in state-tree
    order (the same order the engine's gather walked)."""
    it = iter(payloads)
    return _paged_states_map(
        states, lambda kv: scatter_pages(kv, idx, next(it)))


def _with_page_table(states, pt):
    """Swap the page-table leaf ((P, B, MP), identical across periods) in
    every paged cache for the host scheduler's current mapping."""
    def upd(kv):
        return dict(kv, pt=jnp.broadcast_to(pt, kv["pt"].shape))
    return _paged_states_map(states, upd)


def _sample(logits, temperature: float, keys):
    """Per-lane sampling: ``keys`` (B, 2) uint32, one PRNG stream per lane."""
    with jax.named_scope("sample"):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.vmap(
            lambda k, l: jax.random.categorical(k, l / temperature)
        )(keys, logits).astype(jnp.int32)


def _pow2_bucket(n: int) -> int:
    """Power-of-two histogram bucket for prefix-length stats."""
    b = 1
    while b < n:
        b *= 2
    return b


class ServingEngine:
    """Slot-based continuous batching over the packed-step program family."""

    def __init__(self, params, cfg: ArchConfig, serve_cfg: ServeConfig,
                 kv_source=None):
        # host-resident weights land on the default device here; under TP
        # _init_tp places them sharded instead (never whole on one chip)
        self.params = params if serve_cfg.tp > 1 else jax.device_put(params)
        self.cfg = cfg
        self.scfg = serve_cfg
        self.kv_source = kv_source
        b = serve_cfg.batch_lanes
        self._mode = self._resolve_mode()
        self._buckets = self._token_buckets()
        if self._mode != "tokenwise" and not self._buckets:
            # no bucket fits below max_seq (e.g. max_seq=2): every span
            # would take the cache writer's full-assign path — serve
            # token-at-a-time instead of crashing on an empty bucket table
            self._mode = "tokenwise"
        # sliding-window ring caches get max-bucket slack slots: a C-token
        # span write must not evict keys still inside the window of the
        # span's earliest query (ring size W serves only C == 1)
        self._window_slack = self._buckets[-1] if self._buckets else 0
        self._paged = self._resolve_paged()
        self.pool: PagedKVPool | None = None
        if self._paged:
            # page size must divide max_seq so the gathered per-lane view
            # is slot-for-slot the dense cache layout (bit-identity):
            # demote to the LARGEST divisor <= requested (halving would
            # collapse e.g. 24-into-64 all the way to 1-slot pages)
            ps = min(max(serve_cfg.page_size, 1), serve_cfg.max_seq)
            while serve_cfg.max_seq % ps:
                ps -= 1
            mp = serve_cfg.max_seq // ps
            # explicit pool_pages may be tiny (overload testing): clamp to
            # one lane's worst case + null + spare so a LONE resident lane
            # always completes — that floor is what makes preemption a
            # guaranteed-progress policy rather than a livelock
            n_pages = serve_cfg.pool_pages or (b + 2) * mp + 1
            n_pages = max(n_pages, mp + 2)
            self.pool = PagedKVPool(n_pages, ps, b, mp)
            self._swap_in_fn = jax.jit(_paged_swap_in, donate_argnums=(0,))
            # all attention layers windowed -> the scheduler can cap each
            # lane's LIVE pages at the window (full-attn layers would still
            # need the old keys, so mixed patterns keep everything)
            kinds = {k for k in cfg.block_pattern} & {
                "attn", "moe", "shared_attn", "attn_swa", "moe_swa"}
            self._cap_window = (cfg.sliding_window if kinds and
                                kinds <= {"attn_swa", "moe_swa"} else 0)
            self._clear_fn = jax.jit(_paged_clear, donate_argnums=(0,))
            self._copy_fn = jax.jit(_paged_copy, donate_argnums=(0,))
        self.states = self._init_states()

        def _step_body(params, tokens, positions, states, lane_mask,
                       last_idx, commit_all, verify_rows):
            logits, new_states = forward(params, cfg, tokens,
                                         positions=positions, states=states,
                                         kv_source=kv_source)
            # per-lane gather of the last ``verify_rows`` valid rows
            # (speculative verification reads the greedy argmax at EVERY
            # drafted position; verify_rows == 1 is exactly the old
            # last-row gather).  Indices clip at row 0 — lanes with spans
            # shorter than verify_rows ignore the duplicate leading rows.
            with jax.named_scope("sample"):
                idx = jnp.maximum(last_idx[:, None]
                                  - jnp.arange(verify_rows - 1, -1, -1), 0)
                lg = jnp.take_along_axis(logits, idx[:, :, None], axis=1)
                greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # (B, R)
            if commit_all:  # static: every lane participated, skip select
                return lg[:, -1], greedy, new_states
            return (lg[:, -1], greedy,
                    _masked_commit(states, new_states, lane_mask))

        def _packed_masked(*args):
            ops.check_serving_backend()
            return _step_body(*args)

        self._step_body = _step_body

        # ONE jitted callable for prefill, decode, and mixed packed batches:
        # XLA compiles one program per (bucket, commit_all) — the old
        # prefill/decode dual compile caches are gone.  commit_all is
        # static: the all-lanes steady state skips the full-tree lane
        # select (pure extra cache traffic there).  verify_rows is static
        # too but adds no programs: it is a fixed function of the bucket
        # (min(spec_k + 1, bucket)).  _step_jit stays the jitted callable
        # when a caller wraps _step_fn: its cache size counts compiles.
        self._step_fn = self._step_jit = jax.jit(_packed_masked,
                                                 static_argnums=(6, 7))
        self.tp_mesh = None
        if serve_cfg.tp_overlap not in ("auto", "overlap", "barrier"):
            # validated even at tp=1: a typo'd boundary choice must not
            # lie dormant until the config is first run sharded
            raise ValueError(
                f"tp_overlap must be 'auto', 'overlap', or 'barrier', "
                f"got {serve_cfg.tp_overlap!r}")
        if serve_cfg.tp > 1:
            # serving tensor parallel (dist/tp.py, docs/sharding.md):
            # replace the plain jit with a shard_map over the ("tp",) mesh
            # — same program family, same static_argnums, bit-identical
            # outputs (the boundary collectives move data, never sum it)
            self._init_tp(_packed_masked)
        # -- self-speculative decoding (serve/draft.py, docs/serving.md) --
        # Greedy engines only: acceptance compares drafts against the
        # model's own argmax, which a sampled stream does not follow —
        # sampled engines fall back to vanilla decode so their PRNG
        # streams are bit-identical with spec_k set or not.  Tokenwise
        # mode (recurrent archs) cannot rewind its recurrence, so it
        # never speculates.  Draft length is capped one below the largest
        # bucket: a speculating lane is a (1 + k)-token span.
        self._spec_k = 0
        if (serve_cfg.spec_k > 0 and serve_cfg.temperature <= 0.0
                and self._mode != "tokenwise"):
            self._spec_k = min(serve_cfg.spec_k, self._buckets[-1] - 1)
        # pluggable proposer (tests swap in adversarial drafts — the
        # output contract holds for ANY proposer, only speed varies)
        self._draft_fn = ngram_propose
        self._rollback_fn = jax.jit(_dense_rollback, donate_argnums=(0,))
        self._no_rollback = 1 << 30   # per-lane sentinel: nothing to rewind

        def _reset_lane(states, lane):
            """Clear one batch lane back to its init value (fresh request)."""
            fresh = init_states(cfg, b, serve_cfg.max_seq,
                                int8_kv=serve_cfg.int8_kv,
                                window_slack=self._window_slack)
            if kv_source is not None:
                # static cross-attention KV: projected once, not per token
                fresh = precompute_cross_states(params, cfg, kv_source, fresh)
            return _masked_commit(states, fresh, jnp.arange(b) == lane)

        self._reset_lane = jax.jit(_reset_lane, donate_argnums=(0,))
        if kv_source is not None:
            self.states = jax.jit(precompute_cross_states, static_argnums=(1,))(
                params, cfg, kv_source, self.states)
        # lane bookkeeping (host side)
        self.lane_pos = np.zeros(b, np.int32)
        self.lane_active = np.zeros(b, bool)
        self.lane_request: list[Any] = [None] * b
        self.lane_keys = jnp.zeros((b, 2), jnp.uint32)
        self.base_key = jax.random.PRNGKey(serve_cfg.seed)
        self.queue = AdmissionQueue(serve_cfg.queue_limit)
        self.preempted: list[dict] = []   # swapped-out, waiting to resume
        self.finished: list[dict] = []
        self._submitted = 0
        # injectable for tests; read ONLY for latency measurement — no
        # scheduling decision depends on the clock
        self._clock = time.monotonic
        self.stats: dict[str, Any] = {}
        self.reset_stats()
        self._forwards: list[tuple[int, int]] = []   # this step's (t, lanes)

    def _init_tp(self, packed_masked) -> None:
        """Build the tensor-parallel packed step: shard params (column-
        parallel projections), KV payloads (head axis), and the forward
        itself over a ("tp",) mesh via shard_map.

        The forward runs UNCHANGED per shard — the trace-time ``tp_serving``
        context makes ``models.attention``/``models.mlp`` route their out-
        projections through ``dist.tp.tp_out_projection`` (the only
        collective boundary), and every fused GEMM/attention kernel sees
        plain smaller shapes.  Host-side machinery (swap, preempt, COW,
        speculation rollback) is untouched: the helper jits have no mesh
        annotations, so GSPMD re-partitions them over whatever sharding
        the state tree carries, and ``_gather_pages_host``'s device_get
        assembles full pages from the shards (replication-safe)."""
        from ..dist.sharding import serve_param_specs, serve_state_specs
        from ..dist.tp import TPServing, tp_serving, validate_tp_serving
        from ..kernels import autotune
        from ..launch.mesh import make_tp_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        tp = self.scfg.tp
        validate_tp_serving(self.cfg, tp, kv_source=self.kv_source)
        choice = self.scfg.tp_overlap   # string validated in __init__
        if choice == "auto":
            rows = self.scfg.batch_lanes * (
                self._buckets[-1] if self._buckets else 1)
            choice = autotune.tp_serving_overlap(
                rows, self.cfg.d_model, self.cfg.d_ff,
                self.cfg.n_heads * self.cfg.d_head, tp,
                backend=ops.backend())
        self.tp_overlap_resolved = choice
        ctx = TPServing(axis="tp", size=tp, overlap=(choice == "overlap"))
        mesh = self.tp_mesh = make_tp_mesh(tp)
        pspecs = serve_param_specs(self.params, tp)
        sspecs = serve_state_specs(self.states, tp)
        is_p = lambda x: x is None or isinstance(x, P)
        self.params = jax.device_put(self.params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspecs, is_leaf=is_p))
        self.states = jax.device_put(self.states, jax.tree.map(
            lambda s: NamedSharding(mesh, s), sspecs, is_leaf=is_p))

        def _sharded(params, tokens, positions, states, lane_mask,
                     last_idx, commit_all, verify_rows):
            def inner(params, tokens, positions, states, lane_mask,
                      last_idx):
                with tp_serving(ctx):
                    return packed_masked(params, tokens, positions, states,
                                         lane_mask, last_idx, commit_all,
                                         verify_rows)
            f = jax.shard_map(
                inner, mesh=mesh,
                in_specs=(pspecs, P(), P(), sspecs, P(), P()),
                out_specs=(P(), P(), sspecs), check_vma=False)
            return f(params, tokens, positions, states, lane_mask, last_idx)

        self._step_fn = self._step_jit = jax.jit(_sharded,
                                                 static_argnums=(6, 7))

    def _resolve_mode(self) -> str:
        """'packed' | 'chunked' | 'tokenwise' (recurrent archs: tokenwise —
        their recurrence would consume pad tokens)."""
        if self.cfg.has_recurrent_state:
            return "tokenwise"
        if self.scfg.token_budget > 0:
            # budget 1 is legal: bucket-1 packed, i.e. one token per lane
            return "packed"
        if self.scfg.prefill_chunk > 1:
            return "chunked"
        return "tokenwise"

    def _resolve_paged(self) -> bool:
        """Paged KV needs every per-forward state mutation to flow through
        the position-masked page scatter: recurrent states (Mamba/xLSTM)
        and per-lane cross-attention KV don't, so those archs keep the
        dense layout (the request just falls back silently)."""
        if not self.scfg.paged or self.kv_source is not None:
            return False
        if self.cfg.has_recurrent_state:
            return False
        return not any(k in ("xattn", "dec") for k in self.cfg.block_pattern)

    def _token_buckets(self) -> tuple[int, ...]:
        """Static row lengths for the packed forward.

        Power-of-two lengths up to the mode's cap (``token_budget`` packed,
        ``prefill_chunk`` chunked), strictly below ``max_seq``.  Sliding-
        window ring caches are widened by the largest bucket
        (``_window_slack``), so every cache stays strictly LONGER than any
        per-lane span: a span of exactly cache length would take
        _write_cache's full-assign path (erasing older in-window history)
        and a longer one would scatter duplicate ring slots in a single
        write — implementation-defined in JAX.  Bucket 1 (the all-decode
        steady state) is always present in packed mode.  Empty tuple =
        tokenwise (every call is a single-token row).
        """
        if self._mode == "tokenwise":
            return ()
        cap = (self.scfg.token_budget if self._mode == "packed"
               else self.scfg.prefill_chunk)
        out, b = [1] if self._mode == "packed" else [], 2
        while b <= cap:
            if b < self.scfg.max_seq:
                out.append(b)
            b *= 2
        if cap not in out and cap < self.scfg.max_seq:
            out.append(cap)
        return tuple(sorted(out))

    @property
    def mode(self) -> str:
        """Active schedule: 'packed', 'chunked', or 'tokenwise'."""
        return self._mode

    @property
    def paged(self) -> bool:
        """True when the paged KV pool backs this engine's caches."""
        return self._paged

    def _apply_pool_actions(self, actions) -> None:
        """Replay the allocator's device actions on the arena IN ORDER
        (an evicted page can be re-allocated as a COW target inside one
        batch), coalescing runs of consecutive clears into one masked
        reset."""
        if not actions:
            return
        with tracing.span(
                "engine.pool",
                clears=lambda: sum(a[0] == "clear" for a in actions),
                copies=lambda: sum(a[0] != "clear" for a in actions)):
            pending: list[int] = []

            def flush():
                if pending:
                    mask = np.zeros(self.pool.n, bool)
                    mask[pending] = True
                    self.states = self._clear_fn(self.states,
                                                 jnp.asarray(mask))
                    pending.clear()

            for act in actions:
                if act[0] == "clear":
                    pending.append(act[1])
                    continue
                flush()
                _, src, dst, keep = act
                self.states = self._copy_fn(self.states, np.int32(src),
                                            np.int32(dst), np.int32(keep))
            flush()

    @property
    def chunk_buckets(self) -> tuple[int, ...]:
        """Static packed-row lengths in use (empty = tokenwise)."""
        return self._buckets

    def warmup(self) -> None:
        """Compile EVERY program variant outside any measurement window:
        both ``commit_all`` variants of every bucket.

        One LONE request of exactly the bucket length exercises each
        bucket end to end (admit, reset, sample — drained one at a time;
        co-resident requests would share the largest bucket) and compiles
        the partial-mask (``commit_all=False``) variants.  Warmup requests
        live in a RESERVED PRNG key space (the top of the uint32 fold
        range) and do not advance the submission counter, so later
        requests' sampled tokens are identical with or without warmup.

        The all-lanes steady state (``mask.all()``) is a DIFFERENT static
        program a lone request can never reach; it is compiled per bucket
        with an all-pad dummy batch — every position is -1, so cache
        writes are dropped and the committed states are unchanged (and
        lanes are reset on admission regardless).  Clears the finished
        list and stats afterwards."""
        for bl in [b for b in self._buckets if b > 1]:
            self._submit_warmup([2 + (i % 5) for i in range(bl)], bl)
            self.run_until_drained()
        # bucket-1 program (the all-decode steady state / tokenwise row)
        self._submit_warmup([2], 1)
        self.run_until_drained()
        # bucket 1 always participates even when absent from the table
        # (chunked mode): the all-lanes-DECODING steady state is the
        # dominant production program
        for t in sorted({1, *self._buckets}):
            _, _, self.states = self._step_fn(*self._all_pad_args(t))
        if self._paged:
            # warmup prompts must not linger as shareable prefixes (or hold
            # pages): flush the radix index before real traffic arrives
            self._apply_pool_actions(self.pool.flush_tree())
        self.finished.clear()
        self.reset_stats()

    def _all_pad_args(self, t: int) -> tuple:
        """Arguments of the all-lanes (``commit_all``) step at bucket ``t``
        with every position -1: cache writes are dropped, so running it
        leaves the committed states unchanged."""
        b = self.scfg.batch_lanes
        return (self.params, jnp.zeros((b, t), jnp.int32),
                jnp.full((b, t), -1, jnp.int32), self.states,
                jnp.ones((b,), bool), jnp.zeros((b,), jnp.int32), True,
                min(self._spec_k + 1, t))

    def _init_states(self):
        """Fresh KV/recurrent states for every lane (empty caches)."""
        paged = (dict(paged_pages=self.pool.n, page_size=self.pool.ps)
                 if self._paged else {})
        return init_states(self.cfg, self.scfg.batch_lanes,
                           self.scfg.max_seq, int8_kv=self.scfg.int8_kv,
                           window_slack=self._window_slack, **paged)

    def fixed_batch_logits(self, batches, backend: str) -> list[jax.Array]:
        """Last-row logits of the packed step on the kernel path
        ``backend`` ("pallas" or "jnp") over ``batches``: (tokens,
        positions) (B, T) int32 pairs run in order from fresh states, each
        lane on pages of its own.  A reference comparison of the serving
        step at tp=1: compiled anew per call, it leaves the engine's
        states, pool and programs as they were."""
        assert self.scfg.tp == 1 and self.kv_source is None
        b = self.scfg.batch_lanes
        states = self._init_states()
        if self._paged:
            mp = self.pool.mp
            assert self.pool.n > b * mp, "pool too small for one page set"
            states = _with_page_table(states, jnp.asarray(
                1 + np.arange(b * mp).reshape(b, mp), jnp.int32))
        out = []
        with ops.use_backend(backend):   # read while tracing, just below
            step = jax.jit(self._step_body, static_argnums=(6, 7))
            for tokens, positions in batches:
                last = jnp.full((b,), tokens.shape[1] - 1, jnp.int32)
                lg, _, states = step(self.params, tokens, positions, states,
                                     jnp.ones((b,), bool), last, True, 1)
                out.append(lg)
        return out

    def step_hlo(self, t: int) -> str:
        """Compiled HLO text of the all-lanes packed step at bucket ``t``
        (e.g. to count the Pallas kernels in it: ``tpu_custom_call``)."""
        return self._step_fn.lower(*self._all_pad_args(t)).compile().as_text()

    def _submit_warmup(self, prompt: list[int], bucket: int) -> None:
        """Queue a warmup request keyed in the reserved stream space at the
        TOP of the uint32 fold range (-1 - bucket mod 2^32 — fold_in
        coerces to uint32, so real submission ids counting up from 0 can
        never collide) — never touches ``_submitted``."""
        self.queue.push({"prompt": list(prompt), "max_new": 2,
                         "id": f"_warmup{bucket}", "generated": [],
                         "_seq": 2 ** 32 - 1 - bucket, "priority": 0,
                         "t_submit": self._clock()})

    def reset_stats(self) -> None:
        self.stats = {
            "requests": 0, "steps": 0, "forwards": {},
            "prompt_tokens": 0, "decode_tokens": 0, "pad_tokens": 0,
            "budget_tokens": 0, "prefix_len_hist": {},
            # continuous-batching front end (see docs/serving.md glossary)
            "queue_peak": 0, "rejected": 0,
            "preemptions": 0, "resumes": 0, "preempted_requests": [],
            "swap_out_pages": 0, "swap_in_pages": 0,
            "ttft_ms": [], "tpot_ms": [],
            "slo_ttft_miss": 0, "slo_tpot_miss": 0,
            # self-speculative decoding (docs/serving.md glossary);
            # spec_throttled counts proposals halved under pool pressure
            "spec_drafted": 0, "spec_accepted": 0, "spec_steps": 0,
            "spec_throttled": 0,
            # admission time - submission time of each admitted request
            "queue_wait_ms": [],
            # packed-step compiles or compile-cache loads (after warmup()
            # resets the stats: none expected)
            "compiles": 0,
        }
        if self._paged:
            # prefix-hit / COW / eviction counters live in pool.stats (one
            # source of truth); reset in lockstep with the engine's
            self.pool.reset_stats()

    # -- API -------------------------------------------------------------
    def submit(self, prompt: list[int], max_new: int = 32, request_id=None,
               *, priority: int = 0, ttft_slo_ms: float | None = None,
               tpot_slo_ms: float | None = None, on_token=None):
        """Queue one request for continuous serving.

        ``priority``: higher admits (and survives memory pressure) first;
        equal priorities keep submission order.  ``ttft_slo_ms`` /
        ``tpot_slo_ms``: per-request latency targets — bookkeeping only
        (misses are counted in stats), never a scheduling input.
        ``on_token(request_id, token)`` streams tokens as they commit.

        Invalid requests fail HERE with ``ValueError`` — an empty prompt
        has nothing to prefill, and a prompt of ``max_seq - max_new`` or
        longer cannot fit its decode budget — instead of surfacing as a
        shape/PRNG failure mid-step.  A full bounded queue
        (``ServeConfig.queue_limit``) raises ``QueueFullError``: overload
        is explicit rejection, never a silent drop."""
        with tracing.span("engine.submit", request=lambda: str(request_id)):
            n = len(prompt)
            if n == 0:
                raise ValueError("empty prompt: nothing to prefill "
                                 "(submit at least one token)")
            if max_new < 1:
                raise ValueError(f"max_new must be >= 1, got {max_new}")
            if n >= self.scfg.max_seq - max_new:
                raise ValueError(
                    f"prompt of {n} tokens cannot fit max_new={max_new} "
                    f"within max_seq={self.scfg.max_seq}: need "
                    f"len(prompt) < max_seq - max_new")
            req = {"prompt": list(prompt), "max_new": max_new,
                   "id": request_id, "generated": [],
                   "_seq": self._submitted, "priority": int(priority),
                   "ttft_slo_ms": ttft_slo_ms, "tpot_slo_ms": tpot_slo_ms,
                   "on_token": on_token, "t_submit": self._clock()}
            try:
                self.queue.push(req)
            except QueueFullError:
                self.stats["rejected"] += 1
                raise
            self._submitted += 1
            self.stats["requests"] += 1
            self.stats["queue_peak"] = max(self.stats["queue_peak"],
                                           len(self.queue))
            h = self.stats["prefix_len_hist"]
            bucket = _pow2_bucket(max(len(prompt), 1))
            h[bucket] = h.get(bucket, 0) + 1

    def _admit(self) -> list[dict]:
        """Fill free lanes: preempted requests resume FIRST (highest
        priority, then oldest — they already paid for their progress and
        their KV sits in the host swap buffer), then the priority queue.
        A resume blocked on pool capacity HOLDS its lane rather than
        letting new work jump past a half-served request; in paged mode a
        new request is only admitted while the pool has any headroom
        (free or evictable pages) — under pressure the queue is the
        backpressure, not the allocator.  Returns the requests taken from
        the queue."""
        admitted: list[dict] = []
        for lane in range(self.scfg.batch_lanes):
            if self.lane_active[lane]:
                continue
            if self.preempted:
                req = min(self.preempted,
                          key=lambda r: (-r["priority"], r["_seq"]))
                if not self._try_resume(lane, req):
                    break
                continue
            if not self.queue:
                break
            if (self._paged and
                    self.pool.free_pages + self.pool.evictable_pages < 2):
                break
            req = self.queue.pop()
            req["t_admit"] = self._clock()
            self.stats["queue_wait_ms"].append(
                (req["t_admit"] - req["t_submit"]) * 1e3)
            admitted.append(req)
            if self._paged:
                # lane isolation = page bookkeeping: the previous request's
                # pages were freed (and cleared) at finish; here the radix
                # index maps any registered shared prefix into the lane so
                # prefill SKIPS the shared span entirely
                shared, actions = self.pool.admit(lane, req["prompt"])
                self._apply_pool_actions(actions)
                self.lane_pos[lane] = shared
                req["_pending_prompt"] = req["prompt"][shared:]
            else:
                self.states = self._reset_lane(self.states, lane)
                self.lane_pos[lane] = 0
                req["_pending_prompt"] = req["prompt"][:]
            self.lane_request[lane] = req
            self.lane_active[lane] = True
            # per-lane PRNG stream, keyed by SUBMISSION id: a request's
            # samples never depend on lane count or co-resident traffic
            self.lane_keys = self.lane_keys.at[lane].set(
                jax.random.fold_in(self.base_key, req["_seq"]))
        return admitted

    # -- preemption + KV page swap ----------------------------------------
    def _gather_pages_host(self, pids: list[int]) -> list[dict]:
        """Swap-out, device side: copy the pages' payloads (K/V, scales,
        position ids) into HOST memory — one payload dict per paged
        state, in state-tree order.  Must run BEFORE the release actions
        clear the pages."""
        idx = jnp.asarray(np.asarray(pids, np.int32))
        out = []
        for st in self.states:
            if isinstance(st, dict) and "kv" in st and "ppos" in st["kv"]:
                out.append(jax.device_get(gather_pages(st["kv"], idx)))
        return out

    def _scatter_pages_device(self, pids: list[int],
                              payloads: list[dict]) -> None:
        """Swap-in, device side: one jitted scatter of the saved payloads
        into the freshly allocated pages, padded to the per-lane page
        budget (pad ids are out of bounds → dropped) so every resume hits
        the SAME compiled program."""
        mp = self.pool.mp
        idx = np.full(mp, self.pool.n, np.int32)
        idx[:len(pids)] = pids
        padded = []
        for payload in payloads:
            d = {}
            for k, v in payload.items():
                ax = v.ndim - 2 if k == "ppos" else v.ndim - 4
                if v.shape[ax] < mp:
                    pad = [(0, 0)] * v.ndim
                    pad[ax] = (0, mp - v.shape[ax])
                    v = np.pad(v, pad)
                d[k] = v
            padded.append(d)
        self.states = self._swap_in_fn(self.states, jnp.asarray(idx), padded)

    def _preempt_lane(self, lane: int) -> None:
        """Victim selected: swap the lane's KV pages to host memory and
        free the lane.  The request keeps its position counter, pending
        prompt, and generated tokens — its PRNG stream is keyed by
        submission id, so the eventual resume produces bit-identical
        tokens to an uninterrupted run."""
        req = self.lane_request[lane]
        mapped, actions = self.pool.swap_out(lane)
        js = [j for j, _ in mapped]
        payloads = self._gather_pages_host([p for _, p in mapped]) if js \
            else []
        self._apply_pool_actions(actions)
        req["_swap"] = (js, payloads)
        req["_lane_pos"] = int(self.lane_pos[lane])
        self.lane_active[lane] = False
        self.lane_request[lane] = None
        self.preempted.append(req)
        st = self.stats
        st["preemptions"] += 1
        st["swap_out_pages"] += len(js)
        st["preempted_requests"].append(req["id"])

    def _try_resume(self, lane: int, req: dict) -> bool:
        """Swap a preempted request back in: rebind its logical pages to
        fresh physical pages, scatter the saved payload, restore the
        lane's counters and PRNG stream.  False (and no state change)
        when the pool cannot host it yet."""
        js, payloads = req["_swap"]
        try:
            pids, actions = self.pool.swap_in(lane, js)
        except PoolExhaustedError as e:
            self._apply_pool_actions(e.actions)
            return False
        self._apply_pool_actions(actions)
        if js:
            self._scatter_pages_device(pids, payloads)
        del req["_swap"]
        self.preempted.remove(req)
        self.lane_pos[lane] = req.pop("_lane_pos")
        self.lane_request[lane] = req
        self.lane_active[lane] = True
        self.lane_keys = self.lane_keys.at[lane].set(
            jax.random.fold_in(self.base_key, req["_seq"]))
        self.stats["resumes"] += 1
        self.stats["swap_in_pages"] += len(js)
        return True

    def _reserve_pages(self, plan: dict[int, int]) -> bool:
        """The maybe-preempt stage: back every planned span with
        lane-owned physical pages.  When the pool cannot, preempt a
        victim — lowest priority first, then shortest progress (least
        sunk cost), then lane index — swap its pages out, drop it from
        the plan, and retry with the survivors.  Each retry removes one
        active lane, and a lone lane always fits (pool >= mp + 2 pages),
        so this terminates with forward progress.  Mutates ``plan``;
        returns False when nothing is left to run this iteration."""
        while True:
            try:
                for lane in sorted(plan):
                    p0 = int(self.lane_pos[lane])
                    self._apply_pool_actions(
                        self.pool.ensure_writable(lane, p0, plan[lane]))
                    if self._cap_window:
                        self._apply_pool_actions(
                            self.pool.cap_window(lane, p0, self._cap_window))
                return bool(plan)
            except PoolExhaustedError as e:
                self._apply_pool_actions(e.actions)
                victims = [l for l in range(self.scfg.batch_lanes)
                           if self.lane_active[l]]
                if len(victims) <= 1 or not self.scfg.swap:
                    raise   # lone lanes always fit; swap off -> surface it
                victim = min(victims, key=lambda l: (
                    self.lane_request[l]["priority"],
                    int(self.lane_pos[l]), l))
                self._preempt_lane(victim)
                plan.pop(victim, None)

    def _emit(self, req: dict, tok: int) -> None:
        """Commit one generated token: record first-token latency, stream
        it to the request's callback if any."""
        req["generated"].append(tok)
        if "t_first" not in req:
            req["t_first"] = self._clock()
        cb = req.get("on_token")
        if cb is not None:
            cb(req["id"], tok)

    def _finish_lane(self, lane: int) -> None:
        req = self.lane_request[lane]
        rec = {"id": req["id"], "prompt": req["prompt"],
               "tokens": req["generated"]}
        if "_spec_drafted" in req:
            # per-request draft/accept counters (acceptance rate = how
            # well the proposer predicted THIS request's greedy stream)
            rec["spec_drafted"] = req["_spec_drafted"]
            rec["spec_accepted"] = req["_spec_accepted"]
        if "t_first" in req:
            st = self.stats
            ttft = (req["t_first"] - req["t_submit"]) * 1e3
            st["ttft_ms"].append(ttft)
            rec["ttft_ms"] = ttft
            if (req.get("ttft_slo_ms") is not None
                    and ttft > req["ttft_slo_ms"]):
                st["slo_ttft_miss"] += 1
            n = len(req["generated"])
            if n > 1:
                tpot = (self._clock() - req["t_first"]) * 1e3 / (n - 1)
                st["tpot_ms"].append(tpot)
                rec["tpot_ms"] = tpot
                if (req.get("tpot_slo_ms") is not None
                        and tpot > req["tpot_slo_ms"]):
                    st["slo_tpot_miss"] += 1
        self.finished.append(rec)
        self.lane_active[lane] = False
        self.lane_request[lane] = None
        if self._paged:
            # drop the lane's page references; pages the prefix index still
            # names survive for future sharers, the rest clear + free
            self._apply_pool_actions(self.pool.lane_release(lane))

    def _check_done(self, lane: int) -> None:
        req = self.lane_request[lane]
        done = (len(req["generated"]) >= req["max_new"]
                or (req["generated"]
                    and req["generated"][-1] == self.scfg.eos_token)
                or self.lane_pos[lane] >= self.scfg.max_seq - 1)
        if done:
            self._finish_lane(lane)

    def _keys_at(self, key_pos):
        """(B, 2) sampling keys: lane stream folded at each lane's own fed
        position — deterministic per (request, position), never per engine
        iteration or scheduling mode."""
        return jax.vmap(jax.random.fold_in)(
            self.lane_keys, jnp.asarray(key_pos))

    # -- packed forward over a per-lane token plan ------------------------
    def _propose(self, lane: int) -> list[int]:
        """Draft tokens for a generating lane (self-speculation).  Stores
        the draft on the request (consumed by ``_run_lanes``) and returns
        it; empty when speculation is off or the proposer finds nothing.
        Drafting never outruns what the request could still commit: the
        length is capped at the remaining ``max_new`` budget and the
        lane's sequence room, on top of the bucket cap from __init__.

        SWAP-AWARE THROTTLE: while any request sits preempted (the pool
        is under enough pressure that a lane was swapped out), drafts are
        halved — rejected speculative rows are pure pad under pressure,
        and shorter spans shrink each step's page reservation, helping
        the victim resume sooner.  Draft CONTENT never affects outputs
        (the verifier guarantees bit-identity for any draft), so the
        throttle changes speed only; full-length drafting resumes the
        step after ``preempted`` drains."""
        req = self.lane_request[lane]
        k = self._spec_k
        if k and self.preempted:
            k //= 2
            self.stats["spec_throttled"] += 1
        if k:
            k = min(k, req["max_new"] - len(req["generated"]) - 1,
                    self.scfg.max_seq - 1 - int(self.lane_pos[lane]))
        if k <= 0:
            req["_draft"] = []
        else:
            ctx = req["prompt"] + req["generated"]
            req["_draft"] = [int(t) for t in self._draft_fn(ctx, k)][:k]
        return req["_draft"]

    def _plan_tokens(self, lanes: list[int], budget: int) -> dict[int, int]:
        """Per-lane token counts for one forward: generating lanes take 1
        (plus their speculative draft, when one exists — a speculating
        decode lane is a 1+k-token contributor), prefilling lanes
        waterfill the remaining budget — shortest pending prompt first,
        so a short prompt takes only what it needs and the leftover flows
        to longer ones (each lane gets at least 1 token, capped at the
        largest bucket, its pending prompt, and its remaining sequence
        room).  Lanes whose prompt exhausted the sequence budget are
        finished here."""
        cap = self._buckets[-1] if self._buckets else 1
        prefilling = [l for l in lanes
                      if self.lane_request[l]["_pending_prompt"]]
        plan = {l: 1 + len(self._propose(l))
                for l in lanes if l not in prefilling}
        if not prefilling:
            return plan
        left = budget - sum(plan.values())
        order = sorted(prefilling, key=lambda l: (
            len(self.lane_request[l]["_pending_prompt"]), l))
        for i, lane in enumerate(order):
            room = self.scfg.max_seq - 1 - int(self.lane_pos[lane])
            if room <= 0:  # prompt exhausted the sequence budget
                self._finish_lane(lane)
                continue
            share = max(left // (len(order) - i), 1)
            pending = len(self.lane_request[lane]["_pending_prompt"])
            plan[lane] = max(min(pending, share, cap, room), 1)
            left -= plan[lane]
        return plan

    def _run_lanes(self, plan: dict[int, int]) -> None:
        """ONE packed forward: each lane in ``plan`` contributes its token
        count (prompt tokens if it is still consuming its prompt, else its
        last sampled token plus any speculative draft), rows right-padded
        with position -1 up to the smallest bucket that fits.  Logits
        gather at per-lane last valid indices; sampling keys fold at
        per-lane last fed positions.

        Speculating lanes (span 1 + m) run the draft-then-verify commit:
        the span's greedy argmax rows ARE sequential decode's outputs
        (causal masking derives from absolute positions, so row j of the
        span cannot see the drafted tokens after it), so the verifier
        accepts draft tokens while they match the argmax of the PREVIOUS
        row, commits that run plus one corrective token, and withdraws
        the KV writes of every rejected position — pool.truncate actions
        (paged) or the pos_ids rewind (dense).  Committed tokens replay
        vanilla's per-token stop rules (max_new / EOS / sequence end), so
        the emitted stream is bit-identical to vanilla greedy decode for
        ANY draft content."""
        if not plan:
            return
        b = self.scfg.batch_lanes
        if self._paged:
            # back every logical page this step writes with a lane-owned
            # physical page (alloc / copy-on-write), preempting victims
            # under memory pressure, cap windowed lanes' live pages
            free, pre = self.pool.free_pages, self.stats["preemptions"]
            with tracing.span("engine.reserve") as sp:
                ok = self._reserve_pages(plan)
                tracing.note(
                    sp, pages=lambda: free - self.pool.free_pages,
                    preemptions=lambda: self.stats["preemptions"] - pre)
            if not ok:
                return
        with tracing.span("engine.pack") as sp:
            if self._paged:   # ship the updated page table
                self.states = _with_page_table(self.states,
                                               jnp.asarray(self.pool.table))
            need = max(plan.values())
            t = need if need == 1 else next(
                bk for bk in self._buckets if bk >= need)
            vr = min(self._spec_k + 1, t)     # verify rows (static per bucket)
            tok = np.zeros((b, t), np.int32)
            pos = np.full((b, t), -1, np.int32)  # -1 = pad: write dropped
            last_idx = np.zeros(b, np.int32)
            mask = np.zeros(b, bool)
            key_pos = self.lane_pos.copy()
            n_prompt = 0
            speculating = False
            for lane, c in plan.items():
                req = self.lane_request[lane]
                p0 = int(self.lane_pos[lane])
                if req["_pending_prompt"]:
                    tok[lane, :c] = req["_pending_prompt"][:c]
                    n_prompt += c
                else:
                    if req["generated"]:
                        tok[lane, 0] = req["generated"][-1]
                    if c > 1:                 # speculative draft rows
                        tok[lane, 1:c] = req["_draft"][:c - 1]
                        speculating = True
                pos[lane, :c] = np.arange(p0, p0 + c)
                last_idx[lane] = c - 1
                key_pos[lane] = p0 + c - 1    # last fed position
                mask[lane] = True
            tracing.note(sp, rows=b * t, bucket=t)
        self._forwards.append((t, len(plan)))
        with tracing.span("engine.forward") as sp:
            programs = self._step_jit._cache_size()
            # paged mode always commits the whole tree: the shared arena
            # has no lane dimension to mask (pad writes are position-
            # dropped, and no per-lane state leaves exist on paged-capable
            # archs)
            lg, greedy, self.states = self._step_fn(
                self.params, jnp.asarray(tok), jnp.asarray(pos), self.states,
                jnp.asarray(mask), jnp.asarray(last_idx),
                True if self._paged else bool(mask.all()), vr)
            nxt = _sample(lg, self.scfg.temperature, self._keys_at(key_pos))
            compiled = int(self._step_jit._cache_size() > programs)
            self.stats["compiles"] += compiled
            tracing.note(sp, compiled=compiled)
        with tracing.span("engine.wait"):
            nxt = np.asarray(nxt)
            greedy = np.asarray(greedy) if speculating else None
        with tracing.span("engine.commit") as sp:
            done = len(self.finished)
            n_emitted = self._commit(plan, t, vr, n_prompt, nxt, greedy)
            tracing.note(sp, tokens=n_emitted,
                         finished=lambda: len(self.finished) - done)

    def _commit(self, plan: dict[int, int], t: int, vr: int, n_prompt: int,
                nxt: np.ndarray, greedy: np.ndarray | None) -> int:
        """Commit one forward's sampled (and, for speculating lanes,
        verified) tokens lane by lane; returns the tokens emitted."""
        b = self.scfg.batch_lanes
        st = self.stats
        st["forwards"][t] = st["forwards"].get(t, 0) + 1
        n_first = 0                           # prompts that completed
        n_decode = 0
        rollback_keep = None                  # dense rewind bounds (B,)
        for lane, c in plan.items():
            req = self.lane_request[lane]
            p0 = int(self.lane_pos[lane])
            if req["_pending_prompt"]:
                self.lane_pos[lane] += c
                del req["_pending_prompt"][:c]
                if not req["_pending_prompt"]:
                    # boundary token: sampled from the last prompt logit,
                    # key folded at the last prompt position (= decode rule)
                    self._emit(req, int(nxt[lane]))
                    n_first += 1
                    if self._paged:
                        # prompt fully in cache: register its pages in the
                        # radix index so later submissions can share them
                        self.pool.register_prompt(lane, req["prompt"])
                self._check_done(lane)
                continue
            draft = req.pop("_draft", [])
            if c == 1:                        # vanilla decode row
                self.lane_pos[lane] += 1
                n_decode += 1
                self._emit(req, int(nxt[lane]))
                self._check_done(lane)
                continue
            # draft-then-verify: v[j] = the model's greedy token after
            # feeding span row j (position p0 + j) — this span's last c
            # verify rows.  Accept drafts while they match; commit the
            # accepted run plus the first corrective token.
            m = c - 1
            v = greedy[lane, vr - c:]
            a = 0
            while a < m and draft[a] == v[a]:
                a += 1
            st["spec_drafted"] += m
            st["spec_accepted"] += a
            st["spec_steps"] += 1
            req["_spec_drafted"] = req.get("_spec_drafted", 0) + m
            req["_spec_accepted"] = req.get("_spec_accepted", 0) + a
            # commit one token at a time under vanilla's stop rules —
            # tokens past a stop are discarded exactly as vanilla never
            # would have generated them
            e = 0
            for i in range(a + 1):
                e += 1
                self._emit(req, int(v[i]))
                if (len(req["generated"]) >= req["max_new"]
                        or int(v[i]) == self.scfg.eos_token
                        or p0 + e >= self.scfg.max_seq - 1):
                    break
            self.lane_pos[lane] = p0 + e
            n_decode += e
            if e < c:
                # rejected tail [p0+e, p0+c): withdraw its KV writes so
                # the cache is exactly what sequential decode would hold
                if self._paged:
                    self._apply_pool_actions(
                        self.pool.truncate(lane, p0 + e, p0 + c))
                else:
                    if rollback_keep is None:
                        rollback_keep = np.full(b, self._no_rollback,
                                                np.int32)
                    rollback_keep[lane] = p0 + e
            self._check_done(lane)
        if rollback_keep is not None:
            self.states = self._rollback_fn(self.states,
                                            jnp.asarray(rollback_keep))
        st["prompt_tokens"] += n_prompt
        st["decode_tokens"] += n_decode
        # rejected speculative rows count as pads: they bought no output
        st["pad_tokens"] += t * len(plan) - n_prompt - n_decode
        return n_first + n_decode

    # -- scheduler --------------------------------------------------------
    def step(self) -> None:
        """One engine iteration: admit (resumes first) → maybe-preempt
        (inside ``_reserve_pages``) → pack → forward → commit → complete.
        Packed mode: ONE forward mixing prefill chunk tokens and decode
        tokens under ``token_budget`` — no prefill/decode phase split.
        Chunked mode: the PR 2 two-call schedule (prefill chunk, then
        decode) over the same program family.  Tokenwise: single-token
        rows for every lane.

        While a profiler session records, each stage is a span on the
        trace's clock (``serve/tracing.py``): ``engine.step`` holds
        ``engine.admit``, then per forward ``engine.plan``,
        ``engine.reserve`` (paged), ``engine.pack``, ``engine.forward``,
        ``engine.wait`` and ``engine.commit``; ``engine.pool`` wraps the
        page pool's device actions wherever they run."""
        st = self.stats
        rows0 = (st["prompt_tokens"], st["decode_tokens"], st["pad_tokens"])
        self._forwards = []
        with tracing.span("engine.step") as sp:
            self._schedule()
            tracing.note(
                sp, step=st["steps"], mode=self._mode,
                bucket=lambda: max((t for t, _ in self._forwards), default=0),
                lanes=lambda: sum(n for _, n in self._forwards),
                prompt_rows=lambda: st["prompt_tokens"] - rows0[0],
                decode_rows=lambda: st["decode_tokens"] - rows0[1],
                pad_rows=lambda: st["pad_tokens"] - rows0[2])

    def _schedule(self) -> None:
        resumes = self.stats["resumes"]
        with tracing.span("engine.admit") as sp:
            admitted = self._admit()
            tracing.note(
                sp, requests=lambda: " ".join(
                    f"{r['id']}:{(r['t_admit'] - r['t_submit']) * 1e3:.3f}"
                    for r in admitted),
                resumes=lambda: self.stats["resumes"] - resumes)
        if not self.lane_active.any():
            return
        self.stats["steps"] += 1
        lanes = [l for l in range(self.scfg.batch_lanes)
                 if self.lane_active[l]]
        if self._mode == "packed":
            self.stats["budget_tokens"] += self.scfg.token_budget
            self._run_lanes(self._planned(
                lambda: self._plan_tokens(lanes, self.scfg.token_budget)))
            return
        if self._mode == "chunked":
            prefilling = [l for l in lanes
                          if self.lane_request[l]["_pending_prompt"]]
            if prefilling:
                # budget = lanes x cap: every lane gets a full chunk share
                self._run_lanes(self._planned(lambda: self._plan_tokens(
                    prefilling, len(prefilling) * self._buckets[-1])))
            decoding = [l for l in lanes if self.lane_active[l]
                        and l not in prefilling]
            if decoding:
                # decode call: 1 token per lane + any speculative draft
                self._run_lanes(self._planned(
                    lambda: {l: 1 + len(self._propose(l)) for l in decoding}))
            return
        # tokenwise: prompts feed one token per call (recurrent-arch safe)
        self._run_lanes(self._planned(lambda: {l: 1 for l in lanes}))

    def _planned(self, make) -> dict[int, int]:
        """``make()``'s plan, in an ``engine.plan`` span whose ``lanes``
        argument records, per planned lane, its first position, token
        count and whether the tokens are prompt (``p0:count:0|1``, one
        per lane, space-separated: the profiler separates arguments by
        commas)."""
        with tracing.span("engine.plan") as sp:
            plan = make()
            tracing.note(sp, lanes=lambda: " ".join(
                f"{int(self.lane_pos[l])}:{int(c)}:"
                f"{int(bool(self.lane_request[l]['_pending_prompt']))}"
                for l, c in plan.items()))
        return plan

    def run_until_drained(self, max_iters: int = 10_000) -> list[dict]:
        it = 0
        while (self.queue or self.preempted
               or self.lane_active.any()) and it < max_iters:
            self.step()
            it += 1
        return self.finished

    def run_stream(self, schedule, max_iters: int = 1_000_000):
        """Continuous serving against a TIMED arrival schedule.

        ``schedule`` is ``[(offset_s, submit_kwargs), ...]``: each request
        is submitted — in schedule order — once the wall clock passes its
        offset, with engine iterations running in between (the async
        front end, driven synchronously).  Timing never changes tokens:
        submission ORDER alone keys the PRNG streams, so a streamed drain
        is bit-identical to an offline drain of the same schedule.
        Bounded-queue rejections are collected (as request ids), not
        raised — overload sheds load explicitly while the drain keeps
        going.  Returns ``(finished, rejected_ids)``."""
        pending = collections.deque(schedule)
        t0 = self._clock()
        rejected = []
        it = 0
        while (pending or self.queue or self.preempted
               or self.lane_active.any()) and it < max_iters:
            while pending and self._clock() - t0 >= pending[0][0]:
                _, kw = pending.popleft()
                try:
                    self.submit(**kw)
                except QueueFullError:
                    rejected.append(kw.get("request_id"))
            if (pending and not self.queue and not self.preempted
                    and not self.lane_active.any()):
                # idle gap before the next arrival: don't spin flat out
                time.sleep(min(max(
                    pending[0][0] - (self._clock() - t0), 0.0), 0.001))
            self.step()
            it += 1
        return self.finished, rejected

    def serving_metrics(self) -> dict:
        """TTFT/TPOT and queue-wait percentiles + overload counters for
        the current stats window (see docs/serving.md for the field
        glossary).  Queue wait runs from ``submit`` to admission into a
        lane: TTFT is queue wait plus prefill."""
        st = self.stats
        return {
            "completed": len(st["ttft_ms"]),
            "queue_wait_p50_ms": round(percentile(st["queue_wait_ms"], 50), 3),
            "queue_wait_p99_ms": round(percentile(st["queue_wait_ms"], 99), 3),
            "ttft_p50_ms": round(percentile(st["ttft_ms"], 50), 3),
            "ttft_p99_ms": round(percentile(st["ttft_ms"], 99), 3),
            "tpot_p50_ms": round(percentile(st["tpot_ms"], 50), 3),
            "tpot_p99_ms": round(percentile(st["tpot_ms"], 99), 3),
            "queue_peak": st["queue_peak"],
            "rejected": st["rejected"],
            "preemptions": st["preemptions"],
            "resumes": st["resumes"],
            "swap_out_pages": st["swap_out_pages"],
            "swap_in_pages": st["swap_in_pages"],
            "slo_ttft_miss": st["slo_ttft_miss"],
            "slo_tpot_miss": st["slo_tpot_miss"],
            "spec_drafted": st["spec_drafted"],
            "spec_accepted": st["spec_accepted"],
            "spec_throttled": st["spec_throttled"],
            "spec_accept_rate": round(
                st["spec_accepted"] / st["spec_drafted"], 4)
            if st["spec_drafted"] else 0.0,
        }

    def stats_summary(self) -> str:
        st = self.stats
        fwd = ",".join(f"{k}:{v}" for k, v in sorted(st["forwards"].items()))
        hist = ",".join(f"<={k}:{v}" for k, v in
                        sorted(st["prefix_len_hist"].items()))
        valid = st["prompt_tokens"] + st["decode_tokens"]
        total = valid + st["pad_tokens"]
        eff = 100.0 * valid / total if total else 100.0
        fill = (100.0 * valid / st["budget_tokens"]
                if st["budget_tokens"] else 0.0)
        share = 100.0 * st["decode_tokens"] / valid if valid else 0.0
        out = (f"mode={self._mode} requests={st['requests']} "
               f"steps={st['steps']} compiles={st['compiles']} "
               f"prompt_tokens={st['prompt_tokens']} "
               f"decode_tokens={st['decode_tokens']} (share={share:.0f}%) "
               f"row_eff={eff:.0f}% forwards[{fwd}] prefix_hist[{hist}]")
        if st["budget_tokens"]:
            out += f" budget_fill={fill:.0f}%"
        if self._spec_k:
            rate = (100.0 * st["spec_accepted"] / st["spec_drafted"]
                    if st["spec_drafted"] else 0.0)
            out += (f" spec[k={self._spec_k} drafted={st['spec_drafted']}"
                    f" accepted={st['spec_accepted']} rate={rate:.0f}%]")
        if self._paged:
            ps = self.pool.stats
            out += (f" paged[page={self.pool.ps} hits={ps['prefix_hits']}"
                    f" hit_tokens={ps['prefix_hit_tokens']}"
                    f" cow={ps['cow_copies']} evict={ps['evictions']}"
                    f" pages_peak={ps['pages_peak']}"
                    f" tree_pages={self.pool.tree_pages}]")
        m = self.serving_metrics()
        if m["completed"]:
            out += (f" ttft_p50/p99={m['ttft_p50_ms']:.1f}/"
                    f"{m['ttft_p99_ms']:.1f}ms tpot_p50/p99="
                    f"{m['tpot_p50_ms']:.2f}/{m['tpot_p99_ms']:.2f}ms")
        if m["preemptions"] or m["rejected"]:
            out += (f" overload[preempt={m['preemptions']}"
                    f" resume={m['resumes']} swap_pages="
                    f"{m['swap_out_pages']}/{m['swap_in_pages']}"
                    f" rejected={m['rejected']}"
                    f" queue_peak={m['queue_peak']}]")
        return out
