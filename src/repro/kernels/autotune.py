"""Shape-aware kernel block-size selection (table-then-measure policy).

Every Pallas entry point in ``kernels/ops.py`` asks this module for its
block sizes instead of hardcoding MXU-shaped constants.  Resolution order
for a (kernel, shape, dtype, backend) key:

  1. **Measured cache** — a JSON file of block sizes that were actually
     timed (``REPRO_AUTOTUNE_CACHE`` env var, default
     ``.autotune/measured.json`` at the repo root, not committed).
     Benchmarks populate it via ``measure``; every stored key names the
     ``device_kind`` it was timed on, and an exact hit for THIS device's
     kind always wins — a CPU sweep left in a working tree never chooses
     a chip's tiles.
  2. **Cost-model-seeded table** — the analytic tile costs in
     ``core.costmodel`` (padding waste, compute/HBM roofline, grid-step
     overhead, VMEM wall) evaluated over the legal candidate lattice; the
     argmin is memoized per process.
  3. The candidate lattice itself guarantees legality, so there is no
     third fallback: every returned tile is MXU/VPU-legal (lane dims are
     multiples of 128, sublane dims multiples of 8, or the full extent of
     the array dim, e.g. a cache length below 128) and VMEM-feasible.
     Derived operand blocks follow the same rule: the W4A8 kernels lay
     their group multipliers out so that one K block's groups span a full
     array dim (``int8_gemm._qmul_blocks``).

The policy is "table, then measure": the cost model gives a good default
with zero warmup; real deployments run the benchmark sweep once per
machine and the measured numbers override the table from then on.  Keys
are exact — a measurement for one shape never generalizes to another
(that is the table's job).
"""
from __future__ import annotations

import functools
import json
import os

import jax

from ..core import costmodel

LANE = 128      # last-dim tile: VREG lane width / MXU edge
SUBLANE = 8     # second-to-last-dim tile for 32-bit types

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(_REPO_ROOT, ".autotune", "measured.json"))


_MEASURED: dict[str, dict] | None = None


def _measured() -> dict:
    global _MEASURED
    if _MEASURED is None:
        try:
            with open(cache_path()) as f:
                _MEASURED = json.load(f)
        except (OSError, ValueError):
            _MEASURED = {}
    return _MEASURED


def measured_key(key: str) -> str:
    """The stored form of a lookup key: tagged with the device kind it is
    timed on (``jax.devices()[0].device_kind``, read at first use)."""
    return f"{key}@{jax.devices()[0].device_kind}"


def tile_source() -> str:
    """Where this device's tiles come from: the measured cache when it holds
    entries for this device kind, else the committed cost-model table."""
    kind = jax.devices()[0].device_kind
    n = sum(k.endswith("@" + kind) for k in _measured())
    if n:
        return f"measured cache {cache_path()} ({n} entries for {kind})"
    return f"committed cost-model table (no measured entries for {kind})"


def record(key: str, blocks: tuple[int, ...], us: float) -> None:
    """Persist a measured (key -> blocks) entry; keeps the fastest."""
    cache = _measured()
    key = measured_key(key)
    prev = cache.get(key)
    if prev is not None and prev.get("us", float("inf")) <= us:
        return
    cache[key] = {"blocks": list(blocks), "us": us}
    path = cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def reset_measured_cache() -> None:
    """Drop the in-process view of the measured cache (tests/env changes)."""
    global _MEASURED
    _MEASURED = None
    gemm_blocks.cache_clear()
    gated_mlp_blocks.cache_clear()
    gemm_w4a8_blocks.cache_clear()
    gatedmlp_w4a8_blocks.cache_clear()
    attention_blocks.cache_clear()
    attention_pv_blocks.cache_clear()
    packed_blocks.cache_clear()
    paged_blocks.cache_clear()
    tp_serving_overlap.cache_clear()
    decode_blocks.cache_clear()
    rowwise_blocks.cache_clear()
    moe_group_size.cache_clear()


def measure(key: str, candidates, timer) -> tuple[int, ...]:
    """Time ``timer(blocks) -> us`` over candidates, record + return best."""
    best, best_us = None, float("inf")
    for blocks in candidates:
        us = timer(blocks)
        if us < best_us:
            best, best_us = tuple(blocks), us
    assert best is not None, "no candidates"
    record(key, best, best_us)
    return best


def is_mxu_legal(bm: int, bn: int, bk: int) -> bool:
    """GEMM tile legality: operand/output blocks land on (8, 128) tiles."""
    return bm % SUBLANE == 0 and bn % LANE == 0 and bk % LANE == 0


def _hit(key: str):
    ent = _measured().get(measured_key(key))
    return tuple(ent["blocks"]) if ent else None


# ---------------------------------------------------------------------------
# per-kernel tables
# ---------------------------------------------------------------------------

_GEMM_BMS = (8, 16, 32, 64, 128, 256, 512)
_GEMM_BNS = (128, 256, 512)
_GEMM_BKS = (128, 256, 512)


def _gemm_lattice_argmin(m: int, k: int, n: int,
                         cost_fn) -> tuple[int, int, int]:
    """Argmin of ``cost_fn(bm, bn, bk)`` over the legal GEMM tile lattice
    (shared by every GEMM-shaped key family)."""
    best, best_cost = None, float("inf")
    for bm in _GEMM_BMS:
        if bm > max(_round_up(m, SUBLANE), SUBLANE):
            continue
        for bn in _GEMM_BNS:
            if bn > max(_round_up(n, LANE), LANE):
                continue
            for bk in _GEMM_BKS:
                if bk > max(_round_up(k, LANE), LANE):
                    continue
                c = cost_fn(bm, bn, bk)
                if c < best_cost:
                    best, best_cost = (bm, bn, bk), c
    assert best is not None and is_mxu_legal(*best), (m, k, n, best)
    return best


@functools.lru_cache(maxsize=4096)
def gemm_blocks(m: int, k: int, n: int, dtype: str = "int8",
                backend: str = "pallas") -> tuple[int, int, int]:
    """(bm, bn, bk) for an (M,K)x(K,N) GEMM; wrappers pad up to these."""
    hit = _hit(f"gemm/{m}x{k}x{n}/{dtype}/{backend}")
    if hit:
        return hit
    in_bytes = 1 if dtype == "int8" else 2
    return _gemm_lattice_argmin(
        m, k, n, lambda bm, bn, bk: costmodel.gemm_tile_cost(
            m, k, n, bm, bn, bk, in_bytes=in_bytes))


@functools.lru_cache(maxsize=4096)
def gated_mlp_blocks(m: int, k: int, n: int, dtype: str = "int8",
                     backend: str = "pallas") -> tuple[int, int, int]:
    """(bm, bn, bk) for the dual-GEMM gated MLP (``dual_gemm_gated``).

    Its own key family — the second weight stream and second resident
    accumulator halve the VMEM headroom and shift the roofline relative to
    the single-GEMM table, so a ``gemm/`` optimum need not be optimal here.
    """
    hit = _hit(f"gatedmlp/{m}x{k}x{n}/{dtype}/{backend}")
    if hit:
        return hit
    in_bytes = 1 if dtype == "int8" else 2
    return _gemm_lattice_argmin(
        m, k, n, lambda bm, bn, bk: costmodel.gated_mlp_tile_cost(
            m, k, n, bm, bn, bk, in_bytes=in_bytes, out_bytes=2))


@functools.lru_cache(maxsize=4096)
def gemm_w4a8_blocks(m: int, k: int, n: int, group: int,
                     backend: str = "pallas") -> tuple[int, int, int]:
    """(bm, bn, bk) for the packed-int4 W4A8 GEMM (``int4_gemm``).

    Its own key family, keyed on the scale group size: the half-width
    weight stream shifts the HBM roofline and the nibble-unpack +
    per-group accumulate terms (costmodel.gemm_w4a8_tile_cost) add VPU
    cost that grows as the group shrinks.  bk must be a multiple of the
    group so scale groups never straddle K blocks.
    """
    hit = _hit(f"gemm_w4a8/{m}x{k}x{n}/g{group}/{backend}")
    if hit:
        return hit
    return _gemm_lattice_argmin(
        m, k, n, lambda bm, bn, bk: (
            float("inf") if bk % group else costmodel.gemm_w4a8_tile_cost(
                m, k, n, group, bm, bn, bk)))


@functools.lru_cache(maxsize=4096)
def gatedmlp_w4a8_blocks(m: int, k: int, n: int, group: int,
                         backend: str = "pallas") -> tuple[int, int, int]:
    """(bm, bn, bk) for the W4A8 dual-GEMM gated MLP
    (``dual_int4_gemm_gated``): two packed weight + multiplier streams and
    two resident int32 accumulators change the VMEM wall and roofline relative
    to both the ``gemm_w4a8`` and ``gatedmlp`` tables."""
    hit = _hit(f"gatedmlp_w4a8/{m}x{k}x{n}/g{group}/{backend}")
    if hit:
        return hit
    return _gemm_lattice_argmin(
        m, k, n, lambda bm, bn, bk: (
            float("inf") if bk % group
            else costmodel.gated_mlp_w4a8_tile_cost(
                m, k, n, group, bm, bn, bk)))


# GShard group-size candidates for the MoE dispatch tuner (tokens/group)
_MOE_GROUP_CANDIDATES = (128, 256, 512, 1024, 2048, 4096, 8192)


@functools.lru_cache(maxsize=4096)
def moe_group_size(t: int, d: int, ff: int, e: int, k: int,
                   capacity_factor: float) -> int:
    """Tokens per GShard dispatch group for a ``t``-token MoE forward.

    Same table-then-measure policy as the kernel tiles: an exact measured
    key (``moe/{T}x{D}x{FF}/{E}x{K}x{cf}``) wins, else the capacity-bounded
    all-to-all cost model (``core.costmodel.moe_dispatch_cost``) picks the
    argmin over the candidate group sizes.  Candidates are restricted to
    DIVISORS of ``t`` (one whole-batch group when no listed size divides),
    so the argmin scores the group size that actually runs; callers keep a
    defensive power-of-two demotion for measured-cache overrides that do
    not divide their token count.
    """
    hit = _hit(f"moe/{t}x{d}x{ff}/{e}x{k}x{capacity_factor:g}")
    if hit:
        return hit[0]
    cands = [sg for sg in _MOE_GROUP_CANDIDATES
             if sg <= t and t % sg == 0] or [t]
    best, best_cost = cands[0], float("inf")
    for sg in cands:
        c = costmodel.moe_dispatch_cost(t, d, ff, e, k, capacity_factor, sg)
        if c < best_cost:
            best, best_cost = sg, c
    return best


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _divisor_tiles(s: int, cap: int = 1024) -> list[int]:
    """Divisors of s usable as an un-padded block dim, largest-friendly:
    multiples of SUBLANE preferred, plus s itself when small."""
    out = [d for d in range(SUBLANE, min(s, cap) + 1, SUBLANE) if s % d == 0]
    if s <= cap:
        out.append(s)
    return sorted(set(out)) or [s]


@functools.lru_cache(maxsize=4096)
def attention_blocks(s_q: int, s_kv: int, d: int,
                     dtype: str = "bf16",
                     backend: str = "pallas") -> tuple[int, int]:
    """(bq, bk) for flash attention.  The kernels index without padding, so
    blocks must DIVIDE the sequence lengths exactly."""
    hit = _hit(f"attn/{s_q}x{s_kv}x{d}/{dtype}/{backend}")
    if hit:
        return hit
    in_bytes = 1 if dtype == "int8" else 2
    best, best_cost = None, float("inf")
    q_tiles, k_tiles = _divisor_tiles(s_q), _divisor_tiles(s_kv)
    for bq in q_tiles:
        for bk in k_tiles:
            c = costmodel.attention_tile_cost(s_q, s_kv, d, bq, bk,
                                              in_bytes=in_bytes)
            if c < best_cost:
                best, best_cost = (bq, bk), c
    if best is None:  # every candidate blew VMEM: take the smallest tiles
        best = (q_tiles[0], k_tiles[0])
    return best


@functools.lru_cache(maxsize=4096)
def attention_pv_blocks(s_q: int, s_kv: int, d: int,
                        backend: str = "pallas") -> tuple[int, int]:
    """(bq, bk) for the int8 attention variant with fused per-(token, head)
    PV dequantization (``attention_i8`` with ``v_scale``).  Its own key
    family — the f32 PV accumulator and scale-vector streams shift the
    optimum away from the plain int8 attention table."""
    hit = _hit(f"attnpv/{s_q}x{s_kv}x{d}/int8/{backend}")
    if hit:
        return hit
    best, best_cost = None, float("inf")
    q_tiles, k_tiles = _divisor_tiles(s_q), _divisor_tiles(s_kv)
    for bq in q_tiles:
        for bk in k_tiles:
            c = costmodel.attention_pv_tile_cost(s_q, s_kv, d, bq, bk)
            if c < best_cost:
                best, best_cost = (bq, bk), c
    if best is None:  # every candidate blew VMEM: take the smallest tiles
        best = (q_tiles[0], k_tiles[0])
    return best


def _tp_suffix(hkv: int, tp: int) -> str:
    """Sharded-key suffix for the serving attention families: empty at
    tp=1 so every pre-TP persisted key keeps resolving unchanged; under
    sharding the kernel sees Hkv/tp heads, a different arithmetic
    intensity, so the measurement must not alias the unsharded one."""
    return f"/h{hkv}tp{tp}" if tp > 1 else ""


@functools.lru_cache(maxsize=4096)
def packed_blocks(t_bucket: int, s_kv: int, d: int, arch: str = "",
                  backend: str = "pallas", hkv: int = 0,
                  tp: int = 1) -> tuple[int, int]:
    """(bq, bk) for the packed serving forward's cache-backed attention:
    a ``t_bucket``-row batch mixing prefill chunk tokens and decode tokens
    against an ``s_kv``-slot cache.  Its own key family — keyed on
    (budget bucket, arch) — because neither the pure-prefill table (square
    causal S x S) nor the pure-decode table (single query row) models a
    short ragged query block against a long position-masked cache.  Under
    serving TP the key gains a shard-local ``/h{Hkv}tp{N}`` suffix
    (``hkv`` is the LOCAL kv-head count the kernel actually sees)."""
    hit = _hit(f"packed/{t_bucket}x{s_kv}x{d}/{arch}/{backend}"
               f"{_tp_suffix(hkv, tp)}")
    if hit:
        return hit
    best, best_cost = None, float("inf")
    q_tiles, k_tiles = _divisor_tiles(t_bucket), _divisor_tiles(s_kv)
    for bq in q_tiles:
        for bk in k_tiles:
            c = costmodel.packed_attention_tile_cost(t_bucket, s_kv, d,
                                                     bq, bk)
            if c < best_cost:
                best, best_cost = (bq, bk), c
    if best is None:  # every candidate blew VMEM: take the smallest tiles
        best = (q_tiles[0], k_tiles[0])
    return best


@functools.lru_cache(maxsize=4096)
def paged_blocks(t_bucket: int, page: int, s_view: int, d: int,
                 arch: str = "", backend: str = "pallas", hkv: int = 0,
                 tp: int = 1) -> tuple[int, int]:
    """(bq, bk) for the paged serving attention: a ``t_bucket``-row packed
    batch against an ``s_view``-slot gathered page view (``page``-slot
    pages).  Its own key family (``paged/{budget}x{page}x{D}``) — the KV
    stream is a page GATHER rather than a dense-span read, so the per-page
    descriptor overhead (costmodel.paged_attention_tile_cost) shifts the
    optimum toward larger page-aligned KV blocks than the ``packed``
    table would pick.  KV candidates are page-aligned: the kernel gathers
    whole pages, and a page-straddling block would split a DMA mid-page.
    Like ``packed_blocks``, serving TP adds a ``/h{Hkv}tp{N}`` key suffix
    keyed on the shard-LOCAL kv-head count."""
    q_tiles = _divisor_tiles(t_bucket)
    k_tiles = [k for k in _divisor_tiles(s_view) if k % page == 0] or [page]
    hit = _hit(f"paged/{t_bucket}x{page}x{d}/{arch}/{backend}"
               f"{_tp_suffix(hkv, tp)}")
    if hit:
        # the persisted key deliberately omits s_view (the family is keyed
        # on the BUCKET shape); a measurement recorded at one view length
        # must still satisfy this call's divisibility invariants, so
        # demote each block to the largest legal tile <= the recorded one
        bq, bk = hit
        if t_bucket % bq:
            bq = max([q for q in q_tiles if q <= bq], default=q_tiles[0])
        if s_view % bk or bk % page:
            bk = max([k for k in k_tiles if k <= bk], default=k_tiles[0])
        return bq, bk
    best, best_cost = None, float("inf")
    for bq in q_tiles:
        for bk in k_tiles:
            c = costmodel.paged_attention_tile_cost(t_bucket, s_view, page,
                                                    d, bq, bk)
            if c < best_cost:
                best, best_cost = (bq, bk), c
    if best is None:  # every candidate blew VMEM: take the smallest tiles
        best = (q_tiles[0], k_tiles[0])
    return best


@functools.lru_cache(maxsize=4096)
def tp_serving_overlap(rows: int, d_model: int, d_ff: int, heads_dim: int,
                       tp: int, backend: str = "pallas") -> str:
    """``"overlap"`` or ``"barrier"`` for the serving-TP row-GEMM boundary
    (dist/tp.py): how a step with ``rows`` packed tokens should rebuild
    full activations in front of the replicated wo/w_out projections.

    Same table-then-measure policy as the tile families, but the decision
    is a two-way CHOICE, not a block tuple: a measured key
    (``tpserve/{rows}x{D}x{FF}x{H}/tp{N}/{backend}``) stores 1 for
    overlap, 0 for barrier; otherwise ``costmodel.tp_boundary_cost`` sums
    the two boundaries a block crosses per step (attention out: heads
    dim -> d_model; MLP out: d_ff -> d_model) under each variant and picks
    the cheaper.  Benchmarks (``e2e/serve_tp*``) measure both variants and
    record the winner, which then drives ``tp_overlap="auto"`` engines.
    """
    if tp <= 1:
        return "barrier"
    hit = _hit(f"tpserve/{rows}x{d_model}x{d_ff}x{heads_dim}"
               f"/tp{tp}/{backend}")
    if hit:
        return "overlap" if hit[0] else "barrier"

    def total(overlap: bool) -> float:
        return (costmodel.tp_boundary_cost(rows, heads_dim, d_model, tp,
                                           overlap)
                + costmodel.tp_boundary_cost(rows, d_ff, d_model, tp,
                                             overlap))

    return "overlap" if total(True) < total(False) else "barrier"


def decode_tiles(s: int) -> list[int]:
    """Legal KV blocks for the int8-KV decode kernel: divisors of the cache
    length S that are also legal lane dims of the slot-position row block
    (multiples of 128, or S itself)."""
    return [t for t in _divisor_tiles(s, cap=2048)
            if t % LANE == 0 or t == s] or [s]


@functools.lru_cache(maxsize=4096)
def decode_blocks(s: int, d: int, g: int) -> int:
    """KV block for the int8-KV decode kernel: one query tile (G, D) stays
    resident; bk is drawn from ``decode_tiles(S)``."""
    hit = _hit(f"decode/{s}x{d}x{g}")
    if hit:
        return hit[0]
    tiles = decode_tiles(s)
    best, best_cost = tiles[0], float("inf")
    for bk in tiles:
        c = costmodel.attention_tile_cost(g, s, d, max(g, 1), bk, in_bytes=1)
        if c < best_cost:
            best, best_cost = bk, c
    return best


PAGED_BLOCK_TOKENS = 256   # tokens a paged decode block aims for
# its double-buffered pages: half of VMEM
PAGED_VMEM_BUDGET = costmodel.TPU_VMEM_BYTES // 2


def _vmem_tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) minor tile as the TPU lays it out: rows
    padded to a power of two of at least the dtype's packing (4 int8 or
    2 bf16 rows to a 32-bit row), at most its full sublane tile, cols to
    the lane width."""
    pack = 4 // itemsize
    sub = min(SUBLANE * pack, max(pack, 1 << (rows - 1).bit_length()))
    return _round_up(rows, sub) * _round_up(cols, LANE) * itemsize


@functools.lru_cache(maxsize=4096)
def paged_decode_pages(ps: int, mp: int, hkv: int, d: int,
                       int8: bool = True) -> int:
    """Pages per block of the paged decode kernel: the largest divisor of
    the table width ``mp`` whose block holds at most
    ``PAGED_BLOCK_TOKENS`` tokens (or one page) and whose double-buffered
    pages fit ``PAGED_VMEM_BUDGET``.  A page is its K and V (ps, Hkv, D),
    their (ps, Hkv, 1) f32 scales with int8 pages, and the (8, ps) rows of
    slot positions that hold its own, each padded as VMEM lays it out; the
    pool's size does not enter.  The (Hq, D) query block stays resident
    whatever the block.  Raises if one page does not fit."""
    page = 2 * ps * _vmem_tile_bytes(hkv, d, 1 if int8 else 2)
    if int8:
        page += 2 * ps * _vmem_tile_bytes(hkv, 1, 4)
    page += _vmem_tile_bytes(SUBLANE, ps, 4)
    fits = [ppb for ppb in range(1, mp + 1)
            if mp % ppb == 0 and (ppb == 1 or ppb * ps <= PAGED_BLOCK_TOKENS)
            and 2 * ppb * page <= PAGED_VMEM_BUDGET]
    if not fits:
        raise ValueError(
            f"one page of the paged decode kernel ({2 * page} bytes "
            f"double-buffered at ps={ps}, Hkv={hkv}, D={d}) exceeds "
            f"{PAGED_VMEM_BUDGET} bytes of VMEM")
    return max(fits)


def elementwise_blocks(m: int, n: int, dtype: str = "int32") -> tuple[int, int]:
    """(bm, bn) for 2-D elementwise kernels (GELU, requantize): tuned row
    block + one lane-width column tile (wrappers pad columns up to it)."""
    return rowwise_blocks(m, n, dtype), LANE


@functools.lru_cache(maxsize=4096)
def rowwise_blocks(m: int, n: int, dtype: str = "int32") -> int:
    """Row block for elementwise/row-reduction kernels (softmax, layernorm,
    GELU, quantize, requantize).  Wrappers pad rows up to the block."""
    hit = _hit(f"rowwise/{m}x{n}/{dtype}")
    if hit:
        return hit[0]
    best, best_cost = SUBLANE, float("inf")
    for bm in (8, 16, 32, 64, 128):
        c = costmodel.rowwise_tile_cost(_round_up(m, SUBLANE), max(n, LANE),
                                        bm)
        # padding waste: rows processed vs rows requested
        c *= _round_up(m, bm) / max(m, 1)
        if c < best_cost:
            best, best_cost = bm, c
    return best
