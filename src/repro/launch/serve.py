"""Serving launcher: packed token-budget forward with the
continuous-batching engine (chunked / tokenwise schedules as fallbacks).

``build_parser`` / ``build_engine`` / ``serve`` are the serving entry
point itself: the CLI below and ``chip_smoke.py`` both go through them,
so the chip proof runs exactly the set-up a user runs.  On a TPU the
engine serves through the compiled Pallas kernels (the kernel backend
follows the platform, ``kernels/ops.py``).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --reduced \
      --requests 16 --max-new 32 --int8-kv --token-budget 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..dist.sharding import AxisEnv, set_axis_env
from ..models import init_params
from ..models.config import ArchConfig
from ..models.frontend import vision_tokens_stub
from ..models.layers import DEFAULT_DTYPE
from ..quant import ptq_quantize_params
from ..quant.ptq import DEFAULT_W4_POLICY
from ..serve import ServeConfig, ServingEngine
from .compile_cache import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--w8a8", action="store_true")
    ap.add_argument("--w4a8", action="store_true",
                    help="packed-int4 GEMM weights (group-wise scales, "
                         "in-kernel dequant; attn/mlp projections int4, "
                         "lm head int8 — see docs/quantization.md)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--token-budget", type=int, default=32,
                    help="per-iteration packed-step token budget "
                         "(0 = disable packing; see --prefill-chunk)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="chunked-mode cap when --token-budget is 0 "
                         "(0 = legacy token-at-a-time prompt feed)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV pool with shared-prefix reuse "
                         "(docs/serving.md; falls back to dense caches for "
                         "recurrent/cross-attention archs)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="physical KV pool pages (paged mode; 0 = auto-size "
                         "for the lane count, >0 may force preemption + "
                         "page swap under load)")
    ap.add_argument("--queue-limit", type=int, default=0,
                    help="admission queue bound (0 = unbounded); submits "
                         "beyond it are rejected explicitly")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="self-speculative draft tokens per decode step "
                         "(prompt-lookup drafts verified in one packed "
                         "forward; greedy engines only — bit-identical "
                         "output at any k, see docs/serving.md)")
    ap.add_argument("--tp", type=int, default=1,
                    help="serving tensor parallel: shard the packed step + "
                         "KV page payloads over N devices (docs/sharding.md; "
                         "bit-identical to --tp 1; on CPU emulate devices "
                         "with XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N before launch)")
    ap.add_argument("--tp-overlap", default="auto",
                    choices=("auto", "overlap", "barrier"),
                    help="TP row-GEMM boundary: barrier = all-gather then "
                         "full GEMM; overlap = all-to-all token split so "
                         "the fused epilogue consumes shards as they "
                         "arrive; auto = autotune table-then-measure")
    ap.add_argument("--stream-gap-ms", type=float, default=0.0,
                    help="mean Poisson inter-arrival gap in ms; >0 switches "
                         "from offline drain to the timed run_stream front "
                         "end and prints TTFT/TPOT percentiles")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def precision_of(args) -> str:
    if args.w8a8 and args.w4a8:
        raise ValueError("--w8a8 and --w4a8 are exclusive")
    return "w4a8" if args.w4a8 else "w8a8" if args.w8a8 else "bf16"


def init_serving_params(cfg: ArchConfig, seed: int) -> dict:
    """Random weights from ``seed`` at ``cfg.precision``.  Each block is
    made and quantized on the default device, then moved to host memory:
    the full float tree never exists on a device (a 7B model's bf16
    weights alone fill a 16 GB chip).  Weight matrices that stay float
    (embeddings, bf16 projections) are served in the compute dtype, not as
    f32 master copies.  The engine places the result."""
    policy = {"w8a8": None, "w4a8": DEFAULT_W4_POLICY}

    def serving_dtype(x):
        if x.ndim >= 2 and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(DEFAULT_DTYPE)
        return x

    def finish(tree):
        if cfg.precision in policy:
            tree = ptq_quantize_params(tree, policy=policy[cfg.precision])
        return jax.device_get(jax.tree.map(serving_dtype, tree))

    return init_params(jax.random.PRNGKey(seed), cfg, finish=finish)


def build_engine(args, params: dict | None = None) -> ServingEngine:
    """The engine ``args`` (from ``build_parser``) describe.  Weights are
    ``params`` when given (host-resident, as ``init_serving_params`` makes
    them, so that several engines can share one copy), else made from
    ``args.seed``."""
    cfg = get_config(args.arch, precision=precision_of(args),
                     reduced=args.reduced)
    set_axis_env(AxisEnv())
    if params is None:
        params = init_serving_params(cfg, args.seed)
    kv_source = None
    if cfg.family == "vlm":
        kv_source = vision_tokens_stub(jax.random.PRNGKey(args.seed),
                                       args.lanes, cfg.n_vision_tokens,
                                       cfg.d_model)
    return ServingEngine(
        params, cfg,
        ServeConfig(batch_lanes=args.lanes, max_seq=args.max_seq,
                    int8_kv=args.int8_kv, temperature=args.temperature,
                    token_budget=args.token_budget,
                    prefill_chunk=args.prefill_chunk, seed=args.seed,
                    paged=args.paged, page_size=args.page_size,
                    pool_pages=args.pool_pages,
                    queue_limit=args.queue_limit, spec_k=args.spec_k,
                    tp=args.tp, tp_overlap=args.tp_overlap),
        kv_source=kv_source)


def make_requests(args, vocab_size: int) -> list[dict]:
    """The CLI's traffic: ``args.requests`` random prompts of 4-11 tokens."""
    rng = np.random.default_rng(args.seed)
    return [dict(prompt=rng.integers(2, vocab_size,
                                     size=rng.integers(4, 12)).tolist(),
                 max_new=args.max_new, request_id=i)
            for i in range(args.requests)]


def serve(engine: ServingEngine, reqs: list[dict], warmup: bool = True,
          stream_gap_ms: float = 0.0, seed: int = 0) -> dict:
    """Compile (``engine.warmup``, timed apart), then serve ``reqs``: an
    offline drain, or timed Poisson arrivals when ``stream_gap_ms`` > 0.
    Returns the finished records, rejected ids and both timings."""
    t0 = time.perf_counter()
    if warmup:
        engine.warmup()
    t1 = time.perf_counter()
    rejected: list = []
    if stream_gap_ms > 0:
        rng = np.random.default_rng(seed)
        offs = np.cumsum(rng.exponential(stream_gap_ms / 1e3,
                                         size=len(reqs)))
        done, rejected = engine.run_stream(
            [(float(t), kw) for t, kw in zip(offs, reqs)])
    else:
        for kw in reqs:
            engine.submit(**kw)
        done = engine.run_until_drained()
    t2 = time.perf_counter()
    return {"done": done, "rejected": rejected, "compile_s": t1 - t0,
            "serve_s": t2 - t1,
            "tokens": sum(len(d["tokens"]) for d in done)}


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    engine = build_engine(args)
    if args.tp > 1:
        print(f"tensor parallel: tp={args.tp} over "
              f"{[str(d) for d in engine.tp_mesh.devices.flat]} "
              f"(boundary={engine.tp_overlap_resolved})")
    out = serve(engine, make_requests(args, engine.cfg.vocab_size),
                stream_gap_ms=args.stream_gap_ms, seed=args.seed)
    if out["rejected"]:
        print(f"rejected at admission (queue_limit="
              f"{args.queue_limit}): {out['rejected']}")
    dt = out["serve_s"]
    print(f"compiled in {out['compile_s']:.1f}s")
    print(f"served {len(out['done'])} requests, {out['tokens']} tokens "
          f"in {dt:.1f}s ({out['tokens'] / dt:.1f} tok/s, "
          f"int8_kv={args.int8_kv}, precision={engine.cfg.precision}, "
          f"mode={engine.mode}, paged={engine.paged}, "
          f"buckets={engine.chunk_buckets})")
    print(engine.stats_summary())
    m = engine.serving_metrics()
    print(f"queue wait p50/p99 = {m['queue_wait_p50_ms']}/"
          f"{m['queue_wait_p99_ms']} ms (TTFT = queue wait + prefill)")
    if args.stream_gap_ms > 0:
        print(f"ttft p50/p99 = {m['ttft_p50_ms']}/{m['ttft_p99_ms']} ms, "
              f"tpot p50/p99 = {m['tpot_p50_ms']}/{m['tpot_p99_ms']} ms, "
              f"queue_peak={m['queue_peak']} preempt={m['preemptions']} "
              f"swap_pages={m['swap_out_pages']}/{m['swap_in_pages']} "
              f"rejected={m['rejected']}")


if __name__ == "__main__":
    main()
