"""Chip smoke: serve starcoder2-3b at full width on one TPU through the
compiled Pallas kernels, via the same entry point as the serving CLI
(``repro.launch.serve``).

    python chip_smoke.py           # one chip: phases A and B
    python chip_smoke.py --tp4     # four chips: the tensor-parallel phase only

Phase A serves W8A8 weights with an int8 paged KV cache through the packed
step; phase B serves the same requests in bf16 with a dense KV cache.  Both
run starcoder2-3b at its published widths (30 layers, d_model 3072, 24
query / 2 KV heads, d_ff 12288, vocab 49152) with random weights from
``--seed``.  Between them, one fixed packed batch is run through the Pallas
path and through the jnp reference path on the chip, and their last-row
logits are compared.

``--tp4`` serves codeqwen1.5-7b (W8A8, int8 paged KV) at tp=4 with the
barrier and the overlap boundary and at tp=1 on device 0, one engine at a
time, and requires bit-identical greedy token streams.

Any failed check exits non-zero.  Without a TPU the script exits non-zero
before it serves anything: there is no CPU fallback.  The last line of a
passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

# Pallas path vs the jnp reference on the chip, over one fixed packed batch
# (a 64-token prefill on every lane, then one decode token), in fractions
# of the reference's largest |logit|.
#
# Prefill runs the integer kernels (quant_rows, the W8A8 GEMM epilogues,
# layernorm_i8), each the same arithmetic as its jnp oracle, and on CPU
# the two paths give identical logits.  On the chip they differed (0.2177
# at a logit scale of 5.0128): XLA turns a division by a constant into a
# multiply by the f32 reciprocal while Mosaic divides.  Kernels and oracles
# now multiply by that reciprocal themselves (``kernels.common.recip``),
# which should make the chip's prefill bit-identical too; until a chip run
# shows it, the bound admits the gap measured before that repair.  Any
# single int8 rounding flip moves these logits by several percent of their
# scale, so the bound separates exact from inexact, not degrees of error.
PREFILL_REL_TOL = 0.06
# The decode token adds the paged int8 decode kernel, which dequantizes
# the KV cache in f32 where the jnp path rounds K/V to bf16 before
# attending.  W8A8 re-quantizes the attention output to int8, so a
# rounding flip there moves the logits like a prefill flip does: the chip
# gave 4.4% before the prefill repair.  The kernel itself is held tightly
# against its f32 oracle on its own (DECODE_KERNEL_REL_TOL), and the bound
# below is checked to sit well under the logit spread between lanes, which
# is what a decode step reading the wrong cache would show.
DECODE_REL_TOL = 0.1
# paged decode kernel vs its oracle, same f32 inputs at the engine's
# widths, both contracting at f32 precision: summation order and exp
# rounding only (1.1e-6 on CPU).  bf16 passes would give ~1e-3.
DECODE_KERNEL_REL_TOL = 1e-4

STARCODER = ["--arch", "starcoder2-3b", "--lanes", "4", "--token-budget",
             "128", "--max-seq", "512", "--max-new", "32"]
PHASE_A = ["--w8a8", "--int8-kv", "--paged"]
# four equal 32-token prompts fill one 128-token budget: the drain needs
# only the bucket-32 prefill and bucket-1 decode programs per engine, which
# keeps the three engines' lazy compiles short on four chips
CODEQWEN_TP = ["--arch", "codeqwen1.5-7b", "--w8a8", "--int8-kv", "--paged",
               "--lanes", "4", "--token-budget", "128", "--max-seq", "256",
               "--max-new", "16"]


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def config_of(flags: list[str]):
    from repro.configs import get_config
    from repro.launch import serve as launch
    args = launch.build_parser().parse_args(flags)
    return get_config(args.arch, precision=launch.precision_of(args),
                      reduced=args.reduced)


def requests(n: int, lo: int, hi: int, vocab: int, max_new: int,
             seed: int) -> list[dict]:
    import numpy as np
    rng = np.random.default_rng(seed)
    return [dict(prompt=rng.integers(2, vocab, size=int(length)).tolist(),
                 max_new=max_new, request_id=i)
            for i, length in enumerate(rng.integers(lo, hi + 1, size=n))]


def serve_phase(label: str, flags: list[str], reqs: list[dict], seed: int,
                warmup: bool = True, params=None):
    """Build the engine ``flags`` describe and serve ``reqs``; prints the
    phase's lines and returns (engine, serve() result)."""
    import jax
    from repro.launch import serve as launch

    args = launch.build_parser().parse_args(flags + ["--seed", str(seed)])
    t0 = time.perf_counter()
    engine = launch.build_engine(args, params=params)
    jax.block_until_ready(engine.params)
    init_s = time.perf_counter() - t0
    cfg = engine.cfg
    print(f"[{label}] model {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads / {cfg.n_kv_heads} kv heads, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; precision "
          f"{cfg.precision}, kv {'int8' if args.int8_kv else 'bf16'} "
          f"{'paged' if engine.paged else 'dense'}, tp={args.tp}, "
          f"mode={engine.mode}, buckets={engine.chunk_buckets}", flush=True)
    print(f"[{label}] weights made and placed in {init_s:.1f}s", flush=True)
    out = launch.serve(engine, reqs, warmup=warmup)
    done = {d["id"]: d["tokens"] for d in out["done"]}
    check(sorted(done) == sorted(r["request_id"] for r in reqs),
          f"{label}: served {sorted(done)}, submitted "
          f"{[r['request_id'] for r in reqs]}")
    check(all(len(t) >= 1 and all(0 <= x < cfg.vocab_size for x in t)
              for t in done.values()),
          f"{label}: a request finished without valid tokens")
    ntok = out["tokens"]
    compiled = (f"compile (warmup) {out['compile_s']:.1f}s" if warmup else
                "no warmup: compiles fall inside serve time")
    print(f"[{label}] {compiled}; served "
          f"{len(done)} requests, {sum(len(r['prompt']) for r in reqs)} "
          f"prompt tokens, {ntok} new tokens in {out['serve_s']:.2f}s "
          f"({ntok / out['serve_s']:.1f} tok/s)", flush=True)
    print(f"[{label}] {engine.stats_summary()}", flush=True)
    return engine, out


def peak_bytes(label: str) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[{label}] device 0 peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)


def reference_gap(engine, seed: int, t: int = 64) -> None:
    """Last-row logits of the Pallas path vs the jnp reference over one
    fixed packed batch, each compiled over the engine's own weights and KV
    layout (``ServingEngine.fixed_batch_logits``): a ``t``-token prefill
    on every lane, then one decode token."""
    import jax.numpy as jnp
    import numpy as np

    b, vocab = engine.scfg.batch_lanes, engine.cfg.vocab_size
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(2, vocab, (b, t)), jnp.int32)
    batches = [(tokens, jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32),
                                         (b, t))),
               (tokens[:, -1:], jnp.full((b, 1), t, jnp.int32))]
    got, want = (np.asarray(jnp.stack(engine.fixed_batch_logits(
        batches, backend)), np.float32) for backend in ("pallas", "jnp"))
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          "non-finite logits in the reference comparison")
    scale = float(np.abs(want).max())
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    # what a decode step that read another lane's cache would be off by
    spread = float(np.abs(want[1] - np.roll(want[1], 1, axis=0)).max())
    gaps = {}
    for i, (phase, tol) in enumerate((("prefill", PREFILL_REL_TOL),
                                      ("decode", DECODE_REL_TOL))):
        gaps[phase] = float(np.abs(got[i] - want[i]).max())
        same = bool((got[i] == want[i]).all())
        print(f"[reference] fixed packed batch {b}x{t}, {phase}: "
              f"{'bit-identical' if same else 'not bit-identical'}, max "
              f"|pallas - jnp| last-row logit {gaps[phase]!r}, bound "
              f"{tol} x scale {scale!r} = {tol * scale!r}", flush=True)
        check(gaps[phase] <= tol * scale,
              f"{phase}: pallas vs jnp logit gap {gaps[phase]} > "
              f"{tol} x {scale}")
    print(f"[reference] argmax agreement {agree!r}; decode logit spread "
          f"between lanes {spread!r}", flush=True)
    check(spread >= 3 * DECODE_REL_TOL * scale,
          f"decode bound {DECODE_REL_TOL} x {scale} does not separate a "
          f"wrong cache (lane spread {spread})")


def decode_kernel_gap(engine, seed: int) -> None:
    """The paged int8 decode kernel vs its f32 oracle on the chip, at the
    engine's widths and page geometry: every lane holds a full page
    chain, at four different depths."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    cfg, pool = engine.cfg, engine.pool
    b, hq, hkv, d = (engine.scfg.batch_lanes, cfg.n_heads, cfg.n_kv_heads,
                     cfg.head_dim)
    n, ps, mp = pool.n, pool.ps, pool.mp
    rng = np.random.default_rng(seed)
    arena = [jnp.asarray(rng.integers(-127, 128, (n, ps, hkv, d)), jnp.int8)
             if i % 2 == 0 else jnp.asarray(
                 rng.uniform(1e-3, 2e-2, (n, ps, hkv, 1)), jnp.float32)
             for i in range(4)]                      # pk, pks, pv, pvs
    ppos = np.full((n, ps), -1, np.int32)
    ppos[1:1 + b * mp] = np.arange(mp * ps).reshape(mp, ps)[None].repeat(
        b, 0).reshape(b * mp, ps)
    pt = jnp.asarray(1 + np.arange(b * mp).reshape(b, mp), jnp.int32)
    qpos = jnp.asarray(np.linspace(ps, mp * ps - 1, b).astype(np.int32))
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    out = {}
    for backend in ("pallas", "jnp"):
        with ops.use_backend(backend):
            out[backend] = np.asarray(jax.jit(ops.paged_attention_decode)(
                q, *arena, jnp.asarray(ppos), pt, qpos))
    gap = float(np.abs(out["pallas"] - out["jnp"]).max())
    scale = float(np.abs(out["jnp"]).max())
    print(f"[reference] paged int8 decode kernel vs oracle ({b} lanes, "
          f"{hq}/{hkv} heads, d {d}, {mp} pages of {ps}): max gap {gap!r}, "
          f"bound {DECODE_KERNEL_REL_TOL} x scale {scale!r}", flush=True)
    check(gap <= DECODE_KERNEL_REL_TOL * scale,
          f"paged decode kernel vs oracle gap {gap} > "
          f"{DECODE_KERNEL_REL_TOL} x {scale}")


def one_chip(seed: int, base: list[str] = STARCODER) -> None:
    from repro.kernels import autotune

    print(f"tiles chosen by: {autotune.tile_source()}", flush=True)
    reqs = requests(8, 32, 200, config_of(base).vocab_size, 32, seed)

    engine, out_a = serve_phase("A w8a8 int8-paged", base + PHASE_A,
                                reqs, seed)
    for t in (1, engine.chunk_buckets[-1]):
        n = engine.step_hlo(t).count("tpu_custom_call")
        print(f"[A w8a8 int8-paged] packed step bucket {t}: {n} "
              f"tpu_custom_call", flush=True)
        check(n > 0, f"no Pallas kernel in the compiled bucket-{t} step")
    peak_bytes("A w8a8 int8-paged")
    reference_gap(engine, seed)
    decode_kernel_gap(engine, seed)
    del engine, out_a
    gc.collect()

    _, out_b = serve_phase("B bf16 dense", base, reqs, seed)
    peak_bytes("B bf16 dense")


def tp4(seed: int, base: list[str] = CODEQWEN_TP) -> None:
    """codeqwen1.5-7b W8A8: tp=4 barrier, tp=4 overlap and tp=1 on device
    0 must emit bit-identical greedy tokens.  Weights are made once, on the
    host; each engine places its own copy and is freed before the next."""
    from repro.launch.serve import init_serving_params

    cfg = config_of(base)
    t0 = time.perf_counter()
    params = init_serving_params(cfg, seed)
    print(f"[tp4] {cfg.name} {cfg.precision} weights made on the host in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    reqs = requests(4, 32, 32, cfg.vocab_size, 16, seed)
    streams = {}
    for label, extra in (("tp4 barrier", ["--tp", "4", "--tp-overlap",
                                          "barrier"]),
                         ("tp4 overlap", ["--tp", "4", "--tp-overlap",
                                          "overlap"]),
                         ("tp1", [])):
        engine, out = serve_phase(label, base + extra, reqs, seed,
                                  warmup=False, params=params)
        streams[label] = {d["id"]: d["tokens"] for d in out["done"]}
        peak_bytes(label)
        del engine, out
        gc.collect()
    ref = streams["tp1"]
    for label in ("tp4 barrier", "tp4 overlap"):
        same = streams[label] == ref
        print(f"[tp4] {label} vs tp1: token streams "
              f"{'bit-identical' if same else 'DIFFER'}", flush=True)
        if not same:
            for rid in sorted(ref):
                if streams[label].get(rid) != ref[rid]:
                    print(f"[tp4]   request {rid}: {label} "
                          f"{streams[label].get(rid)} vs tp1 {ref[rid]}")
        check(same, f"{label} token streams differ from tp1")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp4", action="store_true",
                    help="run only the four-chip tensor-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend "
              f"{devices[0].platform!r}); nothing is served without one",
              file=sys.stderr)
        return 2
    if args.tp4 and len(devices) < 4:
        print(f"chip_smoke: --tp4 needs 4 TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    try:
        (tp4 if args.tp4 else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
