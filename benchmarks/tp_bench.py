"""Tensor-parallel serving bench: tp in {1, 2, 4, 8} x {barrier, overlap}.

The packed engine drains identical greedy traffic through every TP degree
and boundary variant on an EMULATED 8-device CPU mesh.  Device emulation
forces a subprocess: the XLA host-platform device count locks at first
jax init, and the parent bench process has already initialized jax with
one device.  The worker (``--worker``) sets the flag, runs the matrix
interleaved (round-robin across engines per round, min-of-N timed rounds
after an untimed warm rehearsal), re-proves bit-identity across ALL
engines in passing, and emits rows as JSON on the last stdout line.

The workload is prefill-heavy (token_budget 128, prompts up to 120
tokens) so the row-scaled work the overlap variant saves is the
dominant term: on the single-core emulated mesh all tp devices
serialize, so the barrier variant pays tp x the wo/w_out row-GEMM
FLOPs while overlap pays 1x plus a fixed number of extra collective
dispatches — exactly the trade ``run.py``'s ``_tp_overlap_gate`` gates
(overlap must never lose to barrier at the same tp).  The model dims
are pinned to d_model = d_ff = 128: XLA CPU's GEMM changes its
K-accumulation order with the OUTPUT width at some row counts when the
contraction dim is 256+ (a full-width dot stops matching its column
shards bit-for-bit — e.g. M=128, K=256, N=256 diverges at tp=2), while
every K=128 sharded dot matches its shards at all row counts and
degrees.  The bit-identity assertion below re-proves it per run.

Rows:
  e2e/serve_tp1_<arch>-reduced_bf16
  e2e/serve_tp{2,4,8}_{barrier,overlap}_<arch>-reduced_bf16
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

ARCH = "codeqwen1.5-7b"
DEGREES = (2, 4, 8)
_MARK = "TPBENCH_ROWS:"


def run(smoke: bool = False) -> list[tuple]:
    """Spawn the emulated-mesh worker and collect its rows.  The worker is
    pinned to the CPU platform: on a chip host the parent may hold the
    TPU, and a child that opened it would fail or hang."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    cmd = [sys.executable, os.path.abspath(__file__), "--worker"]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"tp_bench worker failed (rc={proc.returncode}):\n"
            f"{proc.stdout}\n{proc.stderr}")
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            return [tuple(r) for r in json.loads(line[len(_MARK):])]
    raise RuntimeError(f"tp_bench worker emitted no rows:\n{proc.stdout}")


def _worker(smoke: bool) -> None:
    import dataclasses
    import time

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve import ServeConfig, ServingEngine

    assert len(jax.devices()) >= 8, jax.devices()
    # d_model/d_ff pinned to 128: K=256 contractions change their
    # K-accumulation order with the output width at some row counts on
    # the CPU backend (column shards stop matching the full dot); every
    # K=128 sharded dot is exact at all row counts and degrees
    cfg = dataclasses.replace(
        get_config(ARCH, reduced=True),
        n_heads=8, n_kv_heads=8, d_head=16, d_model=128, d_ff=128)
    params = init_params(jax.random.PRNGKey(0), cfg)

    def make(tp: int, overlap: str) -> ServingEngine:
        # a BIG packed budget: the gate's signal is the row count — the
        # barrier variant's redundant row-GEMM work grows with rows while
        # the overlap variant's extra collective dispatches do not, so
        # prefill-heavy 128-row steps are where overlap earns its keep
        return ServingEngine(params, cfg, ServeConfig(
            batch_lanes=4, max_seq=384, token_budget=256,
            tp=tp, tp_overlap=overlap))

    engines = {"tp1": make(1, "barrier")}
    for tp in DEGREES:
        for overlap in ("barrier", "overlap"):
            engines[f"tp{tp}_{overlap}"] = make(tp, overlap)

    # prefill-heavy traffic: long prompts, short completions, so most
    # steps run full 128-row buckets (the regime the overlap boundary
    # targets); decode steps at 4-8 rows amortize nothing and would
    # drown the signal in per-dispatch overhead
    rng = np.random.default_rng(11)
    lens = [240, 320, 192, 288]
    reqs = [(rng.integers(2, cfg.vocab_size, size=lens[i % len(lens)])
             .tolist(), i) for i in range(8)]

    rounds = 2 if smoke else 4                   # round 0 = untimed warmup
    best = {k: float("inf") for k in engines}
    toks, outs = {}, {}
    for rnd in range(rounds):
        for name, eng in engines.items():
            for prompt, rid in reqs:
                eng.submit(list(prompt), max_new=4, request_id=rid)
            t0 = time.time()
            done = eng.run_until_drained()
            dt = time.time() - t0
            outs[name] = {d["id"]: d["tokens"] for d in done}
            toks[name] = sum(len(d["tokens"]) for d in done)
            eng.finished.clear()
            if rnd:
                best[name] = min(best[name], dt)
    for name in engines:
        assert outs[name] == outs["tp1"], \
            f"{name} diverged from tp1 (bit-identity broken)"

    rows = []
    for name in engines:
        us = best[name] / max(toks[name], 1) * 1e6
        vs = ""
        if name.endswith("_overlap"):
            barrier = best[name.replace("_overlap", "_barrier")]
            vs = f";vs_barrier={barrier / max(best[name], 1e-9):.2f}x"
        rows.append((f"e2e/serve_{name.split('_')[0]}"
                     + (f"_{name.split('_', 1)[1]}" if "_" in name else "")
                     + f"_{ARCH}-reduced_bf16",
                     us,
                     f"tok_s={toks[name] / best[name]:.1f};"
                     f"requests={len(reqs)}{vs}"))
    print(_MARK + json.dumps(rows))


if __name__ == "__main__":
    if "--worker" in sys.argv:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "src"))
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        _worker("--smoke" in sys.argv)
    else:
        for r in run(smoke=True):
            print(r)
