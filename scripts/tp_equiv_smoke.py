"""CI smoke: serving tensor parallel must be a LAYOUT change only.

Spins up 8 emulated CPU devices (the platform pin and the XLA
host-platform flag below must be set before jax initializes) and drains
identical workloads through the packed engine at tp=1 (the plain jit),
tp=8 barrier, and tp=8 overlap,
asserting bit-identical token streams across all three.  Settings cover
the matrix the sharded step must survive: greedy and sampled, dense and
paged KV, spec_k in {0, 4}, and a tiny-pool run where preemption + KV
page swap actually fire (asserted — the identity must be proved on the
live swap-out/swap-in round trip, not on an unpressured drain).

Usage: PYTHONPATH=src python scripts/tp_equiv_smoke.py
"""
import dataclasses
import itertools
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import get_config
from repro.models import init_params
from repro.serve import ServeConfig, ServingEngine

# cyclic prompts so the n-gram proposer engages under spec_k > 0
PROMPTS = [([5, 6, 7, 8] * 6)[:20], ([11, 12, 13] * 7)[:18],
           ([3, 4] * 8)[:14], [9, 3, 11, 4, 2, 30, 31]]

# (label, ServeConfig kwargs, require): the pressure run packs 4
# speculating lanes onto a pool too small for them, forcing preempt +
# swap mid-drain
SETTINGS = [
    ("greedy/dense/k0", dict(), ()),
    ("greedy/paged/k4/pressure",
     dict(batch_lanes=4, token_budget=16, paged=True, page_size=8,
          pool_pages=8, spec_k=4),
     ("preemptions", "resumes", "swap_in_pages", "spec_accepted")),
    ("sampled/paged/k0", dict(paged=True, page_size=8, temperature=0.8), ()),
    ("greedy/paged/k0", dict(paged=True, page_size=8), ()),
]


def run(cfg, params, kwargs, tp: int, overlap: str):
    kwargs = {**dict(batch_lanes=2, max_seq=64, token_budget=8), **kwargs}
    eng = ServingEngine(params, cfg,
                        ServeConfig(tp=tp, tp_overlap=overlap, **kwargs))
    eng._clock = itertools.count().__next__   # decouple stats from wall time
    for i, p in enumerate(PROMPTS):
        eng.submit(list(p), max_new=12, request_id=i)
    toks = {d["id"]: d["tokens"] for d in eng.run_until_drained()}
    return toks, eng.stats


def main() -> None:
    n = len(jax.devices())
    if n < 8:
        print(f"FAIL: expected 8 emulated devices, got {n} (XLA_FLAGS "
              f"must be set before jax initializes)", file=sys.stderr)
        raise SystemExit(1)
    cfg = dataclasses.replace(get_config("codeqwen1.5-7b", reduced=True),
                              n_heads=8, n_kv_heads=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    for label, kwargs, require in SETTINGS:
        want, ref_st = run(cfg, params, kwargs, 1, "barrier")
        for overlap in ("barrier", "overlap"):
            got, st = run(cfg, params, kwargs, 8, overlap)
            if got != want:
                print(f"FAIL: {label} tp=8 {overlap} diverges from tp=1:\n"
                      f"  tp=8: {got}\n  tp=1: {want}", file=sys.stderr)
                raise SystemExit(1)
            for stat in require:
                if st[stat] <= 0:
                    print(f"FAIL: {label} tp=8 {overlap}: {stat}=0 — the "
                          f"pressure run never exercised preempt/swap/"
                          f"speculation under sharding", file=sys.stderr)
                    raise SystemExit(1)
        for stat in require:
            if ref_st[stat] <= 0:
                print(f"FAIL: {label} tp=1 reference: {stat}=0",
                      file=sys.stderr)
                raise SystemExit(1)
        print(f"  {label}: tp=1 == tp=8(barrier) == tp=8(overlap)"
              + (f" [{', '.join(f'{s}={ref_st[s]}' for s in require)}]"
                 if require else ""))
    print("TP equivalence OK: 4 settings x (tp=1, tp=8 barrier, tp=8 "
          "overlap) bit-identical, preempt+swap+speculation live under "
          "sharding")


if __name__ == "__main__":
    main()
