"""Benchmark driver: one function per paper table + kernel/e2e benches.

Prints human tables + ``name,us_per_call,derived`` CSV AND persists two
machine-readable artifacts at the repo root so every PR has a perf
trajectory to regress against:

  BENCH_kernels.json  kernel micro-bench rows  {name: {us, work}}
  BENCH_e2e.json      e2e / paper-table rows   {name: {us, work}}

Keys are stable across runs (fixed RNG seed, shape- and backend-suffixed
names); compare two checkouts with a plain JSON diff.  ``--smoke`` runs a
~30 s subset that only ADDS never-measured keys — it never overwrites an
existing entry, so gating runs (scripts/verify.sh) cannot pollute the
trajectory a full run established.  Smoke runs also SKIP (rather than
fail) kernel families that are unavailable on the requested backend
(kernel_bench runs non-strict under --smoke): a family that only exists
for one backend must not break the other backend's CI gate — the merge
semantics keep its committed keys either way.

``--history`` additionally appends one JSON line per FULL run to
``BENCH_history.jsonl`` carrying the gate-relevant keys (decode ladder,
stream TTFT, spec payoff, serve_tp overlap-vs-barrier) stamped with the
commit — the across-run trajectory the single merged artifact cannot
show (it only keeps the latest number per key).  Smoke runs never
append: their numbers are gates, not measurements.

Usage: PYTHONPATH=src python benchmarks/run.py [--smoke] [--backend jnp]
                                               [--history]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _write_json(path: str, rows: list[tuple], meta: dict,
                smoke: bool, backend: str | None = None) -> None:
    entries = {name: {"us": round(us, 1), "work": derived}
               for name, us, derived in rows}
    full = os.path.join(REPO_ROOT, path)
    prev = {}
    if os.path.exists(full):
        try:
            with open(full) as f:
                prev = json.load(f).get("entries", {})
        except ValueError:
            prev = {}
    if smoke:
        # a smoke run is a gate, not a measurement: it only fills keys that
        # have never been measured, never overwrites a full run's numbers
        entries = {**entries, **prev}
    else:
        # schema stability: a FULL run must re-measure every key the
        # trajectory already has (else the merge below would silently
        # resurrect a stale value for a renamed/dropped bench forever).
        # Exempt: roofline/* rows (exist only when dry-run artifacts are
        # present on this checkout) and, when ``backend`` is given,
        # backend-suffixed keys from OTHER backends — a pallas run cannot
        # and must not re-measure the /jnp key family.
        def exempt(k: str) -> bool:
            if k.startswith("roofline/"):
                return True
            suffix = k.rsplit("/", 1)[-1]
            return (backend is not None and suffix in ("jnp", "pallas")
                    and suffix != backend)

        missing = sorted(k for k in set(prev) - set(entries)
                         if not exempt(k))
        if missing:
            raise SystemExit(
                f"BENCH schema regression: {path} lost keys {missing}")
        entries = {**prev, **entries}
    with open(full, "w") as f:
        json.dump(dict(meta, entries=entries), f, indent=1, sort_keys=True)
    print(f"wrote {path} ({len(entries)} entries)")


def _decode_perf_gate(path: str) -> None:
    """Perf regression gate (ROADMAP): w8a8 decode must stay FASTER than
    bf16 decode for every arch pair the artifact tracks — the whole point
    of the int8 serving path — and w4a8 decode must stay faster than its
    w8a8 twin (the packed weight stream has to pay for its in-kernel
    unpack, or the format is dead weight; e2e_bench times the twins
    interleaved so the few-percent margin is load-noise-proof).  Reads the
    final merged artifact so smoke runs gate against the committed
    trajectory too; prints the headroom so regressions are visible before
    they flip the sign.
    """
    with open(os.path.join(REPO_ROOT, path)) as f:
        entries = json.load(f).get("entries", {})
    ladders = [("_bf16", "_w8a8", "the w8a8 decode path lost its edge"),
               ("_w8a8", "_w4a8", "the packed-int4 weight path no longer "
                                  "pays for its unpack")]
    seen = 0
    for base_sfx, fast_sfx, why in ladders:
        pairs = [(k, k[: -len(base_sfx)] + fast_sfx) for k in entries
                 if k.startswith("e2e/decode_") and k.endswith(base_sfx)
                 and k[: -len(base_sfx)] + fast_sfx in entries]
        seen += len(pairs)
        for bkey, wkey in sorted(pairs):
            b_us, w_us = entries[bkey]["us"], entries[wkey]["us"]
            ratio = b_us / max(w_us, 1e-9)
            print(f"decode gate: {wkey} {w_us}us vs {bkey} {b_us}us "
                  f"({ratio:.1f}x headroom)")
            if w_us >= b_us:
                raise SystemExit(
                    f"PERF regression: {wkey} ({w_us}us) is not faster "
                    f"than {bkey} ({b_us}us) — {why}")
    if not seen:
        print("decode gate: no decode pairs in artifact (fresh checkout)")


def _stream_ttft_gate(path: str) -> None:
    """Overload-robustness gate: under the sustained Poisson workload,
    paged serving WITH memory pressure (preempt + swap-to-host on a tiny
    pool) must keep p99 TTFT within 25% of paged serving without pressure
    — swap is allowed to cost something, but not to blow the tail latency
    the front end exists to bound.  Same merged-artifact semantics as the
    decode gate, so smoke runs enforce it against the committed numbers.
    """
    with open(os.path.join(REPO_ROOT, path)) as f:
        entries = json.load(f).get("entries", {})
    suffix = "_paged_swap"
    pairs = [(k[: -len(suffix)] + "_paged", k) for k in entries
             if k.startswith("e2e/serve_stream_") and k.endswith(suffix)
             and k[: -len(suffix)] + "_paged" in entries]
    for pkey, skey in sorted(pairs):
        p_us, s_us = entries[pkey]["us"], entries[skey]["us"]
        ratio = s_us / max(p_us, 1e-9)
        print(f"stream gate: {skey} p99 TTFT {s_us}us vs {pkey} {p_us}us "
              f"({ratio:.2f}x, limit 1.25x)")
        if s_us > 1.25 * p_us:
            raise SystemExit(
                f"PERF regression: {skey} p99 TTFT ({s_us}us) exceeds "
                f"1.25x {pkey} ({p_us}us) — preempt/swap overhead is no "
                f"longer bounded")
    if not pairs:
        print("stream gate: no serve_stream pairs in artifact "
              "(fresh checkout)")


def _spec_gate(path: str) -> None:
    """Speculation-payoff gate: on the repetition-heavy serve_spec
    workload, every spec_k>1 row must be at least as fast (us/token) as
    the k=1 row — if drafting deeper than one token ever LOSES to the
    single-draft baseline there, the verify-row/rollback overhead has
    outgrown the accepted-token win and the feature is regressing on the
    very traffic it exists for.  (k=1 vs k=0 is not gated: vanilla wins
    on repetition-free traffic by construction — speculation is opt-in.)
    Same merged-artifact semantics as the other gates.
    """
    with open(os.path.join(REPO_ROOT, path)) as f:
        entries = json.load(f).get("entries", {})
    bases = [k for k in entries
             if k.startswith("e2e/serve_spec_") and k.endswith("_k1")]
    pairs = [(b, k) for b in bases for k in entries
             if k.startswith(b[:-len("_k1")] + "_k") and k != b]
    for bkey, kkey in sorted(pairs):
        b_us, k_us = entries[bkey]["us"], entries[kkey]["us"]
        ratio = b_us / max(k_us, 1e-9)
        print(f"spec gate: {kkey} {k_us}us vs {bkey} {b_us}us "
              f"({ratio:.2f}x speedup)")
        if k_us > b_us:
            raise SystemExit(
                f"PERF regression: {kkey} ({k_us}us/token) loses to "
                f"{bkey} ({b_us}us/token) on the repetition-heavy "
                f"workload — speculative overhead outgrew its win")
    if not pairs:
        print("spec gate: no serve_spec pairs in artifact (fresh checkout)")


def _tp_overlap_gate(path: str) -> None:
    """Overlap-payoff gate: for every TP degree the artifact tracks, the
    collective/epilogue-overlap variant of the sharded packed step must be
    at least as fast (us/token) as its barrier twin — the split boundary
    exists purely to hide the post-attention collective behind the fused
    epilogue, so the moment it LOSES to the plain gather-then-compute
    boundary it is dead weight (tp_bench times the twins interleaved on
    the same emulated mesh, min-of-N, so the margin is load-noise-proof).
    Same merged-artifact semantics as the other gates.
    """
    with open(os.path.join(REPO_ROOT, path)) as f:
        entries = json.load(f).get("entries", {})
    marker = "_overlap_"
    pairs = [(k.replace(marker, "_barrier_"), k) for k in entries
             if k.startswith("e2e/serve_tp") and marker in k
             and k.replace(marker, "_barrier_") in entries]
    for bkey, okey in sorted(pairs):
        b_us, o_us = entries[bkey]["us"], entries[okey]["us"]
        ratio = b_us / max(o_us, 1e-9)
        print(f"tp gate: {okey} {o_us}us vs {bkey} {b_us}us "
              f"({ratio:.2f}x speedup)")
        if o_us > b_us:
            raise SystemExit(
                f"PERF regression: {okey} ({o_us}us/token) loses to "
                f"{bkey} ({b_us}us/token) — the split collective no "
                f"longer hides behind the fused epilogue")
    if not pairs:
        print("tp gate: no serve_tp pairs in artifact (fresh checkout)")


# key families the perf gates above read — exactly these go to history
GATE_FAMILIES = ("e2e/decode_", "e2e/serve_stream_", "e2e/serve_spec_",
                 "e2e/serve_tp")


def _append_history(path: str, smoke: bool) -> None:
    """Append this run's gate-relevant rows as one JSON line (schema 1:
    ts/commit/rows) to BENCH_history.jsonl.  Full runs only — a smoke run
    re-gates committed numbers rather than measuring new ones, and a
    trajectory of repeated baselines is noise."""
    if smoke:
        print("history: smoke run, not appending (gates, not measurements)")
        return
    with open(os.path.join(REPO_ROOT, path)) as f:
        entries = json.load(f).get("entries", {})
    rows = {k: v["us"] for k, v in sorted(entries.items())
            if k.startswith(GATE_FAMILIES)}
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, check=True, cwd=REPO_ROOT).stdout.strip()
    except (subprocess.CalledProcessError, OSError):
        commit = "unknown"
    line = {"schema": 1, "ts": round(time.time(), 3), "commit": commit,
            "rows": rows}
    hist = os.path.join(REPO_ROOT, "BENCH_history.jsonl")
    with open(hist, "a") as f:
        f.write(json.dumps(line, sort_keys=True) + "\n")
    print(f"history: appended {len(rows)} gate rows @ {commit} "
          f"to BENCH_history.jsonl")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="~30 s subset; writes the same BENCH_*.json files")
    ap.add_argument("--backend", choices=["jnp", "pallas"], default="jnp")
    ap.add_argument("--history", action="store_true",
                    help="append this full run's gate-relevant rows to "
                         "BENCH_history.jsonl (no-op under --smoke)")
    args = ap.parse_args()

    from benchmarks import cgra_tables, e2e_bench, kernel_bench, tp_bench
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    # smoke implies non-strict (kernel_bench's default): unavailable kernel
    # families are skipped, not fatal
    kernel_rows = kernel_bench.run(backend=args.backend, smoke=args.smoke)

    e2e_rows = []
    e2e_rows += cgra_tables.table_vi()
    if not args.smoke:
        e2e_rows += cgra_tables.table_v()
        e2e_rows += cgra_tables.table_ii()
        e2e_rows += cgra_tables.table_iii_iv()
    e2e_rows += e2e_bench.run(smoke=args.smoke)
    e2e_rows += tp_bench.run(smoke=args.smoke)

    print("\nname,us_per_call,derived")
    for name, us, derived in kernel_rows + e2e_rows:
        print(f"{name},{us:.1f},{derived}")

    meta = {"schema": 1, "seed": kernel_bench.SEED}
    _write_json("BENCH_kernels.json", kernel_rows, meta, smoke=args.smoke,
                backend=args.backend)
    _write_json("BENCH_e2e.json", e2e_rows, meta, smoke=args.smoke)
    _decode_perf_gate("BENCH_e2e.json")
    _stream_ttft_gate("BENCH_e2e.json")
    _spec_gate("BENCH_e2e.json")
    _tp_overlap_gate("BENCH_e2e.json")
    if args.history:
        _append_history("BENCH_e2e.json", smoke=args.smoke)


if __name__ == "__main__":
    main()
