"""The control of a cell's correctness check: the same run with the
program's own lower-precision path switched on (W4A8, packed int4 weights,
in place of the W8A8 the configuration states).  Its ``check`` has to come
out past the limit, which ``bench/tests/test_bench_harness.py`` shows at a
tiny size on the CPU.  On the chip:

    python bench/control.py --workload <name> --seed <n> --seconds <s>

prints the result line of ``bench/run.py``; the driver never runs it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402

if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    run._environment()
    import jax
    run.configure_cache(jax)
    import harness
    out = harness.run_cell(run.ROOT, args.workload, args.seed, args.seconds,
                           False, T_START, precision="w4a8")
    print(json.dumps(out), flush=True)
    sys.exit(0)
