"""Chip benchmark of the serving engine: one run of one cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout.  The cell is an entry of BENCHMARK.json's
``workloads``; its configuration, the model family that configuration
names, its traffic mix, its metrics and the costs of the kernels the
trace reduction counts are files under ``bench/`` found by name
(``bench/harness.py``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(with ``--trace 1`` also ``breakdown``) and last ``check``, each number
compared with the plain reference beside its limit.  Without an
accelerator, or with fewer chips than the cell asks for, it exits 3 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _environment() -> None:
    """Set before JAX loads: the compile cache at a fixed path inside the
    checkout (the program's launchers take it from this variable), every
    program cached, and the program's sources on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]


def configure_cache(jax) -> None:
    """Every program goes to the persistent cache, however quick its
    compile, so that a second run in the checkout compiles nothing."""
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _environment()

    import jax
    configure_cache(jax)
    import harness

    bench = harness.load_benchmark(ROOT)
    cell = harness.workload(bench, args.workload)
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell["chips"]:
        harness.log(f"needs {cell['chips']} accelerator chip(s); JAX has "
                    f"{len(devices)} {devices[0].platform} device(s)")
        return 3
    out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START, bench=bench)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
