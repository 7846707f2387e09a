"""The benchmark's own tests, on the CPU at tiny widths: they put
``bench/`` and the program's sources on the path, and build a directory of
configurations, families, traffic mixes, metric readers and kernel costs
for the harness to find by name."""
import os
import shutil
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def files(tmp_path):
    """configs/, families/, traffic/, metrics/ and kernels/ as the harness
    finds them: the committed families, metric readers and kernel costs,
    the tiny test configurations and mixes."""
    for sub in ("families", "metrics", "kernels"):
        shutil.copytree(os.path.join(BENCH, sub), tmp_path / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic"):
        shutil.copytree(os.path.join(TESTS, "data", sub), tmp_path / sub)
    return tmp_path


def tiny_bench(config: str, traffic: str, extra_per_layer=()) -> dict:
    """A BENCHMARK.json-shaped dict with one cell named "cell"."""
    e2e = ["tokens_per_s", "setup_s"]
    per = ["batch_occupancy", "decode_step_ms", "step_mfu",
           *extra_per_layer]
    return {"workloads": [{"name": "cell", "config": config,
                           "traffic": traffic, "chips": 1}],
            "end_to_end": [{"name": n, "unit": "x"} for n in e2e],
            "per_layer": [{"name": n, "unit": "x"} for n in per]}
