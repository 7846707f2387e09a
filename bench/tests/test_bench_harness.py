"""Whole runs of the harness on the CPU at tiny widths, past its look for
a chip: the timed path compared with the plain reference decides
``correct``; the lower-precision control and the faults a serving cell can
have (a step that returns its state unchanged, a token altered where it
is produced) come out not correct; a new traffic mix and a new metric are
found by name from their files alone."""
import json
import os
import subprocess
import sys
import time

import pytest

import harness
import model
from conftest import BENCH, ROOT, tiny_bench

SEED = 2 ** 31 + 4242
CONFIG = "tiny-starcoder2"


def run(files, config=CONFIG, traffic="tiny-offline", seconds=2.0,
        seed=SEED, **kw):
    return harness.run_cell(str(files), "cell", seed, seconds, False,
                            time.perf_counter(),
                            bench=tiny_bench(config, traffic),
                            files=str(files), **kw)


@pytest.mark.parametrize("config,traffic", [
    ("tiny-starcoder2", "tiny-offline"), ("tiny-qwen2", "tiny-offline")])
def test_timed_path_matches_the_reference(files, config, traffic):
    out = run(files, config, traffic)
    assert out["correct"], out["check"]
    assert out["check"]["requests_checked"]["value"] >= 4
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "check"


def test_control_lower_precision_is_not_correct(files):
    """The program's own W4A8 path in place of W8A8 reads past the limit."""
    out = run(files, precision="w4a8")
    assert not out["correct"], out["check"]


def _patched_engine(monkeypatch, patch):
    build = model.build_engine

    def build_broken(*a, **k):
        engine = build(*a, **k)
        patch(engine)
        return engine

    monkeypatch.setattr(model, "build_engine", build_broken)


def test_fault_state_unchanged(files, monkeypatch):
    """A step that hands back the states it was given (no KV written)."""
    def patch(engine):
        step = engine._step_fn

        def unchanged(*args):
            lg, greedy, _ = step(*args)
            return lg, greedy, args[3]
        engine._step_fn = unchanged
    _patched_engine(monkeypatch, patch)
    out = run(files)
    assert not out["correct"], out["check"]


def test_fault_token_altered(files, monkeypatch):
    """Every fourth token changed where the engine emits it."""
    def patch(engine):
        emit = engine._emit

        def altered(req, tok):
            if len(req["generated"]) % 4 == 3:
                tok = (tok + 1) % engine.cfg.vocab_size
            emit(req, tok)
        engine._emit = altered
    _patched_engine(monkeypatch, patch)
    out = run(files)
    assert not out["correct"], out["check"]


def test_new_mix_and_metric_found_by_name(files):
    """A traffic mix and a per-layer metric added as files, with an entry
    each in the benchmark's list, run without any other file edited."""
    (files / "traffic" / "tiny-new-mix.json").write_text(json.dumps({
        "arrivals": {"kind": "backlog", "depth_per_lane": 1},
        "prompt": {"median": 6, "sigma": 0.3, "min": 2, "max": 12},
        "output": {"median": 4, "sigma": 0.3, "min": 2, "max": 8},
        "block": 8, "lead_in_s": 0.2}))
    (files / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return float(len(run.requests))\n")
    bench = tiny_bench(CONFIG, "tiny-new-mix", ["requests_seen"])
    out = harness.run_cell(str(files), "cell", SEED, 0.5, True,
                           time.perf_counter(), bench=bench,
                           files=str(files))
    assert out["correct"]
    assert out["metrics"]["requests_seen"]["value"] > 0


def test_run_refuses_without_an_accelerator(tmp_path):
    """On the CPU the command exits non-zero and prints no result, also in
    a directory holding only BENCHMARK.json and bench/."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "bench/run.py", "--workload",
           "starcoder2-3b.w8a8.chat-offline", "--seed", str(SEED),
           "--seconds", "1", "--trace", "0"]
    bare = tmp_path / "bare"
    bare.mkdir()
    (bare / "BENCHMARK.json").write_text(
        open(os.path.join(ROOT, "BENCHMARK.json")).read())
    subprocess.run(["cp", "-r", BENCH, str(bare / "bench")], check=True)
    for cwd in (ROOT, bare):
        p = subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                           text=True, timeout=120)
        assert p.returncode != 0 and p.stdout.strip() == "", p.stderr
