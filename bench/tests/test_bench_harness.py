"""Whole runs of the harness on the CPU at tiny widths, past its look for
a chip: the timed path compared with the plain reference decides
``correct``; the lower-precision control and the faults a serving cell can
have (a step that returns its state unchanged, a token altered where it
is produced) come out not correct; a new traffic mix, a new metric, a new
model family and a new kernel cost are found by name from their files
alone."""
import gzip
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import harness
import model
import roofline
from conftest import BENCH, ROOT, TESTS, tiny_bench

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


# appended to a copy of the dense family's reference.py: each work count
# records its calls
RECORDER = """

CALLS = []


def _recorded(f):
    def recorded(*args):
        CALLS.append(f.__name__)
        return f(*args)
    return recorded


for _name in ("ops_per_token", "span_attention_ops", "decode_attn_work",
              "head_ops"):
    globals()[_name] = _recorded(globals()[_name])
"""

KERNEL = '''"""The integer LayerNorm, at a made-up cost."""
KIND = "norm"


def cost_from_run(run):
    return 1e9, 2e9, "bf16_flops"
'''

READERS = {
    "model_ops": """def read(run):
    ref, a = run.family.reference, run.arch
    ops = sum(ref.ops_per_token(a) * c + ref.span_attention_ops(a, p0, c)
              for s in run.steps for p0, c, _ in s.spans)
    return ops + ref.head_ops(a) + ref.decode_attn_work(a, [8])[0]
""",
    "norm_s": """def read(run):
    return (run.trace or {}).get("kernels", {}).get("norm", {}).get("s")
"""}


def test_new_family_and_kernel_found_by_name(files, monkeypatch):
    """A family (a copy of ``dense`` whose work counts record their calls)
    named by a new configuration, and a cost file for a kernel the
    recorded v5e trace holds (``int_layernorm``), added under the test's
    files directory with an entry each in the benchmark's list: run_cell
    loads the family's reference and program by name and hands the family
    to the readers, and the trace reduction finds the kernel (the CPU's
    trace has no TPU plane; the recorded one stands in for it)."""
    fam_dir = files / "families" / "dense_counted"
    shutil.copytree(files / "families" / "dense", fam_dir)
    with open(fam_dir / "reference.py", "a") as f:
        f.write(RECORDER)
    conf = json.loads((files / "configs" / f"{CONFIG}.json").read_text())
    conf["family"] = "dense_counted"
    (files / "configs" / "tiny-counted.json").write_text(json.dumps(conf))
    (files / "kernels" / "int_layernorm.py").write_text(KERNEL)
    for name, text in READERS.items():
        (files / "metrics" / f"{name}.py").write_text(text)
    with gzip.open(os.path.join(TESTS, "data", "trace_v5e.json.gz"),
                   "rt") as f:
        chip = json.load(f)
    monkeypatch.setattr(harness.devtrace, "events", lambda *a: chip)

    bench = tiny_bench("tiny-counted", "tiny-offline", list(READERS))
    out = harness.run_cell(str(files), "cell", SEED, 0.5, True,
                           time.perf_counter(), bench=bench,
                           files=str(files))
    assert out["correct"], out["check"]
    fam = model.family("dense_counted", str(files))
    assert fam.program.reference is fam.reference
    assert set(fam.reference.CALLS) == {"ops_per_token", "head_ops",
                                        "span_attention_ops",
                                        "decode_attn_work"}
    assert out["metrics"]["model_ops"]["value"] > 0
    norm_s = out["metrics"]["norm_s"]["value"]
    assert norm_s > 0

    # with the chip's peaks, the reduction costs the new kernel from the run
    pk = roofline.peaks("TPU v5 lite")
    run = harness.Run("c", 4, fam.reference.arch(conf["model"]), fam, 1.0,
                      0.0, 1.0, [], [], pk, (0.0, 1.0))
    red = harness.devtrace.reduce(chip, run,
                                  harness.devtrace.kernels(str(files)))
    assert red["kernels"]["norm"] == {
        "s": norm_s, "min_s": roofline.min_seconds(1e9, 2e9, pk,
                                                   "bf16_flops")}
    assert "gemm" in red["kernels"] and "decode_attn" in red["kernels"]


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
