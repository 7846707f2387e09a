"""The model families the harness finds by name: the ``dense`` family
makes the weights and the reference's gaps that the benchmark made before
its code moved into ``families/dense/`` (digests of both, taken then, to
the last bit); a layer's counts reach over at most its window; and no
family's reference imports the program."""
import ast
import glob
import hashlib
import os
import struct

import jax
import numpy as np
import pytest

import model
import roofline
from conftest import BENCH, TESTS

SEED = 2 ** 31 + 4242
DATA = os.path.join(TESTS, "data")

# sha256 of the reference's float weights, of the program's served
# weights and of the reference's gaps over ``_seqs``, from the benchmark
# before this family existed
GOLDEN = {
    "tiny-starcoder2": (
        "f8869fe39acfcb8583940aa65848139264ee793284a4bea9a71fdb09a0c287d5",
        "fbb40b4bc6952062e0c7aaccb19968060dc7aa6ff9f07940b1daaa8d3f70f6b6",
        "c673c75aa3c2c0d96fb35ae5582cd5c3f142c7497bb611294acf3b69ae1b01dd"),
    "tiny-qwen2": (
        "bd04f7086793cdc77534062ec4be6f027e787eb55a474422fe97b55b41d81cd8",
        "c3aa3541704c2b11628d4b7177eda12dd8a4e50f4f7770557838635945f1fb55",
        "4d573c841657733ee647f08b20474900360019e844e473454f624698e4750ce3"),
}


def _seqs(vocab):
    return [([(7 * i + 3 * j + 1) % vocab for j in range(5 + 9 * i)],
             [(11 * i + 5 * j + 2) % vocab for j in range(3 + 4 * i)])
            for i in range(3)]


def _update(h, name, x):
    x = np.asarray(x)
    h.update(f"{name}:{x.dtype}:{x.shape}".encode())
    h.update(x.tobytes())


@pytest.mark.parametrize("config", sorted(GOLDEN))
def test_dense_makes_the_same_weights_and_gaps(config):
    conf = model.load(config, DATA)
    fam = model.family(conf["family"])
    ref = fam.reference
    a = ref.arch(conf["model"])
    key = ref.base_key(SEED)
    h = hashlib.sha256()
    for i in range(a["layers"]):
        for k, v in sorted(ref.layer_weights(key, a, i).items()):
            _update(h, f"{i}.{k}", v)
    for k, v in sorted(ref.top_weights(key, a).items()):
        _update(h, k, v)
    ref_weights = h.hexdigest()
    cfg = fam.program.arch_config(conf)
    params = fam.program.program_params(conf, cfg, SEED)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        _update(h, jax.tree_util.keystr(path), leaf)
    served = h.hexdigest()
    gaps, scale = ref.logit_gaps(SEED, conf["model"], _seqs(a["vocab"]))
    h = hashlib.sha256()
    for i, g in enumerate(gaps):
        _update(h, str(i), g)
    h.update(struct.pack("<d", scale))
    assert (ref_weights, served, h.hexdigest()) == GOLDEN[config]


@pytest.mark.parametrize("window", [0, 1, 7, 64, 1024, 5000])
def test_a_layer_attends_at_most_its_window(window):
    """``span_keys`` and ``decode_attn_cost``, which a family's
    ``span_attention_ops`` and ``decode_attn_work`` add up layer by layer,
    count min(context, window) keys for each query (window 0: the whole
    context)."""
    def reach(context):
        return min(context, window) if window else context

    for p0, c in [(0, 1), (0, 256), (100, 64), (990, 50), (1020, 8),
                  (2000, 1), (5100, 3)]:
        want = sum(reach(p + 1) for p in range(p0, p0 + c))
        assert roofline.span_keys(p0, c, window) == want
    ctx = [1, 5, 64, 1000, 1024, 1500, 6000]
    assert roofline.decode_attn_cost(ctx, 8, 2, 16, window) == \
        roofline.decode_attn_cost([reach(c) for c in ctx], 8, 2, 16)


def test_dense_counts_each_layer_over_the_whole_context():
    conf = model.load("starcoder2-3b.w8a8")
    ref = model.family(conf["family"]).reference
    a = ref.arch(conf["model"])
    per_key = 4.0 * 128 * 24
    assert ref.span_attention_ops(a, 1500, 256) == 30 * per_key * sum(
        range(1501, 1757))
    ctx = [500, 1500, 2048]
    ops, nbytes = roofline.decode_attn_cost(ctx, 24, 2, 128)
    assert ref.decode_attn_work(a, ctx) == (30 * ops, 30 * nbytes)
    assert ref.head_ops(a) == 2.0 * 3072 * 49152
    assert ref.ops_per_token(a) == 2.0 * 30 * (
        2 * 3072 * 3072 + 2 * 3072 * 256 + 2 * 3072 * 12288)


def _imported(path):
    names = []
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            names += [n.name for n in node.names]
        elif isinstance(node, ast.ImportFrom):
            names.append(node.module or "")
    return names


def test_no_reference_imports_the_program():
    refs = glob.glob(os.path.join(BENCH, "families", "*", "reference.py"))
    assert refs
    for path in refs:
        mods = _imported(path)
        assert not [m for m in mods if m.split(".")[0] == "repro"], path
