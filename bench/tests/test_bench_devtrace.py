"""The trace reduction: busy time as the union of device op intervals,
idle gaps named by the host span they fall in, kernel device time and the
roofline shares from the kernels' cost files, on synthetic events and on
a short trace recorded on a TPU v5e and kept in ``data/``; the kernel
numbers are those the reduction gave before the costs moved into files,
to the last bit."""
import gzip
import json
import os

import pytest

import devtrace
import harness
import model
import roofline

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHIP = os.path.join(DATA, "trace_v5e.json.gz")
ARCH = {"d": 3072, "layers": 30, "heads": 24, "kv_heads": 2,
        "head_dim": 128, "ff": 12288, "vocab": 49152, "gated": False}
DENSE = model.family("dense")
KERN = devtrace.kernels()


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31)]
    assert devtrace.union_ns(iv) == 26
    assert devtrace.gaps(iv, 0, 40) == [(15, 20), (31, 40)]
    assert devtrace.gaps([], 3, 9) == [(3, 9)]


def test_host_span_is_the_innermost():
    host = [["window", 0, 100], ["step", 10, 20], ["submit", 12, 2]]
    assert devtrace.host_span_at(host, 13) == "submit"
    assert devtrace.host_span_at(host, 25) == "step"
    assert devtrace.host_span_at(host, 50) == "window"
    assert devtrace.host_span_at(host, 500) == "none"


GEMM_LINE = (
    "%int8_gemm.57 = bf16[8,256]{1,0:T(8,128)(2,1)S(1)} custom-call("
    "s8[8,3072]{1,0:T(8,128)(4,1)S(1)} %q.92, s8[3072,256]{1,0:T(8,128)"
    "(4,1)S(1)} %w.15, f32[8,1]{1,0:T(8,128)S(1)} %q.93, f32[1,256]{1,0:"
    "T(1,128)S(1)} %s.30, f32[1,256]{1,0:T(1,128)S(1)} %b.31), "
    "custom_call_target=\"tpu_custom_call\", operand_layout_constraints={"
    "s8[8,3072]{1,0}, s8[3072,256]{1,0}, f32[8,1]{1,0}, f32[1,256]{1,0}, "
    "f32[1,256]{1,0}}, frontend_attributes={kernel_metadata={}}")


def test_classify_by_kernel_name():
    assert devtrace.op_kind(GEMM_LINE) == "int8_gemm"
    assert devtrace.classify(GEMM_LINE, KERN) == "gemm"
    assert devtrace.classify("%dual_gemm_gated.3 = bf16[32,13824]",
                             KERN) == "gemm"
    assert devtrace.classify(
        "%paged_decode_attention.9 = bf16[16,12,128]", KERN) == "decode_attn"
    assert devtrace.classify("%quantize_rows.60 = (s8[8,3072]", KERN) is None
    assert devtrace.op_kind("%while.5 = (s32[]) while(...)") == "while"


def test_gemm_cost_from_hlo_line():
    """Every operand and the result count, wherever XLA placed them; a
    weight already in VMEM (``S(1)``) is named as fed by another op."""
    cost = KERN["int8_gemm"].cost_from_hlo
    assert KERN["dual_gemm_gated"].cost_from_hlo is cost
    ops, nbytes, feeds = cost(GEMM_LINE)
    assert ops == 2 * 8 * 3072 * 256
    assert nbytes == (2 * 8 * 256 + 8 * 3072 + 3072 * 256 + 4 * 8
                      + 4 * 256 + 4 * 256)
    assert feeds == ("%w.15",)
    hbm = GEMM_LINE.replace("s8[3072,256]{1,0:T(8,128)(4,1)S(1)}",
                            "s8[3072,256]{1,0:T(8,128)(4,1)}")
    assert cost(hbm) == (ops, nbytes, ())


FEED_LINE = ("%w.15 = s8[3072,256]{1,0:T(8,128)(4,1)S(1)} fusion(s8[30,"
             "3072,256]{2,1,0:T(8,128)(4,1)} %p.1, s32[] %i.2), kind=kLoop")


def test_device_ops_give_the_gemm_its_weight_feed():
    """The op that placed a GEMM's weight in VMEM is the latest of that
    name before the GEMM: each program run's own, not an earlier one's."""
    raw = [(300, 40, GEMM_LINE), (10, 7, FEED_LINE), (20, 30, GEMM_LINE),
           (200, 9, FEED_LINE), (100, 4, "%fusion.3 = f32[8] fusion()")]
    ops = devtrace.device_ops(raw, KERN)
    assert [o[0] for o in ops] == ["w", "int8_gemm", "fusion", "w",
                                   "int8_gemm"]
    assert [o[5] for o in ops] == [0, 7, 0, 0, 9]
    cost = KERN["int8_gemm"].cost_from_hlo(GEMM_LINE)
    assert ops[1][3:5] == list(cost[:2])


def _run(steps=(), traced=(0.0, 1.0)):
    return harness.Run("c", 8, ARCH, DENSE, 1.0, 0.0, 1.0, [], list(steps),
                       roofline.peaks("TPU v5 lite"), traced)


def test_reduce_synthetic():
    """Ops are clipped to the traced stretch (the GEMM that ends after it
    counts the half of its time and work inside), a GEMM's time takes in
    the op that fed its weight into VMEM; the decode kernel's work is that of the
    traced bucket-1 steps, a lane taking its prompt's last token among
    them."""
    cost = KERN["int8_gemm"].cost_from_hlo(GEMM_LINE)[:2]
    ev = {"device": [[["while", 95, 150, 0, 0, 0],
                      ["dynamic-slice_bitcast_fusion", 93, 5, 0, 0, 0],
                      ["int8_gemm", 100, 50, *cost, 5],
                      ["fusion", 160, 20, 0, 0, 0],
                      ["paged_decode_attention", 200, 40, 0, 0, 0],
                      ["int8_gemm", 290, 20, *cost, 0]]],
          "host": [["traced", 90, 210], ["step", 95, 150]]}
    S = harness.Step
    run = _run([S(0.1, 0.2, 1, 1, [(99, 1, False), (199, 1, True)]),
                S(0.3, 0.4, 8, 1, [(0, 8, True), (49, 1, False)]),
                S(1.5, 1.6, 0, 2, [(10, 1, False), (20, 1, False)])])
    red = devtrace.reduce(ev, run, KERN)
    assert red["window_s"] == pytest.approx(210e-9)
    assert red["busy_s"] == pytest.approx(125e-9)
    assert red["kernels"]["gemm"]["s"] == pytest.approx(65e-9)
    want = 1.5 * roofline.min_seconds(*cost, run.peaks)
    assert red["kernels"]["gemm"]["min_s"] == pytest.approx(want)
    assert red["kernels"]["decode_attn"]["s"] == pytest.approx(40e-9)
    att = roofline.decode_attn_cost([100, 200], 24, 2, 128)
    assert red["kernels"]["decode_attn"]["min_s"] == pytest.approx(
        30 * roofline.min_seconds(*att, run.peaks, "bf16_flops"))
    gaps = dict((round(s * 1e9), n) for n, s in red["breakdown"]["idle_gaps"])
    assert gaps == {3: "traced", 2: "step", 10: "step", 20: "step",
                    50: "traced"}
    assert red["kernels"] == {
        "gemm": {"s": 6.5e-08, "min_s": 1.4966739926739927e-06},
        "decode_attn": {"s": 4e-08, "min_s": 6.746373626373626e-06}}


def test_decode_contexts_are_bucket_one_steps():
    S = harness.Step
    steps = [S(0, 1, 0, 2, [(4, 1, False), (9, 1, False)]),
             S(1, 2, 3, 1, [(0, 3, True), (10, 1, False)]),
             S(2, 3, 0, 0, [])]
    assert devtrace.decode_contexts(steps) == [5, 10]


def test_reduce_chip_trace():
    """Three engine steps traced on a v5e (two 2048-row prefill steps and a
    decode step): busy time is the union of the leaf ops (the layer loop's
    ``while`` spans its children and is left out), every GEMM's weight was
    fed into VMEM by a slice op before it, and the GEMM time holds the
    kernels' and their feeds'; its roofline share lies in (0, 100]."""
    with gzip.open(CHIP, "rt") as f:
        ev = json.load(f)
    red = devtrace.reduce(ev, _run(), KERN)
    ops = ev["device"][0]
    (_, lo, d), = [h for h in ev["host"] if h[0] == "traced"]
    leaf = [(s, s + dd) for k, s, dd, *_ in ops if k != "while"]
    assert red["window_s"] == pytest.approx(d * 1e-9)
    assert red["busy_s"] == pytest.approx(devtrace.union_ns(leaf) * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    gemms = [o for o in ops if o[0] in ("int8_gemm", "dual_gemm_gated")]
    assert gemms and all(feed > 0 for *_, feed in gemms)
    fed = sum(feed for *_, feed in gemms)
    slices = sum(dd for k, _, dd, *_ in ops
                 if k == "dynamic-slice_bitcast_fusion")
    assert fed <= slices
    gemm = sum(dd + feed for _, _, dd, _, _, feed in gemms) * 1e-9
    assert red["kernels"]["gemm"]["s"] == pytest.approx(gemm)
    share = 100 * red["kernels"]["gemm"]["min_s"] / red["kernels"]["gemm"]["s"]
    assert 0 < share <= 100
    top = [k for k, _ in red["breakdown"]["device_ops"]]
    assert {"int8_gemm", "paged_decode_attention"} <= set(top)
    assert "while" not in top
    assert {n for n, _ in red["breakdown"]["idle_gaps"]} <= {"step",
                                                             "traced"}
    assert red["kernels"] == {
        "gemm": {"s": 0.12010538599999988, "min_s": 0.0637444295292456},
        "decode_attn": {"s": 0.029460033999999993, "min_s": 0.0}}


def test_decode_cost_on_the_chip_trace():
    """The decode kernel's least time over the recorded stretch, for forty
    traced bucket-1 steps of eight lanes at contexts 500-1500, counted by
    the dense family over every layer: what the reduction gave when it
    made the count itself, to the last bit."""
    with gzip.open(CHIP, "rt") as f:
        ev = json.load(f)
    S = harness.Step
    steps = [S(0.1 + 0.01 * i, 0.105 + 0.01 * i, 0, 8,
               [(500 + 131 * lane + 7 * i, 1, lane == 3)
                for lane in range(8)]) for i in range(40)]
    red = devtrace.reduce(ev, _run(steps), KERN)
    assert red["kernels"]["gemm"]["min_s"] == 0.0637444295292456
    assert red["kernels"]["decode_attn"]["s"] == 0.029460033999999993
    assert red["kernels"]["decode_attn"]["min_s"] == 0.0069785787545787546
