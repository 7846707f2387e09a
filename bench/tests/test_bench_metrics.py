"""End-to-end and per-layer arithmetic on synthetic timelines: tokens
counted inside the window, decode steps timed by the host, and a stall
that moves both; the comparison's sample."""
import harness
import model

ARCH = {"d": 64, "layers": 2, "heads": 4, "kv_heads": 2, "head_dim": 16,
        "ff": 128, "vocab": 100, "gated": False}
DENSE = model.family("dense")


def read(name, run):
    return harness.reader(name)(run)


def make_run(stall_s=0.0):
    """Four requests, one started every 0.1 s from t=1.0; each emits a
    token 0.05 s after its start and then one every 0.02 s, 10 tokens.  A
    stall of ``stall_s`` delays every token from t=1.2 on."""
    reqs = []
    for i in range(4):
        t0 = 1.0 + 0.1 * i
        ts = [t0 + 0.05 + 0.02 * k for k in range(10)]
        ts = [t + stall_s if t >= 1.2 else t for t in ts]
        reqs.append(harness.Request(i, [5] * 8, 10, ts, [7] * 10))
    return harness.Run("c", 4, ARCH, DENSE, 12.5, 1.0, 1.5, reqs, [], {})


def test_tokens_counted_inside_the_window():
    run = make_run()
    inside = sum(1.0 <= t < 1.5 for r in run.requests for t in r.token_t)
    assert read("tokens_per_s", run) == inside / 0.5
    assert read("setup_s", run) == 12.5


def test_a_stall_moves_tokens_and_decode_steps():
    base, stalled = make_run(), make_run(stall_s=0.2)
    assert read("tokens_per_s", stalled) < read("tokens_per_s", base)
    S = harness.Step
    base.steps = [S(1.0, 1.01, 0, 2, [(10, 1, False), (20, 1, False)])]
    stalled.steps = [S(1.0, 1.21, 0, 2, [(10, 1, False), (20, 1, False)])]
    assert read("decode_step_ms", stalled) > read("decode_step_ms", base)


def _steps():
    S = harness.Step
    return [S(1.0, 1.01, 0, 2, [(10, 1, False), (20, 1, False)]),
            S(1.01, 1.04, 8, 1, [(0, 8, True), (21, 1, False)]),
            S(1.04, 1.05, 0, 0, [])]


def test_step_metrics():
    run = make_run()
    run.steps = _steps()
    assert read("batch_occupancy", run) == 100.0 * 4 / (3 * 4)
    assert abs(read("decode_step_ms", run) - 10.0) < 1e-9


def test_step_mfu_counts_every_token_once():
    run = make_run()
    run.steps = _steps()
    run.peaks = {"int8_ops": 1e12}
    per = 2.0 * 2 * (64 * 64 + 2 * 64 * 32 + 64 * 64 + 2 * 64 * 128)
    att = 4.0 * 16 * 4 * 2
    emitted = sum(1.0 <= t < 1.5 for r in run.requests for t in r.token_t)
    ops = (per * 11 + att * (11 + 21 + sum(range(1, 9)) + 22)
           + 2.0 * 64 * 100 * emitted)
    assert abs(read("step_mfu", run) - 100 * ops / (0.5 * 1e12)) < 1e-9
    # what the reader gave when it made the counts itself, to the last bit
    assert read("step_mfu", run) == 0.0003588096


def test_step_mfu_at_full_width_is_the_parents():
    """starcoder2-3b's widths over forty 8-lane decode steps, two prompt
    spans and the synthetic steps: the count the reader made before the
    dense family made it, to the last bit."""
    run = make_run()
    run.arch = dict(ARCH, d=3072, layers=30, heads=24, head_dim=128,
                    ff=12288, vocab=49152)
    run.peaks = {"int8_ops": 1e12}
    S = harness.Step
    run.steps = [S(0.1 + 0.01 * i, 0.105 + 0.01 * i, 0, 8,
                   [(500 + 131 * lane + 7 * i, 1, lane == 3)
                    for lane in range(8)]) for i in range(40)]
    run.steps += _steps() + [S(1.0, 1.2, 256, 0,
                               [(0, 256, True), (700, 64, True)])]
    assert read("step_mfu", run) == 783.5613954048


def test_readers_find_nothing_without_a_trace():
    run = make_run()
    for name in ("idle_share", "gemm_roofline", "decode_attn_roofline"):
        assert read(name, run) is None


def test_metrics_for_filters_by_cell():
    bench = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["x"]}],
             "per_layer": [{"name": "c", "workloads": ["y"]}]}
    assert [m["name"] for m in harness.metrics_for(bench, "x", False)] == [
        "a", "b"]
    assert [m["name"] for m in harness.metrics_for(bench, "y", False)] == ["a"]
    assert [m["name"] for m in harness.metrics_for(bench, "y", True)] == ["c"]


def _finished(n):
    return [harness.Request(i, [1] * (10 + i), 5, [], [2] * 5)
            for i in range(n)]


def test_sample_keeps_the_longest():
    reqs = _finished(20)
    pick = harness.sample(reqs, 2 ** 33 + 1, 8)
    assert pick[0].index == 19 and len({r.index for r in pick}) == 8
    assert [r.index for r in harness.sample(reqs, 7, 8)] != [
        r.index for r in pick]
    assert len(harness.sample(_finished(3), 7, 8)) == 3
    assert harness.sample([], 7, 8) == []


def test_spread_takes_first_and_last():
    assert list(harness.spread(100, 5)) == [0, 25, 50, 74, 99]
    assert list(harness.spread(3, 48)) == [0, 1, 2]
    assert list(harness.spread(1, 48)) == [0]


def test_too_few_requests_is_not_correct():
    conf = {"correct": {"mean_logit_gap": 1.0, "sample_requests": 32,
                        "min_requests": 8, "tokens_per_request": 4}}
    check = harness.compare(conf, DENSE, 3, [])
    assert check == {"requests_checked": {"value": 0, "limit": 8,
                                          "at_least": True}}
    assert not all(harness.holds(c) for c in check.values())
    assert harness.holds({"value": 0.5, "limit": 1.0})
