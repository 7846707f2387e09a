"""The serving engine's tracing: spans on the profiler's clock, the named
scopes of the packed step, and the counters beside them.

Spans cost one annotation object and compute no argument while no
profiler session records; in a recorded run each ``engine.step`` holds
its stages in order, the scopes are in the compiled step's metadata, a
compile after ``warmup()`` is counted, and ``serving_metrics()`` gives
the queue wait."""
import gc
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.kernels import ops
from repro.models import init_params
from repro.serve import ServeConfig, ServingEngine, tracing

STAGES = ("engine.admit", "engine.plan", "engine.pack", "engine.forward",
          "engine.wait", "engine.commit")
SCOPES = ("embed", "norm", "attn.qkv", "attn.kv_write", "attn.gather",
          "attn.out", "mlp", "head", "sample")


@pytest.fixture(scope="module")
def model():
    cfg = get_config("starcoder2-3b", reduced=True)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _engine(model, **kw):
    cfg, params = model
    kw.setdefault("batch_lanes", 2)
    kw.setdefault("max_seq", 48)
    kw.setdefault("token_budget", 8)
    return ServingEngine(params, cfg, ServeConfig(**kw))


def _trace(trace_dir):
    """A profiler session as the benchmark opens one: host annotations,
    no Python tracer."""
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    return jax.profiler.trace(str(trace_dir), profiler_options=o)


def _events(trace_dir) -> list[tuple[str, int, int, dict]]:
    """(name, start_ns, end_ns, args) of every host event, by start."""
    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(list(ev.stats))))
    return sorted(out, key=lambda e: e[1])


def test_span_args_not_computed_without_a_session():
    calls = []

    def arg():
        calls.append(1)
        return 1

    assert not tracing.recording()
    with tracing.span("test.off", a=arg) as sp:
        tracing.note(sp, b=arg)
    assert calls == []


def test_spans_and_args_land_in_the_profile(tmp_path):
    with _trace(tmp_path):
        assert tracing.recording()
        with tracing.span("test.on", a=lambda: 7, b="x") as sp:
            tracing.note(sp, c=lambda: 9)
        gc.collect()
    ev = {e[0]: e for e in _events(tmp_path)}
    assert ev["test.on"][3] == {"a": 7, "b": "x", "c": 9}
    assert ev["host.gc"][3]["generation"] == 2
    assert "collected" in ev["host.gc"][3]


@pytest.mark.parametrize("mode,kw", [
    ("packed", dict(token_budget=8, paged=True)),
    ("chunked", dict(token_budget=0, prefill_chunk=4)),
    ("tokenwise", dict(token_budget=0, prefill_chunk=0)),
])
def test_each_step_holds_its_stages_in_order(model, tmp_path, mode, kw):
    eng = _engine(model, **kw)
    assert eng.mode == mode
    with _trace(tmp_path):
        for i in range(3):
            eng.submit([5 + i, 6, 7, 8, 9], max_new=4, request_id=i)
        eng.run_until_drained()
        if eng.paged:   # the finished prompts' pages leave the prefix index
            eng._apply_pool_actions(eng.pool.flush_tree())
    evs = _events(tmp_path)
    names = {e[0] for e in evs}
    assert not names & {"traced", "step", "submit"}
    assert "engine.submit" in names
    steps = [e for e in evs if e[0] == "engine.step"]
    assert steps and all(e[3]["mode"] == mode for e in steps)
    for _, s, e, args in steps:
        inside = [n for n, s1, e1, _ in evs
                  if n in STAGES and s <= s1 and e1 <= e]
        if args["lanes"] == 0:       # nothing admitted: no forward
            assert inside == ["engine.admit"]
            continue
        assert inside[0] == "engine.admit"
        # one plan, pack, forward, wait, commit per forward (two a step in
        # chunked mode while a prompt prefills beside decoding lanes)
        rest = inside[1:]
        assert rest and len(rest) % 5 == 0, inside
        assert all(rest[i:i + 5] == list(STAGES[1:])
                   for i in range(0, len(rest), 5)), inside
    if kw.get("paged"):
        assert "engine.reserve" in names
        pool = [e[3] for e in evs if e[0] == "engine.pool"]
        assert pool and pool[-1]["clears"] > 0


def test_scopes_in_the_step_metadata(model):
    eng = _engine(model, paged=True, int8_kv=True)
    for t in (1, 8):
        hlo = eng.step_hlo(t)
        for scope in SCOPES:
            assert f"/{scope}/" in hlo, (t, scope)


def test_paged_decode_scope_on_the_kernel_path(model):
    """At bucket 1 on the Pallas path the paged decode kernel and the
    preparation of its operands lie under ``attn.paged_decode``."""
    eng = _engine(model, paged=True, int8_kv=True)
    with ops.use_backend("pallas"):
        step = jax.jit(eng._step_body, static_argnums=(6, 7))
        text = step.lower(*eng._all_pad_args(1)).as_text(debug_info=True)
    assert "attn.paged_decode/" in text
    assert "attn.gather/" not in text


def test_compiles_counted_after_warmup(model):
    eng = _engine(model, paged=True)
    eng.warmup()
    for i in range(3):
        eng.submit([5 + i, 6, 7, 8, 9, 10], max_new=4, request_id=i)
    eng.run_until_drained()
    assert eng.stats["compiles"] == 0
    # a bucket no warm-up built: a three-token prompt alone in the engine
    eng._buckets = (1, 2, 3, 4, 8)
    eng.submit([40, 41, 42], max_new=2, request_id="new")
    eng.run_until_drained()
    assert eng.stats["compiles"] == 1
    assert "compiles=1" in eng.stats_summary()


def test_queue_wait_percentiles(model):
    eng = _engine(model)
    now = [0.0]
    eng._clock = lambda: now[0]
    for i in range(4):                 # two lanes: two requests wait
        eng.submit([5 + i, 6, 7], max_new=2, request_id=i)
    now[0] = 1.0
    while eng.queue:
        eng.step()
        now[0] += 1.0
    eng.run_until_drained()
    waits = sorted(eng.stats["queue_wait_ms"])
    assert waits[:2] == [1000.0, 1000.0] and waits[2] > 1000.0
    m = eng.serving_metrics()
    assert m["queue_wait_p50_ms"] == 1000.0
    assert m["queue_wait_p99_ms"] == waits[-1]
