"""The readers of the program's spans and scopes: the three metrics on a
synthetic record (an idle gap split over two spans by time, waiting on
the chip left out), None without a trace or on a program without spans,
the HLO walker on a real CPU trace, the engine's plan record against the
harness's, and a tiny traced run through the harness."""
import glob
import gzip
import json
import os
import time

import jax
import jax.numpy as jnp

import harness
import model
import progtrace
from conftest import TESTS, tiny_bench

SEED = 2 ** 31 + 4242
CONFIG = "tiny-starcoder2"
NEW = ["host_ms_per_step", "host_idle_share", "gather_attn_share"]


def synthetic(program=True) -> dict:
    """A 1000 ns stretch; the device idles in [200, 400) (and after 1000);
    one step's pack, forward and wait cover that gap in 60, 90 and 50 ns,
    so a split by the gap's midpoint would give all 200 to the forward."""
    spans = [["traced", 0, 1000, {}], ["step", 90, 370, {}]]
    if program:
        spans += [["engine.step", 100, 350, {}],
                  ["engine.pack", 150, 110, {}],
                  ["engine.forward", 260, 90, {}],
                  ["engine.wait", 350, 70, {}],
                  ["engine.step", 500, 400, {}],
                  ["engine.wait", 850, 40, {}],
                  ["engine.step", 1200, 50, {}]]      # after the stretch
    scope = (lambda s: s) if program else (lambda s: "")
    ops = [[scope("attn.gather"), 0, 200], [scope("mlp"), 400, 200],
           [scope("attn.gather"), 600, 500]]
    return {"spans": spans, "device": [ops]}


def test_host_ms_per_step_leaves_out_the_wait():
    # (350 - 70 + 400 - 40) / 2 ns
    assert abs(progtrace.host_ms_per_step(synthetic()) - 320e-6) < 1e-15


def test_idle_gap_split_over_spans_by_time():
    per, gaps, window = progtrace.idle_by_span(synthetic())
    assert round(window * 1e9) == 1000
    assert [round(g * 1e9) for g, _ in gaps] == [200]
    assert {k: round(v * 1e9) for k, v in per.items()} == {
        "engine.pack": 60, "engine.forward": 90, "engine.wait": 50}
    # the wait is the chip's, not the host's: 150 of 1000 ns
    assert abs(progtrace.host_idle_share(synthetic()) - 15.0) < 1e-9


def test_gather_share_clips_to_the_stretch():
    # 200 ns and 400 of the 500 ns op that crosses the stretch's end
    assert abs(progtrace.scope_share(synthetic(), "attn.gather")
               - 60.0) < 1e-9


def test_no_program_spans_or_scopes_read_none():
    rec = synthetic(program=False)
    assert progtrace.host_ms_per_step(rec) is None
    assert progtrace.host_idle_share(rec) is None
    assert progtrace.scope_share(rec, "attn.gather") is None


def test_recorded_v5e_steps():
    """Five engine steps (buckets 256, 64, 1, 1, 1) of a chat-offline
    trace on a TPU v5e, reduced to the record: every program span nests in
    the benchmark's ``step`` or ``submit`` span (one clock), the idle lies
    in program spans, and the readers give what the chip run's did."""
    with gzip.open(os.path.join(TESTS, "data", "progtrace_v5e.json.gz")) as f:
        rec = json.load(f)
    outer = [(s, s + d) for n, s, d, _ in rec["spans"]
             if n in ("step", "submit")]
    prog = [(s, s + d) for n, s, d, _ in rec["spans"]
            if progtrace.program_span(n)]
    assert len(prog) > 40 and all(
        any(a <= s and e <= b for a, b in outer) for s, e in prog)
    per, gaps, _ = progtrace.idle_by_span(rec)
    assert per[progtrace.OUTSIDE] < 0.01 * sum(per.values())
    assert abs(progtrace.host_ms_per_step(rec) - 7.3653792) < 1e-6
    assert abs(progtrace.host_idle_share(rec) - 2.9007619) < 1e-6
    assert abs(progtrace.scope_share(rec, "attn.gather")
               - 23.5198849) < 1e-6
    scopes, _ = progtrace.device_s_by_scope(rec)
    assert set(progtrace.SCOPES) <= set(scopes)


def test_readers_without_a_trace_read_none():
    run = harness.Run("c", 4, {}, model.family("dense"), 1.0, 0.0, 1.0,
                      [], [], {})
    for name in NEW:
        assert harness.reader(name)(run) is None


def _profile(trace_dir):
    return jax.profiler.trace(str(trace_dir),
                              profiler_options=harness.devtrace.options())


def test_hlo_walker_finds_the_scopes(tmp_path):
    """The serialized HLO the profiler keeps per program maps each
    instruction to its op_name; a TPU op line is placed in its program by
    the ``XLA Modules`` event around it."""
    @jax.jit
    def f(x):
        with jax.named_scope("attn.gather"):
            y = jnp.sin(x) @ x
        with jax.named_scope("mlp"):
            return jnp.tanh(y)

    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    with _profile(tmp_path):
        f(x).block_until_ready()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    with open(path, "rb") as fh:
        programs = progtrace.hlo_scopes(fh.read())
    module = next(k for k in programs if k.startswith("jit_f("))
    table = programs[module]
    scopes = {progtrace.scope_of(op) for op in table.values()}
    assert {"attn.gather", "mlp"} <= scopes
    inst = next(k for k, v in table.items()
                if progtrace.scope_of(v) == "mlp")
    ops = [(10, 5, f"%{inst} = f32[16,16]{{1,0}} tanh(f32[16,16] %p)"),
           (20, 5, "%while.3 = (s32[]) while((s32[]) %t), body=%b"),
           (40, 5, f"%{inst} = f32[16,16]{{1,0}} tanh(f32[16,16] %p)")]
    mods = [(0, 30, module), (35, 10, "jit_other(1)")]
    assert progtrace.scoped_ops(ops, mods, programs) == [
        ["mlp", 10, 5], ["", 40, 5]]


def test_plan_record_matches_the_harness_plans(files, tmp_path):
    """The ``engine.plan`` span's record is the one the benchmark's
    wrapper of ``_plan_tokens`` makes, step for step."""
    conf = model.load(CONFIG, str(files))
    fam = model.family(conf["family"], str(files))
    cfg = fam.program.arch_config(conf)
    eng = model.build_engine(conf, cfg,
                             fam.program.program_params(conf, cfg, SEED),
                             SEED)
    plans = harness._Plans(eng)
    seen = []
    with _profile(tmp_path):
        for i in range(6):
            eng.submit([3 + i] * (5 + 3 * i), max_new=4, request_id=i)
        while eng.queue or eng.lane_active.any():
            plans.last = []
            eng.step()
            seen.append(plans.last)
    rec = progtrace.record(str(tmp_path))
    got = [[(int(p), int(c), c2 == "1") for p, c, c2 in
            (lane.split(":") for lane in a.get("lanes", "").split())]
           for n, _, _, a in rec["spans"] if n == "engine.plan"]
    assert got == seen and len(got) > 4


def test_traced_tiny_run_reports_host_ms_per_step(files):
    """On the CPU the program's spans are read; there is no TPU plane, so
    the readers that join them with device ops read nothing."""
    out = harness.run_cell(str(files), "cell", SEED, 0.5, True,
                           time.perf_counter(),
                           bench=tiny_bench(CONFIG, "tiny-offline", NEW),
                           files=str(files))
    assert out["correct"], out["check"]
    assert out["metrics"]["host_ms_per_step"]["value"] > 0
    assert "gather_attn_share" not in out["metrics"]
    assert "host_idle_share" not in out["metrics"]
