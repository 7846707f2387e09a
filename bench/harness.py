"""One run of one cell: set-up, lead-in, the measured window, the metrics,
and the comparison with the plain reference that decides ``correct``.

Everything a cell needs is found by name: its configuration in
``configs/<config>.json``; the model's own code in
``families/<family>/`` (``reference.py`` and ``program.py``, the family
the configuration names: ``model.py``); its traffic in
``traffic/<traffic>.json``; each metric's reader in
``metrics/<metric>.py`` (a ``read(run)`` that returns a number, or None
where it finds nothing to read); and the cost of each kernel the trace
reduction counts in ``kernels/<kernel>.py`` (``devtrace.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from typing import Any

import jax
import numpy as np

import byname
import devtrace
import model
import roofline
import traffic as traffic_mod

HERE = os.path.dirname(os.path.abspath(__file__))


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in this kind of run."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str, root: str = HERE):
    return byname.module(os.path.join(root, "metrics", f"{name}.py")).read


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    index: int
    prompt: list[int]
    max_new: int
    token_t: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    prompt_tokens: int         # prompt tokens the step computed
    decode_tokens: int
    spans: list[tuple[int, int, bool]]   # per lane: (first pos, count, prompt)


@dataclasses.dataclass
class Run:
    """What a metric reader gets."""
    cell: str
    lanes: int
    arch: dict                 # family.reference.arch of the configuration
    family: model.Family
    setup_s: float
    t_open: float
    t_close: float
    requests: list[Request]
    steps: list[Step]          # steps that started inside the window
    peaks: dict
    traced: tuple[float, float] | None = None   # host clock of the trace
    trace: dict | None = None  # devtrace.reduce(...) of the traced stretch

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: float) -> bool:
        return self.t_open <= t < self.t_close

    def traced_steps(self) -> list[Step]:
        """The steps that started inside the traced stretch."""
        if self.traced is None:
            return []
        lo, hi = self.traced
        return [s for s in self.steps if lo <= s.t0 < hi]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

# the traced stretch of a --trace 1 run: the window's last seconds (reading
# a whole 40 s trace back takes minutes)
TRACE_S = 10.0


class _Plans:
    """Records each packed step's per-lane spans as the engine plans them
    (first position, token count, prompt or decode)."""

    def __init__(self, engine):
        self.engine, self.last = engine, []
        orig = engine._plan_tokens

        def plan_tokens(lanes, budget):
            plan = orig(lanes, budget)
            self.last = [(int(engine.lane_pos[l]), int(c),
                          bool(engine.lane_request[l]["_pending_prompt"]))
                         for l, c in plan.items()]
            return plan

        engine._plan_tokens = plan_tokens


def _annotate(on: bool, name: str):
    return (jax.profiler.TraceAnnotation(name) if on
            else contextlib.nullcontext())


def run_cell(root: str, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, bench: dict | None = None,
             precision: str | None = None, files: str = HERE) -> dict:
    """One run on the first chips of ``jax.devices()``; returns the result
    line's object.  ``precision`` runs the program at another precision
    (the control); ``files`` is the directory holding configs/,
    families/, traffic/, metrics/ and kernels/; a trace of the window's
    last ``TRACE_S`` seconds goes to ``<root>/.bench_out/trace``."""
    bench = bench or load_benchmark(root)
    cell = workload(bench, cell_name)
    conf = model.load(cell["config"], files)
    fam = model.family(conf["family"], files)
    spec = traffic_mod.load(cell["traffic"], files)
    devices = jax.devices()[:cell["chips"]]
    kind = devices[0].device_kind
    pk = roofline.peaks(kind) if devices[0].platform == "tpu" else {}
    arch = fam.reference.arch(conf["model"])
    lanes = conf["serving"]["lanes"]

    from repro.kernels import autotune
    log(f"tiles: {autotune.tile_source()}")
    cfg = fam.program.arch_config(conf, precision)
    params = fam.program.program_params(conf, cfg, seed)
    engine = model.build_engine(conf, cfg, params, seed)
    del params
    engine.warmup()
    plans = _Plans(engine)
    log(f"warm: buckets {engine.chunk_buckets}, "
        f"{time.perf_counter() - t_start:.1f} s after start")

    gen = traffic_mod.Traffic(spec, seed, arch["vocab"])
    clock = time.perf_counter
    reqs: dict[int, Request] = {}
    steps: list[Step] = []
    window: list[float] = [float("inf"), float("inf")]
    tracing = [False]

    def on_token(rid, tok):
        r = reqs[rid]
        r.token_t.append(clock())
        r.tokens.append(int(tok))

    def feed():
        while len(engine.queue) < gen.depth_per_lane * lanes:
            i = len(reqs)
            prompt, out = gen.request(i)
            reqs[i] = Request(i, prompt, out)
            with _annotate(tracing[0], "submit"):
                engine.submit(prompt, max_new=out, request_id=i,
                              on_token=on_token)

    def one_step():
        feed()
        st = engine.stats
        p0, d0 = st["prompt_tokens"], st["decode_tokens"]
        plans.last = []
        t0 = clock()
        with _annotate(tracing[0], "step"):
            engine.step()
        t1 = clock()
        if window[0] <= t0 < window[1]:
            steps.append(Step(t0, t1, st["prompt_tokens"] - p0,
                              st["decode_tokens"] - d0, plans.last))

    t_lead = clock() + float(spec.get("lead_in_s", 0.0))
    while clock() < t_lead:
        one_step()

    trace_dir = os.path.join(root, ".bench_out", "trace")
    traced = span = None
    setup_s = clock() - t_start
    window[0] = clock()
    window[1] = window[0] + seconds
    while clock() < window[1]:
        if trace and not tracing[0] and clock() >= window[1] - TRACE_S:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=devtrace.options())
            tracing[0] = True
            span = jax.profiler.TraceAnnotation("traced")
            span.__enter__()
            traced = clock()
        one_step()
    t_close = clock()
    window[1] = t_close
    if tracing[0]:
        span.__exit__(None, None, None)
        tracing[0] = False
        traced = (traced, t_close)
        jax.profiler.stop_trace()

    mem_peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
                   for d in devices) if devices[0].platform == "tpu" else 0
    run = Run(cell_name, lanes, arch, fam, setup_s, window[0], t_close,
              list(reqs.values()), steps, pk, traced)
    attempted = sum(any(run.in_window(t) for t in r.token_t)
                    for r in run.requests)
    finished = [r for r in run.requests if len(r.tokens) == r.max_new]

    # free the program before the reference takes the chip
    del engine, plans
    gc.collect()
    jax.clear_caches()

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(mem_peak)}
    out: dict[str, Any] = {}
    if trace:
        kern = devtrace.kernels(files)
        red = devtrace.reduce(devtrace.events(trace_dir, len(devices), kern),
                              run, kern)
        run.trace = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = red["breakdown"]
    metrics = {}
    for m in metrics_for(bench, cell_name, trace):
        v = reader(m["name"], files)(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    check = compare(conf, fam, seed, finished)
    correct = all(holds(c) for c in check.values())
    for name, c in check.items():
        log(f"{name} {c['value']!r} limit "
            f"{'>=' if c.get('at_least') else '<='} {c['limit']!r}")
    return {"correct": correct, "attempted": int(attempted), "failed": 0,
            "metrics": metrics, "device": device, **out, "check": check}


def holds(c: dict) -> bool:
    """A compared number against its limit: at most it, or at least it
    where the entry says ``at_least``."""
    return (c["value"] >= c["limit"] if c.get("at_least")
            else c["value"] <= c["limit"])


def sample(finished: list[Request], seed: int, n: int) -> list[Request]:
    """``n`` finished requests: the longest, then others drawn from the
    seed (every finished request where fewer have finished)."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: (len(r.prompt) + r.max_new,
                                             r.index))
    rest = by_len[:-1]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    return [by_len[-1]] + [rest[j] for j in order[:n - 1]]


def spread(n: int, k: int) -> np.ndarray:
    """At most ``k`` of the positions 0..n-1, evenly spaced, the first (the
    token the prompt's last row produced) and the last included."""
    return np.unique(np.round(np.linspace(0, n - 1, min(n, k))).astype(int))


def compare(conf: dict, fam: model.Family, seed: int,
            finished: list[Request]) -> dict:
    """Served tokens against the family's plain reference, over a sample
    of up to ``sample_requests`` finished requests: at
    ``tokens_per_request`` served positions of each, spread over its
    output, the gap by which the served token's logit lies below the
    reference's best; their mean is held to the configuration's limit,
    and the sample must hold at least ``min_requests`` requests.  (The
    widest gap is logged beside it: it swings with one near-tie and does
    not separate the program from the lower-precision control; PERF.md
    gives both readings.)"""
    lim = conf["correct"]
    picked = sample(finished, seed, lim["sample_requests"])
    check = {"requests_checked": {"value": len(picked),
                                  "limit": lim["min_requests"],
                                  "at_least": True}}
    if not picked:
        return check
    t0 = time.perf_counter()
    gaps, scale = fam.reference.logit_gaps(
        seed, conf["model"], [(r.prompt, r.tokens) for r in picked])
    flat = np.concatenate([g[spread(len(g), lim["tokens_per_request"])]
                           for g in gaps])
    served = [t for r in picked for t in r.tokens]
    log(f"reference: {len(picked)} requests, {flat.size} of "
        f"{len(served)} served tokens compared ({len(set(served))} "
        f"distinct), logit scale "
        f"{scale:.4f}, {float(np.mean(flat > 0)):.4f} of them not the "
        f"reference's best, widest gap {float(flat.max())!r}, "
        f"{time.perf_counter() - t0:.1f} s")
    check["mean_logit_gap"] = {"value": float(flat.mean()),
                               "limit": lim["mean_logit_gap"]}
    return check
