"""From a profiler trace to the device's busy time, its idle gaps and the
kernels' device time and roofline shares.

``events(trace_dir)`` reads the ``.xplane.pb`` the profiler wrote into a
small plain record (device ops per chip, host spans); ``reduce`` turns
that record and the run's own bookkeeping into numbers.  The record is
JSON-able, so a trace recorded on the chip can be kept as a test input.

The kernels it costs are found by name, as the harness finds
configurations, families, traffic mixes and metric readers:
``kernels/<kernel>.py`` under the harness's files, named after the HLO
instruction the kernel's custom call lowers to, gives its ``KIND`` (its
key under ``run.trace["kernels"]``) and one of two costs:
``cost_from_hlo(line) -> (ops, bytes, feeds)`` of one call, with the
peak ``PEAK`` it is held to, or ``cost_from_run(run) -> (ops, bytes,
peak_key)`` of all its calls in the traced stretch.
"""
from __future__ import annotations

import glob
import os
import re

import byname
import roofline

HERE = os.path.dirname(os.path.abspath(__file__))

HOST_SPANS = ("traced", "step", "submit")


def options():
    """Profiler options: the host's own annotations, no Python tracer (it
    records every Python call and would slow the host it measures)."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    return o


def kernels(root: str = HERE) -> dict:
    """``<root>/kernels/<kernel>.py`` -> its module, by kernel name."""
    d = os.path.join(root, "kernels")
    return {f[:-3]: byname.module(os.path.join(d, f))
            for f in sorted(os.listdir(d)) if f.endswith(".py")}


def events(trace_dir: str, chips: int, kern: dict) -> dict:
    """The newest trace in ``trace_dir`` as a small plain record:
    {"device": [[kind, start_ns, dur_ns, ops, bytes, feed_ns], ...] per
    chip, "host": [[name, start_ns, dur_ns], ...]}.  ``kind`` is the op's
    instruction name without its number; ``ops`` and ``bytes`` are the
    work of a call of a kernel of ``kern`` costed from its HLO line, and
    ``feed_ns`` the device time of the ops that placed its weights in
    VMEM just before it (all 0 for other ops)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    dev, host, seen = [], [], {}
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m and int(m.group(1)) < chips:
            raw = [(int(ev.start_ns), int(ev.duration_ns), ev.name)
                   for line in plane.lines if line.name == "XLA Ops"
                   for ev in line.events]
            dev.append(device_ops(raw, kern, seen))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"device": dev, "host": sorted(host, key=lambda e: e[1])}


def device_ops(raw, kern: dict, seen: dict | None = None) -> list[list]:
    """[start_ns, dur_ns, HLO line] of one chip's ops -> the record's
    [kind, start_ns, dur_ns, ops, bytes, feed_ns], in time order.  A
    kernel's weight in VMEM was produced by the latest op of its name
    before the call (the one of the same program run and layer); that
    op's time is the call's ``feed_ns``.  ``seen`` caches each distinct
    line's parse (each program's ops repeat)."""
    seen = {} if seen is None else seen
    ops, last = [], {}
    for start, dur, name in sorted(raw, key=lambda e: e[0]):
        if name not in seen:
            kind = op_kind(name)
            k = kern.get(kind)
            cost = (k.cost_from_hlo(name) if hasattr(k, "cost_from_hlo")
                    else (0, 0, ()))
            seen[name] = (kind, instruction(name), *cost)
        kind, instr, n_ops, n_bytes, feeds = seen[name]
        feed = sum(last.get(f, 0) for f in feeds)
        last[instr] = dur
        ops.append([kind, start, dur, n_ops, n_bytes, feed])
    return ops


def union_ns(intervals) -> int:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle [start, end) stretches of [lo, hi) between intervals."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def host_span_at(host, t: int) -> str:
    """The innermost host span covering time t ("none" if none does)."""
    best, best_len = "none", None
    for name, s, d in host:
        if s <= t < s + d and (best_len is None or d < best_len):
            best, best_len = name, d
    return best


# -- kernel attribution ------------------------------------------------------

# an array type in an HLO line, its layout, and the operand's name after it
_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\](\{[^}]*\})?(?:\s+(%[\w.\-]+))?")
_BYTES = {"s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4, "f32": 4}


def array_bytes(dtype: str, dims) -> int:
    out = _BYTES[dtype]
    for x in dims:
        out *= x
    return out


def operand_shapes(text: str) -> list[tuple[str, tuple[int, ...], bool, str]]:
    """(dtype, dims, in VMEM, name) of each array type written in
    ``text``; a layout with memory space ``S(1)`` is in the core's VMEM,
    and the name is the operand's ("" where none follows the type)."""
    return [(t, tuple(int(x) for x in d.split(",") if x), "S(1)" in lay,
             name) for t, d, lay, name in _SHAPE.findall(text)]


# parents whose events span their children's: not leaf device work
CONTROL = ("while", "conditional", "call")
_NAME = re.compile(r"%?([\w.\-]+?)(?:\.\d+)? = ")
_INSTR = re.compile(r"(%[\w.\-]+) = ")


def op_kind(name: str) -> str:
    """The instruction's name without its number: the trace names a device
    op by its HLO line, ``%fusion.57 = bf16[8,256]... fusion(...)`` ->
    ``fusion``; a Pallas call's custom call is named after its kernel."""
    m = _NAME.match(name)
    return m.group(1) if m else name.split(" ")[0].lstrip("%")


def instruction(name: str) -> str:
    """The instruction's name with its number (``%fusion.57``)."""
    m = _INSTR.match(name)
    return m.group(1) if m else name


def classify(name: str, kern: dict):
    """The kind of the benchmark's kernel that a device op is, by the
    kernel name its HLO instruction carries (None for any other op)."""
    k = kern.get(op_kind(name))
    return k.KIND if k else None


def reduce(ev: dict, run, kern: dict) -> dict:
    """busy_s, window_s, the breakdown, and for each kind of the kernels
    in ``kern`` their device time with the least time their work needs
    (``min_s``), over the traced stretch (the host span ``traced``) of
    the record ``ev``.  Every device op is clipped to it; an engine step
    blocks on its sampled tokens, so the device work of the steps that
    started in the stretch lies inside it."""
    host = ev["host"]
    win = [h for h in host if h[0] == "traced"]
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        lo = min(e[1] for ops in ev["device"] for e in ops)
        hi = max(e[1] + e[2] for ops in ev["device"] for e in ops)
    busy, per_op, idle = [], {}, []
    by_kind = {k.KIND: [0.0, 0.0] for k in kern.values()}   # [s, min_s]
    pk = run.peaks if run is not None else {}
    for ops in ev["device"]:
        iv = []
        for base, s, d, n_ops, n_bytes, feed in ops:
            s0, e0 = max(s, lo), min(s + d, hi)
            if e0 <= s0 or base in CONTROL:
                continue
            iv.append((s0, e0))
            per_op[base] = per_op.get(base, 0.0) + (e0 - s0) * 1e-9
            k = kern.get(base)
            if k:
                by_kind[k.KIND][0] += (e0 - s0) * 1e-9
            if hasattr(k, "cost_from_hlo") and pk:
                # a call cut by the stretch's edge counts its share inside;
                # the ops that streamed its weights into VMEM are its time
                part = (e0 - s0) / d
                by_kind[k.KIND][0] += feed * part * 1e-9
                by_kind[k.KIND][1] += roofline.min_seconds(
                    n_ops, n_bytes, pk, k.PEAK) * part
        busy.append(union_ns(iv) * 1e-9)
        for g0, g1 in gaps(iv, lo, hi):
            idle.append((host_span_at(host, (g0 + g1) // 2),
                         (g1 - g0) * 1e-9))
    for k in kern.values():
        if hasattr(k, "cost_from_run") and pk and by_kind[k.KIND][0] > 0:
            ops_, nbytes, peak = k.cost_from_run(run)
            by_kind[k.KIND][1] += roofline.min_seconds(ops_, nbytes, pk,
                                                       peak)
    n = max(len(ev["device"]), 1)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    idle.sort(key=lambda g: -g[1])
    return {
        "busy_s": sum(busy) / n,
        "window_s": (hi - lo) * 1e-9,
        "kernels": {k: {"s": v[0], "min_s": v[1]}
                    for k, v in by_kind.items()},
        "breakdown": {"device_ops": [[k, v] for k, v in top],
                      "idle_gaps": [[k, v] for k, v in idle[:10]]},
    }


def decode_contexts(steps) -> list[int]:
    """The context of every query answered by single-query decode
    attention: the program runs it in the steps whose rows are one token
    a lane (bucket 1), for every lane the step planned, decoding or taking
    its prompt's last token alike; a query at position p attends p + 1
    keys."""
    return [p0 + 1 for s in steps
            if s.spans and all(c == 1 for _, c, _ in s.spans)
            for p0, _, _ in s.spans]
