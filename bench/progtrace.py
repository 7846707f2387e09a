"""The program's own spans and each device op's named scope, from the
``.xplane.pb`` of a traced run, for the per-layer metrics that join them.

The serving engine opens profiler spans (``engine.step`` and its stages,
``engine.pool``, ``host.gc``: ``src/repro/serve/tracing.py``) on the same
clock as the device's ops, and its packed step names its parts with
``jax.named_scope`` (``SCOPES``).  A TPU's ``XLA Ops`` events carry
neither the scope nor a program id, only the HLO line; so each op is
placed in the ``XLA Modules`` event around it, and its instruction looked
up in that program's HLO, which the profiler keeps in the trace's
``/host:metadata`` plane (a serialized ``HloProto`` per program, read
here with a small protobuf walker: the field numbers are XLA's).

``record(trace_dir)`` gives a small plain record, parsed once per process
for each file (memoised by path and modification time):
{"spans": [[name, start_ns, dur_ns, args], ...] of the host (the
benchmark's spans and the program's), "device": [[[scope, start_ns,
dur_ns], ...] per chip]} with leaf ops only and scope "" where the op
carries none.

Readers receive only ``run``.  The harness writes the trace to
``<root>/.bench_out/trace``, where ``root`` is the parent of ``bench/`` in
real runs and the harness's ``files`` directory itself in its tests; a
reader lives in ``<files>/metrics/``, so ``of_run(run, __file__)`` looks in
``<files>/.bench_out/trace`` first and then in the directory above.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import sys

import devtrace

SCOPES = ("embed", "norm", "attn.qkv", "attn.kv_write", "attn.gather",
          "attn.paged_decode", "attn.out", "mlp", "head", "sample")
WAIT = "engine.wait"
OUTSIDE = "outside the engine"


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def program_span(name: str) -> bool:
    return name.startswith(("engine.", "host."))


# -- finding and reading the trace -------------------------------------------

def trace_dir(reader_file: str) -> str | None:
    files = os.path.dirname(os.path.dirname(os.path.abspath(reader_file)))
    for root in (files, os.path.dirname(files)):
        d = os.path.join(root, ".bench_out", "trace")
        if os.path.isdir(d):
            return d
    return None


def of_run(run, reader_file: str) -> dict | None:
    """The record of ``run``'s traced stretch, or None where the run was
    not traced or its trace is not found."""
    if run is None or run.traced is None:
        return None
    d = trace_dir(reader_file)
    return record(d) if d else None


def record(trace_dir: str) -> dict | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    return _parse(files[-1], os.path.getmtime(files[-1]))


@functools.lru_cache(maxsize=2)
def _parse(path: str, mtime: float) -> dict:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        programs = hlo_scopes(f.read())
    pd = ProfileData.from_file(path)
    spans, device = [], []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in devtrace.HOST_SPANS or program_span(
                            ev.name):
                        args = {k: v for k, v in ev.stats
                                if not k.startswith("_")}
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns), args])
        elif re.match(r"/device:TPU:\d+$", plane.name):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = ([(int(e.start_ns), int(e.duration_ns), e.name)
                     for e in lines["XLA Modules"].events]
                    if "XLA Modules" in lines else [])
            ops = [(int(e.start_ns), int(e.duration_ns), e.name)
                   for e in lines["XLA Ops"].events]
            if ops:
                device.append(scoped_ops(ops, mods, programs))
    spans.sort(key=lambda s: s[1])
    return {"spans": spans, "device": device}


def scoped_ops(ops, modules, programs: dict) -> list[list]:
    """[start_ns, dur_ns, HLO line] of one chip's ops -> [scope, start_ns,
    dur_ns] of its leaf ops, in time order; each op's program is the
    ``XLA Modules`` event that holds its start."""
    mods = sorted(modules)
    out, m = [], 0
    for start, dur, line in sorted(ops):
        if devtrace.op_kind(line) in devtrace.CONTROL:
            continue
        while m < len(mods) and mods[m][0] + mods[m][1] <= start:
            m += 1
        table = {}
        if m < len(mods) and mods[m][0] <= start:
            table = programs.get(mods[m][2], {})
        inst = devtrace.instruction(line).lstrip("%")
        out.append([scope_of(table.get(inst, "")), start, dur])
    return out


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an op's ``op_name`` path ("" for
    none); XLA joins the names of fused ops with ';'."""
    for part in reversed(re.split(r"[/;]", op_name)):
        if part in SCOPES:
            return part
    return ""


# -- a protobuf walker for the programs' HLO ---------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def fields(buf):
    """(field number, value) of a serialized message: an int for varints,
    a memoryview for length-delimited fields (fixed-width ones skipped)."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, v


def _ints(v) -> list[int]:
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


# XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map entry: value 2),
# .stat_metadata 5 (map entry: value 2); XEventMetadata.name 2, .stats 5;
# XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .bytes_value 6
def hlo_scopes(xspace: bytes) -> dict:
    """{program: {instruction: op_name}} from the serialized HLO the
    profiler keeps per program in the ``/host:metadata`` plane, keyed by
    the module's name as the ``XLA Modules`` events give it
    (``jit_f(123)``)."""
    out = {}
    for num, plane in fields(xspace):
        if num != 1:
            continue
        name, events, stat_names = None, [], {}
        for f, v in fields(plane):
            if f == 2:
                name = bytes(v).decode()
                if name != "/host:metadata":
                    break
            elif f == 4:
                events.append(dict(fields(v)).get(2))
            elif f == 5:
                sm = dict(fields(dict(fields(v))[2]))
                stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        if name != "/host:metadata":
            continue
        for ev in events:
            if ev is None:
                continue
            ev_name, protos = "", []
            for f, v in fields(ev):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    st = dict(fields(v))
                    if 6 in st:
                        protos.append((st.get(1, 0), st[6]))
            for sid, proto in protos:
                if stat_names.get(sid) == "Hlo Proto":
                    out[ev_name] = _module_op_names(proto)
    return out


# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2, .id 5; HloInstructionProto.name 1,
# .metadata 7, .called_computation_ids 38; OpMetadata.op_name 2
def _module_op_names(hlo_proto) -> dict:
    """{instruction name: op_name} of one program; an instruction with no
    op_name of its own (a fusion XLA made) takes the first one in the
    computations it calls."""
    module = dict(fields(hlo_proto)).get(1, b"")
    insts, comp_names = [], {}
    for f, comp in fields(module):
        if f != 3:
            continue
        cid, names = None, []
        for g, v in fields(comp):
            if g == 5:
                cid = v
            elif g == 2:
                name, op, called = "", "", []
                for h, w in fields(v):
                    if h == 1:
                        name = bytes(w).decode()
                    elif h == 7:
                        op = bytes(dict(fields(w)).get(2, b"")).decode()
                    elif h == 38:
                        called += _ints(w)
                insts.append((name, op, called))
                if op:
                    names.append(op)
        comp_names[cid] = names
    table = {}
    for name, op, called in insts:
        if not op:
            op = next((n for c in called for n in comp_names.get(c, [])), "")
        table[name] = op
    return table


# -- the numbers ---------------------------------------------------------------

def stretch(rec: dict) -> tuple[int, int] | None:
    """The traced stretch: the benchmark's ``traced`` span."""
    for name, s, d, _ in rec["spans"]:
        if name == "traced":
            return s, s + d
    return None


def host_ms_per_step(rec: dict) -> float | None:
    """Mean over the ``engine.step`` spans that start in the traced
    stretch of the span's duration minus its ``engine.wait`` children: the
    host's own time per step, outside waiting on the chip."""
    win = stretch(rec)
    if win is None:
        return None
    lo, hi = win
    steps = [(s, s + d) for n, s, d, _ in rec["spans"]
             if n == "engine.step" and lo <= s < hi]
    if not steps:
        return None
    waits = [(s, s + d) for n, s, d, _ in rec["spans"] if n == WAIT]
    own = 0
    for s, e in steps:
        own += (e - s) - sum(min(we, e) - max(ws, s) for ws, we in waits
                             if ws < e and we > s)
    return 1e-6 * own / len(steps)


def innermost(spans, lo: int, hi: int) -> list[tuple[int, int, str]]:
    """[lo, hi) cut into pieces, each named by the shortest program span
    covering it (``OUTSIDE`` where none does)."""
    iv = [(s, s + d, n) for n, s, d, _ in spans
          if program_span(n) and s < hi and s + d > lo]
    cuts = sorted({lo, hi, *(max(s, lo) for s, _, _ in iv),
                   *(min(e, hi) for _, e, _ in iv)})
    starts = sorted(iv)
    out, active, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(starts) and starts[k][0] <= a:
            active.append(starts[k])
            k += 1
        active = [x for x in active if x[1] > a]
        name = min(active, key=lambda x: x[1] - x[0])[2] if active \
            else OUTSIDE
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_by_span(rec: dict) -> tuple[dict, list, float] | None:
    """The device's idle time in the traced stretch, each gap split over
    the program spans covering it, by time: ({span name: idle s},
    [(gap s, {span name: s}), ...] longest first, stretch s), averaged
    over chips."""
    win = stretch(rec)
    if win is None or not rec["device"] or not any(
            n == "engine.step" for n, *_ in rec["spans"]):
        return None
    lo, hi = win
    pieces = innermost(rec["spans"], lo, hi)
    ends = [b for _, b, _ in pieces]
    per, gaps = {}, []
    for ops in rec["device"]:
        busy = [(max(s, lo), min(s + d, hi)) for _, s, d in ops
                if s < hi and s + d > lo]
        for g0, g1 in devtrace.gaps(busy, lo, hi):
            split = {}
            k = bisect.bisect_right(ends, g0)
            while k < len(pieces) and pieces[k][0] < g1:
                a, b, name = pieces[k]
                k += 1
                t = (min(b, g1) - max(a, g0)) * 1e-9
                split[name] = split.get(name, 0.0) + t
                per[name] = per.get(name, 0.0) + t
            gaps.append(((g1 - g0) * 1e-9, split))
    n = len(rec["device"])
    gaps.sort(key=lambda g: -g[0])
    return {k: v / n for k, v in per.items()}, gaps, (hi - lo) * 1e-9


def host_idle_share(rec: dict) -> float | None:
    """The device's idle time covered by a program span other than
    ``engine.wait``, as a percentage of the traced stretch: the part of
    ``idle_share`` that the engine's host work causes (None for a program
    with no ``engine.step`` spans)."""
    got = idle_by_span(rec)
    if got is None:
        return None
    per, gaps, window = got
    log("idle s by program span: " + ", ".join(
        f"{k} {v:.6f}" for k, v in sorted(per.items(), key=lambda kv: -kv[1])))
    for g, split in gaps[:10]:
        log(f"idle gap {g * 1e3:.3f} ms: " + ", ".join(
            f"{k} {v * 1e3:.3f}" for k, v in
            sorted(split.items(), key=lambda kv: -kv[1])))
    host = sum(v for k, v in per.items() if k not in (WAIT, OUTSIDE))
    return 100.0 * host / window


def device_s_by_scope(rec: dict) -> tuple[dict, float] | None:
    """({scope: device s}, stretch s) of the leaf ops, clipped to the
    traced stretch and averaged over chips ("" = no scope)."""
    win = stretch(rec)
    if win is None or not rec["device"]:
        return None
    lo, hi = win
    per = {}
    for ops in rec["device"]:
        for scope, s, d in ops:
            t = min(s + d, hi) - max(s, lo)
            if t > 0:
                per[scope] = per.get(scope, 0.0) + t * 1e-9
    n = len(rec["device"])
    return {k: v / n for k, v in per.items()}, (hi - lo) * 1e-9


def scope_share(rec: dict, scope: str) -> float | None:
    """Device time under ``scope`` as a percentage of the traced stretch
    (None for a program whose ops carry none of ``SCOPES``)."""
    got = device_s_by_scope(rec)
    if got is None or not any(got[0]):
        return None
    per, window = got
    log("device s by scope: " + ", ".join(
        f"{k or '(none)'} {v:.6f}"
        for k, v in sorted(per.items(), key=lambda kv: -kv[1])))
    return 100.0 * per.get(scope, 0.0) / window
