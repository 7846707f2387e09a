"""Kernel micro-benchmarks: XLA reference path and interpret-mode Pallas.

``--backend jnp`` (default) times the XLA reference path — kernel-exact
semantics, meaningful relative timings.  ``--backend pallas`` runs the same
harness through interpret-mode Pallas: NOT hardware performance (the derived
column carries the work sizes for the roofline; TPU wall-times come from the
dry-run analysis instead), but it exercises the exact kernel + autotuned
block path end-to-end and catches dispatch regressions.

Inputs are generated from a FIXED seed so timings are reproducible run to
run; ``run()`` returns (name, us_per_call, derived) rows that run.py folds
into BENCH_kernels.json.  The fused-epilogue pairs (``*_fused`` vs
``*_unfused``) share inputs, so their delta is exactly the eliminated int32
intermediate traffic (recorded in the derived column).

Fused-vs-unfused protocol: the unfused side runs ONE JITTED DISPATCH PER
ELIMINATED KERNEL (the intermediates materialize between dispatches, as
they do between real unfused kernels), the fused side is a single
dispatch.  A single jit over the unfused composition would let XLA fuse
the very intermediates the kernel fusion eliminates and reduce the
comparison to scheduler noise — per-dispatch staging is what the fused
kernels actually remove.

Rows are grouped into kernel FAMILIES, each with its own fixed-seed RNG.
Full runs on the jnp backend measure every family at both the small and
full shapes (so a full jnp run re-measures every /jnp key the artifact
tracks); the pallas backend ALWAYS uses the small-shape sweep, smoke or
not (interpret mode at the full shapes is prohibitive), and smoke runs
additionally SKIP (rather than fail) any family whose kernels are
unavailable on the requested backend — a gating smoke must not die because
one family cannot run where it is benched.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import inumerics as inum
from repro.kernels import ops

SEED = 0


def _time(fn, *args, reps=3):
    fn(*args)  # compile/warm
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6


def _time_pair(fn_a, fn_b, *args, reps=12):
    """Interleaved min-of-N timing for fused-vs-unfused pairs.

    Alternating the two sides exposes both to the same machine load, and
    taking each side's MINIMUM strips load spikes — the remaining delta
    reflects the work difference (eliminated dispatches + intermediate
    traffic), not scheduler noise.  Plain averaged `_time` calls measured
    seconds apart flip ordering run-to-run on a loaded box.
    """
    fn_a(*args)  # compile/warm
    fn_b(*args)
    best_a = best_b = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a(*args))
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b(*args))
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a * 1e6, best_b * 1e6


def run(backend: str = "jnp", smoke: bool = False,
        strict: bool | None = None) -> list[tuple]:
    """``strict=False`` (the smoke default) skips families whose backend is
    unavailable instead of failing the whole bench."""
    assert backend in ("jnp", "pallas"), backend
    # interpret mode follows the platform: the pallas backend runs
    # interpreted on CPU (correctness timing) and compiled on a TPU
    # interpret mode is slow: shrink the sweep so --backend pallas stays
    # usable as a correctness-timing smoke rather than a coffee break
    small = smoke or backend == "pallas"
    reps = 1 if small else 3
    if strict is None:
        strict = not smoke
    with ops.use_backend(backend):
        return _run_rows(small, reps, backend, strict)


def _run_rows(small: bool, reps: int, backend: str,
              strict: bool = True) -> list[tuple]:
    gemm_shapes = [(64, 256, 256)] if small else [(64, 256, 256),
                                                  (256, 512, 512)]
    families = [
        ("int8_gemm", lambda: _gemm_family(reps, backend, gemm_shapes)),
        ("gated_mlp", lambda: _gated_mlp_family(reps, backend, gemm_shapes)),
        ("gemm_w4a8", lambda: _w4a8_family(reps, backend, gemm_shapes)),
        ("int_softmax", lambda: _softmax_family(
            reps, backend, [(16, 256)] if small else [(16, 256),
                                                      (64, 1024)])),
        ("int_elementwise", lambda: _elementwise_family(
            reps, backend, [(16, 512)] if small else [(16, 512),
                                                      (64, 2048)])),
        ("flash_attention", lambda: _flash_family(
            reps, backend, [128] if small else [128, 512])),
        ("int8_attention", lambda: _int8_attn_family(
            reps, backend, [128] if small else [128, 256])),
        ("int8_kv_decode", lambda: _decode_family(reps, backend)),
        ("paged_decode", lambda: _paged_family(reps, backend)),
    ]
    rows = []
    for name, build in families:
        try:
            rows.extend(build())
        except (NotImplementedError, ImportError) as e:
            if strict:
                raise
            print(f"skip kernel family {name}: "
                  f"unavailable on backend {backend} ({e})", file=sys.stderr)
    return rows


def _gemm_family(reps, backend, shapes):
    rows = []
    for m, k, n in shapes:
        rng = np.random.default_rng(SEED)
        x = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
        w = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
        us = _time(ops.gemm_i8, x, w, reps=reps)
        rows.append((f"kernel/int8_gemm_{m}x{k}x{n}/{backend}", us,
                     f"macs={m*k*n}"))

        # fused requant+GELU epilogue vs the unfused int32-roundtrip
        # composition: one dispatch per unfused kernel (GEMM, then GELU —
        # the int32 accumulator materializes between them), fused = ONE
        s0 = 8.0 / 127.0
        gemm_d = jax.jit(lambda a, b: ops.gemm_i8(a, b).astype(jnp.int32))
        gelu_d = jax.jit(lambda acc: ops.gelu_i8(acc, s0))
        us_f, us_u = _time_pair(
            jax.jit(lambda a, b: ops.gemm_i8_gelu(a, b, s0)),
            lambda a, b: gelu_d(gemm_d(a, b)), x, w, reps=20 * reps)
        rows.append((f"kernel/int8_gemm_gelu_unfused_{m}x{k}x{n}/{backend}",
                     us_u, f"int32_intermediate_bytes={m*n*4}"))
        rows.append((f"kernel/int8_gemm_gelu_fused_{m}x{k}x{n}/{backend}",
                     us_f, "int32_intermediate_bytes=0"))

        # fused requant+residual-add epilogue vs requant-then-add
        rq = inum.compute_requant_params(3e-3, k * 127 * 127)
        res = jnp.asarray(rng.integers(-127, 128, (m, n)), jnp.int8)
        req_d = jax.jit(lambda acc, r: jnp.clip(
            ops.requant(acc, rq).astype(jnp.int32)
            + r.astype(jnp.int32), -128, 127).astype(jnp.int8))
        us_f, us_u = _time_pair(
            jax.jit(lambda a, b, r: ops.gemm_i8_add(a, b, rq, r)),
            lambda a, b, r: req_d(gemm_d(a, b), r), x, w, res,
            reps=20 * reps)
        rows.append((f"kernel/int8_gemm_add_unfused_{m}x{k}x{n}/{backend}",
                     us_u, f"int32_intermediate_bytes={m*n*4}"))
        rows.append((f"kernel/int8_gemm_add_fused_{m}x{k}x{n}/{backend}",
                     us_f, "int32_intermediate_bytes=0"))
    return rows


def _gated_mlp_family(reps, backend, shapes):
    """Fused dual-GEMM gated MLP vs the unfused 2-GEMM composition.

    The unfused w8a8 form is exactly what the model ran before the fusion:
    two scaled-dequant GEMMs over the same quantized activations, the
    integer SiLU of the gate, and the elementwise multiply — the two
    (M, N) bf16 projections materialize between dispatches (each GEMM's
    int32 accumulator is already epilogue-fused in-kernel).  The fused
    form is ONE kernel: the A tile is read once, both accumulators stay
    resident, and no (M, N) intermediate exists at all.
    """
    rows = []
    s_act = 8.0 / 127.0
    for m, k, n in shapes:
        rng = np.random.default_rng(SEED)
        xq = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
        xs = jnp.asarray(np.abs(rng.normal(size=(m, 1))) * 0.01 + 1e-4,
                         jnp.float32)
        wu = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
        wg = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
        us_ = jnp.asarray(np.abs(rng.normal(size=(n,))) + 0.01, jnp.float32)
        gs_ = jnp.asarray(np.abs(rng.normal(size=(n,))) + 0.01, jnp.float32)

        # unfused = one dispatch per eliminated kernel: up GEMM, gate GEMM
        # (two (M, N) accumulators materialize), then activation * multiply
        gemm_d = jax.jit(lambda a, asc, b, bs: ops.gemm_w8a8(a, asc, b, bs))
        act_d = jax.jit(lambda g, h: (ops.silu_i8(
            jnp.clip(jnp.round(g.astype(jnp.float32) / s_act),
                     -128, 127).astype(jnp.int32), s_act)
            .astype(jnp.float32) * ops.silu_out_scale(s_act)
            ).astype(jnp.bfloat16) * h)
        us_f, us_u = _time_pair(
            jax.jit(lambda a, asc: ops.gated_mlp_w8a8(
                a, asc, wu, us_, wg, gs_, act="silu", act_scale=s_act)),
            lambda a, asc: act_d(gemm_d(a, asc, wg, gs_),
                                 gemm_d(a, asc, wu, us_)),
            xq, xs, reps=10 * reps)
        rows.append(
            (f"kernel/gated_mlp_unfused_w8a8_{m}x{k}x{n}/{backend}", us_u,
             f"intermediate_bytes={2*m*n*2}"))
        rows.append((f"kernel/gated_mlp_fused_w8a8_{m}x{k}x{n}/{backend}",
                     us_f, "int32_intermediate_bytes=0"))

        # bf16 pair: the float SwiGLU composition vs the f32-accumulating
        # dual-GEMM (intermediates are the two bf16 projections)
        xf = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
        wuf = jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.bfloat16)
        wgf = jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.bfloat16)
        dot_d = jax.jit(lambda a, b: jnp.dot(
            a, b, preferred_element_type=jnp.bfloat16))
        mul_d = jax.jit(lambda g, h: jax.nn.silu(g) * h)
        us_f, us_u = _time_pair(
            jax.jit(lambda a: ops.gated_mlp(a, wuf, wgf, "silu")),
            lambda a: mul_d(dot_d(a, wgf), dot_d(a, wuf)), xf,
            reps=10 * reps)
        rows.append(
            (f"kernel/gated_mlp_unfused_bf16_{m}x{k}x{n}/{backend}", us_u,
             f"intermediate_bytes={2*m*n*2}"))
        rows.append((f"kernel/gated_mlp_fused_bf16_{m}x{k}x{n}/{backend}",
                     us_f, "intermediate_bytes=0"))
    return rows


def _w4a8_family(reps, backend, shapes):
    """Packed-int4 GEMM family: in-kernel nibble unpack + two-level dequant
    vs the unfused unpack -> int8 group-GEMM composition.

    The fused side never widens the weight stream: packed bytes go HBM ->
    VMEM -> registers.  The unfused side materializes the int8 weight
    tensor (k*n bytes) between dispatches — the real cost of keeping
    weights packed only at rest.  The gated pair additionally shares one A
    tile across both weight streams, like the w8a8 dual-GEMM row above.
    """
    from repro.kernels.quantize import pack_int4, unpack_int4
    rows = []
    group = 64
    s_act = 8.0 / 127.0
    for m, k, n in shapes:
        rng = np.random.default_rng(SEED)
        xq = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
        xs = jnp.asarray(np.abs(rng.normal(size=(m, 1))) * 0.01 + 1e-4,
                         jnp.float32)

        def w4_leaf():
            w4 = pack_int4(jnp.asarray(rng.integers(-8, 8, (k, n)),
                                       jnp.int8))
            qm = jnp.asarray(rng.integers(1, 128, (k // group, n)), jnp.int8)
            ws = jnp.asarray(np.abs(rng.normal(size=(n,))) * 0.001 + 1e-4,
                             jnp.float32)
            return w4, qm, ws

        w4, qm, ws = w4_leaf()
        unpack_d = jax.jit(lambda p: unpack_int4(p, k))

        # unfused group-GEMM over the WIDENED weights: per-group int32 dot,
        # int8-multiplier combine, one float rescale (ref semantics, jitted
        # as a single dispatch so only the unpack is a separate kernel)
        def _combine(a, w8, qmv, wsv, asv):
            aw = a.astype(jnp.int32).reshape(m, k // group, group)
            ww = w8.astype(jnp.int32).reshape(k // group, group, n)
            parts = jnp.einsum("mgk,gkn->gmn", aw, ww)
            acc = jnp.sum(parts * qmv.astype(jnp.int32)[:, None, :], axis=0)
            return (acc.astype(jnp.float32) * wsv * asv).astype(jnp.bfloat16)

        combine_d = jax.jit(_combine)
        us_f, us_u = _time_pair(
            jax.jit(lambda a, asv: ops.gemm_w4a8(a, asv, w4, qm, ws)),
            lambda a, asv: combine_d(a, unpack_d(w4), qm, ws, asv),
            xq, xs, reps=10 * reps)
        rows.append(
            (f"kernel/gemm_w4a8_unfused_{m}x{k}x{n}_g{group}/{backend}",
             us_u, f"int8_weight_bytes={k*n}"))
        rows.append(
            (f"kernel/gemm_w4a8_fused_{m}x{k}x{n}_g{group}/{backend}",
             us_f, "int8_weight_bytes=0"))

        # gated pair: fused dual packed-int4 GEMM vs unpack x2 -> combine
        # GEMM x2 -> integer activation * multiply
        u4, um, us_ = w4_leaf()
        g4, gm, gs_ = w4_leaf()
        act_d = jax.jit(lambda g, h: (ops.silu_i8(
            jnp.clip(jnp.round(g.astype(jnp.float32) / s_act),
                     -128, 127).astype(jnp.int32), s_act)
            .astype(jnp.float32) * ops.silu_out_scale(s_act)
            ).astype(jnp.bfloat16) * h)
        us_f, us_u = _time_pair(
            jax.jit(lambda a, asv: ops.gated_mlp_w4a8(
                a, asv, u4, um, us_, g4, gm, gs_, act="silu",
                act_scale=s_act)),
            lambda a, asv: act_d(
                combine_d(a, unpack_d(g4), gm, gs_, asv),
                combine_d(a, unpack_d(u4), um, us_, asv)),
            xq, xs, reps=10 * reps)
        rows.append(
            (f"kernel/gatedmlp_w4a8_unfused_{m}x{k}x{n}_g{group}/{backend}",
             us_u, f"int8_weight_bytes={2*k*n};intermediate_bytes={2*m*n*2}"))
        rows.append(
            (f"kernel/gatedmlp_w4a8_fused_{m}x{k}x{n}_g{group}/{backend}",
             us_f, "int8_weight_bytes=0"))
    return rows


def _softmax_family(reps, backend, shapes):
    rows = []
    for rs, cs in shapes:
        rng = np.random.default_rng(SEED)
        xs = jnp.asarray(rng.integers(-127, 128, (rs, cs)), jnp.int32)
        us = _time(lambda a: ops.softmax_i8(a, 0.05), xs, reps=reps)
        rows.append((f"kernel/int_softmax_{rs}x{cs}/{backend}", us,
                     f"elems={rs*cs}"))
    return rows


def _elementwise_family(reps, backend, shapes):
    rows = []
    for rl, cl in shapes:
        rng = np.random.default_rng(SEED)
        xl = jnp.asarray(rng.integers(-127, 128, (rl, cl)), jnp.int32)
        g = jnp.asarray(rng.integers(32, 127, (cl,)), jnp.int32)
        b = jnp.zeros((cl,), jnp.int32)
        us = _time(lambda a: ops.layernorm_i8(a, g, b), xl, reps=reps)
        rows.append((f"kernel/int_layernorm_{rl}x{cl}/{backend}", us,
                     f"elems={rl*cl}"))
        us = _time(lambda a: ops.gelu_i8(a, 0.05), xl, reps=reps)
        rows.append((f"kernel/int_gelu_{rl}x{cl}/{backend}", us,
                     f"elems={rl*cl}"))
    return rows


def _flash_family(reps, backend, seqs):
    rows = []
    for s in seqs:
        rng = np.random.default_rng(SEED)
        q = jnp.asarray(rng.normal(size=(2, 8, s, 64)), jnp.float32)
        us = _time(lambda a: ops.attention(a, a, a, causal=True), q,
                   reps=reps)
        rows.append((f"kernel/flash_attention_{s}/{backend}", us,
                     f"flops={2*2*8*s*s*64*2}"))
    return rows


def _int8_attn_family(reps, backend, seqs):
    rows = []
    for si in seqs:
        rng = np.random.default_rng(SEED)
        qi = jnp.asarray(rng.integers(-127, 128, (1, 4, si, 64)), jnp.int8)
        us = _time(lambda a: ops.attention_i8(a, a, a, scale=0.002), qi,
                   reps=reps)
        rows.append((f"kernel/int8_attention_{si}/{backend}", us,
                     "work=int8 QK+softmax+PV"))

        # exact per-(token, head) PV dequant variant (serving prefill path)
        vsc = jnp.asarray(
            np.abs(rng.normal(size=(1, 4, si, 1))) * 0.01 + 1e-4,
            jnp.float32)
        us = _time(lambda a, s_: ops.attention_i8(a, a, a, scale=0.002,
                                                  v_scale=s_), qi, vsc,
                   reps=reps)
        rows.append((f"kernel/int8_attention_pv_{si}/{backend}", us,
                     "work=int8 QK+softmax+f32 PV dequant"))
    return rows


def _decode_family(reps, backend):
    # serving hot path: int8-KV single-token decode attention
    rng = np.random.default_rng(SEED)
    sd, hq, hkv, d = (128, 8, 2, 64)
    qd = jnp.asarray(rng.normal(size=(2, hq, d)), jnp.float32)
    kq = jnp.asarray(rng.integers(-127, 128, (2, sd, hkv, d)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (2, sd, hkv, d)), jnp.int8)
    ks = jnp.asarray(np.abs(rng.normal(size=(2, sd, hkv, 1))) + 1e-3,
                     jnp.float32)
    vs = jnp.asarray(np.abs(rng.normal(size=(2, sd, hkv, 1))) + 1e-3,
                     jnp.float32)
    pos = jnp.asarray(np.tile(np.arange(sd), (2, 1)), jnp.int32)
    qpos = jnp.full((2,), sd - 1, jnp.int32)
    us = _time(lambda *a: ops.decode_attention_int8kv(*a),
               qd, kq, ks, vq, vs, pos, qpos, reps=reps)
    return [(f"kernel/int8_kv_decode_{sd}/{backend}", us,
             f"cache_bytes={2*2*sd*hkv*d}")]


def _paged_inputs(rng, npg=17, ps=16, b=2, hkv=2, d=64):
    """Fixed-seed paged arena + full per-lane page chains (page 0 null)."""
    mp = (npg - 1) // b
    pk = jnp.asarray(rng.integers(-127, 128, (npg, ps, hkv, d)), jnp.int8)
    pv = jnp.asarray(rng.integers(-127, 128, (npg, ps, hkv, d)), jnp.int8)
    pks = jnp.asarray(np.abs(rng.normal(size=(npg, ps, hkv, 1))) + 1e-3,
                      jnp.float32)
    pvs = jnp.asarray(np.abs(rng.normal(size=(npg, ps, hkv, 1))) + 1e-3,
                      jnp.float32)
    ppos = np.zeros((npg, ps), np.int32)
    pt = np.zeros((b, mp), np.int32)
    for lane in range(b):
        for j in range(mp):
            pid = 1 + lane * mp + j
            pt[lane, j] = pid
            ppos[pid] = np.arange(j * ps, (j + 1) * ps)
    ppos[0] = -1
    qpos = jnp.full((b,), mp * ps - 1, jnp.int32)
    return (pk, pks, pv, pvs, jnp.asarray(ppos), jnp.asarray(pt), qpos), mp


def _paged_family(reps, backend):
    """Paged decode attention (gather through the page table) next to the
    dense-span decode row above — the delta is the gather indirection."""
    rng = np.random.default_rng(SEED)
    ps, hq, d = 16, 8, 64
    args, mp = _paged_inputs(rng, ps=ps, d=d)
    qd = jnp.asarray(rng.normal(size=(2, hq, d)), jnp.float32)
    us = _time(lambda *a: ops.paged_attention_decode(*a), qd, *args,
               reps=reps)
    return [(f"kernel/paged_decode_{mp}x{ps}/{backend}", us,
             f"pages={mp};page_slots={ps};cache_bytes={2*2*mp*ps*2*d}")]


# ---------------------------------------------------------------------------
# measured-cache sweep runner (`--sweep`)
# ---------------------------------------------------------------------------


def _sweep_timer(fn):
    """One timed call after a warm call — interpret-mode Pallas is slow
    enough that relative candidate ordering is stable at a single rep; on
    a TPU, where the kernels run compiled, raise reps in the loop below."""
    def timer(blocks):
        f = lambda: fn(*blocks)
        jax.block_until_ready(f())
        t0 = time.perf_counter()
        jax.block_until_ready(f())
        return (time.perf_counter() - t0) * 1e6

    return timer


def sweep(backend: str = "pallas", families: tuple[str, ...] = (),
          reps: int = 1) -> list[str]:
    """Populate the measured cache for every tracked autotune key family.

    For each family, times the REAL kernel (or, for the packed/paged
    serving families, the XLA cache-backed attention those block sizes
    actually drive) over the same candidate lattice the cost model scores,
    at the bench shapes, and records the fastest blocks under the exact
    lookup key via ``autotune.measure`` — written to
    ``autotune.cache_path()`` (``REPRO_AUTOTUNE_CACHE`` overridable), the
    JSON every ``ops.py`` entry point consults before the cost table.

    On a TPU the kernels run compiled and the numbers are hardware truth;
    on CPU the kernel families run interpret-mode Pallas — functionally
    exact, useful for exercising the loop, not for real tile choices.
    Entries are keyed by device kind, so a CPU sweep never chooses a
    chip's tiles.  Returns the recorded keys.
    """
    from repro.kernels import autotune
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.int8_flash_attention import int8_flash_attention
    from repro.kernels.int8_gemm import dual_gemm_gated, int8_gemm
    from repro.kernels.int8_kv_decode_attention import int8_kv_decode_attention
    from repro.kernels.int_softmax import int_softmax
    from repro.kernels.autotune import _divisor_tiles
    from repro.kernels.common import pad_to
    from repro.models.attention import _read_paged, _sdpa

    rng = np.random.default_rng(SEED)
    entries = []

    def gemm_cands(m, k, n):
        up = lambda x, a: -(-x // a) * a
        return [(bm, bn, bk)
                for bm in autotune._GEMM_BMS if bm <= max(up(m, 8), 8)
                for bn in autotune._GEMM_BNS if bn <= max(up(n, 128), 128)
                for bk in autotune._GEMM_BKS if bk <= max(up(k, 128), 128)]

    # GEMM + dual-GEMM gated MLP at the bench shape
    m, k, n = 64, 256, 256
    x8 = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w8 = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    w8b = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    entries.append((
        f"gemm/{m}x{k}x{n}/int8/{backend}", gemm_cands(m, k, n),
        _sweep_timer(lambda bm, bn, bk: int8_gemm(
            pad_to(x8, (bm, bk)), pad_to(w8, (bk, bn)),
            bm=bm, bn=bn, bk=bk))))
    entries.append((
        f"gatedmlp/{m}x{k}x{n}/int8/{backend}", gemm_cands(m, k, n),
        _sweep_timer(lambda bm, bn, bk: dual_gemm_gated(
            pad_to(x8, (bm, bk)), pad_to(w8, (bk, bn)),
            pad_to(w8b, (bk, bn)), act="silu", out_dtype=jnp.int32,
            bm=bm, bn=bn, bk=bk))))

    # packed-int4 W4A8 twins: same lattice restricted to group-aligned bk
    from repro.kernels.int8_gemm import dual_int4_gemm_gated, int4_gemm
    from repro.kernels.quantize import pack_int4
    g4_ = 64
    w4s = pack_int4(jnp.asarray(rng.integers(-8, 8, (k, n)), jnp.int8))
    w4g = pack_int4(jnp.asarray(rng.integers(-8, 8, (k, n)), jnp.int8))
    qmu = jnp.asarray(rng.integers(1, 128, (k // g4_, n)), jnp.int8)
    qmg = jnp.asarray(rng.integers(1, 128, (k // g4_, n)), jnp.int8)
    ws4 = jnp.asarray(np.abs(rng.normal(size=(1, n))) * 0.001 + 1e-4,
                      jnp.float32)
    xs4 = jnp.asarray(np.abs(rng.normal(size=(m, 1))) * 0.01 + 1e-4,
                      jnp.float32)
    w4_cands = [c for c in gemm_cands(m, k, n) if c[2] % g4_ == 0]
    entries.append((
        f"gemm_w4a8/{m}x{k}x{n}/g{g4_}/{backend}", w4_cands,
        _sweep_timer(lambda bm, bn, bk: int4_gemm(
            pad_to(x8, (bm, bk)), pad_to(w4s, (bk // 2, bn)),
            pad_to(qmu, (bk // g4_, bn)), pad_to(ws4, (1, bn)),
            pad_to(xs4, (bm, 1)), group=g4_, bm=bm, bn=bn, bk=bk))))
    entries.append((
        f"gatedmlp_w4a8/{m}x{k}x{n}/g{g4_}/{backend}", w4_cands,
        _sweep_timer(lambda bm, bn, bk: dual_int4_gemm_gated(
            pad_to(x8, (bm, bk)), pad_to(w4s, (bk // 2, bn)),
            pad_to(qmu, (bk // g4_, bn)), pad_to(ws4, (1, bn)),
            pad_to(w4g, (bk // 2, bn)), pad_to(qmg, (bk // g4_, bn)),
            pad_to(ws4, (1, bn)), pad_to(xs4, (bm, 1)), group=g4_,
            act="silu", act_scale=8.0 / 127.0, bm=bm, bn=bn, bk=bk))))

    # flash attention + PV-dequant variant
    s, d = 64, 64
    qf = jnp.asarray(rng.normal(size=(1, 2, s, d)), jnp.float32)
    attn_cands = [(bq, bk) for bq in _divisor_tiles(s)
                  for bk in _divisor_tiles(s)]
    entries.append((
        f"attn/{s}x{s}x{d}/bf16/{backend}", attn_cands,
        _sweep_timer(lambda bq, bk: flash_attention(
            qf, qf, qf, causal=True, bq=bq, bk=bk))))
    qi = jnp.asarray(rng.integers(-127, 128, (1, 2, s, d)), jnp.int8)
    vsc = jnp.asarray(np.abs(rng.normal(size=(1, 2, s, 1))) + 1e-3,
                      jnp.float32)
    entries.append((
        f"attnpv/{s}x{s}x{d}/int8/{backend}", attn_cands,
        _sweep_timer(lambda bq, bk: int8_flash_attention(
            qi, qi, qi, 0.002, v_scale=vsc, bq=bq, bk=bk))))

    # int8-KV decode (dense span) — key family has no backend suffix
    sd, hq, hkv = 128, 8, 2
    qd = jnp.asarray(rng.normal(size=(2, hq, d)), jnp.float32)
    kq = jnp.asarray(rng.integers(-127, 128, (2, sd, hkv, d)), jnp.int8)
    ksc = jnp.asarray(np.abs(rng.normal(size=(2, sd, hkv, 1))) + 1e-3,
                      jnp.float32)
    dpos = jnp.asarray(np.tile(np.arange(sd), (2, 1)), jnp.int32)
    dqpos = jnp.full((2,), sd - 1, jnp.int32)
    g = hq // hkv
    entries.append((
        f"decode/{sd}x{d}x{g}",
        [(bk,) for bk in autotune.decode_tiles(sd)],
        _sweep_timer(lambda bk: int8_kv_decode_attention(
            qd, kq, ksc, kq, ksc, dpos, dqpos, bk=bk))))

    # row-wise (softmax representative for the family)
    rs, cs = 16, 256
    xs = jnp.asarray(rng.integers(-127, 128, (rs, cs)), jnp.int32)
    entries.append((
        f"rowwise/{rs}x{cs}/int32", [(bm,) for bm in (8, 16, 32, 64, 128)],
        _sweep_timer(lambda bm: int_softmax(
            pad_to(xs, (bm, 1)), 0.05, bm=bm))))

    # packed + paged serving families: their blocks drive the XLA
    # cache-backed attention (models/attention.py), so that is what the
    # timer runs — recorded under this backend's key because the lookup is
    # keyed on ops.backend() regardless of which path executes.  Shapes
    # and arch mirror the e2e serve bench (codeqwen reduced, max_seq 128,
    # mid budget bucket), so the recorded keys are EXACTLY what a serving
    # forward looks up — not a synthetic shape no lookup can hit.
    from repro.configs import get_config
    serve_cfg = get_config("codeqwen1.5-7b", reduced=True)
    serve_arch, d_serve = serve_cfg.name, serve_cfg.head_dim
    t_b, skv, ps = 8, 128, 16
    b_l = 2
    qp = jnp.asarray(rng.normal(size=(b_l, t_b, 4, d_serve)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(b_l, skv, 2, d_serve)), jnp.bfloat16)
    qpp = jnp.asarray(np.tile(np.arange(skv - t_b, skv), (b_l, 1)),
                      jnp.int32)
    kpp = jnp.asarray(np.tile(np.arange(skv), (b_l, 1)), jnp.int32)
    sdpa = jax.jit(lambda chunk: _sdpa(
        qp, kc, kc, qpp, kpp, 0.125, jnp.bfloat16, causal=True,
        valid=kpp >= 0, chunk=chunk), static_argnums=0)
    entries.append((
        f"packed/{t_b}x{skv}x{d_serve}/{serve_arch}/{backend}",
        [(bq, skv) for bq in _divisor_tiles(t_b)],
        _sweep_timer(lambda bq, bk: sdpa(max(bq, 1)))))

    from repro.models.attention import init_paged_cache
    npg = b_l * (skv // ps) + 1
    cache = init_paged_cache(serve_cfg, b_l, npg, ps, skv // ps, int8=False)
    cache["pt"] = jnp.asarray(
        np.arange(1, npg, dtype=np.int32).reshape(b_l, -1))
    cache["ppos"] = jnp.asarray(np.concatenate(
        [np.full((1, ps), -1, np.int32)]
        + [np.arange(j * ps, (j + 1) * ps, dtype=np.int32).reshape(1, ps)
           for _ in range(b_l) for j in range(skv // ps)]))

    def paged_path(chunk):
        kv, vv, kpos = _read_paged(cache, jnp.bfloat16)
        return _sdpa(qp, kv, vv, qpp, kpos, 0.125, jnp.bfloat16,
                     causal=True, valid=kpos >= 0, chunk=chunk)

    paged_jit = jax.jit(paged_path, static_argnums=0)
    # the XLA gather path consumes only the query chunk (bq); keep the
    # table's KV block in the recorded entry rather than sweeping noise
    _, bk_tab = autotune.paged_blocks(t_b, ps, skv, d_serve,
                                      arch=serve_arch, backend=backend)
    entries.append((
        f"paged/{t_b}x{ps}x{d_serve}/{serve_arch}/{backend}",
        [(bq, bk_tab) for bq in _divisor_tiles(t_b)],
        _sweep_timer(lambda bq, bk: paged_jit(max(bq, 1)))))

    # MoE group size: time the real gshard forward per candidate group by
    # steering the in-process measured-cache view, then record the winner
    import repro.kernels.autotune as at
    from repro.configs import get_config
    from repro.models.moe import init_moe_params, moe
    from repro.models.lm import exec_mode
    mcfg = get_config("mixtral-8x7b", reduced=True)
    mp_ = init_moe_params(jax.random.PRNGKey(SEED), mcfg)
    xt = jnp.asarray(rng.normal(size=(2, 64, mcfg.d_model)), jnp.bfloat16)
    t_tok = int(np.prod(xt.shape[:2]))
    ff = mcfg.moe_d_ff or mcfg.d_ff
    moe_key = (f"moe/{t_tok}x{mcfg.d_model}x{ff}/"
               f"{mcfg.n_experts}x{mcfg.n_experts_per_tok}x"
               f"{mcfg.capacity_factor:g}")

    def moe_timer(blocks):
        at._measured()[moe_key] = {"blocks": [blocks[0]], "us": 0.0}
        at.moe_group_size.cache_clear()
        f = jax.jit(lambda a: moe(mp_, a, mcfg, exec_mode(mcfg)))
        jax.block_until_ready(f(xt))
        t0 = time.perf_counter()
        jax.block_until_ready(f(xt))
        del at._measured()[moe_key]
        at.moe_group_size.cache_clear()
        return (time.perf_counter() - t0) * 1e6

    entries.append((
        moe_key, [(sg,) for sg in (32, 64, 128) if t_tok % sg == 0],
        moe_timer))

    recorded = []
    for key, cands, timer in entries:
        fam = key.split("/", 1)[0]
        if families and fam not in families:
            continue
        best = autotune.measure(key, cands, timer)
        recorded.append(key)
        print(f"sweep {key}: best={best} "
              f"({len(cands)} candidates)", file=sys.stderr)
    autotune.reset_measured_cache()  # subsequent lookups see the new file
    return recorded


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=["jnp", "pallas"], default="jnp",
                    help="XLA reference path or interpret-mode Pallas")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sweep", action="store_true",
                    help="measured-cache sweep: time every tracked autotune"
                         " key family on --backend and write the fastest "
                         "blocks to autotune.cache_path()")
    ap.add_argument("--families", default="",
                    help="comma list to restrict --sweep (e.g. gemm,attn)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.sweep:
        from repro.kernels import ops as _ops
        from repro.kernels.common import interpret_mode
        # interpret mode follows the platform: compiled on a TPU, so the
        # sweep times hardware kernels there; interpreted on CPU
        with _ops.use_backend(args.backend):
            fams = tuple(f for f in args.families.split(",") if f)
            print(f"sweep: backend={args.backend} "
                  f"interpret={interpret_mode()}", file=sys.stderr)
            keys = sweep(backend=args.backend, families=fams)
        from repro.kernels import autotune
        print(f"recorded {len(keys)} keys -> {autotune.cache_path()}")
        return
    for name, us, derived in run(backend=args.backend, smoke=args.smoke):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
