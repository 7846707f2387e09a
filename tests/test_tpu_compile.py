"""The serving path's Pallas kernels compile for a TPU v5e at model widths.

Interpret-mode tests cannot see what the chip's compiler refuses (block
shapes off the (8, 128) tiling, VMEM overruns, ops the VPU lacks).  Each
test here lowers one kernel through its ``ops`` entry point — tiles from
the autotuner, exactly as a serving step picks them — for a v5e that is
described, not attached, and asserts that the compiled HLO holds the
kernel (``tpu_custom_call``).  Widths are starcoder2-3b's (d_model 3072,
d_ff 12288, 24 query / 2 KV heads of 128) and codeqwen1.5-7b's (d_model
4096, d_ff 13440) at 32 packed rows; the decode kernels see 4 lanes of a
512-slot cache in 16-slot pages.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist only
the worker given this file may take it.  Keep these tests in this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.common import set_interpret

I8, I32, F32, BF16 = jnp.int8, jnp.int32, jnp.float32, jnp.bfloat16
M = 32                                 # packed rows per GEMM call
SC_D, SC_FF = 3072, 12288              # starcoder2-3b
CQ_D, CQ_FF = 4096, 13440              # codeqwen1.5-7b
B, HQ, HKV, D = 4, 24, 2, 128          # starcoder2-3b decode lanes/heads
S, PAGE = 512, 16                      # cache slots, page size
N_PAGES = (B + 2) * (S // PAGE) + 1    # the engine's auto-sized pool
CB, CS = 8, 2048                       # the cell's lanes and max_seq
CN_PAGES = (CB + 2) * (CS // PAGE) + 1  # its pool: 1281 pages
LB, LS = 16, 16384                     # starcoder2-3b's published context
LN_PAGES = (LB + 2) * (LS // PAGE) + 1  # its auto-sized pool: 18433 pages
BIG_POOL = 30001                       # a pool sized to fill HBM
CQ_HKV = 32                            # codeqwen1.5-7b's KV heads, G 1


def _paged_int8(b, hq, hkv, n_pages, s):
    return [((b, hq, D), BF16), ((n_pages, PAGE, hkv, D), I8),
            ((n_pages, PAGE, hkv, 1), F32), ((n_pages, PAGE, hkv, D), I8),
            ((n_pages, PAGE, hkv, 1), F32), ((n_pages, PAGE), I32),
            ((b, s // PAGE), I32), ((b,), I32)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels():
    """Pallas backend, interpret off, inside the test only.  Jit caches are
    dropped on both sides: a trace made under these settings must never
    serve a later CPU call of the same shapes."""
    jax.clear_caches()
    prev_backend = ops.set_backend("pallas")
    prev_interpret = set_interpret(False)
    try:
        yield
    finally:
        ops.set_backend(prev_backend)
        set_interpret(prev_interpret)
        jax.clear_caches()


def _w8a8(x, xs, w, ws, **kw):
    return ops.gemm_w8a8(x, xs, w, ws, **kw)


# name -> (fn, [(shape, dtype), ...]); every entry reaches one kernel
CASES = {
    "gemm_w8a8": (_w8a8, [((M, SC_D), I8), ((M, 1), F32),
                          ((SC_D, SC_FF), I8), ((SC_FF,), F32)]),
    "gemm_w8a8_gelu": (
        lambda x, xs, w, ws: _w8a8(x, xs, w, ws, gelu_scale=0.05),
        [((M, SC_D), I8), ((M, 1), F32), ((SC_D, SC_FF), I8),
         ((SC_FF,), F32)]),
    "gemm_w8a8_residual": (
        lambda x, xs, w, ws, r: _w8a8(x, xs, w, ws, residual=r),
        [((M, SC_FF), I8), ((M, 1), F32), ((SC_FF, SC_D), I8),
         ((SC_D,), F32), ((M, SC_D), BF16)]),
    "quant_rows": (ops.quant_rows, [((M, SC_D), F32)]),
    "gated_mlp_w8a8": (
        lambda x, xs, u, us, g, gs: ops.gated_mlp_w8a8(
            x, xs, u, us, g, gs, act="silu", act_scale=0.05),
        [((M, CQ_D), I8), ((M, 1), F32), ((CQ_D, CQ_FF), I8),
         ((CQ_FF,), F32), ((CQ_D, CQ_FF), I8), ((CQ_FF,), F32)]),
    "layernorm_i8": (
        lambda x, g, b: ops.layernorm_i8(x, g, b),
        [((M, SC_D), I32), ((SC_D,), I32), ((SC_D,), I32)]),
    "gelu_i8": (lambda x: ops.gelu_i8(x, 0.05), [((M, SC_FF), I32)]),
    "decode_attention_int8kv": (
        ops.decode_attention_int8kv,
        [((B, HQ, D), BF16), ((B, S, HKV, D), I8), ((B, S, HKV, 1), F32),
         ((B, S, HKV, D), I8), ((B, S, HKV, 1), F32), ((B, S), I32),
         ((B,), I32)]),
    "paged_attention_decode_int8": (
        ops.paged_attention_decode,
        [((B, HQ, D), BF16), ((N_PAGES, PAGE, HKV, D), I8),
         ((N_PAGES, PAGE, HKV, 1), F32), ((N_PAGES, PAGE, HKV, D), I8),
         ((N_PAGES, PAGE, HKV, 1), F32), ((N_PAGES, PAGE), I32),
         ((B, S // PAGE), I32), ((B,), I32)]),
    "paged_attention_decode_bf16": (
        lambda q, pk, pv, pp, pt, qp: ops.paged_attention_decode(
            q, pk, None, pv, None, pp, pt, qp),
        [((B, HQ, D), BF16), ((N_PAGES, PAGE, HKV, D), BF16),
         ((N_PAGES, PAGE, HKV, D), BF16), ((N_PAGES, PAGE), I32),
         ((B, S // PAGE), I32), ((B,), I32)]),
    "paged_attention_decode_int8_cell": (
        ops.paged_attention_decode, _paged_int8(CB, HQ, HKV, CN_PAGES, CS)),
    # the kernel's VMEM must not grow with the table or the pool
    "paged_attention_decode_int8_long_context": (
        ops.paged_attention_decode, _paged_int8(LB, HQ, HKV, LN_PAGES, LS)),
    "paged_attention_decode_int8_large_pool": (
        ops.paged_attention_decode, _paged_int8(CB, HQ, HKV, BIG_POOL, CS)),
    "paged_attention_decode_int8_codeqwen": (
        ops.paged_attention_decode,
        _paged_int8(CB, CQ_HKV, CQ_HKV, CN_PAGES, CS)),
    # group 128: a 512-row K block holds 4 multiplier rows (the layout case)
    "gemm_w4a8": (
        ops.gemm_w4a8,
        [((M, SC_D), I8), ((M, 1), F32), ((SC_D // 2, SC_FF), I8),
         ((SC_D // 128, SC_FF), I8), ((SC_FF,), F32)]),
    "gated_mlp_w4a8": (
        lambda x, xs, u4, um, us, g4, gm, gs: ops.gated_mlp_w4a8(
            x, xs, u4, um, us, g4, gm, gs, act="silu", act_scale=0.05),
        [((M, CQ_D), I8), ((M, 1), F32), ((CQ_D // 2, CQ_FF), I8),
         ((CQ_D // 64, CQ_FF), I8), ((CQ_FF,), F32),
         ((CQ_D // 2, CQ_FF), I8), ((CQ_D // 64, CQ_FF), I8),
         ((CQ_FF,), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, compiled_kernels):
    fn, sig = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in sig]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, name


def test_paged_decode_wrapper_adds_no_arena_copy(one_chip, compiled_kernels):
    """The layer scan of a bucket-1 step at the cell's sizes: each layer
    scatters its token into a layer-stacked paged arena (``_write_paged``)
    and attends through ``ops.paged_attention_decode``.  No instruction
    under ``jit(paged_decode_attention)`` but the kernel itself may make an
    array the size of an arena: the wrapper hands the kernel the arenas in
    their stored shapes (no transpose, no reshape).  Layout copies that XLA
    files under the scatter's op_name (each f32 scale arena, lane-padded
    for the kernel) are outside this check."""
    from repro.models.attention import _write_paged

    layers = 4

    def step(states, pt, q, k, v, pos):
        def layer(carry, st):
            c = _write_paged(dict(st, pt=pt), k, v, pos)
            o = ops.paged_attention_decode(q, c["pk"], c["pks"], c["pv"],
                                           c["pvs"], c["ppos"], pt,
                                           pos[:, 0])
            del c["pt"]
            return carry + o.astype(F32).sum(), c
        return jax.lax.scan(layer, jnp.zeros((), F32), states)

    arena = lambda *tail, dt: jax.ShapeDtypeStruct(
        (layers, CN_PAGES, PAGE) + tail, dt, sharding=one_chip)
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    states = {"pk": arena(HKV, D, dt=I8), "pv": arena(HKV, D, dt=I8),
              "pks": arena(HKV, 1, dt=F32), "pvs": arena(HKV, 1, dt=F32),
              "ppos": arena(dt=I32)}
    hlo = jax.jit(step).lower(
        states, arg((CB, CS // PAGE), I32), arg((CB, HQ, D), BF16),
        arg((CB, 1, HKV, D), BF16), arg((CB, 1, HKV, D), BF16),
        arg((CB, 1), I32)).compile().as_text()
    itemsize = {"s8": 1, "pred": 1, "bf16": 2, "f32": 4, "s32": 4}
    smallest = CN_PAGES * PAGE * 4                   # the ppos arena
    kernels, made = 0, []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]", line)
        if not m or "jit(paged_decode_attention)" not in line:
            continue
        if "tpu_custom_call" in line:
            kernels += 1
            continue
        dims = [int(x) for x in m.group(3).split(",") if x]
        nbytes = itemsize.get(m.group(2), 4)
        for x in dims:
            nbytes *= x
        if CN_PAGES in dims or nbytes >= smallest:
            made.append(line.strip()[:160])
    assert kernels >= 1
    assert not made, made
