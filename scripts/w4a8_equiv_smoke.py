"""CI smoke: the fused packed-int4 (W4A8) decode path must be
BIT-IDENTICAL to the unfused unpack -> int8 group-GEMM composition.

Drains the same 4-request greedy workload twice per arch — once through
the fused in-kernel-dequant pipeline (``int4_gemm`` /
``dual_int4_gemm_gated`` on interpret-mode Pallas) and once with
``ops.gemm_w4a8`` / ``ops.gated_mlp_w4a8`` monkeypatched to the reference
composition (``ref.gemm_w4a8_ref``: widen the nibbles, per-group int32
GEMM + int8-multiplier combine, one float rescale) — and fails unless
every request's tokens match exactly.  Covers a plain-GELU arch
(starcoder2-3b: the fused scaled_gelu epilogue) and a SwiGLU arch
(codeqwen1.5-7b: the dual-GEMM gated path).

Both drains run on the SAME backend: ``quant_rows`` may differ by 1 ulp
ACROSS backends (interpret-mode lowers the reciprocal differently), so a
pallas-fused vs jnp-unfused comparison would test the activation quant,
not the weight path.  Here only the two W4A8 entry points are swapped;
everything upstream of them is shared.

Usage: PYTHONPATH=src python scripts/w4a8_equiv_smoke.py
"""
import contextlib
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.models import init_params
from repro.quant import ptq_quantize_params
from repro.quant.ptq import DEFAULT_W4_POLICY
from repro.serve import ServeConfig, ServingEngine

REQS = [[5, 6, 7, 8, 9], [30, 31, 32], [9, 9, 9, 9], [40, 41, 42, 43]]


def _unfused_gemm(x_q, x_scale, w4, qmul, w_scale, bias=None, residual=None,
                  gelu_scale=None, out_dtype=None):
    import jax.numpy as jnp
    out_dtype = jnp.bfloat16 if out_dtype is None else out_dtype
    k = x_q.shape[-1]
    lead = x_q.shape[:-1]
    res2 = None if residual is None else residual.reshape(-1,
                                                          residual.shape[-1])
    out = ref.gemm_w4a8_ref(x_q.reshape(-1, k), x_scale.reshape(-1, 1),
                            w4, qmul, w_scale, bias=bias, residual=res2,
                            gelu_scale=gelu_scale, out_dtype=out_dtype)
    return out.reshape(*lead, out.shape[-1])


def _unfused_gated(x_q, x_scale, up4, up_mul, up_scale, gate4, gate_mul,
                   gate_scale, act="silu", act_scale=None, out_dtype=None):
    import jax.numpy as jnp
    out_dtype = jnp.bfloat16 if out_dtype is None else out_dtype
    k = x_q.shape[-1]
    lead = x_q.shape[:-1]
    out = ref.gated_mlp_w4a8_ref(
        x_q.reshape(-1, k), x_scale.reshape(-1, 1), up4, up_mul, up_scale,
        gate4, gate_mul, gate_scale, act=act, act_scale=act_scale,
        out_dtype=out_dtype)
    return out.reshape(*lead, out.shape[-1])


@contextlib.contextmanager
def unfused_w4a8():
    """Swap ONLY the two W4A8 entry points for the reference composition."""
    fused = (ops.gemm_w4a8, ops.gated_mlp_w4a8)
    ops.gemm_w4a8, ops.gated_mlp_w4a8 = _unfused_gemm, _unfused_gated
    try:
        yield
    finally:
        ops.gemm_w4a8, ops.gated_mlp_w4a8 = fused


def drain(params, cfg) -> dict:
    engine = ServingEngine(params, cfg, ServeConfig(
        batch_lanes=2, max_seq=64, token_budget=8, int8_kv=True))
    for i, prompt in enumerate(REQS):
        engine.submit(list(prompt), max_new=4, request_id=i)
    engine.run_until_drained()
    return {d["id"]: d["tokens"] for d in engine.finished}


def main() -> None:
    with ops.use_backend("pallas"):
        for arch in ("starcoder2-3b", "codeqwen1.5-7b"):
            cfg = get_config(arch, precision="w4a8", reduced=True)
            params = ptq_quantize_params(
                init_params(jax.random.PRNGKey(0), cfg),
                policy=DEFAULT_W4_POLICY)
            got = drain(params, cfg)
            with unfused_w4a8():
                want = drain(params, cfg)
            if got != want:
                print(f"FAIL ({arch}): fused W4A8 drain diverges from the "
                      f"unfused unpack->int8-GEMM composition:\n"
                      f"  fused:   {got}\n  unfused: {want}",
                      file=sys.stderr)
                raise SystemExit(1)
            print(f"w4a8 equivalence OK ({arch}): {len(REQS)} requests "
                  f"bit-identical fused vs unfused "
                  f"({sum(len(t) for t in got.values())} tokens)")


if __name__ == "__main__":
    main()
