"""Quickstart: the paper's pipeline in 60 lines.

1. Run a Table-II kernel (gemm) through the NX-CGRA model: static schedule,
   cycle/energy simulation, published-style metrics.
2. Run the same integer arithmetic as a Pallas TPU kernel (interpreted on
   CPU, compiled on a TPU)
   and check bit-exactness.
3. Run a W8A8 transformer forward pass — the technique at model scale.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

# --- 1. the fabric model ----------------------------------------------------
from repro.core import BUILDERS, Simulator, StaticScheduler, metrics_from_sim

ki = BUILDERS["gemm"]()
prog = StaticScheduler().schedule(ki.tasks, name="gemm")
res = Simulator().run(prog, ki.env)
m = metrics_from_sim("gemm", res, ki.useful_ops)
print(f"[CGRA] gemm: {res.cycles} cycles, {m.mops:.0f} MOPS, "
      f"{m.tops_w:.2f} TOPS/W, {m.tops_w_mm2:.2f} TOPS/W/mm^2 "
      f"(paper: 3040 MOPS, 2.01, 11.29)")

# --- 2. the TPU kernel, same arithmetic --------------------------------------
from repro.core import inumerics as inum
from repro.kernels import ops, ref

# the Pallas kernel: interpreted on CPU, compiled on a TPU
rng = np.random.default_rng(0)
x = jnp.asarray(rng.integers(-127, 128, (64, 128)), jnp.int8)
w = jnp.asarray(rng.integers(-127, 128, (128, 96)), jnp.int8)
rq = inum.compute_requant_params(1e-3, 128 * 127 * 127)
with ops.use_backend("pallas"):
    exact = bool((ops.gemm_i8(x, w, requant=rq)
                  == ref.int8_gemm_ref(x, w, requant=rq)).all())
print(f"[Pallas] int8 GEMM + requant epilogue bit-exact vs oracle: {exact}")

# --- 3. W8A8 transformer -----------------------------------------------------
from repro.configs import get_config
from repro.models import forward, init_params
from repro.quant import ptq_quantize_params, quantized_param_fraction

cfg = get_config("codeqwen1.5-7b", precision="w8a8", reduced=True)
params = ptq_quantize_params(
    init_params(jax.random.PRNGKey(0), get_config("codeqwen1.5-7b", reduced=True)))
tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
logits, _ = forward(params, cfg, tokens)
print(f"[W8A8] forward ok: logits {logits.shape}, "
      f"{quantized_param_fraction(params)*100:.0f}% of params on the int8 path")
