"""The ``dense`` family in the program: its ``ArchConfig`` at a
configuration file's numbers, and its served weights made on the device
from the seed.

The float weights are the reference's (``reference.layer_weights`` /
``top_weights``, the file beside this one); the program's own PTQ turns
them into the served types inside one jitted call, layer by layer under
``lax.map``, so the float tree never exists whole on the device.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import byname

reference = byname.sibling(__file__, "reference")


def arch_config(conf: dict, precision: str | None = None):
    """The program's ArchConfig for this file: the program's entry for the
    architecture (its kernel tables are keyed by that name) with every
    number taken from the file."""
    from repro.configs import get_config
    a = reference.arch(conf["model"])
    prec = precision or conf["serving"]["precision"]
    base = get_config(conf["program_arch"], precision=prec)
    return dataclasses.replace(
        base, n_layers=a["layers"], d_model=a["d"], n_heads=a["heads"],
        n_kv_heads=a["kv_heads"], d_ff=a["ff"], vocab_size=a["vocab"],
        rope_theta=a["theta"], norm_eps=a["eps"], qkv_bias=True,
        activation="silu" if a["gated"] else "gelu",
        norm_type="layernorm" if a["layernorm"] else "rmsnorm",
        tie_embeddings=a["tied"], block_pattern=("attn",), d_head=0,
        precision=prec)


def _block(w: dict, a: dict) -> dict:
    """Reference leaves -> the program's block layout."""
    def norm(i):
        p = {"scale": w[f"ln{i}_scale"]}
        if a["layernorm"]:
            p["bias"] = w[f"ln{i}_bias"]
        return p
    attn = {k: w[k].astype(jnp.bfloat16) for k in ("wq", "wk", "wv", "wo")}
    attn.update(bq=w["bq"], bk=w["bk"], bv=w["bv"])
    mlp = {k: w[k].astype(jnp.bfloat16) for k in ("w_in", "w_out", "w_gate")
           if k in w}
    return {"norm1": norm(1), "attn": attn, "norm2": norm(2), "mlp": mlp}


def params_maker(conf: dict, cfg):
    """``make(key)``: the served weights of the program at
    ``cfg.precision`` from a key (``reference.base_key``)."""
    from repro.quant import ptq_quantize_params
    from repro.quant.ptq import DEFAULT_W4_POLICY
    a = reference.arch(conf["model"])
    policy = {"w8a8": None, "w4a8": DEFAULT_W4_POLICY}
    quant = cfg.precision in policy

    def finish(tree):
        return (ptq_quantize_params(tree, policy=policy[cfg.precision])
                if quant else tree)

    def make(key):
        blocks = jax.lax.map(
            lambda i: finish(_block(reference.layer_weights(key, a, i), a)),
            jnp.arange(a["layers"]))
        top = reference.top_weights(key, a)
        pad = cfg.padded_vocab - a["vocab"]
        embed = jnp.pad(top["embed"], ((0, pad), (0, 0)))
        out = {"embed": embed.astype(jnp.bfloat16),
               "final_norm": {"scale": top["lnf_scale"]}}
        if a["layernorm"]:
            out["final_norm"]["bias"] = top["lnf_bias"]
        if not a["tied"]:
            head = jnp.pad(top["head"], ((0, 0), (0, pad)))
            out["unembed"] = head.astype(jnp.bfloat16)
        out = finish(out)
        out["periods"] = [blocks]
        return out

    return make


def program_params(conf: dict, cfg, seed: int) -> dict:
    """Served weights made on the device in one jitted call from ``seed``."""
    params = jax.jit(params_maker(conf, cfg))(reference.base_key(seed))
    return jax.block_until_ready(params)
