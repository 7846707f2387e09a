"""The ``dense`` family's plain float32 reference, and its work counts.

It imports nothing of the program under test.  The weights come from
``layer_weights`` / ``top_weights`` below, which ``program.py`` beside it
also uses to make the program's float weights before the program
quantizes them; the reference makes them again from the seed, layer by
layer, on the device.

The layer equations follow each model type's published description:

* ``starcoder2`` (bigcode/starcoder2, arXiv:2402.19173): LayerNorm with
  bias, rotary positions (rotate-half), grouped-query attention, biases on
  every linear layer, a plain MLP with the tanh GELU.
* ``qwen2`` (the Qwen1.5 lineage, CodeQwen1.5): RMSNorm, rotary positions
  (rotate-half), grouped-query attention with q/k/v biases, SwiGLU MLP.

Weights are drawn from normal laws (std 1/sqrt(fan_in) for projections,
0.02 for the embedding and the head, 1 + N(0, 0.1) for norm scales,
N(0, 0.1) for norm and q/k/v biases) and rounded to bfloat16, the type of
the published checkpoints; every computation below is float32 at
``Precision.HIGHEST``.  The o-projection and MLP biases of ``starcoder2``
are zero: the program has no such parameters (PERF.md, Open questions).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import roofline

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# architecture description from a configuration file's "model" section
# ---------------------------------------------------------------------------

def arch(model: dict) -> dict:
    """The numbers and layer kinds the equations need."""
    kind = model["model_type"]
    if kind not in ("starcoder2", "qwen2"):
        raise ValueError(f"no reference for model_type {kind!r}")
    d, h = model["hidden_size"], model["num_attention_heads"]
    return {
        "kind": kind,
        "d": d, "layers": model["num_hidden_layers"], "heads": h,
        "kv_heads": model["num_key_value_heads"], "head_dim": d // h,
        "ff": model["intermediate_size"], "vocab": model["vocab_size"],
        "theta": float(model["rope_theta"]),
        "eps": float(model.get("norm_epsilon", model.get("rms_norm_eps"))),
        "layernorm": kind == "starcoder2",
        "gated": kind == "qwen2",
        "tied": bool(model.get("tie_word_embeddings", False)),
    }


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def base_key(seed: int) -> jax.Array:
    """A key from any whole seed up to 64 bits (the driver's exceed int32)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std):
    w = jax.random.normal(key, shape, F32) * std
    return w.astype(jnp.bfloat16).astype(F32)


def _leaf_shapes(a: dict) -> dict:
    d, hd = a["d"], a["head_dim"]
    q, kv = a["heads"] * hd, a["kv_heads"] * hd
    s = {"ln1_scale": (d,), "ln2_scale": (d,),
         "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
         "bq": (q,), "bk": (kv,), "bv": (kv,),
         "w_in": (d, a["ff"]), "w_out": (a["ff"], d)}
    if a["layernorm"]:
        s.update(ln1_bias=(d,), ln2_bias=(d,))
    if a["gated"]:
        s["w_gate"] = (d, a["ff"])
    return s


def layer_weights(key: jax.Array, a: dict, layer) -> dict:
    """Layer ``layer``'s float weights (traceable in ``layer``)."""
    lk = jax.random.fold_in(key, 1000 + layer)
    out = {}
    for i, (name, shape) in enumerate(sorted(_leaf_shapes(a).items())):
        k = jax.random.fold_in(lk, i)
        if name.startswith("w"):
            out[name] = _normal(k, shape, 1.0 / math.sqrt(shape[0]))
        elif name.endswith("_scale"):
            out[name] = 1.0 + _normal(k, shape, 0.1)
        else:
            out[name] = _normal(k, shape, 0.1)
    return out


def top_weights(key: jax.Array, a: dict) -> dict:
    """Embedding (vocab, d), final norm and head (d, vocab)."""
    d, v = a["d"], a["vocab"]
    ks = [jax.random.fold_in(key, i) for i in range(4)]
    out = {"embed": _normal(ks[0], (v, d), 0.02),
           "lnf_scale": 1.0 + _normal(ks[1], (d,), 0.1)}
    if a["layernorm"]:
        out["lnf_bias"] = _normal(ks[2], (d,), 0.1)
    out["head"] = (out["embed"].T if a["tied"]
                   else _normal(ks[3], (d, v), 0.02))
    return out


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _norm(x, scale, bias, a):
    if a["layernorm"]:
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + a["eps"]) * scale + bias
    var = jnp.mean(x * x, -1, keepdims=True)
    return x * jax.lax.rsqrt(var + a["eps"]) * scale


def _rope(x, pos, theta):
    """Rotate-half rotary embedding; x (T, H, D), pos (T,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None, None].astype(F32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _layer_one(w, x, a, q_chunk):
    """One decoder layer over one sequence x (T, d), causal."""
    t = x.shape[0]
    hq, hkv, hd = a["heads"], a["kv_heads"], a["head_dim"]
    pos = jnp.arange(t)
    h = _norm(x, w["ln1_scale"], w.get("ln1_bias"), a)
    q = (_mm(h, w["wq"]) + w["bq"]).reshape(t, hq, hd)
    k = (_mm(h, w["wk"]) + w["bk"]).reshape(t, hkv, hd)
    v = (_mm(h, w["wv"]) + w["bv"]).reshape(t, hkv, hd)
    q, k = _rope(q, pos, a["theta"]), _rope(k, pos, a["theta"])
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=1)                       # (T, Hq, D)
    vv = jnp.repeat(v, g, axis=1)

    def chunk(args):
        qc, pc = args                                   # (C, Hq, D), (C,)
        s = jnp.einsum("chd,thd->hct", qc, kk, precision=HIGHEST)
        s = s / math.sqrt(hd)
        s = jnp.where(pos[None, None, :] <= pc[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hct,thd->chd", p, vv, precision=HIGHEST)

    n = t // q_chunk
    o = jax.lax.map(chunk, (q.reshape(n, q_chunk, hq, hd),
                            pos.reshape(n, q_chunk)))
    x = x + _mm(o.reshape(t, hq * hd), w["wo"])
    h = _norm(x, w["ln2_scale"], w.get("ln2_bias"), a)
    if a["gated"]:
        up = _mm(h, w["w_in"])
        gate = _mm(h, w["w_gate"])
        m = jax.nn.silu(gate) * up
    else:
        m = jax.nn.gelu(_mm(h, w["w_in"]), approximate=True)
    return x + _mm(m, w["w_out"])


@functools.partial(jax.jit, static_argnums=(3, 4), donate_argnums=(1,))
def _layer(key, xs, layer, a_items, q_chunk):
    a = dict(a_items)
    w = layer_weights(key, a, layer)
    return jax.lax.map(lambda x: _layer_one(w, x, a, q_chunk), xs)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(key, tokens, a_items):
    return top_weights(key, dict(a_items))["embed"][tokens]


@functools.partial(jax.jit, static_argnums=(4,))
def _gaps(key, xs, rows, toks, a_items):
    """Per sequence: at each row, the reference's best logit minus its
    logit of the token given there, and the logit scale (std)."""
    a = dict(a_items)
    top = top_weights(key, a)

    def one(args):
        x, r, tk = args
        h = _norm(x[r], top["lnf_scale"], top.get("lnf_bias"), a)
        lg = _mm(h, top["head"])                       # (R, V)
        best = jnp.max(lg, -1)
        got = jnp.take_along_axis(lg, tk[:, None], -1)[:, 0]
        return best - got, jnp.std(lg)

    return jax.lax.map(one, (xs, rows, toks))


def logit_gaps(seed: int, model: dict, seqs: list[tuple[list[int], list[int]]],
               pad_to: int = 512) -> tuple[list[np.ndarray], float]:
    """Teacher-forced reference over ``seqs`` = [(prompt, served)]: for each
    sequence, the gap by which each served token's logit lies below the
    reference's best at the position that produced it.  Returns the gaps
    per sequence and the mean logit scale.  Runs layer by layer, the
    sequences padded at the end to one length (causal: the pad changes no
    earlier position)."""
    a = arch(model)
    items = tuple(sorted(a.items()))
    key = base_key(seed)
    n_tok = max(len(p) + len(s) - 1 for p, s in seqs)
    t = -(-n_tok // pad_to) * pad_to
    r = max(len(s) for _, s in seqs)
    tokens = np.zeros((len(seqs), t), np.int32)
    rows = np.zeros((len(seqs), r), np.int32)
    toks = np.zeros((len(seqs), r), np.int32)
    for i, (p, s) in enumerate(seqs):
        full = (list(p) + list(s))[:-1]
        tokens[i, :len(full)] = full
        rows[i, :len(s)] = np.arange(len(p) - 1, len(p) - 1 + len(s))
        toks[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        xs = _embed(key, jnp.asarray(tokens), items)
        for layer in range(a["layers"]):
            xs = _layer(key, xs, layer, items, min(t, 256))
        gaps, scale = _gaps(key, xs, jnp.asarray(rows), jnp.asarray(toks),
                            items)
    gaps = np.asarray(gaps)
    return ([gaps[i, :len(s)] for i, (_, s) in enumerate(seqs)],
            float(np.mean(np.asarray(scale))))


# ---------------------------------------------------------------------------
# work counts: what the model requires, from its numbers alone
# ---------------------------------------------------------------------------
# Every layer attends over the whole context (full causal attention, as in
# ``_layer_one`` and the program).

def ops_per_token(a: dict) -> float:
    """Matmul operations of one token through every layer's projections,
    without attention's score and value products and without the head."""
    d, hd = a["d"], a["head_dim"]
    q, kv = a["heads"] * hd, a["kv_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d
    per_layer += (3 if a["gated"] else 2) * d * a["ff"]
    return 2.0 * a["layers"] * per_layer


def span_attention_ops(a: dict, p0: int, c: int) -> float:
    """Score and value products of ``c`` queries at positions p0 ..
    p0 + c - 1, every layer."""
    return (4.0 * a["head_dim"] * a["heads"] * a["layers"]
            * roofline.span_keys(p0, c))


def decode_attn_work(a: dict, contexts) -> tuple[float, float]:
    """(ops, bytes) of single-query attention over the int8 paged cache at
    each of ``contexts``, summed over the layers."""
    ops, nbytes = roofline.decode_attn_cost(contexts, a["heads"],
                                            a["kv_heads"], a["head_dim"])
    return a["layers"] * ops, a["layers"] * nbytes


def head_ops(a: dict) -> float:
    """The head's operations for one token whose next token is sampled."""
    return 2.0 * a["d"] * a["vocab"]
