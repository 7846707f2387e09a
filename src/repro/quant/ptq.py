"""Post-training quantization: float params -> W8A8 / W4A8 integer execution.

Symmetric per-output-channel int8 for every 2D+ projection weight the
integer path consumes; norms/gates/recurrences stay float (see DESIGN.md
§Arch-applicability).  Quantized leaves are replaced by {"w_q", "scale"}
(int8) or {"w4", "qmul", "scale"} (packed int4, two-level group scales:
per-column f32 x per-group int8 multiplier) dicts, which
``layers.apply_linear`` dispatches on — no model code changes.

Selection mirrors the sharding rules: the same path patterns that make a
weight TP-shardable make it quantizable (they are the GEMM weights).

W4A8 is policy-driven per WEIGHT CLASS (attn projections / mlp projections
/ the lm head), so sensitive tensors can stay int8: the head sees the raw
logit error of every upstream bit dropped and stays int8 by default, and
token embeddings are never on the GEMM path at all (they stay float).
``calibrate_ptq`` searches group size and clip ratio per class against a
logit-MSE-vs-W8A8 proxy on fixed prompts.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

_QUANT_PATTERNS = [
    r"/w(q|k|v|o)$",
    r"/w_(in|gate|out)$",
    r"/(in_proj|out_proj|w_if|wo_gate|w_in)$",
    r"(^|/)unembed$",
]
# recurrent / precision-critical exclusions (router, gates handled by name)
_EXCLUDE = [r"/router/", r"/r_w$", r"/conv_w$", r"/shared_gate$"]

# weight classes for per-class quantization policy.  First match wins.
_CLASS_PATTERNS = [
    ("head", r"(^|/)unembed$"),
    ("attn", r"/w(q|k|v|o)$"),
    ("attn", r"/(in_proj|out_proj)$"),
    ("mlp", r"/w_(in|gate|out)$"),
    ("mlp", r"/(w_if|wo_gate|w_in)$"),
]

# default W4A8 policy: projections drop to int4 at the calibration-search
# midpoint; the lm head stays int8 (it feeds the sampler directly and its
# K dim is the model width — the bytes win is negligible next to the MLP).
DEFAULT_W4_POLICY = {
    "attn": {"bits": 4, "group": 64, "clip": 1.0},
    "mlp": {"bits": 4, "group": 64, "clip": 1.0},
    "head": "int8",
}

W4_GROUPS = (32, 64, 128)
W4_CLIPS = (1.0, 0.9, 0.8)


def _path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def _should_quantize(path: str, x) -> bool:
    if not hasattr(x, "ndim") or x.ndim < 2:
        return False
    if any(re.search(p, path) for p in _EXCLUDE):
        return False
    return any(re.search(p, path) for p in _QUANT_PATTERNS)


def weight_class(path: str) -> str:
    """Quantization-policy class of a quantizable weight path."""
    for cls, pat in _CLASS_PATTERNS:
        if re.search(pat, path):
            return cls
    return "other"


def _quantize_leaf(w: jax.Array) -> dict:
    wf = w.astype(jnp.float32)
    # per-output-channel (last dim) symmetric absmax; leading dims (layer
    # stacks / experts) keep their own channel scales via reduction over the
    # input dim only (axis=-2)
    amax = jnp.maximum(jnp.max(jnp.abs(wf), axis=-2, keepdims=True), 1e-8)
    scale = amax / 127.0
    w_q = jnp.clip(jnp.round(wf / scale), -128, 127).astype(jnp.int8)
    return {"w_q": w_q, "scale": jnp.squeeze(scale, axis=-2).astype(jnp.float32)}


def _fit_group(k: int, group: int) -> int | None:
    """Largest usable scale group <= the requested one that divides K (the
    packed container needs an even K as well); None demotes the leaf to
    int8."""
    if k % 2:
        return None
    for cand in [group] + [g for g in sorted(W4_GROUPS, reverse=True)
                           if g < group]:
        if k % cand == 0:
            return cand
    return None


def _scale_stats(scale: jax.Array) -> dict:
    s = scale.astype(jnp.float32)
    return {"scale_min": float(jnp.min(s)), "scale_max": float(jnp.max(s)),
            "scale_mean": float(jnp.mean(s))}


def ptq_quantize_params(params, policy: dict | None = None,
                        with_report: bool = False):
    """Return a new param tree with GEMM weights PTQ'd to int8 / int4.

    ``policy`` maps weight class -> "int8" | {"bits": 4, "group": g,
    "clip": c}; unlisted classes (and ``policy=None``, the original W8A8
    behavior) quantize to per-channel int8.  A w4 spec whose group cannot
    divide a leaf's contraction dim demotes that leaf to int8.

    ``with_report=True`` additionally returns {path: {class, bits, group,
    clip, scale_min/max/mean}} — the per-layer calibration report.
    """
    from ..models.layers import quantize_weight_w4

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    leaves, report = [], {}
    for path, x in flat:
        p = _path_str(path)
        if not _should_quantize(p, x):
            leaves.append(x)
            continue
        cls = weight_class(p)
        spec = (policy or {}).get(cls, "int8")
        group = None
        if isinstance(spec, dict):
            group = _fit_group(int(x.shape[-2]), int(spec["group"]))
        if group is None:
            q = _quantize_leaf(x)
            if with_report:
                report[p] = {"class": cls, "bits": 8, "group": None,
                             "clip": 1.0, **_scale_stats(q["scale"])}
        else:
            clip = float(spec.get("clip", 1.0))
            q = quantize_weight_w4(x, group=group, clip_ratio=clip)
            if with_report:
                # effective per-group scales: column scale x int8 multiplier
                eff = (q["scale"][..., None, :]
                       * q["qmul"].astype(jnp.float32))
                report[p] = {"class": cls, "bits": 4, "group": group,
                             "clip": clip, **_scale_stats(eff)}
        leaves.append(q)
    tree = jax.tree_util.tree_unflatten(treedef, leaves)
    return (tree, report) if with_report else tree


def calibrate_ptq(params, forward_logits, groups=W4_GROUPS, clips=W4_CLIPS,
                  classes=("attn", "mlp"), max_rel_mse: float | None = None):
    """Greedy per-class W4 calibration search against a W8A8 quality proxy.

    ``forward_logits(quantized_params) -> logits`` must run the model on a
    FIXED calibration prompt set.  For each class (others int8), every
    (group, clip) candidate is scored by logit MSE against the all-int8
    forward; the per-class argmin wins.  With ``max_rel_mse``, a class
    whose best candidate exceeds ``max_rel_mse * mean(w8a8_logits^2)``
    falls back to int8 — the per-class escape hatch for sensitive tensors.
    Returns (policy, report): the policy feeds ``ptq_quantize_params`` and
    the report records every candidate's score.
    """
    base = forward_logits(ptq_quantize_params(params)).astype(jnp.float32)
    base_mag = float(jnp.mean(base * base))
    policy, report = {"head": "int8"}, {}
    for cls in classes:
        scores = []
        for g in groups:
            for c in clips:
                cand = {cls: {"bits": 4, "group": g, "clip": c}}
                lg = forward_logits(
                    ptq_quantize_params(params, policy=cand))
                mse = float(jnp.mean((lg.astype(jnp.float32) - base) ** 2))
                scores.append({"group": g, "clip": c, "mse": mse})
        best = min(scores, key=lambda s: s["mse"])
        demoted = (max_rel_mse is not None
                   and best["mse"] > max_rel_mse * base_mag)
        policy[cls] = "int8" if demoted else {
            "bits": 4, "group": best["group"], "clip": best["clip"]}
        report[cls] = {"best": best, "demoted_to_int8": demoted,
                       "scores": scores, "base_logit_msq": base_mag}
    return policy, report


def quantized_param_fraction(params) -> float:
    """Fraction of LOGICAL model parameters on an integer weight path,
    weighted by parameter count (works on either a float tree — predictive
    — or a PTQ'd tree — actual).  A packed int4 byte holds TWO logical
    weights, and quantization scale vectors are metadata, not parameters
    (a norm's ``/scale`` leaf still counts: only scales whose parent is a
    quantized GEMM weight are excluded)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    q = tot = 0
    for path, x in flat:
        p = _path_str(path)
        if p.endswith("/w_q"):
            q += x.size
            tot += x.size
        elif p.endswith("/w4"):
            q += 2 * x.size
            tot += 2 * x.size
        elif (p.endswith("/scale")
              and any(re.search(pt, p[: -len("/scale")])
                      for pt in _QUANT_PATTERNS)):
            continue
        elif (p.endswith("/qmul")
              and any(re.search(pt, p[: -len("/qmul")])
                      for pt in _QUANT_PATTERNS)):
            continue  # second-level scale multipliers are metadata too
        elif _should_quantize(p, x):
            q += x.size
            tot += x.size
        else:
            tot += x.size
    return q / max(tot, 1)
