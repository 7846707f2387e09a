"""Serving tensor parallel: single-device contracts of the TP machinery.

The cross-device bit-identity proof lives in scripts/tp_equiv_smoke.py
(verify.sh) and the collective-structure assertions in
``launch/dryrun.py --tp-serve`` — both need an emulated 8-device mesh,
which pytest cannot set up after jax has initialized.  What IS testable
on one device, and is covered here: the typed validation surface
(mesh sizes, arch support, divisibility), the PartitionSpec rules the
shard_map step is built from, the no-op behavior of the boundary helpers
outside a TP region (the tp=1 path must stay byte-for-byte the
single-device program), and the cost-model seed that drives
``tp_overlap="auto"``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import costmodel
from repro.dist.sharding import serve_param_specs, serve_state_specs
from repro.dist.tp import (
    TPConfigError,
    TPServing,
    tp_out_projection,
    tp_row_shard,
    tp_row_unshard,
    tp_serving,
    tp_serving_ctx,
    validate_tp_serving,
)
from repro.kernels import autotune
from repro.launch.mesh import MeshDeviceError, make_tp_mesh
from repro.models import init_params


def _cfg(**over):
    cfg = get_config("codeqwen1.5-7b", reduced=True)
    return dataclasses.replace(cfg, **over) if over else cfg


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------

def test_make_tp_mesh_subset_axis():
    mesh = make_tp_mesh(1)
    assert mesh.shape["tp"] == 1
    assert mesh.axis_names == ("tp",)


def test_make_tp_mesh_rejects_oversubscription():
    too_many = len(jax.devices()) + 1
    with pytest.raises(MeshDeviceError, match="xla_force_host_platform"):
        make_tp_mesh(too_many)


def test_make_tp_mesh_rejects_nonpositive():
    with pytest.raises(MeshDeviceError):
        make_tp_mesh(0)


# ---------------------------------------------------------------------------
# arch validation
# ---------------------------------------------------------------------------

def test_validate_accepts_dense_attention_arch():
    validate_tp_serving(_cfg(n_heads=8, n_kv_heads=8, d_ff=128), 4)


def test_validate_tp1_is_always_fine():
    validate_tp_serving(get_config("zamba2-2.7b", reduced=True), 1)


def test_validate_rejects_recurrent_blocks():
    with pytest.raises(TPConfigError, match="mamba2"):
        validate_tp_serving(get_config("zamba2-2.7b", reduced=True), 2)


def test_validate_rejects_cross_attention_source():
    with pytest.raises(TPConfigError, match="kv_source"):
        validate_tp_serving(_cfg(n_heads=8, n_kv_heads=8, d_ff=128), 2,
                            kv_source=jnp.zeros((1, 4, 8)))


def test_validate_rejects_indivisible_heads():
    with pytest.raises(TPConfigError, match="n_heads"):
        validate_tp_serving(_cfg(n_heads=6, n_kv_heads=6, d_ff=128), 4)


def test_validate_rejects_indivisible_dff():
    with pytest.raises(TPConfigError, match="d_ff"):
        validate_tp_serving(_cfg(n_heads=8, n_kv_heads=8, d_ff=100), 8)


# ---------------------------------------------------------------------------
# PartitionSpec rules
# ---------------------------------------------------------------------------

def test_serve_param_specs_rules():
    cfg = _cfg(n_heads=8, n_kv_heads=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    specs = serve_param_specs(params, 2)
    attn = specs["periods"][0]["attn"]
    mlp = specs["periods"][0]["mlp"]
    # column-parallel projections shard their output dim ...
    for leaf in ("wq", "wk", "wv"):
        assert attn[leaf][-1] == "tp", leaf
    assert mlp["w_in"][-1] == "tp"
    assert mlp["w_gate"][-1] == "tp"
    # ... row GEMMs, embeddings, and norms replicate
    assert all(s is None for s in attn["wo"])
    assert all(s is None for s in mlp["w_out"])
    assert all(s is None for s in specs["embed"])
    for spec in jax.tree.leaves(specs["periods"][0]["norm1"],
                                is_leaf=lambda x: isinstance(x, P)):
        assert all(s is None for s in spec)


def test_serve_param_specs_quant_leaves_follow_parent():
    # PTQ payload dicts shard by their PARENT projection's rule
    tree = {"attn": {"wq": {"w_q": np.zeros((16, 8), np.int8),
                            "scale": np.zeros((8,), np.float32)},
                     "wo": {"w_q": np.zeros((8, 16), np.int8),
                            "scale": np.zeros((16,), np.float32)}}}
    specs = serve_param_specs(tree, 2)
    assert specs["attn"]["wq"]["w_q"] == P(None, "tp")
    assert specs["attn"]["wq"]["scale"] == P("tp")
    assert specs["attn"]["wo"]["w_q"] == P(None, None)
    assert specs["attn"]["wo"]["scale"] == P(None)


def test_serve_param_specs_indivisible_raises():
    with pytest.raises(TPConfigError, match="column-shard"):
        serve_param_specs({"attn": {"wq": np.zeros((8, 6))}}, 4)


def test_serve_state_specs_shard_kv_head_axis_only():
    states = [{"kv": {"k": np.zeros((2, 1, 16, 4, 8)),
                      "v": np.zeros((2, 1, 16, 4, 8)),
                      "pos": np.zeros((2, 1), np.int32)}}]
    specs = serve_state_specs(states, 4)
    assert specs[0]["kv"]["k"] == P(None, None, None, "tp", None)
    assert specs[0]["kv"]["v"] == P(None, None, None, "tp", None)
    # scheduler-visible leaves stay whole on every shard
    assert specs[0]["kv"]["pos"] == P(None, None)


def test_serve_state_specs_indivisible_hkv_raises():
    states = [{"kv": {"k": np.zeros((2, 1, 16, 6, 8))}}]
    with pytest.raises(TPConfigError, match="head-shard"):
        serve_state_specs(states, 4)


# ---------------------------------------------------------------------------
# boundary helpers outside a TP region: byte-for-byte no-ops
# ---------------------------------------------------------------------------

def test_helpers_identity_without_ctx():
    assert tp_serving_ctx() is None
    x = jnp.arange(24, dtype=jnp.float32).reshape(1, 4, 6)
    assert tp_row_shard(x) is x
    assert tp_row_unshard(x, 1, 4) is x
    called = {}

    def apply_out(h, residual):
        called["h"] = h
        return h + residual

    out = tp_out_projection(x, 2 * x, apply_out)
    assert called["h"] is x
    np.testing.assert_array_equal(out, 3 * np.asarray(x))


def test_helpers_identity_at_size_one():
    x = jnp.ones((1, 2, 4))
    with tp_serving(TPServing(size=1, overlap=True)):
        assert tp_serving_ctx().size == 1
        assert tp_row_shard(x) is x
        assert tp_row_unshard(x, 1, 2) is x
        assert tp_out_projection(x, x, lambda h, r: h + r).shape == x.shape
    assert tp_serving_ctx() is None


def test_ctx_restored_on_error():
    with pytest.raises(RuntimeError):
        with tp_serving(TPServing(size=8)):
            raise RuntimeError("boom")
    assert tp_serving_ctx() is None


# ---------------------------------------------------------------------------
# cost-model seed + autotune family
# ---------------------------------------------------------------------------

def test_tp_boundary_cost_shape():
    assert costmodel.tp_boundary_cost(64, 128, 128, 1, False) == 0.0
    b = costmodel.tp_boundary_cost(64, 128, 128, 4, False)
    o = costmodel.tp_boundary_cost(64, 128, 128, 4, True)
    assert b > 0 and o > 0
    # monotone in rows
    assert costmodel.tp_boundary_cost(128, 128, 128, 4, False) > b
    # huge-GEMM regime: overlap's 1/tp row work wins
    assert (costmodel.tp_boundary_cost(4096, 4096, 4096, 8, True)
            < costmodel.tp_boundary_cost(4096, 4096, 4096, 8, False))
    # tiny-step regime: overlap's second collective dispatch loses
    assert (costmodel.tp_boundary_cost(1, 64, 64, 8, False)
            < costmodel.tp_boundary_cost(1, 64, 64, 8, True))


def test_tp_serving_overlap_choice():
    autotune.reset_measured_cache()
    try:
        assert autotune.tp_serving_overlap(64, 128, 128, 128, 1) == "barrier"
        assert autotune.tp_serving_overlap(
            64, 128, 128, 128, 8, backend="jnp") in ("overlap", "barrier")
        # a measured key overrides the cost-model seed
        key = autotune.measured_key("tpserve/64x128x128x128/tp8/jnp")
        autotune._MEASURED = {key: {"blocks": [1], "us": 1.0}}
        autotune.tp_serving_overlap.cache_clear()
        assert autotune.tp_serving_overlap(
            64, 128, 128, 128, 8, backend="jnp") == "overlap"
        autotune._MEASURED[key] = {"blocks": [0], "us": 1.0}
        autotune.tp_serving_overlap.cache_clear()
        assert autotune.tp_serving_overlap(
            64, 128, 128, 128, 8, backend="jnp") == "barrier"
    finally:
        autotune.reset_measured_cache()


def test_engine_rejects_bad_overlap_choice():
    from repro.serve import ServeConfig, ServingEngine
    cfg = _cfg(n_heads=8, n_kv_heads=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="tp_overlap"):
        ServingEngine(params, cfg, ServeConfig(
            batch_lanes=2, max_seq=32, token_budget=8,
            tp=1, tp_overlap="sideways"))
