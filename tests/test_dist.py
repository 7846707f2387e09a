"""repro.dist coverage: param_specs validity across every config,
compress/decompress round-trip tolerances, shard_hint no-op contract."""
import jax
import jax.numpy as jnp
import jax.sharding as shd
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.dist.compression import (
    compress_grads,
    decompress_grads,
    init_error_state,
)
from repro.dist.sharding import AxisEnv, param_specs, set_axis_env, shard_hint
from repro.models import init_encdec_params, init_params

KEY = jax.random.PRNGKey(0)

# the production single-pod binding from launch/specs.make_cell_plan
_PROD_ENV = AxisEnv(dp=("data",), fsdp=("data",), tp=("model",),
                    ep=("model",), sp=("model",), active=True,
                    sizes=(("data", 16), ("model", 16)))
_PROD_MESH = shd.AbstractMesh((16, 16), ("data", "model"))


def _abstract_params(arch):
    cfg = get_config(arch)
    init = init_encdec_params if cfg.is_encoder_decoder else init_params
    return jax.eval_shape(lambda: init(KEY, cfg))


class TestParamSpecs:
    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_valid_named_sharding_every_config(self, arch):
        """Acceptance: param_specs -> constructible NamedSharding for every
        config in repro.configs, with every sharded dim divisible."""
        set_axis_env(_PROD_ENV)
        try:
            params = _abstract_params(arch)
            specs = param_specs(params)
        finally:
            set_axis_env(AxisEnv())
        leaves = jax.tree_util.tree_leaves_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))
        shapes = {jax.tree_util.keystr(kp): v.shape
                  for kp, v in jax.tree_util.tree_leaves_with_path(params)}
        assert leaves
        for kp, spec in leaves:
            assert isinstance(spec, P)
            NamedSharding(_PROD_MESH, spec)  # raises on unknown axes
            shape = shapes[jax.tree_util.keystr(kp)]
            for dim, entry in zip(shape, spec):
                if entry is None:
                    continue
                axes = (entry,) if isinstance(entry, str) else entry
                n = 1
                for ax in axes:
                    n *= dict(_PROD_ENV.sizes)[ax]
                assert dim % n == 0, (kp, shape, spec)

    def test_row_parallel_projections_shard_contraction(self):
        set_axis_env(_PROD_ENV)
        try:
            from repro.dist.sharding import _spec_for_path
            # column-parallel: output dim on model
            assert _spec_for_path("periods/0/attn/wq", (8, 2048, 2048))[-1] \
                == "model"
            # row-parallel: contraction dim on model, output on data (fsdp)
            spec = _spec_for_path("periods/0/mlp/w_out", (8, 8192, 2048))
            assert spec[-2] == "model" and spec[-1] == "data"
        finally:
            set_axis_env(AxisEnv())

    def test_expert_dim_on_ep(self):
        set_axis_env(_PROD_ENV)
        try:
            from repro.dist.sharding import _spec_for_path
            spec = _spec_for_path("periods/0/moe/experts/w_in",
                                  (2, 16, 2048, 8192))
            # expert dim takes the model axis; the matrix dims cannot reuse
            # it (duplicate-drop) and fall back to fsdp/replicated
            assert spec[1] == "model"
            assert spec[-1] != "model"
        finally:
            set_axis_env(AxisEnv())


class TestCompression:
    def test_round_trip_within_quant_tolerance(self, rng):
        """Satellite: one compress->decompress stays inside the int8 grid
        half-step, per tensor."""
        g = {"a": jnp.asarray(rng.normal(size=(32, 64)), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(128,)) * 5, jnp.float32)}
        payload, err = compress_grads(g, init_error_state(g))
        got = decompress_grads(payload)
        for k in g:
            scale = float(jnp.max(jnp.abs(g[k]))) / 127.0
            assert float(jnp.max(jnp.abs(got[k] - g[k]))) <= scale * 0.5 + 1e-7
            # the residual is exactly what decompression lost
            np.testing.assert_allclose(
                np.asarray(err[k]), np.asarray(g[k] - got[k]), atol=1e-6)

    def test_error_feedback_telescopes(self, rng):
        """Sum of decompressed grads + final residual == sum of true grads
        (the EF invariant the trainer relies on)."""
        gs = [jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
              for _ in range(8)]
        err = init_error_state({"w": gs[0]})
        acc = np.zeros((16, 16), np.float32)
        for g in gs:
            payload, err = compress_grads({"w": g}, err)
            acc += np.asarray(decompress_grads(payload)["w"])
        total = np.asarray(sum(gs))
        np.testing.assert_allclose(acc + np.asarray(err["w"]), total,
                                   rtol=1e-4, atol=1e-4)

    def test_payload_is_int8_with_scalar_scales(self, rng):
        g = {"w": jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)}
        payload, _ = compress_grads(g, init_error_state(g))
        assert payload["q"]["w"].dtype == jnp.int8
        assert payload["scale"]["w"].ndim == 0


class TestShardHint:
    def test_noop_without_mesh_even_when_active(self):
        set_axis_env(_PROD_ENV)
        try:
            x = jnp.ones((32, 16))
            y = shard_hint(x, "dp", "tp")
            assert (np.asarray(y) == np.asarray(x)).all()
        finally:
            set_axis_env(AxisEnv())

    def test_divisibility_demotion_in_hint(self):
        """A 6-row tensor on a 16-way axis must not crash inside a mesh."""
        mesh = jax.make_mesh((1,), ("model",))
        set_axis_env(AxisEnv(tp=("model",), active=True,
                             sizes=(("model", 1),)))
        try:
            with mesh:
                out = jax.jit(lambda x: shard_hint(x, "tp", None))(
                    jnp.ones((6, 4)))
            assert out.shape == (6, 4)
        finally:
            set_axis_env(AxisEnv())
