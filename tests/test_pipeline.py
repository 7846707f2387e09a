"""Pipeline-parallel wrapper: schedule correctness on a 1-stage mesh and
stage-splitting/bubble math (multi-stage collectives are exercised by the
512-device dry-run)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist.pipeline import bubble_fraction, pipeline_apply, split_stages


def test_split_stages_shapes():
    params = {"w": jnp.ones((8, 4, 4))}
    out = split_stages(params, 2)
    assert out["w"].shape == (2, 4, 4, 4)


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0.0
    assert abs(bubble_fraction(4, 12) - 3 / 15) < 1e-9


def test_single_stage_schedule_matches_direct():
    mesh = jax.make_mesh((1,), ("pod",))
    layers = jnp.asarray(
        np.random.default_rng(0).normal(size=(3, 8, 8)), jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 2, 8)),
                    jnp.float32)  # (M, mb, D)

    def layer_fn(w, h):
        return jnp.tanh(h @ w)

    def run(stage_params, xs):
        return pipeline_apply(layer_fn, stage_params, xs, axis_name="pod")

    with mesh:
        out = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False))(layers, x)

    def direct(h):
        for i in range(3):
            h = layer_fn(layers[i], h)
        return h

    want = jax.vmap(direct)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
