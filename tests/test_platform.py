"""The platform picks the kernel path; entry points share one compile cache.

On a TPU the kernels run as compiled Pallas and asking for interpret mode,
or serving through the jnp reference path, is an error.  Elsewhere the jnp
reference path is the default and explicit Pallas calls are interpreted.
The TPU side is exercised by making ``on_tpu`` report a TPU: no chip is
needed to check the rules themselves.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import common, ops

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def platform_default():
    """Both hooks back at the platform default, restored afterwards."""
    prev_backend = ops.set_backend(None)
    prev_interpret = common.set_interpret(None)
    try:
        yield
    finally:
        ops.set_backend(prev_backend)
        common.set_interpret(prev_interpret)


@pytest.fixture
def fake_tpu(platform_default, monkeypatch):
    # set up after platform_default, so the real on_tpu is back before
    # platform_default restores what earlier tests left (e.g. interpret on)
    monkeypatch.setattr(common, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def test_cpu_defaults_to_reference_path_and_interpret(platform_default):
    assert jax.default_backend() == "cpu"
    assert ops.backend() == "jnp"
    assert common.interpret_mode() is True


def test_tpu_defaults_to_compiled_pallas(fake_tpu):
    assert ops.backend() == "pallas"
    assert common.interpret_mode() is False
    ops.check_serving_backend()          # the default is the serving path


def test_tpu_refuses_interpret_mode(fake_tpu):
    with pytest.raises(RuntimeError, match="interpret"):
        common.set_interpret(True)
    with pytest.raises(RuntimeError, match="interpret"):
        common.interpret_mode(True)      # a kernel's explicit argument


def test_tpu_refuses_serving_on_reference_path(fake_tpu):
    ops.set_backend("jnp")               # allowed: reference comparisons
    assert ops.backend() == "jnp"
    with pytest.raises(RuntimeError, match="jnp reference"):
        ops.check_serving_backend()


def test_engine_step_refuses_reference_path_on_tpu(fake_tpu):
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve import ServeConfig, ServingEngine
    cfg = get_config("starcoder2-3b", reduced=True)
    eng = ServingEngine(init_params(jax.random.PRNGKey(0), cfg), cfg,
                        ServeConfig(batch_lanes=2, max_seq=32,
                                    token_budget=4))
    ops.set_backend("jnp")
    with pytest.raises(RuntimeError, match="jnp reference"):
        eng._step_fn(*eng._all_pad_args(1))


def test_train_step_traces_reference_path_on_tpu(fake_tpu):
    """No kernel defines a VJP: under the TPU default (compiled Pallas) a
    train step still differentiates, through the jnp path, and leaves the
    process default as it found it."""
    import numpy as np
    from repro.configs import get_config
    from repro.models import init_params
    from repro.train import TrainConfig, init_opt_state, make_train_step
    cfg = get_config("starcoder2-3b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        2, cfg.vocab_size, (2, 17)), jnp.int32)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    step = jax.jit(make_train_step(cfg, TrainConfig()))
    params2, _, _, metrics = step(params, init_opt_state(params), None,
                                  batch)
    assert np.isfinite(float(metrics["loss"]))
    moved = jax.tree.map(lambda a, b: bool((a != b).any()), params, params2)
    assert any(jax.tree.leaves(moved))
    assert ops.backend() == "pallas"


def test_use_backend_scopes_and_restores(platform_default):
    with ops.use_backend("pallas"):
        assert ops.backend() == "pallas"
        with ops.use_backend("jnp"):
            assert ops.backend() == "jnp"
        assert ops.backend() == "pallas"
    assert ops.backend() == "jnp"


def test_set_hooks_return_previous_and_validate(platform_default):
    assert ops.set_backend("pallas") is None
    assert ops.set_backend("jnp") == "pallas"
    with pytest.raises(ValueError):
        ops.set_backend("xla")
    assert common.set_interpret(True) is None
    assert common.set_interpret(None) is True


def test_explicit_pallas_call_interprets_on_cpu(platform_default):
    ops.set_backend("pallas")
    x = jnp.arange(16 * 128, dtype=jnp.float32).reshape(16, 128) - 1000.0
    q, s = ops.quant_rows(x)
    assert q.dtype == jnp.int8 and s.shape == (16, 1)


_CACHE_PROBE = (
    "import sys, jax, jax.numpy as jnp\n"
    "from repro.launch.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "if sys.argv[1:] == ['compile']:\n"
    "    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n")


def _probe(env_extra: dict, drop: tuple = (), compile_: bool = False
           ) -> list[str]:
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(REPO, "src"), **env_extra)
    cmd = [sys.executable, "-c", _CACHE_PROBE] + (["compile"] if compile_
                                                  else [])
    out = subprocess.run(cmd, env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return out.stdout.split()


def test_compile_cache_uses_env_dir_and_nothing_else(tmp_path):
    cache = tmp_path / "jc"
    returned, configured = _probe({"JAX_COMPILATION_CACHE_DIR": str(cache)},
                                  compile_=True)
    assert returned == configured == str(cache)
    assert any(cache.iterdir()), "no cache entry written"


def test_compile_cache_defaults_to_fixed_repo_dir():
    """Unset: the fixed ``<repo>/.jax_cache``.  Only the configuration is
    read: a test run writes no cache into the checkout."""
    from repro.launch.compile_cache import DEFAULT_DIR
    returned, configured = _probe({}, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert returned == configured == DEFAULT_DIR == os.path.join(
        REPO, ".jax_cache")


def test_gitignore_lists_compile_cache():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_measured_tiles_apply_only_to_their_device_kind(tmp_path,
                                                        monkeypatch):
    """A measured entry keyed for another device kind (a chip sweep left
    in a CPU checkout, or the reverse) never chooses tiles here."""
    from repro.kernels import autotune
    path = tmp_path / "measured.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.reset_measured_cache()
    try:
        table = autotune.gemm_blocks(256, 512, 512)
        foreign = (16, 128, 128) if table != (16, 128, 128) else (8, 128, 128)
        other_kind = "cpu" if jax.devices()[0].device_kind != "cpu" else \
            "TPU v5 lite"
        path.write_text(json.dumps({
            f"gemm/256x512x512/int8/pallas@{other_kind}":
                {"blocks": list(foreign), "us": 1.0}}))
        autotune.reset_measured_cache()
        assert autotune.gemm_blocks(256, 512, 512) == table
        assert "cost-model table" in autotune.tile_source()
        autotune.record("gemm/256x512x512/int8/pallas", foreign, 1.0)
        autotune.reset_measured_cache()
        assert autotune.gemm_blocks(256, 512, 512) == foreign
        assert "measured cache" in autotune.tile_source()
    finally:
        autotune.reset_measured_cache()
