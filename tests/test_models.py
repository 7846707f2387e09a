"""Per-arch smoke tests (reduced configs) + decode/cache/quant invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, cells, get_config
from repro.models import (
    encdec_forward,
    forward,
    init_encdec_params,
    init_params,
    init_states,
    lm_loss,
)
from repro.models.frontend import audio_frames_stub, vision_tokens_stub
from repro.quant import ptq_quantize_params, quantized_param_fraction

KEY = jax.random.PRNGKey(0)


def _build(arch, precision="bf16"):
    cfg = get_config(arch, precision=precision, reduced=True)
    if cfg.is_encoder_decoder:
        params = init_encdec_params(KEY, cfg)
    else:
        params = init_params(KEY, cfg)
    kv_src = None
    if cfg.family == "vlm":
        kv_src = vision_tokens_stub(KEY, 2, cfg.n_vision_tokens, cfg.d_model)
    return cfg, params, kv_src


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg, params, kv_src = _build(arch)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    if cfg.is_encoder_decoder:
        frames = audio_frames_stub(KEY, 2, cfg.n_audio_frames, cfg.d_model)
        lg, _, _ = encdec_forward(params, cfg, frames, tokens)
    else:
        lg, _ = forward(params, cfg, tokens, kv_source=kv_src)
    assert lg.shape == (2, 16, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(lg.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_loss_finite(arch):
    cfg, params, kv_src = _build(arch)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    labels = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    if cfg.is_encoder_decoder:
        from repro.models import encdec_loss
        frames = audio_frames_stub(KEY, 2, cfg.n_audio_frames, cfg.d_model)
        loss, grads = jax.value_and_grad(
            lambda p: encdec_loss(p, cfg, frames, tokens, labels))(params)
    else:
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(p, cfg, tokens, labels, kv_source=kv_src))(params)
    assert jnp.isfinite(loss)
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", [
    "codeqwen1.5-7b", "starcoder2-3b", "zamba2-2.7b", "xlstm-350m",
    "llama-3.2-vision-90b", "qwen2-moe-a2.7b",
])
def test_decode_matches_full_forward(arch):
    """Incremental decode with caches == full forward (teacher forcing)."""
    cfg, params, kv_src = _build(arch)
    if cfg.n_experts:
        # dropless capacity: GShard capacity-drop behavior legitimately
        # differs between prefill and decode token counts
        cfg = dataclasses.replace(cfg, capacity_factor=float(
            cfg.n_experts / max(cfg.n_experts_per_tok, 1)))
    b, t = 2, 12
    tokens = jax.random.randint(KEY, (b, t), 0, cfg.vocab_size)
    full, _ = forward(params, cfg, tokens, kv_source=kv_src)
    states = init_states(cfg, b, max_seq=16)
    if kv_src is not None:
        from repro.models import precompute_cross_states
        states = precompute_cross_states(params, cfg, kv_src, states)
    pre, states = forward(
        params, cfg, tokens[:, :8],
        positions=jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (b, 8)),
        states=states, kv_source=kv_src)
    errs = [float(jnp.max(jnp.abs(
        full[:, :8].astype(jnp.float32) - pre.astype(jnp.float32))))]
    for i in range(8, t):
        lg, states = forward(params, cfg, tokens[:, i:i + 1],
                             positions=jnp.full((b, 1), i, jnp.int32),
                             states=states, kv_source=kv_src)
        errs.append(float(jnp.max(jnp.abs(
            full[:, i:i + 1].astype(jnp.float32) - lg.astype(jnp.float32)))))
    # recurrent archs: chunked-prefill vs stepwise fp32 drift; MoE: einsum
    # dtype noise (dropless capacity set above)
    recurrent = bool({"mamba2", "mlstm", "slstm"} & set(cfg.block_kinds))
    tol = 0.02 if (cfg.n_experts or recurrent) else 1e-3
    assert max(errs) < tol, errs


def test_sliding_window_ring_buffer_matches_full_window():
    """SWA ring cache (S=window) == full cache with window masking."""
    cfg = get_config("mixtral-8x7b", reduced=True)
    assert cfg.sliding_window == 32
    params = init_params(KEY, cfg)
    b, t = 1, 48  # longer than the window -> ring wraps
    tokens = jax.random.randint(KEY, (b, t), 0, cfg.vocab_size)
    # reference: big cache (no ring wrap) — same window masking
    states_big = init_states(cfg, b, max_seq=64)
    # make the kv cache allocate full length by disabling window allocation
    import repro.models.blocks as blocks
    big = []
    for kind in cfg.block_pattern:
        st = blocks.init_block_state(kind, cfg, b, 64, False, jnp.bfloat16)
        big.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_periods,) + x.shape), st))
    ring = init_states(cfg, b, max_seq=64)  # allocates S=window ring
    lgs_big, lgs_ring = [], []
    sb, sr = big, ring
    for i in range(t):
        pos = jnp.full((b, 1), i, jnp.int32)
        lb, sb = forward(params, cfg, tokens[:, i:i + 1], positions=pos, states=sb)
        lr, sr = forward(params, cfg, tokens[:, i:i + 1], positions=pos, states=sr)
        lgs_big.append(lb)
        lgs_ring.append(lr)
    err = float(jnp.max(jnp.abs(
        jnp.stack(lgs_big).astype(jnp.float32)
        - jnp.stack(lgs_ring).astype(jnp.float32))))
    assert err < 0.25  # MoE capacity noise tolerance; attention itself exact


def test_moe_fused_expert_path_matches_unfused_composition(monkeypatch):
    """The fused dual-GEMM expert path == the unfused per-expert
    two-linear + activation composition, bit for bit, on both backends
    (experts and dense MLPs share one fused datapath)."""
    from repro.kernels import ops as kops
    from repro.kernels.common import set_interpret
    from repro.models import moe as moe_mod
    from repro.models.layers import ExecMode, activation, apply_linear
    cfg = get_config("mixtral-8x7b", precision="w8a8", reduced=True)
    params = ptq_quantize_params(moe_mod.init_moe_params(KEY, cfg))
    x = (jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.float32) * 0.5
         ).astype(jnp.bfloat16)
    mode = ExecMode("w8a8")

    def unfused_hidden(p, xe_, cfg_, mode_, hint=False):
        h = apply_linear(xe_, p["w_in"], mode_)
        g = apply_linear(xe_, p["w_gate"], mode_)
        return activation(g, cfg_.activation, mode_) * h

    set_interpret(True)
    try:
        for backend in ("jnp", "pallas"):
            kops.set_backend(backend)
            fused = moe_mod.moe(params, x, cfg, mode)
            with monkeypatch.context() as mp:
                mp.setattr(moe_mod, "gated_ffn_hidden", unfused_hidden)
                unfused = moe_mod.moe(params, x, cfg, mode)
            assert (jnp.asarray(fused, jnp.float32)
                    == jnp.asarray(unfused, jnp.float32)).all(), backend
    finally:
        kops.set_backend("jnp")


def test_moe_group_size_config_driven():
    """The GShard group size comes from the capacity-bounded all-to-all
    cost model per (T, config) — and always tiles the token count."""
    from repro.models.moe import _group_size
    mixtral = get_config("mixtral-8x7b")
    qwen = get_config("qwen2-moe-a2.7b")
    for t in (24, 160, 8192, 131072):
        for cfg in (mixtral, qwen):
            sg = _group_size(cfg, t)
            assert sg >= 1 and t % sg == 0, (cfg.name, t, sg)
    # the 60-expert config must not pick LARGER groups than the 8-expert
    # one at the same token count (one-hot dispatch footprint scales with E)
    assert _group_size(qwen, 131072) <= _group_size(mixtral, 131072)


def test_int8_kv_cache_close_to_bf16():
    cfg = get_config("codeqwen1.5-7b", reduced=True)
    params = init_params(KEY, cfg)
    b, t = 2, 10
    tokens = jax.random.randint(KEY, (b, t), 0, cfg.vocab_size)
    full, _ = forward(params, cfg, tokens)
    states = init_states(cfg, b, max_seq=16, int8_kv=True)
    outs = []
    for i in range(t):
        lg, states = forward(params, cfg, tokens[:, i:i + 1],
                             positions=jnp.full((b, 1), i, jnp.int32),
                             states=states)
        outs.append(lg)
    got = jnp.concatenate(outs, axis=1).astype(jnp.float32)
    # int8 KV quantization error stays small at logit level
    assert float(jnp.max(jnp.abs(got - full.astype(jnp.float32)))) < 0.6


@pytest.mark.parametrize("arch,policy", [
    ("starcoder2-3b", None), ("codeqwen1.5-7b", "w4"), ("zamba2-2.7b", None)])
def test_init_finish_per_block_matches_stacked(arch, policy):
    """``init_params(finish=PTQ)`` quantizes block by block (the serving
    init never holds the full float tree) and equals PTQ of the stacked
    float tree, leaf for leaf."""
    from repro.quant.ptq import DEFAULT_W4_POLICY
    cfg = get_config(arch, reduced=True)
    pol = DEFAULT_W4_POLICY if policy == "w4" else None
    want = ptq_quantize_params(init_params(KEY, cfg), policy=pol)
    got = init_params(KEY, cfg,
                      finish=lambda t: ptq_quantize_params(t, policy=pol))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and (np.asarray(a) == np.asarray(b)).all()


def test_w8a8_quality_vs_bf16():
    """PTQ W8A8 must stay close to the float model (random init)."""
    cfg16 = get_config("codeqwen1.5-7b", reduced=True)
    cfg8 = get_config("codeqwen1.5-7b", precision="w8a8", reduced=True)
    params = init_params(KEY, cfg16)
    q = ptq_quantize_params(params)
    assert quantized_param_fraction(q) > 0.5
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg16.vocab_size)
    lf, _ = forward(params, cfg16, tokens)
    li, _ = forward(q, cfg8, tokens)
    pf = jax.nn.softmax(lf.astype(jnp.float32), axis=-1)
    pi = jax.nn.softmax(li.astype(jnp.float32), axis=-1)
    # probability-level agreement (logit-level diffs amplify harmlessly)
    assert float(jnp.max(jnp.abs(pf - pi))) < 0.15


def test_long_context_skip_list_matches_design():
    """long_500k runs exactly for the sub-quadratic archs (DESIGN.md §7)."""
    expected = {"xlstm-350m", "zamba2-2.7b", "mixtral-8x7b"}
    got = {a for a in ARCH_IDS if "long_500k" in cells(a)}
    assert got == expected


def test_int8_kv_decode_kernel_path_matches_fallback():
    """The fused int8-KV decode kernel (pallas) == the jnp dequant path."""
    from repro.kernels import ops as kops
    from repro.kernels.common import set_interpret
    cfg = get_config("codeqwen1.5-7b", reduced=True)
    params = init_params(KEY, cfg)
    b = 2
    tokens = jax.random.randint(KEY, (b, 6), 0, cfg.vocab_size)

    def run():
        states = init_states(cfg, b, max_seq=16, int8_kv=True)
        outs = []
        for i in range(6):
            lg, states = forward(params, cfg, tokens[:, i:i + 1],
                                 positions=jnp.full((b, 1), i, jnp.int32),
                                 states=states)
            outs.append(lg)
        return jnp.concatenate(outs, axis=1)

    jnp_out = run()
    kops.set_backend("pallas")
    set_interpret(True)
    try:
        pl_out = run()
    finally:
        kops.set_backend("jnp")
    err = float(jnp.max(jnp.abs(pl_out.astype(jnp.float32)
                                - jnp_out.astype(jnp.float32))))
    assert err < 1e-2, err
