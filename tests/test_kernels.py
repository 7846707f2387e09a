"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles.

Integer kernels must be BIT-EXACT against their jnp oracle; the float flash
attention matches to fp32 tolerance.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import inumerics as inum
from repro.kernels import ops, ref
from repro.kernels.common import set_interpret


@pytest.fixture(autouse=True)
def _pallas_backend():
    ops.set_backend("pallas")
    set_interpret(True)
    yield
    ops.set_backend("jnp")


def _rand_i8(rng, shape):
    return jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)


class TestInt8Gemm:
    @pytest.mark.parametrize("m,k,n", [
        (1, 16, 8), (37, 200, 130), (128, 128, 128), (64, 384, 256),
        (200, 64, 520),
    ])
    def test_exact_vs_ref(self, rng, m, k, n):
        x = _rand_i8(rng, (m, k))
        w = _rand_i8(rng, (k, n))
        assert (ops.gemm_i8(x, w) == ref.int8_gemm_ref(x, w)).all()

    @pytest.mark.parametrize("mult", [1e-4, 3e-3, 0.05])
    def test_requant_epilogue_exact(self, rng, mult):
        x = _rand_i8(rng, (32, 96))
        w = _rand_i8(rng, (96, 72))
        rq = inum.compute_requant_params(mult, 96 * 127 * 127)
        assert (ops.gemm_i8(x, w, requant=rq)
                == ref.int8_gemm_ref(x, w, requant=rq)).all()

    def test_batched_lead_dims(self, rng):
        x = _rand_i8(rng, (2, 5, 40))
        w = _rand_i8(rng, (40, 24))
        got = ops.gemm_i8(x, w)
        assert got.shape == (2, 5, 24)
        assert (got == ref.int8_gemm_ref(x.reshape(-1, 40), w).reshape(2, 5, 24)).all()


class TestIntSoftmax:
    @pytest.mark.parametrize("rows,cols", [(8, 64), (5, 77), (16, 512), (1, 33)])
    @pytest.mark.parametrize("scale", [0.02, 0.08])
    def test_exact_vs_ref(self, rng, rows, cols, scale):
        x = jnp.asarray(rng.integers(-127, 128, (rows, cols)), jnp.int32)
        assert (ops.softmax_i8(x, scale) == ref.int_softmax_ref(x, scale)).all()

    def test_masked_exact(self, rng):
        x = jnp.asarray(rng.integers(-127, 128, (6, 96)), jnp.int32)
        mask = jnp.asarray(rng.random((6, 96)) > 0.2)
        assert (ops.softmax_i8(x, 0.05, mask=mask)
                == ref.int_softmax_ref(x, 0.05, mask)).all()


class TestIntLayerNorm:
    @pytest.mark.parametrize("d", [64, 256, 1000])
    @pytest.mark.parametrize("rms", [False, True])
    def test_exact_vs_ref(self, rng, d, rms):
        x = jnp.asarray(rng.integers(-127, 128, (9, d)), jnp.int32)
        g = jnp.asarray(rng.integers(32, 127, (d,)), jnp.int32)
        b = jnp.asarray(rng.integers(-50, 50, (d,)), jnp.int32)
        assert (ops.layernorm_i8(x, g, b, rms_only=rms)
                == ref.int_layernorm_ref(x, g, b, rms_only=rms)).all()


class TestIntGelu:
    @pytest.mark.parametrize("shape", [(7, 100), (8, 128), (3, 5, 64)])
    def test_exact_vs_ref(self, rng, shape):
        x = jnp.asarray(rng.integers(-127, 128, shape), jnp.int32)
        assert (ops.gelu_i8(x, 0.05) == ref.int_gelu_ref(x, 0.05)).all()


class TestIntSilu:
    @pytest.mark.parametrize("shape", [(7, 100), (8, 128), (3, 5, 64)])
    @pytest.mark.parametrize("scale", [8.0 / 127.0, 0.05])
    def test_exact_vs_ref(self, rng, shape, scale):
        x = jnp.asarray(rng.integers(-127, 128, shape), jnp.int32)
        got = ops.silu_i8(x, scale)
        assert got.dtype == jnp.int32
        assert (got == ref.int_silu_ref(x, scale)).all()

    def test_close_to_float_silu(self, rng):
        """Dequantized integer SiLU tracks float SiLU over the clip range."""
        s = 8.0 / 127.0
        q = jnp.arange(-128, 128, dtype=jnp.int32)[None, :]
        got = np.asarray(ops.silu_i8(q, s), np.float64) * (s / 127.0)
        want = np.asarray(jax.nn.silu(q.astype(jnp.float32) * s), np.float64)
        assert np.abs(got - want).max() < 0.05


class TestDualGemmGatedMLP:
    """Fused dual-GEMM gated MLP (SwiGLU/GeGLU): BIT-EXACT against the
    unfused jnp composition oracle for the W8A8 variant, tolerance vs the
    dense float oracle for the bf16 variant."""

    def _w8a8_inputs(self, rng, m, k, n):
        xf = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        wu = _rand_i8(rng, (k, n))
        wg = _rand_i8(rng, (k, n))
        us = jnp.asarray(np.abs(rng.normal(size=(n,))) + 0.01, jnp.float32)
        gs = jnp.asarray(np.abs(rng.normal(size=(n,))) + 0.01, jnp.float32)
        ops.set_backend("jnp")
        xq, xs = ops.quant_rows(xf)
        ops.set_backend("pallas")
        return xq, xs, wu, us, wg, gs

    @pytest.mark.parametrize("m,k,n", [
        (1, 16, 8), (37, 200, 130), (64, 384, 256), (128, 128, 128),
    ])
    @pytest.mark.parametrize("act", ["silu", "gelu"])
    def test_w8a8_exact_vs_ref(self, rng, m, k, n, act):
        s = 8.0 / 127.0
        xq, xs, wu, us, wg, gs = self._w8a8_inputs(rng, m, k, n)
        want = ref.gated_mlp_w8a8_ref(xq, xs.reshape(-1, 1), wu, us, wg, gs,
                                      act=act, act_scale=s)
        got = ops.gated_mlp_w8a8(xq, xs, wu, us, wg, gs, act=act,
                                 act_scale=s)
        assert got.dtype == jnp.bfloat16
        assert (np.asarray(got, np.float32)
                == np.asarray(want, np.float32)).all()

    @pytest.mark.parametrize("act", ["silu", "gelu"])
    def test_w8a8_batched_lead_dims(self, rng, act):
        s = 8.0 / 127.0
        xq, xs, wu, us, wg, gs = self._w8a8_inputs(rng, 6, 40, 24)
        want = ops.gated_mlp_w8a8(xq, xs, wu, us, wg, gs, act=act,
                                  act_scale=s)
        got = ops.gated_mlp_w8a8(xq.reshape(2, 3, 40), xs.reshape(2, 3, 1),
                                 wu, us, wg, gs, act=act, act_scale=s)
        assert got.shape == (2, 3, 24)
        assert (np.asarray(got, np.float32)
                == np.asarray(want.reshape(2, 3, 24), np.float32)).all()

    @pytest.mark.parametrize("m,k,n", [(5, 64, 128), (33, 100, 72)])
    @pytest.mark.parametrize("act", ["silu", "gelu"])
    def test_bf16_close_vs_dense_oracle(self, rng, m, k, n, act):
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        wu = jnp.asarray(rng.normal(size=(k, n)) * 0.1, jnp.float32)
        wg = jnp.asarray(rng.normal(size=(k, n)) * 0.1, jnp.float32)
        got = np.asarray(ops.gated_mlp(x, wu, wg, act), np.float32)
        h = x @ wu
        g = x @ wg
        a = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(
            g, approximate=False)
        want = np.asarray(a * h, np.float32)
        scale = max(np.abs(want).max(), 1e-6)
        assert np.abs(got - want).max() / scale < 0.03  # bf16 granularity


class TestQuantize:
    def test_rows_exact(self, rng):
        x = jnp.asarray(rng.normal(size=(6, 200)), jnp.float32)
        (q1, s1) = ops.quant_rows(x)
        (q2, s2) = ref.quantize_rows_ref(x)
        assert (q1 == q2).all() and np.allclose(s1, s2)

    def test_requant_exact(self, rng):
        x = jnp.asarray(rng.integers(-2 ** 20, 2 ** 20, (6, 64)), jnp.int32)
        rq = inum.compute_requant_params(1e-3, 2 ** 20)
        assert (ops.requant(x, rq) == ref.requantize_i32_ref(x, rq)).all()

    def test_quant_dequant_roundtrip_error(self, rng):
        x = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
        q, s = ops.quant_rows(x)
        err = jnp.abs(q.astype(jnp.float32) * s - x)
        assert float(err.max()) <= float(s.max()) * 0.5 + 1e-6


class TestConv2d:
    @pytest.mark.parametrize("hw,cin,cout,k", [
        (16, 3, 8, 3), (12, 4, 16, 3), (8, 1, 4, 1),
    ])
    def test_exact_vs_ref(self, rng, hw, cin, cout, k):
        x = _rand_i8(rng, (2, hw, hw, cin))
        w = _rand_i8(rng, (k, k, cin, cout))
        b = jnp.asarray(rng.integers(-1000, 1000, (cout,)), jnp.int32)
        assert (ops.conv2d_i8(x, w, b) == ref.int8_conv2d_ref(x, w, b)).all()

    def test_requant_output(self, rng):
        x = _rand_i8(rng, (1, 10, 10, 3))
        w = _rand_i8(rng, (3, 3, 3, 8))
        b = jnp.asarray(rng.integers(-100, 100, (8,)), jnp.int32)
        rq = inum.compute_requant_params(1e-4, 27 * 127 * 127 + 100)
        got = ops.conv2d_i8(x, w, b, rq)
        assert got.dtype == jnp.int8
        assert (got == ref.int8_conv2d_ref(x, w, b, rq)).all()


class TestFlashAttention:
    @pytest.mark.parametrize("s,d,hq,hkv", [
        (64, 32, 4, 2), (128, 64, 8, 8), (256, 32, 4, 1),
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_close_vs_ref(self, rng, s, d, hq, hkv, causal):
        q = jnp.asarray(rng.normal(size=(2, hq, s, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, hkv, s, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, hkv, s, d)), jnp.float32)
        got = ops.attention(q, k, v, causal=causal)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestInt8FlashAttention:
    @pytest.mark.parametrize("s,d,hq,hkv", [(64, 32, 2, 1), (128, 64, 4, 4)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_exact_vs_ref(self, rng, s, d, hq, hkv, causal):
        q = _rand_i8(rng, (1, hq, s, d))
        k = _rand_i8(rng, (1, hkv, s, d))
        v = _rand_i8(rng, (1, hkv, s, d))
        got = ops.attention_i8(q, k, v, scale=0.002, causal=causal)
        want = ref.int8_flash_attention_ref(q, k, v, scale=0.002, causal=causal)
        assert (got == want).all()

class TestInt8AttentionPVDequant:
    """attention_i8 with per-(token, head) V scales: the exact-dequant PV
    pass (replaces the per-head mean-dequant approximation and its
    tolerance tests — the kernel output is now compared against dense
    oracles, not against a known-inexact mean)."""

    def _quant(self, rng, b, hq, hkv, s, d):
        qf = rng.normal(size=(b, hq, s, d)).astype(np.float32)
        kf = rng.normal(size=(b, hkv, s, d)).astype(np.float32)
        vf = rng.normal(size=(b, hkv, s, d)).astype(np.float32)
        sc = 1.0 / 16.0
        q = jnp.asarray(np.clip(np.round(qf / sc), -128, 127), jnp.int8)
        k = jnp.asarray(np.clip(np.round(kf / sc), -128, 127), jnp.int8)
        vs = np.abs(vf).max(-1, keepdims=True) / 127.0 + 1e-8  # (B,Hkv,S,1)
        v = jnp.asarray(np.clip(np.round(vf / vs), -128, 127), jnp.int8)
        import math
        rshift = int(round(math.log2(math.sqrt(d))))
        s_score = sc * sc * (2.0 ** rshift) / math.sqrt(d)
        return q, k, v, jnp.asarray(vs, jnp.float32), sc, s_score

    def test_bit_match_vs_composition_oracle_single_block(self, rng):
        """One KV block (bk == Skv), two query blocks: the fused PV-dequant
        pass is BIT-IDENTICAL to the jnp composition oracle contracting the
        same (bq, Skv) x (Skv, D) products (same f32 sums)."""
        from repro.kernels.int8_flash_attention import int8_flash_attention
        q, k, v, vs, _, s_score = self._quant(rng, 2, 4, 2, 64, 32)
        got = int8_flash_attention(q, k, v, s_score, causal=True,
                                   v_scale=vs, bq=32, bk=64, interpret=True)
        want = ref.int8_flash_attention_ref(q, k, v, s_score, causal=True,
                                            v_scale=vs, bq=32)
        assert got.dtype == jnp.float32
        assert (np.asarray(got) == np.asarray(want)).all()

    def test_oracle_pv_rows_depend_on_product_rows(self, rng):
        """Why the oracle takes the kernel's ``bq``: on XLA:CPU the same
        query rows sum in another order when 32 of them share a product
        than when all 64 do — last-ulp differences, nothing more.  One
        query block (bq == S) is the unblocked contraction exactly."""
        q, k, v, vs, _, s_score = self._quant(rng, 2, 4, 2, 64, 32)
        orc = functools.partial(ref.int8_flash_attention_ref, q, k, v,
                                s_score, causal=True, v_scale=vs)
        whole, halves = np.asarray(orc()), np.asarray(orc(bq=32))
        assert (np.asarray(orc(bq=64)) == whole).all()
        assert (halves != whole).any()
        np.testing.assert_allclose(halves, whole, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("s,d,hq,hkv", [(64, 32, 4, 2), (128, 64, 4, 4),
                                            (64, 32, 6, 3)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_composition_oracle(self, rng, s, d, hq, hkv, causal):
        """Multi-block streaming: equal to the oracle up to f32 summation
        order (integer probabilities themselves are exact)."""
        q, k, v, vs, _, s_score = self._quant(rng, 2, hq, hkv, s, d)
        got = ops.attention_i8(q, k, v, scale=s_score, causal=causal,
                               v_scale=vs)
        want = ref.int8_flash_attention_ref(q, k, v, s_score, causal=causal,
                                            v_scale=vs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_dense_f32_oracle_quantization_error_only(self, rng):
        """vs DENSE float attention over the dequantized inputs: with exact
        in-kernel PV dequant the only residual is the i-softmax/int8-prob
        error — far below what any per-head mean dequant could achieve
        when per-token scales vary strongly."""
        s, d, h = 64, 32, 2
        q, k, v, vs, sc, s_score = self._quant(rng, 1, h, h, s, d)
        # make per-token V scales strongly non-uniform (mean dequant would
        # be off by ~2x on the extreme tokens)
        mod = (1.0 + 3.0 * rng.random((1, h, s, 1))).astype(np.float32)
        vs = jnp.asarray(np.asarray(vs) * mod)
        got = np.asarray(ops.attention_i8(q, k, v, scale=s_score,
                                          causal=True, v_scale=vs))
        want = np.asarray(ref.flash_attention_ref(
            q.astype(jnp.float32) * sc, k.astype(jnp.float32) * sc,
            v.astype(jnp.float32) * np.asarray(vs), causal=True))
        exact_err = np.abs(got - want).max()
        # the DELETED approximation, reconstructed from the int32 contract:
        # dequant with the per-head MEAN scale instead of per-token scales
        acc = np.asarray(ops.attention_i8(q, k, v, scale=s_score,
                                          causal=True), np.float32)
        mean_out = acc / 127.0 * np.asarray(vs).mean(axis=2, keepdims=True)
        mean_err = np.abs(mean_out - want).max()
        # int8-prob granularity bounds the exact path; the mean path is off
        # by the scale spread itself (~4x worse here)
        assert exact_err < 0.25
        assert exact_err < 0.5 * mean_err, (exact_err, mean_err)

    def test_gqa_scale_groups(self, rng):
        """6 query heads over 3 KV heads: scaling KV head j's V scales must
        move exactly query heads 2j and 2j+1."""
        q, k, v, vs, _, s_score = self._quant(rng, 1, 6, 3, 64, 32)
        base = np.asarray(ops.attention_i8(q, k, v, scale=s_score,
                                           causal=True, v_scale=vs))
        for j in range(3):
            vs2 = np.asarray(vs).copy()
            vs2[:, j] *= 7.0
            got = np.asarray(ops.attention_i8(q, k, v, scale=s_score,
                                              causal=True,
                                              v_scale=jnp.asarray(vs2)))
            moved = [h for h in range(6)
                     if np.abs(got[0, h] - base[0, h]).max() > 1e-6]
            assert moved == [2 * j, 2 * j + 1]

    def test_jnp_backend_matches_pallas(self, rng):
        q, k, v, vs, _, s_score = self._quant(rng, 2, 4, 2, 64, 32)
        pl_out = ops.attention_i8(q, k, v, scale=s_score, v_scale=vs)
        ops.set_backend("jnp")
        jnp_out = ops.attention_i8(q, k, v, scale=s_score, v_scale=vs)
        ops.set_backend("pallas")
        np.testing.assert_allclose(np.asarray(pl_out), np.asarray(jnp_out),
                                   rtol=1e-5, atol=1e-6)


class TestInt8KVDecodeAttention:
    """Decode attention over the int8 ring cache (§Perf cell-C kernel)."""

    def _mk(self, rng, b, s, hq, hkv, d, fill, window=0):
        from repro.kernels.int8_kv_decode_attention import int8_kv_decode_attention
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        kf = rng.normal(size=(b, s, hkv, d)).astype(np.float32)
        vf = rng.normal(size=(b, s, hkv, d)).astype(np.float32)
        ks = np.abs(kf).max(-1, keepdims=True) / 127.0 + 1e-8
        vs = np.abs(vf).max(-1, keepdims=True) / 127.0 + 1e-8
        kq = jnp.asarray(np.clip(np.round(kf / ks), -128, 127), jnp.int8)
        vq = jnp.asarray(np.clip(np.round(vf / vs), -128, 127), jnp.int8)
        pos = np.full((b, s), -1, np.int32)
        pos[:, :fill] = np.arange(fill)
        qpos = jnp.full((b,), fill - 1, jnp.int32)
        args = (q, kq, jnp.asarray(ks), vq, jnp.asarray(vs),
                jnp.asarray(pos), qpos)
        return int8_kv_decode_attention, args

    @pytest.mark.parametrize("s,hq,hkv,d,fill", [
        (128, 4, 2, 64, 128), (256, 8, 8, 32, 100), (128, 6, 1, 64, 17),
    ])
    def test_matches_ref(self, rng, s, hq, hkv, d, fill):
        fn, args = self._mk(rng, 2, s, hq, hkv, d, fill)
        got = fn(*args, bk=64)
        want = ref.int8_kv_decode_attention_ref(*args)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-5, atol=2e-5)

    def test_sliding_window(self, rng):
        fn, args = self._mk(rng, 1, 128, 4, 2, 32, 128, window=32)
        got = fn(*args, window=32, bk=64)
        want = ref.int8_kv_decode_attention_ref(*args, window=32)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-5, atol=2e-5)

    def test_empty_slots_excluded(self, rng):
        """Slots with pos_ids == -1 must contribute zero probability."""
        fn, args = self._mk(rng, 1, 128, 2, 2, 32, 5)
        got = np.asarray(fn(*args, bk=64), np.float32)
        want = np.asarray(ref.int8_kv_decode_attention_ref(*args), np.float32)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


class TestInt8KVDecodeAttentionDense:
    """Satellite coverage: the decode kernel vs a DENSE f32 oracle built
    in-test (not ref.py): ring-buffer pos_ids masking after wraparound,
    the exact sliding-window bound, and GQA group handling."""

    def _dense(self, q, kq, ks, vq, vs, pos, qpos, window=0):
        """Dense f32 attention over the dequantized cache, masks from
        first principles."""
        q = np.asarray(q, np.float32)
        k = np.asarray(kq, np.float32) * np.asarray(ks)
        v = np.asarray(vq, np.float32) * np.asarray(vs)
        pos = np.asarray(pos)
        b, hq, d = q.shape
        s, hkv = k.shape[1], k.shape[2]
        out = np.zeros((b, hq, d), np.float32)
        for bi in range(b):
            for h in range(hq):
                kv_h = h // (hq // hkv)  # GQA: queries share KV groups
                valid = (pos[bi] >= 0) & (pos[bi] <= int(qpos[bi]))
                if window:
                    valid &= pos[bi] > (int(qpos[bi]) - window)
                logits = (k[bi, :, kv_h] @ q[bi, h]) / np.sqrt(d)
                logits = np.where(valid, logits, -1e30)
                p = np.exp(logits - logits.max())
                p = p / p.sum()
                out[bi, h] = p @ v[bi, :, kv_h]
        return out

    def _ring_cache(self, rng, cfg, b, s, n_tokens):
        """Write n_tokens (> S for wraparound) through the REAL model ring
        cache so pos_ids carry genuine overwrite state."""
        from repro.models.attention import _write_cache, init_cache
        cache = init_cache(cfg, b, s, int8=True)
        kf = rng.normal(size=(b, n_tokens, cfg.n_kv_heads, cfg.head_dim))
        vf = rng.normal(size=(b, n_tokens, cfg.n_kv_heads, cfg.head_dim))
        for t in range(n_tokens):
            cache = _write_cache(
                cache,
                jnp.asarray(kf[:, t:t + 1], jnp.float32),
                jnp.asarray(vf[:, t:t + 1], jnp.float32),
                jnp.full((b, 1), t, jnp.int32))
        return cache

    def _cfg(self, hq=4, hkv=2, d=32):
        from repro.models.config import ArchConfig
        return ArchConfig(name="t", family="dense", n_layers=1, d_model=hq * d,
                          n_heads=hq, n_kv_heads=hkv, d_ff=4, vocab_size=8,
                          d_head=d)

    def _run_kernel(self, q, cache, qpos, window=0):
        from repro.kernels.int8_kv_decode_attention import (
            int8_kv_decode_attention,
        )
        return int8_kv_decode_attention(
            q, cache["k"], cache["k_s"], cache["v"], cache["v_s"],
            cache["pos_ids"], qpos, window=window, bk=32)

    def test_ring_wraparound_masks_overwritten_slots(self, rng):
        """After writing 1.5x the cache length, slot i holds position
        i + S for the first half: the kernel must attend to the LATEST
        positions only, exactly like the dense oracle."""
        cfg = self._cfg()
        b, s, n_tok = 2, 64, 96
        cache = self._ring_cache(rng, cfg, b, s, n_tok)
        # wraparound happened: slots 0..31 hold positions 64..95
        assert int(np.asarray(cache["pos_ids"])[0, 0]) == 64
        q = jnp.asarray(rng.normal(size=(b, cfg.n_heads, cfg.head_dim)),
                        jnp.float32)
        qpos = jnp.full((b,), n_tok - 1, jnp.int32)
        got = np.asarray(self._run_kernel(q, cache, qpos), np.float32)
        want = self._dense(q, cache["k"], cache["k_s"], cache["v"],
                           cache["v_s"], cache["pos_ids"], qpos)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_sliding_window_exact_boundary(self, rng):
        """window=W keeps exactly positions (qpos-W, qpos]: check both the
        kernel and the oracle drop position qpos-W and keep qpos-W+1."""
        cfg = self._cfg(hq=2, hkv=2)
        b, s, w = 1, 64, 16
        cache = self._ring_cache(rng, cfg, b, s, s)
        q = jnp.asarray(rng.normal(size=(b, cfg.n_heads, cfg.head_dim)),
                        jnp.float32)
        qpos = jnp.full((b,), s - 1, jnp.int32)
        got = np.asarray(self._run_kernel(q, cache, qpos, window=w),
                         np.float32)
        want = self._dense(q, cache["k"], cache["k_s"], cache["v"],
                           cache["v_s"], cache["pos_ids"], qpos, window=w)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        # boundary sanity on the oracle itself: zero out the newest W keys'
        # values -> output must change; zero only keys OUTSIDE the window
        # -> output must not change
        vq = np.asarray(cache["v"]).copy()
        vq[:, : s - w] = 0  # positions 0..47: outside (qpos-16, qpos]
        outside = self._dense(q, cache["k"], cache["k_s"], jnp.asarray(vq),
                              cache["v_s"], cache["pos_ids"], qpos, window=w)
        np.testing.assert_allclose(outside, want, rtol=2e-5, atol=2e-5)

    def test_gqa_groups_read_their_own_kv_head(self, rng):
        """6 query heads over 3 KV heads: making KV head j distinctive must
        move exactly query heads 2j and 2j+1 (group mapping q_h -> q_h//g)."""
        cfg = self._cfg(hq=6, hkv=3)
        b, s = 1, 32
        cache = self._ring_cache(rng, cfg, b, s, s)
        q = jnp.asarray(rng.normal(size=(b, 6, cfg.head_dim)), jnp.float32)
        qpos = jnp.full((b,), s - 1, jnp.int32)
        base = np.asarray(self._run_kernel(q, cache, qpos), np.float32)
        want = self._dense(q, cache["k"], cache["k_s"], cache["v"],
                           cache["v_s"], cache["pos_ids"], qpos)
        np.testing.assert_allclose(base, want, rtol=2e-5, atol=2e-5)
        for j in range(3):
            vq = np.asarray(cache["v"]).copy()
            vq[:, :, j] = 0
            got = np.asarray(self._run_kernel(
                q, dict(cache, v=jnp.asarray(vq)), qpos), np.float32)
            moved = [h for h in range(6)
                     if np.abs(got[0, h] - base[0, h]).max() > 1e-6]
            assert moved == [2 * j, 2 * j + 1]

    def test_partial_fill_and_ops_dispatch(self, rng):
        """ops-level entry (autotuned bk) on a partially filled cache."""
        cfg = self._cfg()
        b, s, fill = 2, 128, 17
        cache = self._ring_cache(rng, cfg, b, s, fill)
        q = jnp.asarray(rng.normal(size=(b, cfg.n_heads, cfg.head_dim)),
                        jnp.float32)
        qpos = jnp.full((b,), fill - 1, jnp.int32)
        want = self._dense(q, cache["k"], cache["k_s"], cache["v"],
                           cache["v_s"], cache["pos_ids"], qpos)
        for backend in ("jnp", "pallas"):
            ops.set_backend(backend)
            got = np.asarray(ops.decode_attention_int8kv(
                q, cache["k"], cache["k_s"], cache["v"], cache["v_s"],
                cache["pos_ids"], qpos), np.float32)
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


class TestPagedAttentionDense:
    """The gather-based paged decode kernel vs a dense f32 oracle over the
    GATHERED view: page-table indirection, null-page masking, partial
    pages, COW-cleared slots, sliding window, GQA, idle lanes."""

    def _dense_view(self, pk, pks, pv, pvs, ppos, pt):
        """Materialize the per-lane dense view with numpy (from first
        principles, not ref.py)."""
        pk, pv = np.asarray(pk, np.float32), np.asarray(pv, np.float32)
        if pks is not None:
            pk = pk * np.asarray(pks)
            pv = pv * np.asarray(pvs)
        ptc = np.asarray(pt)
        k = pk[ptc]                             # (B, MP, ps, Hkv, D)
        v = pv[ptc]
        pos = np.asarray(ppos)[ptc]             # (B, MP, ps)
        b, mp, ps = pos.shape
        return (k.reshape(b, mp * ps, *k.shape[3:]),
                v.reshape(b, mp * ps, *v.shape[3:]),
                pos.reshape(b, mp * ps))

    def _dense(self, q, k, v, pos, qpos, window=0):
        q = np.asarray(q, np.float32)
        b, hq, d = q.shape
        hkv = k.shape[2]
        out = np.zeros((b, hq, d), np.float32)
        for bi in range(b):
            for h in range(hq):
                kv_h = h // (hq // hkv)
                valid = (pos[bi] >= 0) & (pos[bi] <= int(qpos[bi]))
                if window:
                    valid &= pos[bi] > (int(qpos[bi]) - window)
                if not valid.any():
                    continue
                logits = (k[bi, :, kv_h] @ q[bi, h]) / np.sqrt(d)
                logits = np.where(valid, logits, -1e30)
                p = np.exp(logits - logits.max())
                p = p / p.sum()
                out[bi, h] = p @ v[bi, :, kv_h]
        return out

    def _arena(self, rng, npg=10, ps=8, hkv=2, d=32, int8=True):
        if int8:
            pk = jnp.asarray(rng.integers(-127, 128, (npg, ps, hkv, d)),
                             jnp.int8)
            pv = jnp.asarray(rng.integers(-127, 128, (npg, ps, hkv, d)),
                             jnp.int8)
            pks = jnp.asarray(np.abs(rng.normal(size=(npg, ps, hkv, 1)))
                              + 1e-3, jnp.float32)
            pvs = jnp.asarray(np.abs(rng.normal(size=(npg, ps, hkv, 1)))
                              + 1e-3, jnp.float32)
        else:
            pk = jnp.asarray(rng.normal(size=(npg, ps, hkv, d)), jnp.bfloat16)
            pv = jnp.asarray(rng.normal(size=(npg, ps, hkv, d)), jnp.bfloat16)
            pks = pvs = None
        return pk, pks, pv, pvs

    def _tables(self, ps=8):
        """3 lanes: full chain w/ partial last page; short chain; idle.
        Page 0 = null (ppos -1), plus a COW'd page with cleared tail."""
        npg, mp = 10, 4
        ppos = np.full((npg, ps), -1, np.int32)
        pt = np.zeros((3, mp), np.int32)
        pt[0] = [1, 2, 3, 0]
        for j, pid in enumerate([1, 2, 3]):
            ppos[pid] = np.arange(j * ps, (j + 1) * ps)
        ppos[3, ps // 2:] = -1                   # partial last page
        pt[1] = [4, 5, 0, 0]
        ppos[4] = np.arange(ps)
        ppos[5, :3] = np.arange(ps, ps + 3)      # COW keep=3: tail cleared
        qpos = np.array([2 * ps + ps // 2 - 1, ps + 2, -1], np.int32)
        return jnp.asarray(ppos), jnp.asarray(pt), jnp.asarray(qpos)

    @pytest.mark.parametrize("int8", [True, False])
    @pytest.mark.parametrize("window", [0, 9])
    def test_kernel_matches_dense_oracle(self, rng, int8, window):
        from repro.kernels.paged_attention import paged_decode_attention
        ps, hkv, hq, d = 8, 2, 8, 32
        pk, pks, pv, pvs = self._arena(rng, ps=ps, hkv=hkv, d=d, int8=int8)
        ppos, pt, qpos = self._tables(ps=ps)
        q = jnp.asarray(rng.normal(size=(3, hq, d)), jnp.float32)
        got = np.asarray(paged_decode_attention(
            q, pk, pks, pv, pvs, ppos, pt, qpos, window=window,
            interpret=True), np.float32)
        want = self._dense(q, *self._dense_view(pk, pks, pv, pvs, ppos, pt),
                           qpos, window=window)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        assert (got[2] == 0).all()               # idle lane: all masked

    def test_window_excluding_every_slot_emits_zeros(self, rng):
        """A lane whose cached positions all fell out of the sliding
        window must emit exact zeros from BOTH the kernel and the jnp ref
        (the live-mask must apply the window term too)."""
        from repro.kernels.paged_attention import paged_decode_attention
        ps = 8
        pk, pks, pv, pvs = self._arena(rng, ps=ps)
        ppos, pt, _ = self._tables(ps=ps)
        # lane 0 holds positions 0..19; qpos far ahead with window 4
        qpos = jnp.asarray([100, 100, -1], jnp.int32)
        q = jnp.asarray(rng.normal(size=(3, 8, 32)), jnp.float32)
        got = np.asarray(paged_decode_attention(
            q, pk, pks, pv, pvs, ppos, pt, qpos, window=4, interpret=True))
        ref_out = np.asarray(ref.paged_decode_attention_ref(
            q, pk, pks, pv, pvs, ppos, pt, qpos, window=4))
        assert (got == 0).all()
        assert (ref_out == 0).all()

    def test_ops_dispatch_both_backends(self, rng):
        """ops.paged_attention_decode: jnp gather path == pallas kernel."""
        ps = 8
        pk, pks, pv, pvs = self._arena(rng, ps=ps)
        ppos, pt, qpos = self._tables(ps=ps)
        q = jnp.asarray(rng.normal(size=(3, 8, 32)), jnp.float32)
        prev = ops.backend()
        try:
            outs = {}
            for backend in ("jnp", "pallas"):
                ops.set_backend(backend)
                outs[backend] = np.asarray(ops.paged_attention_decode(
                    q, pk, pks, pv, pvs, ppos, pt, qpos), np.float32)
        finally:
            ops.set_backend(prev)
        np.testing.assert_allclose(outs["jnp"], outs["pallas"],
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_groups_read_their_own_kv_head(self, rng):
        """6 query heads over 3 KV heads through the page indirection:
        zeroing KV head j moves exactly query heads 2j, 2j+1."""
        from repro.kernels.paged_attention import paged_decode_attention
        ps, hkv, hq, d = 8, 3, 6, 32
        pk, pks, pv, pvs = self._arena(rng, ps=ps, hkv=hkv, d=d)
        ppos, pt, qpos = self._tables(ps=ps)
        q = jnp.asarray(rng.normal(size=(3, hq, d)), jnp.float32)
        run = lambda pv_: np.asarray(paged_decode_attention(
            q, pk, pks, pv_, pvs, ppos, pt, qpos, interpret=True),
            np.float32)
        base = run(pv)
        for j in range(hkv):
            vz = np.asarray(pv).copy()
            vz[:, :, j] = 0
            got = run(jnp.asarray(vz))
            moved = [h for h in range(hq)
                     if np.abs(got[0, h] - base[0, h]).max() > 1e-6]
            assert moved == [2 * j, 2 * j + 1]

    # -- block gather: several blocks per lane, dead slots never read -----

    PS, MP, D = 8, 16, 32

    @pytest.fixture
    def pages_per_block(self, monkeypatch):
        """Pin the kernel's pages per block (the shape rule would give these
        small tables a single block); the jit cache is dropped on both
        sides, since the block size is read while tracing."""
        from repro.kernels import autotune
        from repro.kernels import paged_attention as pa

        def pin(ppb):
            monkeypatch.setattr(autotune, "paged_decode_pages",
                                lambda *a, **k: ppb)
            pa.paged_decode_attention.clear_cache()
        yield pin
        pa.paged_decode_attention.clear_cache()

    def _lanes(self, rng, qpos, hkv, int8=True, dead_page=None):
        """An arena and page tables for lanes at positions ``qpos``: each
        lane's live logical pages map to pages of its own, filled as
        ``_write_paged`` fills them (slot s of logical page j holds
        position j*ps + s up to qpos, -1 past it), with a few slots below
        qpos cleared (-1) as COW leaves them.  Table slots past the last
        live page name ``dead_page`` (default: the null page 0)."""
        ps, mp = self.PS, self.MP
        live = [q // ps + 1 if q >= 0 else 0 for q in qpos]
        npg = 2 + sum(live)
        pk, pks, pv, pvs = self._arena(rng, npg=npg, ps=ps, hkv=hkv,
                                       d=self.D, int8=int8)
        if int8:                                  # absmax / 127 scales
            pks, pvs = pks / 127, pvs / 127
        ppos = np.full((npg, ps), -1, np.int32)
        pt = np.full((len(qpos), mp), 0 if dead_page is None else dead_page,
                     np.int32)
        nxt = 2                                   # 0: null, 1: spare
        for lane, (q, n) in enumerate(zip(qpos, live)):
            for j in range(n):
                pos = j * ps + np.arange(ps)
                ppos[nxt] = np.where(pos <= q, pos, -1)
                pt[lane, j] = nxt
                nxt += 1
            if n > 2:                             # COW-cleared slots
                ppos[pt[lane, 1], 1:3] = -1
        return pk, pks, pv, pvs, ppos, pt

    def _dense_valid(self, q, k, v, pos, qpos, window=0):
        """Oracle over the valid slots only, so that what lies in masked
        slots (NaN included) cannot reach it; in float64."""
        q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
        b, hq, d = q.shape
        hkv = k.shape[2]
        out = np.zeros((b, hq, d), np.float64)
        for bi in range(b):
            valid = (pos[bi] >= 0) & (pos[bi] <= int(qpos[bi]))
            if window:
                valid &= pos[bi] > (int(qpos[bi]) - window)
            if not valid.any():
                continue
            for h in range(hq):
                kv_h = h // (hq // hkv)
                logits = (k[bi, valid, kv_h] @ q[bi, h]) / np.sqrt(d)
                p = np.exp(logits - logits.max())
                out[bi, h] = (p / p.sum()) @ v[bi, valid, kv_h]
        return out

    # (qpos per lane, window, Hkv, G, pages per block); MP*PS = 128 slots
    BLOCK_CASES = {
        "several_blocks": ([100, 37, 64, -1], 0, 2, 4, 4),
        "qpos_first_slot_of_block": ([64, 32, 96, 0], 0, 2, 4, 4),
        "full_table": ([127, 127, 5, -1], 0, 2, 4, 4),
        "window_drops_blocks": ([100, 127, 40, 20], 20, 2, 4, 4),
        "hkv1_g1": ([100, 37, 64, -1], 0, 1, 1, 4),
        "hkv2_g12": ([100, 37, 64, -1], 0, 2, 12, 2),
        "idle_lanes": ([-1, 70, -1, -1], 0, 2, 4, 8),
    }

    @pytest.mark.parametrize("int8", [True, False])
    @pytest.mark.parametrize("case", sorted(BLOCK_CASES))
    def test_block_gather_matches_dense_oracle(self, rng, pages_per_block,
                                               case, int8):
        from repro.kernels.paged_attention import paged_decode_attention
        qpos, window, hkv, g, ppb = self.BLOCK_CASES[case]
        pages_per_block(ppb)
        pk, pks, pv, pvs, ppos, pt = self._lanes(rng, qpos, hkv, int8=int8)
        q = jnp.asarray(rng.normal(size=(len(qpos), hkv * g, self.D)),
                        jnp.float32)
        got = np.asarray(paged_decode_attention(
            q, pk, pks, pv, pvs, jnp.asarray(ppos), jnp.asarray(pt),
            jnp.asarray(qpos, jnp.int32), window=window, interpret=True),
            np.float32)
        want = self._dense_valid(
            q, *self._dense_view(pk, pks, pv, pvs, ppos, pt), qpos,
            window=window)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        for lane, p in enumerate(qpos):
            if p < 0:
                assert (got[lane] == 0).all()     # idle lane: exact zeros

    @pytest.mark.parametrize("int8", [True, False])
    def test_dead_slots_are_never_read(self, rng, pages_per_block, int8):
        """Table slots past each lane's last live page name a page of NaN
        K/V (and scales) whose positions lie past every qpos: the output
        stays finite and equal to the oracle, so no such page reaches the
        arithmetic, in a live block or a dead one."""
        from repro.kernels.paged_attention import paged_decode_attention
        pages_per_block(4)
        qpos = [100, 37, 64, -1]
        pk, pks, pv, pvs, ppos, pt = self._lanes(rng, qpos, 2, int8=int8,
                                                 dead_page=1)
        nan = np.nan
        if int8:
            pks = pks.at[1].set(nan)
            pvs = pvs.at[1].set(nan)
        else:
            pk = pk.at[1].set(nan)
            pv = pv.at[1].set(nan)
        ppos[1] = 10 ** 6
        q = jnp.asarray(rng.normal(size=(4, 8, self.D)), jnp.float32)
        got = np.asarray(paged_decode_attention(
            q, pk, pks, pv, pvs, jnp.asarray(ppos), jnp.asarray(pt),
            jnp.asarray(qpos, jnp.int32), interpret=True), np.float32)
        assert np.isfinite(got).all()
        want = self._dense_valid(
            q, *self._dense_view(pk, pks, pv, pvs, ppos, pt), qpos)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        assert (got[3] == 0).all()

    def test_model_write_then_gather_roundtrip(self, rng):
        """models/attention paged write + gathered read reproduces the
        dense cache contents slot for slot (the bit-identity substrate)."""
        from repro.models.attention import (
            _read_cache, _read_paged, _write_cache, _write_paged,
            init_cache, init_paged_cache,
        )
        from repro.models.config import ArchConfig
        cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=64,
                         n_heads=4, n_kv_heads=2, d_ff=4, vocab_size=8,
                         d_head=16)
        b, max_seq, ps = 2, 32, 8
        dense = init_cache(cfg, b, max_seq, int8=True)
        paged = init_paged_cache(cfg, b, 2 * b * (max_seq // ps) + 1, ps,
                                 max_seq // ps, int8=True)
        # identity-ish page table: lane 0 -> pages 1..4, lane 1 -> 5..8
        pt = jnp.asarray(np.arange(1, 2 * max_seq // ps + 1,
                                   dtype=np.int32).reshape(b, -1))
        paged = dict(paged, pt=pt)
        # two span writes at different depths + a pad column
        for p0, c in ((0, 5), (5, 3)):
            k = jnp.asarray(rng.normal(size=(b, c + 1, 2, 16)), jnp.float32)
            v = jnp.asarray(rng.normal(size=(b, c + 1, 2, 16)), jnp.float32)
            pos = np.tile(np.arange(p0, p0 + c + 1, dtype=np.int32), (b, 1))
            pos[:, -1] = -1                       # pad: both paths drop it
            dense = _write_cache(dense, k, v, jnp.asarray(pos))
            paged = _write_paged(paged, k, v, jnp.asarray(pos))
        kd, vd = _read_cache(dense, jnp.float32)
        kp, vp, kpos = _read_paged(paged, jnp.float32)
        valid = np.asarray(dense["pos_ids"]) >= 0
        assert (np.asarray(kpos) == np.asarray(dense["pos_ids"])).all()
        assert (np.asarray(kd)[valid] == np.asarray(kp)[valid]).all()
        assert (np.asarray(vd)[valid] == np.asarray(vp)[valid]).all()


class TestSSDScan:
    """Chunked Mamba-2 SSD kernel vs the sequential-recurrence oracle."""

    @pytest.mark.parametrize("t,n,p,chunk", [
        (128, 16, 32, 64), (256, 64, 64, 128), (64, 8, 16, 32),
    ])
    def test_matches_sequential_recurrence(self, rng, t, n, p, chunk):
        from repro.kernels.ssd_scan import ssd_scan
        bh = 3
        x = jnp.asarray(rng.normal(size=(bh, t, p)), jnp.float32)
        dt = jnp.asarray(np.abs(rng.normal(size=(bh, t))) * 0.5 + 0.01,
                         jnp.float32)
        b = jnp.asarray(rng.normal(size=(bh, t, n)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(bh, t, n)), jnp.float32)
        a = jnp.asarray(-np.abs(rng.normal(size=(bh, 1))) - 0.1, jnp.float32)
        got = ssd_scan(x, dt, b, c, a, chunk=chunk)
        want = ref.ssd_scan_ref(x, dt, b, c, a)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)

    def test_matches_model_ssd(self, rng):
        """Consistency with the model substrate's chunked-jnp SSD."""
        from repro.kernels.ssd_scan import ssd_scan
        from repro.models.ssm import _ssd_chunked
        bsz, t, h, p, n = 2, 128, 2, 32, 16
        xh = jnp.asarray(rng.normal(size=(bsz, t, h, p)), jnp.float32)
        dt = jnp.asarray(np.abs(rng.normal(size=(bsz, t, h))) * 0.5 + 0.01,
                         jnp.float32)
        a = jnp.asarray(-np.abs(rng.normal(size=(h,))) - 0.1, jnp.float32)
        bm = jnp.asarray(rng.normal(size=(bsz, t, n)), jnp.float32)
        cm = jnp.asarray(rng.normal(size=(bsz, t, n)), jnp.float32)
        y_model, _ = _ssd_chunked(xh, dt, a, bm, cm, chunk=64)
        # kernel layout: fold (B, H) and pre-scale x by nothing; B/C shared
        # across heads in the model -> broadcast
        xk = jnp.transpose(xh, (0, 2, 1, 3)).reshape(bsz * h, t, p)
        dtk = jnp.transpose(dt, (0, 2, 1)).reshape(bsz * h, t)
        bk = jnp.broadcast_to(bm[:, None], (bsz, h, t, n)).reshape(bsz * h, t, n)
        ck = jnp.broadcast_to(cm[:, None], (bsz, h, t, n)).reshape(bsz * h, t, n)
        ak = jnp.broadcast_to(a[None, :, None], (bsz, h, 1)).reshape(bsz * h, 1)
        # model applies dt INSIDE the update on x as well: dt_j B_j (dt x)_j?
        # no — model: h += dt_j B_j x_j with y = C.h; kernel identical
        y_k = ssd_scan(xk, dtk, bk, ck, ak, chunk=64)
        y_k = jnp.transpose(y_k.reshape(bsz, h, t, p), (0, 2, 1, 3))
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_model),
                                   rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# W4A8: int4 pack/unpack container + packed GEMM family
# ---------------------------------------------------------------------------

from hypothesis import given, settings, strategies as st

from repro.kernels.quantize import pack_int4, unpack_int4


class TestInt4Pack:
    """Property: pack_int4/unpack_int4 roundtrip and match the independent
    modular-arithmetic oracle — odd K, negatives, group boundaries."""

    # deadline=None: the first example compiles JAX
    @settings(max_examples=40, deadline=None)
    @given(st.integers(1, 131), st.integers(1, 24))
    def test_roundtrip_property(self, k, n):
        rng = np.random.default_rng(k * 1000 + n)
        w = rng.integers(-8, 8, size=(k, n)).astype(np.int8)
        # force the extremes onto a group-boundary row and the last row
        w[0, :] = -8
        w[k - 1, :] = 7
        if k > 32:
            w[32, :] = rng.choice([-8, -1, 0, 7], size=n)
        packed = pack_int4(jnp.asarray(w))
        assert packed.shape == (-(-k // 2), n)
        assert (np.asarray(unpack_int4(packed, k)) == w).all()
        assert (np.asarray(ref.unpack_int4_ref(packed, k)) == w).all()

    def test_unpack_matches_oracle_for_every_byte(self):
        """All 256 byte patterns: shift-based unpack == modular oracle."""
        b = jnp.asarray(np.arange(-128, 128, dtype=np.int8).reshape(16, 16))
        assert (unpack_int4(b, 32) == ref.unpack_int4_ref(b, 32)).all()

    def test_leading_dims(self, rng):
        w = jnp.asarray(rng.integers(-8, 8, size=(3, 64, 8)), jnp.int8)
        p = pack_int4(w)
        assert p.shape == (3, 32, 8)
        assert (unpack_int4(p, 64) == w).all()

    def test_quantize_weight_w4_roundtrip_error_bound(self, rng):
        from repro.models.layers import quantize_weight_w4
        w = jnp.asarray(rng.normal(size=(128, 24)), jnp.float32)
        q = quantize_weight_w4(w, group=32)
        assert q["qmul"].dtype == jnp.int8 and q["qmul"].shape == (4, 24)
        assert (np.asarray(q["qmul"]) >= 1).all()
        assert q["scale"].shape == (24,)
        # effective per-group scale: per-column f32 x int8 multiplier
        eff = q["scale"][None, :] * q["qmul"].astype(jnp.float32)
        eff_rep = jnp.repeat(eff, 32, axis=0)
        deq = unpack_int4(q["w4"], 128).astype(jnp.float32) * eff_rep
        err = np.asarray(jnp.abs(deq - w))
        # round-to-nearest against the effective scale is <= eff/2; a group
        # whose multiplier rounded DOWN can clip its absmax element, adding
        # at most 7 * (col_scale/2) on top
        bound = (np.asarray(eff_rep) / 2
                 + 3.5 * np.asarray(q["scale"])[None, :] + 1e-7)
        assert (err <= bound).all()


def _rand_w4(rng, k, n, g):
    """Random two-level W4 weight leaf: packed nibbles + int8 group
    multipliers in [1, 127] + per-column f32 scale."""
    w4 = pack_int4(jnp.asarray(rng.integers(-8, 8, (k, n)), jnp.int8))
    qm = jnp.asarray(rng.integers(1, 128, (k // g, n)), jnp.int8)
    ws = jnp.asarray(np.abs(rng.normal(size=(n,))) * 0.001 + 1e-4,
                     jnp.float32)
    return w4, qm, ws


class TestW4A8Gemm:
    @pytest.mark.parametrize("m,k,n,g", [
        (1, 32, 8, 32), (37, 96, 130, 32), (16, 64, 128, 64),
        (64, 384, 256, 128), (8, 256, 72, 64),
    ])
    def test_exact_vs_ref(self, rng, m, k, n, g):
        xq = _rand_i8(rng, (m, k))
        xs = jnp.asarray(np.abs(rng.normal(size=(m, 1))) * 0.01 + 1e-3,
                         jnp.float32)
        w4, qm, ws = _rand_w4(rng, k, n, g)
        got = ops.gemm_w4a8(xq, xs, w4, qm, ws)
        want = ref.gemm_w4a8_ref(xq, xs, w4, qm, ws)
        assert (got.astype(jnp.float32) == want.astype(jnp.float32)).all()

    @pytest.mark.parametrize("epi", ["bias", "residual", "gelu"])
    def test_epilogues_exact_vs_ref(self, rng, epi):
        m, k, n, g = 16, 96, 72, 32
        xq = _rand_i8(rng, (m, k))
        xs = jnp.asarray(np.abs(rng.normal(size=(m, 1))) * 0.01 + 1e-3,
                         jnp.float32)
        w4, qm, ws = _rand_w4(rng, k, n, g)
        kw = {}
        if epi == "bias":
            kw["bias"] = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        elif epi == "residual":
            kw["residual"] = jnp.asarray(rng.normal(size=(m, n)),
                                         jnp.bfloat16)
        else:
            kw["gelu_scale"] = 8.0 / 127.0
        got = ops.gemm_w4a8(xq, xs, w4, qm, ws, **kw)
        want = ref.gemm_w4a8_ref(xq, xs, w4, qm, ws, **kw)
        assert (got.astype(jnp.float32) == want.astype(jnp.float32)).all()

    def test_batched_lead_dims(self, rng):
        xq = _rand_i8(rng, (2, 5, 64))
        xs = jnp.asarray(np.abs(rng.normal(size=(2, 5, 1))) * 0.01 + 1e-3,
                         jnp.float32)
        w4, qm, ws = _rand_w4(rng, 64, 24, 32)
        got = ops.gemm_w4a8(xq, xs, w4, qm, ws)
        assert got.shape == (2, 5, 24)
        want = ref.gemm_w4a8_ref(xq.reshape(-1, 64), xs.reshape(-1, 1),
                                 w4, qm, ws).reshape(2, 5, 24)
        assert (got.astype(jnp.float32) == want.astype(jnp.float32)).all()

    @pytest.mark.parametrize("act", ["silu", "gelu"])
    def test_gated_exact_vs_ref(self, rng, act):
        m, k, n, g = 11, 96, 72, 32
        xq = _rand_i8(rng, (m, k))
        xs = jnp.asarray(np.abs(rng.normal(size=(m, 1))) * 0.01 + 1e-3,
                         jnp.float32)
        u4, um, us = _rand_w4(rng, k, n, g)
        g4, gm, gs = _rand_w4(rng, k, n, g)
        s0 = 8.0 / 127.0
        got = ops.gated_mlp_w4a8(xq, xs, u4, um, us, g4, gm, gs, act=act,
                                 act_scale=s0)
        want = ref.gated_mlp_w4a8_ref(xq, xs, u4, um, us, g4, gm, gs,
                                      act=act, act_scale=s0)
        assert (got.astype(jnp.float32) == want.astype(jnp.float32)).all()


class TestPTQCalibration:
    def test_logit_mse_monotone_in_group_size(self, rng):
        """Finer scale groups fit the weight distribution at least as well:
        the logit-MSE-vs-w8a8 proxy is monotone non-decreasing in group
        size on a fixed-seed toy model."""
        from repro.models.layers import ExecMode, apply_linear
        from repro.quant.ptq import ptq_quantize_params
        params = {"blk": {
            "w_in": jnp.asarray(rng.normal(size=(128, 256)), jnp.float32),
            "w_out": jnp.asarray(rng.normal(size=(256, 128)), jnp.float32),
        }}
        x = jnp.asarray(rng.normal(size=(16, 128)), jnp.bfloat16)
        mode = ExecMode("w4a8")

        def logits(p):
            h = apply_linear(x, p["blk"]["w_in"], mode)
            return apply_linear(jax.nn.gelu(h), p["blk"]["w_out"], mode)

        ops.set_backend("jnp")  # proxy scoring runs the reference path
        base = logits(ptq_quantize_params(params)).astype(jnp.float32)
        mses = []
        for g in (32, 64, 128):
            qp = ptq_quantize_params(
                params, policy={"mlp": {"bits": 4, "group": g, "clip": 1.0}})
            lg = logits(qp).astype(jnp.float32)
            mses.append(float(jnp.mean((lg - base) ** 2)))
        assert mses[0] > 0.0, "w4 must differ from the w8a8 baseline"
        assert mses[0] <= mses[1] <= mses[2], mses

    def test_calibrate_ptq_searches_and_pins_head(self, rng):
        from repro.models.layers import ExecMode, apply_linear
        from repro.quant.ptq import calibrate_ptq, ptq_quantize_params
        params = {
            "blk": {"w_in": jnp.asarray(rng.normal(size=(64, 96)),
                                        jnp.float32),
                    "w_out": jnp.asarray(rng.normal(size=(96, 64)),
                                         jnp.float32)},
            "unembed": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
        }
        x = jnp.asarray(rng.normal(size=(8, 64)), jnp.bfloat16)
        mode = ExecMode("w4a8")

        def fwd(p):
            h = apply_linear(x, p["blk"]["w_in"], mode)
            h = apply_linear(jax.nn.gelu(h), p["blk"]["w_out"], mode)
            return apply_linear(h, p["unembed"], mode)

        ops.set_backend("jnp")
        policy, rep = calibrate_ptq(params, fwd, groups=(32, 64),
                                    clips=(1.0, 0.9), classes=("mlp",))
        assert policy["head"] == "int8"
        assert policy["mlp"]["bits"] == 4
        assert policy["mlp"]["group"] in (32, 64)
        assert len(rep["mlp"]["scores"]) == 4
        best = rep["mlp"]["best"]["mse"]
        assert all(s["mse"] >= best for s in rep["mlp"]["scores"])
        # the searched policy quantizes: head int8, mlp int4
        qp, qrep = ptq_quantize_params(params, policy=policy,
                                       with_report=True)
        assert "w4" in qp["blk"]["w_in"] and "w_q" in qp["unembed"]
        assert qrep["unembed"]["bits"] == 8
        assert qrep["blk/w_in"]["bits"] == 4

    def test_quantized_param_fraction_counts_logical_params(self, rng):
        """A packed int4 byte holds two logical weights; quant scale leaves
        are metadata — the fraction must be identical before and after PTQ
        and across int8/int4 policies."""
        from repro.quant.ptq import (DEFAULT_W4_POLICY, ptq_quantize_params,
                                     quantized_param_fraction)
        params = {
            "blk": {"w_in": jnp.asarray(rng.normal(size=(64, 96)),
                                        jnp.float32),
                    "w_out": jnp.asarray(rng.normal(size=(96, 64)),
                                         jnp.float32),
                    "norm": {"scale": jnp.ones((64,), jnp.float32)}},
            "unembed": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
        }
        pred = quantized_param_fraction(params)
        f8 = quantized_param_fraction(ptq_quantize_params(params))
        f4 = quantized_param_fraction(
            ptq_quantize_params(params, policy=DEFAULT_W4_POLICY))
        expect = (64 * 96 + 96 * 64 + 64 * 32) / (
            64 * 96 + 96 * 64 + 64 * 32 + 64)
        assert abs(pred - expect) < 1e-9
        assert abs(f8 - expect) < 1e-9
        assert abs(f4 - expect) < 1e-9
