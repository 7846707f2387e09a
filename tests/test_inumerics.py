"""Integer-only numerics vs float references (+ hypothesis properties).

These bounds are the arithmetic contract the CGRA simulator, the Pallas
kernels and the w8a8 model path all inherit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import inumerics as inum

F32 = np.float32


class TestSoftmax:
    @pytest.mark.parametrize("rows,cols", [(1, 8), (4, 64), (3, 257), (2, 1024)])
    def test_close_to_float(self, rng, rows, cols):
        x = rng.normal(size=(rows, cols)).astype(F32) * 3
        s = float(inum.absmax_scale(jnp.asarray(x)))
        q = inum.quantize(jnp.asarray(x), s)
        p = np.asarray(inum.i_softmax(q, s)) * inum.SOFTMAX_OUT_SCALE
        ref = np.asarray(jax.nn.softmax(jnp.asarray(x), axis=-1))
        # error is dominated by the int8 input quantization: the top logit
        # moves by +-s/2, shifting its probability by ~p*s
        assert np.abs(p - ref).max() < max(0.05, 1.2 * s)

    def test_rows_sum_to_one_ish(self, rng):
        x = rng.normal(size=(8, 128)).astype(F32) * 5
        s = float(inum.absmax_scale(jnp.asarray(x)))
        q = inum.quantize(jnp.asarray(x), s)
        p = np.asarray(inum.i_softmax(q, s)) * inum.SOFTMAX_OUT_SCALE
        assert np.abs(p.sum(-1) - 1.0).max() < 0.05

    def test_mask_zeroes_probability(self, rng):
        x = rng.normal(size=(4, 32)).astype(F32)
        mask = rng.random((4, 32)) > 0.3
        mask[:, 0] = True  # keep at least one
        q = inum.quantize(jnp.asarray(x), 0.02)
        p = np.asarray(inum.i_softmax(q, 0.02, mask=jnp.asarray(mask)))
        assert (p[~mask] == 0).all()

    @settings(max_examples=30, deadline=None)
    @given(st.integers(0, 2 ** 31), st.floats(0.01, 0.2))
    def test_output_range_invariant(self, seed, scale):
        rng = np.random.default_rng(seed)
        x = rng.integers(-127, 128, size=(2, 16)).astype(np.int32)
        p = np.asarray(inum.i_softmax(jnp.asarray(x), scale))
        assert p.min() >= 0 and p.max() <= 127


class TestGeluSilu:
    def test_gelu_close(self):
        x = np.linspace(-6, 6, 241).astype(F32)
        s = float(inum.absmax_scale(jnp.asarray(x)))
        q = inum.quantize(jnp.asarray(x), s)
        g, sg = inum.i_gelu(q, s)
        ref = np.asarray(jax.nn.gelu(jnp.asarray(x), approximate=False))
        assert np.abs(np.asarray(g) * sg - ref).max() < 0.05

    def test_silu_close(self):
        x = np.linspace(-6, 6, 241).astype(F32)
        s = float(inum.absmax_scale(jnp.asarray(x)))
        q = inum.quantize(jnp.asarray(x), s)
        g, sg = inum.i_silu(q, s)
        ref = np.asarray(jax.nn.silu(jnp.asarray(x)))
        assert np.abs(np.asarray(g) * sg - ref).max() < 0.06

    @settings(max_examples=30, deadline=None)
    @given(st.floats(0.01, 0.1))
    def test_gelu_monotone_on_positive(self, scale):
        q = jnp.arange(0, 127, dtype=jnp.int32)
        g, sg = inum.i_gelu(q, scale)
        vals = np.asarray(g) * sg
        assert (np.diff(vals) >= -1e-6).all()


class TestSqrtNormRequant:
    @settings(max_examples=50, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1))
    def test_isqrt_exact_floor(self, n):
        got = int(inum.i_sqrt(jnp.asarray(n, jnp.int32)))
        assert got == int(np.floor(np.sqrt(n)))

    @pytest.mark.parametrize("d", [64, 256, 1024, 4096])
    def test_layernorm_close(self, rng, d):
        x = rng.normal(size=(4, d)).astype(F32) * 2 + 0.3
        s = float(inum.absmax_scale(jnp.asarray(x)))
        q = inum.quantize(jnp.asarray(x), s)
        gamma = rng.normal(size=(d,)).astype(F32)
        beta = rng.normal(size=(d,)).astype(F32) * 0.1
        gbs = float(max(np.abs(gamma).max(), np.abs(beta).max()) / 127)
        gq = inum.quantize(jnp.asarray(gamma), gbs)
        bq = inum.quantize(jnp.asarray(beta), gbs)
        out, so = inum.i_layernorm(q, s, gq, bq, gbs)
        mu = x.mean(-1, keepdims=True)
        sd = x.std(-1, keepdims=True) + 1e-6
        ref = (x - mu) / sd * gamma + beta
        # error floor = int8 input quantization; large D adds the adaptive
        # variance pre-shift truncation (~1 extra count at D=4096)
        assert np.abs(np.asarray(out) * so - ref).max() < (0.13 if d >= 4096
                                                           else 0.12)

    def test_rmsnorm_close(self, rng):
        d = 512
        x = rng.normal(size=(4, d)).astype(F32)
        s = float(inum.absmax_scale(jnp.asarray(x)))
        q = inum.quantize(jnp.asarray(x), s)
        gamma = np.abs(rng.normal(size=(d,))).astype(F32) + 0.5
        gbs = float(np.abs(gamma).max() / 127)
        gq = inum.quantize(jnp.asarray(gamma), gbs)
        out, so = inum.i_layernorm(q, s, gq, jnp.zeros_like(gq), gbs,
                                   rms_only=True)
        ref = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-9) * gamma
        assert np.abs(np.asarray(out) * so - ref).max() < 0.12

    @settings(max_examples=50, deadline=None)
    @given(st.floats(1e-6, 0.5), st.integers(10, 10_000_000))
    def test_requant_matches_float_rounding(self, mult, bound):
        p = inum.compute_requant_params(mult, bound)
        rng = np.random.default_rng(0)
        acc = rng.integers(-bound, bound, size=256).astype(np.int32)
        got = np.asarray(inum.requantize(jnp.asarray(acc), p))
        ref = np.clip(np.round(acc * mult), -128, 127)
        # double rounding: pre-shift discards s1 bits (error 0.5*2^s1 in acc
        # units -> 0.5*mult*2^s1 in output units) plus the final 0.5 ulp
        bound = 1.0 + 0.5 * mult * (2 ** p.s1)
        assert np.abs(got - ref).max() <= bound

    @settings(max_examples=30, deadline=None)
    @given(st.integers(1, 60), st.integers(0, 2 ** 31))
    def test_matmul_exact_int32(self, k, seed):
        rng = np.random.default_rng(seed)
        a = rng.integers(-127, 128, size=(3, k)).astype(np.int8)
        b = rng.integers(-127, 128, size=(k, 5)).astype(np.int8)
        got = np.asarray(inum.i_matmul(jnp.asarray(a), jnp.asarray(b)))
        ref = a.astype(np.int64) @ b.astype(np.int64)
        assert (got == ref).all()
