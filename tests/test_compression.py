"""Property tests for dist/compression.py (error-feedback int8 grads).

tests/test_dist.py covers the fixed-seed happy path; this file drives the
compressor with RANDOMIZED magnitudes, shapes, and step counts
(hypothesis) and checks the two invariants the trainer actually relies on:

  round trip   decompress(compress(g)) stays within half an int8 grid
               step of g at EVERY magnitude, and the residual is exactly
               what decompression lost;
  telescoping  the SUM of decompressed payloads plus the final residual
               equals the sum of the true gradients — each step is coarse,
               the accumulated update is not.
"""
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.dist.compression import (
    compress_grads,
    decompress_grads,
    init_error_state,
)

F32 = np.float32


def _grad_tree(seed: int, log_mag: float) -> dict:
    """Two-leaf gradient tree with a controlled dynamic range: leaf "a"
    at 10**log_mag, leaf "b" 1000x smaller with an outlier spike (the
    regime where naive int8 rounds the bulk of the tensor to zero)."""
    rng = np.random.default_rng(seed)
    mag = 10.0 ** log_mag
    a = rng.normal(size=(17, 9)).astype(F32) * mag
    b = rng.normal(size=(33,)).astype(F32) * (mag / 1000.0)
    b[0] = mag  # outlier: absmax calibration must survive it
    return {"a": jnp.asarray(a), "b": jnp.asarray(b)}


class TestCompressionProperties:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.floats(-3.0, 3.0))
    def test_round_trip_half_step_bound(self, seed, log_mag):
        g = _grad_tree(seed, log_mag)
        payload, err = compress_grads(g, init_error_state(g))
        got = decompress_grads(payload)
        for k in g:
            scale = max(float(jnp.max(jnp.abs(g[k]))) / 127.0, 1e-8 / 127.0)
            diff = np.abs(np.asarray(got[k]) - np.asarray(g[k]))
            assert diff.max() <= 0.5 * scale * (1 + 1e-5) + 1e-12
            # the residual is EXACTLY the round-trip loss: err = g - deq
            np.testing.assert_allclose(
                np.asarray(err[k]),
                np.asarray(g[k]) - np.asarray(got[k]),
                rtol=1e-6, atol=1e-6 * scale + 1e-12)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.floats(-3.0, 3.0))
    def test_payload_schema(self, seed, log_mag):
        g = _grad_tree(seed, log_mag)
        payload, _ = compress_grads(g, init_error_state(g))
        for k in g:
            q, s = payload["q"][k], payload["scale"][k]
            assert q.dtype == jnp.int8 and q.shape == g[k].shape
            assert s.ndim == 0 and float(s) > 0.0
            assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= 127

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(2, 12))
    def test_error_feedback_telescopes(self, seed, steps):
        rng = np.random.default_rng(seed)
        gs = [rng.normal(size=(11, 7)).astype(F32) for _ in range(steps)]
        err = init_error_state({"w": jnp.asarray(gs[0])})
        acc = np.zeros((11, 7), F32)
        for g in gs:
            payload, err = compress_grads({"w": jnp.asarray(g)}, err)
            acc += np.asarray(decompress_grads(payload)["w"])
        # acc + final residual == true sum: the EF sum telescopes, so the
        # drift never exceeds ONE step's quantization error regardless of
        # how many coarse steps were taken
        np.testing.assert_allclose(acc + np.asarray(err["w"]), sum(gs),
                                   rtol=1e-4, atol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(4, 32))
    def test_error_feedback_recovers_subgrid_signal(self, seed, steps):
        """A constant gradient far below one grid step quantizes to zero
        every single step — yet with error feedback the ACCUMULATED
        update converges on the true sum (the whole point of carrying
        the residual instead of dropping it)."""
        rng = np.random.default_rng(seed)
        tiny = np.full((5, 5), 1e-3, F32)
        tiny[0, 0] = 1.0  # outlier pins scale at ~1/127 >> 1e-3
        g = jnp.asarray(tiny * (0.5 + rng.uniform()))
        err = init_error_state({"w": g})
        acc = np.zeros((5, 5), F32)
        for _ in range(steps):
            payload, err = compress_grads({"w": g}, err)
            acc += np.asarray(decompress_grads(payload)["w"])
        scale = float(jnp.max(jnp.abs(g))) / 127.0
        drift = np.abs(acc - steps * np.asarray(g))
        assert drift.max() <= 0.5 * scale * (1 + 1e-5) + 1e-7

    @settings(max_examples=5, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_zero_gradients_are_fixed_point(self, seed):
        del seed  # exercised for stability across the example budget
        g = {"w": jnp.zeros((6, 4), jnp.float32)}
        payload, err = compress_grads(g, init_error_state(g))
        assert int(jnp.sum(jnp.abs(payload["q"]["w"]))) == 0
        np.testing.assert_array_equal(np.asarray(err["w"]),
                                      np.zeros((6, 4), F32))
        np.testing.assert_array_equal(
            np.asarray(decompress_grads(payload)["w"]),
            np.zeros((6, 4), F32))
