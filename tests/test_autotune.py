"""Autotuner legality + fused-epilogue exactness + measured-cache policy."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.core import inumerics as inum
from repro.kernels import autotune, ops, ref
from repro.kernels.common import set_interpret


@pytest.fixture(autouse=True)
def _fresh_tuner():
    autotune.reset_measured_cache()
    yield
    autotune.reset_measured_cache()


def _config_gemm_shapes(max_archs=None):
    """(m, k, n) GEMM shapes as the models actually issue them: a token
    batch against each projection of the arch's full-size config."""
    shapes = []
    for arch in ARCH_IDS[:max_archs]:
        cfg = get_config(arch)
        m = 4 * 128  # decode lanes x partial prefill rows
        shapes.append((m, cfg.d_model, cfg.n_heads * cfg.head_dim))   # wq
        shapes.append((m, cfg.d_model, cfg.d_ff))                     # w_in
        shapes.append((m, cfg.d_ff, cfg.d_model))                     # w_out
    return shapes


class TestTileLegality:
    def test_config_shapes_mxu_legal(self):
        """Acceptance: MXU/VPU-legal tiles for >= 6 distinct config shapes."""
        shapes = sorted(set(_config_gemm_shapes()))
        assert len(shapes) >= 6
        for m, k, n in shapes:
            bm, bn, bk = autotune.gemm_blocks(m, k, n)
            assert autotune.is_mxu_legal(bm, bn, bk), (m, k, n, bm, bn, bk)
            # VMEM feasibility comes from the cost model's wall
            from repro.core.costmodel import TPU_VMEM_BYTES, gemm_tile_cost
            assert gemm_tile_cost(m, k, n, bm, bn, bk) < float("inf")
            assert 2 * (bm * bk + bk * bn) + bm * bn * 8 <= TPU_VMEM_BYTES

    def test_small_shapes_avoid_padding_waste(self):
        """A (1, K, N) decode GEMM must not get a 128-row tile."""
        bm, _, _ = autotune.gemm_blocks(1, 4096, 4096)
        assert bm == 8
        bm_big, _, _ = autotune.gemm_blocks(4096, 4096, 4096)
        assert bm_big >= 128

    def test_attention_blocks_divide_sequence(self):
        for s_q, s_kv in [(64, 64), (512, 512), (100, 100), (4096, 4096),
                          (1, 32768)]:
            bq, bk = autotune.attention_blocks(s_q, s_kv, 64)
            assert s_q % bq == 0 and s_kv % bk == 0, (s_q, s_kv, bq, bk)

    def test_decode_blocks_divide_cache(self):
        for s in (128, 256, 1024, 32768):
            bk = autotune.decode_blocks(s, 64, 4)
            assert s % bk == 0

    # worked by hand from the TPU's VMEM tiles (int8 rows pack 4 to a
    # 32-bit row, f32 (Hkv, 1) scales pad to (Hkv rounded to 2..8, 128),
    # an (8, ps) block of int32 positions is 4 KiB); budget 8 MiB
    @pytest.mark.parametrize("ps,mp,hkv,d,int8,want", [
        # starcoder2-3b: K+V 16 KiB, scales 32 KiB, positions 4 KiB a
        # page; 16 pages (256 tokens) double-buffered take 1.6 MiB
        (16, 128, 2, 128, True, 16),
        # codeqwen1.5-7b as configured, 32 KV heads: K+V 128 KiB, scales
        # 512 KiB a page; 6 pages would fit, 4 divides the table
        (16, 128, 32, 128, True, 4),
        (16, 128, 1, 128, True, 16),       # a TP shard of starcoder2-3b
        (16, 128, 2, 128, False, 16),      # bf16 pages
        (16, 1024, 2, 128, True, 16),      # a 16384-token table
        (8, 4, 2, 32, True, 4),            # a table narrower than a block
        # a 64-slot page of 32 heads: 2.5 MiB a page, one fits
        (64, 128, 32, 128, True, 1),
    ])
    def test_paged_decode_pages_fit(self, ps, mp, hkv, d, int8, want):
        """Pages per block, worked out independently of the rule's own
        padding model for each shape."""
        assert autotune.paged_decode_pages(ps, mp, hkv, d, int8) == want

    def test_paged_decode_pages_refuses_a_page_past_the_budget(self):
        """A page that alone overflows VMEM is an error, not a silent
        one-page block (1024 heads: 16 MiB of scales a page)."""
        with pytest.raises(ValueError, match="exceeds"):
            autotune.paged_decode_pages(16, 128, 1024, 128, True)

    def test_paged_decode_pages_shrink_with_heads(self):
        """More KV heads make every page wider: fewer pages per block."""
        few = autotune.paged_decode_pages(16, 128, 2, 128)
        many = autotune.paged_decode_pages(16, 128, 32, 128)
        assert few * 16 == autotune.PAGED_BLOCK_TOKENS and many < few

    def test_attention_pv_blocks_divide_sequence(self):
        """The PV-dequant variant's own key family (f32 accumulator +
        scale-vector streams) still returns sequence-dividing tiles."""
        for s_q, s_kv in [(64, 64), (512, 512), (100, 100), (2048, 2048)]:
            bq, bk = autotune.attention_pv_blocks(s_q, s_kv, 64)
            assert s_q % bq == 0 and s_kv % bk == 0, (s_q, s_kv, bq, bk)
        from repro.core.costmodel import attention_pv_tile_cost
        bq, bk = autotune.attention_pv_blocks(512, 512, 64)
        assert attention_pv_tile_cost(512, 512, 64, bq, bk) < float("inf")

    def test_attention_pv_measured_override(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "measured.json"))
        autotune.reset_measured_cache()
        autotune.record("attnpv/512x512x64/int8/pallas", (8, 8), 1.0)
        autotune.reset_measured_cache()
        assert autotune.attention_pv_blocks(512, 512, 64) == (8, 8)

    def test_packed_blocks_divide_bucket_and_cache(self):
        """The packed serving family (mixed prefill+decode rows vs a long
        cache) returns tiles dividing both the budget bucket and the cache
        length, and VMEM-feasible ones."""
        for t, s in [(1, 128), (8, 2048), (16, 128), (32, 4096),
                     (64, 32768)]:
            bq, bk = autotune.packed_blocks(t, s, 64, arch="starcoder2-3b")
            assert t % bq == 0 and s % bk == 0, (t, s, bq, bk)
        from repro.core.costmodel import packed_attention_tile_cost
        bq, bk = autotune.packed_blocks(32, 4096, 64, arch="starcoder2-3b")
        assert packed_attention_tile_cost(32, 4096, 64, bq, bk) < float("inf")

    def test_packed_small_bucket_takes_whole_rows(self):
        """Serving buckets are small: re-streaming the cache per query
        sub-block can never pay off, so bq must cover the whole bucket."""
        for t in (2, 4, 8, 16, 32):
            bq, _ = autotune.packed_blocks(t, 2048, 64, arch="any")
            assert bq == t, (t, bq)

    def test_packed_measured_override(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "measured.json"))
        autotune.reset_measured_cache()
        autotune.record("packed/16x128x64/starcoder2-3b/jnp", (8, 8), 1.0)
        autotune.reset_measured_cache()
        assert autotune.packed_blocks(
            16, 128, 64, arch="starcoder2-3b", backend="jnp") == (8, 8)

    def test_paged_blocks_page_aligned(self):
        """The paged serving family returns a bq dividing the bucket and a
        PAGE-ALIGNED bk dividing the gathered view (the kernel gathers
        whole pages; a page-straddling block would split a DMA mid-page)."""
        for t, ps, s in [(1, 16, 128), (8, 16, 2048), (16, 8, 128),
                         (32, 16, 4096)]:
            bq, bk = autotune.paged_blocks(t, ps, s, 64, arch="codeqwen")
            assert t % bq == 0 and s % bk == 0 and bk % ps == 0, \
                (t, ps, s, bq, bk)
        from repro.core.costmodel import paged_attention_tile_cost
        bq, bk = autotune.paged_blocks(32, 16, 4096, 64, arch="codeqwen")
        assert paged_attention_tile_cost(32, 4096, 16, 64, bq, bk) \
            < float("inf")

    def test_paged_gather_overhead_prefers_larger_kv_blocks(self):
        """The per-page descriptor cost makes tiny KV blocks strictly worse
        under the paged model than the packed one at equal shapes: the
        paged argmin's bk must be >= the packed argmin's bk."""
        for t, s in [(8, 2048), (32, 4096)]:
            _, bk_paged = autotune.paged_blocks(t, 8, s, 64, arch="a")
            _, bk_packed = autotune.packed_blocks(t, s, 64, arch="a")
            assert bk_paged >= bk_packed, (t, s, bk_paged, bk_packed)

    def test_paged_measured_override(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "measured.json"))
        autotune.reset_measured_cache()
        autotune.record("paged/8x16x64/codeqwen/jnp", (8, 32), 1.0)
        autotune.reset_measured_cache()
        assert autotune.paged_blocks(
            8, 16, 128, 64, arch="codeqwen", backend="jnp") == (8, 32)
        # the key omits s_view: a hit recorded at one view length must be
        # demoted to legal tiles at another (128 does not divide 192)
        autotune.record("paged/4x16x64/codeqwen/jnp", (4, 128), 1.0)
        autotune.reset_measured_cache()
        bq, bk = autotune.paged_blocks(
            4, 16, 192, 64, arch="codeqwen", backend="jnp")
        assert 4 % bq == 0 and 192 % bk == 0 and bk % 16 == 0, (bq, bk)
        assert bk <= 128
        autotune.reset_measured_cache()

    def test_rowwise_blocks_sublane_aligned(self):
        for m in (1, 7, 8, 100, 4096):
            bm = autotune.rowwise_blocks(m, 2048)
            assert bm % 8 == 0

    def test_gated_mlp_blocks_legal_for_config_shapes(self):
        """The gatedmlp family returns MXU-legal, VMEM-feasible tiles at
        the gated archs' (tokens, d_model, d_ff) shapes."""
        from repro.core.costmodel import gated_mlp_tile_cost
        for arch in ("codeqwen1.5-7b", "yi-34b", "mixtral-8x7b"):
            cfg = get_config(arch)
            m, k, n = 4 * 128, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
            bm, bn, bk = autotune.gated_mlp_blocks(m, k, n)
            assert autotune.is_mxu_legal(bm, bn, bk), (arch, bm, bn, bk)
            assert gated_mlp_tile_cost(m, k, n, bm, bn, bk) < float("inf")

    def test_gated_mlp_vmem_wall_accounts_both_accumulators(self):
        """The dual-GEMM holds TWO weight streams and TWO accumulators: its
        chosen tile must fit that working set, not the single-GEMM one."""
        from repro.core.costmodel import TPU_VMEM_BYTES
        bm, bn, bk = autotune.gated_mlp_blocks(4096, 8192, 28672)
        assert (2 * (bm * bk + 2 * bk * bn) + 2 * bm * bn * 4
                + bm * bn * 2) <= TPU_VMEM_BYTES

    def test_gated_mlp_measured_override(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "measured.json"))
        autotune.reset_measured_cache()
        autotune.record("gatedmlp/256x512x512/int8/pallas",
                        (8, 128, 128), 1.0)
        autotune.reset_measured_cache()
        assert autotune.gated_mlp_blocks(256, 512, 512) == (8, 128, 128)


class TestMoEGroupSize:
    """Capacity-bounded all-to-all cost model -> config-driven group size
    (replaces the MOE_GROUP_SIZE = 2048 constant)."""

    def test_returns_candidate_bounded_by_tokens(self):
        for t in (32, 512, 8192, 131072):
            sg = autotune.moe_group_size(t, 4096, 14336, 8, 2, 1.25)
            assert sg <= t
            assert sg in autotune._MOE_GROUP_CANDIDATES or sg == t

    def test_wider_expert_fanout_prefers_smaller_groups(self):
        """More experts blow up the (G, S, E, C) one-hot footprint, so the
        tuner must not pick LARGER groups for wider expert counts."""
        few = autotune.moe_group_size(131072, 2048, 1408, 8, 2, 1.25)
        many = autotune.moe_group_size(131072, 2048, 1408, 60, 4, 1.25)
        assert many <= few

    def test_measured_override(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "measured.json"))
        autotune.reset_measured_cache()
        autotune.record("moe/8192x4096x14336/8x2x1.25", (1024,), 1.0)
        autotune.reset_measured_cache()
        assert autotune.moe_group_size(8192, 4096, 14336, 8, 2, 1.25) == 1024

    def test_capacity_formula_matches_model(self):
        from repro.core.costmodel import moe_capacity
        for sg, e, k, cf in [(2048, 8, 2, 1.25), (64, 60, 4, 1.25),
                             (8, 4, 2, 1.0)]:
            assert moe_capacity(sg, e, k, cf) == min(
                max(int(cf * sg * k / e), 4), sg)


class TestMeasuredCache:
    def test_measured_entry_overrides_table(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "measured.json"))
        autotune.reset_measured_cache()
        table = autotune.gemm_blocks(256, 512, 512)
        autotune.record("gemm/256x512x512/int8/pallas", (8, 128, 128), 1.0)
        autotune.reset_measured_cache()
        assert autotune.gemm_blocks(256, 512, 512) == (8, 128, 128)
        assert table != (8, 128, 128) or True  # table value need not differ

    def test_record_keeps_fastest(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "measured.json"))
        autotune.reset_measured_cache()
        autotune.record("k", (8, 128, 128), 5.0)
        autotune.record("k", (16, 128, 128), 9.0)   # slower: ignored
        with open(autotune.cache_path()) as f:
            stored = json.load(f)
        assert list(stored) == [autotune.measured_key("k")]
        assert stored[autotune.measured_key("k")]["blocks"] == [8, 128, 128]

    def test_measure_times_candidates(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "measured.json"))
        autotune.reset_measured_cache()
        best = autotune.measure(
            "gemm/64x64x64/int8/pallas",
            [(8, 128, 128), (64, 128, 128)],
            timer=lambda blocks: float(blocks[0]))  # "faster" = smaller bm
        assert best == (8, 128, 128)
        autotune.reset_measured_cache()
        assert autotune.gemm_blocks(64, 64, 64) == (8, 128, 128)


class TestSweepRunner:
    def test_sweep_writes_keys_autotune_consumes(self, tmp_path,
                                                 monkeypatch):
        """`kernel_bench.py --sweep` round trip: the runner times real
        candidates, records under the exact lookup keys, and a fresh
        autotune lookup returns the measured blocks."""
        import sys as _sys, os as _os
        _sys.path.insert(0, _os.path.join(_os.path.dirname(__file__), ".."))
        from benchmarks import kernel_bench
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "measured.json"))
        autotune.reset_measured_cache()
        keys = kernel_bench.sweep(backend="jnp",
                                  families=("rowwise", "decode"))
        assert any(k.startswith("rowwise/") for k in keys)
        assert any(k.startswith("decode/") for k in keys)
        import json
        cache = json.loads((tmp_path / "measured.json").read_text())
        for key in keys:
            ent = cache[autotune.measured_key(key)]
            assert "blocks" in ent and "us" in ent
        # the lookup path consumes what the sweep wrote
        dec = next(k for k in keys if k.startswith("decode/"))
        s, d, g = (int(v) for v in dec.split("/")[1].split("x"))
        assert autotune.decode_blocks(s, d, g) == \
            cache[autotune.measured_key(dec)]["blocks"][0]
        autotune.reset_measured_cache()


class TestFusedEpilogues:
    """Acceptance: fused == unfused bit-for-bit on BOTH backends."""

    @pytest.fixture(autouse=True)
    def _interp(self):
        set_interpret(True)
        yield
        ops.set_backend("jnp")

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_gemm_gelu_bit_identical(self, rng, backend):
        x = jnp.asarray(rng.integers(-127, 128, (37, 96)), jnp.int8)
        w = jnp.asarray(rng.integers(-127, 128, (96, 72)), jnp.int8)
        s0 = 8.0 / 127.0
        ops.set_backend(backend)
        unfused = ops.gelu_i8(ops.gemm_i8(x, w).astype(jnp.int32), s0)
        fused = ops.gemm_i8_gelu(x, w, s0)
        assert (fused == unfused).all()
        # and both match the jnp oracle exactly
        assert (fused == ref.int8_gemm_gelu_ref(x, w, s0)).all()

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_gemm_residual_bit_identical(self, rng, backend):
        x = jnp.asarray(rng.integers(-127, 128, (32, 96)), jnp.int8)
        w = jnp.asarray(rng.integers(-127, 128, (96, 72)), jnp.int8)
        res = jnp.asarray(rng.integers(-127, 128, (32, 72)), jnp.int8)
        rq = inum.compute_requant_params(3e-3, 96 * 127 * 127)
        ops.set_backend(backend)
        unfused = jnp.clip(
            ops.requant(ops.gemm_i8(x, w), rq).astype(jnp.int32)
            + res.astype(jnp.int32), -128, 127).astype(jnp.int8)
        fused = ops.gemm_i8_add(x, w, rq, res)
        assert (fused == unfused).all()
        assert (fused == ref.int8_gemm_add_ref(x, w, rq, res)).all()

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_w8a8_scaled_epilogues_bit_identical(self, rng, backend):
        """The model-path fusion: dequant (+gelu | +residual) in-kernel."""
        xf = jnp.asarray(rng.normal(size=(11, 96)), jnp.float32)
        w = jnp.asarray(rng.integers(-127, 128, (96, 72)), jnp.int8)
        ws = jnp.asarray(np.abs(rng.normal(size=(72,))) + 0.01, jnp.float32)
        resf = jnp.asarray(rng.normal(size=(11, 72)), jnp.bfloat16)
        s0 = 8.0 / 127.0
        ops.set_backend("jnp")
        xq, xs = ops.quant_rows(xf)
        plain_ref = ref.gemm_w8a8_ref(xq, xs, w, ws)
        add_ref = ref.gemm_w8a8_ref(xq, xs, w, ws, residual=resf)
        gelu_ref = ref.gemm_w8a8_ref(xq, xs, w, ws, gelu_scale=s0)
        ops.set_backend(backend)
        assert (ops.gemm_w8a8(xq, xs, w, ws) == plain_ref).all()
        assert (ops.gemm_w8a8(xq, xs, w, ws, residual=resf) == add_ref).all()
        assert (ops.gemm_w8a8(xq, xs, w, ws, gelu_scale=s0) == gelu_ref).all()

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    @pytest.mark.parametrize("act", ["silu", "gelu"])
    def test_gated_mlp_dual_gemm_bit_identical(self, rng, backend, act):
        """The last matrix row: dual-GEMM dequant + activation(gate) * up
        == the unfused two-GEMM composition, bit for bit."""
        xf = jnp.asarray(rng.normal(size=(11, 96)), jnp.float32)
        wu = jnp.asarray(rng.integers(-127, 128, (96, 72)), jnp.int8)
        wg = jnp.asarray(rng.integers(-127, 128, (96, 72)), jnp.int8)
        us = jnp.asarray(np.abs(rng.normal(size=(72,))) + 0.01, jnp.float32)
        gs = jnp.asarray(np.abs(rng.normal(size=(72,))) + 0.01, jnp.float32)
        s0 = 8.0 / 127.0
        ops.set_backend("jnp")
        xq, xs = ops.quant_rows(xf)
        unfused_ref = ref.gated_mlp_w8a8_ref(xq, xs, wu, us, wg, gs,
                                             act=act, act_scale=s0)
        ops.set_backend(backend)
        fused = ops.gated_mlp_w8a8(xq, xs, wu, us, wg, gs, act=act,
                                   act_scale=s0)
        assert (np.asarray(fused, np.float32)
                == np.asarray(unfused_ref, np.float32)).all()

    def test_model_gated_path_matches_unfused_forward(self, rng):
        """End-to-end: ``linear_gated_w8a8`` (the model's fused SwiGLU/GeGLU
        hidden) == linear_w8a8 x2 -> integer activation -> multiply, on
        both backends' dispatch decisions."""
        from repro.models.layers import (
            ExecMode, activation, linear_gated_w8a8, linear_w8a8)
        mode = ExecMode("w8a8")
        x = jnp.asarray(rng.normal(size=(5, 64)), jnp.bfloat16)
        wu = jnp.asarray(rng.integers(-127, 128, (64, 128)), jnp.int8)
        wg = jnp.asarray(rng.integers(-127, 128, (64, 128)), jnp.int8)
        us = jnp.asarray(np.abs(rng.normal(size=(128,))) + 0.01, jnp.float32)
        gs = jnp.asarray(np.abs(rng.normal(size=(128,))) + 0.01, jnp.float32)
        for act in ("silu", "gelu"):
            ops.set_backend("jnp")
            unfused = (activation(linear_w8a8(x, wg, gs), act, mode)
                       * linear_w8a8(x, wu, us))
            for backend in ("jnp", "pallas"):
                ops.set_backend(backend)
                fused = linear_gated_w8a8(x, wu, us, wg, gs, act)
                assert (np.asarray(fused, np.float32)
                        == np.asarray(unfused, np.float32)).all(), (
                    act, backend)

    def test_model_fused_paths_match_unfused_forward(self, rng):
        """End-to-end: the integer MLP/attention fusions leave the w8a8
        forward pass bit-identical between backends' dispatch decisions."""
        from repro.models.layers import (
            ExecMode, GELU_INT_SCALE, activation, linear_gelu_w8a8,
            linear_w8a8)
        mode = ExecMode("w8a8")
        x = jnp.asarray(rng.normal(size=(5, 64)), jnp.bfloat16)
        w = jnp.asarray(rng.integers(-127, 128, (64, 128)), jnp.int8)
        ws = jnp.asarray(np.abs(rng.normal(size=(128,))) + 0.01, jnp.float32)
        ops.set_backend("jnp")
        unfused = activation(linear_w8a8(x, w, ws), "gelu", mode)
        for backend in ("jnp", "pallas"):
            ops.set_backend(backend)
            fused = linear_gelu_w8a8(x, w, ws)
            assert (fused == unfused).all(), backend
        assert GELU_INT_SCALE == pytest.approx(8.0 / 127.0)


class TestW4A8Blocks:
    """The packed-int4 families: group-aligned, MXU-legal, overridable."""

    def test_gemm_w4a8_blocks_group_aligned_for_config_shapes(self):
        from repro.core.costmodel import gemm_w4a8_tile_cost
        shapes = sorted(set(_config_gemm_shapes(max_archs=3)))
        for m, k, n in shapes:
            for g in (32, 64, 128):
                if k % g:
                    continue
                bm, bn, bk = autotune.gemm_w4a8_blocks(m, k, n, g)
                assert autotune.is_mxu_legal(bm, bn, bk), (m, k, n, g)
                assert bk % g == 0, (m, k, n, g, bk)
                assert gemm_w4a8_tile_cost(m, k, n, g, bm, bn, bk) \
                    < float("inf")

    def test_gatedmlp_w4a8_blocks_group_aligned(self):
        from repro.core.costmodel import gated_mlp_w4a8_tile_cost
        for arch in ("codeqwen1.5-7b", "yi-34b"):
            cfg = get_config(arch)
            m, k, n = 4 * 128, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
            for g in (64, 128):
                bm, bn, bk = autotune.gatedmlp_w4a8_blocks(m, k, n, g)
                assert autotune.is_mxu_legal(bm, bn, bk), (arch, g)
                assert bk % g == 0, (arch, g, bk)
                assert gated_mlp_w4a8_tile_cost(m, k, n, g, bm, bn, bk) \
                    < float("inf")

    def test_smaller_groups_never_pick_group_straddling_bk(self):
        """A bk the group does not divide would split a scale group across
        K blocks; the lattice must treat it as illegal, so the chosen bk is
        always a multiple of the group even when the plain gemm table's
        optimum is not."""
        for g in (32, 64, 128):
            _, _, bk = autotune.gemm_w4a8_blocks(512, 4096, 4096, g)
            assert bk % g == 0

    def test_measured_override_both_families(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "measured.json"))
        autotune.reset_measured_cache()
        autotune.record("gemm_w4a8/256x512x512/g64/pallas",
                        (8, 128, 128), 1.0)
        autotune.record("gatedmlp_w4a8/256x512x512/g64/pallas",
                        (8, 128, 128), 1.0)
        autotune.reset_measured_cache()
        assert autotune.gemm_w4a8_blocks(256, 512, 512, 64) == (8, 128, 128)
        assert autotune.gatedmlp_w4a8_blocks(256, 512, 512, 64) \
            == (8, 128, 128)
        # a different group size is a DIFFERENT key: no false sharing
        assert autotune.gemm_w4a8_blocks(256, 512, 512, 128) \
            != autotune.gemm_w4a8_blocks(256, 512, 512, 64) \
            or autotune.gemm_w4a8_blocks(256, 512, 512, 128)[2] % 128 == 0


class TestW4A8Fused:
    """Acceptance: fused packed-int4 kernels == the unfused unpack ->
    int8-GEMM -> dequant composition bit-for-bit on BOTH backends."""

    @pytest.fixture(autouse=True)
    def _interp(self):
        set_interpret(True)
        yield
        ops.set_backend("jnp")

    @staticmethod
    def _w4_leaf(rng, k, n, g):
        from repro.kernels.quantize import pack_int4
        w4 = pack_int4(jnp.asarray(rng.integers(-8, 8, (k, n)), jnp.int8))
        qm = jnp.asarray(rng.integers(1, 128, (k // g, n)), jnp.int8)
        ws = jnp.asarray(np.abs(rng.normal(size=(n,))) * 0.001 + 1e-4,
                         jnp.float32)
        return w4, qm, ws

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_w4a8_scaled_epilogues_bit_identical(self, rng, backend):
        xf = jnp.asarray(rng.normal(size=(11, 96)), jnp.float32)
        w4, qm, ws = self._w4_leaf(rng, 96, 72, 32)
        resf = jnp.asarray(rng.normal(size=(11, 72)), jnp.bfloat16)
        bias = jnp.asarray(rng.normal(size=(72,)), jnp.float32)
        s0 = 8.0 / 127.0
        ops.set_backend("jnp")
        xq, xs = ops.quant_rows(xf)
        plain_ref = ref.gemm_w4a8_ref(xq, xs, w4, qm, ws)
        bias_ref = ref.gemm_w4a8_ref(xq, xs, w4, qm, ws, bias=bias)
        add_ref = ref.gemm_w4a8_ref(xq, xs, w4, qm, ws, residual=resf)
        gelu_ref = ref.gemm_w4a8_ref(xq, xs, w4, qm, ws, gelu_scale=s0)
        ops.set_backend(backend)
        assert (ops.gemm_w4a8(xq, xs, w4, qm, ws) == plain_ref).all()
        assert (ops.gemm_w4a8(xq, xs, w4, qm, ws, bias=bias)
                == bias_ref).all()
        assert (ops.gemm_w4a8(xq, xs, w4, qm, ws, residual=resf)
                == add_ref).all()
        assert (ops.gemm_w4a8(xq, xs, w4, qm, ws, gelu_scale=s0)
                == gelu_ref).all()

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    @pytest.mark.parametrize("act", ["silu", "gelu"])
    def test_gated_w4a8_dual_gemm_bit_identical(self, rng, backend, act):
        xf = jnp.asarray(rng.normal(size=(11, 96)), jnp.float32)
        u4, um, us = self._w4_leaf(rng, 96, 72, 32)
        g4, gm, gs = self._w4_leaf(rng, 96, 72, 32)
        s0 = 8.0 / 127.0
        ops.set_backend("jnp")
        xq, xs = ops.quant_rows(xf)
        unfused_ref = ref.gated_mlp_w4a8_ref(xq, xs, u4, um, us, g4, gm, gs,
                                             act=act, act_scale=s0)
        ops.set_backend(backend)
        fused = ops.gated_mlp_w4a8(xq, xs, u4, um, us, g4, gm, gs,
                                   act=act, act_scale=s0)
        assert (np.asarray(fused, np.float32)
                == np.asarray(unfused_ref, np.float32)).all()

    def test_model_w4_gated_path_matches_unfused_forward(self, rng):
        """``linear_gated_w4a8`` == linear_w4a8 x2 -> integer activation ->
        multiply.  Compared per backend: the dynamic activation quant runs
        inside both sides, and quant_rows may differ by 1 ulp ACROSS
        backends (interpret-mode reciprocal-multiply), so fused and unfused
        must share a backend to be comparable bit-for-bit."""
        from repro.models.layers import (
            ExecMode, activation, linear_gated_w4a8, linear_w4a8)
        mode = ExecMode("w4a8")
        x = jnp.asarray(rng.normal(size=(5, 64)), jnp.bfloat16)
        u4, um, us = self._w4_leaf(rng, 64, 128, 32)
        g4, gm, gs = self._w4_leaf(rng, 64, 128, 32)
        up = {"w4": u4, "qmul": um, "scale": us}
        gate = {"w4": g4, "qmul": gm, "scale": gs}
        for act in ("silu", "gelu"):
            for backend in ("jnp", "pallas"):
                ops.set_backend(backend)
                unfused = (activation(linear_w4a8(x, g4, gm, gs), act, mode)
                           * linear_w4a8(x, u4, um, us))
                fused = linear_gated_w4a8(x, up, gate, act)
                assert (np.asarray(fused, np.float32)
                        == np.asarray(unfused, np.float32)).all(), (
                    act, backend)

    def test_model_w4_gelu_path_matches_unfused_forward(self, rng):
        from repro.models.layers import (
            ExecMode, activation, linear_gelu_w4a8, linear_w4a8)
        mode = ExecMode("w4a8")
        x = jnp.asarray(rng.normal(size=(5, 64)), jnp.bfloat16)
        w4, qm, ws = self._w4_leaf(rng, 64, 128, 32)
        for backend in ("jnp", "pallas"):
            ops.set_backend(backend)
            unfused = activation(linear_w4a8(x, w4, qm, ws), "gelu", mode)
            fused = linear_gelu_w4a8(x, w4, qm, ws)
            assert (fused == unfused).all(), backend
