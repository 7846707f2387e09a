"""Self-speculative decoding: the speculative ≡ vanilla equivalence harness.

The engine's speculative path (serve/engine.py, serve/draft.py) must be a
SCHEDULING change only: greedy output bit-identical to vanilla decode at
every k, across precision (bf16 / int8 KV), layout (dense / paged),
schedule (packed / chunked), and memory pressure (offline / preempt+swap).
The proposer is pluggable, so the harness also drives ADVERSARIAL drafts
through the real engine — all-accept (the oracle), all-reject (always
wrong), and random garbage — and the output must not move: drafts buy
speed, never correctness.

The heaviest matrix slices are marked ``slow`` (see tests/conftest.py):
scripts/verify.sh runs ``pytest -m "not slow"`` as the fast tier; a plain
``pytest`` run still covers everything.
"""
import itertools

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import init_params
from repro.serve import ServeConfig, ServingEngine
from repro.serve.draft import ngram_propose

KEY = jax.random.PRNGKey(0)

# repetition-heavy + one aperiodic prompt: the n-gram proposer must both
# fire (cyclic prompts, and reduced-model greedy decode itself settles
# into cycles) and stay harmless where it has nothing to propose
PROMPTS = [
    ([5, 6, 7, 8] * 6)[:20],
    ([11, 12, 13] * 7)[:18],
    ([3, 4] * 8)[:14],
    [9, 3, 11, 4, 2, 30, 31],
]

_MODEL = {}
_BASELINE = {}


def _model():
    if not _MODEL:
        cfg = get_config("starcoder2-3b", reduced=True)
        _MODEL["m"] = (cfg, init_params(KEY, cfg))
    return _MODEL["m"]


def _engine(**kw):
    cfg, params = _model()
    kw.setdefault("batch_lanes", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("token_budget", 8)
    return ServingEngine(params, cfg, ServeConfig(**kw))


def _drain(eng, prompts=PROMPTS, max_new=12):
    for i, p in enumerate(prompts):
        eng.submit(list(p), max_new=max_new, request_id=i)
    done = eng.run_until_drained()
    assert len(done) == len(prompts)
    return {d["id"]: d["tokens"] for d in done}


def _vanilla(**kw):
    """Cached vanilla (spec_k=0) drain for a given engine config."""
    key = tuple(sorted(kw.items()))
    if key not in _BASELINE:
        _BASELINE[key] = _drain(_engine(**kw))
    return _BASELINE[key]


# ---------------------------------------------------------------------------
# the proposer itself (pure host code)
# ---------------------------------------------------------------------------
class TestDraftProposer:
    def test_proposes_continuation_of_most_recent_match(self):
        #          match here ↓ (latest occurrence of trailing [1, 2])
        ctx = [1, 2, 9, 9, 1, 2, 7, 8, 1, 2]
        assert ngram_propose(ctx, 3) == [7, 8, 1]

    def test_longest_ngram_wins_over_recency(self):
        # trailing 3-gram [1,2,3] matches early; trailing 1-gram [3] has a
        # later match — the longer pattern is the better evidence
        ctx = [1, 2, 3, 7, 5, 3, 9, 1, 2, 3]
        assert ngram_propose(ctx, 2) == [7, 5]

    def test_cycle_proposes_the_cycle(self):
        # the most recent trailing-3-gram match overlaps the context end
        # (continuation clipped to one period's remainder); an older match
        # carries a full k-token continuation and must win
        ctx = [4, 5, 6] * 5
        assert ngram_propose(ctx, 6) == [4, 5, 6, 4, 5, 6]
        assert ngram_propose(ctx, 2) == [4, 5]

    def test_constant_tail_drafts_full_k(self):
        # the degenerate period-1 cycle greedy decode loves to fall into:
        # every draft slot must fill, not just the 1-token clipped match
        ctx = [7, 3] + [9] * 10
        assert ngram_propose(ctx, 5) == [9] * 5

    def test_no_repetition_proposes_nothing(self):
        assert ngram_propose([1, 2, 3, 4, 5, 6, 7], 4) == []

    def test_k_zero_and_tiny_context(self):
        assert ngram_propose([1, 2, 1, 9], 0) == []
        assert ngram_propose([], 4) == []
        assert ngram_propose([7], 4) == []

    def test_draft_shorter_than_k_near_context_end(self):
        ctx = [1, 2, 3, 9, 1, 2, 3]        # match continuation has 1 token
        assert ngram_propose(ctx, 8) == [9, 1, 2, 3][:8]

    @settings(max_examples=50)
    @given(st.integers(0, 2 ** 31), st.integers(1, 8), st.integers(2, 40))
    def test_properties_on_random_contexts(self, seed, k, n):
        """Any context: drafts are a copied slice of the context, at most
        k long, and deterministic."""
        rng = np.random.default_rng(seed)
        ctx = [int(t) for t in rng.integers(0, 4, size=n)]
        d = ngram_propose(ctx, k)
        assert len(d) <= k
        assert d == ngram_propose(ctx, k)          # deterministic
        if d:
            # the draft is the continuation of some earlier occurrence of
            # a trailing n-gram
            found = False
            for ng in range(1, 4):
                pat = ctx[-ng:]
                for i in range(len(ctx) - ng):
                    if (ctx[i:i + ng] == pat
                            and ctx[i + ng:i + ng + k] == d):
                        found = True
            assert found, (ctx, d)


# ---------------------------------------------------------------------------
# speculative ≡ vanilla: the k x precision x layout x schedule matrix
# ---------------------------------------------------------------------------
class TestSpeculativeExact:
    @pytest.mark.parametrize("paged", [False, True])
    @pytest.mark.parametrize("k", [2, 4, 8])
    def test_packed_offline_bf16(self, k, paged):
        eng = _engine(spec_k=k, paged=paged)
        assert _drain(eng) == _vanilla(paged=paged)
        st_ = eng.stats
        assert st_["spec_drafted"] > 0 and st_["spec_accepted"] > 0
        if paged:
            eng.pool.check()

    @pytest.mark.slow
    @pytest.mark.parametrize("paged", [False, True])
    @pytest.mark.parametrize("k", [2, 4, 8])
    def test_packed_offline_int8(self, k, paged):
        eng = _engine(spec_k=k, paged=paged, int8_kv=True)
        assert _drain(eng) == _vanilla(paged=paged, int8_kv=True)
        assert eng.stats["spec_accepted"] > 0

    @pytest.mark.parametrize("paged", [False, True])
    def test_chunked_offline(self, paged):
        eng = _engine(spec_k=4, paged=paged, token_budget=0, prefill_chunk=8)
        assert eng.mode == "chunked"
        assert _drain(eng) == _vanilla(paged=paged, token_budget=0,
                                       prefill_chunk=8)
        assert eng.stats["spec_accepted"] > 0

    @pytest.mark.slow
    @pytest.mark.parametrize("paged", [False, True])
    @pytest.mark.parametrize("k", [2, 8])
    def test_chunked_offline_matrix(self, k, paged):
        eng = _engine(spec_k=k, paged=paged, token_budget=0, prefill_chunk=8)
        assert _drain(eng) == _vanilla(paged=paged, token_budget=0,
                                       prefill_chunk=8)
        assert eng.stats["spec_accepted"] > 0

    def test_chunked_equals_packed_with_speculation(self):
        """The schedule-equivalence guarantee survives speculation: the
        SAME spec_k through packed and chunked drains to identical
        tokens."""
        packed = _drain(_engine(spec_k=4))
        chunked = _drain(_engine(spec_k=4, token_budget=0, prefill_chunk=8))
        assert packed == chunked

    @pytest.mark.parametrize("k", [2, 4])
    def test_pressure_preempts_speculating_lane_exactly(self, k):
        """Tiny pool under 4 co-resident speculating lanes: lanes are
        preempted mid-request (KV pages swapped to host) and resumed —
        the drain must still match the UNCONSTRAINED vanilla run
        bit-for-bit, with both machinery counters engaged (preemptions
        AND accepted drafts), and the pool must drain leak-free."""
        base = _vanilla(batch_lanes=4, paged=True, int8_kv=True,
                        token_budget=16)
        eng = _engine(spec_k=k, batch_lanes=4, paged=True, int8_kv=True,
                      token_budget=16, pool_pages=8, page_size=8)
        eng._clock = itertools.count().__next__
        assert _drain(eng) == base
        st_ = eng.stats
        assert st_["preemptions"] > 0 and st_["resumes"] > 0
        assert st_["swap_out_pages"] == st_["swap_in_pages"] > 0
        assert st_["spec_drafted"] > 0 and st_["spec_accepted"] > 0
        eng.pool.check()
        assert not eng.pool.table.any()            # drained: zero pages held

    def test_pressure_throttles_draft_length(self):
        """Swap-aware draft adaptation: while any lane sits preempted for
        pool pressure, drafts run at HALF their configured spec_k (the
        spec_throttled counter ticks once per halved proposal) — and
        because draft content never reaches the committed stream, the
        throttled drain still matches unconstrained vanilla bit-for-bit
        with acceptance engaged.  Full-length drafting must resume once
        the pressure clears: with max spec_k under an empty preempted
        queue, at least one proposal must reach the un-halved cap."""
        base = _vanilla(batch_lanes=4, paged=True, int8_kv=True,
                        token_budget=16)
        eng = _engine(spec_k=4, batch_lanes=4, paged=True, int8_kv=True,
                      token_budget=16, pool_pages=8, page_size=8)
        eng._clock = itertools.count().__next__
        drafted_lens = []
        orig = eng._propose

        def spy(lane):
            d = orig(lane)
            drafted_lens.append((len(eng.preempted), len(d)))
            return d

        eng._propose = spy
        assert _drain(eng) == base
        st_ = eng.stats
        assert st_["preemptions"] > 0 and st_["resumes"] > 0
        assert st_["spec_throttled"] > 0
        assert st_["spec_drafted"] > 0 and st_["spec_accepted"] > 0
        # every proposal made under pressure respected the halved cap ...
        assert all(n <= 2 for p, n in drafted_lens if p > 0)
        # ... and full-length drafting resumed after the pool cleared
        assert any(n > 2 for p, n in drafted_lens if p == 0)
        eng.pool.check()
        assert not eng.pool.table.any()

    def test_no_throttle_without_pressure(self):
        """An unpressured speculative drain never ticks spec_throttled —
        the throttle must not tax the common case."""
        eng = _engine(spec_k=4, paged=True, page_size=8)
        _drain(eng)
        assert eng.stats["spec_throttled"] == 0
        assert eng.stats["spec_drafted"] > 0

    @pytest.mark.slow
    def test_pressure_k8(self):
        base = _vanilla(batch_lanes=4, paged=True, int8_kv=True,
                        token_budget=16)
        eng = _engine(spec_k=8, batch_lanes=4, paged=True, int8_kv=True,
                      token_budget=16, pool_pages=8, page_size=8)
        assert _drain(eng) == base
        assert eng.stats["preemptions"] > 0
        assert eng.stats["spec_accepted"] > 0
        eng.pool.check()

    def test_speculation_reduces_forwards_on_repetitive_workload(self):
        """The point of the whole exercise: fewer engine steps (forwards)
        per committed token when drafts accept."""
        v = _engine()
        _drain(v, max_new=32)
        s = _engine(spec_k=4)
        toks = _drain(s, max_new=32)
        assert toks == {d["id"]: d["tokens"] for d in v.finished}
        assert s.stats["steps"] < v.stats["steps"]
        assert s.stats["spec_accepted"] > 0

    def test_per_request_stats_and_metrics(self):
        eng = _engine(spec_k=4)
        _drain(eng)
        done = {d["id"]: d for d in eng.finished}
        drafted = sum(d.get("spec_drafted", 0) for d in done.values())
        accepted = sum(d.get("spec_accepted", 0) for d in done.values())
        assert drafted == eng.stats["spec_drafted"] > 0
        assert accepted == eng.stats["spec_accepted"] > 0
        m = eng.serving_metrics()
        assert m["spec_drafted"] == drafted
        assert 0 < m["spec_accept_rate"] <= 1
        assert f"spec[k=4" in eng.stats_summary()

    def test_spec_k_ignored_by_tokenwise_mode(self):
        eng = _engine(spec_k=4, token_budget=0, prefill_chunk=0)
        assert eng.mode == "tokenwise"
        assert eng._spec_k == 0
        assert _drain(eng) == _vanilla(token_budget=0, prefill_chunk=0)


# ---------------------------------------------------------------------------
# adversarial draft sequences through the REAL engine: output must not move
# ---------------------------------------------------------------------------
class _ScriptedDrafts:
    """Proposer that knows each request's vanilla greedy stream (keyed by
    prompt prefix) and drafts a chosen distortion of it."""

    def __init__(self, vanilla: dict, prompts, distort):
        self._streams = {tuple(p): vanilla[i] for i, p in enumerate(prompts)}
        self._distort = distort

    def __call__(self, ctx, k):
        for p, stream in self._streams.items():
            if tuple(ctx[:len(p)]) == p and list(ctx[len(p):]) == \
                    stream[:len(ctx) - len(p)]:
                nxt = stream[len(ctx) - len(p):len(ctx) - len(p) + k]
                return [self._distort(t) for t in nxt]
        raise AssertionError(f"context diverged from vanilla: {ctx}")


class TestAdversarialDrafts:
    @pytest.mark.parametrize("paged", [False, True])
    def test_all_accept_oracle_drafts(self, paged):
        """Drafts = the vanilla stream itself: every draft verifies, the
        engine commits k+1 tokens per speculative step, and the output is
        (trivially but measurably) unchanged."""
        base = _vanilla(paged=paged)
        eng = _engine(spec_k=4, paged=paged)
        eng._draft_fn = _ScriptedDrafts(base, PROMPTS, lambda t: t)
        assert _drain(eng) == base
        st_ = eng.stats
        assert st_["spec_drafted"] == st_["spec_accepted"] > 0

    @pytest.mark.parametrize("paged", [False, True])
    def test_all_reject_drafts(self, paged):
        """Drafts = vanilla stream + 1 (mod vocab): every draft token is
        wrong, every speculative step rolls its whole tail back, and the
        output STILL matches vanilla — the corrective token carries the
        stream forward alone."""
        cfg, _ = _model()
        base = _vanilla(paged=paged)
        eng = _engine(spec_k=4, paged=paged)
        eng._draft_fn = _ScriptedDrafts(
            base, PROMPTS, lambda t: (t + 1) % cfg.vocab_size)
        assert _drain(eng) == base
        st_ = eng.stats
        assert st_["spec_drafted"] > 0 and st_["spec_accepted"] == 0

    # deadline=None: the first example compiles JAX
    @settings(max_examples=5, deadline=None)
    @given(st.integers(0, 2 ** 31))
    def test_random_garbage_drafts(self, seed):
        """ANY proposer is output-safe: random tokens, random lengths
        (including empty), dense and paged."""
        cfg, _ = _model()
        rng = np.random.default_rng(seed)

        def garbage(ctx, k):
            return [int(t) for t in
                    rng.integers(0, cfg.vocab_size,
                                 size=rng.integers(0, k + 1))]

        for paged in (False, True):
            eng = _engine(spec_k=4, paged=paged)
            eng._draft_fn = garbage
            assert _drain(eng) == _vanilla(paged=paged)

    def test_mixed_right_and_wrong_prefixes(self):
        """Drafts correct for the first j tokens then wrong: the commit
        must take exactly the verified prefix + 1 corrective token."""
        cfg, _ = _model()
        base = _vanilla()
        flip = itertools.cycle([0, 1, 2, 3])   # how many leading tokens right

        class Mixed(_ScriptedDrafts):
            def __call__(self, ctx, k):
                right = next(flip)
                self._distort = lambda t, n=itertools.count(): (
                    t if next(n) < right else (t + 7) % cfg.vocab_size)
                return super().__call__(ctx, k)

        eng = _engine(spec_k=4)
        eng._draft_fn = Mixed(base, PROMPTS, lambda t: t)
        assert _drain(eng) == base
        st_ = eng.stats
        assert 0 < st_["spec_accepted"] < st_["spec_drafted"]


# ---------------------------------------------------------------------------
# PRNG-stream invariance: speculation must never touch sampled lanes
# ---------------------------------------------------------------------------
class TestSpecPRNGInvariance:
    def test_sampled_streams_unmoved_by_spec_k(self):
        """Extends the PR 3 warmup-invariance contract: a sampled engine
        (temperature > 0) with spec_k set must produce bit-identical
        tokens to one without — speculation silently disables rather than
        perturbing the per-lane PRNG fold sequence."""
        base = _drain(_engine(temperature=0.9, seed=7))
        eng = _engine(temperature=0.9, seed=7, spec_k=8)
        assert eng._spec_k == 0                    # resolved off, not capped
        assert _drain(eng) == base
        assert eng.stats["spec_steps"] == 0

    def test_warmup_with_speculation_does_not_shift_streams(self):
        """Warmup drains may themselves speculate (greedy engines); the
        reserved warmup key space + draft determinism keep later requests'
        tokens identical with or without warmup."""
        base = _drain(_engine(spec_k=4))
        eng = _engine(spec_k=4)
        eng.warmup()
        assert _drain(eng) == base

    def test_greedy_tokens_independent_of_spec_k_value(self):
        """k is a throughput knob, not a model input: every k drains to
        the same tokens (transitively pinned to vanilla elsewhere)."""
        outs = [_drain(_engine(spec_k=k)) for k in (0, 1, 2, 3, 5, 8)]
        assert all(o == outs[0] for o in outs)
