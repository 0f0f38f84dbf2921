"""Serve-path batching tests.

Covers the continuous-batching serve path end to end: single-pass batched
prefill parity against the sequential replay baseline (model- and
engine-level, across ≥2 (batch, seq) buckets), the batched-prefill cache
write against its old gather formula, chunked prefill vs unchunked,
admission-policy ordering, O(#(B, S) buckets) compile counts under
varying batch composition, §4.4 escalation on the batched artifact, and
the ``TreeSpec`` pytree padding it rides on.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import disc
from repro.configs import get_config
from repro.data.pipeline import Request
from repro.models import layers as L
from repro.models.registry import get_model, replay_prefill
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.policies import (ADMISSION_POLICIES, get_admission_policy,
                                  priority_first, shortest_prompt_first)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama_11b").reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(vocab, lens, max_new=4, prios=None):
    rng = np.random.RandomState(7)
    return [Request(rid=i,
                    tokens=rng.randint(0, vocab, size=ln).astype(np.int32),
                    max_new_tokens=max_new,
                    priority=0 if prios is None else prios[i])
            for i, ln in enumerate(lens)]


def _engine(model, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq", 96)
    return ServeEngine(model, params, ServeConfig(**kw))


# ----------------------------------------------------------------- parity --

class TestPrefillParity:
    @pytest.mark.parametrize("lens_set", [[5, 12, 16], [33, 20, 40]])
    def test_single_pass_matches_replay_model_level(self, tiny, lens_set):
        """model.prefill ≡ decode-step replay: logits and every valid
        cache position, across two different (B, S) shapes."""
        cfg, model, params = tiny
        b, smax = len(lens_set), max(lens_set)
        rng = np.random.RandomState(1)
        tokens = np.zeros((b, smax), np.int32)
        for r, ln in enumerate(lens_set):
            tokens[r, :ln] = rng.randint(0, cfg.vocab, size=ln)
        tokens = jnp.asarray(tokens)
        lens = jnp.asarray(lens_set, jnp.int32)
        offsets = jnp.zeros((b,), jnp.int32)
        cache0 = model.init_cache(b, 96)

        log_sp, cache_sp = model.prefill(params, cache0, tokens, lens,
                                         offsets)
        log_rp, cache_rp = replay_prefill(model.decode_step)(
            params, cache0, tokens, lens, offsets)

        np.testing.assert_allclose(np.asarray(log_sp), np.asarray(log_rp),
                                   atol=2e-4, rtol=2e-4)
        for leaf_sp, leaf_rp in zip(jax.tree.leaves(cache_sp),
                                    jax.tree.leaves(cache_rp)):
            a, c = np.asarray(leaf_sp), np.asarray(leaf_rp)
            for r, ln in enumerate(lens_set):  # (L, B, hkv, Lc, hd)
                np.testing.assert_allclose(a[:, r, :, :ln], c[:, r, :, :ln],
                                           atol=2e-4, rtol=2e-4)

    def test_engine_generations_match_replay(self, tiny):
        """End to end: same requests, same generated ids, while the
        batched engine launches strictly fewer prefills."""
        cfg, model, params = tiny
        lens = [5, 9, 14, 40, 33, 12]  # spans S buckets 16 and 64
        outs, calls = {}, {}
        for mode in ("batched", "replay"):
            eng = _engine(model, params, prefill_mode=mode)
            eng.submit(_requests(cfg.vocab, lens))
            outs[mode] = eng.run_until_done(max_steps=500)
            calls[mode] = eng.stats["prefill_calls"]
        assert outs["batched"] == outs["replay"]
        assert len(outs["batched"]) == len(lens)
        assert calls["batched"] < calls["replay"] == len(lens)

    def test_chunked_prefill_matches_unchunked(self, tiny):
        """Chunk-offset continuation reproduces the one-shot prefill, at
        the model level (explicit offsets) and through the engine."""
        cfg, model, params = tiny
        # model level: 24-token prompt in two 12-token chunks
        rng = np.random.RandomState(3)
        toks = rng.randint(0, cfg.vocab, size=(1, 24)).astype(np.int32)
        cache0 = model.init_cache(1, 96)
        one = jnp.asarray([12], jnp.int32)
        log_a, cache_a = model.prefill(
            params, cache0, jnp.asarray(toks), jnp.asarray([24], jnp.int32),
            jnp.zeros((1,), jnp.int32))
        _, cache_h = model.prefill(params, cache0, jnp.asarray(toks[:, :12]),
                                   one, jnp.zeros((1,), jnp.int32))
        log_b, cache_b = model.prefill(params, cache_h,
                                       jnp.asarray(toks[:, 12:]), one,
                                       jnp.asarray([12], jnp.int32))
        np.testing.assert_allclose(np.asarray(log_a), np.asarray(log_b),
                                   atol=2e-4, rtol=2e-4)
        for la, lb in zip(jax.tree.leaves(cache_a), jax.tree.leaves(cache_b)):
            np.testing.assert_allclose(np.asarray(la)[:, :, :, :24],
                                       np.asarray(lb)[:, :, :, :24],
                                       atol=2e-4, rtol=2e-4)

        # engine level: long prompts forced through 8-token chunks
        lens = [30, 22, 6, 17]
        base, chunked = {}, {}
        for chunk, sink in ((None, base), (8, chunked)):
            eng = _engine(model, params, prefill_chunk=chunk)
            eng.submit(_requests(cfg.vocab, lens))
            sink.update(eng.run_until_done(max_steps=500))
            if chunk:
                assert eng.stats["prefill_chunks"] > 0
        assert base == chunked


# ------------------------------------------------------------ cache write --

def _gather_write(kc, k, offsets, lens):
    """The element-wise formula the slice write replaced: an index tensor
    over the whole cache row and a ``take_along_axis`` gather, kept as the
    reference."""
    b, hkv, lc, hd = kc.shape
    j = jnp.arange(lc)[None, :] - offsets[:, None]
    written = (j >= 0) & (j < lens[:, None])
    idx = jnp.broadcast_to(jnp.clip(j, 0, k.shape[2] - 1)[:, None, :, None],
                           (b, hkv, lc, hd))
    return jnp.where(written[:, None, :, None],
                     jnp.take_along_axis(k, idx, axis=2).astype(kc.dtype), kc)


def _write_case(kind, b, rng, s=8, lc=24):
    """(offsets, lens, cache length) for one batched-prefill write."""
    lens = rng.randint(1, s + 1, size=b)
    if kind == "fresh":
        offsets = np.zeros(b, int)
    elif kind == "offset":
        offsets = rng.randint(1, lc - s + 1, size=b)
    elif kind == "overrun":          # offset + S runs past max_seq
        offsets = rng.randint(lc - s + 1, lc, size=b)
        lens[0] = s
    elif kind == "empty_rows":       # bucket-padded rows: lens 0
        offsets = rng.randint(0, lc, size=b)
        lens[::2] = 0
    else:                            # chunk wider than the cache
        lc = s - 3
        offsets = rng.randint(0, lc, size=b)
    return (jnp.asarray(offsets, jnp.int32), jnp.asarray(lens, jnp.int32),
            lc)


def _cache_write_gathers(hlo):
    return [ln for ln in hlo.splitlines()
            if re.search(r"\bgather\(", ln) and "attn/cache_write" in ln]


class TestCacheWrite:
    @pytest.mark.parametrize("kind", ["fresh", "offset", "overrun",
                                      "empty_rows", "wide_chunk"])
    @pytest.mark.parametrize("b", [1, 2, 3, 4])
    def test_slice_write_matches_gather(self, b, kind):
        """Bit for bit the gather formula; only [offset, offset+len)
        changes in each row."""
        rng = np.random.RandomState(100 * b + len(kind))
        s, hkv, hd = 8, 2, 4
        offsets, lens, lc = _write_case(kind, b, rng, s=s)
        kc = jnp.asarray(rng.randn(b, hkv, lc, hd), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, hkv, s, hd), jnp.float32)
        got = np.asarray(jax.jit(L.write_chunk)(kc, k, offsets, lens))
        want = np.asarray(jax.jit(_gather_write)(kc, k, offsets, lens))
        np.testing.assert_array_equal(got.view(np.uint16),
                                      want.view(np.uint16))
        old, new_ = np.asarray(kc), np.asarray(k.astype(jnp.bfloat16))
        for r in range(b):
            lo, hi = int(offsets[r]), int(offsets[r] + lens[r])
            pos = np.arange(lc)
            out = (pos < lo) | (pos >= hi)
            np.testing.assert_array_equal(
                got[r][:, out].view(np.uint16), old[r][:, out].view(np.uint16))
            inside = pos[~out]
            np.testing.assert_array_equal(got[r][:, inside],
                                          new_[r][:, inside - lo])

    def test_prefill_has_no_cache_write_gather(self, tiny):
        """The lowered batched prefill holds no gather in the
        ``attn/cache_write`` scope (the check itself finds the old
        formula's gather)."""
        cfg, model, params = tiny
        cache = model.init_cache(2, 32)
        tokens = jnp.zeros((2, 8), jnp.int32)
        lens = jnp.asarray([5, 8], jnp.int32)
        offsets = jnp.asarray([0, 3], jnp.int32)
        hlo = jax.jit(model.prefill).lower(
            params, cache, tokens, lens, offsets).compile().as_text()
        assert "attn/cache_write" in hlo
        assert _cache_write_gathers(hlo) == []

        def old(kc, k, offsets, lens):
            with jax.named_scope("attn/cache_write"):
                return _gather_write(kc, k, offsets, lens)

        kc = jnp.zeros((2, 2, 32, 4), jnp.bfloat16)
        k = jnp.ones((2, 2, 8, 4), jnp.float32)
        ref = jax.jit(old).lower(kc, k, offsets, lens).compile().as_text()
        assert _cache_write_gathers(ref)


# -------------------------------------------------------------- admission --

class TestAdmission:
    def test_policy_orderings(self):
        reqs = _requests(64, [24, 6, 12], prios=[0, 1, 3])
        assert [r.rid for r in ADMISSION_POLICIES["fifo"](reqs)] == [0, 1, 2]
        assert [r.rid for r in shortest_prompt_first(reqs)] == [1, 2, 0]
        assert [r.rid for r in priority_first(reqs)] == [2, 1, 0]
        assert get_admission_policy(shortest_prompt_first) \
            is shortest_prompt_first
        with pytest.raises(ValueError, match="unknown admission policy"):
            get_admission_policy("nope")

    def test_overlong_prompt_rejected_at_submit(self, tiny):
        """A prompt longer than max_seq is rejected gracefully — counted
        in stats and recorded in ``eng.rejected`` — while the rest of the
        batch is admitted and completes."""
        cfg, model, params = tiny
        eng = _engine(model, params, max_seq=64, prefill_chunk=16)
        reqs = _requests(cfg.vocab, [65, 8, 70, 12], max_new=2)
        eng.submit(reqs)
        assert eng.stats["rejected_requests"] == 2
        assert eng.rejected == [reqs[0].rid, reqs[2].rid]
        assert [r.rid for r in eng.queue] == [reqs[1].rid, reqs[3].rid]
        eng.run_until_done(max_steps=200)
        assert sorted(eng.done) == [reqs[1].rid, reqs[3].rid]
        assert eng.stats["requests_completed"] == 2

    def test_duplicate_rid_rejected_auto_rid_admits(self, tiny):
        """rids are the engine's stable request identity: submitting a
        rid that is already pending raises, while auto-assigned rids
        (Request(rid=None)) are unique and both requests complete."""
        cfg, model, params = tiny
        eng = _engine(model, params, max_batch=2)
        a, b = _requests(cfg.vocab, [8, 8], max_new=2)
        b.rid = a.rid
        with pytest.raises(ValueError, match="already pending"):
            eng.submit([a, b])
        rng = np.random.RandomState(7)
        auto = [Request(tokens=rng.randint(0, cfg.vocab, size=8)
                        .astype(np.int32), max_new_tokens=2)
                for _ in range(2)]
        assert auto[0].rid != auto[1].rid
        eng.submit(auto)
        eng.run_until_done(max_steps=100)
        assert eng.stats["requests_completed"] == 2

    def test_paged_decode_parity_across_buckets(self, tiny):
        """Unconstrained-pool paged decode is bit-parity with the
        fixed-row baseline, across ≥2 (B, S) prefill buckets (short and
        long prompts, full and partial batches)."""
        cfg, model, params = tiny
        lens = [5, 12, 40, 60, 9, 33]
        fixed = _engine(model, params, max_batch=3, max_seq=96)
        fixed.submit(_requests(cfg.vocab, lens, max_new=4))
        fixed.run_until_done(max_steps=400)
        paged = _engine(model, params, max_batch=3, max_seq=96,
                        kv_block_size=16)
        paged.submit(_requests(cfg.vocab, lens, max_new=4))
        paged.run_until_done(max_steps=400)
        assert fixed.stats["prefill_bucket_pairs"] >= 2
        assert paged.done == fixed.done
        assert paged.stats["kv_preemptions"] == 0
        assert paged.stats["kv_blocks_in_use"] == 0
        paged.alloc.assert_consistent()

    @pytest.mark.parametrize("policy,expected", [
        ("fifo", [0, 1, 2]),
        ("shortest-prompt-first", [1, 2, 0]),
        ("priority", [2, 1, 0]),
    ])
    def test_engine_completion_order(self, tiny, policy, expected):
        """With one slot, completion order is exactly admission order."""
        cfg, model, params = tiny
        eng = _engine(model, params, max_batch=1, admission=policy)
        eng.submit(_requests(cfg.vocab, [24, 6, 12], max_new=2,
                             prios=[0, 1, 3]))
        done = eng.run_until_done(max_steps=300)
        assert list(done) == expected


# ---------------------------------------------------------- compile counts --

class TestCompileCounts:
    def test_o_buckets_across_batch_compositions(self, tiny):
        """A mixed trace re-using (B, S) buckets never recompiles; a new
        group size does — exactly once per new pair."""
        cfg, model, params = tiny
        eng = _engine(model, params)
        eng.submit(_requests(cfg.vocab, [9, 12, 14, 10]))   # (4, 16)
        eng.run_until_done(max_steps=300)
        first = eng.compile_counts()["prefill"]["bucket"]
        assert first == 1

        eng.submit(_requests(cfg.vocab, [13, 10, 15, 11]))  # (4, 16) again
        eng.run_until_done(max_steps=300)
        assert eng.compile_counts()["prefill"]["bucket"] == first

        eng.submit(_requests(cfg.vocab, [12, 12]))          # (2, 16): new B
        eng.run_until_done(max_steps=300)
        counts = eng.compile_counts()["prefill"]
        assert counts["bucket"] == first + 1
        assert counts["bucket"] == eng.stats["prefill_bucket_pairs"] == 2

    def test_escalation_on_hot_batched_signature(self, tiny):
        """§4.4 still works on the 2-D artifact: a hot exact (B, S)
        signature gets an unpadded specialization."""
        cfg, model, params = tiny
        eng = _engine(model, params, max_batch=2, max_seq=64,
                      escalation_threshold=2)
        for round_ in range(3):
            eng.submit(_requests(cfg.vocab, [7, 5], max_new=2))
            eng.run_until_done(max_steps=200)
        assert eng.stats["prefill_escalations"] >= 1
        assert eng.stats["requests_completed"] == 6

    def test_stats_keys_documented(self, tiny):
        from repro.serve.engine import STATS_KEYS
        cfg, model, params = tiny
        eng = _engine(model, params)
        assert set(eng.stats) == set(STATS_KEYS)


# --------------------------------------------------------------- TreeSpec --

class TestTreeSpec:
    def test_pads_pytree_leaves_to_bucket(self):
        seen = []

        def f(tree, x):
            seen.append((tree["a"].shape, x.shape))
            return tree["a"].sum() + x.sum()

        fn = disc.compile(
            f, specs=[disc.TreeSpec({0: "B"}),
                      disc.ArgSpec(("B", 2), jnp.float32)],
            options=disc.CompileOptions(pipeline="jit"))
        assert float(fn({"a": jnp.ones((3, 2))}, jnp.ones((3, 2)))) == 12.0
        assert seen[0] == ((16, 2), (16, 2))  # POW2 granule-16 bucket
        # in-bucket second call: padded shapes identical, no new compile
        assert float(fn({"a": jnp.ones((5, 2))}, jnp.ones((5, 2)))) == 20.0
        assert fn.compile_counts()["total"] == 1

    def test_tree_only_dim_is_rejected(self):
        with pytest.raises(ValueError, match="not observable"):
            disc.compile(lambda t: t, specs=[disc.TreeSpec({0: "B"})],
                         options=disc.CompileOptions(pipeline="jit"))
