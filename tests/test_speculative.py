"""Speculative decoding tests: draft models, the accept rule, stats,
and the engine-level draft-k/verify-1 exactness gate (speculative output
must be BIT-IDENTICAL to plain greedy decode — speculation may only
change how many sequential forwards it takes)."""

import pytest

from kubedl_tpu.serving.speculative import (
    NgramDraft,
    RepeatDraft,
    ScriptedDraft,
    SpecStats,
    accept_length,
    make_draft,
)


class TestAcceptRule:
    def test_full_agreement(self):
        assert accept_length([1, 2, 3], [1, 2, 3]) == 3

    def test_no_agreement(self):
        assert accept_length([1, 2, 3], [9, 2, 3]) == 0

    def test_longest_prefix_only(self):
        # agreement after a mismatch never counts: position 2 diverges
        assert accept_length([1, 2, 3, 4], [1, 2, 9, 4]) == 2

    def test_empty(self):
        assert accept_length([], []) == 0


class TestDrafts:
    def test_repeat_draft(self):
        d = RepeatDraft()
        assert d.propose([5, 9, 13], 3) == [13, 13, 13]
        assert d.propose([], 2) == []  # empty context: nothing to repeat

    def test_ngram_draft_prompt_lookup(self):
        # context ends with [7, 8]; the same bigram appeared earlier
        # followed by [9, 10] -> those are the proposal
        ctx = [1, 7, 8, 9, 10, 2, 7, 8]
        d = NgramDraft(max_ngram=2)
        assert d.propose(ctx, 2) == [9, 10]

    def test_ngram_draft_falls_back_to_repeat(self):
        d = NgramDraft()
        out = d.propose([1, 2, 3], 3)
        assert out == [3, 3, 3]  # no earlier match: repeat tail

    def test_ngram_prefers_longest_match(self):
        # trigram [5,6,7] matched (followed by 1); bigram [6,7] also
        # appears (followed by 2) — the longer n-gram wins
        ctx = [5, 6, 7, 1, 0, 6, 7, 2, 0, 5, 6, 7]
        d = NgramDraft(max_ngram=3)
        assert d.propose(ctx, 1) == [1]

    def test_scripted_draft(self):
        d = ScriptedDraft([[1, 2], [3, 4]])
        assert d.propose([0], 2) == [1, 2]
        assert d.propose([0], 2) == [3, 4]
        # script exhausted: repeat fallback
        assert d.propose([9], 2) == [9, 9]

    def test_make_draft(self):
        assert isinstance(make_draft("ngram"), NgramDraft)
        assert isinstance(make_draft("repeat"), RepeatDraft)
        with pytest.raises(ValueError):
            make_draft("oracle")


class TestSpecStats:
    def test_accounting(self):
        st = SpecStats()
        st.record(proposed=4, accepted=2, emitted=3)
        st.record(proposed=4, accepted=4, emitted=5)
        snap = st.snapshot()
        assert snap["proposed"] == 8
        assert snap["accepted"] == 6
        assert snap["verifies"] == 2
        assert snap["emitted"] == 8
        assert snap["acceptance_rate"] == 0.75
        assert snap["tokens_per_verify"] == 4.0
        assert snap["accept_len_mean"] == 3.0

    def test_empty_snapshot(self):
        snap = SpecStats().snapshot()
        assert snap["verifies"] == 0
        assert snap["acceptance_rate"] == 0.0


def _oracle(eng, prompt, n):
    import jax
    import jax.numpy as jnp

    from kubedl_tpu.models import llama

    cfg = eng.cfg
    decode = jax.jit(lambda p, c, t: llama.decode_step(p, c, t, cfg))
    cache = llama.init_cache(cfg, 1, eng.max_seq)
    logits = None
    for tok in prompt:
        logits, cache = decode(eng.params, cache,
                               jnp.full((1, 1), int(tok), jnp.int32))
    out = []
    for _ in range(n):
        nxt = int(logits[0].argmax())
        out.append(nxt)
        logits, cache = decode(eng.params, cache,
                               jnp.full((1, 1), nxt, jnp.int32))
    return out


class TestSpeculativeEngine:
    def test_spec_bit_identical_to_plain_greedy(self):
        """THE speculative exactness gate: spec_k > 0 changes latency,
        never tokens — outputs match the plain contiguous engine and the
        single-sequence oracle bit-for-bit."""
        from kubedl_tpu.serving.server import LlamaEngine

        prompts = [[5, 9, 13], [1, 2, 3, 4, 5, 6, 7, 8, 9], [7]]
        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", spec_k=4)
        try:
            for p in prompts:
                got = eng.generate(p, max_tokens=10)
                assert got["token_ids"] == _oracle(eng, p, 10), p
            snap = eng.stats()["speculative"]
            assert snap["verifies"] > 0
            # the first token of each request comes from prefill; the
            # remaining 9 per request are spec-emitted
            assert snap["emitted"] == 27
        finally:
            eng.close()

    def test_spec_requires_paged(self):
        from kubedl_tpu.serving.server import LlamaEngine

        with pytest.raises(ValueError):
            LlamaEngine(preset="tiny", kv_layout="contiguous", spec_k=4)

    def test_acceptance_length_distribution_scripted(self):
        """Seeded acceptance distribution: feed the verifier drafts that
        ARE the target's own greedy continuations (computed by the
        oracle) — every draft must be accepted, so each verify emits
        k+1 tokens and the accept-length stats pin to k."""
        from kubedl_tpu.serving.server import LlamaEngine

        k = 3
        eng = LlamaEngine(preset="tiny", max_batch=1, max_seq=64,
                          kv_layout="paged", spec_k=k,
                          prefix_cache_mb=0)
        try:
            prompt = [5, 9, 13]
            want = _oracle(eng, prompt, 12)
            # each fully-accepted verify emits k+1 tokens (k accepted +
            # 1 bonus), so verify j starts from want[j*(k+1)] and must
            # be fed the k true continuations after it; the first
            # proposal starts after the prefill token (want[0])
            script = [want[1 + j * (k + 1): 1 + j * (k + 1) + k]
                      for j in range((len(want) - 2) // (k + 1) + 1)]
            eng._draft = ScriptedDraft(script)
            got = eng.generate(prompt, max_tokens=12)
            assert got["token_ids"] == want
            snap = eng.stats()["speculative"]
            # perfect drafts: every verify accepted all k proposals
            assert snap["acceptance_rate"] == 1.0
            assert snap["accept_len_p50"] == k
            assert snap["accept_len_mean"] == k
        finally:
            eng.close()

    def test_wrong_drafts_all_rejected_still_exact(self):
        """Adversarial draft (always proposes an unlikely token): zero
        acceptance, pure verify-1 decode — output still exact, and the
        rejected-suffix blocks are freed (pool drains to empty)."""
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=1, max_seq=64,
                          kv_layout="paged", spec_k=4, prefix_cache_mb=0)
        try:
            prompt = [5, 9, 13]
            want = _oracle(eng, prompt, 8)
            eng._draft = ScriptedDraft([])  # exhausted: repeats tail
            # repeats of the previous token are near-never the greedy
            # pick for this model after the first few steps; accept rate
            # just has to be < 1 for the rollback path to be exercised
            got = eng.generate(prompt, max_tokens=8)
            assert got["token_ids"] == want
            snap = eng.stats()["speculative"]
            assert snap["acceptance_rate"] < 1.0
            st = eng.stats()["kv_blocks"]
            assert st["used"] == 0  # rejected-suffix blocks came home
        finally:
            eng.close()

    def test_non_greedy_falls_back_to_segment_path(self):
        """temperature > 0 rows cannot be verified greedily: the tick
        falls through to the plain segment path (no verify recorded)."""
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=1, max_seq=64,
                          kv_layout="paged", spec_k=4)
        try:
            out = eng.generate([5, 9, 13], max_tokens=6, temperature=0.9)
            assert len(out["token_ids"]) == 6
            assert eng.stats()["speculative"]["verifies"] == 0
        finally:
            eng.close()

    def test_spec_metrics_exported(self):
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=1, max_seq=64,
                          kv_layout="paged", spec_k=4)
        try:
            eng.generate([5, 9, 13], max_tokens=6)
            body = eng.metrics.registry.render()
            for fam in ("kubedl_tpu_serving_spec_tokens_proposed",
                        "kubedl_tpu_serving_spec_tokens_accepted",
                        "kubedl_tpu_serving_spec_acceptance_rate"):
                assert fam in body, fam
        finally:
            eng.close()

    def test_mixed_batch_greedy_exactness(self):
        """Two concurrent greedy requests share verify ticks; both still
        match their oracles exactly."""
        import threading

        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", spec_k=4)
        try:
            prompts = [[5, 9, 13], [1, 2, 3]]
            want = [_oracle(eng, p, 8) for p in prompts]
            results = [None] * 2

            def worker(i):
                results[i] = eng.generate(prompts[i], max_tokens=8)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert [r["token_ids"] for r in results] == want
        finally:
            eng.close()


class TestDraftTree:
    """Host-side trie unit tests: insert/dedup/cap, the fixed-shape
    array layout, and the greedy walk."""

    def _tree(self):
        from kubedl_tpu.serving.speculative import build_tree

        # chains sharing the 7 -> 3 prefix + one divergent chain
        return build_tree(42, [[7, 3, 8], [7, 3, 2], [9, 1]], k=3, m_max=16)

    def test_insert_dedups_shared_prefixes(self):
        tr = self._tree()
        # root + {7, 3, 8, 2, 9, 1}: the 7->3 prefix is stored once
        assert tr.size == 7
        assert tr.tokens[0] == 42 and tr.depth[0] == 0
        n7 = tr.children[0][7]
        n3 = tr.children[n7][3]
        assert sorted(tr.children[n3]) == [2, 8]
        assert tr.depth[n3] == 2

    def test_cap_drops_excess_suffix_only(self):
        from kubedl_tpu.serving.speculative import build_tree

        tr = build_tree(42, [[7, 3, 8], [9, 1, 2]], k=3, m_max=5)
        # candidate 0 fits whole (4 nodes); candidate 1 gets one node
        assert tr.size == 5
        assert 9 in tr.children[0]
        n9 = tr.children[0][9]
        assert tr.children[n9] == {}  # 1, 2 dropped by the cap

    def test_k_truncates_chains(self):
        from kubedl_tpu.serving.speculative import build_tree

        tr = build_tree(42, [[7, 3, 8, 5, 6]], k=2, m_max=16)
        assert tr.size == 3  # root + 7 + 3

    def test_arrays_layout_and_pad_nodes(self):
        import numpy as np

        tr = self._tree()
        toks, dep, mask = tr.arrays(10)
        assert toks.shape == (10,) and mask.shape == (10, 10)
        assert list(toks[:2]) == [42, 7]
        # ancestor mask: leaf 8 sees root -> 7 -> 3 -> itself, nothing else
        n8 = tr.children[tr.children[tr.children[0][7]][3]][8]
        assert mask[n8].sum() == 4
        assert mask[n8, 0] and mask[n8, n8]
        # pad nodes: depth-1 root children repeating the root token,
        # masked to themselves + root only
        for m in range(tr.size, 10):
            assert toks[m] == 42 and dep[m] == 1
            assert mask[m].sum() == 2 and mask[m, 0] and mask[m, m]
        # no live node attends a pad node
        assert not mask[:tr.size, tr.size:].any()
        with pytest.raises(ValueError):
            tr.arrays(tr.size - 1)

    def test_walk_follows_greedy_chain(self):
        tr = self._tree()
        ids = [0] * tr.size
        n7 = tr.children[0][7]
        n3 = tr.children[n7][3]
        ids[0] = 7       # root's continuation matches child 7
        ids[n7] = 3      # then 3
        ids[n3] = 2      # then the 2 branch (not 8)
        assert tr.walk(ids) == [7, 3, 2]
        ids[n3] = 5      # no child matches: path stops at depth 2
        assert tr.walk(ids) == [7, 3]
        ids[0] = 1       # no root child matches at all
        assert tr.walk(ids) == []


class TestTreeSpeculativeEngine:
    def test_tree_spec_bit_identical_to_plain_greedy(self):
        """THE tree exactness gate: spec_tree=True changes how drafts
        are scored, never the emitted tokens — outputs match the oracle
        and the flat multi-candidate engine bit-for-bit."""
        from kubedl_tpu.serving.server import LlamaEngine

        prompts = [[5, 9, 13], [1, 2, 3, 4, 5, 6, 7, 8, 9], [7]]
        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", spec_k=4, spec_candidates=3,
                          spec_tree=True)
        try:
            assert eng._runner._verify_tree is not None
            for p in prompts:
                got = eng.generate(p, max_tokens=10)
                assert got["token_ids"] == _oracle(eng, p, 10), p
            snap = eng.stats()["speculative"]
            assert snap["verifies"] > 0
            assert snap["candidates_scored"] > 0
        finally:
            eng.close()

    def test_traced_request_through_speculation_records_its_rounds(self):
        """A traced request's verify rounds are `engine.spec_round` spans.
        From PR 24 to PR 30 the span's start was an undefined name: the
        tick raised and the request failed."""
        import json

        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", spec_k=4)
        try:
            got = eng.generate([5, 9, 13], max_tokens=8, debug_trace=True)
            assert got.get("token_ids") == _oracle(eng, [5, 9, 13], 8), got
            assert "engine.spec_round" in json.dumps(got["trace"])
            assert eng.pipeline_stats()["errors"] == 0
        finally:
            eng.close()

    def test_tree_needs_candidates(self):
        """spec_tree quietly degrades to flat verify when there is
        nothing to branch on (one candidate) or no speculation at all."""
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", spec_k=4, spec_tree=True)
        try:
            assert eng.spec_tree is False
            assert eng._runner._verify_tree is None
        finally:
            eng.close()


class TestZooDraft:
    def test_from_zoo_and_engine_exactness(self):
        """A trained-architecture draft from MODEL_ZOO drives the engine
        and stays bit-exact (acceptance may be poor at random init; the
        accept rule keeps the output the target's own)."""
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", spec_k=3,
                          spec_draft="zoo:tiny")
        try:
            assert eng._draft.name == "zoo:tiny"
            p = [5, 9, 13]
            assert eng.generate(p, max_tokens=8)["token_ids"] == \
                _oracle(eng, p, 8)
        finally:
            eng.close()

    def test_save_load_roundtrip(self, tmp_path):
        import jax
        import numpy as np

        from kubedl_tpu.models import llama
        from kubedl_tpu.serving.speculative import ModelDraft

        cfg = llama.preset("tiny")
        d = ModelDraft.from_zoo("tiny", cfg, seed=3, max_context=64)
        path = str(tmp_path / "draft.npz")
        d.save(path)
        d2 = ModelDraft.from_zoo("tiny", cfg, seed=9, ckpt_path=path,
                                 max_context=64)
        for a, b in zip(jax.tree_util.tree_leaves(d.params),
                        jax.tree_util.tree_leaves(d2.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_distill_reduces_loss(self):
        """A few hard-label distillation steps against the target's own
        rollouts must drive the draft's loss down — the training loop
        that turns a zoo architecture into a useful draft."""
        import jax

        from kubedl_tpu.models import llama
        from kubedl_tpu.serving.speculative import (
            ModelDraft,
            distill_draft,
        )

        cfg = llama.preset("tiny")
        target = llama.llama_init(jax.random.PRNGKey(0), cfg)
        d = ModelDraft.from_zoo("tiny", cfg, max_context=64)
        losses = distill_draft(d, target, cfg, [[5, 9, 13], [1, 2, 3]],
                               gen_len=4, steps=3)
        assert len(losses) == 3
        assert losses[-1] < losses[0]
