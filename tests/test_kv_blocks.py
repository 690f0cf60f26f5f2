"""Paged KV-block allocator + paged serving engine tests.

Three layers, mirroring the subsystem's split:

- `BlockAllocator` (pure host bookkeeping): alloc/free/refcount/COW
  invariants, fragmentation behavior, watermark hysteresis — property
  style where a random workload must conserve blocks.
- Model-level exactness: the paged prefill/decode twins produce
  bit-identical outputs to the contiguous functions (the tier-1 gate's
  foundation), including the graft-spill case the contiguous path needed
  a dispatch-time fixup for (trash routing retires it).
- Engine-level: paged vs contiguous bit-identity, prefix-entry block
  sharing (incref, not copy), watermark shedding, and block-exhaustion
  preempt-and-requeue under the `serving.kv_alloc` chaos site.
"""

import random

import pytest

from kubedl_tpu.serving.kv_blocks import TRASH_BLOCK, BlockAllocator


class TestAllocator:
    def test_trash_block_reserved(self):
        a = BlockAllocator(num_blocks=8, block_size=16)
        assert a.total == 7
        assert a.refcount(TRASH_BLOCK) == 1
        got = a.alloc(7)
        assert got is not None and TRASH_BLOCK not in got
        # trash is immune to free/incref bookkeeping
        a.free([TRASH_BLOCK])
        a.incref([TRASH_BLOCK])
        assert a.refcount(TRASH_BLOCK) == 1

    def test_alloc_all_or_nothing(self):
        a = BlockAllocator(num_blocks=5, block_size=16)
        assert a.alloc(4) is not None
        assert a.free_count == 0
        # nothing left: a partial grant must not happen
        assert a.alloc(1) is None
        assert a.stats()["alloc_failures"] == 1

    def test_free_returns_blocks_lifo(self):
        a = BlockAllocator(num_blocks=6, block_size=16)
        got = a.alloc(3)
        a.free(got)
        again = a.alloc(3)
        # LIFO: the just-freed blocks come back first (dense working set)
        assert set(again) == set(got)

    def test_refcount_sharing(self):
        a = BlockAllocator(num_blocks=6, block_size=16)
        (b,) = a.alloc(1)
        a.incref([b])
        assert a.refcount(b) == 2
        assert a.is_shared(b)
        assert a.shared_count == 1
        # first free drops a ref but does not reclaim
        assert a.free([b]) == 0
        assert a.refcount(b) == 1
        assert not a.is_shared(b)
        # second free reclaims
        assert a.free([b]) == 1
        assert a.free_count == a.total

    def test_double_free_raises(self):
        a = BlockAllocator(num_blocks=4, block_size=16)
        (b,) = a.alloc(1)
        a.free([b])
        with pytest.raises(ValueError):
            a.free([b])
        with pytest.raises(ValueError):
            a.incref([b])

    def test_cow_unshared_is_identity(self):
        a = BlockAllocator(num_blocks=6, block_size=16)
        (b,) = a.alloc(1)
        assert a.cow(b) == b
        assert a.stats()["cow_copies"] == 0

    def test_cow_shared_allocates_replacement(self):
        a = BlockAllocator(num_blocks=6, block_size=16)
        (b,) = a.alloc(1)
        a.incref([b])  # a prefix entry now shares it
        new = a.cow(b)
        assert new is not None and new != b
        assert a.refcount(new) == 1
        assert a.refcount(b) == 1  # the entry keeps its reference
        assert a.stats()["cow_copies"] == 1

    def test_cow_after_other_owner_leaves_is_identity(self):
        a = BlockAllocator(num_blocks=6, block_size=16)
        (b,) = a.alloc(1)
        a.incref([b])
        a.free([b])  # the other owner left first
        # back to sole ownership: no copy needed, writes are private
        assert a.cow(b) == b
        assert a.stats()["cow_copies"] == 0

    def test_blocks_for(self):
        a = BlockAllocator(num_blocks=8, block_size=16)
        assert a.blocks_for(0) == 0
        assert a.blocks_for(1) == 1
        assert a.blocks_for(16) == 1
        assert a.blocks_for(17) == 2
        assert a.blocks_for(64) == 4

    def test_watermark_hysteresis(self):
        a = BlockAllocator(num_blocks=11, block_size=16,
                           low_watermark=0.2, high_watermark=0.5)
        assert a.admission_open()
        got = a.alloc(9)  # 1/10 free = 0.1 < low
        assert not a.admission_open()
        a.free(got[:3])  # 4/10 free = 0.4: still below high -> stays shut
        assert not a.admission_open()
        a.free(got[3:5])  # 6/10 free = 0.6 >= high -> reopens
        assert a.admission_open()

    def test_property_random_workload_conserves_blocks(self):
        """Random alloc/incref/free/cow sequence: the allocator never
        loses or duplicates a block, and free+used == total throughout
        — the fragmentation-safety property (blocks are fixed-size, so
        any free block satisfies any request)."""
        rng = random.Random(7)
        a = BlockAllocator(num_blocks=33, block_size=16)
        refs = {}  # block -> references this test holds
        for _ in range(2000):
            op = rng.random()
            blocks = list(refs)
            if op < 0.4:
                got = a.alloc(rng.randint(1, 4))
                if got is not None:
                    for b in got:
                        refs[b] = refs.get(b, 0) + 1
            elif op < 0.55 and blocks:
                b = rng.choice(blocks)
                a.incref([b])
                refs[b] += 1
            elif op < 0.9 and blocks:
                b = rng.choice(blocks)
                a.free([b])
                refs[b] -= 1
                if refs[b] == 0:
                    del refs[b]
            elif blocks:
                b = rng.choice(blocks)
                new = a.cow(b)
                if new is not None and new != b:
                    refs[b] -= 1  # cow dropped this owner's reference
                    if refs[b] == 0:
                        del refs[b]
                    refs[new] = refs.get(new, 0) + 1
            # invariant: every block is either free or referenced
            st = a.stats()
            assert st["free"] + st["used"] == st["total"]
            assert st["used"] == len(refs)
        # drain every held reference: all blocks must come home
        for b, r in list(refs.items()):
            a.free([b] * r)
        assert a.free_count == a.total
        assert a.shared_count == 0

    def test_ctor_validation(self):
        with pytest.raises(ValueError):
            BlockAllocator(num_blocks=1, block_size=16)
        with pytest.raises(ValueError):
            BlockAllocator(num_blocks=4, block_size=0)
        with pytest.raises(ValueError):
            BlockAllocator(num_blocks=4, block_size=16,
                           low_watermark=0.5, high_watermark=0.2)


class TestPagedModelExactness:
    """The device half: every paged function is bit-identical to its
    contiguous twin over the same logical positions."""

    def _setup(self, batch=2, max_seq=64, block_size=16):
        import jax
        import jax.numpy as jnp

        from kubedl_tpu.models import llama

        cfg = llama.preset("tiny")
        params = llama.llama_init(jax.random.PRNGKey(0), cfg)
        cache_c = llama.init_cache(cfg, batch, max_seq)
        nb = 1 + batch * (max_seq // block_size)
        cache_p = llama.init_paged_cache(cfg, batch, max_seq, nb, block_size)
        # identity-ish block table: row b owns blocks [1 + b*mb, ...)
        mb = max_seq // block_size
        bt = jnp.arange(1, 1 + batch * mb, dtype=jnp.int32).reshape(batch, mb)
        cache_p["bt"] = bt
        return llama, cfg, params, cache_c, cache_p

    def test_prefill_bit_identical(self):
        import jax.numpy as jnp
        import numpy as np

        llama, cfg, params, cache_c, cache_p = self._setup()
        toks = jnp.asarray(np.array([[5, 9, 13, 0], [1, 2, 0, 0]], np.int32))
        lens = jnp.asarray(np.array([3, 2], np.int32))
        lc, cache_c = llama.prefill_batched(params, cache_c, toks, lens, cfg)
        lp, cache_p = llama.paged_prefill_batched(
            params, cache_p, toks, lens, cfg
        )
        assert np.array_equal(np.asarray(lc), np.asarray(lp))

    def test_decode_chain_bit_identical(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        llama, cfg, params, cache_c, cache_p = self._setup()
        toks = jnp.asarray(np.array([[5, 9, 13, 0], [1, 2, 0, 0]], np.int32))
        lens = jnp.asarray(np.array([3, 2], np.int32))
        lc, cache_c = llama.prefill_batched(params, cache_c, toks, lens, cfg)
        lp, cache_p = llama.paged_prefill_batched(
            params, cache_p, toks, lens, cfg
        )
        nxt = jnp.argmax(lc, axis=-1).astype(jnp.int32)[:, None]
        temps = jnp.zeros((2,), jnp.float32)
        key = jax.random.PRNGKey(1)
        tc, _, _, cache_c = llama.decode_segment(
            params, cache_c, nxt, temps, key, cfg, n_steps=8, greedy=True
        )
        tp, _, _, cache_p = llama.paged_decode_segment(
            params, cache_p, nxt, temps, key, cfg, n_steps=8, greedy=True
        )
        assert np.array_equal(np.asarray(tc), np.asarray(tp))

    def test_overflow_fixup_retired_by_trash_routing(self):
        """PR 4's contiguous engine needed a dispatch-time fixup: a graft
        whose start + prefill bucket spilled past max_seq would have
        clamped writes onto live tail positions. The paged suffix
        forward routes every beyond-lens / beyond-max_seq write to the
        trash block instead — prove the spill case leaves real rows
        as a clean prefill computes them. The two programs attend over
        differently shaped key sets (a 64-key gathered view against 16
        local keys), so the logits agree to float tolerance and pick the
        same token; bit-identity is not theirs to have."""
        import jax.numpy as jnp
        import numpy as np

        llama, cfg, params, _, cache_p = self._setup(batch=2, max_seq=64)
        # row 0: start so deep that start + padded bucket > max_seq
        start = 60
        cache_p["pos"] = jnp.asarray(np.array([start, 0], np.int32))
        toks = np.zeros((2, 16), np.int32)  # bucket 16: 60 + 16 > 64
        toks[0, :3] = [5, 9, 13]
        toks[1, :2] = [1, 2]
        lens = jnp.asarray(np.array([3, 2], np.int32))
        starts = jnp.asarray(np.array([start, 0], np.int32))
        before = np.asarray(cache_p["k"][:, TRASH_BLOCK]).copy()
        logits, cache_p = llama.paged_prefill_from(
            params, cache_p, jnp.asarray(toks), lens, starts, cfg
        )
        # row 1 (start 0, no spill) matches a clean prefill of its own
        _, cfg2, params2, _, fresh = self._setup(batch=2, max_seq=64)
        l2, _ = llama.paged_prefill_batched(
            params2, fresh, jnp.asarray(toks), lens, cfg2
        )
        np.testing.assert_allclose(
            np.asarray(logits[1]), np.asarray(l2[1]), rtol=1e-5, atol=1e-5
        )
        assert int(np.argmax(logits[1])) == int(np.argmax(l2[1]))
        # and the spill landed in the trash block, not in live rows
        after = np.asarray(cache_p["k"][:, TRASH_BLOCK])
        assert not np.array_equal(before, after)


def _oracle(eng, prompt, n):
    """Single-sequence contiguous decode loop — the exactness oracle."""
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


class TestPagedEngine:
    def test_paged_matches_contiguous_bit_identical(self):
        """THE exactness gate: same prompts, greedy, paged vs contiguous
        engines produce identical token ids (multi-block rows included)."""
        from kubedl_tpu.serving.server import LlamaEngine

        prompts = [
            [5, 9, 13],
            [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18],
            [7],
        ]
        results = {}
        for layout in ("contiguous", "paged"):
            eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                              kv_layout=layout)
            try:
                results[layout] = [
                    eng.generate(p, max_tokens=8)["token_ids"]
                    for p in prompts
                ]
            finally:
                eng.close()
        assert results["paged"] == results["contiguous"]

    def test_paged_matches_oracle(self):
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged")
        try:
            prompt = [5, 9, 13]
            got = eng.generate(prompt, max_tokens=6)
            assert got["token_ids"] == _oracle(eng, prompt, 6)
        finally:
            eng.close()

    def test_blocks_freed_on_completion(self):
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", prefix_cache_mb=0)
        try:
            eng.generate([5, 9, 13], max_tokens=6)
            st = eng.stats()["kv_blocks"]
            assert st["used"] == 0
            assert st["free"] == st["total"]
            assert st["allocs"] > 0 and st["frees"] == st["allocs"]
        finally:
            eng.close()

    def test_prefix_entry_shares_row_blocks(self):
        """Paged prefix insert SHARES the row's full blocks (incref) and
        device-copies only the partial tail; a later identical prompt
        grafts from the shared blocks and still matches the oracle."""
        from kubedl_tpu.serving.server import LlamaEngine

        # prompt spans 2 full blocks (block_size 4: 8 prompt tokens
        # = 2 full + the engine's +1 suffix need)
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", kv_block_size=4,
                          prefix_min_len=4)
        try:
            want = _oracle(eng, prompt, 6)
            r1 = eng.generate(prompt, max_tokens=6, cache_prefix=True)
            assert r1["token_ids"] == want
            st = eng.stats()["kv_blocks"]
            # the entry holds block references while no row is resident
            assert st["used"] > 0
            r2 = eng.generate(prompt, max_tokens=6)
            assert r2["token_ids"] == want
            assert r2["cached_prefix_len"] > 0
            # sharing happened by reference, never by whole-prefix copy:
            # at most one COW/tail copy alloc beyond the suffix blocks
            assert eng.stats()["prefix_cache"]["hits"] >= 1
        finally:
            eng.close()

    def test_prefix_entry_blocks_freed_on_eviction(self):
        from kubedl_tpu.serving.server import LlamaEngine

        prompt = list(range(1, 11))
        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", kv_block_size=4,
                          prefix_min_len=4)
        try:
            eng.generate(prompt, max_tokens=4, cache_prefix=True)
            held = eng.stats()["kv_blocks"]["used"]
            assert held > 0
            # reclaim evicts the (unpinned) entry -> blocks come home
            freed = eng._pcache.reclaim(10**9)
            assert freed > 0
            st = eng.stats()["kv_blocks"]
            assert st["used"] == 0
        finally:
            eng.close()

    def test_low_watermark_sheds_503(self):
        """Once the free fraction crosses the low watermark, generate()
        rejects at the door with Retry-After instead of queueing."""
        from kubedl_tpu.serving.server import EngineOverloaded, LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", prefix_cache_mb=0)
        try:
            # drain the pool host-side: admission gate shuts
            grabbed = eng._alloc.alloc(eng._alloc.free_count)
            assert not eng._alloc.admission_open()
            with pytest.raises(EngineOverloaded) as ei:
                eng.generate([1, 2, 3], max_tokens=2)
            assert ei.value.retry_after_s > 0
            assert eng.stats()["kv_sheds"] == 1
            eng._alloc.free(grabbed)
            assert eng._alloc.admission_open()
            # recovered: requests flow again
            out = eng.generate([5, 9, 13], max_tokens=4)
            assert len(out["token_ids"]) == 4
        finally:
            eng.close()

    def test_chaos_kv_alloc_preempts_and_requeues(self):
        """The `serving.kv_alloc` chaos site injects one block-allocation
        failure mid-decode: the engine preempts the youngest resident
        row, requeues it, and EVERY request still completes with exactly
        the greedy oracle's tokens (preemption re-prefills from scratch,
        so outputs never change)."""
        import threading

        from kubedl_tpu import chaos
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", prefix_cache_mb=0)
        try:
            prompts = [[5, 9, 13], [1, 2], [7, 11]]
            want = [_oracle(eng, p, 6) for p in prompts]
            results = [None] * len(prompts)

            def worker(i):
                results[i] = eng.generate(prompts[i], max_tokens=6)

            with chaos.FaultPlan(seed=3, sites={
                "serving.kv_alloc": [chaos.FaultSpec.nth(1)],
            }):
                threads = [threading.Thread(target=worker, args=(i,))
                           for i in range(len(prompts))]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=120)
            assert [r["token_ids"] for r in results] == want
            # the injected failure was consumed by the reserve path
            st = eng.stats()["kv_blocks"]
            assert st["used"] == 0  # everything returned home
        finally:
            eng.close()

    def test_preempt_requeue_exhaustion(self):
        """A pool too small for two full-length rows: the second row's
        growth preempts the younger resident, which requeues and still
        finishes with oracle-exact output."""
        from kubedl_tpu.serving.server import LlamaEngine
        import threading

        # mb = 64/16 = 4; kv_blocks=6 -> 5 usable: two rows needing up
        # to 3 blocks each cannot BOTH grow to 3 (6 > 5)
        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", kv_blocks=6,
                          kv_low_watermark=0.0, kv_high_watermark=0.0,
                          prefix_cache_mb=0)
        try:
            prompts = [[5, 9, 13], [1, 2, 3]]
            want = [_oracle(eng, p, 30) for p in prompts]
            results = [None] * 2

            def worker(i):
                results[i] = eng.generate(prompts[i], max_tokens=30,
                                          timeout_s=120)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert [r["token_ids"] for r in results] == want
        finally:
            eng.close()

    def test_kv_metrics_exported(self):
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged")
        try:
            eng.generate([5, 9, 13], max_tokens=4)
            body = eng.metrics.registry.render()
            for fam in ("kubedl_tpu_serving_kv_blocks_total",
                        "kubedl_tpu_serving_kv_blocks_free",
                        "kubedl_tpu_serving_kv_blocks_shared",
                        "kubedl_tpu_serving_kv_preemptions",
                        "kubedl_tpu_serving_kv_block_sheds"):
                assert fam in body, fam
            st = eng.stats()
            assert st["kv_blocks"]["total"] > 0
            assert "kv_preemptions" in st and "kv_sheds" in st
        finally:
            eng.close()

    def test_contiguous_engine_unchanged_no_kv_stats(self):
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="contiguous")
        try:
            out = eng.generate([5, 9, 13], max_tokens=4)
            assert len(out["token_ids"]) == 4
            assert "kv_blocks" not in eng.stats()
        finally:
            eng.close()

    def test_unknown_layout_rejected(self):
        from kubedl_tpu.serving.server import LlamaEngine

        with pytest.raises(ValueError):
            LlamaEngine(preset="tiny", kv_layout="interleaved")


class TestChunkedAdmission:
    """Continuous batching: prompts prefill in block-sized chunks
    interleaved with decode, so admission latency is bounded by the
    chunk budget instead of the longest queued prompt. The exactness
    gate is unchanged — chunking may only change WHEN work happens."""

    def test_chunked_bit_identical_to_slot_granularity(self):
        from kubedl_tpu.serving.server import LlamaEngine

        prompts = [
            [5, 9, 13],
            list(range(1, 19)),
            [7],
            list(range(3, 40)),  # spans 3 chunks at budget 16
        ]
        results = {}
        for name, kw in (("plain", {}),
                         ("chunked", {"prefill_chunk_tokens": 16})):
            eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                              kv_layout="paged", **kw)
            try:
                results[name] = [
                    eng.generate(p, max_tokens=8)["token_ids"]
                    for p in prompts
                ]
            finally:
                eng.close()
        assert results["chunked"] == results["plain"]

    def test_chunk_budget_rounds_to_blocks(self):
        """The knob is block-aligned (intermediate chunks must never
        split a KV block across two dispatches) and paged-only."""
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", kv_block_size=16,
                          prefill_chunk_tokens=25)
        try:
            assert eng.prefill_chunk_tokens == 16  # floor to block size
        finally:
            eng.close()
        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", kv_block_size=16,
                          prefill_chunk_tokens=7)
        try:
            assert eng.prefill_chunk_tokens == 16  # never below a block
        finally:
            eng.close()
        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="contiguous", prefill_chunk_tokens=16)
        try:
            assert eng.prefill_chunk_tokens == 0  # paged-only
        finally:
            eng.close()

    def test_admission_metrics_and_stats(self):
        """Chunk dispatches are counted, and per-request queue wait
        surfaces as p50/p95 in stats() — the number the chunk budget
        exists to bound."""
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", prefill_chunk_tokens=16)
        try:
            # 37 prompt tokens at budget 16 -> 3 chunks
            eng.generate(list(range(3, 40)), max_tokens=4)
            eng.generate([5, 9, 13], max_tokens=4)  # 1 chunk
            body = eng.metrics.registry.render()
            line = [l for l in body.splitlines()
                    if l.startswith("kubedl_tpu_serving_admission_chunks ")]
            assert line and float(line[0].split()[-1]) == 4.0, line
            assert "kubedl_tpu_serving_queue_wait_ms" in body
            st = eng.stats()
            assert st["queue_wait_ms_p50"] >= 0.0
            assert st["queue_wait_ms_p95"] >= st["queue_wait_ms_p50"]
        finally:
            eng.close()

    def test_chunked_with_spec_and_prefix_cache(self):
        """Chunked admission composes with speculation and prefix reuse
        without perturbing outputs (repeat prompts ride the cache; their
        FIRST chunk starts at the grafted length)."""
        from kubedl_tpu.serving.server import LlamaEngine

        prompts = [list(range(1, 19)), list(range(1, 19)), [5, 9, 13]]
        results = {}
        for name, kw in (
            ("plain", {}),
            ("chunked", {"prefill_chunk_tokens": 16, "spec_k": 3}),
        ):
            eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                              kv_layout="paged", **kw)
            try:
                results[name] = [
                    eng.generate(p, max_tokens=6)["token_ids"]
                    for p in prompts
                ]
            finally:
                eng.close()
        assert results["chunked"] == results["plain"]

    def test_chaos_chunk_admit_scheduler_survives(self):
        """An injected fault at the chunk dispatch fails the in-flight
        request loudly and the engine keeps serving — same contract as
        `serving.dispatch` (docs/robustness.md)."""
        from kubedl_tpu import chaos
        from kubedl_tpu.serving.server import LlamaEngine

        eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                          kv_layout="paged", prefill_chunk_tokens=16)
        try:
            with chaos.FaultPlan(seed=7, sites={
                "serving.chunk_admit": [chaos.FaultSpec.nth(1)],
            }):
                r1 = eng.generate([5, 9, 13], max_tokens=4)
            assert "error" in r1, r1
            r2 = eng.generate([5, 9, 13], max_tokens=4)
            assert len(r2["token_ids"]) == 4
            assert eng.stats()["kv_blocks"]["used"] == 0
        finally:
            eng.close()
