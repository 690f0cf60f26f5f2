"""Prefill computes only the rows that hold a prompt (docs/serving.md
"Continuous batching").

Model level: ``paged_prefill_from`` / ``paged_prefill_batched`` on a compact
batch (``rows``) do to those cache rows what the all-rows call did, and
leave every other row's blocks and ``pos`` alone. Engine level: a paged
engine dispatches one one-row program for each row with work, serves the
single-sequence oracle's greedy tokens whatever arrives together, and
compiles nothing new when a second row shares a tick."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubedl_tpu.models import llama, paged_attention
from kubedl_tpu.observability.tracing import TRACER
from tests.test_kv_blocks import _oracle

TRASH_BLOCK = 0
B, MAX_SEQ, BS = 4, 64, 16


# ---- model level -----------------------------------------------------------


@pytest.fixture(scope="module")
def model():
    cfg = llama.preset("tiny")
    return cfg, llama.llama_init(jax.random.PRNGKey(0), cfg)


def _cache_with_history(cfg, params):
    """A paged cache whose four rows already hold a few tokens each, row b
    owning blocks ``[1 + b*mb, 1 + (b+1)*mb)``."""
    mb = MAX_SEQ // BS
    cache = llama.init_paged_cache(cfg, B, MAX_SEQ, 1 + B * mb, BS)
    cache["bt"] = jnp.arange(1, 1 + B * mb, dtype=jnp.int32).reshape(B, mb)
    hist = np.array([[3, 1, 4, 1, 5, 9, 2, 6]] * B, np.int32) + np.arange(B)[:, None]
    _, cache = llama.paged_prefill_batched(
        params, cache, jnp.asarray(hist), jnp.asarray([5, 6, 7, 8], jnp.int32), cfg)
    return cache


def _live(cache):
    """K and V of every block but the trash block (pad positions and rows
    without work write there, and which of them did is not compared)."""
    return (np.asarray(cache["k"][:, TRASH_BLOCK + 1:]),
            np.asarray(cache["v"][:, TRASH_BLOCK + 1:]))


CASES = {
    # rows, suffix lengths, starts, bucket
    "starts_gt_0": ([1, 3], [4, 3], [6, 8], 4),
    "pad_tail": ([0, 2], [3, 9], [5, 7], 16),
    "unsorted_rows": ([3, 0], [5, 2], [8, 5], 8),
    "one_row": ([2], [11], [7], 16),
    # start + bucket spills past max_seq: clamped writes go to the trash block
    "spill_past_max_seq": ([1, 2], [3, 2], [60, 7], 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_suffix_prefill_on_a_subset_of_rows(model, case):
    cfg, params = model
    rows, lens, starts, bucket = CASES[case]
    rng = np.random.default_rng(len(case))
    cache = _cache_with_history(cfg, params)
    if case == "spill_past_max_seq":
        cache["pos"] = cache["pos"].at[1].set(60)
    toks = np.zeros((len(rows), bucket), np.int32)
    for j, n in enumerate(lens):
        toks[j, :n] = rng.integers(1, cfg.vocab_size, n)
    # what the all-rows program did: the same rows, the others with length 0
    toks_all = np.zeros((B, bucket), np.int32)
    lens_all, starts_all = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
    toks_all[rows], lens_all[rows], starts_all[rows] = toks, lens, starts
    want, cache_all = llama.paged_prefill_from(
        params, dict(cache), jnp.asarray(toks_all), jnp.asarray(lens_all),
        jnp.asarray(starts_all), cfg)
    got, cache_rows = llama.paged_prefill_from(
        params, dict(cache), jnp.asarray(toks), jnp.asarray(lens, jnp.int32),
        jnp.asarray(starts, jnp.int32), cfg, rows=jnp.asarray(rows, jnp.int32))
    assert got.shape == (len(rows), cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[rows], rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.argmax(got, -1), np.argmax(np.asarray(want)[rows], -1))
    # the same pool blocks, the same positions, the table untouched
    for a, b in zip(_live(cache_rows), _live(cache_all)):
        np.testing.assert_allclose(a.astype(np.float32), b.astype(np.float32), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(cache_rows["pos"]), np.asarray(cache_all["pos"]))
    assert np.array_equal(np.asarray(cache_rows["bt"]), np.asarray(cache["bt"]))
    # rows outside the batch: blocks and pos bit for bit what they were
    others = [b for b in range(B) if b not in rows]
    mb = MAX_SEQ // BS
    for before, after in zip(_live(cache), _live(cache_rows)):
        for b in others:
            assert np.array_equal(before[:, b * mb:(b + 1) * mb], after[:, b * mb:(b + 1) * mb])
    assert np.array_equal(np.asarray(cache_rows["pos"])[others], np.asarray(cache["pos"])[others])
    want_pos = np.minimum(np.array(starts) + np.array(lens), MAX_SEQ - 1)
    assert np.array_equal(np.asarray(cache_rows["pos"])[rows], want_pos)
    if case == "spill_past_max_seq":
        assert not np.array_equal(np.asarray(cache["k"][:, TRASH_BLOCK]),
                                  np.asarray(cache_rows["k"][:, TRASH_BLOCK]))


def test_whole_prompt_prefill_on_a_subset_of_rows(model):
    cfg, params = model
    cache = _cache_with_history(cfg, params)
    rows, lens = [2, 0], [7, 3]
    toks = np.zeros((2, 8), np.int32)
    toks[0, :7], toks[1, :3] = np.arange(11, 18), [5, 9, 13]
    toks_all, lens_all = np.zeros((B, 8), np.int32), np.zeros((B,), np.int32)
    toks_all[rows], lens_all[rows] = toks, lens
    want, cache_all = llama.paged_prefill_batched(
        params, dict(cache), jnp.asarray(toks_all), jnp.asarray(lens_all), cfg)
    got, cache_rows = llama.paged_prefill_batched(
        params, dict(cache), jnp.asarray(toks), jnp.asarray(lens, jnp.int32), cfg,
        rows=jnp.asarray(rows, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[rows], rtol=1e-5, atol=1e-5)
    for a, b in zip(_live(cache_rows), _live(cache_all)):
        np.testing.assert_allclose(a.astype(np.float32), b.astype(np.float32), rtol=1e-5, atol=1e-5)
    assert np.asarray(cache_rows["pos"]).tolist() == [3, 6, 7, 8]
    assert np.array_equal(np.asarray(cache_rows["pos"]), np.asarray(cache_all["pos"]))


def test_every_row_in_order_is_the_all_rows_call(model):
    """``rows=None`` (the speculative verify entry points) and the compact
    batch of every row compute the same thing."""
    cfg, params = model
    cache = _cache_with_history(cfg, params)
    toks = jnp.asarray(np.arange(1, 1 + B * 4, dtype=np.int32).reshape(B, 4))
    lens = jnp.asarray([4, 0, 2, 3], jnp.int32)
    starts = jnp.asarray([5, 6, 7, 8], jnp.int32)
    want, c1 = llama.paged_prefill_from(params, dict(cache), toks, lens, starts, cfg)
    got, c2 = llama.paged_prefill_from(
        params, dict(cache), toks, lens, starts, cfg, rows=jnp.arange(B, dtype=jnp.int32))
    live = np.asarray(lens) > 0  # a row of length 0 has no last token to read
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(c1["pos"]), np.asarray(c2["pos"]))
    assert np.asarray(c2["pos"]).tolist() == [9, 6, 9, 11]


# ---- engine level ----------------------------------------------------------


# ---- the pool carried through the layer scan: the same bits ------------------


def _scanned_pools_forward(params, cache, tokens, lengths, starts, cfg, kv_attention):
    """The form ``models/llama.py`` had before the pools were carried: the
    write path of the suffix forward over every cache row, with this
    layer's pool ``[NB, BS, KV, hd]`` scanned in as ``xs`` and stacked out
    as ``ys``. Decode is its one-token case (``lengths`` 1, ``starts`` =
    ``pos``). Returns (final-norm hidden states, K pool, V pool)."""
    from jax import lax

    nb, S = tokens.shape
    hd, bt, bs = cfg.head_dim, cache["bt"], cache["k"].shape[2]
    max_s = bt.shape[1] * bs
    x = llama.gather_embed(params["embed"], tokens).astype(cfg.dtype)
    cos, sin = llama.rope_freqs(cfg, max_s)
    posq = jnp.minimum(starts[:, None] + jnp.arange(S)[None, :], max_s - 1)
    cos_t, sin_t = cos[posq][:, :, None, :], sin[posq][:, :, None, :]
    mask = (jnp.arange(max_s)[None, None, :] <= posq[:, :, None])[:, None, None]
    writable = (lengths > 0)[:, None] & (jnp.arange(S)[None, :] < lengths[:, None])
    blk = jnp.where(writable, bt[jnp.arange(nb)[:, None], posq // bs], 0)
    off = posq % bs

    def rot(t):
        t1, t2 = jnp.split(t.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [t1 * cos_t - t2 * sin_t, t1 * sin_t + t2 * cos_t], axis=-1).astype(t.dtype)

    def view(pool):
        return pool[bt].reshape(nb, max_s, pool.shape[2], pool.shape[3])

    def body(x, inp):
        lp, ckp, cvp = inp
        h = llama.rmsnorm(x, lp["attn_norm"], cfg.norm_eps, cfg.norm_plus_one)
        q = rot((h @ lp["wq"]).reshape(nb, S, cfg.n_heads, hd))
        k = rot((h @ lp["wk"]).reshape(nb, S, cfg.n_kv_heads, hd))
        v = (h @ lp["wv"]).reshape(nb, S, cfg.n_kv_heads, hd)
        ckp, cvp = ckp.at[blk, off].set(k), cvp.at[blk, off].set(v)
        if kv_attention == "blocked":
            attn = paged_attention.paged_attention(q, ckp, cvp, bt, starts)
        else:
            attn = llama.attention(q, view(ckp), view(cvp), causal=False, mask=mask)
        x = x + attn.reshape(nb, S, cfg.n_heads * hd) @ lp["wo"]
        h = llama.rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, cfg.norm_plus_one)
        gate = jax.nn.silu((h @ lp["w_gate"]).astype(jnp.float32)).astype(h.dtype)
        x = x + (gate * (h @ lp["w_up"])) @ lp["w_down"]
        return x, (ckp, cvp)

    x, (new_k, new_v) = lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    return (llama.rmsnorm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one),
            new_k, new_v)


@pytest.mark.parametrize("kv_attention", ["gather", "blocked"])
@pytest.mark.parametrize("program", ["decode", "prefill_from", "verify"])
def test_carried_pools_are_the_scanned_pools_to_the_bit(model, program, kv_attention):
    """Logits (ids for verify, with its hidden states) and both returned
    pools of the carried form equal the xs/ys form's, bit for bit."""
    cfg, params = model
    cache = _cache_with_history(cfg, params)
    head = llama.lm_head_of(params, cfg)
    if program == "decode":
        toks = jnp.asarray([[7], [11], [13], [17]], jnp.int32)
        lens, starts = jnp.ones((B,), jnp.int32), cache["pos"]
        got, new = llama.paged_decode_step_batched(
            params, dict(cache), toks, cfg, kv_attention=kv_attention)
        x, want_k, want_v = _scanned_pools_forward(
            params, cache, toks, lens, starts, cfg, kv_attention)
        want = (x[:, 0] @ head).astype(jnp.float32)
    else:
        toks = jnp.asarray(np.arange(1, 1 + B * 8, dtype=np.int32).reshape(B, 8))
        lens, starts = jnp.asarray([8, 0, 3, 5], jnp.int32), cache["pos"]
        x, want_k, want_v = _scanned_pools_forward(
            params, cache, toks, lens, starts, cfg, kv_attention)
        if program == "prefill_from":
            got, new = llama.paged_prefill_from(
                params, dict(cache), toks, lens, starts, cfg, kv_attention=kv_attention)
            last = jnp.maximum(lens - 1, 0)[:, None, None]
            want = (jnp.take_along_axis(x, last, axis=1)[:, 0] @ head).astype(jnp.float32)
        else:
            got, new = llama.paged_verify(
                params, dict(cache), toks, lens, starts, cfg, kv_attention=kv_attention)
            want = jnp.argmax((x @ head).astype(jnp.float32), axis=-1).astype(jnp.int32)
            hidden, _ = llama._paged_suffix_forward(
                params, dict(cache), toks, lens, starts, cfg, kv_attention=kv_attention)
            assert np.array_equal(np.asarray(hidden), np.asarray(x))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(new["k"]), np.asarray(want_k))
    assert np.array_equal(np.asarray(new["v"]), np.asarray(want_v))
    assert not np.array_equal(np.asarray(new["k"]), np.asarray(cache["k"]))


def _engine(chunk, **kw):
    from kubedl_tpu.serving.server import LlamaEngine

    kw.setdefault("kv_block_size", 4)
    eng = LlamaEngine(preset="tiny", max_batch=4, max_seq=128, prefix_min_len=4,
                      prefill_chunk_tokens=chunk, **kw)
    # freeze the scheduler thread: the test drives ticks, so what arrives
    # together is what the test queued together
    with eng._cv:
        eng._stop = True
        eng._cv.notify_all()
    eng._thread.join(timeout=10)
    eng._stop = False
    return eng


def _serve_together(eng, requests, **slot_kw):
    """Queue ``[(prompt, max_tokens)]`` at once and tick until all are done."""
    from kubedl_tpu.serving.server import _Slot

    slots = [_Slot(list(p), n, 0.0, **slot_kw) for p, n in requests]
    with eng._cv:
        eng._waiting.extend(slots)
    ticks = 0
    while not all(s.done.is_set() for s in slots):
        eng._loop_once()
        ticks += 1
        assert ticks < 400, "engine did not converge"
    assert all("error" not in s.result for s in slots), [s.result for s in slots]
    return slots


@pytest.fixture
def phases(monkeypatch):
    """Every phase the engine opens, in order: ``(name, attributes)``."""
    seen = []
    real = TRACER.phase

    def phase(name, **attrs):
        seen.append((name, attrs))
        return real(name, **attrs)

    monkeypatch.setattr(TRACER, "phase", phase)
    return seen


def _dispatches_by_tick(phases):
    ticks = []
    for name, attrs in phases:
        if name == "engine.tick":
            ticks.append([])
        elif name == "engine.prefill_dispatch":
            ticks[-1].append(attrs)
    return ticks


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


@pytest.mark.parametrize("chunk", [0, 16], ids=["whole_prompt", "chunked"])
class TestEngineComputesOnlyRowsWithWork:
    def test_arrivals_of_one_two_and_four_serve_the_oracle(self, chunk, phases):
        eng = _engine(chunk)
        try:
            shared = _prompt(1, 12)
            waves = [
                [(shared + [7, 8], 6)],                                 # alone; its prefix is stored
                [(shared + [7, 8, 9, 9, 9], 5), (_prompt(2, 23), 7)],  # two; the first grafts it
                [(_prompt(3, 5), 9), (_prompt(4, 41), 4), (_prompt(5, 18), 6), (_prompt(6, 30), 3)],
            ]
            cached = []
            for n, wave in enumerate(waves):
                slots = _serve_together(eng, wave, cache_prefix=(n == 0))
                for s, (prompt, k) in zip(slots, wave):
                    assert s.result["token_ids"] == _oracle(eng, prompt, k), (n, prompt)
                    cached.append(int(s.result.get("cached_prefix_len", 0)))
            assert cached[1] > 0, "the second wave's first prompt grafts the stored prefix"
            spans = [a for t in _dispatches_by_tick(phases) for a in t]
            # a paged program computes the rows it feeds: one each
            assert spans and all(a["rows"] == a["slots"] == 1 for a in spans)
            assert all(0 < a["tokens"] <= a["bucket"] for a in spans)
            prefilled = sum(len(p) for w in waves for p, _k in w) - sum(cached)
            assert sum(a["tokens"] for a in spans) == prefilled
            # the lone prompt of the first wave: one row computed a dispatch
            first = _dispatches_by_tick(phases)
            assert [len(t) for t in first if t][0] == 1
            # four at once: at some tick more than one row had work
            if not chunk:
                assert max(len(t) for t in first) == 4
            st = eng.stats()
            assert st["prefill_tokens"] == prefilled == eng.metrics.prefill_tokens.value()
            positions = sum(a["slots"] * a["bucket"] for a in spans)
            assert st["prefill_positions"] == positions == eng.metrics.prefill_positions.value()
        finally:
            eng.close()

    def test_last_chunk_shares_a_tick_with_the_next_prompts_first(self, chunk, phases):
        """20 tokens then 30 under a budget of 16: the first prompt's last
        4 tokens and the next prompt's first 12 go out in one tick, as two
        one-row programs, each in its own bucket."""
        eng = _engine(chunk)
        try:
            reqs = [(_prompt(7, 20), 5), (_prompt(8, 30), 6)]
            slots = _serve_together(eng, reqs)
            for s, (prompt, k) in zip(slots, reqs):
                assert s.result["token_ids"] == _oracle(eng, prompt, k)
            ticks = [t for t in _dispatches_by_tick(phases) if t]
            assert all(a["rows"] == a["slots"] == 1 for t in ticks for a in t)
            if chunk:
                assert [[a["tokens"] for a in t] for t in ticks] == [[16], [4, 12], [16], [2]]
                assert [[a["bucket"] for a in t] for t in ticks] == [[16], [16, 16], [16], [16]]
            else:
                assert [[a["tokens"] for a in t] for t in ticks] == [[20, 30]]
                assert [[a["bucket"] for a in t] for t in ticks] == [[32, 32]]
        finally:
            eng.close()

    def test_a_second_row_in_a_tick_compiles_nothing_new(self, chunk):
        """The program set is one per bucket, whatever arrives together."""
        eng = _engine(chunk and 32, prefix_cache_mb=0)
        try:
            for n in (16, 32):  # one request a bucket, one at a time
                _serve_together(eng, [(_prompt(n, n), 3)])
            r = eng._runner
            programs = [r._prefill, r._prefill_from, r.sample_first, r.merge_chain]
            before = [f._cache_size() for f in programs]
            assert sum(before[:2]) == 2, before
            slots = _serve_together(eng, [(_prompt(9, 12), 3), (_prompt(10, 30), 3)])
            assert all(len(s.result["token_ids"]) == 3 for s in slots)
            assert [f._cache_size() for f in programs] == before
        finally:
            eng.close()


def test_a_row_preempted_between_its_chunks_prefills_from_its_first_token():
    """The second decode reserve fails (chaos): the victim is the row that
    is one chunk into its prompt. Its blocks are freed, so on re-admission
    its prefill starts over, and both requests serve the oracle's tokens."""
    from kubedl_tpu import chaos
    from kubedl_tpu.serving.server import _Slot

    eng = _engine(16, prefix_cache_mb=0, kv_block_size=16)
    try:
        reqs = [([5, 9, 13], 12), (_prompt(13, 40), 5)]
        slots = [_Slot(p, n, 0.0) for p, n in reqs]
        with eng._cv:
            eng._waiting.extend(slots)
        with chaos.FaultPlan(seed=3, sites={"serving.kv_alloc": [chaos.FaultSpec.nth(2)]}):
            ticks = 0
            while not all(s.done.is_set() for s in slots):
                eng._loop_once()
                ticks += 1
                assert ticks < 300
        assert eng.stats()["kv_preemptions"] == 1
        for s, (prompt, k) in zip(slots, reqs):
            assert s.result["token_ids"] == _oracle(eng, prompt, k)
    finally:
        eng.close()


def test_contiguous_engine_still_computes_every_row(phases):
    """A contiguous cache is addressed by batch row: its prefill program is
    left alone, and its span says so (``slots`` = ``max_batch``)."""
    eng = _engine(0, kv_layout="contiguous")
    try:
        reqs = [(_prompt(11, 9), 4), (_prompt(12, 21), 4)]
        slots = _serve_together(eng, reqs)
        for s, (prompt, k) in zip(slots, reqs):
            assert s.result["token_ids"] == _oracle(eng, prompt, k)
        spans = [a for t in _dispatches_by_tick(phases) for a in t]
        assert [(a["rows"], a["slots"], a["bucket"]) for a in spans] == [(2, 4, 32)]
        st = eng.stats()
        assert (st["prefill_tokens"], st["prefill_positions"]) == (30, 128)
    finally:
        eng.close()
