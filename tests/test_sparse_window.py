"""The sparse-expert decoder with window and full attention layers
(models/sparse_window.py), its two kinds of K/V block
(serving/kv_blocks.py ``WindowTable``) and its runner behind ``LlamaEngine``.

Everything runs at the ``tiny-sparse`` preset in float32 on the CPU with
seeded weights, and is held to ``benchmark/reference/sparse_window_ref.py``
(plain float32, attention dense under a mask, the experts a loop under a
mask) in LOGITS. Tolerances: the program and the reference compute the same
float32 sums in another order (a grouped product over sorted tokens against a
masked loop, a gathered view against a dense mask), so logits of deviation 1
agree to a few 1e-6; 1e-4 is some thirty times that and a ten-thousandth of a
logit's deviation, which a dropped expert (its gate is a tenth and more of
the layer), a key outside the window or a block read from the wrong place
passes by orders of magnitude.
"""

import logging
import math
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sparse_window_ref as ref
from test_expert_gmm import forced_kernel
from test_hybrid_ssm import _Recorded, serve_together
from kubedl_tpu.models import sparse_window as sw
from kubedl_tpu.observability.tracing import TRACER
from kubedl_tpu.serving.kv_blocks import BlockAllocator, WindowTable

CFG = sw.TINY_SPARSE
#: the reference's view of the tiny preset: the published key names
CONFIG = {
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention"] * 2,
    "mlp_layer_types": ["sparse"] * 6, "num_hidden_layers": 6, "hidden_size": 64,
    "vocab_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "sliding_window": 32, "rms_norm_eps": 1e-6, "attention_bias": False,
    "hidden_act": "silu", "norm_topk_prob": True, "tie_word_embeddings": False,
    "use_sliding_window": True,
    "rope_parameters": {
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0},
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                           "original_max_position_embeddings": 64, "beta_fast": 32.0,
                           "beta_slow": 1.0, "attention_factor": 0.1 * math.log(4.0) + 1.0},
    },
}
TOL = 1e-4
BS = 16


def ref_tree(params):
    """The program's parameter tree under the reference's leaf names."""
    def attention(a):
        return {"input_norm": a["norm"], "q_proj": a["wq"], "k_proj": a["wk"],
                "v_proj": a["wv"], "o_proj": a["wo"]}

    m = params["moe"]
    return {
        "embed": params["embed"], "lm_head": params["lm_head"],
        "final_norm": params["final_norm"],
        "sliding_attention": attention(params["window"]),
        "full_attention": attention(params["full"]),
        "moe": {"post_attention_norm": m["norm"], "router": m["router"],
                "gate_up_proj": m["w_in"], "down_proj": m["w_out"]},
    }


@pytest.fixture(scope="module")
def params():
    return sw.sparse_init(jax.random.PRNGKey(3), CFG)


def reference_logits(params, tokens):
    return np.asarray(ref.forward(ref_tree(params), jnp.asarray(tokens, jnp.int32), CONFIG))


# ---- the expert layer ---------------------------------------------------------


def _reference_moe(params, layer, h, first=0, count=None):
    """The reference's expert layer for rows ``h`` that are normed already:
    its own routing, then its loop over the experts (``[first, first +
    count)`` of them), each computing every row under its gate."""
    lw = jax.tree_util.tree_map(lambda leaf: leaf[layer], ref_tree(params)["moe"])
    top_e, gates = ref.routing(h, lw["router"], CFG.top_k, "float32")
    y = jnp.zeros_like(h)
    count = CFG.n_experts - first if count is None else count
    for e in range(first, first + count):
        g = jnp.sum(jnp.where(top_e == e, gates, 0.0), axis=-1)
        gu = h @ lw["gate_up_proj"][e]
        F = CFG.expert_ffn
        y = y + g[:, None] * ((jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ lw["down_proj"][e])
    return np.asarray(y), np.asarray(top_e)


def _toward(params, layer, experts, n, seed=0):
    """``n`` normed rows that the router of ``layer`` sends to ``experts``
    first: a random row plus those experts' router columns."""
    h = jax.random.normal(jax.random.PRNGKey(seed), (n, CFG.dim), jnp.float32)
    push = sum(params["moe"]["router"][layer][:, e] for e in experts)
    return h + 40.0 * push[None, :]


@pytest.fixture(params=["kernel", "grouped"])
def form(request, monkeypatch):
    """Both forms of the expert layer's two products over the order by
    expert: the Pallas kernel a TPU runs (through the interpreter, at the
    tiles the shapes give), or the two ``lax.ragged_dot`` of every other
    backend."""
    if request.param == "kernel":
        forced_kernel(monkeypatch)
    return request.param


@pytest.mark.parametrize("case", ["ragged", "an expert with no token", "all on one expert",
                                  "padded positions"])
def test_the_expert_layer_is_the_reference_loop(params, case, form):
    layer = 4
    h = jax.random.normal(jax.random.PRNGKey(1), (37, CFG.dim), jnp.float32)
    kept = jnp.ones((37,), bool)
    if case == "all on one expert":
        h = _toward(params, layer, [5], 37)
    elif case == "an expert with no token":
        h = _toward(params, layer, [1, 2, 6], 37)
    elif case == "padded positions":
        kept = jnp.arange(37) % 3 != 1
    want, top_e = _reference_moe(params, layer, h)
    got, load = sw.expert_layer(h, params["moe"], jnp.int32(layer), kept, CFG)
    counts = np.bincount(top_e[np.asarray(kept)].reshape(-1), minlength=CFG.n_experts)
    assert np.array_equal(np.asarray(load), counts)  # a padded position routes nowhere
    if case == "an expert with no token":
        assert (counts == 0).any()
    if case == "all on one expert":
        assert counts[5] == 37
    if case == "ragged":
        assert counts.max() > counts.min()
    assert int(load.sum()) == int(kept.sum()) * CFG.top_k  # nothing dropped
    want = np.where(np.asarray(kept)[:, None], want, 0.0)
    assert np.abs(np.asarray(got) - want).max() <= TOL * max(1.0, np.abs(want).max())


def test_an_expert_nobody_is_routed_to_costs_nothing_and_changes_nothing(params, form):
    """Rows pushed to experts 1 and 2: the others get no token, the grouped
    product meets empty groups, and the result is still the loop's."""
    h = _toward(params, 0, [1, 2], 9, seed=4)
    want, _ = _reference_moe(params, 0, h)
    got, load = sw.expert_layer(h, params["moe"], jnp.int32(0), jnp.ones((9,), bool), CFG)
    assert list(np.asarray(load)) == [0, 9, 9, 0, 0, 0, 0, 0]
    assert np.abs(np.asarray(got) - want).max() <= TOL * np.abs(want).max()


def test_the_shares_add_up(params, form):
    """Four ranges of two experts, each told which it holds, each routing over
    all eight and computing its own part: the parts sum to the uncut layer
    (the reference's), and the loads side by side are the whole load. The
    router's work is every share's alike and is counted once: each part holds
    only its own experts' terms."""
    layer = 2
    h = jax.random.normal(jax.random.PRNGKey(8), (50, CFG.dim), jnp.float32)
    kept = jnp.arange(50) < 45
    whole, load = sw.expert_layer(h, params["moe"], jnp.int32(layer), kept, CFG)
    parts = [sw.expert_layer(h, params["moe"], jnp.int32(layer), kept, CFG, first=f, count=2)
             for f in (0, 2, 4, 6)]
    assert np.array_equal(np.concatenate([np.asarray(n) for _, n in parts]), np.asarray(load))
    total = sum(np.asarray(y) for y, _ in parts)
    want, _ = _reference_moe(params, layer, h)
    want = np.where(np.asarray(kept)[:, None], want, 0.0)
    assert np.abs(total - want).max() <= TOL * np.abs(want).max()
    assert np.abs(total - np.asarray(whole)).max() <= TOL * np.abs(want).max()
    one, _ = _reference_moe(params, layer, h, first=2, count=2)
    assert np.abs(np.asarray(parts[1][0]) - np.where(np.asarray(kept)[:, None], one, 0)).max() \
        <= TOL * np.abs(want).max()


# ---- rotary tables and the window ---------------------------------------------


def test_the_yarn_table_is_the_formula():
    """Mellum2's full-attention entry: dimensions up to ``low`` = 18 keep
    their frequency, from ``high`` = 35 on they are divided by 16, between
    them the blend is linear; the window layers' table is the plain one."""
    rope = sw.MELLUM2_12B.rope_full
    inv = sw.inv_freq(rope, 128)
    plain = 500000.0 ** (-np.arange(64) / 64.0)
    turns = lambda beta: 64 * math.log(8192 / (beta * 2 * math.pi)) / math.log(500000.0)  # noqa: E731
    assert (math.floor(turns(32)), math.ceil(turns(1))) == (18, 35)
    assert abs(turns(32) - 18.08) < 0.01 and abs(turns(1) - 34.98) < 0.01
    for d, want in ((0, plain[0]), (18, plain[18]), (35, plain[35] / 16), (63, plain[63] / 16)):
        assert abs(inv[d] / want - 1) < 1e-6, d
    m = 1 - (26 - 18) / (35 - 18)
    assert abs(inv[26] / (plain[26] / 16 * (1 - m) + plain[26] * m) - 1) < 1e-6
    assert abs(rope.attention_factor - 1.2772588722239782) < 1e-12
    assert np.allclose(sw.inv_freq(sw.MELLUM2_12B.rope_window, 128), plain, rtol=1e-6)
    # and the reference's own table, from the published keys, is the same one
    published = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16, "beta_fast": 32,
                 "beta_slow": 1, "original_max_position_embeddings": 8192}
    assert np.allclose(ref.inv_freq(published, 128), inv, rtol=1e-6)
    cos, sin = sw._rope_at(rope, 128, jnp.asarray([[7]]))
    assert np.allclose(np.asarray(cos)[0, 0, 0], np.cos(7 * inv) * rope.attention_factor, atol=1e-6)


def test_a_window_layer_sees_its_window_and_nothing_before_it():
    """A window of 1,024 keys: the query at position 3,000 sees keys 1,977 to
    3,000. A change to key 1,900 changes nothing, to the bit; a change to key
    1,977 shows. Through the pool's view by position, and in the reference."""
    cfg = sw.SparseWindowConfig(
        vocab_size=64, dim=32, periods=1, period=("window", "full"), n_heads=2,
        n_kv_heads=1, head_dim=16, window=1024, n_experts=2, top_k=1, expert_ffn=16,
        max_seq=4096, dtype=jnp.float32)
    n_blocks, width = 4096 // BS, 16
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    wk = jax.random.normal(keys[0], (1, 1 + n_blocks, BS, width), jnp.float32)
    wv = jax.random.normal(keys[1], (1, 1 + n_blocks, BS, width), jnp.float32)
    q = jax.random.normal(keys[2], (1, 1, 2, 16), jnp.float32)
    wbt = jnp.arange(1, 1 + n_blocks, dtype=jnp.int32)[None, :]
    pos = jnp.asarray([[3000]])
    first = pos[:, 0] // BS - cfg.window // BS

    def out(wk, wv):
        return np.asarray(sw._attend_window(q, wk, wv, jnp.int32(0), wbt, pos, first,
                                            cfg.window // BS + 1, cfg))

    def poke(pool, key):
        return pool.at[0, 1 + key // BS, key % BS].add(5.0)

    base = out(wk, wv)
    assert np.array_equal(out(poke(wk, 1900), poke(wv, 1900)), base)
    assert np.array_equal(out(poke(wk, 1976), poke(wv, 1976)), base)
    assert np.abs(out(poke(wk, 1977), wv) - base).max() > 1e-6
    assert np.abs(out(wk, poke(wv, 3000)) - base).max() > 1e-6
    assert np.array_equal(out(poke(wk, 3001), poke(wv, 3001)), base)  # the future
    # the reference's mask, on the same keys laid out in order
    k = wk[0, 1:].reshape(4096, 1, 16)[:3001]
    v = wv[0, 1:].reshape(4096, 1, 16)[:3001]
    qs = jnp.zeros((3001, 2, 16)).at[3000].set(q[0, 0])
    want = np.asarray(ref.attention(qs, k, v, 1024, "float32"))[3000]
    assert np.abs(base[0, 0] - want).max() <= 1e-5


# ---- prefill and decode through both pools -------------------------------------


def fresh_cache(batch=3, max_seq=256):
    """Both pools with room for every row's whole table, each row's two
    tables filled in (row ``r``'s blocks follow row ``r - 1``'s)."""
    mb = max_seq // BS
    cache = sw.init_cache(CFG, batch, max_seq, 1 + batch * mb, 1 + batch * mb, BS)
    table = 1 + np.arange(batch * mb, dtype=np.int32).reshape(batch, mb)
    cache["bt"] = jnp.asarray(table)
    cache["wbt"] = jnp.asarray(table)
    return cache


SPANS = (64, 128, 256)


def compiled(fn, **static):
    """``fn`` of the model jitted for this test, ``cfg`` and ``static`` bound: a
    loop of eager calls would trace and compile the layer scan anew at every
    call, hundreds of programs a test, and the process runs out of room for
    compiled code. Made inside a test, so that it is traced under the test's
    own form of the expert layer."""
    return jax.jit(partial(fn, cfg=CFG, **static))


def prefill_in_chunks(params, cache, tokens, row, chunk, spans=SPANS):
    n, logits = len(tokens), None
    suffix = compiled(sw.prefill, spans=spans)
    for base in range(0, n, chunk):
        take = min(chunk, n - base)
        bucket = 16
        while bucket < take:
            bucket *= 2
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :take] = tokens[base:base + take]
        logits, cache = suffix(
            params, cache, jnp.asarray(toks), jnp.asarray([take]), rows=jnp.asarray([row]),
            starts=jnp.asarray([base]), live_to=jnp.int32(min(base + bucket, 256)))
    return np.asarray(logits)[0], cache


@pytest.mark.parametrize("n", [27, 150])  # below and above the window of 32
def test_prefill_in_chunks_is_prefill_in_one(params, n, form):
    tokens = np.random.default_rng(n).integers(0, CFG.vocab_size, n)
    want = reference_logits(params, tokens)[n - 1]
    toks = np.zeros((1, 256), np.int32)
    toks[0, :n] = tokens
    whole, _ = compiled(sw.prefill)(params, fresh_cache(), jnp.asarray(toks), jnp.asarray([n]),
                                    rows=jnp.asarray([1]))
    assert np.abs(np.asarray(whole)[0] - want).max() <= TOL
    for chunks in (2, 7):
        chunk = -(-n // chunks // BS) * BS
        got, _ = prefill_in_chunks(params, fresh_cache(), tokens, 1, chunk)
        assert np.abs(got - want).max() <= TOL, (chunks, chunk)


def test_prefill_then_decode_through_both_pools_is_the_full_forward(params, form):
    """A prompt of 40 tokens prefilled in chunks of 16, then 80 tokens decoded
    one by one, the window's blocks RELEASED behind it as the engine would
    (their table entries back at trash, the blocks scribbled over): every
    step's logits are the reference's full forward pass, long after the first
    blocks have gone. The row beside it keeps no token and touches nothing."""
    tokens = np.random.default_rng(11).integers(0, CFG.vocab_size, 120)
    want = reference_logits(params, tokens)
    table = WindowTable(BlockAllocator(1 + 3 * 16, BS), 3, 16, CFG.window)
    table.reserve(1, 120)
    cache = fresh_cache()
    cache["wbt"] = jnp.asarray(table.table)  # row 1's blocks, the others at trash
    _, cache = prefill_in_chunks(params, cache, tokens[:40], 1, 16)
    released = 0
    step = compiled(sw.decode_step, spans=SPANS)
    for p in range(40, 120):
        released += table.release_behind(1, p)
        gone = np.asarray(cache["wbt"])[1][table.table[1] == 0]
        cache["wk"] = cache["wk"].at[:, gone[gone > 0]].set(99.0)  # as a new owner would
        cache["wv"] = cache["wv"].at[:, gone[gone > 0]].set(-99.0)
        cache["wbt"] = jnp.asarray(table.table)
        toks = np.zeros((3, 1), np.int32)
        toks[1, 0] = tokens[p]
        logits, cache, load = step(
            params, cache, jnp.asarray(toks), jnp.asarray([False, True, False]),
            live_to=jnp.int32(p + 1))
        assert np.abs(np.asarray(logits)[1] - want[p]).max() <= TOL, p
        assert int(load.sum()) == CFG.n_layers * CFG.top_k  # one kept token a layer
    assert released == (119 - 32 + 1) // BS == 5 and table.held(1) == 8 - 5


def test_a_decode_segment_counts_what_its_kept_tokens_touched(params):
    """Two rows, budgets 3 and 1 of a 4-step segment: steps past a row's
    budget route nowhere, so the counters hold 4 kept tokens a layer, beside
    the 50 prompt tokens the prefill programs left counted in the cache."""
    tokens = np.random.default_rng(12).integers(0, CFG.vocab_size, 30)
    _, cache = prefill_in_chunks(params, fresh_cache(), tokens[:20], 0, 32)
    _, cache = prefill_in_chunks(params, cache, tokens[:30], 2, 32)
    toks, last, _key, cache, counters = compiled(
        sw.decode_segment, n_steps=4, greedy=True, spans=SPANS)(
        params, cache, jnp.asarray([[3], [0], [5]], jnp.int32), jnp.zeros((3,)),
        jax.random.PRNGKey(0), jnp.asarray([3, 0, 1], jnp.int32), live_to=jnp.int32(34))
    assert toks.shape == (3, 4) and last.shape == (3, 1)
    assert int(counters["expert_tokens"].sum()) == (4 + 20 + 30) * CFG.n_layers * CFG.top_k
    assert not np.asarray(cache["expert_tokens"]).any()  # taken over, handed out once
    assert int(counters["expert_steps"]) == 3 * CFG.n_layers
    per_step_layer = int(counters["experts_touched"]) / (3 * CFG.n_layers)
    assert CFG.top_k <= per_step_layer <= 2 * CFG.top_k
    assert list(np.asarray(cache["pos"])) == [24, 4, 34]


# ---- the window's blocks on the host --------------------------------------------


def test_a_window_table_releases_behind_the_window_and_gives_everything_back():
    alloc = BlockAllocator(1 + 2 * 5, BS)
    table = WindowTable(alloc, 2, 16, window=32)
    assert WindowTable.blocks_per_row(32, 32, BS, 16) == 5
    assert table.reserve(0, 33) and table.held(0) == 3
    assert list(table.table[0, :4]) != [0, 0, 0, 0] and table.table[0, 3] == 0
    assert table.release_behind(0, 32) == 0  # the query at 32 still sees key 1
    assert table.release_behind(0, 47) == 1  # first key seen: 16
    assert table.table[0, 0] == 0 and table.held(0) == 2
    assert table.reserve(0, 80) and table.held(0) == 4
    assert table.reserve(1, 80)  # five blocks: the pool's other half
    assert not table.reserve(0, 100) and table.held(0) == 4  # all or nothing
    table.trim(0, 50)
    assert table.held(0) == 3 and table.table[0, 4] == 0
    st = table.stats([0, 1])
    assert (st["released"], st["held"], st["spanned"]) == (1, 8, 9)
    table.free_row(0)
    table.free_row(1)
    assert alloc.free_count == alloc.total and not table.table.any()
    with pytest.raises(ValueError, match="whole blocks"):
        WindowTable(alloc, 2, 16, window=40)


# ---- the engine -----------------------------------------------------------------


def make_engine(**kw):
    from kubedl_tpu.serving.server import LlamaEngine

    settings = dict(preset="tiny-sparse", max_batch=3, max_seq=256, kv_block_size=BS,
                    prefill_chunk_tokens=32)
    settings.update(kw)
    return LlamaEngine(**settings)


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    yield eng
    eng.close()


PROMPTS = [np.random.default_rng(5).integers(0, CFG.vocab_size, n).tolist()
           for n in (5, 70, 23, 130, 41)]


def test_engine_serves_concurrent_requests_as_the_reference_would(engine):
    """Five requests on three rows, prompts of one to five chunks, three of
    them past the window: every served token is the reference's best at its
    position, by its logits (a near-tie may go either way, so the served
    token's logit is held to the best within the tolerance, not the token to
    the token). Then both pools are all free again."""
    served = serve_together(engine, PROMPTS, max_tokens=40)
    for prompt, tokens in zip(PROMPTS, served):
        assert len(tokens) == 40
        logits = reference_logits(engine.params, prompt + tokens[:-1])[len(prompt) - 1:]
        gaps = logits.max(axis=-1) - logits[np.arange(40), tokens]
        assert gaps.max() <= TOL, gaps
    st = engine.stats()["kv_blocks"]
    assert st["free"] == st["total"] and st["window"]["free"] == st["window"]["total"]
    assert st["window"]["released"] > 0 and st["window"]["held"] == 0
    assert not engine._wtable.table.any() and not engine._bt_host.any()


def test_a_reused_row_and_a_reused_block_give_what_they_give_alone(engine):
    """Rows are reused as requests finish, and a window block that one row
    released is taken by another while the first still decodes (five
    requests, three rows, a pool of 15 window blocks of which the long rows
    pass through 10 and more each): what a request is served does not depend
    on either."""
    before = engine.stats()["kv_blocks"]["window"]
    together = serve_together(engine, PROMPTS, max_tokens=40)
    after = engine.stats()["kv_blocks"]["window"]
    assert after["allocs"] - before["allocs"] > after["total"]  # blocks went round
    alone = [engine.generate(p, max_tokens=40, temperature=0.0)["token_ids"] for p in PROMPTS]
    assert together == alone


def test_a_rows_window_blocks_are_released_while_it_runs(engine):
    """A row holds at most the window, one dispatch's reach and one block: 5
    here, of the 12 its 190 positions span (14 with a 32-step segment's
    reserve past the last)."""
    seen = []
    t = threading.Thread(target=lambda: engine.generate(
        PROMPTS[3], max_tokens=60, temperature=0.0))
    t.start()
    while t.is_alive():
        w = engine.stats()["kv_blocks"]["window"]
        seen.append((w["held"], w["spanned"]))
        t.join(timeout=0.005)
    assert max(h for h, _ in seen) <= 5 < max(s for _, s in seen) <= 14
    assert any(0 < h < s for h, s in seen)


def test_a_preempted_request_regenerates_the_same_tokens(engine):
    """A row is preempted between two chunks of its prompt, past the point
    where its first window blocks were released (`_preempt_locked`, as block
    exhaustion would): both kinds of block go back, the request is requeued,
    prefilled again from position 0 and served the tokens it is served
    alone."""
    prompt = np.random.default_rng(9).integers(0, CFG.vocab_size, 200).tolist()  # 7 chunks
    alone = engine.generate(prompt, max_tokens=10, temperature=0.0)["token_ids"]
    before = engine.stats()["kv_preemptions"]
    for _attempt in range(5):
        got = []
        t = threading.Thread(target=lambda: got.append(
            engine.generate(prompt, max_tokens=10, temperature=0.0)["token_ids"]))
        t.start()
        caught = False
        while t.is_alive() and not caught:
            with engine._cv:
                for i, s in enumerate(engine._slots):
                    if s is not None and s.prefill_pos > 64 and s.fed == 0 and not s.pending:
                        assert engine._wtable.held(i) < engine._alloc.blocks_for(s.prefill_pos)
                        engine._preempt_locked(i)
                        assert engine._wtable.held(i) == 0
                        caught = True
            time.sleep(0.0005)  # the scheduler needs the lock between two looks
        t.join(timeout=300)
        assert got == [alone]
        if caught:
            break
    assert caught and engine.stats()["kv_preemptions"] > before
    st = engine.stats()["kv_blocks"]
    assert st["free"] == st["total"] and st["window"]["free"] == st["window"]["total"]


def test_without_chunks_the_window_pool_holds_whole_prompts():
    """``prefill_chunk_tokens`` 0: a prompt is prefilled whole, locally, and
    its window blocks are all written before any is released, so the pool is
    sized for whole rows."""
    eng = make_engine(prefill_chunk_tokens=0, max_batch=2)
    try:
        assert eng.window_kv_blocks == eng._runner.window_blocks == 1 + 2 * 16
        tokens = eng.generate(PROMPTS[3], max_tokens=8, temperature=0.0)["token_ids"]
        logits = reference_logits(eng.params, PROMPTS[3] + tokens[:-1])[len(PROMPTS[3]) - 1:]
        assert (logits.max(axis=-1) - logits[np.arange(8), tokens]).max() <= TOL
    finally:
        eng.close()


def test_phases_and_counters_say_what_the_experts_and_the_window_did(engine, monkeypatch):
    log, real = [], TRACER.phase
    monkeypatch.setattr(TRACER, "phase",
                        lambda name, **attrs: _Recorded(real(name, **attrs), name, attrs, log))
    before = engine.stats()
    engine.generate(PROMPTS[1], max_tokens=6, temperature=0.0)  # 70 tokens: chunks 32, 32, 6
    after = engine.stats()
    pre = [a for n, a in log if n == "engine.prefill_dispatch"]
    assert [a["tokens"] for a in pre] == [32, 32, 6] and all("span" in a for a in pre)
    dec = [a for n, a in log if n == "engine.decode_dispatch" and "k" in a]
    assert dec and all({"take", "slots", "k", "span", "keys", "wkeys", "rows", "seq"} <= set(a)
                       for a in dec)
    assert (dec[0]["keys"], dec[0]["wkeys"]) == (70, 32)
    harvests = {a["seq"]: a for n, a in log if n == "engine.harvest_host" and a.get("seq")}
    assert sorted(harvests) == [a["seq"] for a in dec]
    take = sum(a["take"] for a in dec)
    assert take == 5  # the first of the six came from the prefill
    # the counters appear with the first harvested segment
    tokens = np.asarray(after["expert_tokens"]) - np.asarray(before.get("expert_tokens", 0))
    assert tokens.shape == (CFG.n_layers, CFG.n_experts)
    # the prompt's 70 tokens in the prefill programs and the 5 decoded ones
    assert tokens.sum() == (70 + take) * CFG.n_layers * CFG.top_k
    touched = after["experts_touched"] - before.get("experts_touched", 0)
    assert touched == sum(a["experts_touched"] for a in harvests.values())
    assert touched == take * CFG.n_layers * CFG.top_k  # the decode steps' alone
    assert after["expert_steps"] - before.get("expert_steps", 0) == take * CFG.n_layers
    # this backend multiplies by the plain twin: the kernel ran no tile, and says so
    assert all(a["expert_tiles"] == 0 for a in harvests.values()) and after["expert_tiles"] == 0
    w0, w1 = before["kv_blocks"]["window"], after["kv_blocks"]["window"]
    assert w1["released"] - w0["released"] == (70 + 5 - 32) // BS


def test_an_engine_on_the_kernel_serves_the_reference_and_counts_its_tiles(monkeypatch):
    """The engine built and driven as a TPU process would trace it (the
    expert kernel through the interpreter in every program): a served
    request's tokens are the reference's best at their positions, and
    ``expert_tiles`` rides the ``engine.harvest_host`` span and ``stats()``
    beside ``experts_touched``. One row decodes, so a step's two assignments
    a layer stand in one row tile and each touched expert is one tile."""
    forced_kernel(monkeypatch)
    log, real = [], TRACER.phase
    monkeypatch.setattr(TRACER, "phase",
                        lambda name, **attrs: _Recorded(real(name, **attrs), name, attrs, log))
    eng = make_engine()
    try:
        tokens = eng.generate(PROMPTS[1], max_tokens=6, temperature=0.0)["token_ids"]
        stats = eng.stats()
    finally:
        eng.close()
    logits = reference_logits(eng.params, PROMPTS[1] + tokens[:-1])[len(PROMPTS[1]) - 1:]
    assert (logits.max(axis=-1) - logits[np.arange(6), tokens]).max() <= TOL
    harvests = [a for n, a in log if n == "engine.harvest_host" and a.get("seq")]
    assert harvests and all(a["expert_tiles"] == a["experts_touched"] for a in harvests)
    assert stats["expert_tiles"] == stats["experts_touched"] == 5 * CFG.n_layers * CFG.top_k


@pytest.mark.parametrize("kw, reason", [
    ({"spec_k": 2}, "window blocks the row has released"),
    ({"role": "prefill"}, "released behind the window"),
    ({"role": "decode"}, "released behind the window"),
    ({"kv_layout": "contiguous"}, "kv_layout='contiguous'"),
    ({"mesh_axes": {"tensor": 2}}, "mesh_axes"),
    # the blocked arm is this runner's since PR 45 (tests/test_parallel_sparse.py), paged only
    ({"kv_layout": "contiguous", "kv_attention": "blocked"}, "kv_layout='contiguous'"),
    ({"quantize": "int8"}, "quantize"),
])
def test_what_two_kinds_of_block_cannot_carry_is_refused_at_construction(kw, reason):
    with pytest.raises(ValueError, match=reason) as err:
        make_engine(**kw)
    assert "two kinds of K/V block" in str(err.value)


def test_a_window_that_is_not_whole_blocks_is_refused():
    with pytest.raises(ValueError, match="not whole blocks"):
        make_engine(kv_block_size=24)


def test_no_prefix_cache_is_built_and_the_log_says_so(caplog):
    with caplog.at_level(logging.INFO, logger="kubedl_tpu.serving"):
        eng = make_engine(prefix_cache_mb=64.0)
    try:
        assert eng._pcache is None and "prefix_cache" not in eng.stats()
        said = [r.getMessage() for r in caplog.records if "no prefix cache" in r.getMessage()]
        assert len(said) == 1 and "window" in said[0]
        with pytest.raises(ValueError, match="what the window layers released"):
            eng.prefill_handoff([1, 2, 3], max_tokens=4)
    finally:
        eng.close()


def test_the_third_runner_stands_behind_make_runner():
    from kubedl_tpu.serving.model_runner import (
        HybridRunner, ModelRunner, SparseWindowRunner, make_runner)

    made = make_runner("tiny-sparse", max_batch=2, max_seq=100)
    assert type(made) is SparseWindowRunner and made.window == 32 and made.max_seq == 112
    assert made.state_bytes_per_row == 0 and made.spans == (112,)
    assert made.block_bytes == 2 * 2 * BS * 32 * 4 and made.window_block_bytes == 2 * made.block_bytes
    assert type(make_runner("tiny", max_batch=2, max_seq=64)) is ModelRunner
    assert type(make_runner("tiny-hybrid", max_batch=2, max_seq=64)) is HybridRunner
    assert ModelRunner.window == HybridRunner.window == 0
    assert sw.pattern_of(CONFIG["layer_types"]) == (2, ("window", "window", "full"))
    assert sw.pattern_of(["sliding_attention"] * 3 + ["full_attention"]) == (
        1, ("window", "window", "window", "full"))
    with pytest.raises(ValueError, match="window and full attention only"):
        sw.pattern_of(["mamba"])
    assert sw.MELLUM2_12B.num_params() == 28 * 417_747_456 + 2 * 226_492_416 + 2304
