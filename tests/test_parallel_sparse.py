"""The parallel attention-and-experts block as settings of the sparse-window
model (models/sparse_window.py), one chip's share of its routed experts, and
the blocked arm of its attention behind ``LlamaEngine``.

Everything runs at the ``tiny-parallel`` preset in float32 on the CPU with
seeded weights, and is held to ``benchmark/reference/parallel_sparse_ref.py``
(plain float32: attention dense under a mask, the experts a loop under a
mask, the shared experts one at a time) in LOGITS. Tolerances: the program and
the reference compute the same float32 sums in another order (an online
softmax over tiles against a dense softmax, one product over four shared
experts against four), so logits of deviation 1 agree to a few 1e-6; 1e-4 is
some thirty times that, and a dropped expert, a key outside the window, a
block read from the wrong place or the mean taken as a sum passes it by orders
of magnitude.
"""

import dataclasses
import hashlib
import json
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import parallel_sparse_ref as ref
from test_expert_gmm import forced_kernel
from test_hybrid_ssm import _Recorded, serve_together
from kubedl_tpu.models import paged_attention
from kubedl_tpu.models import sparse_window as sw
from kubedl_tpu.observability.tracing import TRACER
from kubedl_tpu.serving.kv_blocks import BlockAllocator, WindowTable

ROOT = Path(__file__).resolve().parents[1]
CFG = sw.TINY_PARALLEL
#: the reference's view of the tiny preset: the published key names
CONFIG = {
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention"] * 2,
    "num_hidden_layers": 6, "hidden_size": 64, "vocab_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 2, "expert_first": 2,
    "published": {"num_experts": 8}, "num_experts_per_tok": 2, "intermediate_size": 32,
    "num_shared_experts": 2, "sliding_window": 32, "layer_norm_eps": 1e-5,
    "attention_bias": False, "hidden_act": "silu", "norm_topk_prob": True,
    "tie_word_embeddings": True, "use_parallel_block": True, "use_qk_norm": False,
    "expert_selection_fn": "sigmoid", "use_gated_activation": True,
    "position_embedding_type": "rope_gptj", "rotary_pct": 1, "rope_theta": 10000.0,
    "shared_expert_combination_strategy": "average", "first_k_dense_replace": 0,
    "logit_scale": 0.5,
}
TOL = 1e-4
BS = 16
SPANS = (64, 128, 256)


def ref_tree(params):
    """The program's parameter tree under the reference's leaf names."""
    def attention(a):
        return {"input_norm": a["norm"], "q_proj": a["wq"], "k_proj": a["wk"],
                "v_proj": a["wv"], "o_proj": a["wo"]}

    m = params["moe"]
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "sliding_attention": attention(params["window"]),
            "full_attention": attention(params["full"]),
            "moe": {"router": m["router"], "gate_up_proj": m["w_in"], "down_proj": m["w_out"],
                    "shared_gate_up_proj": m["shared_in"], "shared_down_proj": m["shared_out"]}}


def seeded(cfg, seed=3):
    """``sparse_init`` with norm weights that are not all ones."""
    params = sw.sparse_init(jax.random.PRNGKey(seed), cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), 3)
    for kind, k in zip(("window", "full"), keys):
        if cfg.period.count(kind):
            params[kind]["norm"] = 1.0 + 0.2 * jax.random.normal(k, params[kind]["norm"].shape)
    params["final_norm"] = jnp.where(
        jax.random.bernoulli(keys[2], 0.5, params["final_norm"].shape), 1.0, -1.0)
    return params


@pytest.fixture(scope="module")
def params():
    return seeded(CFG)


def padded(tokens):
    out = np.zeros(-(-len(tokens) // ref.QUERY_BLOCK) * ref.QUERY_BLOCK, np.int32)
    out[:len(tokens)] = tokens
    return jnp.asarray(out)


def reference_logits(params, tokens, config=CONFIG):
    return np.asarray(ref.forward(ref_tree(params), padded(tokens), config))[:len(tokens)]


def fresh_cache(blocked, cfg=CFG, batch=3, max_seq=256):
    mb = max_seq // BS
    cache = sw.init_cache(cfg, batch, max_seq, 1 + batch * mb, 1 + batch * mb, BS,
                          blocked=blocked)
    table = 1 + np.arange(batch * mb, dtype=np.int32).reshape(batch, mb)
    cache["bt"] = jnp.asarray(table)
    cache["wbt"] = jnp.asarray(table)
    return cache


@pytest.fixture(params=["gather", "blocked"])
def arm(request, monkeypatch):
    """Both arms of the attention: gathered views with dense float32 scores,
    or the pools read in tiles (of 64 keys here, so that a 120-token row
    folds two) and, in a decode step, through ``paged_attention``."""
    monkeypatch.setattr(sw, "PREFILL_TILE", 64)
    return request.param == "blocked"


def programs(blocked, cfg=CFG):
    spans = {} if blocked else {"spans": SPANS}
    return (jax.jit(partial(sw.prefill, cfg=cfg, **spans)),
            jax.jit(partial(sw.decode_step, cfg=cfg, **spans)))


def prefill_in_chunks(suffix, blocked, params, cache, tokens, row, chunk):
    logits = None
    for base in range(0, len(tokens), chunk):
        take = min(chunk, len(tokens) - base)
        bucket = 16
        while bucket < take:
            bucket *= 2
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :take] = tokens[base:base + take]
        live = {} if blocked else {"live_to": jnp.int32(min(base + bucket, 256))}
        logits, cache = suffix(params, cache, jnp.asarray(toks), jnp.asarray([take]),
                               rows=jnp.asarray([row]), starts=jnp.asarray([base]), **live)
    return np.asarray(logits)[0], cache


# ---- the block's settings, each against its formula ------------------------------


def test_sigmoid_top_k_with_renormalisation_is_the_loop(params):
    """Each expert's own sigmoid, the two largest of eight, their scores over
    their sum: against a loop over tokens in numpy."""
    h = jax.random.normal(jax.random.PRNGKey(1), (23, CFG.dim), jnp.float32)
    router = params["moe"]["router"][4]
    top_e, gates = sw.route(h, router, CFG)
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(h, np.float64) @ np.asarray(router, np.float64))))
    for t in range(23):
        best = np.argsort(-scores[t])[:CFG.top_k]
        assert sorted(best) == sorted(np.asarray(top_e[t]))
        want = {int(e): scores[t, e] / scores[t, best].sum() for e in best}
        for e, g in zip(np.asarray(top_e[t]), np.asarray(gates[t])):
            assert abs(g - want[int(e)]) <= 1e-6
    assert np.allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)
    # a softmax router scores the same logits otherwise: Mellum2's setting is untouched
    soft_e, soft_g = sw.route(h, router, sw.TINY_SPARSE)
    p = jax.nn.softmax(h @ router, axis=-1)
    assert np.array_equal(np.asarray(soft_e), np.asarray(jax.lax.top_k(p, 2)[1]))
    assert not np.allclose(np.asarray(soft_g), np.asarray(gates))


def test_interleaved_rope_is_the_pairwise_formula():
    """Pair ``i`` is dimensions ``2i`` and ``2i + 1`` and turns by ``pos *
    theta^(-2i / hd)``; the rotate-half form pairs ``d`` with ``d + hd / 2``;
    a kind without positions rotates nothing at all."""
    rope = sw.Rope(theta=50000.0, form="interleaved")
    t = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (2, 5, 3, 16), jnp.float32))
    pos = np.asarray([[0, 1, 7, 300, 77], [9, 10, 11, 12, 13]])  # float32 angles: small ones
    cos, sin = sw._rope_at(rope, 16, jnp.asarray(pos))
    got = np.asarray(sw._rotate(jnp.asarray(t), cos, sin, "interleaved"))
    for b in range(2):
        for s in range(5):
            for i in range(8):
                ang = pos[b, s] * 50000.0 ** (-2 * i / 16)
                x, y = t[b, s, :, 2 * i], t[b, s, :, 2 * i + 1]
                assert np.allclose(got[b, s, :, 2 * i], x * np.cos(ang) - y * np.sin(ang), atol=1e-4)
                assert np.allclose(got[b, s, :, 2 * i + 1], x * np.sin(ang) + y * np.cos(ang), atol=1e-4)
    half = np.asarray(sw._rotate(jnp.asarray(t), cos, sin))
    assert not np.allclose(half, got, atol=1e-3)  # another pairing, another rotation
    assert np.allclose(np.asarray(ref.rotate_pairs(jnp.asarray(t[0]), *ref.rope_table(50000.0, 16, pos[0]))),
                       got[0], atol=1e-4)
    # no positions: q and k of a "none" kind are the bare projections
    cfg = dataclasses.replace(CFG, rope_full=sw.Rope(form="none"))
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 4, CFG.dim), jnp.float32)
    lp = {n: jax.random.normal(jax.random.PRNGKey(4), s) for n, s in
          (("wq", (64, 64)), ("wk", (64, 32)), ("wv", (64, 32)))}
    q, k, _v = sw._qkv(h, lp, cfg.rope_full, jnp.asarray([[5, 6, 7, 8]]), cfg)
    assert np.array_equal(np.asarray(q).reshape(1, 4, 64), np.asarray(h @ lp["wq"]))
    assert np.array_equal(np.asarray(k), np.asarray(h @ lp["wk"]))


def test_the_norm_centres_on_the_mean_and_the_shared_experts_are_their_mean(params):
    x = 3.0 + jax.random.normal(jax.random.PRNGKey(5), (7, CFG.dim), jnp.float32)
    w = params["window"]["norm"][1]
    a = np.asarray(x, np.float64)
    want = (a - a.mean(-1, keepdims=True)) / np.sqrt(a.var(-1, keepdims=True) + 1e-5) * np.asarray(w)
    assert np.abs(np.asarray(sw._norm(x, w, CFG)) - want).max() <= 1e-5
    assert np.abs(np.asarray(ref.layernorm(x, w, 1e-5)) - want).max() <= 1e-5
    assert np.abs(np.asarray(sw._norm(x, w, sw.TINY_SPARSE)) - want).max() > 0.1  # rms: not centred
    # one product of width n x F against each expert alone, averaged
    m, F = params["moe"], CFG.shared_ffn
    got = np.asarray(sw.shared_experts(x, m, jnp.int32(2), CFG))
    each = []
    for j in range(CFG.n_shared):
        cols = slice(j * F, (j + 1) * F)
        gate, up = x @ m["shared_in"][2][:, cols], x @ m["shared_in"][2][:, CFG.shared_width:][:, cols]
        each.append(np.asarray((jax.nn.silu(gate) * up) @ m["shared_out"][2][cols]))
    assert np.abs(got - np.mean(each, axis=0)).max() <= 1e-5
    summed = np.asarray(sw.shared_experts(x, m, jnp.int32(2),
                                          dataclasses.replace(CFG, shared_average=False)))
    assert np.abs(summed - np.sum(each, axis=0)).max() <= 1e-5


# ---- the share -------------------------------------------------------------------


def one_layer(kind):
    """A one-layer model of ``kind`` that holds EVERY expert, and its four
    shares of two experts each, their stacks cut from the whole one's."""
    whole = dataclasses.replace(CFG, periods=1, period=(kind,), expert_first=0, experts_held=0)
    params = seeded(whole, 7)
    shares = []
    for first in range(0, 8, 2):
        cfg = dataclasses.replace(whole, expert_first=first, experts_held=2)
        moe = dict(params["moe"], w_in=params["moe"]["w_in"][:, first:first + 2],
                   w_out=params["moe"]["w_out"][:, first:first + 2])
        shares.append((cfg, {**params, "moe": moe}))
    config = {**CONFIG, "layer_types": [{"window": "sliding_attention", "full": "full_attention"}[kind]],
              "num_hidden_layers": 1, "num_experts": 8, "expert_first": 0}
    return whole, params, shares, config


@pytest.mark.parametrize("kind", ["window", "full"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(kind, monkeypatch):
    """Four chips hold two of a layer's eight experts each; every one of them
    computes the attention and the shared experts alike. The four streams they
    hand on, the part every chip computes counted ONCE, add up to what the
    uncut reference gives for the whole layer (``model-configs`` guide, section
    4): ``sum_s x_s - 3 (h + attention + shared) = h + attention + shared +
    routed``, the base from the reference with no routed expert at all."""
    whole, params, shares, config = one_layer(kind)
    monkeypatch.setattr(sw, "_logits", lambda params, x, cfg: x)  # the stream, not the logits
    tokens = np.random.default_rng(2).integers(0, 256, 90)
    toks = np.zeros((1, 128), np.int32)
    toks[0, :90] = tokens
    streams = []
    for cfg, part in shares:
        x, _ = jax.jit(partial(sw.prefill, cfg=cfg))(
            part, fresh_cache(False, cfg), jnp.asarray(toks), jnp.asarray([90]), rows=jnp.asarray([1]))
        streams.append(np.asarray(x)[0])
    tree = ref_tree(params)
    uncut = np.asarray(ref.hidden(tree, padded(tokens), config))[89]
    base = np.asarray(ref.hidden(tree, padded(tokens), config, first=0, count=0))[89]
    assert np.abs(uncut - base).max() > 1e-2  # the routed part is there to be shared out
    assert np.abs(np.sum(streams, axis=0) - 3 * base - uncut).max() <= TOL
    # and the program that holds all eight gives the uncut layer itself
    x, _ = jax.jit(partial(sw.prefill, cfg=whole))(
        params, fresh_cache(False, whole), jnp.asarray(toks), jnp.asarray([90]), rows=jnp.asarray([1]))
    assert np.abs(np.asarray(x)[0] - uncut).max() <= TOL


@pytest.mark.parametrize("case", ["an even router", "every token routed here"])
def test_a_shares_kernel_call_runs_no_tile_past_the_assignments_that_fell_on_it(params, case, monkeypatch):
    """A chunk of 600 tokens, two of eight experts held: the kernel is handed
    all 1,200 assignments in their order by expert and runs the row tiles
    that hold the some 300 that fell here (one for each expert and tile, at
    most one more than those rows fill, since the two groups share one tile
    at most), and none past ``sum(load)``; with every token pushed to the
    held pair it runs them all. Either way the layer is the reference's loop
    over the held experts, nothing dropped."""
    layer, T, rows, seen = 3, 600, 64, []
    forced_kernel(monkeypatch, (rows, 32), seen)
    h = jax.random.normal(jax.random.PRNGKey(8), (T, CFG.dim), jnp.float32)
    if case == "every token routed here":
        h = h + 40.0 * (params["moe"]["router"][layer][:, 2] + params["moe"]["router"][layer][:, 3])[None]
    kept = jnp.arange(T) % 7 != 3
    got, load = sw.expert_layer(h, params["moe"], jnp.int32(layer), kept, CFG)
    lw = jax.tree_util.tree_map(lambda leaf: leaf[layer], ref_tree(params)["moe"])
    top_e, gates = ref.routing(h, lw["router"], CFG.top_k, "float32")
    want = jnp.zeros_like(h)
    for e in (2, 3):
        g = jnp.sum(jnp.where(top_e == e, gates, 0.0), axis=-1) * kept
        gu = h @ lw["gate_up_proj"][e - 2]
        want = want + g[:, None] * ((jax.nn.silu(gu[:, :32]) * gu[:, 32:]) @ lw["down_proj"][e - 2])
    here = int(np.isin(np.asarray(top_e)[np.asarray(kept)], (2, 3)).sum())
    (seen_load, entries, tile), = seen  # one call, of all the assignments
    assert int(jnp.sum(seen_load)) == int(load.sum()) == here
    filled = -(-here // rows)
    assert filled <= int(entries) <= filled + 1
    assert int(tile[:int(entries)].max()) == filled - 1  # no entry names a tile past the rows that fell here
    every = T * CFG.top_k // rows
    assert (here > 1000) == (case == "every token routed here") == (int(entries) > every // 2)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-4


def test_assign_held_of_assign_all_sums_over_the_shares_to_all():
    """The counters of a held share: every share counts the same kept
    assignments (``assign_all``: tokens x top_k, a layer), and those that fell
    on its own experts (``assign_held``) add up over the shares to all of
    them: nothing is dropped and nothing counted twice."""
    _whole, _params, shares, _config = one_layer("full")
    tokens = np.random.default_rng(4).integers(0, 256, 40)
    held, every, loads = [], [], []
    for cfg, part in shares:
        cache = fresh_cache(False, cfg)
        _, cache = jax.jit(partial(sw.prefill, cfg=cfg))(
            part, cache, jnp.asarray(tokens[None, :32]), jnp.asarray([30]), rows=jnp.asarray([0]))
        assert int(cache["assign_all"]) == 30 * cfg.top_k
        _toks, _last, _key, cache, counters = jax.jit(partial(
            sw.decode_segment, cfg=cfg, n_steps=4, greedy=True))(
            part, cache, jnp.asarray([[3], [0], [5]], jnp.int32), jnp.zeros((3,)),
            jax.random.PRNGKey(0), jnp.asarray([3, 0, 0], jnp.int32))
        assert not int(cache["assign_all"]) and not np.asarray(cache["expert_tokens"]).any()
        held.append(int(counters["assign_held"]))
        every.append(int(counters["assign_all"]))
        loads.append(np.asarray(counters["expert_tokens"]))
        assert loads[-1].shape == (1, 2) and held[-1] == loads[-1].sum()
    # 30 prompt tokens and 3 kept steps of one row; greedy tokens may differ between the
    # shares after the first step (their streams differ), the counts may not
    assert every == [(30 + 3) * CFG.top_k] * 4
    first_step = [jax.jit(partial(sw.prefill, cfg=cfg))(
        part, fresh_cache(False, cfg), jnp.asarray(tokens[None, :32]), jnp.asarray([30]),
        rows=jnp.asarray([0]))[1]["expert_tokens"] for cfg, part in shares]
    assert int(np.sum([np.asarray(t) for t in first_step])) == 30 * CFG.top_k  # over the shares: all


# ---- the blocked arm -------------------------------------------------------------


def test_the_blocked_prefill_is_the_dense_scores_it_replaces(params, monkeypatch):
    """Two rows of ragged lengths in one program, each from its own start,
    the window's early blocks released and one of them REUSED by the other
    row: the tiles folded with an online softmax give what the gathered views
    with dense float32 scores give, in both kinds of layer, to a few 1e-6."""
    monkeypatch.setattr(sw, "PREFILL_TILE", 64)
    tokens = [np.random.default_rng(s).integers(0, CFG.vocab_size, n) for s, n in ((1, 150), (2, 75))]
    out = {}
    for blocked in (False, True):
        suffix, _ = programs(blocked)
        table = WindowTable(BlockAllocator(1 + 24, BS), 3, 16, CFG.window)
        cache = fresh_cache(blocked)
        table.reserve(0, 128)
        cache["wbt"] = jnp.asarray(table.table)
        _, cache = prefill_in_chunks(suffix, blocked, params, cache, tokens[0][:128], 0, 32)
        freed = table.release_behind(0, 128)
        mine = set(table.table[0][table.table[0] > 0])
        table.reserve(0, 160)
        table.reserve(2, 96)  # takes blocks row 0 has just released
        assert freed >= 5 and set(table.table[2][table.table[2] > 0]) - mine
        cache["wbt"] = jnp.asarray(table.table)
        _, cache = prefill_in_chunks(suffix, blocked, params, cache, tokens[1][:64], 2, 32)
        toks = np.zeros((2, 32), np.int32)
        toks[0, :22], toks[1, :11] = tokens[0][128:150], tokens[1][64:75]
        live = {} if blocked else {"live_to": jnp.int32(160)}
        logits, _ = suffix(params, cache, jnp.asarray(toks), jnp.asarray([22, 11]),
                           rows=jnp.asarray([0, 2]), starts=jnp.asarray([128, 64]), **live)
        out[blocked] = np.asarray(logits)
    assert np.abs(out[True] - out[False]).max() <= 1e-5
    for r, n in ((0, 150), (1, 75)):
        assert np.abs(out[True][r] - reference_logits(params, tokens[r])[n - 1]).max() <= TOL


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
def test_a_decode_step_reads_the_run_of_blocks_that_ends_at_the_row(kernel):
    """``paged_attention`` with a window, handed a RUN of a row's table that
    begins ``window / BS`` blocks back and a position counted from the run's
    first key: what dense attention over the last ``window`` keys gives. On
    both arms (the Pallas kernel through the interpreter); a row that is not
    live reads nothing."""
    B, H, KV, hd, window, MB = 3, 4, 2, 16, 64, 32
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(6), 3)
    kp = jax.random.normal(k0, (2, 1 + B * MB, BS, KV, hd), jnp.float32)
    vp = jax.random.normal(k1, (2, 1 + B * MB, BS, KV, hd), jnp.float32)
    q = jax.random.normal(k2, (B, 1, H, hd), jnp.float32)
    wbt = jnp.asarray(1 + np.arange(B * MB, dtype=np.int32).reshape(B, MB))
    pos = jnp.asarray([5, 200, 511], jnp.int32)
    run, at = sw._run_of_blocks(wbt, pos, BS, window)
    assert run.shape == (B, window // BS + 16) and list(np.asarray(at)) == [5, 200 - 8 * 16, 511 - 27 * 16]
    live = jnp.asarray([True, True, False])
    got = np.asarray(paged_attention.paged_attention(
        q, kp, vp, run, at, layer=jnp.int32(1), live=live, window=window, kernel=kernel,
        interpret=kernel == "pallas"))
    for b in range(B):
        p = int(pos[b])
        keys = np.asarray(kp[1])[np.asarray(wbt[b])].reshape(MB * BS, KV, hd)
        vals = np.asarray(vp[1])[np.asarray(wbt[b])].reshape(MB * BS, KV, hd)
        lo = max(0, p - window + 1)
        for h in range(H):
            s = keys[lo:p + 1, h // 2] @ np.asarray(q[b, 0, h]) / 4.0
            w = np.exp(s - s.max())
            want = (w / w.sum()) @ vals[lo:p + 1, h // 2]
            assert np.abs(got[b, 0, h] - (want if b < 2 else 0.0)).max() <= 1e-5, (b, h)
    # without the window the same call sees every earlier key: another answer
    wide = np.asarray(paged_attention.paged_attention(
        q, kp, vp, run, at, layer=jnp.int32(1), live=live, kernel=kernel,
        interpret=kernel == "pallas"))
    assert np.abs(wide[1] - got[1]).max() > 1e-3 and np.abs(wide[0] - got[0]).max() <= 1e-6


def test_prefill_then_decode_through_both_pools_is_the_full_forward(params, arm):
    """A prompt of 70 tokens (past the window of 32) prefilled in chunks of
    16, then 50 tokens decoded one by one, the window's blocks RELEASED behind
    it as the engine would (their table entries back at trash, the blocks
    scribbled over): every step's logits are the reference's full forward
    pass. On both arms. The row beside it keeps no token and touches nothing."""
    tokens = np.random.default_rng(11).integers(0, CFG.vocab_size, 120)
    want = reference_logits(params, tokens)
    suffix, step = programs(arm)
    table = WindowTable(BlockAllocator(1 + 3 * 16, BS), 3, 16, CFG.window)
    table.reserve(1, 120)
    cache = fresh_cache(arm)
    cache["wbt"] = jnp.asarray(table.table)
    got, cache = prefill_in_chunks(suffix, arm, params, cache, tokens[:70], 1, 16)
    assert np.abs(got - want[69]).max() <= TOL
    for p in range(70, 120):
        table.release_behind(1, p)
        gone = np.asarray(cache["wbt"])[1][table.table[1] == 0]
        cache["wk"] = cache["wk"].at[:, gone[gone > 0]].set(99.0)  # as a new owner would
        cache["wv"] = cache["wv"].at[:, gone[gone > 0]].set(-99.0)
        cache["wbt"] = jnp.asarray(table.table)
        toks = np.zeros((3, 1), np.int32)
        toks[1, 0] = tokens[p]
        live = {} if arm else {"live_to": jnp.int32(p + 1)}
        logits, cache, load = step(params, cache, jnp.asarray(toks),
                                   jnp.asarray([False, True, False]), **live)
        assert np.abs(np.asarray(logits)[1] - want[p]).max() <= TOL, p
        assert load.shape == (CFG.n_layers, 2)  # the held experts' alone
    assert table.held(1) == 8 - (119 - 32 + 1) // BS


#: sha256 of ``str(jax.make_jaxpr(...))`` of tiny-sparse's three programs as PR 46
#: left them (one grouped form of the expert layer for a chunk and a decode step
#: alike; before it, since commit 83b04a5, a decode step's rows were multiplied by
#: every expert): equal text is an equal program
MELLUM_PROGRAMS = {"prefill": "e7f508bb523c1b22", "prefill_from": "e69a641f157c4a6f",
                   "decode_seg4": "9bc49a98e0d14243"}


@pytest.mark.parametrize("program", sorted(MELLUM_PROGRAMS))
def test_mellum2s_tiny_preset_traces_to_the_program_it_was(program):
    """The settings default to Mellum2's block: its tiny preset's programs are,
    to the character of their jaxprs, what the last PR that meant to change
    them left, so their logits are what they were bit for bit. A PR that
    means to change that program changes the digest with it (PR 46 did, and
    held the new programs' logits to the reference loop instead:
    ``tests/test_expert_gmm.py``, ``tests/test_sparse_window.py``)."""
    cfg, S = sw.TINY_SPARSE, jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: sw.sparse_init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: sw.init_cache(cfg, 3, 256, 49, 49, 16))
    one, i32 = S((1,), jnp.int32), S((), jnp.int32)
    if program == "decode_seg4":
        text = jax.make_jaxpr(partial(sw.decode_segment, cfg=cfg, n_steps=4, greedy=True, spans=SPANS))(
            params, cache, S((3, 1), jnp.int32), S((3,), jnp.float32), S((2,), jnp.uint32),
            S((3,), jnp.int32), live_to=i32)
    elif program == "prefill":
        text = jax.make_jaxpr(partial(sw.prefill, cfg=cfg))(
            params, cache, S((1, 32), jnp.int32), one, rows=one)
    else:
        text = jax.make_jaxpr(partial(sw.prefill, cfg=cfg, spans=SPANS))(
            params, cache, S((1, 32), jnp.int32), one, rows=one, starts=one, live_to=i32)
    assert hashlib.sha256(str(text).encode()).hexdigest()[:16] == MELLUM_PROGRAMS[program]


# ---- the cost functions, by hand ---------------------------------------------------


def test_the_costs_are_the_shapes_worked_by_hand():
    from benchmark import parallel_sparse_costs as costs

    config = json.loads((ROOT / "benchmark" / "configs" / "command-a-plus-05-2026-l4.json").read_text())
    assert costs.attention_params(config) == 2 * 4096 * 16384 + 2 * 4096 * 1024 == 142_606_336
    assert costs.router_params(config) == 4096 * 128  # every published expert, held or not
    assert costs.expert_params(config) == 3 * 4096 * 4096 == 50_331_648
    assert costs.shared_params(config) == 201_326_592
    assert costs.layer_params(config) == 142_606_336 + 524_288 + 201_326_592 + 16 * 50_331_648 + 4096
    assert costs.model_params(config) == 4 * 1_149_767_680 + 32768 * 4096 + 4096 == 4_733_292_544
    assert sw.COMMAND_A_PLUS_L4.num_params() == costs.model_params(config)  # 9.47 GB in bfloat16
    assert costs.kv_bytes_per_key(config) == 2 * 8 * 128 * 2 == 4096
    step = costs.step_bytes(config)
    assert step == 2 * (4 * (142_606_336 + 524_288 + 201_326_592 + 4096) + 32768 * 4096 + 4096)
    # every held expert of every layer touched in each of 4 steps, no keys: all the weights
    assert costs.decode_segment_bytes(config, 4, 16, 64, 0, 0, 4 * 4 * 16) == 4 * 2 * costs.model_params(config)
    # one row of two keeps 3 of 32 steps: 2 steps at least, its keys in 1 full and 3 window layers
    assert costs.decode_segment_bytes(config, 32, 2, 3, 20000, 4096, 10) == (
        2 * step + 10 * 100_663_296 + 4096 * (20000 + 3 * 4096) * 3 / 2)
    assert costs.decode_segment_bytes(config, 4, 0, 0, 0, 0, 0) == 0.0
    # a chunk of 1024 from position 8192: a full layer's pairs whole, a window layer's 4096 a token
    keys = 1024 * 8192 + 1024 * 1025 // 2
    per_token = 2 * 4 * (142_606_336 + 524_288 + 201_326_592 + 8 * 0.125 * 50_331_648)
    assert costs.prefill_flops(config, 1024, keys, 0.125) == (
        1024 * per_token + 4.0 * 128 * 128 * (keys + 3 * 1024 * 4096))
    assert 4.6e12 < costs.prefill_flops(config, 1024, keys, 0.125) < 4.7e12  # ISSUE 45: "some 4.4"
    assert costs.held_share(config, {}) == 0.125
    assert costs.held_share(config, {"assign_held": 30, "assign_all": 200}) == 0.15
    assert costs.folded_keys(config, 1024, keys, 1024) == {"full": 9216, "window": 5632}
    assert costs.folded_keys(config, 7, 7 * 2048 + 28, 16) == {"full": 2560, "window": 4608}
    # the kernel's calls of a 4-step segment: 8 rows, all steps kept
    assert costs.kernel_segment_bytes(config, 4, 8, 32, 4 * 8 * 1024, 8 * 1000) == (
        4096 * (4 * 8 * 1024 + 3 * 8 * 1000 * 4))
    with pytest.raises(ValueError, match="use_parallel_block"):
        costs.sizes_of({**config, "use_parallel_block": False})


# ---- the engine ------------------------------------------------------------------


def make_engine(**kw):
    from kubedl_tpu.serving.server import LlamaEngine

    settings = dict(preset="tiny-parallel", max_batch=3, max_seq=256, kv_block_size=BS,
                    prefill_chunk_tokens=32, kv_attention="blocked")
    settings.update(kw)
    return LlamaEngine(**settings)


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    yield eng
    eng.close()


PROMPTS = [np.random.default_rng(5).integers(0, CFG.vocab_size, n).tolist()
           for n in (5, 70, 23, 130, 41)]


def engine_reference(eng, tokens):
    """The reference on the engine's own (seed 0) parameters."""
    return reference_logits(eng.params, np.asarray(tokens))


def test_the_engine_serves_the_block_on_the_blocked_arm_as_the_reference_would(engine):
    """Five requests on three rows through the tick that serves Mellum2,
    prompts of one to five chunks, three of them past the window: every served
    token is the reference's best at its position, by its logits. Then both
    pools are all free again, window blocks were released on the way, and the
    share's counters say that a part of the assignments fell here."""
    assert engine._runner.blocked and engine._runner.spans == (256,)
    assert engine._runner.cache["k"].ndim == 5 and engine.kv_attention == "blocked"
    served = serve_together(engine, PROMPTS, max_tokens=30)
    for prompt, tokens in zip(PROMPTS, served):
        assert len(tokens) == 30
        logits = engine_reference(engine, prompt + tokens[:-1])[len(prompt) - 1:]
        gaps = logits.max(axis=-1) - logits[np.arange(30), tokens]
        assert gaps.max() <= TOL, gaps
    st = engine.stats()
    kv = st["kv_blocks"]
    assert kv["free"] == kv["total"] and kv["window"]["free"] == kv["window"]["total"]
    assert kv["window"]["released"] > 0 and kv["window"]["held"] == 0
    assert 0 < st["assign_held"] < st["assign_all"]
    assert st["assign_held"] == np.asarray(st["expert_tokens"]).sum()
    assert np.asarray(st["expert_tokens"]).shape == (CFG.n_layers, 2)


def test_what_a_request_is_served_does_not_depend_on_its_neighbours(engine):
    together = serve_together(engine, PROMPTS, max_tokens=20)
    alone = [engine.generate(p, max_tokens=20, temperature=0.0)["token_ids"] for p in PROMPTS]
    assert together == alone


def test_the_phases_say_what_the_share_and_the_blocked_arm_did(engine, monkeypatch):
    log, real = [], TRACER.phase
    monkeypatch.setattr(TRACER, "phase",
                        lambda name, **attrs: _Recorded(real(name, **attrs), name, attrs, log))
    before = engine.stats()
    engine.generate(PROMPTS[1], max_tokens=6, temperature=0.0)  # 70 tokens: chunks 32, 32, 6
    after = engine.stats()
    pre = [a for n, a in log if n == "engine.prefill_dispatch"]
    assert [a["tokens"] for a in pre] == [32, 32, 6]
    # no view: what the full layers fold, the program's reach in whole tiles of 512
    assert [a["span"] for a in pre] == [256, 256, 256]
    dec = [a for n, a in log if n == "engine.decode_dispatch" and "k" in a]
    assert dec and (dec[0]["keys"], dec[0]["wkeys"]) == (70, 32)
    take = sum(a["take"] for a in dec)
    every = after["assign_all"] - before.get("assign_all", 0)
    assert every == (70 + take) * CFG.n_layers * CFG.top_k  # every kept assignment, held or not
    held = after["assign_held"] - before.get("assign_held", 0)
    assert 0 < held < every


def test_mellum2s_tiny_preset_is_served_alike_on_both_arms():
    """The blocked arm is the runner's, not one model's: the sequential
    RMSNorm block with its YaRN table serves the same tokens through it."""
    from kubedl_tpu.serving.server import LlamaEngine

    served = {}
    for arm in ("gather", "blocked"):
        eng = LlamaEngine(preset="tiny-sparse", max_batch=3, max_seq=256, kv_block_size=BS,
                          prefill_chunk_tokens=32, kv_attention=arm)
        try:
            served[arm] = serve_together(eng, PROMPTS[:3], max_tokens=12)
        finally:
            eng.close()
    assert served["gather"] == served["blocked"]


@pytest.mark.parametrize("preset, kw, reason", [
    ("tiny-parallel", {"spec_k": 2}, "window blocks the row has released"),
    ("tiny-parallel", {"quantize": "int8"}, "quantize"),
    ("tiny-parallel", {"kv_layout": "contiguous"}, "kv_layout='contiguous'"),
    ("tiny-hybrid", {"kv_attention": "blocked"}, "kv_attention='blocked'"),
    ("tiny-retention", {"kv_attention": "blocked"}, "kv_attention='blocked'"),
])
def test_refusals_name_their_reason(preset, kw, reason):
    from kubedl_tpu.serving.model_runner import make_runner

    settings = dict(max_batch=2, max_seq=64, **kw)
    paged = settings.pop("kv_layout", "paged") == "paged"
    with pytest.raises(ValueError, match=reason):
        make_runner(preset, paged=paged, **settings)


def test_the_runner_of_the_block_stands_behind_make_runner():
    from kubedl_tpu.serving.model_runner import SparseWindowRunner, make_runner

    made = make_runner("tiny-parallel", max_batch=2, max_seq=100, kv_attention="blocked")
    assert type(made) is SparseWindowRunner and made.blocked and made.window == 32
    assert made.spans == (112,) and made.decode_tile == 0  # a CPU: the lax arm, no kernel
    assert made.keys_read([10, 20], 4) is None and made.span_for(5) == 112
    gathered = make_runner("tiny-parallel", max_batch=2, max_seq=100)
    assert not gathered.blocked and gathered.spans == (112,)
    with pytest.raises(ValueError, match="unknown kv_attention"):
        SparseWindowRunner("tiny-parallel", max_batch=2, kv_attention="flash")
    big = sw.preset("command-a-plus-05-2026-l4")
    assert (big.n_layers, big.n_window, big.n_full, big.held, big.shared_width) == (4, 3, 1, 16, 16384)
    assert paged_attention.decode_kernel_fits(1, big.n_heads, big.n_kv_heads, big.head_dim, 16, big.dtype)
    # a row's blocks at the cell's sizes: 64 KiB a block a layer
    sized = make_runner("command-a-plus-05-2026-l4", max_batch=16, max_seq=32768, kv_attention="blocked")
    assert sized.block_bytes == 65536 and sized.window_block_bytes == 3 * 65536
    assert sized.size_window_pool(1024) == 1 + 16 * (256 + 64 + 1)
