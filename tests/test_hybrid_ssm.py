"""The hybrid state-space model (models/hybrid_ssm.py, ops/ssd_scan.py) and
its runner behind ``LlamaEngine``: recurrent state beside the paged K/V.

Everything runs at the ``tiny-hybrid`` preset in float32 on the CPU with
seeded weights, and is held to ``benchmark/reference/hybrid_ref.py`` (plain
float32, the recurrence token by token) in LOGITS. Tolerances: the program
and the reference compute the same float32 sums in another order (chunked
against sequential, one fused projection against three), so logits of
deviation 0.01 agree to a few 1e-7; 1e-5 is fifty times that and a
thousandth of a logit's deviation, which a dropped term or a state carried
wrongly passes by orders of magnitude (a zeroed state moves logits by 1e-2).
"""

import logging
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import hybrid_ref
from kubedl_tpu.models import hybrid_ssm as hy
from kubedl_tpu.models import llama
from kubedl_tpu.observability.tracing import TRACER
from kubedl_tpu.ops import ssd_scan

CFG = hy.TINY_HYBRID
#: the reference's view of the tiny preset: the published key names
CONFIG = {
    "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2, "num_hidden_layers": 8,
    "hidden_size": 64, "vocab_size": 256, "shared_intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_n_groups": 1, "num_local_experts": 0, "rms_norm_eps": 1e-5,
    "residual_multiplier": 0.22, "embedding_multiplier": 12.0, "logits_scaling": 8.0,
    "attention_multiplier": 1.0 / 16, "tie_word_embeddings": True,
}
TOL = 1e-5


def ref_tree(params):
    """The program's parameter tree under the reference's leaf names."""
    m, a, f = params["mamba"], params["attn"], params["mlp"]
    return {
        "embed": params["embed"], "final_norm": params["final_norm"],
        "mamba": {"mixer_norm": m["norm"], "in_proj_z": m["in_z"], "in_proj_xbc": m["in_xbc"],
                  "in_proj_dt": m["in_dt"], **{k: m[k] for k in (
                      "conv_w", "conv_b", "dt_bias", "A_log", "D", "gate_norm", "out_proj")}},
        "attention": {"mixer_norm": a["norm"], "q_proj": a["wq"], "k_proj": a["wk"],
                      "v_proj": a["wv"], "o_proj": a["wo"]},
        "mlp": {"mlp_norm": f["norm"], "input_linear": f["w_in"], "output_linear": f["w_out"]},
    }


@pytest.fixture(scope="module")
def params():
    p = hy.hybrid_init(jax.random.PRNGKey(3), CFG)
    # a bias that is not zero, so that leaving it out would show
    p["mamba"]["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), p["mamba"]["conv_b"].shape)
    return p


def reference_logits(params, seq):
    return np.asarray(hybrid_ref.forward(ref_tree(params), jnp.asarray(seq, jnp.int32), CONFIG))


def fresh_cache(rows=3, max_seq=64, block=8):
    cache = hy.init_cache(CFG, rows, max_seq, 1 + rows * max_seq // block, block)
    table = 1 + np.arange(rows * max_seq // block, dtype=np.int32).reshape(rows, -1)
    cache["bt"] = jnp.asarray(table)
    # rows that were used before: a slab that is not zero must not leak
    cache["ssm"] = cache["ssm"] + 1.0
    cache["conv"] = cache["conv"] + 1.0
    return cache


def run_prefill(params, cache, row, tokens, start=None, bucket=None, spans=(32, 64)):
    bucket = bucket or max(16, 1 << (len(tokens) - 1).bit_length())
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(tokens)] = tokens
    kw = {}
    if start is not None:
        kw = {"starts": jnp.asarray([start], jnp.int32), "spans": spans,
              "live_to": jnp.int32(min(start + bucket, spans[-1]))}
    with jax.default_matmul_precision("highest"):
        return hy.prefill(params, cache, jnp.asarray(toks), jnp.asarray([len(tokens)], jnp.int32),
                          CFG, jnp.asarray([row], jnp.int32), **kw)


# ---- the scan ---------------------------------------------------------------


def scan_inputs(S, lengths, seed=0, B=2, H=3, P=4, N=5):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)))
    real = jnp.arange(S)[None, :, None] < jnp.asarray(lengths)[:, None, None]
    return (jax.random.normal(k[0], (B, S, H, P)), jnp.where(real, dt, 0.0),
            -jnp.exp(jax.random.normal(k[2], (H,))), jax.random.normal(k[3], (B, S, N)),
            jax.random.normal(k[4], (B, S, N)), jax.random.normal(k[5], (B, H, P, N)))


@pytest.mark.parametrize("S, chunk, lengths", [
    (64, 16, (64, 64)),   # whole chunks
    (64, 16, (37, 5)),    # ragged: a length inside a chunk, one inside the first
    (64, 16, (48, 0)),    # a length on a chunk's edge, and a row with no token
    (16, 256, (16, 9)),   # a bucket shorter than the chunk: one chunk
    (40, 16, (40, 23)),   # no whole number of chunks: the tail is padded
    (64, 64, (64, 1)),    # one chunk of everything
])
def test_chunked_scan_equals_the_sequential_twin(S, chunk, lengths):
    x, dt, A, Bm, Cm, state = scan_inputs(S, lengths)
    with jax.default_matmul_precision("highest"):
        y0, s0 = ssd_scan.ssd_sequential(x, dt, A, Bm, Cm, state)
        y1, s1 = ssd_scan.ssd_chunked(x, dt, A, Bm, Cm, state, chunk)
    # float32 sums of up to 64 terms of size about 10, in another order
    np.testing.assert_allclose(y1, y0, atol=2e-4)
    np.testing.assert_allclose(s1, s0, atol=2e-4)
    # a padded tail leaves the state where the last real token left it
    for b, n in enumerate(lengths):
        _, upto = ssd_scan.ssd_sequential(x[b:b + 1, :n], dt[b:b + 1, :n], A, Bm[b:b + 1, :n],
                                          Cm[b:b + 1, :n], state[b:b + 1])
        np.testing.assert_allclose(s1[b], upto[0], atol=2e-4)


def test_scan_in_two_calls_carries_its_state():
    x, dt, A, Bm, Cm, state = scan_inputs(48, (48, 48), seed=1)
    with jax.default_matmul_precision("highest"):
        y, s = ssd_scan.ssd_chunked(x, dt, A, Bm, Cm, state, 16)
        ya, sa = ssd_scan.ssd_chunked(x[:, :24], dt[:, :24], A, Bm[:, :24], Cm[:, :24], state, 16)
        yb, sb = ssd_scan.ssd_chunked(x[:, 24:], dt[:, 24:], A, Bm[:, 24:], Cm[:, 24:], sa, 16)
    np.testing.assert_allclose(jnp.concatenate([ya, yb], 1), y, atol=2e-4)
    np.testing.assert_allclose(sb, s, atol=2e-4)


# ---- the one-step kernel ------------------------------------------------------


def forced_kernel(mp):
    """What a TPU process observes, steered for a CPU: the backend's name,
    the tiling predicate (the tiny preset's ``N`` is 16), and the interpreter
    in the compiled kernel's place. Holds for as long as ``mp`` does:
    programs trace on first use."""
    import functools

    mp.setattr(jax, "default_backend", lambda: "tpu")
    mp.setattr(ssd_scan, "step_kernel_fits", lambda state: True)
    mp.setattr(ssd_scan, "ssd_step_rows",
               functools.partial(ssd_scan.ssd_step_rows, interpret=True))


SCHEDULED = {"none": (), "one": (3,), "three": (0, 2, 4), "every": (0, 1, 2, 3, 4)}


@pytest.mark.parametrize("which", sorted(SCHEDULED))
def test_the_step_kernel_advances_the_listed_rows_and_touches_no_other(which, monkeypatch):
    """``ssd_step_rows`` through the interpreter against ``ssd_step`` on the
    tiny preset's shapes, five rows: ``y`` and the listed rows' slabs of the
    layer agree to float32 rounding (the state's arithmetic is the same in
    the same order, ``y`` sums ``N`` in its own), every other slab of the
    state is bit for bit what it was, and a decode segment's counters say
    how many slabs its steps fetched."""
    B, L = 5, CFG.n_mamba
    H, P, N = CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state
    k = jax.random.split(jax.random.PRNGKey(40), 6)
    x = jax.random.normal(k[0], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm, Cm = jax.random.normal(k[3], (B, N)), jax.random.normal(k[4], (B, N))
    state = jax.random.normal(k[5], (B, L, H, P, N))
    live = np.zeros((B,), bool)
    live[list(SCHEDULED[which])] = True
    rows, count = ssd_scan.scheduled_rows(jnp.asarray(live))
    assert int(count) == live.sum() and rows[:int(count)].tolist() == list(SCHEDULED[which])
    for layer in (0, L - 1):
        got_s, got_y = jax.jit(lambda *a: ssd_scan.ssd_step_rows(*a, interpret=True))(
            x, dt, A, Bm, Cm, state, jnp.int32(layer), rows, count)
        want_s, want_y = ssd_scan.ssd_step(x, dt, A, Bm, Cm, state[:, layer])
        np.testing.assert_allclose(np.asarray(got_y)[live], np.asarray(want_y)[live],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_s)[live, layer], np.asarray(want_s)[live],
                                   rtol=1e-6, atol=1e-6)
        untouched = np.ones((B, L), bool)
        untouched[live, layer] = False
        assert (np.asarray(got_s)[untouched] == np.asarray(state)[untouched]).all()
        assert not np.asarray(got_y)[~live].any()
    # the same set through two steps of the whole model: the counters, and
    # (whatever the set) no slab of a row that was not scheduled moves
    forced_kernel(monkeypatch)
    params = hy.hybrid_init(jax.random.PRNGKey(3), CFG)
    cache = fresh_cache(rows=B)
    cache["pos"] = jnp.full((B,), 5, jnp.int32)
    _toks, _last, _key, after, counters = jax.jit(
        lambda p, c, live: hy.decode_segment(
            p, c, jnp.ones((B, 1), jnp.int32), jnp.zeros((B,), jnp.float32),
            jax.random.PRNGKey(0), live, CFG, n_steps=2, greedy=True)
    )(params, cache, jnp.asarray(live))
    assert {n: int(v) for n, v in counters.items()} == {
        "slabs_stepped": 2 * int(live.sum()), "slabs_held": 2 * B}
    assert (np.asarray(after["ssm"])[~live] == np.asarray(cache["ssm"])[~live]).all()
    if live.any():
        assert (np.asarray(after["ssm"])[live] != np.asarray(cache["ssm"])[live]).any()


@pytest.mark.parametrize("shape, dtype, fits", [
    ((32, 36, 64, 64, 128), jnp.float32, True),   # granite-4.0-h-micro's state
    ((3, 6, 4, 32, 16), jnp.float32, False),      # the tiny preset's: N is no whole lane tile
    ((32, 36, 64, 64, 128), jnp.bfloat16, False),  # another type
    ((32, 36, 64, 12, 128), jnp.float32, False),   # P is no whole sublane tile
])
def test_the_step_kernel_takes_a_float32_state_of_whole_tiles(shape, dtype, fits):
    state = jax.ShapeDtypeStruct(shape, dtype)
    assert ssd_scan.step_kernel_fits(state) is fits
    # and no CPU process picks it, whatever the state
    assert not hy.steps_listed_rows({"ssm": state})


def test_a_runner_with_the_kernel_serves_the_sweeps_tokens(params):
    """``HybridRunner.decode_segment`` for 4 steps, two of three rows
    scheduled, with the kernel forced through the interpreter and as a CPU
    runs it (``ssd_step`` over every row): the same greedy tokens, final
    slabs that agree to float32 rounding, the row that sat out bit for bit
    where it was, and counters that say what each path fetched."""
    from kubedl_tpu.serving.model_runner import HybridRunner

    def run(force):
        with pytest.MonkeyPatch.context() as mp:
            if force:
                forced_kernel(mp)
            r = HybridRunner("tiny-hybrid", max_batch=3, max_seq=64, kv_block_size=8)
            r.new_cache(1 + 3 * 64 // 8)
            r.cache["bt"] = jnp.asarray(1 + np.arange(3 * 8, dtype=np.int32).reshape(3, 8))
            r.cache["ssm"] = r.cache["ssm"] + 1.0  # a slab that sits out is not zero
            for row, prompt in ((0, PROMPTS[2]), (2, PROMPTS[0])):
                toks = np.zeros((1, 32), np.int32)
                toks[0, :len(prompt)] = prompt
                r.prefill(params, jnp.asarray(toks), jnp.asarray([len(prompt)], jnp.int32),
                          rows=jnp.asarray([row], jnp.int32))
            before = np.asarray(r.cache["ssm"])
            toks, _last, _key = r.decode_segment(
                4, True, params, jnp.asarray([[7], [0], [9]], jnp.int32),
                jnp.zeros((3,), jnp.float32), jax.random.PRNGKey(0), live_to=64, rows=[0, 2])
            counted = {n: int(v) for n, v in r.segment_counters.items()}
            return np.asarray(toks), before, np.asarray(r.cache["ssm"]), counted

    toks, before, after, counted = run(force=True)
    want_toks, _, want_after, want_counted = run(force=False)
    assert (toks[[0, 2]] == want_toks[[0, 2]]).all()
    np.testing.assert_allclose(after[[0, 2]], want_after[[0, 2]], rtol=1e-5, atol=1e-6)
    assert (after[1] == before[1]).all() and (after[0] != before[0]).any()
    assert counted == {"slabs_stepped": 4 * 2, "slabs_held": 4 * 3}
    assert want_counted == {"slabs_stepped": 4 * 3, "slabs_held": 4 * 3}


def test_one_query_attention_is_the_head_by_head_form():
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k[0], (3, 1, 4, 16))
    kv = [jax.random.normal(k[i], (3, 24, 2 * 16)) for i in (1, 2)]
    mask = jnp.arange(24)[None, :] <= jnp.asarray([23, 4, 0])[:, None]
    with jax.default_matmul_precision("highest"):
        got = hy._attention_one_query(q, kv[0], kv[1], mask)
        want = llama.attention(q, kv[0].reshape(3, 24, 2, 16), kv[1].reshape(3, 24, 2, 16),
                               causal=False, mask=mask[:, None, None, None, :])
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---- the model through its cache -------------------------------------------


def test_prefill_then_decode_through_the_cache_is_the_references_forward(params):
    rng = np.random.default_rng(0)
    seq = rng.integers(0, CFG.vocab_size, 34)
    ref = reference_logits(params, seq)
    logits, cache = run_prefill(params, fresh_cache(), 1, seq[:21])
    np.testing.assert_allclose(np.asarray(logits)[0], ref[20], atol=TOL)
    live = jnp.asarray([False, True, False])
    before = jax.tree_util.tree_map(np.asarray, {k: cache[k] for k in ("ssm", "conv")})
    for t in range(21, 34):
        tokens = jnp.asarray([[0], [seq[t]], [0]], jnp.int32)
        with jax.default_matmul_precision("highest"):
            logits, cache = hy.decode_step(params, cache, tokens, live, CFG, spans=(32, 64),
                                           live_to=jnp.int32(t + 1))
        np.testing.assert_allclose(np.asarray(logits)[1], ref[t], atol=TOL)
    # rows the steps did not name kept their slabs, to the bit
    for name in ("ssm", "conv"):
        np.testing.assert_array_equal(np.asarray(cache[name])[[0, 2]], before[name][[0, 2]])


@pytest.mark.parametrize("split", [16, 8, 19])
def test_prefill_in_two_chunks_is_prefill_in_one(params, split):
    """A chunk boundary on the scan's chunk (8 and 16) and off it (19)."""
    seq = np.random.default_rng(1).integers(0, CFG.vocab_size, 27)
    one, c1 = run_prefill(params, fresh_cache(), 2, seq, start=0)
    _, c2 = run_prefill(params, fresh_cache(), 2, seq[:split], start=0)
    two, c2 = run_prefill(params, c2, 2, seq[split:], start=split)
    np.testing.assert_allclose(two, one, atol=TOL)
    np.testing.assert_allclose(np.asarray(two)[0], reference_logits(params, seq)[-1], atol=TOL)
    np.testing.assert_allclose(c2["ssm"][2], c1["ssm"][2], atol=1e-5)
    np.testing.assert_allclose(c2["conv"][2], c1["conv"][2], atol=1e-5)
    assert int(c2["pos"][2]) == int(c1["pos"][2]) == 27


def test_a_wider_bucket_changes_nothing(params):
    seq = np.random.default_rng(2).integers(0, CFG.vocab_size, 11)
    a, ca = run_prefill(params, fresh_cache(), 0, seq, bucket=16)
    b, cb = run_prefill(params, fresh_cache(), 0, seq, bucket=64)
    np.testing.assert_allclose(b, a, atol=TOL)
    np.testing.assert_allclose(cb["ssm"][0], ca["ssm"][0], atol=1e-5)
    np.testing.assert_allclose(cb["conv"][0], ca["conv"][0], atol=1e-5)


def test_a_row_without_tokens_in_a_prefill_program_keeps_its_slab(params):
    """A compact batch may name a row it feeds nothing (``lengths`` 0, as the
    decoder's programs allow): its slab, window and position stay as they
    were, to the bit, and its K/V goes to the trash block."""
    seq = np.random.default_rng(3).integers(0, CFG.vocab_size, 11)
    cache = fresh_cache()
    before = {k: np.asarray(cache[k]) for k in ("ssm", "conv", "pos")}
    toks = np.zeros((2, 16), np.int32)
    toks[0, :11] = seq
    with jax.default_matmul_precision("highest"):
        logits, after = hy.prefill(
            params, cache, jnp.asarray(toks), jnp.asarray([11, 0], jnp.int32), CFG,
            jnp.asarray([2, 0], jnp.int32), starts=jnp.zeros((2,), jnp.int32),
            spans=(32, 64), live_to=jnp.int32(16))
    np.testing.assert_allclose(np.asarray(logits)[0], reference_logits(params, seq)[-1], atol=TOL)
    for name in ("ssm", "conv", "pos"):
        np.testing.assert_array_equal(np.asarray(after[name])[[0, 1]], before[name][[0, 1]])
    assert int(after["pos"][2]) == 11


def test_sizes_are_the_published_models():
    g = hy.GRANITE_4_H_MICRO
    assert (g.n_layers, g.n_mamba, g.periods) == (40, 36, 4)
    assert (g.ssm_inner, g.conv_dim, g.in_proj_dim) == (4096, 4352, 8512)
    assert abs(g.num_params() - 3.19e9) < 0.01e9
    assert hy.state_bytes_per_row(g) == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    kinds = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    assert hy.pattern_of(kinds) == (4, 5, 4)
    with pytest.raises(ValueError, match="does not repeat one period"):
        hy.pattern_of(["mamba", "attention", "attention", "mamba"])


# ---- the engine ---------------------------------------------------------------


def make_engine(**kw):
    from kubedl_tpu.serving.server import LlamaEngine

    settings = dict(preset="tiny-hybrid", max_batch=3, max_seq=128, kv_block_size=8,
                    prefill_chunk_tokens=16)
    settings.update(kw)
    return LlamaEngine(**settings)


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    yield eng
    eng.close()


PROMPTS = [np.random.default_rng(5).integers(0, CFG.vocab_size, n).tolist()
           for n in (5, 40, 23, 61, 9)]


def serve_together(eng, prompts, max_tokens=12):
    out = [None] * len(prompts)

    def go(i):
        out[i] = eng.generate(prompts[i], max_tokens=max_tokens, temperature=0.0)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert all(o is not None and "error" not in o for o in out), out
    return [o["token_ids"] for o in out]


def test_engine_serves_concurrent_requests_as_the_reference_would(engine):
    """Five requests on three rows, prompts of one to four chunks: every
    served token is the reference's best at its position, by its logits (a
    near-tie may go either way, so the served token's logit is held to the
    best within the tolerance, not the token to the token)."""
    served = serve_together(engine, PROMPTS)
    for prompt, tokens in zip(PROMPTS, served):
        assert len(tokens) == 12
        ref = reference_logits(engine.params, prompt + tokens[:-1])[len(prompt) - 1:]
        gaps = ref.max(axis=-1) - ref[np.arange(12), tokens]
        assert gaps.max() <= TOL, gaps
    st = engine.stats()
    assert st["state_rows"] == 0 and st["state_bytes"] == 0  # every row is free again
    assert st["state_resets"] >= len(PROMPTS)


def test_a_reused_row_gives_what_it_gives_alone(engine):
    """Rows are reused as requests finish (five requests, three rows): what
    a request is served does not depend on what its row held."""
    together = serve_together(engine, PROMPTS)
    alone = [engine.generate(p, max_tokens=12, temperature=0.0)["token_ids"] for p in PROMPTS]
    assert together == alone


def test_a_preempted_request_regenerates_the_same_tokens(engine):
    """A row is preempted between two chunks of its prompt, its state carried
    so far (`_preempt_locked`, as block exhaustion would): the request is
    requeued, prefilled again from position 0, where its slab is zeroed
    again, and served the tokens it is served alone."""
    prompt = np.random.default_rng(9).integers(0, CFG.vocab_size, 100).tolist()  # 7 chunks
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
                    if s is not None and s.prefill_pos > 0 and s.fed == 0 and not s.pending:
                        engine._preempt_locked(i)
                        caught = True
            time.sleep(0.0005)  # the scheduler needs the lock between two looks
        t.join(timeout=300)
        assert got == [alone]
        if caught:
            break
    assert caught and engine.stats()["kv_preemptions"] > before


def test_a_row_the_block_reserve_leaves_out_keeps_its_state():
    """A pool too small for both rows to grow to their ends (19 blocks for
    14 + 12): a row that cannot grow sits decode dispatches out while the
    other runs, and goes on from the state it had."""
    eng = make_engine(max_batch=2, kv_blocks=20, kv_low_watermark=0.0,
                      kv_high_watermark=0.0)
    try:
        prompts = [PROMPTS[1], PROMPTS[2]]  # 40 and 23 tokens, 70 more each
        alone = [eng.generate(p, max_tokens=70, temperature=0.0)["token_ids"] for p in prompts]
        assert serve_together(eng, prompts, max_tokens=70) == alone
    finally:
        eng.close()


class _Recorded:
    """A phase handle that keeps what ``set()`` is given."""

    def __init__(self, real, name, attrs, log):
        self._real, self.attrs = real, dict(attrs)
        log.append((name, self.attrs))

    def __enter__(self):
        self._real.__enter__()
        return self

    def __exit__(self, *exc):
        out = self._real.__exit__(*exc)
        self.ms = self._real.ms
        return out

    def set(self, **attrs):
        self.attrs.update(attrs)
        self._real.set(**attrs)


def test_dispatch_phases_say_what_the_state_did(engine, monkeypatch):
    log, real = [], TRACER.phase
    monkeypatch.setattr(TRACER, "phase",
                        lambda name, **attrs: _Recorded(real(name, **attrs), name, attrs, log))
    resets = engine.stats()["state_resets"]
    engine.generate(PROMPTS[1], max_tokens=6, temperature=0.0)  # 40 tokens: chunks 16, 16, 8
    pre = [a for n, a in log if n == "engine.prefill_dispatch"]
    assert [a["carried"] for a in pre] == [0, 1, 1]
    assert [a["keys"] for a in pre] == [16 * 17 // 2, 16 * 16 + 16 * 17 // 2, 8 * 32 + 8 * 9 // 2]
    dec = [a for n, a in log if n == "engine.decode_dispatch" and "k" in a]
    assert dec and all({"take", "slots", "k", "span", "keys", "rows"} <= set(a) for a in dec)
    assert dec[0]["keys"] == 40
    assert engine.stats()["state_resets"] == resets + 1
    assert "kubedl_tpu_serving_state_resets" in engine.metrics.registry.render()


def test_stats_and_metrics_say_how_many_slabs_the_steps_fetched(engine):
    """After a request ``stats()`` holds the runner's two segment counters
    under its names, and ``/metrics`` renders them. On a CPU ``ssd_step``
    sweeps every row's slab, so the two are equal: the counter says what the
    path that ran did, not what a TPU would have done."""
    before = engine.stats()
    engine.generate(PROMPTS[0], max_tokens=6, temperature=0.0)
    after = engine.stats()
    held = after["slabs_held"] - before.get("slabs_held", 0)
    assert held > 0 and held % engine.max_batch == 0
    assert after["slabs_stepped"] - before.get("slabs_stepped", 0) == held
    text = engine.metrics.registry.render()
    for name in ("kubedl_tpu_serving_slabs_stepped", "kubedl_tpu_serving_slabs_held"):
        (line,) = [ln for ln in text.splitlines() if ln.startswith(name + " ")]
        assert float(line.split()[1]) >= held


def test_live_state_is_counted_while_a_request_runs(engine):
    seen = []
    t = threading.Thread(target=lambda: engine.generate(PROMPTS[3], max_tokens=60, temperature=0.0))
    t.start()
    while t.is_alive():
        st = engine.stats()
        seen.append((st["state_rows"], st["state_bytes"]))
        t.join(timeout=0.01)
    per_row = hy.state_bytes_per_row(CFG)
    assert (1, per_row) in seen and set(seen) <= {(0, 0), (1, per_row)}


@pytest.mark.parametrize("kw, reason", [
    ({"spec_k": 2}, "roll the recurrent state back"),
    ({"role": "prefill"}, "the state would stay behind"),
    ({"role": "decode"}, "the state would stay behind"),
    ({"kv_layout": "contiguous"}, "kv_layout='contiguous'"),
    ({"kv_attention": "blocked"}, "kv_attention='blocked'"),
    ({"quantize": "int8"}, "quantize"),
])
def test_what_a_prefix_of_blocks_cannot_carry_is_refused_at_construction(kw, reason):
    with pytest.raises(ValueError, match=reason) as err:
        make_engine(**kw)
    assert "recurrent state" in str(err.value)


def test_no_prefix_cache_is_built_and_the_log_says_so(caplog):
    with caplog.at_level(logging.INFO, logger="kubedl_tpu.serving"):
        eng = make_engine(prefix_cache_mb=64.0)
    try:
        assert eng._pcache is None and "prefix_cache" not in eng.stats()
        said = [r.getMessage() for r in caplog.records if "no prefix cache" in r.getMessage()]
        assert len(said) == 1 and "recurrent state" in said[0]
        with pytest.raises(ValueError, match="hand-off would leave it behind"):
            eng.prefill_handoff([1, 2, 3], max_tokens=4)
    finally:
        eng.close()


# ---- the decoder is as it was ------------------------------------------------


def test_the_decoder_gets_the_runner_and_the_programs_it_had():
    """``make_runner`` hands a ``llama`` preset the ``ModelRunner`` that
    ``LlamaEngine`` used to build itself, and the ``rows`` the engine now
    passes with a decode segment reach no program: the text a decoder's
    segment lowers to does not know them."""
    from kubedl_tpu.serving.model_runner import HybridRunner, ModelRunner, make_runner

    made = make_runner("tiny", max_batch=2, max_seq=64)
    direct = ModelRunner("tiny", max_batch=2, max_seq=64)
    assert type(made) is ModelRunner and made.state_bytes_per_row == 0
    assert type(make_runner("tiny-hybrid", max_batch=2, max_seq=64)) is HybridRunner
    for runner in (made, direct):
        runner.new_cache(9)
    params = made.build_params("")
    args = (params, made.cache, jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.float32),
            jax.random.PRNGKey(0))
    texts = {r._segment_fn(4, True).lower(*args).as_text() for r in (made, direct)}
    assert len(texts) == 1
    toks, _last, _key = made.decode_segment(4, True, *args[0:1], *args[2:], rows=[0])
    assert toks.shape == (2, 4)
