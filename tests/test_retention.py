"""The attention-free decoder (models/retention.py, ops/power_retention.py)
and its runner behind ``LlamaEngine``: a row that is recurrent state and owns
no K/V block.

Everything runs at the ``tiny-retention`` preset in float32 on the CPU with
seeded weights and the slow gate (``gamma`` in 0.98-0.9995, so that what a
token sees reaches back hundreds of positions), and is held to
``benchmark/reference/retention_ref.py`` (plain float32, the state-free sum)
in LOGITS. Tolerances: the program and the reference compute the same float32
sums in another order (a state of products against a dense matrix of
weights, chunks against one sum), so logits of deviation 1 agree to a few
1e-6; ``TOL`` 1e-4 is thirty times that and a ten-thousandth of a logit's
deviation, and the faults planted below (a carried state dropped at one
chunk boundary, another row's slab read) move logits by 1e-2 and more: the
tolerance is shown to see them.
"""

import dataclasses
import functools
import logging
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import retention_ref
from benchmark.retention_program import LEAVES
from kubedl_tpu.models import retention as rt
from kubedl_tpu.observability.tracing import TRACER
from kubedl_tpu.ops import power_retention as pr
from kubedl_tpu.ops import ssd_scan

CFG = rt.TINY_RETENTION
#: the reference's view of the tiny preset: the published key names
CONFIG = {
    "num_hidden_layers": 2, "hidden_size": 64, "vocab_size": 256, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "rope_scaling": None, "attention_bias": False, "hidden_act": "silu",
    "tie_word_embeddings": False, "use_sliding_window": False,
}
TOL = 1e-4
def ref_tree(params):
    """The program's parameter tree under the reference's leaf names."""
    return {**{n: params[n] for n in ("embed", "lm_head", "final_norm")},
            "layers": {ref: params["layers"][own] for ref, own in LEAVES.items()}}


@pytest.fixture(scope="module")
def params():
    p = rt.retention_init(jax.random.PRNGKey(3), CFG)
    # norms that are not ones, so that leaving one out would show
    for name, key in (("q_norm", 4), ("k_norm", 5)):
        p["layers"][name] = 1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(key), p["layers"][name].shape)
    return p


def reference_logits(params, seq):
    return np.asarray(retention_ref.forward(ref_tree(params), jnp.asarray(seq, jnp.int32), CONFIG))


def fresh_cache(rows=3):
    cache = rt.init_cache(CFG, rows)
    # rows that were used before: a slab that is not zero must not leak
    return {"pos": cache["pos"], "S": cache["S"] + 1.0, "z": cache["z"] + 1.0}


@functools.lru_cache(maxsize=None)
def compiled(bucket, carried, model=rt):
    """One jitted prefill a shape: an un-jitted layer scan compiles anew at
    every call, and some hundreds of them exhaust XLA:CPU's JIT."""
    def run(params, cache, toks, length, row, start):
        kw = {"starts": start} if carried else {}
        with jax.default_matmul_precision("highest"):
            return model.prefill(params, cache, toks, length, CFG, row, **kw)
    return jax.jit(run)


def run_prefill(params, cache, row, tokens, start=None, bucket=None):
    bucket = bucket or max(16, 1 << (len(tokens) - 1).bit_length())
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(tokens)] = tokens
    return compiled(bucket, start is not None)(
        params, cache, jnp.asarray(toks), jnp.asarray([len(tokens)], jnp.int32),
        jnp.asarray([row], jnp.int32), jnp.asarray([start or 0], jnp.int32))


@functools.lru_cache(maxsize=None)
def compiled_step():
    def run(params, cache, tok, live):
        with jax.default_matmul_precision("highest"):
            return rt.decode_step(params, cache, tok, live, CFG)
    return jax.jit(run)


# ---- the operation ------------------------------------------------------------


@pytest.mark.parametrize("hd", [2, 16, 128])
def test_the_feature_map_squares_the_dot_product(hd):
    a, b = jax.random.normal(jax.random.PRNGKey(hd), (2, 7, hd))
    fa, fb = pr.phi(a), pr.phi(b)
    assert fa.shape == (7, hd // 2 + 1, hd) and pr.features(hd) == (hd // 2 + 1) * hd
    # float32 sums of hd (hd + 1) / 2 products of size about 1
    np.testing.assert_allclose(jnp.sum(fa * fb, axis=(-2, -1)), jnp.sum(a * b, axis=-1) ** 2,
                               rtol=2e-5, atol=2e-5 * hd)
    with pytest.raises(ValueError, match="odd"):
        pr.diagonals(15)


def mixer_inputs(T, lengths, seed=0, B=2, H=4, KV=2, hd=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    real = jnp.arange(T)[None, :] < jnp.asarray(lengths)[:, None]
    log_g = jnp.log(jax.random.uniform(k[3], (B, T, KV), jnp.float32, 0.9, 0.9995))
    return (jax.random.normal(k[0], (B, T, H, hd)),
            jnp.where(real[..., None, None], jax.random.normal(k[1], (B, T, KV, hd)), 0.0),
            jax.random.normal(k[2], (B, T, KV, hd)), jnp.where(real[..., None], log_g, 0.0))


def empty_state(B=2, KV=2, hd=16):
    ND = pr.diagonals(hd)
    return jnp.zeros((B, KV, ND, hd, hd)), jnp.zeros((B, KV, ND, hd))


@pytest.mark.parametrize("T, chunk, lengths", [
    (48, 1, (48, 48)),    # a chunk a token: the recurrence itself
    (48, 16, (48, 48)),   # whole chunks
    (48, 16, (37, 5)),    # ragged: a length inside a chunk, one inside the first
    (48, 16, (32, 0)),    # a length on a chunk's edge, and a row with no token
    (48, 48, (48, 1)),    # one chunk of everything
    (40, 16, (40, 23)),   # no whole number of chunks: the tail is padded
    (16, 256, (16, 9)),   # a bucket shorter than the chunk: one chunk
])
def test_the_chunked_form_equals_the_state_free_form(T, chunk, lengths):
    q, k, v, log_g = mixer_inputs(T, lengths)
    S0, z0 = empty_state()
    run = jax.jit(pr.retention_chunked, static_argnums=(6,))
    with jax.default_matmul_precision("highest"):
        y, S, z = run(q, k, v, log_g, S0, z0, chunk)
        y_skip, S_skip, _ = run(q, k, v, log_g, S0, z0, chunk, jnp.asarray(True))
    for b, n in enumerate(lengths):
        want = pr.retention_reference(q[b, :n], k[b, :n], v[b, :n], log_g[b, :n])
        # float32 sums of up to 48 weights of size about 16^2, over their sum
        np.testing.assert_allclose(y[b, :n], want, atol=2e-5)
        # a padded tail leaves the state where the last real token left it
        _, upto, _ = run(q[b:b + 1, :max(n, 1)], k[b:b + 1, :max(n, 1)] * (n > 0),
                         v[b:b + 1, :max(n, 1)], log_g[b:b + 1, :max(n, 1)] * (n > 0),
                         S0[:1], z0[:1], chunk)
        np.testing.assert_allclose(S[b], upto[0], rtol=1e-5, atol=1e-4)
    # a first chunk that takes no product with a zero state gives what one that does gives
    np.testing.assert_allclose(y_skip, y, atol=1e-6)
    np.testing.assert_allclose(S_skip, S, atol=1e-6)


def test_the_one_step_form_goes_on_from_a_chunks_state():
    """Twenty positions by chunks of 8, then 28 steps of the recurrence from
    the state they left: every output is the state-free sum's."""
    T, at = 48, 20
    q, k, v, log_g = mixer_inputs(T, (T, T), seed=1)
    with jax.default_matmul_precision("highest"):
        y0, S, z = pr.retention_chunked(q[:, :at], k[:, :at], v[:, :at], log_g[:, :at],
                                        *empty_state(), 8)
        ys = [y0]
        step = jax.jit(pr.retention_step)
        for t in range(at, T):
            S, z, y = step(q[:, t], k[:, t], v[:, t], log_g[:, t], S, z)
            ys.append(y[:, None])
        # and the chunked form from the same state, in one more call
        y1, S1, _ = pr.retention_chunked(q[:, at:], k[:, at:], v[:, at:], log_g[:, at:],
                                         *pr.retention_chunked(q[:, :at], k[:, :at], v[:, :at],
                                                               log_g[:, :at], *empty_state(), 8)[1:], 8)
    got = jnp.concatenate(ys, axis=1)
    for b in range(2):
        want = pr.retention_reference(q[b], k[b], v[b], log_g[b])
        np.testing.assert_allclose(got[b], want, atol=2e-5)
        np.testing.assert_allclose(y1[b], want[at:], atol=2e-5)
    np.testing.assert_allclose(S1, S, rtol=1e-5, atol=1e-4)


# ---- the one-step kernel ------------------------------------------------------


def forced_kernel(mp):
    """What a TPU process observes, steered for a CPU: the backend's name,
    the tiling predicate (the tiny preset's head is 16), and the interpreter
    in the compiled kernel's place. Holds for as long as ``mp`` does:
    programs trace on first use."""
    mp.setattr(jax, "default_backend", lambda: "tpu")
    mp.setattr(pr, "step_kernel_fits", lambda S: True)
    mp.setattr(pr, "retention_step_rows",
               functools.partial(pr.retention_step_rows, interpret=True))


SCHEDULED = {"none": (), "one": (3,), "three": (0, 2, 4), "every": (0, 1, 2, 3, 4)}


@pytest.mark.parametrize("which", sorted(SCHEDULED))
def test_the_step_kernel_advances_the_listed_rows_and_touches_no_other(which, monkeypatch):
    """``retention_step_rows`` through the interpreter against
    ``retention_step`` on the tiny preset's shapes, five rows: ``y`` and the
    listed rows' slabs of the layer agree to float32 rounding (the state's
    arithmetic is the same, the read-out sums in its own order), every other
    slab is bit for bit what it was, and a decode segment's counters say how
    many slabs its steps fetched."""
    B, L, H, KV, hd = 5, CFG.n_layers, CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    ND = pr.diagonals(hd)
    k = jax.random.split(jax.random.PRNGKey(40), 6)
    q, kk, v = (jax.random.normal(k[i], (B, n, hd)) for i, n in ((0, H), (1, KV), (2, KV)))
    log_g = jnp.log(jax.random.uniform(k[3], (B, KV), jnp.float32, 0.9, 0.9995))
    S = jax.random.normal(k[4], (B, L, KV, ND, hd, hd))
    z = jnp.abs(jax.random.normal(k[5], (B, L, KV, ND, hd))) * 50.0
    live = np.zeros((B,), bool)
    live[list(SCHEDULED[which])] = True
    rows, count = ssd_scan.scheduled_rows(jnp.asarray(live))
    for layer in (0, L - 1):
        got_S, got_z, got_y = pr.retention_step_rows(
            q, kk, v, log_g, S, z, jnp.int32(layer), rows, count, interpret=True)
        want_S, want_z, want_y = pr.retention_step(q, kk, v, log_g, S[:, layer], z[:, layer])
        np.testing.assert_allclose(np.asarray(got_y)[live], np.asarray(want_y)[live],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_S)[live, layer], np.asarray(want_S)[live],
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_z)[live, layer], np.asarray(want_z)[live],
                                   rtol=1e-6, atol=1e-6)
        untouched = np.ones((B, L), bool)
        untouched[live, layer] = False
        assert (np.asarray(got_S)[untouched] == np.asarray(S)[untouched]).all()
        assert (np.asarray(got_z)[untouched] == np.asarray(z)[untouched]).all()
        assert not np.asarray(got_y)[~live].any()
    # the same set through two steps of the whole model: the counters, and
    # (whatever the set) no slab of a row that was not scheduled moves
    forced_kernel(monkeypatch)
    params = rt.retention_init(jax.random.PRNGKey(3), CFG)
    cache = fresh_cache(rows=B)
    cache["pos"] = jnp.full((B,), 5, jnp.int32)
    _toks, _last, _key, after, counters = jax.jit(
        lambda p, c, live: rt.decode_segment(
            p, c, jnp.ones((B, 1), jnp.int32), jnp.zeros((B,), jnp.float32),
            jax.random.PRNGKey(0), live, CFG, n_steps=2, greedy=True)
    )(params, cache, jnp.asarray(live))
    assert {n: int(c) for n, c in counters.items()} == {
        "slabs_stepped": 2 * int(live.sum()), "slabs_held": 2 * B}
    for leaf in ("S", "z"):
        assert (np.asarray(after[leaf])[~live] == np.asarray(cache[leaf])[~live]).all()
        if live.any():
            assert (np.asarray(after[leaf])[live] != np.asarray(cache[leaf])[live]).any()


@pytest.mark.parametrize("shape, dtype, fits", [
    ((16, 8, 8, 65, 128, 128), jnp.float32, True),    # the cell's state
    ((3, 2, 2, 9, 16, 16), jnp.float32, False),       # the tiny preset's: a head is no whole lane tile
    ((16, 8, 8, 65, 128, 128), jnp.bfloat16, False),  # another type
    ((16, 8, 8, 64, 128, 128), jnp.float32, False),   # not this feature map's diagonals
])
def test_the_step_kernel_takes_a_float32_state_of_whole_tiles(shape, dtype, fits):
    S = jax.ShapeDtypeStruct(shape, dtype)
    assert pr.step_kernel_fits(S) is fits
    # and no CPU process picks it, whatever the state
    assert not rt.steps_listed_rows({"S": S})


def test_a_runner_with_the_kernel_serves_the_sweeps_tokens(params):
    """``RetentionRunner.decode_segment`` for 4 steps, two of three rows
    scheduled, with the kernel forced through the interpreter and as a CPU
    runs it (``retention_step`` over every row): the same greedy tokens,
    final slabs that agree to float32 rounding, the row that sat out bit for
    bit where it was, and counters that say what each path fetched."""
    from kubedl_tpu.serving.model_runner import RetentionRunner

    def run(force):
        with pytest.MonkeyPatch.context() as mp:
            if force:
                forced_kernel(mp)
            runner = RetentionRunner("tiny-retention", max_batch=3, max_seq=64, kv_block_size=8)
            runner.new_cache()
            assert set(runner.cache) == {"pos", "S", "z"} and runner.block_bytes == 0
            for row, n in ((0, 9), (1, 5), (2, 12)):
                toks = np.zeros((1, 16), np.int32)
                toks[0, :n] = np.arange(1, n + 1) * (row + 2) % CFG.vocab_size
                runner.prefill(params, jnp.asarray(toks), jnp.asarray([n], jnp.int32),
                               rows=jnp.asarray([row], jnp.int32))
            before = {n: np.asarray(runner.cache[n]) for n in ("S", "z")}
            toks, _last, _key = runner.decode_segment(
                4, True, params, jnp.asarray([[7], [8], [9]], jnp.int32),
                jnp.zeros((3,), jnp.float32), jax.random.PRNGKey(0), rows=[0, 2])
            return (np.asarray(toks), before, {n: np.asarray(runner.cache[n]) for n in ("S", "z")},
                    {n: int(c) for n, c in runner.segment_counters.items()})

    toks_k, before_k, after_k, count_k = run(True)
    toks_s, before_s, after_s, count_s = run(False)
    assert (toks_k[[0, 2]] == toks_s[[0, 2]]).all()
    assert count_k == {"slabs_stepped": 8, "slabs_held": 12}
    assert count_s == {"slabs_stepped": 12, "slabs_held": 12}
    for leaf in ("S", "z"):
        # four steps of the whole model, the read-out summed in another order
        np.testing.assert_allclose(after_k[leaf], after_s[leaf], rtol=1e-4, atol=1e-4)
        for before, after in ((before_k, after_k), (before_s, after_s)):
            assert (after[leaf][1] == before[leaf][1]).all()
            assert (after[leaf][[0, 2]] != before[leaf][[0, 2]]).any()


# ---- the model ----------------------------------------------------------------


SEQ = np.random.default_rng(11).integers(0, CFG.vocab_size, 120).tolist()


def test_prefill_then_decode_through_the_slabs_is_the_references_forward(params):
    """A 56-token prompt into row 1 of a used cache in one program, then 64
    tokens a step at a time through the slabs: the logits of every position
    from the prompt's last are the reference's full forward pass."""
    P, N = 56, 64
    ref = reference_logits(params, SEQ[:P + N])
    logits, cache = run_prefill(params, fresh_cache(), 1, SEQ[:P])
    got = [np.asarray(logits[0])]
    live = jnp.asarray([False, True, False])
    before = {n: np.asarray(cache[n]) for n in ("S", "z")}
    for t in range(P, P + N):
        tok = np.zeros((3, 1), np.int32)
        tok[1, 0] = SEQ[t]
        logits, cache = compiled_step()(params, cache, jnp.asarray(tok), live)
        got.append(np.asarray(logits[1]))
    np.testing.assert_allclose(np.stack(got), ref[P - 1:], atol=TOL)
    assert int(cache["pos"][1]) == P + N
    for leaf in ("S", "z"):  # the rows that sat out keep their slabs bit for bit
        assert (np.asarray(cache[leaf])[[0, 2]] == before[leaf][[0, 2]]).all()


@pytest.mark.parametrize("cuts", [(), (32,), (8, 16, 24, 32, 40, 48)], ids=["one", "two", "seven"])
def test_prefill_in_chunks_is_prefill_in_one(params, cuts):
    """The 56-token prompt in one, two and seven programs (the later ones
    carry the slab the earlier left): the last logits are the reference's."""
    ref = reference_logits(params, SEQ[:56])[-1]
    cache = fresh_cache()
    edges = [0, *cuts, 56]
    for lo, hi in zip(edges[:-1], edges[1:]):
        logits, cache = run_prefill(params, cache, 2, SEQ[lo:hi], start=lo)
    np.testing.assert_allclose(logits[0], ref, atol=TOL)


def test_a_wider_bucket_and_a_row_without_tokens_change_nothing(params):
    ref = reference_logits(params, SEQ[:21])[-1]
    logits, cache = run_prefill(params, fresh_cache(), 0, SEQ[:21], bucket=64)
    np.testing.assert_allclose(logits[0], ref, atol=TOL)
    # a program whose row has no token leaves that row's slab and position alone
    toks = jnp.zeros((1, 16), jnp.int32)
    _, after = compiled(16, True)(params, cache, toks, jnp.asarray([0], jnp.int32),
                                  jnp.asarray([0], jnp.int32), jnp.asarray([0], jnp.int32))
    for leaf in ("S", "z", "pos"):
        assert (np.asarray(after[leaf]) == np.asarray(cache[leaf])).all()


@pytest.mark.parametrize("fault", ["state dropped at a chunk boundary", "another row's slab read"])
def test_a_planted_fault_moves_the_logits_past_the_tolerance(params, fault):
    """The tolerance sees what it is there to see: with the slow gate a token
    at position 56 still weighs the first chunk's keys, so a carried state
    zeroed between two chunks, or row 1's slab read where row 0's should be,
    moves the last logits by a hundred tolerances and more."""
    ref = reference_logits(params, SEQ[:56])[-1]
    cache = fresh_cache()
    _, cache = run_prefill(params, cache, 0, SEQ[:32], start=0)
    _, cache = run_prefill(params, cache, 1, SEQ[60:92], start=0)
    if fault.startswith("state dropped"):
        cache = {**cache, "S": cache["S"].at[0].set(0.0), "z": cache["z"].at[0].set(0.0)}
    else:
        cache = {**cache, "S": cache["S"].at[0].set(cache["S"][1]),
                 "z": cache["z"].at[0].set(cache["z"][1])}
    logits, _ = run_prefill(params, cache, 0, SEQ[32:56], start=32)
    assert np.abs(np.asarray(logits[0]) - ref).max() > 100 * TOL


def test_sizes_are_the_published_models():
    cfg = rt.BRUMBY_14B_BASE
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 8 * 5120 + 8 + 2 * 128 + 3 * 5120 * 17408 + 2 * 5120
    assert layer == 330_352_904  # the issue's 330.35M a layer
    assert cfg.num_params() == 40 * layer + 2 * 151936 * 5120 + 5120
    assert abs(cfg.num_params() - 14.77e9) < 0.01e9
    # a row's state: 8 key groups x 65 x 128 features x (128 values + 1) in float32, a layer
    assert rt.state_bytes_per_row(cfg) == 40 * 8 * 8320 * 129 * 4
    assert abs(rt.state_bytes_per_row(dataclasses.replace(cfg, n_layers=8)) - 275e6) < 1e6
    cache = jax.eval_shape(lambda: rt.init_cache(dataclasses.replace(cfg, n_layers=8), 16))
    assert set(cache) == {"pos", "S", "z"}  # no pool, no block table
    assert cache["S"].shape == (16, 8, 8, 65, 128, 128) and cache["z"].shape == (16, 8, 8, 65, 128)
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in cache.values())
    assert held == 16 * rt.state_bytes_per_row(dataclasses.replace(cfg, n_layers=8)) + 16 * 4
    # the gate's bias puts gamma where a trained layer's lies
    g = jax.nn.sigmoid(rt.gate_bias(jax.random.PRNGKey(0), (4096,), cfg.gamma_range))
    assert 0.98 <= float(g.min()) < 0.982 and 0.9993 < float(g.max()) <= 0.9995


# ---- the engine ---------------------------------------------------------------


def make_engine(**kw):
    from kubedl_tpu.serving.server import LlamaEngine

    kw = {"max_batch": 3, "max_seq": 128, "kv_block_size": 8, "prefill_chunk_tokens": 16, **kw}
    return LlamaEngine(preset="tiny-retention", **kw)


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


def test_the_engine_builds_no_pool_for_a_row_without_blocks(engine):
    from kubedl_tpu.serving.kv_blocks import NoBlocks
    from kubedl_tpu.serving.model_runner import RetentionRunner

    assert type(engine._runner) is RetentionRunner and engine._runner.block_bytes == 0
    assert engine.kv_blocks == 0 and type(engine._alloc) is NoBlocks
    assert engine._bt_host.shape == (3, 0) and set(engine._runner.cache) == {"pos", "S", "z"}
    blocks = engine.stats()["kv_blocks"]
    assert (blocks["total"], blocks["free"], blocks["used"]) == (0, 0, 0) and blocks["admission_open"]
    assert engine._pcache is None  # a prefix would be state, not blocks
    # an explicit pool size has nothing to size
    eng = make_engine(kv_blocks=50)
    try:
        assert eng.kv_blocks == 0
    finally:
        eng.close()


def test_engine_serves_concurrent_requests_as_the_reference_would(engine):
    """Five requests on three rows, prompts of one to four chunks, so two
    queue and are admitted as rows free: every served token is the
    reference's best at its position, by its logits (a near-tie may go either
    way, so the served token's logit is held to the best within the
    tolerance, not the token to the token). Then no row holds live state,
    nothing was preempted, and no block was ever handed out."""
    served = serve_together(engine, PROMPTS)
    for prompt, tokens in zip(PROMPTS, served):
        assert len(tokens) == 12
        ref = reference_logits(engine.params, prompt + tokens[:-1])[len(prompt) - 1:]
        gaps = ref.max(axis=-1) - ref[np.arange(12), tokens]
        assert gaps.max() <= TOL, gaps
    st = engine.stats()
    assert st["state_rows"] == 0 and st["state_bytes"] == 0 and st["active_slots"] == 0
    assert st["state_resets"] >= len(PROMPTS) and st["kv_preemptions"] == 0
    assert st["kv_blocks"]["allocs"] == 0 and st["kv_blocks"]["used"] == 0
    assert st["queue_wait_ms_p99"] > 0  # two of the five waited for a row


def test_a_reused_row_gives_what_it_gives_alone(engine):
    """Rows are reused as requests finish: what a request gets from a row
    another request left (its slab still full of the other's state, freed
    by `_free_row_locked` without a device write) is what it gets alone,
    because its first chunk starts from a zero slab."""
    together = serve_together(engine, PROMPTS)
    for prompt, tokens in zip(PROMPTS, together):
        assert engine.generate(prompt, max_tokens=12, temperature=0.0)["token_ids"] == tokens
    assert np.asarray(engine._runner.cache["S"]).any()  # the slabs are not wiped, only re-begun


def test_more_requests_than_rows_queue_and_none_is_preempted(engine):
    before = engine.stats()
    served = serve_together(engine, [PROMPTS[i % 5] for i in range(9)], max_tokens=6)
    assert len(served) == 9
    after = engine.stats()
    assert after["requests"] - before["requests"] == 9
    assert after["kv_preemptions"] == 0 and after["shed"] == before["shed"]
    assert after["state_rows"] == 0 and after["queued"] == 0


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
    assert [(a["tokens"], a["slots"], a["bucket"], a["base"]) for a in pre] == [
        (16, 1, 16, 0), (16, 1, 16, 16), (8, 1, 16, 32)]
    assert all(a["span"] == 0 for a in pre)  # no program gathers a view
    dec = [a for n, a in log if n == "engine.decode_dispatch" and "k" in a]
    assert dec and all({"take", "slots", "k", "rows", "backlog"} <= set(a) for a in dec)
    # no key is read: ``read`` and ``span`` are 0, and ``keys`` is the
    # positions the scheduled rows stand at, none of which a step fetches
    assert all(a["read"] == 0 and a["span"] == 0 for a in dec) and dec[0]["keys"] == 40
    st = engine.stats()
    assert st["state_resets"] == resets + 1 and st["view_keys"] == 0
    assert "kubedl_tpu_serving_state_resets" in engine.metrics.registry.render()


def test_stats_and_metrics_say_how_many_slabs_the_steps_fetched(engine):
    before = engine.stats()
    engine.generate(PROMPTS[0], max_tokens=6, temperature=0.0)
    after = engine.stats()
    held = after["slabs_held"] - before.get("slabs_held", 0)
    assert held > 0 and held % engine.max_batch == 0
    # on a CPU ``retention_step`` sweeps every row's slab: the two are equal
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
        seen.append((st["state_rows"], st["state_bytes"], st["kv_blocks"]["used"]))
        t.join(timeout=0.01)
    per_row = rt.state_bytes_per_row(CFG)
    assert (1, per_row, 0) in seen and set(seen) <= {(0, 0, 0), (1, per_row, 0)}


def test_a_position_is_bounded_by_max_seq_and_by_nothing_else():
    """``max_seq`` bounds positions only: a prompt and its tokens fill a row
    to the last position with no block behind any of them."""
    eng = make_engine(max_seq=64, max_batch=2)
    try:
        out = eng.generate(PROMPTS[1], max_tokens=100, temperature=0.0)
        assert len(PROMPTS[1]) + len(out["token_ids"]) == 63
        ref = reference_logits(eng.params, PROMPTS[1] + out["token_ids"][:-1])[len(PROMPTS[1]) - 1:]
        gaps = ref.max(axis=-1) - ref[np.arange(len(out["token_ids"])), out["token_ids"]]
        assert gaps.max() <= TOL
    finally:
        eng.close()


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
    assert "holds recurrent state and no K/V block" in str(err.value)


def test_no_prefix_cache_is_built_and_the_log_says_so(caplog):
    with caplog.at_level(logging.INFO, logger="kubedl_tpu.serving"):
        eng = make_engine(prefix_cache_mb=64.0)
    try:
        assert eng._pcache is None and "prefix_cache" not in eng.stats()
        said = [r.getMessage() for r in caplog.records if "no prefix cache" in r.getMessage()]
        assert len(said) == 1 and "recurrent state" in said[0]
        with pytest.raises(ValueError, match="and no K/V block: a block hand-off would leave it behind"):
            eng.prefill_handoff([1, 2, 3], max_tokens=4)
    finally:
        eng.close()


def test_make_runner_picks_by_the_presets_config():
    from kubedl_tpu.serving.model_runner import (
        HybridRunner, ModelRunner, RetentionRunner, make_runner)

    assert type(make_runner("tiny-retention", max_batch=2, max_seq=64)) is RetentionRunner
    assert type(make_runner("tiny-hybrid", max_batch=2, max_seq=64)) is HybridRunner
    assert type(make_runner("tiny", max_batch=2, max_seq=64)) is ModelRunner
    with pytest.raises(ValueError, match="holds recurrent state beside its K/V blocks"):
        make_runner("tiny-hybrid", max_batch=2, quantize="int8")
