"""The gathered view stops at the longest live row (docs/serving.md "The
view's span").

Model level: the paged decode segment and ``paged_prefill_from``, given the
ladder of spans and a ``live_to``, attend over the smallest span that holds
it and give every row that stands below it the full view's logits and pool,
to the bit; a row parked at or beyond the span keeps its blocks and the
trash block takes its write; without spans they lower to the text they had
before they took any. Runner level: the ladder, which span a dispatch's
``live_to`` picks, and that a runner with one span passes no ``live_to``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubedl_tpu.models import llama
from kubedl_tpu.serving.model_runner import ModelRunner

TRASH_BLOCK = 0
B, MAX_SEQ, BS = 4, 256, 16
MB = MAX_SEQ // BS
SPANS = (64, 128, 256)
#: where the four rows stand: three inside the smallest span, row 3 parked
#: beyond every span but the last
HISTORY = (5, 40, 17, 200)


@pytest.fixture(scope="module")
def model():
    cfg = llama.preset("tiny")
    return cfg, llama.llama_init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def cache0(model):
    """A paged cache whose row b owns blocks ``[1 + b*MB, 1 + (b+1)*MB)`` and
    holds ``HISTORY[b]`` tokens."""
    cfg, params = model
    cache = llama.init_paged_cache(cfg, B, MAX_SEQ, 1 + B * MB, BS)
    cache["bt"] = jnp.arange(1, 1 + B * MB, dtype=jnp.int32).reshape(B, MB)
    rng = np.random.default_rng(0)
    toks = rng.integers(1, cfg.vocab_size, (B, MAX_SEQ)).astype(np.int32)
    _, cache = llama.paged_prefill_batched(
        params, cache, jnp.asarray(toks), jnp.asarray(HISTORY, jnp.int32), cfg)
    return jax.tree_util.tree_map(np.asarray, cache)


def _fresh(cache0):
    return jax.tree_util.tree_map(jnp.asarray, cache0)


def _blocks_of(cache, row):
    own = slice(1 + row * MB, 1 + (row + 1) * MB)
    return np.asarray(cache["k"][:, own]), np.asarray(cache["v"][:, own])


def _same_bits(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


# ---- the decode segment ----------------------------------------------------


def _live(span):
    """A ``live_to`` that picks ``span``: its upper edge."""
    return jnp.int32(span)


@pytest.mark.parametrize("span", SPANS)
def test_decode_segment_at_a_span_is_the_full_view_on_scheduled_rows(model, cache0, span):
    """Rows 0–2 stand below every span: their tokens, their step logits and
    every block they own equal the full view's to the bit."""
    cfg, params = model
    k = 8
    tokens = jnp.asarray([[3], [1], [4], [1]], jnp.int32)
    temps, key = jnp.zeros((B,), jnp.float32), jax.random.PRNGKey(1)
    want_toks, want_last, _, want = llama.paged_decode_segment(
        params, _fresh(cache0), tokens, temps, key, cfg, n_steps=k, greedy=True)
    got_toks, got_last, _, got = llama.paged_decode_segment(
        params, _fresh(cache0), tokens, temps, key, cfg, n_steps=k, greedy=True,
        spans=SPANS, live_to=_live(span))
    sched = np.array([0, 1, 2] + ([3] if span == MAX_SEQ else []))
    assert _same_bits(got_toks[sched], want_toks[sched])
    assert _same_bits(got_last[sched], want_last[sched])
    for row in sched:
        for a, b in zip(_blocks_of(got, row), _blocks_of(want, row)):
            assert np.array_equal(a, b), row
    # positions advance by the row's real length, whatever the view's
    assert _same_bits(got["pos"], want["pos"])
    # one step's logits too, not only their argmax
    want_lg, _ = llama.paged_decode_step_batched(params, _fresh(cache0), tokens, cfg)
    got_lg, _ = llama.paged_decode_step_batched(
        params, _fresh(cache0), tokens, cfg, spans=SPANS, live_to=_live(span))
    assert _same_bits(got_lg[sched], want_lg[sched])


@pytest.mark.parametrize("span", SPANS[:-1])
def test_a_row_parked_beyond_the_span_keeps_its_blocks(model, cache0, span):
    """Row 3 stands at 200, beyond the span the dispatch's ``live_to`` picks:
    its write goes to the trash block, and every block the row owns is bit
    for bit what it was. (The full view writes the row's stale token into
    its own block, as it always did.)"""
    cfg, params = model
    tokens = jnp.asarray([[3], [1], [4], [1]], jnp.int32)
    before = _blocks_of(cache0, 3)
    trash_before = np.asarray(cache0["k"][:, TRASH_BLOCK]).copy()
    _, got = llama.paged_decode_step_batched(
        params, _fresh(cache0), tokens, cfg, spans=SPANS, live_to=_live(span))
    for a, b in zip(_blocks_of(got, 3), before):
        assert np.array_equal(a, b)
    off = HISTORY[3] % BS
    assert not np.array_equal(np.asarray(got["k"][:, TRASH_BLOCK, off]),
                              trash_before[:, off])
    _, full = llama.paged_decode_step_batched(params, _fresh(cache0), tokens, cfg)
    assert not np.array_equal(_blocks_of(full, 3)[0], before[0])


# ---- the suffix prefill ----------------------------------------------------


#: row, start, take, bucket
CHUNKS = [(0, 5, 11, 16), (1, 40, 24, 32), (2, 17, 30, 32), (1, 40, 20, 64)]


@pytest.mark.parametrize("span,row,start,take,bucket", [
    # the runner never picks a span shorter than start + bucket
    (span, *chunk) for span in SPANS for chunk in CHUNKS
    if chunk[1] + chunk[3] <= span])
def test_suffix_prefill_at_a_span_is_the_full_view(model, cache0, span, row, start, take, bucket):
    """A one-row chunk whose ``start + bucket`` the span holds: logits, the
    row's blocks and every other row's, to the bit."""
    cfg, params = model
    rng = np.random.default_rng(start)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :take] = rng.integers(1, cfg.vocab_size, take)
    args = (jnp.asarray(toks), jnp.asarray([take], jnp.int32),
            jnp.asarray([start], jnp.int32), cfg)
    rows = jnp.asarray([row], jnp.int32)
    want_lg, want = llama.paged_prefill_from(params, _fresh(cache0), *args, rows=rows)
    got_lg, got = llama.paged_prefill_from(
        params, _fresh(cache0), *args, rows=rows, spans=SPANS,
        live_to=jnp.int32(max(start + bucket, span // 2 + 1)))
    assert _same_bits(got_lg, want_lg)
    for r in range(B):
        for a, b in zip(_blocks_of(got, r), _blocks_of(want, r)):
            assert np.array_equal(a, b), r
    assert _same_bits(got["pos"], want["pos"])


def test_spans_are_whole_blocks_up_to_the_table_on_the_gathered_write_path(model, cache0):
    cfg, params = model
    tokens, live = jnp.zeros((B, 1), jnp.int32), jnp.int32(9)
    for bad in ((24, MAX_SEQ), (64, 128), (128, 64, MAX_SEQ), (0, MAX_SEQ)):
        with pytest.raises(ValueError, match="spans"):
            llama.paged_decode_step_batched(
                params, _fresh(cache0), tokens, cfg, spans=bad, live_to=live)
    with pytest.raises(ValueError, match="live_to"):
        llama.paged_decode_step_batched(
            params, _fresh(cache0), tokens, cfg, spans=SPANS)
    with pytest.raises(ValueError, match="gather"):
        llama.paged_decode_step_batched(
            params, _fresh(cache0), tokens, cfg, kv_attention="blocked",
            spans=SPANS, live_to=live)
    with pytest.raises(ValueError, match="self_contained"):
        llama._paged_suffix_forward(
            params, _fresh(cache0), jnp.zeros((B, 4), jnp.int32),
            jnp.ones((B,), jnp.int32), jnp.zeros((B,), jnp.int32), cfg,
            self_contained=True, spans=SPANS, live_to=live)


@pytest.mark.parametrize("live_to,want", [
    (1, 0), (64, 0), (65, 1), (128, 1), (129, 2), (256, 2), (999, 2)])
def test_the_program_takes_the_branch_the_runner_names(live_to, want):
    """``llama._span_index`` on the device and ``ModelRunner.span_for`` on
    the host are one rule: the smallest span that holds ``live_to``."""
    assert int(llama._span_index(SPANS, jnp.int32(live_to))) == want


# ---- span=None is the program that was -------------------------------------


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _text(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


@pytest.mark.parametrize("entry", [
    "decode_segment", "prefill_from", "verify", "verify_multi", "verify_tree"])
def test_span_none_lowers_to_the_text_without_a_span(model, cache0, entry):
    """Every caller that passes no spans (the three verifies, which share the
    suffix forward; an engine with one span) lowers to the same text as a
    call that names ``spans=None``: no conditional, the whole table's view.
    Spans change that text only for the two programs that take them."""
    cfg, params = model
    p, c = _shapes(params), _shapes(cache0)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    if entry == "decode_segment":
        args = (p, c, i32(B, 1), jax.ShapeDtypeStruct((B,), jnp.float32),
                _shapes(jax.random.PRNGKey(0)))
        fn = functools.partial(llama.paged_decode_segment, cfg=cfg, n_steps=4, greedy=True)
    elif entry == "prefill_from":
        args = (p, c, i32(1, 16), i32(1), i32(1))
        fn = functools.partial(llama.paged_prefill_from, cfg=cfg, rows=jnp.asarray([2]))
    elif entry == "verify":
        args = (p, c, i32(B, 5), i32(B), i32(B))
        fn = functools.partial(llama.paged_verify, cfg=cfg)
    elif entry == "verify_multi":
        args = (p, c, i32(B, 2, 5), i32(B), i32(B))
        fn = functools.partial(llama.paged_verify_multi, cfg=cfg)
    else:
        args = (p, c, i32(B, 9), i32(B, 9),
                jax.ShapeDtypeStruct((B, 9, 9), jnp.bool_), i32(B), i32(B))
        fn = functools.partial(llama.paged_verify_tree, cfg=cfg)
    plain = _text(lambda *a: fn(*a), *args)
    kv = f"x{MAX_SEQ}x{cfg.n_kv_heads}x{cfg.head_dim}x"
    assert kv in plain and f"x64x{cfg.n_kv_heads}x{cfg.head_dim}x" not in plain
    assert "stablehlo.case" not in plain
    if entry in ("decode_segment", "prefill_from"):
        assert _text(lambda *a: fn(*a, spans=None, live_to=None), *args) == plain
        spanned = _text(lambda *a: fn(*a[:-1], spans=SPANS, live_to=a[-1]),
                        *args, i32())
        assert "stablehlo.case" in spanned
        assert f"x64x{cfg.n_kv_heads}x{cfg.head_dim}x" in spanned
    else:
        # the verifies take no spans at all
        with pytest.raises(TypeError):
            fn(*args, spans=SPANS, live_to=jnp.int32(9))


# ---- the ladder and the choice ---------------------------------------------


@pytest.mark.parametrize("max_seq,block,want", [
    (512, 16, (512,)),
    (1024, 16, (1024,)),
    (4096, 16, (1024, 2048, 4096)),
    (3008, 16, (1024, 2048, 3008)),  # no power of two: max_seq is the last
    (2048, 16, (1024, 2048)),
    (16, 16, (16,)),
])
def test_span_ladder(max_seq, block, want):
    assert ModelRunner.span_ladder(max_seq, block) == want


def test_ladder_keeps_whole_blocks_only(monkeypatch):
    monkeypatch.setattr(ModelRunner, "SPAN_FLOOR", 8)
    assert ModelRunner.span_ladder(100, 16) == (16, 32, 64, 100)


@pytest.fixture()
def runner(monkeypatch):
    monkeypatch.setattr(ModelRunner, "SPAN_FLOOR", 64)
    return ModelRunner("tiny", max_batch=B, max_seq=MAX_SEQ)


@pytest.mark.parametrize("live_to,want", [
    (1, 64), (63, 64), (64, 64), (65, 128), (128, 128), (129, 256),
    (256, 256), (10_000, 256), (None, 256)])
def test_live_to_on_a_spans_edge_picks_that_span(runner, live_to, want):
    assert runner.spans == SPANS
    assert runner.span_for(live_to) == want
    # what the program is handed: live_to itself, the whole table for None
    (arg,) = runner._live_to(live_to)
    assert arg.dtype == np.int32 and arg == (MAX_SEQ if live_to is None else live_to)


@pytest.mark.parametrize("kw", [
    {"paged": False}, {"kv_attention": "blocked"}, {"max_seq": 48}])
def test_one_span_where_there_is_no_gathered_view_to_cut(monkeypatch, kw):
    """The contiguous cache, the blocked kernel (it walks the table and
    gathers no view) and a short ``max_seq`` have the one span they had."""
    monkeypatch.setattr(ModelRunner, "SPAN_FLOOR", 16)
    r = ModelRunner("tiny", **{"max_batch": 2, "max_seq": MAX_SEQ, **kw})
    if "max_seq" in kw:
        assert r.spans == (16, 32, 48)
    else:
        assert r.spans == (MAX_SEQ,) and r.span_for(3) == MAX_SEQ


def test_program_names_are_what_they_were(runner):
    """``benchmark/span_reader.py`` finds programs by ``jit_engine_decode_seg<k>``
    and ``jit_engine_prefill``: a program holds every span, so no name grew."""
    assert runner._segment_fn(4, True).__name__ == "engine_decode_seg4"
    assert runner._segment_fn(1, False).__name__ == "engine_decode_seg1_sampled"
    assert runner._prefill_from.__name__ == "engine_prefill_from"


def test_a_runner_with_one_span_passes_no_live_to():
    """Its programs are the ones it always compiled: no extra argument."""
    r = ModelRunner("tiny", max_batch=2, max_seq=MAX_SEQ)
    assert r.spans == (MAX_SEQ,) and r._live_to(7) == () and r._live_to(None) == ()
