"""The seam between the scheduler and the model (PR 30): `LlamaEngine`
keeps the schedule, one `ModelRunner` (serving/model_runner.py) owns the
weights, the K/V arrays and every device program. What must hold at that
seam, and what files outside the repo's reach rely on."""

import ast
import contextlib
import dataclasses
import inspect
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVING = os.path.join(REPO, "kubedl_tpu", "serving")


def _tree(name):
    with open(os.path.join(SERVING, name)) as f:
        return ast.parse(f.read())


class TestTheSchedulerNamesNoModel:
    def test_server_imports_no_model_and_builds_no_program(self):
        tree = _tree("server.py")
        imported = []
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                imported += [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                imported += [f"{node.module}.{a.name}" for a in node.names]
        assert not [m for m in imported if m.startswith("kubedl_tpu.models")]
        jits = [n.lineno for n in ast.walk(tree)
                if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
                and n.func.attr == "jit"]
        assert not jits, f"server.py builds a program at lines {jits}"
        handles = [n.lineno for n in ast.walk(tree)
                   if isinstance(n, ast.Attribute) and n.attr in ("_llama", "_jax")]
        assert not handles, f"server.py keeps a module handle at lines {handles}"

    def test_only_the_runner_names_engine_programs(self):
        """A program's name (`engine_*`) is given where it is built."""
        for name in sorted(os.listdir(SERVING)):
            if not name.endswith(".py") or name == "model_runner.py":
                continue
            named = [n.value for n in ast.walk(_tree(name))
                     if isinstance(n, ast.Constant) and isinstance(n.value, str)
                     and n.value.startswith("engine_")]
            assert not named, (name, named)

    def test_the_device_function_family_is_chosen_once(self):
        """One `if paged:` picks paged or contiguous `llama` functions: no
        other statement of the runner names both a paged and a contiguous one."""
        contiguous = {"decode_step_batched", "prefill_batched", "prefill_batched_from",
                      "copy_prefix_into_row", "decode_segment", "init_batched_cache"}
        sites = []
        for node in ast.walk(_tree("model_runner.py")):
            if not isinstance(node, ast.If):
                continue
            used = {n.attr for n in ast.walk(node) if isinstance(n, ast.Attribute)
                    and isinstance(n.value, ast.Name) and n.value.id == "llama"}
            if used & contiguous and {u for u in used if u.startswith("paged_")}:
                sites.append(node.lineno)
        assert len(sites) == 1, sites


def test_constructor_signature_is_the_one_the_benchmark_calls():
    """`benchmark/program.py` passes a configuration file's `engine` keys
    to `LlamaEngine(preset=name, **settings)`, and `engine_kwargs` maps the
    `KUBEDL_SERVE_*` variables onto the same parameters."""
    from kubedl_tpu.serving.server import LlamaEngine

    sig = inspect.signature(LlamaEngine.__init__)
    got = {k: v.default for k, v in sig.parameters.items() if k != "self"}
    assert got == {
        "preset": "tiny", "ckpt_dir": "", "batch": 0, "max_seq": 0,
        "max_batch": 4, "quantize": "", "mesh_axes": None, "metrics": None,
        "max_queue_depth": 64, "max_queue_age_s": 30.0,
        "prefix_cache_mb": 64.0, "prefix_min_len": 8, "kv_layout": "paged",
        "kv_block_size": 16, "kv_blocks": 0, "kv_low_watermark": 0.05,
        "kv_high_watermark": 0.15, "spec_k": 0, "spec_draft": "ngram",
        "kv_attention": "gather", "spec_candidates": 1,
        "spec_draft_layers": 0, "spec_tree": False,
        "prefill_chunk_tokens": 0, "role": "colocated",
        "advertise_prefix_len": 8, "handoff_ttl_s": 30.0,
        "model_version": "base",
    }


@contextlib.contextmanager
def _bridged(name, cfg, weights):
    """As `benchmark/program.py` `_bridged`: the two module attributes are
    swapped while the engine is built, and put back."""
    from kubedl_tpu.models import llama

    real_preset, real_init = llama.preset, llama.llama_init

    def preset(asked):
        return cfg if asked == name else real_preset(asked)

    def init(_key, asked):
        if asked is not cfg:
            return real_init(_key, asked)
        return weights

    llama.preset, llama.llama_init = preset, init
    try:
        yield
    finally:
        llama.preset, llama.llama_init = real_preset, real_init


def test_engine_serves_the_bridged_config_and_tree():
    """The benchmark's bridge: a name no preset table holds, a config and a
    weights tree made outside. The engine must look `llama.preset` and
    `llama.llama_init` up on the module when it is built."""
    import jax

    from kubedl_tpu.models import llama
    from kubedl_tpu.serving.server import LlamaEngine
    from test_serving import TestContinuousBatching

    cfg = dataclasses.replace(llama.preset("tiny"), ffn_dim=96)
    weights = llama.llama_init(jax.random.PRNGKey(7), cfg)
    with pytest.raises(KeyError):
        llama.preset("not-in-the-table")
    with _bridged("not-in-the-table", cfg, weights):
        eng = LlamaEngine(preset="not-in-the-table", max_batch=2, max_seq=64)
    try:
        assert eng.cfg is cfg
        assert eng.params is weights
        got = eng.generate([5, 9, 13], max_tokens=6)
        want = TestContinuousBatching()._reference_generate(eng, [5, 9, 13], 6)
        assert got["token_ids"] == want
    finally:
        eng.close()


@pytest.mark.parametrize("kv_layout", ["paged", "contiguous"])
def test_recovery_builds_the_cache_as_the_constructor_did(kv_layout):
    """One `new_cache` with two callers: after a segment fails, the loop's
    recovery rebuilds the runner's cache through the constructor's call,
    with its argument, to its shapes and dtypes."""
    import jax

    from kubedl_tpu.serving.server import LlamaEngine

    calls = []
    eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64,
                      kv_layout=kv_layout)
    try:
        runner = eng._runner
        built = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), runner.cache)
        new_cache, segment_fn = runner.new_cache, runner._segment_fn

        def counted(kv_blocks=0):
            calls.append(kv_blocks)
            new_cache(kv_blocks)

        def boom(k, greedy):
            runner._segment_fn = segment_fn

            def raises(*a, **kw):
                raise RuntimeError("injected segment failure")

            return raises

        runner.new_cache, runner._segment_fn = counted, boom
        r1 = eng.generate([5, 9], max_tokens=6, timeout_s=60)
        assert "injected segment failure" in r1.get("error", ""), r1
        r2 = eng.generate([5, 9, 13], max_tokens=6, timeout_s=60)
        assert len(r2["token_ids"]) == 6
        assert calls == [eng.kv_blocks]
        assert (eng.kv_blocks > 0) == (kv_layout == "paged")
        rebuilt = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), runner.cache)
        assert rebuilt == built
    finally:
        eng.close()


# ---- the view's span, at the engine (PR 31) --------------------------------

#: (prompt length, max_tokens): decode and prefill end in the 64-, the 128-
#: and the 256-key span of a 256-key engine whose floor is shrunk to 64
SPAN_REQUESTS = [(10, 8), (70, 20), (150, 40)]


def _span_prompts():
    import numpy as np

    rng = np.random.default_rng(31)
    return [(rng.integers(1, 200, n).tolist(), out) for n, out in SPAN_REQUESTS]


@pytest.fixture(scope="module")
def span_engines():
    """``(many, one)``: the same engine with three spans and with one."""
    from kubedl_tpu.serving.model_runner import ModelRunner
    from kubedl_tpu.serving.server import LlamaEngine

    kw = dict(preset="tiny", max_batch=4, max_seq=256,
              prefill_chunk_tokens=64, prefix_cache_mb=0)
    floor = ModelRunner.SPAN_FLOOR
    ModelRunner.SPAN_FLOOR = 64
    try:
        many = LlamaEngine(**kw)
    finally:
        ModelRunner.SPAN_FLOOR = floor
    one = LlamaEngine(**kw)
    yield many, one
    many.close()
    one.close()


class TestSeveralSpans:
    def test_one_program_holds_every_span(self, span_engines):
        """Spans are branches inside a program, not programs: both engines
        have the same programs under the same names, and the one with
        several spans hands each a ``live_to`` where the other hands none."""
        many, one = span_engines
        assert many._runner.spans == (64, 128, 256) and one._runner.spans == (256,)
        assert many._runner._live_to(70) == (70,) and one._runner._live_to(70) == ()
        names = lambda r: sorted(f.__name__ for f in r._segments.values())  # noqa: E731
        for eng in span_engines:
            eng.generate([5, 9, 13], max_tokens=6)
        assert names(many._runner) == names(one._runner) != []

    @pytest.mark.parametrize("how", ["alone", "together"])
    def test_serves_the_one_span_engines_tokens(self, span_engines, how):
        from test_phase_spans import serve

        many, one = span_engines
        requests = _span_prompts()
        if how == "together":
            got, want = serve(many, requests), serve(one, requests)
        else:
            got = [serve(many, [r])[0] for r in requests]
            want = [serve(one, [r])[0] for r in requests]
        assert [r["token_ids"] for r in got] == [r["token_ids"] for r in want]
        assert [len(r["token_ids"]) for r in got] == [n for _p, n in SPAN_REQUESTS]

    def test_nothing_compiles_while_requests_of_every_span_are_served(self, span_engines):
        """A request reaches a span by how long its row is, and every span
        is a branch of programs that short requests have already compiled:
        after a warm-up of SHORT requests (one of every token bucket and
        segment size, as a deployment's warm-up sends), requests that run
        in the longer spans compile nothing."""
        import jax

        from test_phase_spans import serve

        many, _one = span_engines
        requests = _span_prompts()
        # short rows only: buckets 16, 32, 64 and every segment size, all
        # inside the 64-key span
        serve(many, [([7] * n, 3) for n in (9, 20, 40)] + [([5, 6], 40)])
        serve(many, [([8, 9], 2), ([8, 9, 10], 9)])
        fired, armed = [], [True]

        def listener(event, _secs, **_kw):
            if armed[0] and event == "/jax/core/compile/backend_compile_duration":
                fired.append(event)

        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            for r in requests:
                serve(many, [r])
            serve(many, requests)
        finally:
            armed[0] = False
        assert not fired

    def test_phases_carry_the_span_and_the_counters_close(self, span_engines, tmp_path, monkeypatch):
        from kubedl_tpu.observability.tracing import TRACER
        from test_phase_spans import capture, serve

        monkeypatch.setattr(TRACER, "enabled", True)
        many, one = span_engines
        before = many.stats()
        with capture(tmp_path) as cap:
            serve(many, _span_prompts())
        after = many.stats()
        decode = cap.named("engine.decode_dispatch")
        prefill = cap.named("engine.prefill_dispatch")
        assert {e[4]["span"] for e in decode} == {64, 128, 256}
        assert {e[4]["span"] for e in prefill} == {64, 128, 256}
        for e in prefill:  # a chunk's view holds every position it touches
            assert e[4]["bucket"] <= e[4]["span"]
        keys = (sum(e[4]["span"] * e[4]["k"] * e[4]["slots"] for e in decode)
                + sum(e[4]["span"] * e[4]["slots"] for e in prefill))
        full = (sum(256 * e[4]["k"] * e[4]["slots"] for e in decode)
                + sum(256 * e[4]["slots"] for e in prefill))
        assert all(e[4]["slots"] == many.max_batch for e in decode)
        assert after["view_keys"] - before["view_keys"] == keys
        assert after["view_keys_full"] - before["view_keys_full"] == full
        assert 0 < keys < full
        assert many.metrics.view_keys.value() == after["view_keys"]
        assert many.metrics.view_keys_full.value() == after["view_keys_full"]
        # an engine with one span gathers the whole view every time
        st = one.stats()
        assert 0 < st["view_keys"] == st["view_keys_full"]


# ---- the programs an engine runs, text for text -----------------------------

PROGRAMS_FILE = os.path.join(REPO, "tests", "data", "engine_programs.json")
#: the benchmark's two serve presets in miniature: a decoder and a model with
#: recurrent state, paged, 16-token blocks, several view spans
PROGRAM_PRESETS = ("tiny", "tiny-hybrid")


@pytest.fixture(scope="module")
def kernel_engines():
    """``(kernel, plain)``: an engine whose runner saw a TPU and a pool the
    decode kernel can take (steered here: the backend's name, the tiling
    predicate, and the interpreter in the compiled kernel's place), and the
    same engine as a CPU builds it."""
    import functools

    import jax

    from kubedl_tpu.models import paged_attention as pa
    from kubedl_tpu.serving.server import LlamaEngine

    kw = dict(preset="tiny", max_batch=3, max_seq=128, prefill_chunk_tokens=16,
              prefix_cache_mb=0)
    with pytest.MonkeyPatch.context() as mp:
        # for as long as the engines live: their programs trace on first use
        mp.setattr(pa, "paged_attention", functools.partial(
            pa.paged_attention, kernel="pallas", interpret=True, tile=32))
        with pytest.MonkeyPatch.context() as seen:  # what the constructor observes
            seen.setattr(jax, "default_backend", lambda: "tpu")
            seen.setattr(pa, "decode_kernel_fits", lambda *a: True)
            seen.setattr(pa, "DEFAULT_TILE", 32)
            kernel = LlamaEngine(**kw)
        plain = LlamaEngine(**kw)
        yield kernel, plain
        kernel.close()
        plain.close()


class TestDecodeKernelRunner:
    REQUESTS = [
        (list(range(1, 6)), 9),      # 5-token prompt
        (list(range(10, 33)), 5),    # 23 tokens: two chunks
        (list(range(40, 81)), 14),   # 41 tokens: three chunks of 16
    ]

    def test_the_runner_picks_the_kernel_from_what_it_observes(self, kernel_engines):
        kernel, plain = kernel_engines
        assert kernel._runner.decode_tile == 32 and plain._runner.decode_tile == 0
        assert plain._runner.keys_read([5, 40], 4) is None
        # rows at 5 and 40 keys, 4 steps: 6..9 and 41..44 keys, in blocks of 32
        assert kernel._runner.keys_read([5, 40], 4) == 4 * 32 + 4 * 64
        assert kernel._runner.keys_read([126], 4) == 4 * 128  # clamped at max_seq
        # the option still names the suffix programs' arm; spans stay theirs
        assert kernel._runner.spans == plain._runner.spans

    def test_serves_the_gathered_engines_tokens_with_rows_sitting_out(
            self, kernel_engines, tmp_path, monkeypatch):
        """Chunked prompts beside decoding rows: a row mid-prompt sits the
        decode dispatches out (the kernel fetches nothing for it) and every
        request gets the tokens the gathered view gives; the dispatch phase
        carries ``read`` and the counters add what the kernel fetched."""
        from kubedl_tpu.observability.tracing import TRACER
        from test_phase_spans import capture, serve

        monkeypatch.setattr(TRACER, "enabled", True)
        kernel, plain = kernel_engines
        want = serve(plain, self.REQUESTS)
        before = kernel.stats()
        with capture(tmp_path) as cap:
            got = serve(kernel, self.REQUESTS)
        after = kernel.stats()
        assert [r["token_ids"] for r in got] == [r["token_ids"] for r in want]
        decode = [e[4] for e in cap.named("engine.decode_dispatch")]
        assert decode and all("read" in e and "span" in e for e in decode)
        assert any(e["rows"] < 3 for e in decode)  # somebody sat out
        for e in decode:  # whole compute blocks of what the rows hold
            assert e["read"] % 32 == 0
            assert e["keys"] * e["k"] < e["read"] <= (
                e["keys"] + e["rows"] * (e["k"] + 32)) * e["k"]
        prefill = cap.named("engine.prefill_dispatch")
        keys = (sum(e["read"] for e in decode)
                + sum(e[4]["span"] * e[4]["slots"] for e in prefill if "span" in e[4]))
        assert after["view_keys"] - before["view_keys"] == keys
        full = after["view_keys_full"] - before["view_keys_full"]
        assert 0 < keys < full
        with capture(tmp_path / "plain") as cap:  # the gathered view names no read
            serve(plain, self.REQUESTS)
        assert not any("read" in e[4] for e in cap.named("engine.decode_dispatch"))


def engine_program_texts(preset):
    """name -> lowered text of every device program a paged engine on
    ``preset`` can dispatch, at ``max_batch`` 2 and ``max_seq`` 4096 (view
    spans 1024, 2048, 4096, as the benchmark's decoder cells have): the
    three decode segments greedy and sampled, whole-prompt and suffix prefill
    at a short and at a chunk-sized bucket, and whatever else the runner
    holds jitted. The scheduler's choices are host code and appear in none."""
    import jax
    import jax.numpy as jnp

    from kubedl_tpu.serving.model_runner import HybridRunner, make_runner
    from kubedl_tpu.serving.server import LlamaEngine

    b, max_seq = 2, 4096
    r = make_runner(preset, max_batch=b, max_seq=max_seq)
    assert r.spans == (1024, 2048, 4096)
    r.new_cache(1 + b * max_seq // r.kv_block_size)
    shapes = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    p, c = shapes(r.build_params("")), shapes(r.cache)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    key, logits = shapes(jax.random.PRNGKey(0)), f32(b, r.cfg.vocab_size)
    hybrid = isinstance(r, HybridRunner)
    live = (jax.ShapeDtypeStruct((b,), jnp.bool_),) if hybrid else ()
    out = {}
    for k in LlamaEngine.SEGMENT_BUCKETS:
        for greedy in (True, False):
            fn = r._segment_fn(k, greedy)
            out[fn.__name__] = fn.lower(p, c, i32(b, 1), f32(b), key, *live, i32())
    for bucket in (16, 1024):
        out[f"engine_prefill@{bucket}"] = r._prefill.lower(
            p, c, i32(1, bucket), i32(1), i32(1), logits)
        out[f"engine_prefill_from@{bucket}"] = r._prefill_from.lower(
            p, c, i32(1, bucket), i32(1), i32(1), i32(1), logits, i32())
    out["engine_sample_first"] = r.sample_first.lower(logits, f32(b), key)
    out["engine_merge_chain"] = r.merge_chain.lower(
        i32(b, 1), i32(b), jax.ShapeDtypeStruct((b,), jnp.bool_))
    if not hybrid:
        out["engine_decode_step"] = r._decode.lower(p, c, i32(b, 1))
        out["engine_copy_block"] = r._copy_block.lower(c, 1, 2)
    jitted = type(jax.jit(lambda: 0))
    built = {v.__name__ for v in vars(r).values() if isinstance(v, jitted)}
    built |= {fn.__name__ for fn in r._segments.values()}
    # `engine_graft` takes a prefix-cache entry's arrays: direct inserts in
    # tests only, its text is held by the prefix-cache tests' identities
    assert built - {"engine_graft"} == {n.split("@")[0] for n in out}, built
    return {name: lowered.as_text() for name, lowered in out.items()}


def engine_program_digests():
    import hashlib

    return {preset: {name: hashlib.sha256(text.encode()).hexdigest()
                     for name, text in sorted(engine_program_texts(preset).items())}
            for preset in PROGRAM_PRESETS}


@pytest.fixture(scope="module")
def program_digests():
    return engine_program_digests()


@pytest.mark.parametrize("preset", PROGRAM_PRESETS)
def test_engine_programs_are_the_recorded_ones(preset, program_digests):
    """The set of programs and each one's lowered text are what
    ``tests/data/engine_programs.json`` recorded: a PR to the scheduler
    (PR 36: how the tick chooses its segment) adds no executable and changes
    no device work. A PR that means to change a program writes the file anew,
    ``python -c "import test_model_runner as t; t.write_engine_programs()"``
    from ``tests/``, and says so."""
    import json

    with open(PROGRAMS_FILE) as f:
        recorded = json.load(f)[preset]
    got = program_digests[preset]
    assert sorted(got) == sorted(recorded), "the set of programs changed"
    changed = [name for name in got if got[name] != recorded[name]]
    assert not changed, f"lowered text differs from the recorded one: {changed}"


def write_engine_programs(path=PROGRAMS_FILE):
    import json

    with open(path, "w") as f:
        json.dump(engine_program_digests(), f, indent=1, sort_keys=True)
        f.write("\n")
