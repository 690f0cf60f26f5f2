"""Phase spans and program names (docs/observability.md "Device profiles").

The engine's scheduler loop and the trainer's loop write their phases to
the profiler (``TRACER.phase`` / ``TRACER.step``), and every jitted program
of the engine has a name. These tests read a CPU ``jax.profiler`` capture
back, so they check what a capture on the chip will hold beside the device
plane: names, nesting, and counts that close on what was served. Times from
a CPU run are never read as speeds here; they are only compared with one
another."""

import contextlib
import glob
import os
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from kubedl_tpu.analysis import engine as analysis_engine
from kubedl_tpu.analysis.rules import span_names
from kubedl_tpu.observability import tracing
from kubedl_tpu.observability.tracing import TRACER, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIPELINE_KEYS = {
    "ticks", "segments", "segments_by_k", "segments_short",
    "deferred_harvests", "flushes", "chain_rebuilds",
    "errors", "inflight", "queued", "first_tokens", "compiles",
    "dispatch_ms_avg", "harvest_ms_avg",
    "host_ms_avg", "tick_ms_avg", "overlap_ratio", "dispatch_ms_p50",
    "harvest_ms_p50", "host_ms_p50", "tick_ms_p50",
}


@pytest.fixture(autouse=True)
def _armed_tracer():
    TRACER.clear()
    TRACER.enabled = True
    yield
    TRACER.clear()
    TRACER.enabled = True


class Capture:
    """Host-plane ``engine.*`` / ``train.*`` events of one CPU capture:
    ``(line, name, start_ns, end_ns, stats)``."""

    def __init__(self):
        self.events = []

    def named(self, name):
        return [e for e in self.events if e[1] == name]

    def total(self, name, stat):
        return sum(e[4][stat] for e in self.named(name) if stat in e[4])


@contextlib.contextmanager
def capture(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as the benchmark's traced run
    cap = Capture()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        yield cap
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "train.")):
                    cap.events.append((line.name, e.name, e.start_ns,
                                       e.start_ns + e.duration_ns, dict(e.stats)))


def make_engine(**kw):
    from kubedl_tpu.serving.server import LlamaEngine

    return LlamaEngine(preset="tiny", max_batch=2, max_seq=128, **kw)


def serve(eng, requests):
    """Every ``(prompt, max_tokens)`` at once, from a thread each."""
    replies = [None] * len(requests)

    def one(i, prompt, n):
        replies[i] = eng.generate(prompt, max_tokens=n, temperature=0.0)

    threads = [threading.Thread(target=one, args=(i, p, n))
               for i, (p, n) in enumerate(requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert all(r is not None and "error" not in r for r in replies), replies
    return replies


REQUESTS = [
    (list(range(1, 6)), 9),      # 5-token prompt
    (list(range(10, 33)), 5),    # 23 tokens
    (list(range(40, 81)), 14),   # 41 tokens: three chunks of 16 when chunked
]


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def chunked_engine():
    eng = make_engine(prefill_chunk_tokens=16)
    yield eng
    eng.close()


# ---- the tracer's new span kind -------------------------------------------


class TestPhaseHandle:
    def test_armed_phase_measures_and_takes_attributes(self):
        t = Tracer()
        with t.phase("engine.decode_dispatch", k=4) as ph:
            ph.set(rows=2)  # no capture listening: still legal
            sum(range(1000))
        assert ph.ms > 0.0
        with t.step("train.step", 7) as st:
            pass
        assert st.ms >= 0.0

    def test_phases_never_enter_the_ring(self, engine):
        t = Tracer()
        with t.phase("engine.tick"):
            pass
        assert t.spans() == []
        before = len(TRACER.spans())
        serve(engine, REQUESTS[:2])  # no trace context: no per-request span
        assert engine.pipeline_stats()["ticks"] > 0
        assert len(TRACER.spans()) == before

    def test_disarmed_phase_times_and_annotates_nothing(self):
        # the tick's accounting reads .ms: it must not depend on the switch
        t = Tracer()
        t.enabled = False
        for ph in (t.phase("engine.tick", k=1), t.step("train.step", 0)):
            assert ph._ann is None
            with ph:
                ph.set(rows=1)
                sum(range(1000))
            assert ph.ms > 0.0

    def test_disarmed_budget_covers_phase(self):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            from scheduler_microbench import run_tracing_microbench
        finally:
            sys.path.pop(0)
        out = run_tracing_microbench(calls=20_000)
        assert out["within_budget"] and 0 < out["phase_us"] <= out["budget_us"]

    def test_dead_hooks_are_gone(self):
        assert not hasattr(tracing, "xprof_trace")
        assert not hasattr(tracing, "use_context")


# ---- program names ---------------------------------------------------------


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _lowerings(eng):
    """name -> a thunk that lowers that program of ``eng`` on shapes."""
    b = eng.max_batch
    r = eng._runner
    p, c = _shapes(eng.params), _shapes(r.cache)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    key = _shapes(eng._key)
    vocab = eng.cfg.vocab_size
    return {
        "engine_decode_step": lambda: r._decode.lower(p, c, i32(b, 1)),
        # a paged prefill program computes one row, named by ``rows``
        "engine_prefill": lambda: r._prefill.lower(
            p, c, i32(1, 16), i32(1), i32(1), f32(b, vocab)),
        "engine_prefill_from": lambda: r._prefill_from.lower(
            p, c, i32(1, 16), i32(1), i32(1), i32(1), f32(b, vocab)),
        "engine_decode_seg4": lambda: r._segment_fn(4, True).lower(
            p, c, i32(b, 1), f32(b), key),
        "engine_decode_seg4_sampled": lambda: r._segment_fn(4, False).lower(
            p, c, i32(b, 1), f32(b), key),
        "engine_sample_first": lambda: r.sample_first.lower(
            f32(b, vocab), f32(b), key),
        "engine_merge_chain": lambda: r.merge_chain.lower(
            i32(b, 1), i32(b), jax.ShapeDtypeStruct((b,), jnp.bool_)),
        "engine_copy_block": lambda: r._copy_block.lower(c, 1, 2),
    }


class TestProgramNames:
    @pytest.mark.parametrize("name", [
        "engine_decode_step", "engine_prefill", "engine_prefill_from",
        "engine_decode_seg4", "engine_decode_seg4_sampled",
        "engine_sample_first", "engine_merge_chain", "engine_copy_block",
    ])
    def test_program_lowers_under_its_name(self, engine, name):
        text = _lowerings(engine)[name]().as_text()
        assert f"module @jit_{name} " in text or f"module @jit_{name}\n" in text, text[:200]

    def test_no_program_is_a_lambda_or_unknown(self):
        """Every jitted attribute of an engine's runner, the speculative ones and the
        contiguous layout's included, wraps a function named ``engine_*``."""
        jitted = type(jax.jit(lambda: 0))
        seen = set()
        for kw in ({"spec_k": 2, "spec_candidates": 2, "spec_tree": True},
                   {"kv_layout": "contiguous"}):
            eng = make_engine(**kw)
            try:
                r = eng._runner
                r._segment_fn(4, True), r._segment_fn(1, False)
                fns = [v for v in vars(r).values() if isinstance(v, jitted)]
                fns += list(r._segments.values())
                assert len(fns) >= 8
                for fn in fns:
                    assert fn.__name__.startswith("engine_"), fn
                    seen.add(fn.__name__)
            finally:
                eng.close()
        assert {"engine_verify", "engine_verify_multi", "engine_verify_tree",
                "engine_graft", "engine_extract", "engine_copy_block",
                "engine_decode_seg4", "engine_decode_seg1_sampled"} <= seen


# ---- the engine's phases in a capture -------------------------------------


class TestEnginePhases:
    def test_phases_nest_inside_their_tick_on_the_scheduler_line(self, tmp_path):
        # `serve` returns from inside the loop's last tick (its harvest hands
        # the reply over), and a capture stopped there drops that tick's span
        # and keeps its leaves: the loop is joined before the capture stops
        eng = make_engine()
        with capture(tmp_path) as cap:
            try:
                serve(eng, REQUESTS)
            finally:
                eng.close()
        names = {e[1] for e in cap.events}
        assert {"engine.tick", "engine.admit", "engine.prefill_dispatch",
                "engine.decode_dispatch", "engine.harvest_wait",
                "engine.harvest_host"} <= names
        ticks = cap.named("engine.tick")
        assert len({e[0] for e in cap.events}) == 1, "one thread runs the loop"
        assert all("segments" in t[4] and "waiting" in t[4] for t in ticks)
        for leaf in ("engine.prefill_dispatch", "engine.decode_dispatch",
                     "engine.harvest_wait", "engine.harvest_host"):
            for e in cap.named(leaf):
                assert any(t[2] <= e[2] and e[3] <= t[3] for t in ticks), e
        assert any(t[2] <= a[2] and a[3] <= t[3]
                   for a in cap.named("engine.admit") for t in ticks)
        assert {e[4]["what"] for e in cap.named("engine.harvest_wait")} == {
            "segment", "prefill"}
        # a tick is not idle time, and idle time is not in a tick
        for w in cap.named("engine.idle_wait"):
            assert not any(t[2] < w[3] and w[2] < t[3] for t in ticks)

    @pytest.mark.parametrize("which", ["whole_prompt", "chunked"])
    def test_counts_close_on_what_was_served(self, which, engine, chunked_engine, tmp_path):
        eng = engine if which == "whole_prompt" else chunked_engine
        # the last request repeats the first one's prompt, so part of it may
        # come from the prefix cache; the reply says how much
        requests = REQUESTS + [(REQUESTS[2][0], 3)]
        with capture(tmp_path) as cap:
            replies = serve(eng, requests)
        delivered = sum(len(r["token_ids"]) for r in replies)
        assert delivered == sum(n for _p, n in requests)
        # every request's first token comes from its prefill
        assert cap.total("engine.decode_dispatch", "take") == delivered - len(requests)
        prefilled = sum(len(p) - int(r.get("cached_prefix_len", 0))
                        for (p, _n), r in zip(requests, replies))
        assert cap.total("engine.prefill_dispatch", "tokens") == prefilled
        for e in cap.named("engine.prefill_dispatch"):
            # a paged prefill program computes the rows it feeds, one each
            assert e[4]["rows"] == e[4]["slots"] == 1
            assert 0 < e[4]["tokens"] <= e[4]["rows"] * e[4]["bucket"]
        stats = eng.stats()
        assert stats["prefill_tokens"] <= stats["prefill_positions"]
        for e in cap.named("engine.decode_dispatch"):
            assert 0 < e[4]["take"] <= e[4]["rows"] * e[4]["k"]
            assert e[4]["rows"] <= e[4]["slots"] == eng.max_batch
        if which == "chunked":
            # 41 tokens at 16 a tick: the prompt landed in three dispatches
            assert len(cap.named("engine.prefill_dispatch")) >= 5

    @pytest.mark.parametrize("reason", ["prefill", "waiting"])
    def test_decode_dispatch_says_what_the_tick_owed(self, reason, tmp_path):
        """``backlog`` on every decode dispatch, ``short`` on those the owed
        prefill work made shorter, and the engine's counters of both close
        on the spans. Ticks are driven by hand: A decodes a long budget
        while B's three-chunk prompt comes in (``prefill``), or two rows
        are taken and a third request waits for one (``waiting``)."""
        from kubedl_tpu.serving.server import _Slot

        eng = make_engine(prefill_chunk_tokens=16, prefix_cache_mb=0)
        with eng._cv:
            eng._stop = True
            eng._cv.notify_all()
        eng._thread.join(timeout=10)
        eng._stop = False
        if reason == "prefill":
            first = [_Slot([5, 9, 13], 60, 0.0)]
            then = [_Slot(list(range(40, 81)), 4, 0.0)]
        else:
            first = [_Slot([5, 9, 13 + j], 40, 0.0) for j in range(3)]
            then = []
        try:
            with capture(tmp_path) as cap:
                with eng._cv:
                    eng._waiting.extend(first)
                eng._loop_once()
                with eng._cv:
                    eng._waiting.extend(then)
                for _ in range(400):
                    if all(s.done.is_set() for s in first + then):
                        break
                    eng._loop_once()
            pipe = eng.pipeline_stats()
            metric = eng.metrics.segment_lengths
        finally:
            eng.close()
        assert all(s.done.is_set() for s in first + then)
        spans = cap.named("engine.decode_dispatch")
        assert spans and all("backlog" in e[4] for e in spans)
        short = [e for e in spans if "short" in e[4]]
        assert short, [e[4] for e in spans]
        # one row mid-prompt beside one decoding is one step from its next
        # chunk; a request with no row waits out four
        for e in short:
            assert e[4]["short"] == reason
            assert e[4]["k"] == (1 if reason == "prefill" else 4)
            assert e[4]["backlog"] >= 1
        # the cap engages only where something is owed; with nothing owed
        # the budgets choose, and a long budget takes the long segment
        assert any(e[4]["k"] == 32 and e[4]["backlog"] == 0 for e in spans)
        other = "waiting" if reason == "prefill" else "prefill"
        assert pipe["segments_short"] == {reason: len(short), other: 0}
        for k in (32, 4, 1):
            n = sum(1 for e in spans if e[4]["k"] == k)
            assert pipe["segments_by_k"][str(k)] == n
            assert sum(metric.value(k=str(k), short=why)
                       for why in ("no", "waiting", "prefill")) == n
        assert metric.value(k="1" if reason == "prefill" else "4",
                            short=reason) == len(short)
        assert pipe["segments"] == len(spans)

    def test_tick_accounting_is_the_spans_own_durations(self, monkeypatch):
        eng = make_engine()
        handles, ticks = [], []
        real_phase, real_commit = TRACER.phase, eng._commit_tick

        def phase(name, **attrs):
            h = real_phase(name, **attrs)
            handles.append((name, h))
            return h

        def commit(acct, tick_ms):
            at = max(i for i, (n, _h) in enumerate(handles) if n == "engine.tick")
            ticks.append((dict(acct), tick_ms, handles[at][1],
                          [(n, h.ms) for n, h in handles[at + 1:]]))
            return real_commit(acct, tick_ms)

        monkeypatch.setattr(TRACER, "phase", phase)
        monkeypatch.setattr(eng, "_commit_tick", commit)
        try:
            serve(eng, REQUESTS)
            stats = eng.pipeline_stats()
        finally:
            eng.close()
        assert len(ticks) >= 3 and PIPELINE_KEYS <= set(stats)
        assert stats["dispatch_ms_avg"] > 0 and stats["tick_ms_p50"] > 0
        for acct, tick_ms, tick, parts in ticks:
            ms = lambda *names: sum(m for n, m in parts if n in names)  # noqa: E731
            assert acct["dispatch_ms"] == pytest.approx(
                ms("engine.prefill_dispatch", "engine.decode_dispatch"), abs=1e-9)
            assert acct["harvest_ms"] == pytest.approx(ms("engine.harvest_wait"), abs=1e-9)
            assert acct["host_ms"] == pytest.approx(ms("engine.harvest_host"), abs=1e-9)
            assert tick_ms == tick.ms
            assert tick.ms >= acct["dispatch_ms"] + acct["harvest_ms"] + acct["host_ms"] - 1e-6

    def test_disarmed_engine_leaves_no_event_and_keeps_its_accounting(self, tmp_path):
        TRACER.enabled = False
        eng = make_engine()
        try:
            with capture(tmp_path) as cap:
                replies = serve(eng, REQUESTS[:2])
            stats = eng.pipeline_stats()
        finally:
            eng.close()
        assert [len(r["token_ids"]) for r in replies] == [9, 5]
        assert cap.events == []
        # the tick's times are not the tracer's to switch off
        assert PIPELINE_KEYS <= set(stats)
        for key in ("dispatch_ms_avg", "harvest_ms_avg", "host_ms_avg", "tick_ms_avg",
                    "tick_ms_p50", "overlap_ratio"):
            assert stats[key] > 0, key


# ---- where a request's time to first token went ---------------------------


def first_tokens(eng):
    return eng.pipeline_stats()["first_tokens"]


def stop_loop(eng):
    """Join the scheduler thread: the test drives `_loop_once` by hand."""
    with eng._cv:
        eng._stop = True
        eng._cv.notify_all()
    eng._thread.join(timeout=10)
    eng._stop = False


def drive(eng, slots, ticks=600):
    for _ in range(ticks):
        if all(s.done.is_set() for s in slots):
            return
        eng._loop_once()
    raise AssertionError("requests still open")


class TestFirstTokenRecords:
    @pytest.mark.parametrize("which", ["whole_prompt", "chunked"])
    def test_every_request_leaves_one_record_that_sums_to_its_ttft(
            self, which, engine, chunked_engine):
        eng = engine if which == "whole_prompt" else chunked_engine
        for prompt, n in REQUESTS:
            before = first_tokens(eng)
            (reply,) = serve(eng, [(prompt, n)])  # alone: nothing is put ahead
            after = first_tokens(eng)
            assert len(after) == len(before) + 1 and after[:-1] == before[-1023:]
            seq, queue, backlog, chunks, first, n_chunks, segments, steps = after[-1]
            assert seq > (before[-1][0] if before else 0)
            assert min(queue, backlog, chunks, first) >= 0 and first > 0
            assert queue + backlog + chunks + first == pytest.approx(
                reply["ttft_ms"], abs=0.01)
            suffix = len(prompt) - int(reply.get("cached_prefix_len", 0))
            assert n_chunks == (-(-suffix // 16) if which == "chunked" else 1)
            assert (chunks > 0) == (n_chunks > 1)
            assert (segments, steps) == (0, 0)

    @pytest.mark.parametrize("waits_for", ["a_row", "anothers_chunks"])
    def test_the_parts_say_what_a_request_waited_for(self, waits_for):
        from kubedl_tpu.serving.server import LlamaEngine, _Slot

        eng = LlamaEngine(preset="tiny", max_batch=2 if waits_for == "a_row" else 3,
                          max_seq=128, prefill_chunk_tokens=16, prefix_cache_mb=0)
        stop_loop(eng)
        if waits_for == "a_row":
            # three long budgets on two rows: the third has no row until one ends
            first = [_Slot([5, 9, 13 + j], 40, 0.0) for j in range(3)]
            then = []
        else:
            # A decodes; B's three chunks take the tick's 16 tokens ahead of C's
            first = [_Slot([5, 9, 13], 60, 0.0)]
            then = [_Slot(list(range(40, 81)), 4, 0.0), _Slot(list(range(10, 33)), 4, 0.0)]
        try:
            for s in first:
                eng._enqueue_slot_locked_checks(s)
            eng._loop_once()
            for s in then:
                eng._enqueue_slot_locked_checks(s)
            drive(eng, first + then)
            records = {r[0]: r for r in first_tokens(eng)}
        finally:
            eng.close()
        assert sorted(records) == [s.seq for s in first + then] == [1, 2, 3]
        for s in first + then:
            assert sum(records[s.seq][1:5]) == pytest.approx(s.ttft_ms, abs=0.01)
        if waits_for == "a_row":
            _seq, queue, _b, _c, _f, n_chunks, _segments, _steps = records[3]
            assert queue > 10 * max(records[1][1], records[2][1]) and n_chunks == 1
            assert queue > 0.5 * first[2].ttft_ms  # it is most of what it waited
        else:
            a, b, c = (records[n] for n in (1, 2, 3))
            assert a[5:] == (1, 0, 0)
            # B: three programs with A's one-step segments between them
            assert b[5] == 3 and b[3] > 0 and b[6] >= 2 and b[7] >= b[6]
            # C has a row from the same tick on and gets no token of three ticks
            assert c[5] == 2 and c[2] > b[2] and c[6] >= 3 and c[7] >= c[6]
            assert c[2] > 0.5 * b[3]

    @pytest.mark.parametrize("which", ["whole_prompt", "chunked"])
    def test_capture_marks_each_first_token_and_names_each_prefill_program(
            self, which, engine, chunked_engine, tmp_path):
        eng = engine if which == "whole_prompt" else chunked_engine
        before = len(first_tokens(eng))
        with capture(tmp_path) as cap:
            replies = serve(eng, REQUESTS)
        records = first_tokens(eng)[before:]
        assert len(records) == len(REQUESTS)
        marks = cap.named("engine.first_token")
        keys = ("req", "queue", "backlog", "chunks", "first", "n_chunks", "segments", "steps")
        assert sorted(tuple(e[4][k] for k in keys) for e in marks) == sorted(records)
        hosts = cap.named("engine.harvest_host")
        for e in marks:
            around = [h for h in hosts if h[0] == e[0] and h[2] <= e[2] and e[3] <= h[3]]
            # inside the prefill's harvest, which names no segment
            assert len(around) == 1 and "seq" not in around[0][4], around
        programs = cap.named("engine.prefill_dispatch")
        assert all({"req", "base", "final"} <= set(e[4]) for e in programs)
        by_req = {r[0]: r for r in records}
        for seq, rec in by_req.items():
            mine = sorted((e for e in programs if e[4]["req"] == seq), key=lambda e: e[2])
            assert len(mine) == rec[5]  # n_chunks
            assert [e[4]["final"] for e in mine] == [0] * (len(mine) - 1) + [1]
            assert [e[4]["base"] for e in mine] == sorted(e[4]["base"] for e in mine)
        assert sum(e[4]["final"] for e in programs) == len(replies)
        assert all("compiles" in t[4] for t in cap.named("engine.tick"))

    def test_disarmed_engine_keeps_its_records_and_percentiles(self, tmp_path):
        TRACER.enabled = False
        eng = make_engine(prefill_chunk_tokens=16)
        try:
            with capture(tmp_path) as cap:
                replies = serve(eng, REQUESTS)
            records, stats = first_tokens(eng), eng.stats()
            total, _sum = eng.metrics.ttft_part_ms.summary(part="first")
        finally:
            eng.close()
        assert cap.events == []
        assert len(records) == len(REQUESTS) == total
        assert sorted(round(sum(r[1:5]), 3) for r in records) == pytest.approx(
            sorted(r["ttft_ms"] for r in replies), abs=0.01)
        assert sorted(r[5] for r in records) == [1, 2, 3]
        for part in ("queue", "backlog", "chunks", "first"):
            for q in (50, 95, 99):
                assert stats[f"ttft_{part}_ms_p{q}"] >= 0
        assert stats["ttft_first_ms_p99"] == max(r[4] for r in records)
        assert stats["queue_wait_ms_p99"] >= stats["queue_wait_ms_p50"] > 0
        assert stats["pipeline"]["compiles"] >= 0


# ---- the benchmark's readers of that record -------------------------------


def _reader(name):
    from benchmark.run import load_reader

    return load_reader("layer_metrics", name)


def _served(ttfts, late=0.25):
    return {"kind": "serve", "window_s": 51.0, "requests": [
        {"ok": t is not None, "ttft_ms": None if t is None else t + late, "late_ms": late}
        for t in ttfts]}


WARM_UP = [(1, 0.1, 0.2, 0.0, 30.0, 1, 0, 0), (2, 0.1, 0.3, 0.0, 31.0, 1, 0, 0)]
WINDOW = [(3, 100.0, 1.0, 0.0, 50.0, 1, 0, 0), (4, 5.0, 40.0, 90.0, 300.0, 3, 2, 5),
          (5, 0.5, 0.5, 0.0, 20.0, 1, 0, 0)]


class TestFirstTokenReaders:
    def test_the_window_is_the_last_n_records(self, capsys):
        stats = {"pipeline": {"first_tokens": WARM_UP + WINDOW}}
        record = _served([151.0, 435.0, 21.0])
        got = {part: _reader(f"ttft_{part}_mean_ms")(None, stats, record)
               for part in ("queue", "backlog", "chunks", "first")}
        assert got == pytest.approx(
            {"queue": 105.5 / 3, "backlog": 41.5 / 3, "chunks": 30.0, "first": 370.0 / 3})
        assert sum(got.values()) == pytest.approx((151.0 + 435.0 + 21.0) / 3)
        out = capsys.readouterr().out
        assert "median 5.000, p95 100.000 (n=3)" in out
        assert "n_chunks 1, segments 0, steps 0" in out

    @pytest.mark.parametrize("why", ["no_record_kept", "fewer_records", "another_requests"])
    def test_none_where_the_records_are_not_the_windows(self, why, capsys):
        read = _reader("ttft_first_mean_ms")
        if why == "no_record_kept":  # a program from before the record
            assert read(None, {"pipeline": {"ticks": 3}}, _served([151.0])) is None
            assert read(None, {}, _served([151.0])) is None
            assert capsys.readouterr().out == ""
        elif why == "fewer_records":
            stats = {"pipeline": {"first_tokens": WINDOW[:2]}}
            assert read(None, stats, _served([151.0, 435.0, 21.0])) is None
            assert "2 records for 3 finished requests" in capsys.readouterr().out
        else:
            # a request that got its first token and then failed left a record
            # and is not ok: the last two records are not the two ok requests'
            stats = {"pipeline": {"first_tokens": WARM_UP + WINDOW}}
            assert read(None, stats, _served([151.0, None, 21.0])) is None
            assert "sum to" in capsys.readouterr().out
        assert _reader("prefill_dev_wait_ms")(None, {}, _served([151.0])) is None

    def test_a_prefill_programs_wait_is_from_its_spans_end_to_its_start(self):
        from benchmark import first_tokens as ft
        from benchmark.span_reader import Module, Span, Spans

        def span(start, end, **stats):
            return Span("engine.prefill_dispatch", start, end, stats, "decode-scheduler")

        spans = Spans((10.0, 14.0), spans=[
            span(10.100, 10.101, req=7, base=0, final=0),
            span(10.200, 10.202, req=7, base=1024, final=1),
            span(10.900, 10.903, req=8, base=0, final=1),
        ], modules=[[
            Module("jit_engine_decode_seg32", 9.9, 10.25),
            Module("jit_engine_prefill_from", 10.25, 10.30),   # waited out the segment
            Module("jit_engine_prefill_from", 10.30, 10.35),
            Module("jit_engine_prefill_from", 10.902, 10.95),  # began inside its span
        ]])
        waits = ft.prefill_waits(spans)
        assert [(s.stats["req"], s.stats["final"]) for s, _m, _w in waits] == [
            (7, 0), (7, 1), (8, 1)]
        assert [w for _s, _m, w in waits] == pytest.approx([149.0, 98.0, 0.0])


# ---- the trainer's phases --------------------------------------------------


def test_fit_leaves_one_data_and_one_dispatch_a_step(tmp_path):
    from kubedl_tpu.api.topology import MeshSpec
    from kubedl_tpu.training.data import SyntheticTokens
    from kubedl_tpu.models import llama
    from kubedl_tpu.parallel.mesh import build_mesh
    from kubedl_tpu.training.trainer import TrainConfig, Trainer

    cfg = TrainConfig(model=llama.TINY, global_batch=2, seq_len=16, steps=3)
    trainer = Trainer(cfg, build_mesh(MeshSpec({"data": 1}), jax.devices()[:1]))
    state = trainer.init_state()
    seen = []
    with capture(tmp_path) as cap:
        trainer.fit(iter(SyntheticTokens(2, 16, llama.TINY.vocab_size)), state=state,
                    on_step=lambda i, _m: seen.append(i))
    steps = sorted(cap.named("train.step"), key=lambda e: e[2])
    assert [e[4]["step_num"] for e in steps] == seen == [0, 1, 2]
    for step in steps:
        inside = [e[1] for e in cap.events
                  if e is not step and step[2] <= e[2] and e[3] <= step[3]]
        assert inside.count("train.data") == 1
        assert inside.count("train.dispatch") == 1
        assert inside.count("train.on_step") == 1
    # the first step's loss is fetched inside it; the last one's after the loop
    assert len(cap.named("train.fetch")) == 2


# ---- the catalog rule ------------------------------------------------------


class TestSpanCatalogRule:
    def test_undocumented_phase_literal_is_flagged(self, tmp_path):
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "observability.md").write_text(
            "| Span | Layer | Meaning |\n|---|---|---|\n| `engine.tick` | engine | a tick |\n")
        src = tmp_path / "kubedl_tpu" / "loop.py"
        src.parent.mkdir()
        src.write_text(
            "from kubedl_tpu.observability.tracing import TRACER\n"
            "with TRACER.phase('engine.tick'):\n"
            "    with TRACER.phase('x'):\n"
            "        pass\n"
            "with TRACER.step('y', 0):\n"
            "    pass\n")
        ctx = analysis_engine.parse_file(src, tmp_path)
        found = {f.snippet for f in span_names.check_project(tmp_path, [ctx])}
        assert found == {"undocumented-span:x", "undocumented-span:y"}

    def test_the_tree_passes(self):
        root = analysis_engine.REPO_ROOT
        contexts = [analysis_engine.parse_file(p, root)
                    for p in analysis_engine.iter_source_files(root)]
        assert span_names.check_project(root, [c for c in contexts if c]) == []
