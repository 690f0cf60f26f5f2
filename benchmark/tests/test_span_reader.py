"""The span reader on a small trace recorded on a v5e
(``data/record_spans.py``, my chip run, PR 24): a scheduler thread's three
ticks around two named programs, an idle gap under ``engine.idle_wait`` and
one under ``engine.tick`` alone, then three ``train.step``. Every expected
number below was worked out by hand from the events that script printed
(nanoseconds on the trace's clock), not by the code under test."""
from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark import span_reader, trace_reader

DATA = Path(__file__).parent / "data"
PATH = str(DATA / "spans.xplane.pb")
WINDOW_NS = (43_828_608, 91_804_498)  # bench.trace_window: start, start + 47,975,890
# the three gaps between operations (a fourth, 1.9 ms, runs to the window's
# end: the capture's edge, not counted)
GAPS_NS = [(45_144_244, 11_321_236), (57_277_108, 6_799_456), (64_887_969, 6_024_262)]
SERVE, TRAIN = {"kind": "serve"}, {"kind": "train"}
READERS = ("decode_step_dev_ms", "prefill_dev_share", "decode_row_use", "prefill_tok_use",
           "idle_named.serve", "train_host_ms")


@pytest.fixture(scope="module")
def trace():
    return trace_reader.load(PATH)


@pytest.fixture()
def read(monkeypatch):
    """A metric's reader, pointed at the recorded file instead of a run's."""
    monkeypatch.setattr(span_reader, "newest_xplane", lambda: PATH)
    return lambda name: harness.load_reader("layer_metrics", name)


def test_what_the_file_holds(trace):
    spans = span_reader.parse(PATH)
    assert [round(t * 1e9) for t in spans.window] == list(WINDOW_NS)
    assert spans.window == trace.window
    assert [m.name for m in spans.modules[0]] == [
        "jit_engine_prefill_from"] + ["jit_engine_decode_seg4"] * 3 + ["jit_train_step"] * 3
    assert {s.line for s in spans.spans} == {"python3"}
    first = spans.in_window("engine.prefill_dispatch")[0]
    assert first.stats == {"bucket": 256, "rows": 1, "tokens": 200, "slots": 4}
    assert [s.stats["take"] for s in spans.in_window("engine.decode_dispatch")] == [7, 12, 2]
    assert [s.stats["step_num"] for s in spans.in_window("train.step")] == [0, 1, 2]
    assert [s.name for s in spans.leaves("train.")].count("train.step") == 0
    table = span_reader.program_seconds(spans)
    assert table["jit_train_step"][0] == 3 and table["jit_engine_decode_seg4"][0] == 3
    # 6,330,753 + 6,330,934 + 6,332,867 ns
    assert table["jit_train_step"][1] == pytest.approx(18_994_554e-9, rel=1e-9)


def test_each_reader_against_the_hand_computed_value(trace, read):
    # three segments of four steps wholly inside: 811,861 + 811,635 + 811,413 ns over 12
    assert read("decode_step_dev_ms")(trace, {}, SERVE) == pytest.approx(2_434_909 / 12 * 1e-6, rel=1e-9)
    # the prefill program started before the window: 44,330,246 - 43,828,608 ns of it inside
    assert read("prefill_dev_share")(trace, {}, SERVE) == pytest.approx(
        100 * 501_638 / 47_975_890, rel=1e-9)
    assert read("decode_row_use")(trace, {}, SERVE) == pytest.approx(100 * (7 + 12 + 2) / (3 * 4 * 4))
    assert read("prefill_tok_use")(trace, {}, SERVE) == pytest.approx(100 * 200 / (4 * 256))
    # data + dispatch a step: 5,388,040, 433,270, 447,600 ns; the median
    assert read("train_host_ms")(trace, {}, TRAIN) == pytest.approx(0.4476, rel=1e-9)


def test_idle_time_under_a_named_phase(trace, read):
    gaps = span_reader.idle_gaps(trace)
    assert [(round(a * 1e9), round((b - a) * 1e9)) for a, b in gaps] == GAPS_NS
    idle_ns = sum(n for _s, n in GAPS_NS)  # 24,144,954
    # gap 1: harvest_wait 1,882,624 + harvest_host 3,789 + idle_wait 9,378,812
    # gap 2: idle_wait's tail 193,720 + decode_dispatch 355,400 + harvest_wait 1,279,100
    #        + harvest_host 3,930; the 5 ms sleep lies under engine.tick alone
    # gap 3: decode_dispatch 316,070 + harvest_wait 1,358,090 + harvest_host 3,220
    named_ns = 11_265_225 + 1_832_150 + 1_677_380
    assert read("idle_named.serve")(trace, {}, SERVE) == pytest.approx(100 * named_ns / idle_ns, rel=1e-6)
    # of the train.* leaves only train.data reaches into a gap: 70,912,231 - 66,818,748
    # (no cell has a gap under a trainer's phase yet, so no reader asks with this prefix)
    assert span_reader.idle_named(trace, span_reader.parse(PATH), "train.") == pytest.approx(
        100 * 4_093_483 / idle_ns, rel=1e-6)


def test_the_longest_gap_is_named_in_the_printed_line(trace, capsys):
    span_reader.idle_named(trace, span_reader.parse(PATH), "engine.")
    out = capsys.readouterr().out
    assert "the longest gap, 11.321 ms, lies under engine.idle_wait" in out
    assert "engine.idle_wait 9.573 ms" in out  # 9,378,812 + 193,720 ns


def test_a_trace_of_another_file_is_refused(trace, read):
    lo, hi = trace.window
    other = trace_reader.Trace(trace.devices, trace.annotations, (lo, hi + 1e-6))
    assert span_reader.load(other) is None
    assert span_reader.load(None) is None
    for name in READERS:
        assert read(name)(other, {}, SERVE if "train" not in name else TRAIN) is None
        assert read(name)(None, {}, SERVE) is None


def test_nothing_to_read_is_none(trace, read):
    # a train record given to a serve reader, and the other way round
    for name in READERS:
        wrong = SERVE if "train" in name else TRAIN
        assert read(name)(trace, {}, wrong) is None
    # no gap between operations: one operation covers the window
    lo, hi = trace.window
    busy = trace_reader.Trace([[trace_reader.Op("%f = f32[] fusion()", lo, hi)]], [], trace.window)
    assert span_reader.idle_gaps(busy) == []
    assert span_reader.idle_named(busy, span_reader.parse(PATH), "engine.") is None
    # a program from before PR 24: no phase span, no engine program name
    spans = span_reader.parse(PATH)
    bare = span_reader.Spans(spans.window, [], [[span_reader.Module("jit__lambda", lo, hi)]])
    assert span_reader.decode_step_seconds(bare) is None
    assert span_reader.program_share(bare, "jit_engine_prefill") is None
    assert span_reader.use_share(bare, "engine.decode_dispatch", "take", "k") is None
    assert span_reader.idle_named(trace, bare, "engine.") is None
    assert span_reader.train_host_ms(bare) is None


def test_a_dispatch_that_ran_nothing_is_not_counted():
    lo = 1.0
    spans = span_reader.Spans((lo, lo + 1), [
        span_reader.Span("engine.decode_dispatch", lo + 0.1, lo + 0.2, {}, "t"),  # reserve left no row
        span_reader.Span("engine.decode_dispatch", lo + 0.3, lo + 0.4,
                         {"k": 32, "rows": 1, "take": 5, "slots": 4}, "t"),
        span_reader.Span("engine.decode_dispatch", lo + 1.3, lo + 1.4,  # after the window
                         {"k": 32, "rows": 4, "take": 128, "slots": 4}, "t"),
    ])
    assert span_reader.use_share(spans, "engine.decode_dispatch", "take", "k") == pytest.approx(100 * 5 / 128)
