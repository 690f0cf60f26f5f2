"""The trace reader on a small trace recorded on a v5e
(``data/record_trace.py``): three steps of a scanned matmul program, a 20 ms
pause under ``bench.pause``, one flash-attention forward and backward."""
from pathlib import Path

import pytest

from benchmark import trace_reader

TRACE = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return trace_reader.load(str(TRACE))


def test_planes_and_names(trace):
    assert len(trace.devices) == 1
    names = {op.name for op in trace.devices[0]}
    assert {"while", "fusion.13", "jvp__.1", "transpose_jvp___.1"} <= names
    assert {a[0] for a in trace.annotations} == {"bench.step", "bench.pause", "bench.flash"}


def test_union_merges_overlaps():
    assert trace_reader.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]


def test_busy_is_the_union_not_the_sum(trace):
    ops = trace.devices[0]
    busy = trace_reader.busy_seconds(trace)
    # a while contains its body, so the plain sum counts the body twice
    assert busy < sum(o.end - o.start for o in ops)
    # three steps of about 9 us and one 45 us flash program in a 24 ms window
    assert 60e-6 < busy < 80e-6
    assert trace_reader.idle_share(trace) == pytest.approx(100 * (1 - busy / trace.window_s))
    assert trace_reader.idle_share(trace) > 99.0


def test_self_time_takes_the_body_out_of_the_while(trace):
    whiles = [o for o in trace.devices[0] if o.opcode == "while"]
    assert len(whiles) == 3
    for w in whiles:
        assert 0 <= w.self_s < 0.2 * (w.end - w.start)


def test_kernel_sums(trace):
    seconds, count = trace_reader.op_seconds(trace, trace_reader.is_kernel)
    assert count == 2  # one forward, one fused backward
    assert seconds == pytest.approx(17.967e-6 + 17.238e-6, rel=1e-3)
    top = trace_reader.top_ops(trace, 3)
    # twelve runs of the scan body's 2 us fusion outweigh one 18 us kernel
    assert [t[0].split()[0] for t in top] == ["fusion.13", "jvp__.1", "transpose_jvp___.1"]
    assert top[0][1] == pytest.approx(12 * 2.0e-6, rel=0.1)
    assert all("while" not in t[0].split()[-1] for t in top)


def test_gap_labelling(trace):
    gaps = trace_reader.idle_gaps(trace, 3, "trainer")
    # the longest gap is the pause, and the annotation over it names it
    assert gaps[0][0] == "bench.pause" and 0.020 < gaps[0][1] < 0.024
    # host and device clocks differ by a millisecond or so in this trace, so
    # the 0.8 ms gaps between the steps fall outside their bench.step spans
    assert gaps[1][1] < 0.001
    # without annotations a gap belongs to the program
    bare = trace_reader.Trace(trace.devices, [], trace.window)
    assert trace_reader.idle_gaps(bare, 1, "trainer")[0][0] == "trainer"


def test_exposed_collectives_none_on_one_chip(trace):
    assert trace_reader.exposed_collective_seconds(trace) == 0.0
