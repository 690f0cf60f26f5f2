"""The generators' schedules: a function of the seed alone, the same work for
every seed, and times taken from when a request was due."""
import json
import time
from pathlib import Path

import pytest

from benchmark.generators import _serve, closed_loop, open_loop

MIX = json.loads((Path(__file__).parents[1] / "traffic" / "chat-open.json").read_text())


def test_schedule_is_a_function_of_the_seed_alone():
    a = open_loop.schedule(MIX, 2**31 + 5, 40, 32768)
    b = open_loop.schedule(MIX, 2**31 + 5, 40, 32768)
    assert a == b
    c = open_loop.schedule(MIX, 7, 40, 32768)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]


def test_every_seed_offers_the_same_work_at_the_same_times():
    a = open_loop.schedule(MIX, 1, 40, 32768)
    c = open_loop.schedule(MIX, 2, 40, 32768)
    assert len(a) == len(c) == round(MIX["rate_per_s"] * 40)
    shape = lambda rs: [(r["due_s"], len(r["prompt"]), r["max_tokens"]) for r in rs]  # noqa: E731
    assert shape(a) == shape(c)
    assert 0 < a[0]["due_s"] and a[-1]["due_s"] < 40
    assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)
    lo, hi = MIX["prompt_tokens"]["min"], MIX["prompt_tokens"]["max"]
    assert all(lo <= len(r["prompt"]) <= hi and 0 <= min(r["prompt"]) and max(r["prompt"]) < 32768
               for r in a)
    assert len({len(r["prompt"]) for r in a}) > len(a) // 2


class _Program:
    def generate(self, prompt, max_tokens):
        time.sleep(0.01)
        return {"token_ids": [1] * max_tokens, "ttft_ms": 5.0}


def test_times_run_from_the_due_time():
    t0 = time.perf_counter()
    due = t0 - 0.050  # handed over 50 ms late
    rec = _serve.call(_Program(), {"prompt": [1, 2], "max_tokens": 3}, due, t0)
    assert rec["ok"] and rec["n_out"] == 3
    assert 50.0 <= rec["late_ms"] < 60.0
    assert rec["ttft_ms"] == 5.0 + rec["late_ms"]
    assert rec["latency_ms"] >= rec["late_ms"] + 10.0


def test_a_failed_request_is_not_ok():
    class Shed:
        def generate(self, prompt, max_tokens):
            return {"error": "shed: queue full"}

    t0 = time.perf_counter()
    rec = _serve.call(Shed(), {"prompt": [1], "max_tokens": 3}, t0, t0)
    assert not rec["ok"] and rec["ttft_ms"] is None


CLOSED = {"clients": 2, "requests_drawn": 8, "shape_seed": 3,
          "prompt_tokens": {"dist": "uniform", "min": 4, "max": 8},
          "output_tokens": {"dist": "uniform", "min": 2, "max": 4}}


class _Paced:
    def __init__(self, seconds):
        self.seconds = seconds

    def generate(self, prompt, max_tokens):
        time.sleep(self.seconds)
        return {"token_ids": [1] * max_tokens, "ttft_ms": 1.0}


@pytest.mark.parametrize("pace_s, runs_dry", [(0.005, True), (0.25, False)])
def test_a_closed_loop_says_when_its_sequence_ran_out(pace_s, runs_dry):
    """Four requests a client: at 5 ms each the sequence is used up long before
    the window's 0.6 s end and ``ran_dry_s`` is that time; at 250 ms each a
    client is still sending at the end and it is null."""
    found = {}
    t0 = time.perf_counter()
    reqs = closed_loop.drive(_Paced(pace_s), CLOSED, 1, 0.6, 64, t0, found)
    if runs_dry:
        assert len(reqs) == CLOSED["requests_drawn"]
        assert 0.0 < found["ran_dry_s"] < 0.3
        assert found["ran_dry_s"] <= max(r["done_s"] for r in reqs) + 0.05
    else:
        assert len(reqs) < CLOSED["requests_drawn"] and found["ran_dry_s"] is None
    assert closed_loop.drive(_Paced(0.0), CLOSED, 1, 0.1, 64, time.perf_counter())  # and without it
