"""Open loop: requests are sent on a schedule, whether or not earlier ones came back.

Independent users make an open loop. The mix fixes the rate
(``rate_per_s``); arrivals are a Poisson process whose gaps are drawn once from
the mix's ``shape_seed``, scaled so that the last arrival falls inside the
window, and replayed in the same order by every seed (see ``_serve.draw_requests``). Every request is timed from when it was
*due*, so a stall's cost to later requests counts, and the generator's own
lateness (hand-over minus due) is reported beside it.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

import numpy as np

from benchmark.generators import _serve


def schedule(mix: Dict[str, Any], seed: int, seconds: float, vocab: int) -> List[Dict[str, Any]]:
    """The window's requests with their due times (seconds from its start):
    a function of the mix, ``seed`` and ``seconds`` alone."""
    n = max(1, int(round(float(mix["rate_per_s"]) * float(seconds))))
    gaps = np.random.default_rng(int(mix["shape_seed"]) + 1).exponential(1.0, n)
    gaps *= float(seconds) * n / (n + 1) / gaps.sum()
    requests = _serve.draw_requests(mix, n, seed, vocab)
    due = np.cumsum(gaps)
    for r, d in zip(requests, due):
        r["due_s"] = float(d)
    return requests


def drive(program: Any, mix: Dict[str, Any], seed: int, seconds: float, vocab: int,
          t0: float) -> List[Dict[str, Any]]:
    requests = schedule(mix, seed, seconds, vocab)
    futures = []
    with ThreadPoolExecutor(max_workers=int(mix.get("max_in_flight", 96)),
                            thread_name_prefix="bench-client") as pool:
        for req in requests:
            due = t0 + req["due_s"]
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            futures.append(pool.submit(_serve.call, program, req, due, t0))
        return [f.result() for f in futures]


def run_cell(ctx: Any) -> Dict[str, Any]:
    return _serve.run_cell(ctx, drive)
