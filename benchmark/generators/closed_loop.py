"""Closed loop: a fixed number of clients, each sending its next request when
the last one's reply is back.

Batch callers (evaluation, summarising stored documents) make a closed loop: a
slow system receives less load. The mix fixes ``clients``; the requests are the
mix's fixed sequence of sizes, dealt to the clients in turn. A request is due when its client became free.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

from benchmark.generators import _serve


def drive(program: Any, mix: Dict[str, Any], seed: int, seconds: float, vocab: int,
          t0: float) -> List[Dict[str, Any]]:
    clients = int(mix["clients"])
    requests = _serve.draw_requests(mix, int(mix["requests_drawn"]), seed, vocab)
    records: List[List[Dict[str, Any]]] = [[] for _ in range(clients)]
    end = t0 + float(seconds)

    def client(c: int) -> None:
        for req in requests[c::clients]:
            due = time.perf_counter()
            if due >= end:
                return
            records[c].append(_serve.call(program, req, due, t0))

    threads = [threading.Thread(target=client, args=(c,), name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted((r for rs in records for r in rs), key=lambda r: r["due_s"])


def run_cell(ctx: Any) -> Dict[str, Any]:
    return _serve.run_cell(ctx, drive)
