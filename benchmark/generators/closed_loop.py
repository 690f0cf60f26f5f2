"""Closed loop: a fixed number of clients, each sending its next request when
the last one's reply is back.

Batch callers (evaluation, summarising stored documents) make a closed loop: a
slow system receives less load. The mix fixes ``clients``; the requests are the
mix's fixed sequence of sizes, dealt to the clients in turn. A request is due when its client became free.

The sequence has to outlast the window (``requests_drawn`` at least twice what
any run finishes): a client that finds its share used up stops sending, and the
rate then reads the file's length and not the system. When the first client ran
dry, in seconds from the window's start, is the result line's ``ran_dry_s``
(null: the loop stayed loaded to the end).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Dict, List, Optional

from benchmark.generators import _serve


def drive(program: Any, mix: Dict[str, Any], seed: int, seconds: float, vocab: int,
          t0: float, found: Optional[Dict[str, Any]] = None) -> List[Dict[str, Any]]:
    """``found``, where given, gets ``ran_dry_s``."""
    clients = int(mix["clients"])
    requests = _serve.draw_requests(mix, int(mix["requests_drawn"]), seed, vocab)
    records: List[List[Dict[str, Any]]] = [[] for _ in range(clients)]
    end = t0 + float(seconds)
    dry: List[float] = []  # when a client found its share used up, inside the window

    def client(c: int) -> None:
        for req in requests[c::clients]:
            due = time.perf_counter()
            if due >= end:
                return
            records[c].append(_serve.call(program, req, due, t0))
        now = time.perf_counter()
        if now < end:
            dry.append(now - t0)

    threads = [threading.Thread(target=client, args=(c,), name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if found is not None:
        found["ran_dry_s"] = min(dry) if dry else None
    return sorted((r for rs in records for r in rs), key=lambda r: r["due_s"])


def run_cell(ctx: Any) -> Dict[str, Any]:
    found: Dict[str, Any] = {}
    out = _serve.run_cell(ctx, functools.partial(drive, found=found))
    out["record"]["ran_dry_s"] = found.get("ran_dry_s")
    return out
