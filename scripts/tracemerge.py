#!/usr/bin/env python
"""Fuse per-replica trace dumps into ONE Chrome/Perfetto trace.

Every process exports its own spans (router + each serving replica via
``GET /v1/trace``) with timestamps already anchored to the wall-clock epoch
(kubedl_tpu/observability/tracing.py), so fusing is pure bookkeeping:
assign each input file a distinct ``pid`` (Perfetto renders one process
track per pid), emit a ``process_name`` metadata event naming the source
file, and concatenate the events. Cross-process spans line up on the
shared epoch timeline, and span/parent ids (carried in ``args``) let you
follow one request router → prefill replica → decode replica.

Input shape: flight-recorder / ``/v1/trace`` JSON, ``{"spans": [<span
dicts>]}`` (also a bare list of span dicts).

Usage::

    python scripts/tracemerge.py router.json prefill.json decode.json \
        -o merged.json [--trace-id <32 hex>]

Open ``merged.json`` in https://ui.perfetto.dev or chrome://tracing.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any, Dict, List


def _span_to_event(span: Dict[str, Any], pid: int,
                   tids: Dict[str, int]) -> Dict[str, Any]:
    """Span dict (span_to_dict shape) -> Chrome 'X' complete event."""
    tid = tids.setdefault(str(span.get("thread", "main")), len(tids) + 1)
    args = dict(span.get("attrs") or {})
    for key in ("trace_id", "span_id", "parent_id"):
        if span.get(key):
            args[key] = span[key]
    return {
        "name": span.get("name", "?"),
        "ph": "X",
        "ts": float(span.get("ts", 0.0)) * 1e6,  # epoch s -> µs
        "dur": float(span.get("duration_ms", 0.0)) * 1e3,  # ms -> µs
        "pid": pid,
        "tid": tid,
        "args": args,
    }


def load_events(path: Path, pid: int) -> List[Dict[str, Any]]:
    """Read one dump, turning every span into an event on ``pid``."""
    data = json.loads(path.read_text())
    spans = data.get("spans", data) if isinstance(data, dict) else data
    if not isinstance(spans, list):
        raise ValueError(f"{path}: unrecognized trace dump shape")
    tids: Dict[str, int] = {}
    return [_span_to_event(s, pid, tids) for s in spans]


def merge(paths: List[Path], trace_id: str = "") -> Dict[str, Any]:
    events: List[Dict[str, Any]] = []
    for pid, path in enumerate(paths, start=1):
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": path.stem},
        })
        for ev in load_events(path, pid):
            if trace_id and ev.get("ph") == "X" and (
                (ev.get("args") or {}).get("trace_id") != trace_id
            ):
                continue
            events.append(ev)
    return {"traceEvents": events}


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("inputs", nargs="+", type=Path,
                    help="per-process trace dumps (/v1/trace span JSON)")
    ap.add_argument("-o", "--output", type=Path, default=Path("merged.json"))
    ap.add_argument("--trace-id", default="",
                    help="keep only spans of one trace (32 hex chars)")
    args = ap.parse_args(argv)
    out = merge(args.inputs, args.trace_id)
    args.output.write_text(json.dumps(out, indent=1))
    n = sum(1 for e in out["traceEvents"] if e.get("ph") == "X")
    print(f"{args.output}: {n} spans from {len(args.inputs)} process(es)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
