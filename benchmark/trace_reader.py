"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but JAX.

``jax.profiler.ProfileData`` reads the file: planes, their lines, and events
with a start and a duration in nanoseconds. What a v5e's trace looks like
(jax 0.9.0, libtpu 0.0.34; ``benchmark/tests/data/small.xplane.pb``):

- one plane per chip, ``/device:TPU:<n>``; its line ``XLA Ops`` holds every
  HLO operation that ran, named by its whole HLO text
  (``%fusion.13 = bf16[256,512]{...} fusion(...)``). A ``while`` (a scanned
  layer stack) is one event that *contains* its body's events on the same line,
  so sums are taken over self time: an event's duration less its children's.
  Pallas kernels are ``custom-call`` events with the target
  ``tpu_custom_call``, named after the traced function around them (here
  ``%jvp__.1`` forward and ``%transpose_jvp___.1`` backward; in the trainer's
  step ``closed_call.N`` forward and ``checkpoint.N`` backward);
- ``/host:CPU`` holds one line a thread; ``jax.profiler.TraceAnnotation`` spans
  appear there under their own names. The benchmark's are all ``bench.*``.
  Host and device clocks agree to a millisecond or two, which is enough to say
  what the host was doing during an idle gap of tens of milliseconds.

The traced window is the ``bench.trace_window`` annotation that the harness
holds open from after ``start_trace`` to before ``stop_trace``; device events
are clipped to it.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

WINDOW_ANNOTATION = "bench.trace_window"
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"= \(?([a-z0-9]+\[[0-9,]*\])")
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")


@dataclass
class Op:
    """One device operation: times in seconds from the trace's own zero."""
    text: str
    start: float
    end: float
    self_s: float = 0.0

    @property
    def name(self) -> str:
        return self.text.split(" = ", 1)[0].lstrip("%")

    @property
    def opcode(self) -> str:
        m = _OPCODE.search(self.text.split(" = ", 1)[-1])
        return m.group(1) if m else ""

    @property
    def label(self) -> str:
        """Name, first result shape and opcode: what a reader can recognise."""
        m = _SHAPE.search(self.text)
        return " ".join(p for p in (self.name, m.group(1) if m else "", self.opcode) if p)


@dataclass
class Trace:
    devices: List[List[Op]] = field(default_factory=list)
    annotations: List[Tuple[str, float, float]] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _self_times(ops: List[Op]) -> None:
    """Each op's duration less the ops nested inside it (same line, so nesting
    is containment)."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    for op in ops:
        op.self_s = op.end - op.start
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end + 1e-12:
            stack[-1].self_s -= op.end - op.start
        stack.append(op)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``; the window is ``bench.trace_window`` where the
    trace has one, else the span of the device's operations."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [
                Op(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events
            ]
            _self_times(ops)
            trace.devices.append(ops)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        trace.annotations.append(
                            (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        )
    windows = [(s, e) for n, s, e in trace.annotations if n == WINDOW_ANNOTATION]
    if windows:
        trace.window = max(windows, key=lambda w: w[1] - w[0])
    else:
        all_ops = [o for ops in trace.devices for o in ops]
        if all_ops:
            trace.window = (min(o.start for o in all_ops), max(o.end for o in all_ops))
    return trace


def _clipped(ops: Sequence[Op], window: Tuple[float, float]) -> List[Tuple[float, float]]:
    lo, hi = window
    return [(max(o.start, lo), min(o.end, hi)) for o in ops if o.end > lo and o.start < hi]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Disjoint, sorted cover of ``intervals``."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_seconds(trace: Trace) -> float:
    """Seconds in which some operation ran on the device, averaged over chips."""
    if not trace.devices:
        return 0.0
    per_chip = [
        sum(e - s for s, e in union(_clipped(ops, trace.window))) for ops in trace.devices
    ]
    return sum(per_chip) / len(per_chip)


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy/window, as a percentage; None where no operation was traced."""
    if trace.window_s <= 0 or not any(trace.devices):
        return None
    return 100.0 * (1.0 - busy_seconds(trace) / trace.window_s)


def window_ops(trace: Trace) -> List[Op]:
    """Every chip's operations that touch the traced window."""
    lo, hi = trace.window
    return [o for ops in trace.devices for o in ops if o.end > lo and o.start < hi]


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` operations with the most summed self time, over all chips:
    ``[[label, seconds], ...]``."""
    sums: Dict[str, float] = {}
    for op in window_ops(trace):
        if op.opcode not in CONTAINERS:
            sums[op.label] = sums.get(op.label, 0.0) + op.self_s
    return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def op_seconds(trace: Trace, wanted: Callable[[Op], bool]) -> Tuple[float, int]:
    """Summed self time and count of the window's operations that ``wanted``
    picks, averaged over chips."""
    picked = [o for o in window_ops(trace) if wanted(o)]
    chips = max(1, len(trace.devices))
    return sum(o.self_s for o in picked) / chips, len(picked) // chips


def is_kernel(op: Op) -> bool:
    """A Pallas (Mosaic) kernel: a ``custom-call`` whose target is
    ``tpu_custom_call`` (a step holds other, empty custom-calls too)."""
    return op.opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in op.text


#: a pause between two operations of one program is not a gap worth a line
MIN_GAP_S = 50e-6


def idle_gaps(trace: Trace, n: int = 10, unlabelled: str = "program") -> List[List]:
    """The ``n`` longest gaps with no operation on the first chip, each named
    by the ``bench.*`` annotation that covers most of it (the window's own
    aside), else ``unlabelled``: ``[[label, seconds], ...]``."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    busy = union(_clipped(trace.devices[0], trace.window))
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= MIN_GAP_S]
    spans = [a for a in trace.annotations if a[0] != WINDOW_ANNOTATION]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        cover: Dict[str, float] = {}
        for name, a, b in spans:
            overlap = min(e, b) - max(s, a)
            if overlap > 0:
                cover[name] = cover.get(name, 0.0) + overlap
        best = max(cover.items(), key=lambda kv: kv[1], default=(unlabelled, 0.0))
        label = best[0] if best[1] >= 0.5 * (e - s) else unlabelled
        out.append([label, e - s])
    return out


def exposed_collective_seconds(trace: Trace) -> float:
    """Seconds inside collective operations during which no other operation
    ran on that chip, averaged over chips."""
    if not trace.devices:
        return 0.0
    total = 0.0
    for ops in trace.devices:
        lo, hi = trace.window
        inside = [o for o in ops if o.end > lo and o.start < hi and o.opcode not in CONTAINERS]
        coll = union([(o.start, o.end) for o in inside if o.opcode.startswith(COLLECTIVES)])
        other = union([(o.start, o.end) for o in inside if not o.opcode.startswith(COLLECTIVES)])
        covered = 0.0
        for s, e in coll:
            covered += sum(max(0.0, min(e, b) - max(s, a)) for a, b in other)
        total += sum(e - s for s, e in coll) - covered
    return total / len(trace.devices)
