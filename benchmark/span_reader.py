"""The program's own phase spans and the device's program executions, from the
traced run's ``.xplane.pb``.

``trace_reader.load`` keeps the benchmark's ``bench.*`` annotations and the
device's ``XLA Ops`` line. Since PR 24 the program writes its loops' phases to
the profiler itself (``engine.*`` on the scheduler thread, ``train.*`` in
``Trainer.fit``; ``docs/observability.md`` has the catalog) and names every
jitted program, so the same file also holds:

- on ``/host:CPU``, one line a thread: the phase spans, with their attributes
  as event stats (``k``, ``rows``, ``take``, ``slots``, ``bucket``, ``tokens``);
- on each ``/device:TPU:<n>`` plane, the line ``XLA Modules``: one event for
  each execution of a program, named ``jit_<function>(<fingerprint>)``, from
  the program's first operation to its last.

The harness hands a reader the ``trace_reader.Trace``, not the file, so
``load`` finds the file again: the newest ``.xplane.pb`` under
``<checkout>/.cache/bench_trace/*/``, accepted only if its
``bench.trace_window`` is the ``Trace``'s window to the nanosecond, which only
the same file can be. The parse is kept for the readers that share it. A
program from before PR 24 writes no phase span and names its serve programs
``jit__lambda``: every reader then finds nothing and returns None.
"""

from __future__ import annotations

import glob
import os
import statistics
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import trace_reader

ROOT = Path(__file__).resolve().parents[1]
#: the spans that only group others: time under them alone is not named
GROUPS = ("engine.tick", "train.step")
Interval = Tuple[float, float]


@dataclass
class Span:
    """One phase span: seconds on the trace's clock, the thread's line."""
    name: str
    start: float
    end: float
    stats: Dict[str, Any]
    line: str

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class Module:
    """One execution of a program on a chip; ``name`` without the fingerprint."""
    name: str
    start: float
    end: float


@dataclass
class Spans:
    window: Interval
    spans: List[Span] = field(default_factory=list)
    modules: List[List[Module]] = field(default_factory=list)  # one list a chip

    def in_window(self, name: str) -> List[Span]:
        """The spans of that name that start inside the window."""
        lo, hi = self.window
        return [s for s in self.spans if s.name == name and lo <= s.start < hi]

    def leaves(self, prefix: str) -> List[Span]:
        return [s for s in self.spans if s.name.startswith(prefix) and s.name not in GROUPS]


def parse(path: str) -> Spans:
    """Everything this module reads from one ``.xplane.pb``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = Spans((0.0, 0.0))
    windows: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            out.modules.append([
                Module(e.name.split("(", 1)[0], e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)
                for line in plane.lines if line.name == "XLA Modules"
                for e in line.events
            ])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    start, end = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                    if e.name == trace_reader.WINDOW_ANNOTATION:
                        windows.append((start, end))
                    elif e.name.startswith(("engine.", "train.")):
                        out.spans.append(Span(e.name, start, end, dict(e.stats), line.name))
    if windows:
        out.window = max(windows, key=lambda w: w[1] - w[0])
    out.spans.sort(key=lambda s: s.start)
    return out


_LOADED: Dict[Tuple[str, float], Spans] = {}


def newest_xplane() -> Optional[str]:
    paths = glob.glob(str(ROOT / ".cache" / "bench_trace" / "*" / "plugins" / "profile"
                          / "*" / "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load(trace: Optional[trace_reader.Trace]) -> Optional[Spans]:
    """The spans of the file ``trace`` was read from, or None: an untraced
    run, no file, or a file whose window is not ``trace``'s."""
    path = newest_xplane() if trace is not None else None
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()  # one run reads one file
        _LOADED[key] = parse(path)
        print(program_table(_LOADED[key]), flush=True)
    spans = _LOADED[key]
    same = all(abs(a - b) < 1e-9 for a, b in zip(spans.window, trace.window))
    return spans if same and trace.window_s > 0 else None


# ---- the device's programs --------------------------------------------------


def clipped(items: Sequence[Any], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(i.start, lo), min(i.end, hi)) for i in items if i.end > lo and i.start < hi]


def program_seconds(spans: Spans, prefix: str = "") -> Dict[str, Tuple[int, float]]:
    """name -> (executions that touch the window, their seconds inside it) on
    the first chip, for programs whose name starts with ``prefix``."""
    out: Dict[str, Tuple[int, float]] = {}
    for m in (spans.modules[0] if spans.modules else []):
        if not m.name.startswith(prefix):
            continue
        for s, e in clipped([m], spans.window):
            n, t = out.get(m.name, (0, 0.0))
            out[m.name] = (n + 1, t + e - s)
    return out


def program_table(spans: Spans) -> str:
    table = program_seconds(spans)
    total = sum(t for _n, t in table.values())
    rows = ", ".join(f"{k} x{n} {t:.4f}s" for k, (n, t) in
                     sorted(table.items(), key=lambda kv: -kv[1][1]))
    return (f"programs on chip 0 in the traced window of {spans.window[1] - spans.window[0]:.4f} s: "
            f"{rows or 'none'}; all programs {total:.4f} s")


def decode_step_seconds(spans: Spans) -> Optional[float]:
    """Device seconds a decode step: over the ``jit_engine_decode_seg<k>``
    executions that lie wholly inside the window, their time over their
    ``k``. (A clipped execution would count all its steps for part of its
    time.)"""
    lo, hi = spans.window
    seconds, steps, runs = 0.0, 0, 0
    for m in (spans.modules[0] if spans.modules else []):
        if not m.name.startswith("jit_engine_decode_seg") or m.start < lo or m.end > hi:
            continue
        digits = m.name[len("jit_engine_decode_seg"):].split("_", 1)[0]
        if digits.isdigit():
            seconds += m.end - m.start
            steps += int(digits)
            runs += 1
    if not steps:
        return None
    print(f"decode segments wholly inside the traced window: {runs}, {steps} steps, "
          f"{seconds:.4f} s on chip 0", flush=True)
    return seconds / steps


def program_share(spans: Spans, prefix: str) -> Optional[float]:
    """Percent of the window in which a program named ``prefix*`` ran on the
    first chip; None where the trace names no engine program at all."""
    if not program_seconds(spans, "jit_engine_"):
        return None
    width = spans.window[1] - spans.window[0]
    return 100.0 * sum(t for _n, t in program_seconds(spans, prefix).values()) / width


# ---- rows and tokens computed against rows and tokens used -----------------


def use_share(spans: Spans, name: str, used: str, per_slot: str) -> Optional[float]:
    """Percent: sum of ``used`` over sum of ``slots * per_slot``, over the
    window's spans of ``name`` that carry all three."""
    num = den = 0
    for s in spans.in_window(name):
        if all(k in s.stats for k in (used, per_slot, "slots")):
            num += int(s.stats[used])
            den += int(s.stats["slots"]) * int(s.stats[per_slot])
    return 100.0 * num / den if den else None


# ---- idle time under a named phase -----------------------------------------


def idle_gaps(trace: trace_reader.Trace) -> List[Interval]:
    """The first chip's gaps of ``trace_reader.MIN_GAP_S`` and more between
    two operations of the window. ``trace_reader.idle_gaps`` also counts the
    stretch from the window's start to the first operation and from the last
    one to its end; those are left out here, because they are the capture's
    edges and not the program's: an operation still running when the capture
    stops is not in the file (in the training cell that reads as 0.9 ms of
    idle at the window's end, my chip run, PR 24), and neither is the host span
    that was open then, so nothing could name it."""
    if not trace.devices:
        return []
    busy = trace_reader.union(clipped(trace.devices[0], trace.window))
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])
            if b[0] - a[1] >= trace_reader.MIN_GAP_S]


def overlap(a: Interval, spans: Sequence[Interval]) -> float:
    return sum(max(0.0, min(a[1], e) - max(a[0], s)) for s, e in spans)


def idle_named(trace: trace_reader.Trace, spans: Spans, prefix: str) -> Optional[float]:
    """Of the window's idle time on the first chip, the percentage that lies
    under a leaf phase span named ``prefix*``; None where there is no gap, or
    no such span at all (a program that writes none). Prints the idle time by
    span name and the longest gap's."""
    gaps = idle_gaps(trace)
    leaves = spans.leaves(prefix)
    if not gaps or not leaves:
        return None
    total = sum(e - s for s, e in gaps)
    covered = sum(overlap(g, trace_reader.union([(l.start, l.end) for l in leaves]))
                  for g in gaps)
    by_name: Dict[str, float] = {}
    for l in leaves:
        got = overlap((l.start, l.end), gaps)
        if got > 0:
            by_name[l.name] = by_name.get(l.name, 0.0) + got
    longest = max(gaps, key=lambda g: g[1] - g[0])
    # the innermost phase that covers most of it: phases nest (an admission
    # pass inside engine.harvest_host), and the shortest such span says most
    over = [l for l in leaves
            if overlap(longest, [(l.start, l.end)]) >= 0.5 * (longest[1] - longest[0])]
    label = min(over, key=lambda l: l.seconds).name if over else "no one span"
    print(f"idle on chip 0: {total * 1e3:.3f} ms in {len(gaps)} gaps, {covered * 1e3:.3f} ms "
          f"under a {prefix}* phase; by phase (nested phases each count): "
          + ", ".join(f"{k} {v * 1e3:.3f} ms" for k, v in sorted(by_name.items(), key=lambda kv: -kv[1]))
          + f"; the longest gap, {1e3 * (longest[1] - longest[0]):.3f} ms, lies under {label}",
          flush=True)
    return 100.0 * covered / total


# ---- the trainer's own turn a step -----------------------------------------


def train_host_ms(spans: Spans) -> Optional[float]:
    """Median over the window's ``train.step`` spans of their ``train.data``
    plus ``train.dispatch``, in milliseconds. Prints the split."""
    hi = spans.window[1]
    data_ms, dispatch_ms = [], []
    for step in spans.in_window("train.step"):
        if step.end > hi:
            continue
        parts = [s for s in spans.spans if s.line == step.line
                 and step.start <= s.start and s.end <= step.end]
        data = sum(s.seconds for s in parts if s.name == "train.data")
        dispatch = sum(s.seconds for s in parts if s.name == "train.dispatch")
        if data or dispatch:
            data_ms.append(1e3 * data)
            dispatch_ms.append(1e3 * dispatch)
    if not data_ms:
        return None
    both = [a + b for a, b in zip(data_ms, dispatch_ms)]
    # what the summary line's host_ms times, over the traced steps only:
    # from one on_step callback's return to the next one's start
    calls = spans.in_window("train.on_step")
    between = [1e3 * (b.start - a.end) for a, b in zip(calls, calls[1:])]
    print(f"trainer host turn over {len(both)} steps: train.data median "
          f"{statistics.median(data_ms):.3f} ms, train.dispatch median "
          f"{statistics.median(dispatch_ms):.3f} ms; from one on_step to the next, median "
          f"{statistics.median(between) if between else float('nan'):.3f} ms", flush=True)
    return statistics.median(both)
