"""One run of one cell: ``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

One process; it holds the chip, builds the cell's program, warms up the
shapes the cell's traffic uses (set-up), measures for ``--seconds``, compares
what the timed path produced with the plain reference, and prints one JSON
object as the last line of its standard output. Each number compared stands
beside its limit three times: in ``compared:`` lines before that object, under
the object's last key, and as the last lines on standard error (what a
driver keeps of a run that was not correct). Everything that belongs to one
configuration, one model family, one traffic mix, one generator kind or one
metric is a file found by the name in ``BENCHMARK.json`` (a family's by the
configuration's ``family`` key); see ``benchmark/README.md``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python lets us see it

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import statistics
import sys
import threading
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

BENCH = Path(__file__).resolve().parent
# libtpu's logs would go to a fixed /tmp/tpu_logs that two checkouts share
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".cache" / "tpu_logs"))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the traced run traces this much of its window, starting a second in: traces
#: are large, what comes back is capped, and tracing slows the host
TRACE_SECONDS = 4.0
TRACE_DELAY = 1.0


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_reader(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``'s ``read``; names may hold dots."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"benchmark.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metrics_of(manifest: Dict[str, Any], section: str, cell: str) -> List[Dict[str, Any]]:
    """The section's metrics that this cell reports: those that list it, and
    those without a list whose end-to-end metric (``moves``) it reports."""
    e2e = {m["name"] for m in manifest["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    out = []
    for m in manifest[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


class Context:
    """What a generator kind gets: the cell's files, and the harness's hooks."""

    def __init__(self, root: Path, manifest: Dict[str, Any], workload: str, seed: int,
                 seconds: float, trace: bool) -> None:
        cells = {w["name"]: w for w in manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}: have {sorted(cells)}")
        self.root = root
        self.manifest = manifest
        self.cell = cells[workload]
        entry = next(c for c in manifest["configs"] if c["name"] == self.cell["config"])
        self.config_name = entry["name"]
        self.config = load_json(root / entry["file"])
        self.mix = load_json(root / manifest["paths"][0] / "traffic" / f"{self.cell['traffic']}.json")
        self.limits = load_json(root / manifest["paths"][0] / "limits" / f"{workload}.json")
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.chips = int(self.cell["chips"])
        self.setup_s: Optional[float] = None
        self.process_t0 = T0
        self.window_t0 = 0.0
        self.compiles_in_window = 0
        self._in_window = False
        self.memory_peak_bytes = 0
        self.trace_dir = root / ".cache" / "bench_trace" / workload
        self._tracer: Optional[threading.Thread] = None
        self.trace_error = ""

    def _on_event(self, event: str, _secs: float, **_kw: Any) -> None:
        if self._in_window and event == COMPILE_EVENT:
            self.compiles_in_window += 1

    def _trace_part(self) -> None:
        import jax

        time.sleep(TRACE_DELAY)
        length = min(TRACE_SECONDS, max(0.5, self.seconds - TRACE_DELAY - 0.5))
        # the Python tracer held the interpreter for two seconds at a trace's
        # start and made the generator that late (PERF.md, PR 23); the
        # benchmark's own spans are TraceAnnotations, which the host tracer keeps
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        try:
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation("bench.trace_window"):
                    time.sleep(length)
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # reported, and the traced run then fails
            self.trace_error = repr(e)

    def begin_window(self) -> float:
        """Set-up ends here; returns the window's zero (``perf_counter``)."""
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self._tracer = threading.Thread(target=self._trace_part, name="bench-tracer")
        self._in_window = True
        self.window_t0 = time.perf_counter()
        self.setup_s = self.window_t0 - T0
        if self._tracer is not None:
            self._tracer.start()
        return self.window_t0

    def end_window(self) -> None:
        self._in_window = False
        if self._tracer is not None:
            self._tracer.join()

    def note_memory_peak(self) -> None:
        """The program's peak on the fullest chip, before the reference runs."""
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
        self.memory_peak_bytes = int(max(peaks, default=0))


def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if require_tpu:
        from benchmark.kernel_costs import load_peaks

        if info["platform"] != "tpu":
            raise SystemExit(f"no accelerator: JAX found {info}; the benchmark measures a TPU")
        if info["count"] < chips:
            raise SystemExit(f"the cell needs {chips} chips, JAX found {info['count']}")
        if info["kind"] not in load_peaks():
            raise SystemExit(f"device kind {info['kind']!r} is not in benchmark/peaks.json")
    return info


def _summary_line(record: Dict[str, Any], stats: Dict[str, Any]) -> str:
    if record["kind"] == "serve":
        from benchmark.stats import percentile

        ok = [r for r in record["requests"] if r["ok"]]
        med = lambda xs: statistics.median(xs) if xs else float("nan")  # noqa: E731
        multi = [r for r in ok if r["n_out"] >= 2]
        decode_ms = sum(r["latency_ms"] - r["ttft_ms"] for r in multi)
        return (
            f"requests attempted {record['attempted']} failed {record['failed']} "
            f"shed {stats.get('shed')} open at the window's end "
            f"{sum(1 for r in record['requests'] if r['done_s'] > record['window_s'])} "
            f"ttft_ms median {med([r['ttft_ms'] for r in ok]):.2f} "
            f"mean {statistics.fmean([r['ttft_ms'] for r in ok] or [float('nan')]):.2f} "
            f"p95 {percentile([r['ttft_ms'] for r in ok] or [float('nan')], 95):.2f} "
            f"p99 {percentile([r['ttft_ms'] for r in ok] or [float('nan')], 99):.2f} (n={len(ok)}) "
            f"tpot_ms median "
            f"{med([(r['latency_ms'] - r['ttft_ms']) / (r['n_out'] - 1) for r in multi]):.3f} "
            f"over all gaps {decode_ms / max(1, sum(r['n_out'] - 1 for r in multi)):.3f} "
            f"(n={len(multi)}) latency_ms a token {sum(r['latency_ms'] for r in ok) / max(1, sum(r['n_out'] for r in ok)):.3f} "
            f"late_ms median {med([r['late_ms'] for r in record['requests']]):.3f} "
            f"output tokens {sum(r['n_out'] for r in ok)}"
        )
    return (
        f"steps in window {record['steps']} step_ms median {record['step_ms_median']:.2f} "
        f"(n={record['steps']}) host_ms median {record['host_ms_median']:.2f} losses first {record['loss_first']:.4f} last {record['loss_last']:.4f}"
    )


def run(root: Path, manifest: Dict[str, Any], workload: str, seed: int, seconds: float,
        trace: bool, require_tpu: bool = True) -> Dict[str, Any]:
    """Drive one run and return the result object (also printed, last)."""
    ctx = Context(root, manifest, workload, seed, seconds, trace)
    device = device_info(ctx.chips, require_tpu)
    print(f"device: platform {device['platform']} kind {device['kind']} count {device['count']}",
          flush=True)
    generator = importlib.import_module(f"benchmark.generators.{ctx.mix['generator']}")
    out = generator.run_cell(ctx)
    record, stats = out["record"], out["stats"]
    record.update(setup_s=ctx.setup_s, chips=ctx.chips, config=ctx.config,
                  device_kind=device["kind"])
    print(f"summary: setup_s {ctx.setup_s:.2f} {_summary_line(record, stats)}", flush=True)
    print(f"compiles_in_window: {ctx.compiles_in_window}; the comparison with the reference "
          f"took {record['check_s']:.1f} s, outside the window and outside setup_s", flush=True)
    compared_lines = [
        f"compared: {c['name']} = {c['value']:.6g} (limit {'>=' if c.get('at_least') else '<='} "
        f"{c['limit']:.6g}) {'ok' if c['ok'] else 'NOT OK'}" for c in record["compared"]]
    print("\n".join(compared_lines), flush=True)
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    result: Dict[str, Any] = {
        "correct": all(c["ok"] for c in record["compared"]),
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": {},
        "device": device,
    }
    tr = None
    if trace:
        from benchmark import trace_reader

        if ctx.trace_error:
            raise SystemExit(f"the profiler failed: {ctx.trace_error}")
        tr = trace_reader.load(trace_reader.find_xplane(str(ctx.trace_dir)))
        busy = trace_reader.busy_seconds(tr)
        if require_tpu and busy <= 0:
            raise SystemExit("the traced window holds no device operation")
        device["busy_s"], device["window_s"] = busy, tr.window_s
        result["breakdown"] = {
            "device_ops": trace_reader.top_ops(tr, 10),
            "idle_gaps": trace_reader.idle_gaps(
                tr, 10, "engine" if record["kind"] == "serve" else "trainer"),
        }
    section = "per_layer" if trace else "end_to_end"
    for m in metrics_of(manifest, section, workload):
        if m["name"] == "setup_s":
            value: Optional[float] = ctx.setup_s
        else:
            value = load_reader("layer_metrics" if trace else "end_to_end", m["name"])(
                tr, stats, record)
        if value is None:
            continue
        if (m["unit"] == "%" and ("roofline" in m["name"] or "mfu" in m["name"])
                and value > 105.0):
            raise SystemExit(f"{m['name']} = {value}% is over 105%: the count or the peak is wrong")
        result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
    if "ran_dry_s" in record:  # a closed loop: when its sequence ran out, or null
        result["ran_dry_s"] = record["ran_dry_s"]
    result["compared"] = {
        c["name"]: {"value": c["value"], "limit": c["limit"],
                    "must_be": "at_least" if c.get("at_least") else "at_most", "ok": c["ok"]}
        for c in record["compared"]}
    print(json.dumps(result), flush=True)
    print("\n".join(compared_lines), file=sys.stderr, flush=True)
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = load_json(ROOT / "BENCHMARK.json")
    run(ROOT, manifest, args.workload, args.seed, args.seconds, bool(args.trace))
    return 0


if __name__ == "__main__":
    sys.exit(main())
