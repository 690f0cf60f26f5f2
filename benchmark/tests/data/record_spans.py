"""Records the small trace that benchmark/tests/test_span_reader.py reads.

Run on the chip, by hand: ``python benchmark/tests/data/record_spans.py OUT_DIR``.
Inside one ``bench.trace_window`` it plays a scheduler thread and a training
loop with the program's own writer (``TRACER.phase`` / ``TRACER.step``): two
named programs of different lengths (``jit_engine_prefill_from``, a longer
matmul stack, and ``jit_engine_decode_seg4``, four steps of a smaller one),
dispatch spans that carry ``k``/``rows``/``take``/``slots`` and
``bucket``/``tokens``/``slots``, one idle gap under a leaf span
(``engine.idle_wait``, 10 ms), one under ``engine.tick`` alone (5 ms), and three
``train.step`` with their parts. It prints every event it recorded; the
test's numbers were computed by hand from that print-out.
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "../../..")))

from kubedl_tpu.observability.tracing import TRACER  # noqa: E402


def main(out_dir: str) -> int:
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, jax.device_count(), flush=True)

    def engine_prefill_from(x, w):
        def body(c, wl):
            return jnp.tanh(c @ wl), None
        return jax.lax.scan(body, x, w)[0]

    def engine_decode_seg4(x, w):
        def step(c, _):
            def body(h, wl):
                return jnp.tanh(h @ wl), None
            return jax.lax.scan(body, c, w)[0], None
        return jax.lax.scan(step, x, None, length=4)[0]

    def train_step(x, w):
        return jax.grad(lambda w: engine_prefill_from(x, w).astype(jnp.float32).sum())(w)

    prefill, decode, train = jax.jit(engine_prefill_from), jax.jit(engine_decode_seg4), jax.jit(train_step)
    w = jnp.ones((16, 2048, 2048), jnp.bfloat16) * 0.01
    rows = jnp.ones((2048, 2048), jnp.bfloat16)
    few = jnp.ones((8, 2048), jnp.bfloat16)
    jax.block_until_ready((prefill(rows, w), decode(few, w), train(rows, w)))

    def segment(k, rows_, take):
        with TRACER.phase("engine.decode_dispatch") as ph:
            out = decode(few, w)
            ph.set(k=k, rows=rows_, take=take, slots=4)
        with TRACER.phase("engine.harvest_wait", what="segment"):
            out.block_until_ready()
        with TRACER.phase("engine.harvest_host"):
            pass

    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as the harness's traced run
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        with TRACER.phase("engine.tick") as tick:
            with TRACER.phase("engine.prefill_dispatch", bucket=256, rows=1, tokens=200, slots=4):
                first = prefill(rows, w)
            segment(4, 2, 7)
            first.block_until_ready()
            tick.set(segments=1, waiting=0)
        with TRACER.phase("engine.idle_wait"):
            time.sleep(0.010)  # the device is idle, and a leaf span says why
        with TRACER.phase("engine.tick") as tick:
            segment(4, 3, 12)
            time.sleep(0.005)  # idle inside the tick, under no leaf
            tick.set(segments=1, waiting=1)
        with TRACER.phase("engine.tick") as tick:
            segment(4, 1, 2)
            tick.set(segments=1, waiting=0)
        pending = None
        for i in range(3):
            with TRACER.step("train.step", i):
                with TRACER.phase("train.data"):
                    batch = jax.device_put(rows)
                with TRACER.phase("train.dispatch"):
                    grads = train(batch, w)
                with TRACER.phase("train.on_step"):
                    if pending is not None:
                        pending.block_until_ready()
                    pending = grads
        with TRACER.phase("train.fetch"):
            pending.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out_dir, "spans.xplane.pb"))
    print("trace bytes", os.path.getsize(path))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            if plane.name.startswith("/device:") and line.name in ("XLA Modules", "XLA Ops"):
                print("LINE", plane.name, repr(line.name), len(events))
                for e in events if line.name == "XLA Modules" else events[:0]:
                    print("   ", e.name, e.start_ns, e.duration_ns)
            for e in events:
                if e.name.startswith(("engine.", "train.", "bench.")):
                    print("SPAN", repr(line.name), e.name, e.start_ns, e.duration_ns, dict(e.stats))
    from benchmark import span_reader, trace_reader

    tr = trace_reader.load(path)
    sp = span_reader.parse(path)
    print("window", tr.window, "busy_s", trace_reader.busy_seconds(tr))
    print("gaps", [(round(a - tr.window[0], 9), round(b - a, 9)) for a, b in span_reader.idle_gaps(tr)])
    print(span_reader.program_table(sp))
    print("decode_step_seconds", span_reader.decode_step_seconds(sp))
    print("prefill_dev_share", span_reader.program_share(sp, "jit_engine_prefill"))
    print("decode_row_use", span_reader.use_share(sp, "engine.decode_dispatch", "take", "k"))
    print("prefill_tok_use", span_reader.use_share(sp, "engine.prefill_dispatch", "tokens", "bucket"))
    print("idle_named.serve", span_reader.idle_named(tr, sp, "engine."))
    print("idle_named.train", span_reader.idle_named(tr, sp, "train."))
    print("train_host_ms", span_reader.train_host_ms(sp))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
