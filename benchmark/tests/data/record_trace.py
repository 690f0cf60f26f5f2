"""Records the small trace that benchmark/tests/test_trace_reader.py reads.

Run on the chip, by hand: ``python benchmark/tests/data/record_trace.py OUT_DIR``.
It traces a few steps of a scanned matmul program, one flash-attention
forward and backward, and an idle gap under a ``TraceAnnotation``, then
prints the planes, lines and first events it recorded.
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "../../..")))


def main(out_dir: str) -> int:
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, jax.device_count(), flush=True)

    def step(x, w):
        def body(c, wl):
            return jnp.tanh(c @ wl), None
        y, _ = jax.lax.scan(body, x, w)
        return y

    step = jax.jit(step)
    x = jnp.ones((256, 512), jnp.bfloat16)
    w = jnp.ones((4, 512, 512), jnp.bfloat16) * 0.01
    step(x, w).block_until_ready()
    flash = None
    if dev.platform == "tpu":
        from kubedl_tpu.ops import flash_attention_module as fa

        q = jnp.ones((1, 512, 8, 128), jnp.bfloat16)
        kv = jnp.ones((1, 512, 2, 128), jnp.bfloat16)

        def loss(q, k, v):
            return fa.flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

        flash = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        jax.block_until_ready(flash(q, kv, kv))
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            step(x, w).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.pause"):
        time.sleep(0.02)
    if flash is not None:
        with jax.profiler.TraceAnnotation("bench.flash"):
            jax.block_until_ready(flash(q, kv, kv))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    print("trace bytes", os.path.getsize(path))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print("PLANE", plane.name, len(lines))
        for line in lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for e in events[:12]:
                stats = {k: (str(v)[:60]) for k, v in e.stats}
                print("     ", repr(e.name)[:90], e.start_ns, e.duration_ns, stats if len(stats) < 8 else list(stats)[:12])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
