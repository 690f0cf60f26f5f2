"""Host-side scheduler microbench: per-tick overhead with the device
stubbed out.

The double-buffered pipeline's whole point is that host work (dispatch
bookkeeping, harvest copy-out handling, slot finalization, admission)
hides behind device compute — which only works while that host work stays
small. This bench puts one fake (`StubRunner`) where a real `LlamaEngine`'s
`ModelRunner` stood, drives `_loop_once` directly, and
reports the tick timings the engine itself accounts
(`pipeline_stats()`). With a no-op device, tick time IS host overhead.

Runs as part of tier-1 (`pytest -m 'not slow'` via
tests/test_serving.py::TestSchedulerMicrobench) so a host-overhead
regression fails CI instead of waiting for a full bench run, and
standalone:

    JAX_PLATFORMS=cpu python scripts/scheduler_microbench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

#: p50 per-tick host-overhead budget (ms) asserted by the tier-1 test.
#: A steady-state tick is slot bookkeeping + one device_get of a tiny
#: [B, k] int32 array + admission — well under a millisecond on any
#: CPU; 5 ms leaves ~10x headroom for slow shared CI machines while
#: still catching an accidental O(vocab) host copy or per-token Python
#: loop (the r5 overhead bug class this guards against).
TICK_BUDGET_MS = 5.0

#: p50 per-tick budget (ms) for ticks that ride the PREFIX-CACHE path:
#: admission additionally walks the observation trie, matches the
#: prompt, and dispatches a graft. All host-side trie work on prompts of
#: a few hundred tokens — the same 5 ms envelope must hold, or prefix
#: reuse would pay back its prefill savings as scheduler overhead.
PREFIX_BUDGET_MS = 5.0

#: p50 per-tick budget (ms) for the PAGED layout: on top of the plain
#: tick, every dispatch re-uploads the pos/block-table mirrors (two tiny
#: int32 arrays, [B] + [B, max_seq/block_size]) and admission/finalize
#: run allocator alloc/free. All of it is O(batch * blocks-per-row) host
#: work on arrays of a few dozen ints — the same 5 ms envelope must
#: hold, or paging's occupancy win would be paid back as per-tick
#: scheduler overhead.
PAGED_BUDGET_MS = 5.0

#: p50 per-tick budget (ms) for the paged engine running the BLOCKED
#: attention kernel (kubedl_tpu/models/paged_attention.py): the kernel
#: is pure device compute, so the scheduler tick — mirror uploads, slot
#: bookkeeping, the kv_attention plumbing itself — must cost exactly
#: what the gather tick costs. A separate per-dispatch timing guards the
#: compiled kernel's HOST dispatch cost: the lax path lowers to a
#: scan-heavy executable with far more XLA ops than one gather, and an
#: accidental re-trace per call (e.g. a non-hashable kwarg breaking the
#: jit cache) would show up here as milliseconds, not microseconds.
BLOCKED_BUDGET_MS = 5.0

#: p95 per-plan budget (ms) for the auto-parallelism planner (kubedl_tpu/
#: planner/): plan() runs inside reconcile_job, so it must stay a rounding
#: error next to the engine's per-pass work. The search space is the
#: divisor lattice of a slice's chips (≤ ~200 candidates at 256 chips),
#: each priced by a handful of closed-form collective formulas — pure
#: Python arithmetic. 50 ms leaves ~10x headroom over the worst observed
#: catalog entry on a shared CI machine while still catching an
#: accidental combinatorial blow-up or per-candidate allocation storm.
PLANNER_BUDGET_MS = 50.0

#: p95 budget (ms) for planning the gradient-bucket scatter layout
#: (kubedl_tpu/training/buckets.py): plan_grad_buckets runs on the host
#: inside Trainer.__init__ for every (re)build — greedy first-fit over a
#: few hundred parameter leaves, pure Python arithmetic, no jax. 5 ms
#: leaves ~50x headroom on a shared CI machine while catching an
#: accidental O(leaves^2) pass or a stray device round-trip sneaking
#: into trainer construction.
BUCKET_BUDGET_MS = 5.0

#: p50 per-call budget (µs) for a DISARMED tracer span. Every hot path
#: — scheduler tick, router dispatch, reconcile — calls TRACER.span /
#: TRACER.record / TRACER.phase unconditionally; with `enabled = False` the call must
#: collapse to one attribute test returning a shared null handle (a phase
#: keeps its clock, since the tick's accounting reads it: one small object
#: and two perf_counter calls, no annotation).
#: Sub-microsecond on any CPU; 5 µs leaves slack for slow shared CI
#: machines while catching an accidental allocation, lock acquisition,
#: or id-minting sneaking onto the disarmed path.
TRACING_DISARMED_US = 5.0

#: p50 per-tick budget (ms) for CHUNKED admission (continuous batching):
#: on top of the paged tick, every admission tick runs the FIFO chunk
#: scheduler — sort the not-yet-prefilled rows by arrival, carve the
#: token budget into block-aligned chunks, and advance per-row progress
#: cursors. All O(batch) host arithmetic; the same 5 ms envelope must
#: hold, or bounding TTFT with chunking would pay itself back as
#: per-tick scheduler overhead on every decode step.
CHUNKED_BUDGET_MS = 5.0

#: p95 per-key budget (µs) for the shard-map route (kubedl_tpu/shards/
#: shardmap.py): every workqueue enqueue, store write, and watch
#: delivery in the sharded control plane calls ``lookup(key)``, so HRW
#: scoring must stay noise next to the reconcile it routes. One crc32
#: per shard over a short string (memoized for hot keys); 5 µs leaves
#: wide headroom on shared CI machines while catching an accidental
#: per-call allocation storm, a busted memo cache, or a switch to a
#: Python-level hash loop.
SHARDMAP_LOOKUP_BUDGET_US = 5.0

#: per-event budget (µs) for a COALESCED workqueue add — the absorbed
#: path (item already dirty/cooling) every event storm rides: one lock
#: round-trip, two set probes, a counter bump. 10 µs leaves headroom on
#: shared CI machines while catching an accidental heap push, dict
#: rebuild, or timestamp scan sneaking onto the hot absorb path.
WORKQUEUE_ADD_BUDGET_US = 10.0

#: pickups-per-key ceiling for an event storm under coalescing: a burst
#: of N events on an already-reconciled key must cost ~1 follow-up
#: pickup (the window-edge re-add), not N. 3 allows the window to roll
#: over once on a slow machine while still failing the
#: reconcile-per-event shape this guards against.
WORKQUEUE_STORM_PICKUPS_PER_KEY = 3.0


class StubRunner:
    """Stands where an engine's ``ModelRunner`` stood: every program the
    tick dispatches returns at once, with arrays made ahead of time, and
    everything else (the cache, the mirror upload, the block format) is
    the real runner's."""

    def __init__(self, real):
        import jax
        import jax.numpy as jnp

        from kubedl_tpu.serving.server import LlamaEngine

        self._real = real
        B = real.max_batch
        self._last = jnp.ones((B, 1), jnp.int32)
        self._ids = jnp.ones((B,), jnp.int32)
        self._logits = jnp.zeros((B, 8), jnp.float32)  # shape never inspected
        self._seg_toks = {
            k: jnp.ones((B, k), jnp.int32)
            for k in LlamaEngine.SEGMENT_BUCKETS
        }
        jax.block_until_ready(
            (self._last, self._ids, self._logits, self._seg_toks)
        )

    def __getattr__(self, name):
        return getattr(self._real, name)

    # whole-prompt and suffix prefill alike, whatever batch they are
    # handed (a paged engine's is compact: rows + the logits array)
    def prefill(self, params, toks, lens, starts=None, rows=None, acc=None,
                live_to=None):
        return self._logits

    def sample_first(self, logits, temps, key):
        return self._ids

    def merge_chain(self, last, ids, mask):
        return last

    def decode_segment(self, n_steps, greedy, params, tokens, temps, key,
                       live_to=None, rows=None, takes=None):
        return self._seg_toks[n_steps], self._last, key

    def graft(self, k, v, row, length):
        pass

    def extract(self, row, p_len):
        return None, None


def build_stub_engine(max_batch: int = 4, max_seq: int = 128,
                      kv_layout: str = "contiguous",
                      kv_attention: str = "gather",
                      prefill_chunk_tokens: int = 0):
    """A real LlamaEngine whose device calls are instant stubs: the
    scheduler loop, slot machinery, chain/pending bookkeeping, and
    accounting all run for real; only the model math is elided."""
    from kubedl_tpu.serving.server import LlamaEngine

    eng = LlamaEngine(preset="tiny", max_batch=max_batch, max_seq=max_seq,
                      kv_layout=kv_layout, kv_attention=kv_attention,
                      prefill_chunk_tokens=prefill_chunk_tokens)
    # freeze the background scheduler: the bench thread drives ticks
    with eng._cv:
        eng._stop = True
        eng._cv.notify_all()
    eng._thread.join(timeout=10)
    eng._stop = False
    eng._runner = StubRunner(eng._runner)
    return eng


def _drive(eng, slots, budget_ticks: int):
    """Queue ``slots``, warm one tick, reset counters, then tick the
    pipeline to completion. Returns (wall_ms, tokens, pipeline_stats)."""
    with eng._cv:
        eng._waiting.extend(slots)
        eng._cv.notify_all()
    # warm tick (first segment-size/temps paths), then reset counters
    eng._loop_once()
    with eng._cv:
        for k in eng._pipe:
            eng._pipe[k] = 0.0 if isinstance(eng._pipe[k], float) else 0
        eng._pipe_recent.clear()
    t0 = time.perf_counter()
    ticks = 0
    while not all(s.done.is_set() for s in slots):
        eng._loop_once()
        ticks += 1
        if ticks > budget_ticks:
            raise RuntimeError("microbench did not converge")
    wall_ms = (time.perf_counter() - t0) * 1e3
    tokens = sum(len(s.out_ids) for s in slots)
    return wall_ms, tokens, eng.pipeline_stats()


def run_microbench(requests: int = 32, max_tokens: int = 32,
                   max_batch: int = 4) -> dict:
    """Push ``requests`` stub requests through the pipeline tick-by-tick
    and return the engine's own per-tick accounting plus derived
    per-token host overhead."""
    from kubedl_tpu.serving.server import _Slot

    eng = build_stub_engine(max_batch=max_batch)
    try:
        slots = [
            _Slot([1, 2, 3], max_tokens, 0.0) for _ in range(requests)
        ]
        wall_ms, tokens, pipe = _drive(
            eng, slots, requests * max_tokens + 100
        )
        assert all(
            len(s.out_ids) == max_tokens for s in slots
        ), "stub pipeline dropped tokens"
        return {
            "requests": requests,
            "max_tokens": max_tokens,
            "max_batch": max_batch,
            "ticks": pipe["ticks"],
            "tokens": tokens,
            "wall_ms": round(wall_ms, 2),
            "tick_ms_p50": pipe.get("tick_ms_p50", 0.0),
            "dispatch_ms_p50": pipe.get("dispatch_ms_p50", 0.0),
            "harvest_ms_p50": pipe.get("harvest_ms_p50", 0.0),
            "host_ms_p50": pipe.get("host_ms_p50", 0.0),
            "host_overhead_ms_per_token": round(wall_ms / max(tokens, 1), 4),
            "budget_ms": TICK_BUDGET_MS,
            "within_budget": pipe.get("tick_ms_p50", 0.0) <= TICK_BUDGET_MS,
        }
    finally:
        eng.close()


def run_prefix_microbench(requests: int = 32, max_tokens: int = 8,
                          max_batch: int = 4, prefix_len: int = 64) -> dict:
    """Host overhead of the prefix-cache admission path: every request
    shares a ``prefix_len``-token prefix already stored in the cache, so
    each admission walks the observation trie, longest-prefix-matches,
    pins, and dispatches a (stubbed) graft + suffix prefill. Reports the
    engine's tick accounting plus an isolated match+graft microtiming."""
    import numpy as np

    from kubedl_tpu.serving.server import _Slot

    eng = build_stub_engine(max_batch=max_batch)
    try:
        prefix = list(range(3, 3 + prefix_len))
        payload = np.zeros((1,), np.float32)
        assert eng._pcache is not None, "stub engine must enable the cache"
        assert eng._pcache.insert(prefix, payload, payload, prefix_len)
        # isolated host cost of one match (trie walk + pin) + graft
        # dispatch, without the rest of the tick around it
        probe = prefix + [999]
        iters = 2000
        t0 = time.perf_counter()
        for _ in range(iters):
            e, n = eng._pcache.match(probe)
            eng._runner.graft(e.k, e.v, 0, n)
            eng._pcache.unpin(e)
        match_graft_ms = (time.perf_counter() - t0) * 1e3 / iters
        hits0 = eng._pcache.stats()["hits"]

        slots = [
            _Slot(prefix + [1000 + j], max_tokens, 0.0)
            for j in range(requests)
        ]
        _drive(eng, slots, requests * max_tokens + 100)
        st = eng._pcache.stats()
        pipe = eng.pipeline_stats()
        tick_p50 = pipe.get("tick_ms_p50", 0.0)
        return {
            "requests": requests,
            "prefix_len": prefix_len,
            "hits": st["hits"] - hits0,
            "tokens_saved": st["tokens_saved"],
            "ticks": pipe["ticks"],
            "tick_ms_p50": tick_p50,
            "match_graft_ms": round(match_graft_ms, 4),
            "budget_ms": PREFIX_BUDGET_MS,
            "within_budget": (
                tick_p50 <= PREFIX_BUDGET_MS
                and match_graft_ms <= PREFIX_BUDGET_MS
            ),
        }
    finally:
        eng.close()


def run_paged_microbench(requests: int = 32, max_tokens: int = 32,
                         max_batch: int = 4) -> dict:
    """Host overhead of the PAGED layout's block-table bookkeeping:
    every dispatch re-uploads the pos/block-table mirrors and admission/
    finalize run allocator alloc/free, all on top of the plain tick.
    Reports the engine's tick accounting, an isolated mirror-upload
    microtiming, and proves block conservation (the pool drains back to
    empty once every request finishes)."""
    import jax

    from kubedl_tpu.serving.server import _Slot

    eng = build_stub_engine(max_batch=max_batch, kv_layout="paged")
    try:
        # isolated host cost of one mirror upload pair (pos + block
        # table), the per-dispatch tax unique to the paged layout
        iters = 2000
        t0 = time.perf_counter()
        for _ in range(iters):
            eng._runner.upload_mirrors(eng._bt_host, eng._pos_host)
            jax.block_until_ready(eng._runner.cache)
        mirror_upload_ms = (time.perf_counter() - t0) * 1e3 / iters

        slots = [
            # distinct prompts so no run rides the prefix cache: this
            # bench isolates the block-table path
            _Slot([1, 2, 3 + j], max_tokens, 0.0)
            for j in range(requests)
        ]
        wall_ms, tokens, pipe = _drive(
            eng, slots, requests * max_tokens + 100
        )
        assert all(
            len(s.out_ids) == max_tokens for s in slots
        ), "stub paged pipeline dropped tokens"
        st = eng._alloc.stats()
        assert st["used"] == 0, f"block leak: {st}"
        tick_p50 = pipe.get("tick_ms_p50", 0.0)
        return {
            "requests": requests,
            "max_tokens": max_tokens,
            "max_batch": max_batch,
            "kv_blocks": eng.kv_blocks,
            "block_size": eng.kv_block_size,
            "ticks": pipe["ticks"],
            "tokens": tokens,
            "wall_ms": round(wall_ms, 2),
            "tick_ms_p50": tick_p50,
            "host_ms_p50": pipe.get("host_ms_p50", 0.0),
            "mirror_upload_ms": round(mirror_upload_ms, 4),
            "blocks_leaked": st["used"],
            "budget_ms": PAGED_BUDGET_MS,
            "within_budget": (
                tick_p50 <= PAGED_BUDGET_MS
                and mirror_upload_ms <= PAGED_BUDGET_MS
            ),
        }
    finally:
        eng.close()


def run_chunked_admission_microbench(requests: int = 16,
                                     prompt_len: int = 48,
                                     max_tokens: int = 8,
                                     max_batch: int = 4,
                                     chunk: int = 16,
                                     decoders: int = 0,
                                     decoder_tokens: int = 64) -> dict:
    """Host overhead of CHUNKED admission (continuous batching): every
    tick with queued prompts runs the FIFO chunk scheduler — arrival
    sort, block-aligned budget carving, per-row progress cursors — on
    top of the paged tick. With the device stubbed, the tick must fit
    the same envelope as slot-granularity admission; reports chunk
    accounting so a budget miscount (chunks != ceil(len/budget)) fails
    loudly too. ``decoders`` requests of a one-chunk prompt and a long
    budget go first: with every request in a row (``requests + decoders
    <= max_batch``) they decode while the others' prompts are mid-way,
    which is the mix in which the tick counts the prefill work it owes
    and runs short segments for it (``segments_short``)."""
    from kubedl_tpu.serving.server import _Slot

    eng = build_stub_engine(max_batch=max_batch, kv_layout="paged",
                            prefill_chunk_tokens=chunk)
    try:
        assert eng.prefill_chunk_tokens == chunk
        slots = [
            _Slot([200 + j, 2, 3], decoder_tokens, 0.0)
            for j in range(decoders)
        ] + [
            # distinct multi-chunk prompts (no prefix-cache rides)
            _Slot([j + 1] + list(range(5, 4 + prompt_len)), max_tokens, 0.0)
            for j in range(requests)
        ]
        wall_ms, tokens, pipe = _drive(
            eng, slots, requests * (max_tokens + prompt_len)
            + decoders * decoder_tokens + 100
        )
        assert all(
            len(s.out_ids) == s.max_tokens for s in slots
        ), "chunked stub pipeline dropped tokens"
        body = eng.metrics.registry.render()
        chunks = next(
            float(l.split()[-1]) for l in body.splitlines()
            if l.startswith("kubedl_tpu_serving_admission_chunks ")
        )
        want = requests * -(-prompt_len // chunk) + decoders  # ceil each
        assert chunks == want, (chunks, want)
        st = eng._alloc.stats()
        assert st["used"] == 0, f"block leak: {st}"
        tick_p50 = pipe.get("tick_ms_p50", 0.0)
        return {
            "requests": requests,
            "prompt_len": prompt_len,
            "chunk_tokens": chunk,
            "chunks": int(chunks),
            "ticks": pipe["ticks"],
            "tokens": tokens,
            "wall_ms": round(wall_ms, 2),
            "tick_ms_p50": tick_p50,
            "host_ms_p50": pipe.get("host_ms_p50", 0.0),
            "segments_by_k": pipe["segments_by_k"],
            "segments_short": pipe["segments_short"],
            "blocks_leaked": st["used"],
            "budget_ms": CHUNKED_BUDGET_MS,
            "within_budget": tick_p50 <= CHUNKED_BUDGET_MS,
        }
    finally:
        eng.close()


def run_blocked_attention_microbench(requests: int = 32,
                                     max_tokens: int = 32,
                                     max_batch: int = 4,
                                     iters: int = 200) -> dict:
    """Host overhead of the blocked paged-attention path: (1) drive the
    stub paged engine with ``kv_attention="blocked"`` — the tick must fit
    the same envelope as the gather tick, proving the kernel selection
    plumbing adds no per-tick host work; (2) time one dispatch of the
    COMPILED blocked kernel at a trivial shape where device compute is
    negligible, so per-call wall is the host dispatch + jit-cache-lookup
    cost of the scan-heavy executable."""
    import jax
    import jax.numpy as jnp

    from kubedl_tpu.serving.server import _Slot

    eng = build_stub_engine(max_batch=max_batch, kv_layout="paged",
                            kv_attention="blocked")
    try:
        assert eng.kv_attention == "blocked"
        slots = [
            _Slot([1, 2, 3 + j], max_tokens, 0.0)
            for j in range(requests)
        ]
        wall_ms, tokens, pipe = _drive(
            eng, slots, requests * max_tokens + 100
        )
        assert all(
            len(s.out_ids) == max_tokens for s in slots
        ), "stub blocked pipeline dropped tokens"
        st = eng._alloc.stats()
        assert st["used"] == 0, f"block leak: {st}"
        tick_p50 = pipe.get("tick_ms_p50", 0.0)
    finally:
        eng.close()

    # isolated compiled-kernel dispatch at a tiny decode shape: S=1,
    # 4 rows, 8 blocks/row of 16 — microseconds of compute on any host
    from kubedl_tpu.models import paged_attention as pa

    B, S, H, KV, hd, BS, MB = 4, 1, 4, 2, 16, 16, 8
    NB = 1 + B * MB
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    kp = jax.random.normal(key, (NB, BS, KV, hd), jnp.float32)
    vp = jax.random.normal(key, (NB, BS, KV, hd), jnp.float32)
    bt = jnp.arange(1, 1 + B * MB, dtype=jnp.int32).reshape(B, MB)
    starts = jnp.full((B,), BS * MB - 2, jnp.int32)
    fn = jax.jit(lambda q, kp, vp, bt, st: pa.paged_attention(
        q, kp, vp, bt, st, kernel="lax"))
    jax.block_until_ready(fn(q, kp, vp, bt, starts))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(q, kp, vp, bt, starts)
    jax.block_until_ready(r)
    dispatch_ms = (time.perf_counter() - t0) * 1e3 / iters

    return {
        "requests": requests,
        "max_tokens": max_tokens,
        "max_batch": max_batch,
        "ticks": pipe["ticks"],
        "tokens": tokens,
        "wall_ms": round(wall_ms, 2),
        "tick_ms_p50": tick_p50,
        "host_ms_p50": pipe.get("host_ms_p50", 0.0),
        "kernel_dispatch_ms": round(dispatch_ms, 4),
        "blocks_leaked": st["used"],
        "budget_ms": BLOCKED_BUDGET_MS,
        "within_budget": (
            tick_p50 <= BLOCKED_BUDGET_MS
            and dispatch_ms <= BLOCKED_BUDGET_MS
        ),
    }


def run_planner_microbench() -> dict:
    """Host overhead of plan(): every catalog topology x every zoo model
    (the full admission matrix), reporting per-plan wall-time percentiles
    against PLANNER_BUDGET_MS. Infeasible combinations (PlanError) count —
    proving infeasibility walks the same candidate lattice."""
    from kubedl_tpu.api.topology import SLICE_CATALOG
    from kubedl_tpu.planner import MODEL_ZOO, PlanError, plan

    times = []
    candidates = 0
    plans = 0
    infeasible = 0
    for topo in SLICE_CATALOG.values():
        for model in MODEL_ZOO.values():
            t0 = time.perf_counter()
            try:
                p = plan(model, topo)
                candidates += p.candidates_evaluated
                plans += 1
            except PlanError:
                infeasible += 1
            times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    p50 = times[len(times) // 2]
    p95 = times[int(len(times) * 0.95)]
    return {
        "plans": plans,
        "infeasible": infeasible,
        "candidates_evaluated": candidates,
        "plan_ms_p50": round(p50, 3),
        "plan_ms_p95": round(p95, 3),
        "plan_ms_max": round(times[-1], 3),
        "budget_ms": PLANNER_BUDGET_MS,
        "within_budget": p95 <= PLANNER_BUDGET_MS,
    }


def run_bucket_microbench(iters: int = 200) -> dict:
    """Host overhead of the gradient-bucket scatter plan: price a
    realistic large-model leaf census (a few hundred leaves spanning
    norm-scale bytes to embedding GiBs) ``iters`` times and report the
    per-plan percentiles against BUCKET_BUDGET_MS."""
    from kubedl_tpu.training.buckets import plan_grad_buckets

    # ~8B-class census: 80 stacked layers x (7 matmul leaves + 2 norms)
    # + embed/head/final-norm, fp32 grad bytes
    leaf_bytes = []
    for _ in range(80):
        leaf_bytes += [4 * 4096 * 4096] * 4   # attention projections
        leaf_bytes += [4 * 4096 * 14336] * 3  # ffn
        leaf_bytes += [4 * 4096] * 2          # rms norms
    leaf_bytes += [4 * 128256 * 4096] * 2 + [4 * 4096]
    times = []
    plan = None
    for _ in range(iters):
        t0 = time.perf_counter()
        plan = plan_grad_buckets(leaf_bytes)
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    p50 = times[len(times) // 2]
    p95 = times[int(len(times) * 0.95)]
    return {
        "leaves": len(leaf_bytes),
        "buckets": plan.n_buckets,
        "scattered_fraction": round(plan.scattered_fraction, 4),
        "plan_ms_p50": round(p50, 4),
        "plan_ms_p95": round(p95, 4),
        "plan_ms_max": round(times[-1], 4),
        "budget_ms": BUCKET_BUDGET_MS,
        "within_budget": p95 <= BUCKET_BUDGET_MS,
    }


def run_shardmap_microbench(keys: int = 100_000, shards: int = 4) -> dict:
    """Per-key cost of the HRW shard route over ``keys`` distinct
    ``ns/name`` keys (every lookup a memo MISS — the worst case; hot
    reconcile keys hit the memo and cost a dict probe), plus the memo-hit
    path timed separately, against SHARDMAP_LOOKUP_BUDGET_US. Also
    reports the balance spread so a degenerate hash (everything on one
    shard) fails loudly here, not in a scale run."""
    from kubedl_tpu.shards.shardmap import ShardMap

    all_keys = [f"ns-{i % 7}/job-{i:06d}" for i in range(keys)]
    sm = ShardMap(shards)
    # per-key timing over cold keys (every one a memo miss): individual
    # samples make the p95 robust to scheduler preemption on shared CI —
    # a descheduling poisons only the keys it lands on, not a whole
    # batch average. perf_counter_ns call-pair overhead (~0.1 µs) rides
    # inside each sample; it is noise against the 5 µs budget.
    ns = time.perf_counter_ns
    lookup = sm.lookup
    times = []
    for k in all_keys:
        t0 = ns()
        lookup(k)
        times.append((ns() - t0) / 1e3)
    times.sort()
    p50 = times[len(times) // 2]
    p95 = times[int(len(times) * 0.95)]

    hot = all_keys[-1]
    iters = 100_000
    t0 = time.perf_counter()
    for _ in range(iters):
        sm.lookup(hot)
    hit_us = (time.perf_counter() - t0) * 1e6 / iters

    counts = sm.spread(all_keys)
    lo, hi = min(counts.values()), max(counts.values())
    return {
        "keys": keys,
        "shards": shards,
        "lookup_us_p50": round(p50, 4),
        "lookup_us_p95": round(p95, 4),
        "memo_hit_us": round(hit_us, 4),
        "spread_min": lo,
        "spread_max": hi,
        "spread_imbalance": round(hi / max(lo, 1), 3),
        "budget_us": SHARDMAP_LOOKUP_BUDGET_US,
        "within_budget": p95 <= SHARDMAP_LOOKUP_BUDGET_US,
    }


def run_workqueue_microbench(keys: int = 200,
                             events_per_key: int = 50) -> dict:
    """Workqueue burst coalescing under an enqueue storm: ``keys``
    already-reconciled keys each take ``events_per_key`` rapid-fire
    re-adds (the 10-pods-churn-per-job shape), then the queue drains.
    Reports dequeue count vs event count — the whole point of coalescing
    is that the storm costs ~1 follow-up pickup per key, not one per
    event — plus the per-event cost of the absorbed-add hot path."""
    from kubedl_tpu.core.workqueue import WorkQueue

    window = 0.02
    q = WorkQueue(coalesce_window=window)
    # phase 1: every key reconciled once (stamps its last-get time)
    for i in range(keys):
        q.add(i)
    while True:
        batch = q.get_batch(max_items=64, timeout=0.01)
        if not batch:
            break
        for item in batch:
            q.done(item)
    # phase 2: the storm, timed — every add lands within the window of
    # its key's pickup, so adds 2..N ride the absorbed fast path
    events = keys * events_per_key
    t0 = time.perf_counter()
    for i in range(keys):
        for _ in range(events_per_key):
            q.add(i)
    add_us = (time.perf_counter() - t0) * 1e6 / events
    # phase 3: drain — count how many pickups the storm actually cost
    pickups = 0
    deadline = time.time() + 5.0
    while time.time() < deadline:
        batch = q.get_batch(max_items=64, timeout=window)
        if batch:
            pickups += len(batch)
            for item in batch:
                q.done(item)
        elif len(q) == 0:
            break
    per_key = pickups / max(keys, 1)
    return {
        "keys": keys,
        "events": events,
        "coalesce_window_ms": window * 1e3,
        "storm_pickups": pickups,
        "pickups_per_key": round(per_key, 3),
        "coalesced": q.coalesced,
        "add_us": round(add_us, 4),
        "add_budget_us": WORKQUEUE_ADD_BUDGET_US,
        "pickups_per_key_budget": WORKQUEUE_STORM_PICKUPS_PER_KEY,
        "within_budget": (
            per_key <= WORKQUEUE_STORM_PICKUPS_PER_KEY
            and pickups >= keys  # final state never dropped
            and add_us <= WORKQUEUE_ADD_BUDGET_US
        ),
    }


def run_tracing_microbench(calls: int = 200_000) -> dict:
    """Per-call cost of the DISARMED tracing fast path: a fresh local
    Tracer with ``enabled = False``, timing the four hot-path entry
    points (``span`` context manager, ``begin``/``finish``, ``record``,
    and the ``phase`` context manager with attributes, as the engine's
    tick and the trainer's loop open it) against TRACING_DISARMED_US. Uses a local instance so the shared
    TRACER singleton's arm state is untouched."""
    from kubedl_tpu.observability.tracing import Tracer

    t = Tracer()
    t.enabled = False

    t0 = time.perf_counter()
    for _ in range(calls):
        with t.span("bench.noop"):
            pass
    span_us = (time.perf_counter() - t0) * 1e6 / calls

    t0 = time.perf_counter()
    for _ in range(calls):
        t.begin("bench.noop").finish()
    begin_us = (time.perf_counter() - t0) * 1e6 / calls

    t0 = time.perf_counter()
    for _ in range(calls):
        t.record("bench.noop", duration=0.0)
    record_us = (time.perf_counter() - t0) * 1e6 / calls

    t0 = time.perf_counter()
    for _ in range(calls):
        with t.phase("bench.noop", k=32, rows=2) as ph:
            ph.set(take=40)
    phase_us = (time.perf_counter() - t0) * 1e6 / calls

    assert not t.spans(), "disarmed tracer must record nothing"
    worst = max(span_us, begin_us, record_us, phase_us)
    return {
        "calls": calls,
        "span_us": round(span_us, 4),
        "begin_finish_us": round(begin_us, 4),
        "record_us": round(record_us, 4),
        "phase_us": round(phase_us, 4),
        "budget_us": TRACING_DISARMED_US,
        "within_budget": worst <= TRACING_DISARMED_US,
    }


def main() -> int:
    out = run_microbench()
    out["prefix"] = run_prefix_microbench()
    out["paged"] = run_paged_microbench()
    out["chunked_admission"] = run_chunked_admission_microbench()
    out["owed_prefill"] = run_chunked_admission_microbench(
        requests=12, decoders=2, max_batch=16, decoder_tokens=100)
    out["blocked_attention"] = run_blocked_attention_microbench()
    out["planner"] = run_planner_microbench()
    out["buckets"] = run_bucket_microbench()
    out["tracing"] = run_tracing_microbench()
    out["shardmap"] = run_shardmap_microbench()
    out["workqueue"] = run_workqueue_microbench()
    print(json.dumps(out, indent=2))
    ok = (out["within_budget"] and out["prefix"]["within_budget"]
          and out["paged"]["within_budget"]
          and out["chunked_admission"]["within_budget"]
          and out["owed_prefill"]["within_budget"]
          and out["blocked_attention"]["within_budget"]
          and out["planner"]["within_budget"]
          and out["buckets"]["within_budget"]
          and out["tracing"]["within_budget"]
          and out["shardmap"]["within_budget"]
          and out["workqueue"]["within_budget"])
    return 0 if ok else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
