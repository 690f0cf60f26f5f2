"""JAX inference server: the workload a JAX-framework predictor pod runs.

TPU-native serving path (BASELINE.md target 5): loads the checkpoint the
lineage pipeline published (KUBEDL_MODEL_PATH), jit-compiles the static-
shape KV-cache decode step ONCE (`llama.decode_step` — pre-allocated cache,
no retracing), and serves greedy decoding over HTTP:

- GET  /healthz            -> {"status": "ok"}
- GET  /v1/models          -> model metadata
- POST /v1/generate        -> {"prompt_ids": [...], "max_tokens": N}
                              -> {"token_ids": [...], "latency_ms": ...}

Runs under either container runtime: entrypoint
"kubedl_tpu.serving.server:serve_main" (ThreadRuntime) or
`python -m kubedl_tpu.serving.server` (SubprocessRuntime).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional

from kubedl_tpu import chaos
from kubedl_tpu.observability.tracing import (
    TRACE_HEADER,
    TRACER,
    TraceContext,
    new_span_id,
    new_trace_id,
    parse_trace_header,
    span_to_dict,
)

log = logging.getLogger("kubedl_tpu.serving.server")


class EngineOverloaded(Exception):
    """Queue-depth/age budget exceeded — callers get 503 + Retry-After
    instead of joining a queue that can no longer meet its latency budget
    (docs/robustness.md: shedding early keeps the served fraction fast).

    ``reason`` distinguishes the two admission-stop causes the router
    must treat differently: "overloaded" (come back after Retry-After)
    vs "draining" (this replica is going away — fail over NOW, and the
    rejection never counts against the retry budget because the request
    was never admitted)."""

    def __init__(self, msg: str, retry_after_s: float = 1.0,
                 reason: str = "overloaded") -> None:
        super().__init__(msg)
        self.retry_after_s = retry_after_s
        self.reason = reason


class UnknownModelVersion(ValueError):
    """A request named a model version this engine has not loaded (or
    one already retiring) — a client/config error, not overload: the
    handler answers 400, never 503, so the router does not fail it over
    to a replica that cannot know the version either."""


def _to_host(dev):
    """A device array's value as a numpy array the host owns (blocks until
    the device has it). The copy matters: ``device_get`` may return a
    zero-copy VIEW of the device buffer, which a later donated dispatch
    can reuse."""
    import jax
    import numpy as np

    return np.array(jax.device_get(dev))


#: the parts of a request's time to first token, in the order its record
#: holds them (`LlamaEngine._note_first_token_locked`)
TTFT_PARTS = ("queue", "backlog", "chunks", "first")

#: backend compiles that ended in this process since the first engine was
#: built (jax's monitoring listeners are global and cannot be taken off,
#: so one listener feeds every engine)
_COMPILES = [0]
_COMPILE_LISTENER_ON = False


def _count_compiles() -> int:
    """Start counting (once a process) and return the count so far. The
    event closes around ``compile_or_get_cached``: a program loaded from
    the persistent cache counts as one built does."""
    global _COMPILE_LISTENER_ON
    if not _COMPILE_LISTENER_ON:
        _COMPILE_LISTENER_ON = True
        import jax.monitoring

        def _on_duration(event: str, _secs: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILES[0] += 1

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    return _COMPILES[0]


class _Slot:
    """One in-flight sequence occupying a batch row."""

    def __init__(self, prompt, max_tokens: int, temperature: float,
                 cache_prefix: bool = False, request_id: str = "") -> None:
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.temperature = temperature
        self.cache_prefix = cache_prefix  # request opted into insertion
        self.request_id = request_id  # non-empty: cancellable via cancel()
        self.fed = 0  # inputs consumed (prompt + generated)
        self.pending = 0  # tokens dispatched on device, not yet harvested
        self.cached_len = 0  # prompt tokens grafted from the prefix cache
        #: chunked-prefill progress: prompt tokens whose KV is committed
        #: (grafted prefix + dispatched chunks); -1 = chunking not
        #: started. Stays strictly below len(prompt) until the FINAL
        #: chunk lands, which is also when ``fed`` jumps to the prompt
        #: length and the row becomes a decoding row.
        self.prefill_pos = -1
        self.pinned = None  # PrefixEntry pinned while this row uses it
        self.ttft_ms: Optional[float] = None
        self.out_ids: list = []
        #: disaggregation (docs/serving.md "Disaggregated serving"):
        #: ``handoff`` (a meta dict) marks a prefill-pool slot that
        #: finalizes into a parked KVHandoff after its first token;
        #: ``adopt`` (a KVHandoff) marks a decode-pool slot that skips
        #: prefill and resumes from imported blocks
        self.handoff: Optional[Dict] = None
        self.adopt = None
        #: weight version serving this row (docs/serving.md "Model
        #: lifecycle"): resolved at admission-gate time to a loaded
        #: version id ("" until then = the engine default). Dispatch is
        #: partitioned by version per tick, so one forward never mixes
        #: parameter trees.
        self.version = ""
        #: distributed tracing (docs/observability.md): ``trace`` is the
        #: caller's context (X-Trace-Context); ``span_id`` is this
        #: request's PRE-MINTED engine.request id, so scheduler-side
        #: sub-spans recorded before the request span exists can already
        #: parent under it
        self.trace: Optional[TraceContext] = None
        self.span_id = ""
        #: the way to the first token (docs/observability.md "Where a
        #: request's time to first token goes"), stamped for every
        #: request on ``t0``'s clock: ``seq`` the engine's arrival number,
        #: ``t_row`` the row assigned, ``prefill_t0`` the dispatch of its
        #: first prefill program, ``t_final`` that of the program holding
        #: its last prompt token; ``n_chunks`` the programs it took,
        #: ``seg_mark`` the engine's (segments, steps) dispatched so far
        #: at ``t_row`` and ``ahead`` how many more at ``t_final``
        self.seq = 0
        self.t_row: Optional[float] = None
        self.prefill_t0: Optional[float] = None
        self.t_final: Optional[float] = None
        self.n_chunks = 0
        self.seg_mark = (0, 0)
        self.ahead = (0, 0)
        self.done = threading.Event()
        self.result: Optional[Dict] = None
        self.t0 = time.perf_counter()

    def next_input(self) -> int:
        seq = self.prompt + self.out_ids
        return int(seq[self.fed])


class LlamaEngine:
    """Continuous-batching decode engine (the reference only *models*
    batching in the API, inference_types.go:96-104 — here it is real):
    up to ``max_batch`` sequences share one jitted
    `llama.decode_step_batched` with per-row positions; a scheduler thread
    admits waiting requests into free rows between steps, so concurrent
    requests interleave instead of queueing behind a lock. Static shapes:
    one compile serves every mix of in-flight requests. Decode runs in
    multi-step SEGMENTS with on-device sampling (llama.decode_segment):
    only sampled ids cross to the host, once per segment."""

    #: allowed decode-segment sizes, largest first — a small fixed menu
    #: bounds compiles to len(menu) while still amortizing the dispatch +
    #: host round trip ~32x on long generations; segments shrink to 4 or
    #: 1 while the tick still owes prefill work: a request waits for a
    #: row, or a row waits for its prompt's next chunk (`choose_segment`)
    SEGMENT_BUCKETS = (32, 4, 1)

    def __init__(self, preset: str = "tiny", ckpt_dir: str = "",
                 batch: int = 0, max_seq: int = 0, max_batch: int = 4,
                 quantize: str = "", mesh_axes: Optional[Dict] = None,
                 metrics=None, max_queue_depth: int = 64,
                 max_queue_age_s: float = 30.0,
                 prefix_cache_mb: float = 64.0,
                 prefix_min_len: int = 8,
                 kv_layout: str = "paged", kv_block_size: int = 16,
                 kv_blocks: int = 0, kv_low_watermark: float = 0.05,
                 kv_high_watermark: float = 0.15,
                 spec_k: int = 0, spec_draft: str = "ngram",
                 kv_attention: str = "gather",
                 spec_candidates: int = 1,
                 spec_draft_layers: int = 0,
                 spec_tree: bool = False,
                 prefill_chunk_tokens: int = 0,
                 role: str = "colocated",
                 advertise_prefix_len: int = 8,
                 handoff_ttl_s: float = 30.0,
                 model_version: str = "base") -> None:
        import jax

        from kubedl_tpu.serving.model_runner import make_runner

        if kv_layout not in ("paged", "contiguous"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if role not in ("", "colocated", "prefill", "decode"):
            raise ValueError(
                f"unknown serving role {role!r} "
                "(have: colocated, prefill, decode)"
            )
        #: fleet role, ADVISORY: a prefill/decode engine still serves the
        #: full /v1/generate path (the router's colocated fallback when
        #: the peer pool is down depends on it) — the role only tells the
        #: router how to partition dispatch
        self.role = role or "colocated"
        self.preset_name = preset
        self.advertise_prefix_len = int(advertise_prefix_len)
        self.handoff_ttl_s = float(handoff_ttl_s)
        if kv_attention not in ("gather", "blocked"):
            raise ValueError(
                f"unknown kv_attention {kv_attention!r} "
                "(have: gather, blocked)"
            )
        if mesh_axes and kv_layout == "paged":
            # megatron-sharded serving keeps the CONTIGUOUS layout: the
            # paged pool gather reorders attention reductions enough to
            # flip near-tie argmaxes under row-parallel psum, which would
            # break the sharded==unsharded exactness contract. Paged KV
            # is a single-host batch-density lever.
            kv_layout = "contiguous"
            spec_k = 0
        self.kv_layout = kv_layout
        self._paged = kv_layout == "paged"
        #: which paged-attention implementation the jitted hot paths
        #: compile in: "gather" (the bit-exactness oracle, default) or
        #: "blocked" (models.paged_attention — the online-softmax kernel
        #: that never materializes the [B, max_seq] view; fp-close,
        #: greedy-token-identical). Contiguous engines ignore it.
        self.kv_attention = kv_attention if self._paged else "gather"
        self.spec_k = int(spec_k)
        self.spec_candidates = max(1, int(spec_candidates))
        if self.spec_k and not self._paged:
            raise ValueError(
                "speculative decoding requires kv_layout='paged' (the "
                "verify rollback frees rejected-suffix blocks in place)"
            )
        #: tree speculation (docs/serving.md "Tree speculation"): fold
        #: the N candidate chains into a prefix trie and score every
        #: node in one read-only forward. Needs multi-candidate paged
        #: speculation to mean anything — silently off otherwise (the
        #: same normalization style as the mesh/paged interactions).
        self.spec_tree = (
            bool(spec_tree) and self.spec_k > 0 and self.spec_candidates > 1
        )
        self.max_batch = batch or max_batch
        #: the model's side (serving/model_runner.py): the weights' making,
        #: the K/V arrays and every device program. Nothing below names
        #: the model; the runner never takes ``_cv``.
        self._runner = runner = make_runner(
            preset, max_batch=self.max_batch, max_seq=max_seq,
            paged=self._paged, kv_block_size=kv_block_size,
            kv_attention=self.kv_attention, quantize=quantize,
            mesh_axes=mesh_axes, spec_k=self.spec_k,
            spec_candidates=self.spec_candidates, spec_tree=self.spec_tree,
        )
        self.cfg = runner.cfg
        self.max_seq = runner.max_seq
        #: bytes of recurrent state a row owns beside its blocks (0: none;
        #: docs/serving.md "Models with recurrent state"). The runner's
        #: programs reset, carry and advance it; the engine only counts it,
        #: and refuses what takes a prefix to be a list of blocks
        self._state_bytes = int(runner.state_bytes_per_row)
        if self._state_bytes:
            #: what such a row holds, in a refusal's words
            self._state_held = "holds recurrent state " + (
                "beside its K/V blocks" if runner.block_bytes
                else "and no K/V block")
            if self.role != "colocated":
                raise ValueError(
                    f"preset {preset!r} {self._state_held}: "
                    f"role={self.role!r} hands a prompt over as "
                    "blocks, and the state would stay behind"
                )
            if prefix_cache_mb > 0:
                log.info(
                    "preset %r holds recurrent state: no prefix cache is "
                    "built (a cached prefix is blocks without the state "
                    "that follows them)", preset,
                )
                prefix_cache_mb = 0.0
        #: keys the layers of a second, windowed pool read back from a row's
        #: position (0: one kind of block; docs/serving.md "Models whose
        #: layers keep different amounts of context"). The engine keeps that
        #: pool's table and releases what falls behind the window, and again
        #: refuses what takes a prefix to be a list of blocks
        self._window = int(runner.window)
        if self._window:
            if self.role != "colocated":
                raise ValueError(
                    f"preset {preset!r} keeps two kinds of K/V block: "
                    f"role={self.role!r} hands a prompt over as blocks, and "
                    "the window layers' have been released behind the window"
                )
            if prefix_cache_mb > 0:
                log.info(
                    "preset %r keeps only a window of K/V in some layers: no "
                    "prefix cache is built (a cached prefix's window blocks "
                    "are gone)", preset,
                )
                prefix_cache_mb = 0.0
        if self._paged:
            self.kv_block_size = runner.kv_block_size
        #: chunked prefill (docs/serving.md "Continuous batching"): > 0
        #: caps the PROMPT tokens one scheduler tick may prefill, so
        #: long prompts land block-sized chunk by chunk, interleaved
        #: with decode segments, instead of stalling the whole running
        #: batch for one giant forward. Paged-only (chunks must be
        #: block-aligned to keep every block fully owned by one write).
        pct = max(0, int(prefill_chunk_tokens))
        if pct and self._paged:
            pct = max(self.kv_block_size,
                      (pct // self.kv_block_size) * self.kv_block_size)
            self.prefill_chunk_tokens = pct
        else:
            self.prefill_chunk_tokens = 0
        #: versioned weights (docs/serving.md "Model lifecycle"): every
        #: loaded parameter tree lives here under a version id; the
        #: default version serves requests that name none. All jitted
        #: entry points take params as an explicit argument, so a second
        #: tree rides the SAME compiles — hot-swap is just passing a
        #: different pytree.
        self._default_version = str(model_version) or "base"
        params = runner.build_params(ckpt_dir)
        self.params = params
        self._versions: Dict[str, object] = {self._default_version: params}
        #: versions drained-and-awaiting-eviction: unroutable for new
        #: requests, evicted (tree dropped) once their last in-flight
        #: row frees — never while a row still dispatches on them
        self._retiring: set = set()
        self._vers_rr = 0
        self.kv_blocks = self.window_kv_blocks = 0
        self._wtable = None
        self._draft = self._spec_stats = None
        if self._paged:
            import math

            from kubedl_tpu.serving.speculative import SpecStats, make_draft

            mb = self.max_seq // self.kv_block_size
            if not runner.block_bytes:
                # a row of this model owns no block (it is recurrent state
                # and nothing else): no pool, whatever ``kv_blocks`` asks;
                # rows bound admission and ``max_seq`` bounds positions only
                nb = 0
            elif kv_blocks:
                nb = int(kv_blocks)
                if nb < mb + 1:
                    raise ValueError(
                        f"kv_blocks={nb} cannot hold one max_seq row "
                        f"({mb} blocks + trash)"
                    )
            else:
                # parity sizing: every batch row can still reach max_seq
                # (the contiguous footprint), plus headroom for prefix-
                # cache entries capped at one batch's worth of blocks
                prefix_blocks = 0
                if prefix_cache_mb > 0:
                    prefix_blocks = min(
                        math.ceil(prefix_cache_mb * 1e6 / runner.block_bytes),
                        self.max_batch * mb,
                    )
                nb = 1 + self.max_batch * mb + prefix_blocks
            self.kv_blocks = nb
            #: the windowed pool is sized so that every row can hold what it
            #: may at once: the window and one dispatch's reach (a prefill
            #: chunk, or the longest decode segment)
            if self._window:
                self.window_kv_blocks = runner.size_window_pool(max(
                    self.prefill_chunk_tokens or self.max_seq,
                    self.SEGMENT_BUCKETS[0]))
            self._new_block_state(kv_low_watermark, kv_high_watermark)
            self.spec_draft = spec_draft
            if self.spec_k:
                self._draft = make_draft(
                    spec_draft, params=self.params, cfg=self.cfg,
                    max_context=self.max_seq, n_layers=spec_draft_layers,
                )
                self._spec_stats = SpecStats()
                #: the tree scorer's fixed node budget (one compile)
                self._spec_tree_m = 1 + self.spec_candidates * self.spec_k
        runner.new_cache(self.kv_blocks)
        from collections import deque as _deque

        self._slots: list = [None] * self.max_batch
        # deque: admission pops the HEAD (popleft) and shedding peeks head
        # age on every generate() — a plain list made both O(n) in queue
        # depth, which showed up in the scheduler microbench under bursts
        self._waiting: "_deque[_Slot]" = _deque()
        self._cv = threading.Condition()
        #: parked prefill handoffs: id -> {blocks (increfed), pos, meta}.
        #: The handoff holds its OWN block references across the transfer
        #: window — the row frees normally, a fetch (or TTL GC / failure)
        #: decrefs, so conservation holds whatever the transfer does.
        self._handoffs: Dict[str, Dict] = {}
        #: export requests serviced by the scheduler thread (it alone may
        #: touch the donated device cache): (handoff_id, reply box, event)
        self._export_q: "_deque[tuple]" = _deque()
        #: device-resident prefix KV cache (docs/serving.md "Prefix
        #: cache"): admission grafts the longest cached prefix into the
        #: row and prefills only the suffix. 0 MB disables it.
        from kubedl_tpu.serving.prefix_cache import PrefixCache

        #: paged entries hold block REFERENCES, so eviction must give the
        #: refs back to the allocator (the engine callback frees them)
        _on_evict = self._paged_entry_evicted if self._paged else None
        self._pcache: Optional[PrefixCache] = (
            PrefixCache(int(prefix_cache_mb * 1e6), min_len=prefix_min_len,
                        on_evict=_on_evict)
            if prefix_cache_mb > 0 else None
        )
        self._prefix_evictions_seen = 0  # metric delta vs pcache stats
        self._stop = False
        #: graceful drain (docs/serving.md "Router"): once set, NEW
        #: requests are rejected with a distinguishable 503 while every
        #: already-admitted/queued request still runs to completion
        self._draining = False
        #: request_id -> slot for requests that opted into cancellation
        #: (the router's hedge-loser path)
        self._requests: Dict[str, _Slot] = {}
        #: the PRNG chain for on-device sampling (a segment's output)
        self._key = jax.random.PRNGKey(0)
        #: device-chained feed between segments: (prefill_gen, rows,
        #: last-token device array) where ``rows`` are the rows whose
        #: device token is current. Segment outputs cover the segment's
        #: rows; an interleaved prefill MERGES its sampled first tokens in
        #: (per-row validity) instead of invalidating the whole chain, so
        #: the next segment's input tokens never leave the device even
        #: across admissions.
        self._chain: Optional[tuple] = None
        self._prefill_gen = 0
        #: device copy of the per-row temperatures, re-uploaded only when
        #: they actually change
        self._temps_cache: Optional[tuple] = None
        #: the deferred in-flight decode segment (double buffering):
        #: {"toks": [B, k] device array, "sched": [(row, slot, take)]}.
        #: Dispatched one tick, harvested the next — the device_get and
        #: all host bookkeeping behind it overlap the NEXT segment's
        #: device compute instead of idling the chip between segments.
        self._pending: Optional[Dict] = None
        self._stats = {"requests": 0, "tokens_out": 0, "tokens_in": 0,
                       "shed": 0, "drain_rejects": 0,
                       "kv_preemptions": 0, "kv_sheds": 0,
                       "handoffs_out": 0, "handoffs_in": 0,
                       "handoff_failures": 0,
                       "prefill_tokens": 0, "prefill_positions": 0,
                       "view_keys": 0, "view_keys_full": 0,
                       "state_resets": 0,
                       "started_at": time.time()}
        #: what the runner's decode segments count beside their tokens
        #: (`_Runner.segment_counters`), summed at each harvest, by name
        self._segment_counters: Dict[str, object] = {}
        #: decode segments dispatched so far, and their steps (Σ k)
        self._segment_seq = 0
        self._steps_dispatched = 0
        #: requests enqueued so far: a slot's ``seq``
        self._arrivals = 0
        #: load-shedding budget: reject (503) instead of queueing once the
        #: queue is deeper than max_queue_depth or its head has waited
        #: longer than max_queue_age_s (the queue is not draining)
        self.max_queue_depth = max(1, int(max_queue_depth))
        self.max_queue_age_s = float(max_queue_age_s)
        from collections import deque

        from kubedl_tpu.observability.metrics import ServingMetrics

        self.metrics = metrics or ServingMetrics()
        #: per-tick pipeline accounting (sums + lifetime counters); the
        #: recent deque feeds median reporting in stats()/bench
        self._pipe = {
            "ticks": 0, "segments": 0, "deferred_harvests": 0,
            "flushes": 0, "chain_rebuilds": 0, "errors": 0, "inflight": 0,
            "dispatch_ms_sum": 0.0, "harvest_ms_sum": 0.0,
            "host_ms_sum": 0.0, "tick_ms_sum": 0.0, "overlap_ms_sum": 0.0,
            # decode segments by length, and those the owed prefill work
            # made short, by reason (`choose_segment`)
            **{f"segments_k{k}": 0 for k in self.SEGMENT_BUCKETS},
            "short_waiting": 0, "short_prefill": 0,
        }
        self._pipe_recent: "deque[tuple]" = deque(maxlen=2048)
        #: completion timestamps for windowed QPS (autoscale signal must
        #: track LIVE load, not a lifetime average)
        self._recent: "deque[float]" = deque(maxlen=100_000)
        #: shed timestamps, same window: the autoscaler folds recent sheds
        #: into its backlog signal (rejected demand is still demand)
        self._shed_recent: "deque[float]" = deque(maxlen=100_000)
        #: per-request time-to-first-token samples (ms) for p50/p95
        self._ttft_recent: "deque[float]" = deque(maxlen=4096)
        #: per-request admission queue wait (enqueue -> admission), ms —
        #: the half of TTFT chunked prefill is built to shrink, so it
        #: gets its own p50/p95 in stats() and the Poisson bench arm
        self._queue_wait_recent: "deque[float]" = deque(maxlen=4096)
        #: where each request's time to first token went, newest 1,024:
        #: ``(seq, queue, backlog, chunks, first, n_chunks, segments,
        #: steps)``, the four parts in ms summing to its ``ttft_ms``
        #: (`_note_first_token_locked`), rounded when stored
        self._first_tokens: "deque[tuple]" = deque(maxlen=1024)
        self._compiles0 = _count_compiles()
        self.qps_window_s = 60.0
        runner.warmup(self.params)
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="decode-scheduler"
        )
        self._thread.start()

    # -- versioned weights / hot swap (docs/serving.md "Model lifecycle") --

    def load_version(self, version: str, ckpt_dir: str) -> None:
        """Load a second (third, …) parameter tree alongside the serving
        ones with ZERO downtime: the build runs entirely off to the side
        on the caller's thread, and only a fully restored/quantized/
        sharded tree is committed under the lock. A failed load (torn
        artifact, injected ``serving.weight_swap`` crash) raises and the
        already-loaded versions keep serving — there is no intermediate
        state a request could observe. Idempotent for an already-loaded
        version."""
        if not self._paged:
            raise ValueError(
                "weight hot-swap requires kv_layout='paged' (per-row "
                "block isolation is what lets rows of the other version "
                "sit a dispatch out safely)"
            )
        version = str(version)
        if not version:
            raise ValueError("model version id must be non-empty")
        with self._cv:
            if version in self._retiring:
                raise ValueError(
                    f"version {version!r} is retiring; wait for eviction "
                    "before reloading it"
                )
            if version in self._versions:
                return
        params = self._runner.build_params(ckpt_dir, require_ckpt=True)
        with self._cv:
            self._versions[version] = params
        log.info("hot-loaded model version %r from %s", version, ckpt_dir)

    def activate_version(self, version: str) -> str:
        """Make a loaded version the DEFAULT for requests that name no
        version (the rollback/promotion flip is the router's weight
        change; this is the engine-local equivalent). Returns the
        previous default."""
        version = str(version)
        with self._cv:
            if version not in self._versions or version in self._retiring:
                raise UnknownModelVersion(
                    f"cannot activate {version!r} "
                    f"(loaded: {sorted(self._versions)})"
                )
            prev, self._default_version = self._default_version, version
            self.params = self._versions[version]
        log.info("activated model version %r (was %r)", version, prev)
        return prev

    def retire_version(self, version: str) -> bool:
        """Fence a version from NEW requests and evict its tree once the
        last in-flight row referencing it drains — never mid-flight: a
        row dispatching on the tree keeps it alive. The default version
        cannot retire (activate another first). Returns False for a
        version that was never loaded."""
        version = str(version)
        with self._cv:
            if version == self._default_version:
                raise ValueError(
                    f"cannot retire the default version {version!r}; "
                    "activate another version first"
                )
            if version not in self._versions:
                return False
            self._retiring.add(version)
            self._maybe_evict_versions_locked()
        return True

    def versions(self) -> Dict:
        """Live version inventory (feeds /v1/models, stats(), and the
        rollout drive's torn-state assertions)."""
        with self._cv:
            rows: Dict[str, int] = {}
            for s in self._slots:
                if s is not None:
                    v = s.version or self._default_version
                    rows[v] = rows.get(v, 0) + 1
            return {
                "default": self._default_version,
                "loaded": sorted(self._versions),
                "retiring": sorted(self._retiring),
                "active_rows": rows,
            }

    def _resolve_version_locked(self, requested: str) -> str:
        """Admission-gate resolution: "" → the default; anything else
        must be a loaded, non-retiring version. Caller holds cv."""
        v = str(requested or "") or self._default_version
        if v not in self._versions or v in self._retiring:
            raise UnknownModelVersion(
                f"unknown or retiring model version {v!r} "
                f"(loaded: {sorted(set(self._versions) - self._retiring)})"
            )
        return v

    def _version_refs_locked(self, version: str) -> int:
        n = sum(
            1 for s in self._slots
            if s is not None and (s.version or self._default_version) == version
        )
        n += sum(
            1 for s in self._waiting
            if (s.version or self._default_version) == version
        )
        return n

    def _maybe_evict_versions_locked(self) -> None:
        """Drop retiring trees whose last referencing row/queue entry is
        gone (drain-then-evict). Hooked into _admit_locked so every
        admission pass — which follows every row free — re-checks.
        Caller holds cv."""
        for v in list(self._retiring):
            if v == self._default_version:
                continue
            if self._version_refs_locked(v) == 0:
                self._versions.pop(v, None)
                self._retiring.discard(v)
                log.info("evicted retired model version %r", v)

    def _pick_tick_version_locked(self, active) -> str:
        """One version per scheduler tick: dispatch (prefill group,
        decode segment, spec round) never mixes parameter trees. With
        versions co-resident the tick alternates round-robin over those
        with live rows — rows of the others sit the tick out, which is
        safe in paged mode because the host pos/bt mirrors are
        authoritative (re-uploaded before every dispatch, so the skipped
        steps never happened for them). Caller holds cv."""
        vers = sorted({
            (s.version or self._default_version)
            for s in active if s is not None
        })
        if not vers:
            return self._default_version
        if len(vers) == 1:
            return vers[0]
        self._vers_rr = (self._vers_rr + 1) % len(vers)
        return vers[self._vers_rr]

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=5)

    # -- graceful drain ----------------------------------------------------

    def drain(self, wait: bool = False, timeout_s: float = 30.0) -> bool:
        """Stop ADMISSION, not work: new requests get a 503 whose reason
        is "draining" (vs the shed path's "overloaded" — the router fails
        those over immediately instead of backing off), while every
        queued/in-flight request still runs to completion. The graceful
        half of shutdown that `close()` alone never had — `close()`
        hard-joins with a 5 s timeout and strands in-flight rows."""
        with self._cv:
            self._draining = True
            self._cv.notify_all()
        if wait:
            return self.wait_drained(timeout_s)
        return True

    def wait_drained(self, timeout_s: float = 30.0) -> bool:
        """Block until no request is queued, resident in a row, or in
        flight on device (then `close()` severs nothing). True on idle."""
        deadline = time.perf_counter() + timeout_s
        while True:
            with self._cv:
                idle = (
                    not self._waiting
                    and self._pending is None
                    and all(s is None for s in self._slots)
                )
            if idle:
                return True
            if time.perf_counter() >= deadline:
                return False
            time.sleep(0.01)

    @property
    def draining(self) -> bool:
        return self._draining

    # -- request path ------------------------------------------------------

    def cancel(self, request_id: str) -> bool:
        """Cancel a request by id (the router's hedge-loser path): a
        queued request leaves the admission queue, an in-flight one has
        its row vacated (same mechanics as the generate() timeout path —
        prefix pin released, stale device work masked by the harvest's
        identity check). The waiter wakes with a ``cancelled`` result.
        Returns False for unknown/already-finished ids."""
        with self._cv:
            slot = self._requests.pop(request_id, None)
            if slot is None or slot.done.is_set():
                return False
            self._vacate_locked(slot)
            slot.result = {"error": "cancelled", "cancelled": True}
            slot.done.set()
            self._cv.notify_all()
        return True

    def _vacate_locked(self, slot: _Slot) -> None:
        """Take ``slot`` out of the queue and out of its row: a cancelled
        or abandoned request must not keep occupying a batch slot (and
        decode work) under overload, nor keep its prefix-cache entry
        pinned — the pin would block eviction for good. Caller holds cv."""
        try:
            self._waiting.remove(slot)
        except ValueError:
            pass
        for i, s in enumerate(self._slots):
            if s is slot:
                self._slots[i] = None
                self._free_row_locked(i)
        self._release_prefix_locked(slot)

    def _await_slot(self, slot: _Slot, timeout_s: float) -> Dict:
        """The scheduler's result for ``slot``, counted into stats(); one
        that does not come in ``timeout_s`` is vacated and reads
        ``timed_out``."""
        if not slot.done.wait(timeout=timeout_s):
            with self._cv:
                self._vacate_locked(slot)
        result = slot.result or {"error": "timed out", "timed_out": True}
        with self._cv:
            if slot.request_id:
                self._requests.pop(slot.request_id, None)
            self._stats["requests"] += 1
            self._stats["tokens_in"] += len(slot.prompt)
            self._stats["tokens_out"] += len(result.get("token_ids", []))
            self._recent.append(time.time())
        return result

    # -- distributed tracing (docs/observability.md) -----------------------

    @staticmethod
    def _arm_trace(slot: _Slot, trace: Optional[TraceContext],
                   debug_trace: bool = False) -> None:
        """Give a slot span identity: the caller sent a context, or asked
        for a flight recording without one (mint a fresh trace so the
        recording still has a root). Disarmed tracer: stays a no-op —
        every scheduler-side record guards on ``slot.span_id``."""
        if not TRACER.enabled:
            return
        if trace is None and debug_trace:
            trace = TraceContext(new_trace_id(), "")
        if trace is not None:
            slot.trace = trace
            slot.span_id = new_span_id()

    def _trace_admitted_locked(self, s: _Slot, t_adm: float,
                               row: int) -> None:
        """Record queue wait (enqueue → admission start) — the stats()
        percentile sample and metric for EVERY admission, plus the
        engine.queue_wait/engine.admission spans when the request is
        traced. Caller holds cv. Chunked admission changes nothing
        here: a request is admitted once (the wait ends when its row is
        assigned), however many prefill chunks follow. ``t_adm`` is the
        slot's ``t_row``: the end of the ``queue`` part of its time to
        first token."""
        s.t_row = t_adm
        s.seg_mark = (self._segment_seq, self._steps_dispatched)
        wait_ms = (t_adm - s.t0) * 1e3
        self._queue_wait_recent.append(wait_ms)
        self.metrics.queue_wait_ms.observe(wait_ms)
        if not s.span_id:
            return
        now = time.perf_counter()
        TRACER.record("engine.queue_wait", start=s.t0,
                      duration=t_adm - s.t0, trace=s.trace,
                      parent_id=s.span_id)
        TRACER.record("engine.admission", start=t_adm,
                      duration=now - t_adm, trace=s.trace,
                      parent_id=s.span_id, row=row)

    def _trace_request_locked(self, s: _Slot, kind: str) -> None:
        """Close the request span (id pre-minted at arm time) BEFORE the
        waiter wakes, so a flight-recorder read right after done.wait()
        already sees the whole tree. Caller holds cv."""
        if s.span_id:
            TRACER.record("engine.request", start=s.t0,
                          duration=time.perf_counter() - s.t0,
                          trace=s.trace, span_id=s.span_id, kind=kind,
                          tokens=len(s.out_ids))

    @staticmethod
    def _trace_result(slot: _Slot, result: Dict,
                      debug_trace: bool) -> Dict:
        """Stamp the trace id on a finished result; with the flight
        recorder armed, attach the request's own span tree inline."""
        if slot.span_id and slot.trace is not None:
            tid = slot.trace.trace_id
            result.setdefault("trace_id", tid)
            if debug_trace:
                result["trace"] = {
                    "trace_id": tid,
                    "spans": TRACER.span_tree(tid),
                }
        return result

    def generate(self, prompt_ids, max_tokens: int = 16,
                 temperature: float = 0.0, timeout_s: float = 600.0,
                 cache_prefix: bool = False, request_id: str = "",
                 trace: Optional[TraceContext] = None,
                 debug_trace: bool = False,
                 model_version: str = "") -> Dict:
        budget = self.max_seq - 1
        prompt = [int(t) for t in list(prompt_ids)[:budget]]
        if not prompt:
            prompt = [0]
        max_tokens = max(0, min(int(max_tokens), budget - len(prompt)))
        slot = _Slot(prompt, max_tokens, float(temperature), cache_prefix,
                     request_id=request_id)
        slot.version = str(model_version or "")
        self._arm_trace(slot, trace, debug_trace)
        self._enqueue_slot_locked_checks(slot)
        result = self._await_slot(slot, timeout_s)
        return self._trace_result(slot, result, debug_trace)

    def stats(self) -> Dict:
        """Live serving counters (feeds autoscaling signals + /v1/stats).

        One snapshot under ONE cv acquisition: the old code re-took the
        lock three times, so counters, the qps window, and the queue
        depth could describe three different moments of a moving engine.
        Derived values are computed outside the lock from the snapshot."""
        now = time.time()
        with self._cv:
            out = dict(self._stats)
            recent = sum(1 for t in self._recent if t > now - self.qps_window_s)
            shed_recent = sum(
                1 for t in self._shed_recent if t > now - self.qps_window_s
            )
            queued = len(self._waiting)
            active = sum(1 for s in self._slots if s is not None)
            # rows whose slab of recurrent state is live: a first prefill
            # program has run for them (0 for a model that has none)
            state_rows = sum(
                1 for s in self._slots
                if s is not None and (s.fed > 0 or s.prefill_pos > 0)
            ) if self._state_bytes else 0
            ttft = list(self._ttft_recent)
            qwait = list(self._queue_wait_recent)
            draining = self._draining
            parked_handoffs = len(self._handoffs)
            # scalars as ints, a table (by layer and expert) as lists
            out.update({name: v.tolist()
                        for name, v in self._segment_counters.items()})
            window_blocks = self._wtable.stats(
                i for i, s in enumerate(self._slots) if s is not None
            ) if self._wtable is not None else None
        up = max(now - out["started_at"], 1e-9)
        import jax

        dev = jax.devices()[0]
        # which device the counters below were taken on
        out["device"] = {"platform": dev.platform,
                         "device_kind": dev.device_kind,
                         "count": jax.device_count()}
        out["role"] = self.role
        out["handoffs_parked"] = parked_handoffs
        # surfaced so both the router (stop picking this replica, don't
        # count its rejections as overload) and the autoscaler see drain
        out["draining"] = draining
        out["uptime_s"] = round(up, 1)
        # windowed rate over min(window, uptime): a fresh engine under a
        # burst reports the burst, a long-idle engine reports ~0
        span = min(self.qps_window_s, up)
        out["qps"] = round(recent / max(span, 1e-9), 3)
        out["lifetime_qps"] = round(out["requests"] / up, 3)
        out["active_slots"] = active
        out["max_batch"] = self.max_batch
        out["state_rows"] = state_rows
        out["state_bytes"] = state_rows * self._state_bytes
        out["queued"] = queued
        out["shed_recent"] = shed_recent
        out["pipeline"] = self.pipeline_stats()
        # each part of the time to first token beside the whole and beside
        # the queue wait (the first part, over a longer record)
        parts = [(f"ttft_{part}_ms",
                  [r[n] for r in out["pipeline"]["first_tokens"]])
                 for n, part in enumerate(TTFT_PARTS, start=1)]
        for name, samples in [("ttft_ms", ttft), ("queue_wait_ms", qwait),
                              *parts]:
            if samples:
                srt = sorted(samples)
                for q in (50, 95, 99):
                    out[f"{name}_p{q}"] = round(
                        srt[min(len(srt) - 1, len(srt) * q // 100)], 3)
        if self._pcache is not None:
            out["prefix_cache"] = self._pcache.stats()
            # block-aware affinity advertisement: digests of the cached
            # prefixes this replica already holds blocks for, in the same
            # hash the router's ring keys on — the prober folds these into
            # an advertised-prefix map and steers repeats here
            from kubedl_tpu.serving.router_policy import prefix_digest

            plen = self.advertise_prefix_len
            adv = set()
            for key in self._pcache.prefix_keys():
                d = prefix_digest(key, plen)
                if d is not None:
                    adv.add(d)
            out["prefix_cache"]["advertised"] = sorted(adv)
        if self._paged:
            out["kv_blocks"] = self._alloc.stats()
            out["kv_blocks"]["attention_kernel"] = self.kv_attention
            out["kv_blocks"]["role"] = self.role
            if window_blocks is not None:
                # the second kind of block, with what the rows released
                out["kv_blocks"]["window"] = window_blocks
        if self._spec_stats is not None:
            out["speculative"] = self._spec_stats.snapshot()
            out["speculative"]["draft_kind"] = getattr(
                self._draft, "name", self.spec_draft
            )
            out["speculative"]["candidates"] = self.spec_candidates
        out["versions"] = self.versions()
        return out

    def pipeline_stats(self) -> Dict:
        """Decode-pipeline accounting: per-tick dispatch/harvest/host
        timings (avg + p50 over the recent window), overlap ratio, and
        lifetime segment/harvest counters. Feeds `/v1/stats`, the
        Prometheus family (`observability.metrics.ServingMetrics`), and
        bench.py's serving_engine medians."""
        import statistics

        with self._cv:
            p = dict(self._pipe)
            recent = list(self._pipe_recent)
            queued = len(self._waiting)
            first = list(self._first_tokens)
        out = {
            "ticks": p["ticks"],
            "segments": p["segments"],
            "segments_by_k": {
                str(k): p[f"segments_k{k}"] for k in self.SEGMENT_BUCKETS
            },
            "segments_short": {"waiting": p["short_waiting"],
                               "prefill": p["short_prefill"]},
            "deferred_harvests": p["deferred_harvests"],
            "flushes": p["flushes"],
            "chain_rebuilds": p["chain_rebuilds"],
            "errors": p["errors"],
            "inflight": p["inflight"],
            "queued": queued,
            # where each request's time to first token went, oldest first:
            # (seq, queue, backlog, chunks, first, n_chunks, segments, steps)
            "first_tokens": first,
            # programs built or loaded since the engine's own warm-up began
            "compiles": _COMPILES[0] - self._compiles0,
        }
        if p["ticks"]:
            n = p["ticks"]
            out["dispatch_ms_avg"] = round(p["dispatch_ms_sum"] / n, 4)
            out["harvest_ms_avg"] = round(p["harvest_ms_sum"] / n, 4)
            out["host_ms_avg"] = round(p["host_ms_sum"] / n, 4)
            out["tick_ms_avg"] = round(p["tick_ms_sum"] / n, 4)
            out["overlap_ratio"] = round(
                p["overlap_ms_sum"] / max(p["tick_ms_sum"], 1e-9), 4
            )
        if recent:
            med = statistics.median
            out["dispatch_ms_p50"] = round(med([r[0] for r in recent]), 4)
            out["harvest_ms_p50"] = round(med([r[1] for r in recent]), 4)
            out["host_ms_p50"] = round(med([r[2] for r in recent]), 4)
            out["tick_ms_p50"] = round(med([r[3] for r in recent]), 4)
        return out

    # -- scheduler loop ----------------------------------------------------

    def _release_prefix_locked(self, slot: _Slot) -> None:
        """Drop a slot's pin on its grafted prefix entry (finalize /
        vacation / error recovery). Idempotent; caller holds cv."""
        if slot.pinned is not None and self._pcache is not None:
            self._pcache.unpin(slot.pinned)
        slot.pinned = None

    def _maybe_insert_prefix_locked(self, i: int, s: _Slot) -> None:
        """After row ``i``'s prefill completes, store its prompt prefix
        when traffic says it is shared (observation trie: >= min_seen
        requests walked it) or the request tagged itself cacheable.
        Extraction is an async device copy dispatched BEFORE any later
        graft into the same row, so the copied span is this prefill's
        output even if the row turns over immediately. Caller holds cv."""
        if self._pcache is None:
            return
        cand = self._pcache.insert_candidate(s.prompt, s.cache_prefix)
        # cap at len-1: a full-prompt entry can never match (the engine
        # always needs >= 1 suffix token for last-token logits), while
        # len-1 serves exact-repeat traffic too
        cand = min(cand, len(s.prompt) - 1)
        if cand <= s.cached_len or cand < self._pcache.min_len:
            return  # nothing new beyond what the matched entry covers
        if self._paged:
            # paged insert is (almost) free: the entry SHARES the row's
            # full prefix blocks by reference (incref), and only the
            # partial tail block is device-copied — the row keeps
            # appending inside its own tail, so the entry needs a
            # frozen copy (the insert-side half of copy-on-write)
            bs = self.kv_block_size
            full = cand // bs
            row_blocks = self._row_blocks[i]
            blocks = list(row_blocks[:full])
            if cand % bs:
                got = self._alloc.alloc(1)
                if got is None:
                    return  # pool pressure: skip the insert
                self._runner.copy_block(row_blocks[full], got[0])
                blocks.append(got[0])
            self._alloc.incref(blocks[:full])
            ok = self._pcache.insert(
                s.prompt[:cand], None, None, cand,
                blocks=tuple(blocks),
                nbytes=len(blocks) * self._runner.block_bytes,
            )
            if not ok:
                self._alloc.free(blocks)  # duplicate/over-budget: undo
                return
        else:
            k, v = self._runner.extract(i, self._prefill_bucket(cand))
            if not self._pcache.insert(s.prompt[:cand], k, v, cand):
                return
        st = self._pcache.stats()
        m = self.metrics
        m.prefix_inserts.inc()
        m.prefix_bytes.set(float(st["bytes"]))
        m.prefix_entries.set(float(st["entries"]))
        ev = st["evictions"] - self._prefix_evictions_seen
        if ev > 0:
            m.prefix_evictions.inc(ev)
        self._prefix_evictions_seen = st["evictions"]

    # -- paged KV bookkeeping (host mirrors + block lifecycle) -------------

    def _new_block_state(self, low: float, high: float) -> None:
        """Every block free, no row owning any: the constructor's state
        and, the pool rebuilt, recovery's. ``_pos_host``/``_bt_host`` are
        the host-authoritative mirrors of the device cache's pos/bt —
        uploaded before EVERY dispatch so rollbacks (speculative
        rejection, preemption, vacation) are just mirror edits."""
        import numpy as np

        from kubedl_tpu.serving.kv_blocks import (
            BlockAllocator, NoBlocks, WindowTable)

        bs = self.kv_block_size
        # no pool (a runner whose rows own no block): reserve, trim and free
        # find nothing to do, and the table has no column
        self._alloc = BlockAllocator(
            self.kv_blocks, bs, low_watermark=low, high_watermark=high,
        ) if self.kv_blocks else NoBlocks(bs)
        self._pos_host = np.zeros((self.max_batch,), np.int32)
        self._bt_host = np.zeros(
            (self.max_batch, self.max_seq // bs if self.kv_blocks else 0),
            np.int32)
        self._row_blocks: list = [[] for _ in range(self.max_batch)]
        #: the windowed pool's allocator, table mirror and rows' ranges
        #: (None: the runner has one kind of block). No watermarks: the
        #: pool holds every row's most at once (`size_window_pool`), so it
        #: is full by design and never a reason to hold admission
        self._wtable = WindowTable(
            BlockAllocator(self.window_kv_blocks, bs, low_watermark=0.0,
                           high_watermark=0.0),
            self.max_batch, self.max_seq // bs, self._window,
        ) if self._window else None
        self._window_released_seen = 0  # metric delta vs the table's count

    def _upload_mirrors(self, pos: bool = True) -> None:
        """The host mirrors to the device, before a dispatch: the block
        table, the positions unless ``pos`` is False, and the windowed
        pool's table where there is one."""
        extra = {} if self._wtable is None else {"wbt": self._wtable.table}
        self._runner.upload_mirrors(
            self._bt_host, self._pos_host if pos else None, **extra)

    def _advance_pos_locked(self, i: int, pos: int) -> None:
        """Row ``i``'s position mirror after a dispatch that left it at
        ``pos``; its window blocks that now lie wholly behind the window are
        released and their table entries pointed at trash. The dispatch in
        flight reads through the table it was given, and the device runs
        dispatches in order, so a later owner's writes land after its reads
        (the argument of `_free_row_locked`). Caller holds cv."""
        self._pos_host[i] = min(int(pos), self.max_seq - 1)
        if self._wtable is not None:
            self._wtable.release_behind(i, int(self._pos_host[i]))

    def _reserve_window_locked(self, i: int, n_tokens: int) -> None:
        """Grow row ``i``'s window blocks to cover ``n_tokens`` positions
        before a prefill dispatch writes them. The pool holds every row's
        most at once, so this cannot fail while the releases keep up; if it
        does the tick raises and the engine recovers. Caller holds cv."""
        if self._wtable is not None and not self._wtable.reserve(i, n_tokens):
            raise RuntimeError(
                f"window pool exhausted growing row {i} to {n_tokens} tokens")

    def _free_row_locked(self, i: int) -> None:
        """Return row ``i``'s blocks to the pool and point its table rows
        at the trash block. Any still-in-flight dispatch keeps writing
        through its own bt SNAPSHOT, but the device executes enqueued
        calls in order, so a later owner's writes always land last.
        Caller holds cv; no-op in contiguous mode."""
        if not self._paged:
            return
        blocks = self._row_blocks[i]
        if blocks:
            self._alloc.free(blocks)
        self._row_blocks[i] = []
        self._bt_host[i, :] = 0
        self._pos_host[i] = 0
        if self._wtable is not None:
            self._wtable.free_row(i)

    def _reserve_locked(self, i: int, n_tokens: int) -> bool:
        """Grow row ``i``'s block list to cover ``n_tokens`` cached
        positions (all-or-nothing), in the windowed pool too where there is
        one. Caller holds cv."""
        need = self._alloc.blocks_for(min(int(n_tokens), self.max_seq))
        blocks = self._row_blocks[i]
        if self._wtable is not None and not self._wtable.reserve(i, n_tokens):
            return False  # what it did grow, the row keeps for its next try
        if need <= len(blocks):
            return True
        got = self._alloc.alloc(need - len(blocks))
        if got is None:
            return False
        self._bt_host[i, len(blocks):need] = got
        blocks.extend(got)
        return True

    def _trim_row_locked(self, i: int, n_tokens: int) -> None:
        """Free row blocks beyond what ``n_tokens`` cached positions need
        — how a rejected speculative suffix's KV is freed IN PLACE (its
        positions are beyond the rolled-back pos mirror)."""
        keep = self._alloc.blocks_for(min(int(n_tokens), self.max_seq))
        blocks = self._row_blocks[i]
        if self._wtable is not None:
            self._wtable.trim(i, n_tokens)
        if len(blocks) <= keep:
            return
        drop = blocks[keep:]
        del blocks[keep:]
        self._bt_host[i, keep:keep + len(drop)] = 0
        self._alloc.free(drop)

    def _paged_entry_evicted(self, entry) -> None:
        """PrefixCache eviction callback: hand the entry's block
        references back to the allocator. Runs under the pcache lock and
        touches only the allocator (its own lock) — never cv."""
        blocks = getattr(entry, "blocks", None)
        if blocks:
            self._alloc.free(blocks)

    def _reclaim_prefix_locked(self) -> bool:
        """Evict unpinned prefix-cache entries to recover at least one
        block; True when anything came back. The cheapest relief valve —
        cache entries are an optimization, resident rows are work."""
        if self._pcache is None or not self._paged:
            return False
        return self._pcache.reclaim(self._runner.block_bytes) > 0

    def _pick_victim_locked(self, held) -> Optional[int]:
        """Pick the preemption victim: the YOUNGEST resident row (latest
        arrival — least sunk decode work) that is not in ``held`` and has
        nothing in flight (``pending`` rows owe tokens to the deferred
        harvest's count-based accounting)."""
        best = None
        for j, s in enumerate(self._slots):
            if s is None or j in held or s.pending or not self._row_blocks[j]:
                continue
            if best is None or s.t0 > self._slots[best].t0:
                best = j
        return best

    def _preempt_locked(self, j: int) -> None:
        """Preempt-and-requeue row ``j`` under block exhaustion: free its
        blocks, reset the slot to its pre-admission state, and put it at
        the FRONT of the queue (it was admitted first — it re-admits
        first once blocks free up). Greedy requests regenerate the exact
        same tokens from prefill, so preemption never changes output."""
        s = self._slots[j]
        self._slots[j] = None
        self._free_row_locked(j)
        self._release_prefix_locked(s)
        s.fed = 0
        s.cached_len = 0
        s.prefill_pos = -1  # its chunks went with its blocks: start over
        s.prefill_t0 = s.t_final = None  # and so does its way to a token
        s.n_chunks = 0
        s.out_ids = []
        s.pending = 0
        self._waiting.appendleft(s)
        self._stats["kv_preemptions"] += 1
        self.metrics.kv_preemptions.inc()
        log.warning("KV blocks exhausted: preempted row %d (requeued)", j)

    def _reserve_decode_locked(self, decoding, steps: int):
        """Ensure every decoding row can cache ``steps`` more positions,
        preempting victims when the pool runs dry (chaos site
        ``serving.kv_alloc`` injects the failure). Rows that still cannot
        grow sit this dispatch out and retry next tick. Caller holds cv;
        returns the surviving rows."""
        out = []
        inject = chaos.should_fail("serving.kv_alloc")
        for i, s in decoding:
            if self._slots[i] is not s:
                continue  # preempted earlier in this very loop
            need = min(int(self._pos_host[i]) + steps, self.max_seq)
            while True:
                if not inject and self._reserve_locked(i, need):
                    out.append((i, s))
                    break
                inject = False  # one injected failure exercises the path
                if self._reclaim_prefix_locked():
                    continue
                victim = self._pick_victim_locked({i} | {j for j, _ in out})
                if victim is None:
                    break
                self._preempt_locked(victim)
        return out

    def _admit_row_paged_locked(self, i: int, slot: _Slot) -> bool:
        """Admit ``slot`` into row ``i`` under the block allocator: match
        the prefix cache, SHARE the entry's full blocks by reference
        (incref — no device copy at all), copy-on-write its partial tail
        block, and allocate fresh blocks for the suffix. All-or-nothing:
        on pool exhaustion every side effect is rolled back and the slot
        stays queued. Caller holds cv."""
        a = self._alloc
        bs = self.kv_block_size
        need_total = a.blocks_for(min(len(slot.prompt) + 1, self.max_seq))
        entry, mlen = None, 0
        if self._pcache is not None:
            self._pcache.observe(slot.prompt)
            entry, mlen = self._pcache.match(slot.prompt)
        entry_blocks = (
            getattr(entry, "blocks", None) if entry is not None else None
        )
        shared: list = []
        tail_src = None
        if entry_blocks:
            full = mlen // bs
            shared = list(entry_blocks[:full])
            if mlen % bs:
                tail_src = entry_blocks[full]
        n_alloc = need_total - len(shared)
        t_alloc = time.perf_counter()
        got = a.alloc(n_alloc)
        if got is None and self._reclaim_prefix_locked():
            got = a.alloc(n_alloc)
        if got is None:
            if entry is not None:
                self._pcache.unpin(entry)
            return False
        a.incref(shared)
        blocks = list(shared)
        if tail_src is not None:
            # copy-on-write: the entry's partial tail block is SHARED and
            # this row's suffix prefill appends inside it — copy before
            # any divergent write can land
            tail_copy = got.pop(0)
            self._runner.copy_block(tail_src, tail_copy)
            blocks.append(tail_copy)
        blocks.extend(got)
        self._row_blocks[i] = blocks
        self._bt_host[i, :] = 0
        self._bt_host[i, :len(blocks)] = blocks
        self._pos_host[i] = mlen
        self._slots[i] = slot
        if slot.span_id:
            TRACER.record("engine.kv_alloc", start=t_alloc,
                          duration=time.perf_counter() - t_alloc,
                          trace=slot.trace, parent_id=slot.span_id,
                          blocks=len(blocks), shared=len(shared))
        if entry is None:
            if self._pcache is not None:
                self.metrics.prefix_misses.inc()
            return True
        self.metrics.prefix_hits.inc()
        slot.cached_len = mlen
        slot.pinned = entry
        if not entry_blocks:
            # array-payload entry (direct insert): scatter its K/V into
            # the row's fresh blocks through the just-updated table
            self._upload_mirrors(pos=False)
            self._runner.graft(entry.k, entry.v, i, mlen)
        return True

    def _admit_locked(self) -> None:
        # retiring versions evict here: every row free is followed by an
        # admission pass, so "last in-flight row drains" is observed at
        # the next admission opportunity
        self._maybe_evict_versions_locked()
        if not self._waiting:
            return
        with TRACER.phase("engine.admit") as ph:
            ph.set(admitted=self._admit_waiting_locked())

    def _admit_waiting_locked(self) -> int:
        """Move waiters into free rows (KV blocks, prefix match) until
        rows or blocks run out; returns how many were admitted."""
        admitted = 0
        for i in range(self.max_batch):
            if self._slots[i] is None and self._waiting:
                if self._paged:
                    if not self._alloc.admission_open():
                        break  # below low watermark: hysteresis holds
                    head = self._waiting[0]
                    t_adm = time.perf_counter()
                    if head.adopt is not None:
                        r = self._admit_row_adopt_locked(i, head)
                        if r is None:
                            break  # pool dry: wait for frees
                        self._waiting.popleft()
                        if r:
                            admitted += 1
                            self._trace_admitted_locked(head, t_adm, i)
                        continue  # r False: waiter already failed/woken
                    if not self._admit_row_paged_locked(i, head):
                        break  # pool dry: wait for frees / preemption
                    self._waiting.popleft()
                    admitted += 1
                    self._trace_admitted_locked(head, t_adm, i)
                    continue
                slot = self._waiting.popleft()
                t_adm = time.perf_counter()
                self._slots[i] = slot
                admitted += 1
                self._trace_admitted_locked(slot, t_adm, i)
                # reset this row's position; stale KV is masked by pos
                self._runner.reset_row(i)
                if self._pcache is None:
                    continue
                # prefix reuse: graft the longest cached prefix into the
                # row NOW (its K/V land in HBM, pos := prefix len) so the
                # prefill dispatch only consumes the suffix. Ordering is
                # safe: within a tick, prefill dispatch precedes decode
                # dispatch, and pos = prefix_len keeps decode writes out
                # of the grafted span.
                self._pcache.observe(slot.prompt)
                entry, mlen = self._pcache.match(slot.prompt)
                if entry is None:
                    self.metrics.prefix_misses.inc()
                    continue
                self.metrics.prefix_hits.inc()
                self._runner.graft(entry.k, entry.v, i, mlen)
                slot.cached_len = mlen
                slot.pinned = entry
        return admitted

    def _loop(self) -> None:
        while True:
            try:
                if self._loop_once():
                    return
            except Exception as e:  # the singleton scheduler must survive:
                # fail every in-flight request, keep serving new ones
                log.exception("decode scheduler step failed")
                with self._cv:
                    for i, s in enumerate(self._slots):
                        if s is not None:
                            s.result = {"error": str(e)}
                            self._slots[i] = None
                            self._release_prefix_locked(s)
                            s.done.set()
                    # the cache is DONATED to prefill/decode: a call that
                    # raised after donation leaves the runner's cache
                    # pointing at deleted buffers — rebuild or every later
                    # tick dies.
                    # The PRNG key and token chain are segment OUTPUTS
                    # too: a segment that failed after the assignment
                    # leaves them referencing poisoned buffers, which
                    # would wedge every later request — re-seed/clear.
                    self._runner.new_cache(self.kv_blocks)
                    if self._paged:
                        self._new_block_state(
                            self._alloc.low_watermark,
                            self._alloc.high_watermark,
                        )
                        if self._pcache is not None:
                            # every entry references the dead pool's
                            # blocks — drop them all (no evict callbacks:
                            # the allocator was just rebuilt)
                            self._pcache.clear()
                        # parked handoffs reference the dead pool too;
                        # fail any fetch waiting on them
                        self._handoffs.clear()
                        for _hid, box, ev in list(self._export_q):
                            box["error"] = (
                                "engine recovered from a scheduler error"
                            )
                            ev.set()
                        self._export_q.clear()
                    import jax

                    self._key = jax.random.PRNGKey(
                        int(time.time()) & 0x7FFFFFFF
                    )
                    self._reset_pipeline_locked()

    def _reset_pipeline_locked(self) -> None:
        """Drop every piece of pipeline state that may reference poisoned
        device buffers or failed slots. The deferred in-flight segment is
        POISONED too (its outputs chain from the donated cache the failed
        call consumed) — discard it UNHARVESTED; its slots were already
        failed above, so no tokens are owed. Latency/queue accounting is
        reset alongside (r5 stats()/error-path drift: the old handler
        left counters describing the crashed pipeline), so post-recovery
        stats describe the recovered engine. Caller holds cv."""
        self._chain = None
        self._temps_cache = None
        self._pending = None
        p = self._pipe
        p["errors"] += 1
        p["inflight"] = 0
        p["ticks"] = 0
        for k in ("dispatch_ms_sum", "harvest_ms_sum", "host_ms_sum",
                  "tick_ms_sum", "overlap_ms_sum"):
            p[k] = 0.0
        self._pipe_recent.clear()
        self.metrics.scheduler_errors.inc()
        self.metrics.queue_depth.set(float(len(self._waiting)))

    def _rem(self, s: _Slot) -> int:
        """Remaining token budget for a slot, counting tokens already
        DISPATCHED on device but not yet harvested (``s.pending``): the
        pipeline schedules purely from counts — values arrive a tick
        later."""
        done = len(s.out_ids) + s.pending
        return min(s.max_tokens - done,
                   (self.max_seq - 1) - (len(s.prompt) + done))

    def _maybe_finalize_locked(self, i: int, s: _Slot) -> None:
        """Completion is token-COUNT based (what lets the scheduler size
        decode segments without seeing token values). A slot with tokens
        still in flight on device can never finalize — its values arrive
        at the next harvest. Caller holds cv."""
        if s.pending:
            return
        if s.handoff is not None and s.fed >= len(s.prompt) and s.out_ids:
            # prefill-pool slot: instead of decoding, park the row's
            # blocks under a handoff id and hand the waiter the ticket
            self._finalize_handoff_locked(i, s)
            return
        if (
            len(s.out_ids) >= s.max_tokens
            or len(s.prompt) + len(s.out_ids) >= self.max_seq - 1
        ):
            ms = (time.perf_counter() - s.t0) * 1e3
            s.result = {
                "token_ids": s.out_ids,
                "prompt_len": len(s.prompt),
                "latency_ms": round(ms, 2),
                "tokens_per_sec": round(
                    len(s.out_ids) / (ms / 1e3), 2
                ) if ms > 0 else 0.0,
                "cached_prefix_len": s.cached_len,
                # which weight version actually served the request — the
                # rollout drive's no-version-mixing assertion reads this
                "model_version": s.version or self._default_version,
            }
            if s.ttft_ms is not None:
                s.result["ttft_ms"] = round(s.ttft_ms, 3)
            self._slots[i] = None
            self._free_row_locked(i)
            self._release_prefix_locked(s)
            self._trace_request_locked(
                s, "adopt" if s.adopt is not None else "generate"
            )
            s.done.set()

    # -- disaggregated prefill/decode (docs/serving.md) --------------------

    def _finalize_handoff_locked(self, i: int, s: _Slot) -> None:
        """Park row ``i``'s blocks under a fresh handoff id: the handoff
        takes its OWN reference on every block (incref) so the row can
        free normally — the blocks stay alive until a fetch exports them
        (or the TTL GC gives up on the transfer). Caller holds cv."""
        import uuid

        hid = uuid.uuid4().hex
        blocks = list(self._row_blocks[i])
        self._alloc.incref(blocks)
        self._handoffs[hid] = {
            "blocks": blocks,
            "pos": int(self._pos_host[i]),
            "prompt": list(s.prompt),
            "first_token": int(s.out_ids[0]),
            "max_tokens": int(s.handoff["max_tokens"]),
            "temperature": float(s.temperature),
            "cache_prefix": bool(s.cache_prefix),
            "request_id": s.request_id,
            "ttft_ms": s.ttft_ms,
            "trace": s.trace,
            "span_id": s.span_id,
            # the adopting decode engine must keep serving the SAME
            # weight version the prefill ran on — rides the KVHandoff
            # header so disagg legs never mix versions
            "model_version": s.version or self._default_version,
            "t": time.time(),
        }
        ms = (time.perf_counter() - s.t0) * 1e3
        s.result = {
            "handoff_id": hid,
            "first_token": int(s.out_ids[0]),
            "prompt_len": len(s.prompt),
            "pos": int(self._pos_host[i]),
            "latency_ms": round(ms, 2),
            "cached_prefix_len": s.cached_len,
        }
        if s.ttft_ms is not None:
            s.result["ttft_ms"] = round(s.ttft_ms, 3)
        self._stats["handoffs_out"] += 1
        self._slots[i] = None
        self._free_row_locked(i)
        self._release_prefix_locked(s)
        self._trace_request_locked(s, "prefill")
        s.done.set()

    def _refuse_handoff_with_state(self) -> None:
        """A hand-off is a list of blocks; a row of a model with recurrent
        state is more than that, and the state would stay behind."""
        if self._state_bytes:
            raise ValueError(
                f"preset {self.preset_name!r} {self._state_held}: a block "
                "hand-off would leave it behind"
            )
        if self._window:
            raise ValueError(
                f"preset {self.preset_name!r} keeps two kinds of K/V block: "
                "a block hand-off would miss what the window layers released"
            )

    def prefill_handoff(self, prompt_ids, max_tokens: int = 16,
                        temperature: float = 0.0, timeout_s: float = 600.0,
                        cache_prefix: bool = False, request_id: str = "",
                        trace: Optional[TraceContext] = None,
                        model_version: str = ""):
        """Prefill-pool entry: run the whole-prompt prefill + on-device
        first-token sample exactly like generate(), then export the row's
        KV blocks instead of decoding. Returns a
        :class:`~kubedl_tpu.serving.disagg.KVHandoff` ready for a decode
        replica's :meth:`adopt_handoff`. The handoff point is the
        colocated engine's own prefill/decode seam, which is what makes
        disaggregated greedy output bit-identical."""
        from kubedl_tpu.serving.disagg import HandoffError

        if not self._paged:
            raise ValueError(
                "disaggregated prefill requires kv_layout='paged'"
            )
        self._refuse_handoff_with_state()
        budget = self.max_seq - 1
        prompt = [int(t) for t in list(prompt_ids)[:budget]]
        if not prompt:
            prompt = [0]
        max_tokens = max(0, min(int(max_tokens), budget - len(prompt)))
        if max_tokens < 1:
            raise ValueError(
                "prompt leaves no token budget to hand off "
                f"(len {len(prompt)} of max_seq {self.max_seq})"
            )
        # the prefill row only ever produces the FIRST token (budget 1);
        # the request's real decode budget rides in the handoff meta
        slot = _Slot(prompt, 1, float(temperature), cache_prefix,
                     request_id=request_id)
        slot.handoff = {"max_tokens": max_tokens}
        slot.version = str(model_version or "")
        self._arm_trace(slot, trace)
        self._enqueue_slot_locked_checks(slot)
        result = self._await_slot(slot, timeout_s)
        hid = result.get("handoff_id")
        if hid is None:
            raise HandoffError(result.get("error", "prefill failed"))
        return self.fetch_handoff(hid, timeout_s=min(timeout_s, 60.0))

    def _enqueue_slot_locked_checks(self, slot: _Slot) -> None:
        """Admission gate of generate() and its disaggregated siblings:
        drain rejection, queue-depth/age shedding, KV watermark shedding
        — identical budgets, identical 503 reasons. Also resolves the
        slot's weight version (slot.version holds the REQUESTED id on
        entry; unknown/retiring → UnknownModelVersion, a 400 not a
        503)."""
        with self._cv:
            slot.version = self._resolve_version_locked(slot.version)
            if self._draining:
                self._stats["drain_rejects"] += 1
                raise EngineOverloaded(
                    "engine is draining", retry_after_s=1.0,
                    reason="draining",
                )
            depth = len(self._waiting)
            head_age = (
                time.perf_counter() - self._waiting[0].t0
                if self._waiting else 0.0
            )
            if depth >= self.max_queue_depth or head_age > self.max_queue_age_s:
                # shed instead of queueing: an over-budget queue serves
                # nobody well — tell the client when to come back and let
                # the autoscaler see the rejected demand as backlog
                self._stats["shed"] += 1
                self._shed_recent.append(time.time())
                self.metrics.shed_requests.inc()
                retry = max(1.0, min(self.max_queue_age_s, 0.25 * depth))
                raise EngineOverloaded(
                    f"queue depth {depth} (budget {self.max_queue_depth}), "
                    f"head age {head_age:.1f}s "
                    f"(budget {self.max_queue_age_s}s)",
                    retry_after_s=retry,
                )
            if self._paged and not self._alloc.admission_open():
                # KV-pool pressure sheds too: below the low watermark a
                # queued request cannot be admitted anyway, so reject at
                # the door (hysteresis reopens at the high watermark)
                self._stats["shed"] += 1
                self._stats["kv_sheds"] += 1
                self._shed_recent.append(time.time())
                self.metrics.shed_requests.inc()
                self.metrics.kv_block_sheds.inc()
                raise EngineOverloaded(
                    f"free KV blocks {self._alloc.free_count}/"
                    f"{self._alloc.total} below low watermark",
                    retry_after_s=1.0,
                )
            self._arrivals += 1
            slot.seq = self._arrivals
            self._waiting.append(slot)
            if slot.request_id:
                self._requests[slot.request_id] = slot
            self._cv.notify_all()

    def fetch_handoff(self, hid: str, timeout_s: float = 30.0):
        """Export a parked handoff's block payloads as a KVHandoff and
        release the handoff's block references. The device gather runs on
        the scheduler thread (the only thread that may read the donated
        cache between dispatches); this call just queues the request and
        waits. Raises HandoffError on transfer failure — the blocks are
        freed either way (conservation)."""
        from kubedl_tpu.serving.disagg import HandoffError

        ev = threading.Event()
        box: Dict = {}
        with self._cv:
            if hid not in self._handoffs:
                raise HandoffError(f"unknown or expired handoff {hid!r}")
            self._export_q.append((hid, box, ev))
            self._cv.notify_all()
        if not ev.wait(timeout=timeout_s):
            raise HandoffError(f"handoff export {hid} timed out")
        if "error" in box:
            raise HandoffError(box["error"])
        return box["handoff"]

    def _service_exports(self) -> None:
        """Scheduler-thread half of fetch_handoff: GC expired parked
        handoffs, then export each queued request's blocks (gather →
        host copy → KVHandoff) and free the handoff's references. The
        chaos site ``serving.kv_handoff`` injects a transfer failure
        here — the blocks are freed on that path too."""
        if not self._paged:
            return
        with self._cv:
            if not self._export_q and not self._handoffs:
                return
            now = time.time()
            for hid in [h for h, rec in self._handoffs.items()
                        if now - rec["t"] > self.handoff_ttl_s]:
                rec = self._handoffs.pop(hid)
                self._alloc.free(rec["blocks"])
                self._stats["handoff_failures"] += 1
            work = []
            while self._export_q:
                hid, box, ev = self._export_q.popleft()
                work.append((hid, box, ev, self._handoffs.pop(hid, None)))
        if work:
            with TRACER.phase("engine.exports", handoffs=len(work)):
                self._export_handoffs(work)

    def _export_handoffs(self, work) -> None:
        from kubedl_tpu.serving.disagg import KVHandoff

        for hid, box, ev, rec in work:
            if rec is None:
                box["error"] = f"unknown or expired handoff {hid!r}"
                ev.set()
                continue
            t0 = time.perf_counter()
            try:
                chaos.check("serving.kv_handoff")
                k, v = self._runner.export_blocks(rec["blocks"])
                # the handoff carries its trace as a header-format string
                # (parent = the prefill request span) so a decode engine
                # adopting it WITHOUT an HTTP header still joins the trace
                th = ""
                if rec.get("span_id") and rec.get("trace") is not None:
                    th = TraceContext(
                        rec["trace"].trace_id, rec["span_id"]
                    ).to_header()
                h = KVHandoff(
                    model=self.preset_name,
                    prompt_ids=rec["prompt"],
                    first_token=rec["first_token"],
                    pos=rec["pos"],
                    block_size=self.kv_block_size,
                    k=k, v=v,
                    max_tokens=rec["max_tokens"],
                    temperature=rec["temperature"],
                    request_id=rec["request_id"],
                    cache_prefix=rec["cache_prefix"],
                    ttft_ms=rec["ttft_ms"],
                    trace=th,
                    model_version=rec.get("model_version", ""),
                )
                box["handoff"] = h
                m = self.metrics
                m.handoff_total.inc(direction="export")
                m.handoff_bytes.inc(h.nbytes, direction="export")
                m.handoff_ms.observe(
                    (time.perf_counter() - t0) * 1e3, direction="export"
                )
                if rec.get("span_id"):
                    TRACER.record(
                        "engine.handoff_export", start=t0,
                        duration=time.perf_counter() - t0,
                        trace=rec["trace"], parent_id=rec["span_id"],
                        nbytes=h.nbytes,
                    )
            except Exception as e:
                box["error"] = f"handoff export failed: {e}"
                with self._cv:
                    self._stats["handoff_failures"] += 1
            finally:
                self._alloc.free(rec["blocks"])
                ev.set()

    def adopt_handoff(self, h, timeout_s: float = 600.0,
                      request_id: str = "",
                      trace: Optional[TraceContext] = None,
                      debug_trace: bool = False) -> Dict:
        """Decode-pool entry: adopt a prefill replica's KVHandoff —
        allocate blocks from THIS engine's pool (all-or-nothing, same
        watermark admission as generate), scatter the payloads in, and
        resume decoding from the first token. The returned result has the
        same shape as generate()'s, and for greedy requests the token ids
        are bit-identical to a colocated single-engine call."""
        self._refuse_handoff_with_state()
        if not self._paged:
            raise ValueError(
                "adopting a KV handoff requires kv_layout='paged'"
            )
        if int(h.block_size) != self.kv_block_size:
            raise ValueError(
                f"handoff block_size {h.block_size} != engine "
                f"{self.kv_block_size}"
            )
        if not self._runner.fits_pool(h.k.shape):
            raise ValueError(
                f"handoff KV geometry {h.k.shape} does not fit pool "
                f"{self._runner.pool_shape} "
                f"(model mismatch? handoff model={h.model!r})"
            )
        prompt = [int(t) for t in h.prompt_ids]
        budget = self.max_seq - 1
        if len(prompt) >= budget:
            raise ValueError(
                f"handoff prompt len {len(prompt)} exceeds adopter budget "
                f"{budget}"
            )
        max_tokens = max(1, min(int(h.max_tokens), budget - len(prompt)))
        slot = _Slot(prompt, max_tokens, float(h.temperature),
                     h.cache_prefix, request_id=request_id or h.request_id)
        slot.adopt = h
        # version stickiness across the disagg seam: decode on exactly
        # the version that prefilled (rides the handoff header); a decode
        # replica that has not loaded it rejects the adopt cleanly
        slot.version = str(getattr(h, "model_version", "") or "")
        # explicit context (HTTP header) wins; else the handoff's own
        # embedded trace keeps direct engine→engine adoption on-trace
        if trace is None:
            trace = parse_trace_header(getattr(h, "trace", ""))
        self._arm_trace(slot, trace, debug_trace)
        self._enqueue_slot_locked_checks(slot)
        result = self._await_slot(slot, timeout_s)
        return self._trace_result(slot, result, debug_trace)

    def _admit_row_adopt_locked(self, i: int, slot: _Slot):
        """Admit an adopted slot into row ``i``: allocate the handoff's
        block count from this pool (sharing any prefix-cache match's full
        blocks by reference instead of re-importing them), scatter the
        remaining payloads, and seed the slot at the prefill/decode seam
        (fed = prompt len, out_ids = [first_token], pos = prompt len).
        Returns True (admitted), None (pool dry — slot stays queued), or
        False (transfer failed — waiter woken with an error, blocks all
        returned). Caller holds cv."""
        h = slot.adopt
        a = self._alloc
        bs = self.kv_block_size
        n_blocks = int(h.k.shape[1])
        entry, mlen = None, 0
        if self._pcache is not None:
            self._pcache.observe(slot.prompt)
            entry, mlen = self._pcache.match(slot.prompt)
        entry_blocks = (
            getattr(entry, "blocks", None) if entry is not None else None
        )
        # share only FULL matched blocks: the partial tail needs no COW
        # here because the handoff carries the payload — importing it
        # fresh is cheaper than a device block copy
        shared = list(entry_blocks[:mlen // bs]) if entry_blocks else []
        if len(shared) > n_blocks:
            shared = shared[:n_blocks]
        n_alloc = n_blocks - len(shared)
        got = a.alloc(n_alloc)
        if got is None and self._reclaim_prefix_locked():
            got = a.alloc(n_alloc)
        if got is None:
            if entry is not None:
                self._pcache.unpin(entry)
            return None
        a.incref(shared)
        blocks = shared + got
        if chaos.should_fail("serving.kv_handoff"):
            # transfer failure mid-flight: every reference taken above
            # goes straight back (conservation), the waiter learns why
            a.free(blocks)
            if entry is not None:
                self._pcache.unpin(entry)
            self._stats["handoff_failures"] += 1
            slot.result = {
                "error": "handoff transfer failed (injected)",
                "handoff_failed": True,
            }
            slot.done.set()
            return False
        t0 = time.perf_counter()
        if got:
            start = len(shared)
            self._runner.import_blocks(
                h.k[:, start:n_blocks], h.v[:, start:n_blocks], got
            )
        self._row_blocks[i] = blocks
        self._bt_host[i, :] = 0
        self._bt_host[i, :len(blocks)] = blocks
        self._pos_host[i] = min(int(h.pos), self.max_seq - 1)
        slot.fed = len(slot.prompt)
        slot.out_ids = [int(h.first_token)]
        slot.cached_len = len(shared) * bs
        if slot.ttft_ms is None and h.ttft_ms is not None:
            slot.ttft_ms = float(h.ttft_ms)
        self._slots[i] = slot
        # the adopted row's first decode input (h.first_token) exists only
        # HOST-side — a device chain left by this row's previous tenant
        # would otherwise pass the chain_ok row check and feed that
        # tenant's stale sampled id instead
        self._chain = None
        if entry is not None:
            self.metrics.prefix_hits.inc()
            # the row is self-contained once the shares are increfed —
            # no prefill will read through the entry, drop the pin now
            self._pcache.unpin(entry)
        elif self._pcache is not None:
            self.metrics.prefix_misses.inc()
        self._stats["handoffs_in"] += 1
        m = self.metrics
        m.handoff_total.inc(direction="adopt")
        m.handoff_bytes.inc(h.nbytes, direction="adopt")
        m.handoff_ms.observe(
            (time.perf_counter() - t0) * 1e3, direction="adopt"
        )
        if slot.span_id:
            TRACER.record("engine.handoff_adopt", start=t0,
                          duration=time.perf_counter() - t0,
                          trace=slot.trace, parent_id=slot.span_id,
                          blocks=len(blocks), shared=len(shared))
        # adopted prompts join this replica's prefix cache so the
        # router's block-aware affinity can steer repeats here
        self._maybe_insert_prefix_locked(i, slot)
        self._maybe_finalize_locked(i, slot)
        return True

    def _prefill_bucket(self, max_len: int) -> int:
        """Pad prompts to power-of-2 buckets: bounded compile count
        (one per bucket, <= log2(max_seq)) with at most 2x padding."""
        b = 16
        while b < max_len:
            b <<= 1
        return min(b, self.max_seq)

    def _count_view_keys_locked(self, keys: int, gathers: int) -> None:
        """Attention touched ``keys`` keys (a layer) where ``gathers`` rows'
        whole tables would have been ``max_seq`` each: the share of the
        full table that a view's span, or the decode kernel's reads, leave.
        Caller holds cv."""
        self.metrics.view_keys.inc(keys)
        self.metrics.view_keys_full.inc(self.max_seq * gathers)
        self._stats["view_keys"] += keys
        self._stats["view_keys_full"] += self.max_seq * gathers

    @staticmethod
    def segment_size(need: int, cap: int,
                     buckets: tuple = SEGMENT_BUCKETS) -> int:
        """Pure host-side bucket policy (unit-testable without a device):
        pick the segment size for a remaining budget of ``need`` tokens.
        Rounds UP to the smallest covering bucket only when the overshoot
        is small (<= a quarter of the bucket: rem=31 runs one 32-segment
        discarding 1), else steps DOWN to the largest bucket below
        (rem=7 runs a 4-segment instead of burning 25 wasted decodes).
        ``cap`` (4 or 1 while prefill work is owed, `choose_segment`)
        bounds how long that work waits."""
        need = max(1, min(int(need), int(cap)))
        up = next((b for b in reversed(buckets) if b >= need), buckets[0])
        if up - need <= up // 4:
            return up
        return next((b for b in buckets if b <= need), 1)

    @classmethod
    def choose_segment(cls, need: int, waiting: int, mid_prefill: int,
                       decoding: int,
                       buckets: tuple = SEGMENT_BUCKETS) -> tuple:
        """The tick's decode segment, from the prefill work it still owes
        once this tick's prefill budget is spent: ``waiting`` requests
        that have no row yet and ``mid_prefill`` rows whose prompt is not
        wholly fed. Either is served only between two segments, so while
        there is any the segment is short; with none owed the choice is
        `segment_size` over the rows' budgets alone. A segment costs what
        its steps cost (PERF.md section 5), so how short it is only
        splits the device between the owed prompts and the ``decoding``
        rows. A request with no row needs a row to finish: 4 steps. Rows
        mid-prompt need only the next tick: one step where they are at
        least as many as the rows decoding and nobody waits for a row
        (more requests gain a sooner chunk than pay a longer token gap),
        else 4 steps too. Returns ``(k, short)``; ``short`` says why the
        cap made the segment shorter than the budgets asked for:
        ``"waiting"``, ``"prefill"`` (no request waits, a row does), or
        ``""`` where it did not."""
        free = cls.segment_size(need, buckets[0], buckets)
        if not (waiting or mid_prefill):
            return free, ""
        prefill_bound = not waiting and mid_prefill >= decoding
        k = cls.segment_size(need, buckets[2 if prefill_bound else 1],
                             buckets)
        if k >= free:
            return k, ""
        return k, "waiting" if waiting else "prefill"

    # -- pipeline stages ---------------------------------------------------

    def _harvest_segment(self, acct: Dict) -> None:
        """Harvest the deferred in-flight decode segment: `device_get` its
        sampled ids (blocks until the device finishes the segment), append
        the values to each slot, finalize completed requests, and admit
        waiters. No-op when nothing is in flight. Adds the time blocked
        and the host's time to the tick's ``acct``."""
        pend, self._pending = self._pending, None
        if pend is None:
            return
        with TRACER.phase("engine.harvest_wait", what="segment") as wait:
            rows = _to_host(pend["toks"])  # [B, k]
            # and what the runner's segment counted beside them, by name
            counted = {name: _to_host(v)
                       for name, v in (pend["counters"] or {}).items()}
        t1 = time.perf_counter()
        seg_t0 = pend.get("t0", t1)
        with TRACER.phase("engine.harvest_host", seq=pend["seq"], **{
            name: int(v) for name, v in counted.items() if v.ndim == 0
        }) as host, self._cv:
            self._pipe["inflight"] = 0
            for name, v in counted.items():
                self._segment_counters[name] = (
                    self._segment_counters.get(name, 0) + v)
                if name in self.metrics.segment_counters:
                    self.metrics.segment_counters[name].inc(int(v))
            for i, s, take in pend["sched"]:
                s.pending -= take
                if self._slots[i] is not s:
                    continue  # vacated (request timeout) mid-segment
                s.out_ids.extend(int(t) for t in rows[i][:take])
                if s.span_id and take:
                    # segment wall time is SHARED by every scheduled row
                    # (one batched dispatch); each row gets its own span
                    # so per-request trees stay self-contained
                    TRACER.record("engine.decode_segment", start=seg_t0,
                                  duration=t1 - seg_t0, trace=s.trace,
                                  parent_id=s.span_id, tokens=take)
                self._maybe_finalize_locked(i, s)
            self._admit_locked()
            self._cv.notify_all()
        acct["harvest_ms"] += wait.ms
        acct["host_ms"] += host.ms

    def _note_first_token_locked(self, s: _Slot, now: float) -> None:
        """Where ``s``'s time to first token went, from its stamps and the
        ``now`` that stamped ``ttft_ms``: the four `TTFT_PARTS` in ms
        (``queue`` to the row, ``backlog`` to its first prefill program,
        ``chunks`` to its final one, ``first`` to the token on the host),
        which share ``ttft_ms``'s two ends and so sum to it, then the
        prefill programs it took and the decode segments and steps the
        tick dispatched between its row and its final program. One record
        for `stats()` and ``pipeline_stats()["first_tokens"]``, one
        histogram sample a part, one ``engine.first_token`` phase (inside
        the caller's ``engine.harvest_host``). Caller holds cv."""
        ms = [round((b - a) * 1e3, 3) for a, b in zip(
            (s.t0, s.t_row, s.prefill_t0, s.t_final),
            (s.t_row, s.prefill_t0, s.t_final, now))]
        self._first_tokens.append((s.seq, *ms, s.n_chunks, *s.ahead))
        for part, v in zip(TTFT_PARTS, ms):
            self.metrics.ttft_part_ms.observe(v, part=part)
        with TRACER.phase("engine.first_token", req=s.seq,
                          **dict(zip(TTFT_PARTS, ms)), n_chunks=s.n_chunks,
                          segments=s.ahead[0], steps=s.ahead[1]):
            pass

    def _harvest_prefill(self, pre, ids_dev, acct: Dict) -> None:
        """Harvest prefill's device-sampled first tokens ([B] int32 — the
        logits never left the device) and record them. Runs AFTER the next
        decode segment is dispatched, so the copy-out overlaps device
        compute. Adds its times to ``acct`` as `_harvest_segment` does."""
        with TRACER.phase("engine.harvest_wait", what="prefill") as wait:
            ids = _to_host(ids_dev)
        now = time.perf_counter()
        with TRACER.phase("engine.harvest_host") as host, self._cv:
            for i, s, budgeted in pre:
                if budgeted:
                    s.pending -= 1
                if self._slots[i] is not s:
                    # vacated (request timeout) mid-prefill; the vacate
                    # path already released any prefix pin
                    continue
                if budgeted and s.ttft_ms is None:
                    s.ttft_ms = (now - s.t0) * 1e3
                    self._ttft_recent.append(s.ttft_ms)
                    self.metrics.ttft_ms.observe(s.ttft_ms)
                    self._note_first_token_locked(s, now)
                if budgeted:
                    s.out_ids.append(int(ids[i]))
                if s.span_id:
                    TRACER.record("engine.prefill", start=s.prefill_t0,
                                  duration=now - s.prefill_t0,
                                  trace=s.trace, parent_id=s.span_id,
                                  prompt_len=len(s.prompt),
                                  cached_len=s.cached_len,
                                  chunks=s.n_chunks, segments=s.ahead[0],
                                  steps=s.ahead[1])
                # the row's prefix KV is now self-contained (prefill has
                # completed) — the grafted entry no longer needs its pin
                self._release_prefix_locked(s)
                self._maybe_insert_prefix_locked(i, s)
                self._maybe_finalize_locked(i, s)
            self._admit_locked()
            self._cv.notify_all()
        acct["harvest_ms"] += wait.ms
        acct["host_ms"] += host.ms

    def _dispatch_prefill(self, sched, acct: Dict, params, suffix: bool):
        """Dispatch the prefill of ``sched`` = ``[(row, slot, base, take,
        final)]``: ``take`` prompt tokens of each row from position
        ``base``, in ``sched``'s order. A paged engine computes ONLY the
        rows that hold prompt tokens: one one-row program for each row of
        ``sched``, each in its own power-of-2 bucket — the program set is
        one per bucket however many rows arrive together, so nothing new
        compiles when a second row shares a tick, and a row's last chunk
        does not pad to its neighbour's. A contiguous cache is addressed
        by batch row, so there one program still computes all
        ``max_batch`` rows. ``suffix`` forces the suffix program (chunks
        always attend through the pool); else a row (a batch, contiguous)
        with nothing grafted runs the local whole-prompt program.

        One key split, one first-token sample over the ``[max_batch, V]``
        logits and one chain merge (final rows only) for the whole of
        ``sched``, whatever the number of programs, so a sampled request
        draws the noise it always drew. Each program runs under its own
        ``engine.prefill_dispatch`` phase (``slots`` = rows it computes),
        and its dispatch stamps the slots it feeds (`_stamp_dispatched`).
        Returns the sampled ids, still on the device."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        groups = [[t] for t in sched] if self._paged else [sched]
        logits = None  # of the tick's earlier programs
        prefill_ids = None
        saved = positions = view_keys = views = resets = 0
        for n, group in enumerate(groups):
            slots = len(group) if self._paged else self.max_batch
            bucket = self._prefill_bucket(
                max(max(t for _i, _s, _b, t, _f in group), 1)
            )
            with TRACER.phase(
                "engine.prefill_dispatch", bucket=bucket, rows=len(group),
                tokens=sum(t for _i, _s, _b, t, _f in group), slots=slots,
                # query-key pairs causal attention needs for these tokens
                keys=sum(t * b + t * (t + 1) // 2 for _i, _s, b, t, _f in group),
            ) as ph:
                toks = np.zeros((slots, bucket), np.int32)
                lens = np.zeros((slots,), np.int32)
                starts = np.zeros((slots,), np.int32)
                for j, (i, s, base, take, _final) in enumerate(group):
                    r = j if self._paged else i
                    toks[r, :take] = s.prompt[base:base + take]
                    lens[r] = take
                    starts[r] = base
                    if s.prefill_pos < 0 and s.cached_len:
                        saved += s.cached_len  # first dispatch of a graft
                if n == 0:
                    self._key, pick_key = jax.random.split(self._key)
                    if self._paged:
                        # the programs after the first run on the cache
                        # the one before returned
                        self._upload_mirrors()
                from_prefix = suffix or bool(np.any(starts > 0))
                # every position the program reads or writes lies below
                live_to = min(int(starts.max()) + bucket, self.max_seq)
                t_disp = time.perf_counter()
                logits = self._runner.prefill(
                    params, jnp.asarray(toks), jnp.asarray(lens),
                    starts=jnp.asarray(starts) if from_prefix else None,
                    rows=jnp.asarray(
                        np.array([i for i, *_ in group], np.int32)
                    ) if self._paged else None,
                    acc=logits, live_to=live_to,
                )
                self._stamp_dispatched(group, t_disp, ph)
                positions += slots * bucket
                if self._state_bytes:
                    # rows that go on from the state their last chunk left;
                    # the others start from a zero slab, inside the program
                    carried = sum(1 for _i, _s, b, _t, _f in group if b > 0)
                    ph.set(carried=carried)
                    resets += len(group) - carried
                if from_prefix and self._paged:
                    # the whole-prompt program attends locally: no view
                    span = self._runner.span_for(live_to)
                    ph.set(span=span)
                    view_keys += span * slots
                    views += slots
                if n == len(groups) - 1:
                    prefill_ids = self._sample_first(sched, logits, pick_key)
            acct["dispatch_ms"] += ph.ms
        tokens = sum(t for _i, _s, _b, t, _f in sched)
        if saved:
            if self._pcache is not None:
                self._pcache.add_tokens_saved(saved)
            self.metrics.prefix_tokens_saved.inc(saved)
        self.metrics.prefill_tokens.inc(tokens)
        self.metrics.prefill_positions.inc(positions)
        if resets:
            self.metrics.state_resets.inc(resets)
        with self._cv:
            self._stats["prefill_tokens"] += tokens
            self._stats["prefill_positions"] += positions
            self._stats["state_resets"] += resets
            self._count_view_keys_locked(view_keys, views)
        return prefill_ids

    def _stamp_dispatched(self, group, t_disp: float, phase) -> None:
        """One prefill program of ``group`` was dispatched at ``t_disp``
        (taken just before the jitted call): stamp the slots it feeds
        with ``prefill_t0`` (their first program), ``t_final`` and the
        decode work dispatched since their row (the program that holds
        their last prompt token) and ``n_chunks``, and name on the
        program's ``engine.prefill_dispatch`` phase whose tokens it
        computes (``req``), from which position (``base``) and whether
        they end the prompt (``final``); a batched program's ``req`` and
        ``base`` are comma-joined in the order of its rows. Kept out of
        `_dispatch_prefill`: the time JAX takes to lower a program it
        meets for the first time moves with the code of the frame that
        calls it (PERF.md section 6, PR 39)."""
        for _i, s, _base, _take, final in group:
            s.n_chunks += 1
            if s.prefill_t0 is None:
                s.prefill_t0 = t_disp
            if final:
                s.t_final = t_disp
                s.ahead = (self._segment_seq - s.seg_mark[0],
                           self._steps_dispatched - s.seg_mark[1])
        if len(group) == 1:
            _i, s, base, _take, final = group[0]
            phase.set(req=s.seq, base=base, final=int(final))
        else:
            phase.set(req=",".join(str(s.seq) for _i, s, *_ in group),
                      base=",".join(str(b) for _i, _s, b, *_ in group),
                      final=int(all(t[4] for t in group)))

    def _sample_first(self, sched, logits, pick_key):
        """Sample the first token of every row on the device (``[B]``
        int32; only the rows of ``sched`` are ever read) and graft the
        FINAL rows' into the device token chain, so they join the next
        decode segment with zero host->device traffic. Rows whose chunk
        was intermediate leave the chain (and its generation) alone, so
        in-flight decode feeds stay valid between chunks."""
        import numpy as np
        import jax.numpy as jnp

        temps0 = np.zeros((self.max_batch,), np.float32)
        for i, s, _base, _take, _final in sched:
            temps0[i] = max(float(s.temperature), 0.0)
        prefill_ids = self._runner.sample_first(
            logits, jnp.asarray(temps0), pick_key
        )  # stays on device until after the next dispatch
        final_rows = tuple(i for i, _s, _b, _t, f in sched if f)
        if not final_rows:
            return prefill_ids
        self._prefill_gen += 1
        mask = np.zeros((self.max_batch,), bool)
        mask[list(final_rows)] = True
        if self._chain is not None:
            # per-row chain validity: untouched rows keep the in-flight
            # segment's output tokens
            merged = self._runner.merge_chain(
                self._chain[2], prefill_ids, jnp.asarray(mask)
            )
            self._chain = (
                self._prefill_gen,
                tuple(sorted(set(self._chain[1]) | set(final_rows))),
                merged,
            )
        else:
            self._chain = (
                self._prefill_gen, final_rows, prefill_ids[:, None]
            )
        return prefill_ids

    def _prompt_fed_locked(self, i: int, s: _Slot) -> tuple:
        """Row ``i``'s whole prompt is dispatched: it decodes from here, and
        owes a first token (in flight now) unless it has no budget for one.
        Returns the row's entry for `_harvest_prefill`. Caller holds cv."""
        s.fed = len(s.prompt)
        budgeted = (
            s.max_tokens > 0
            and len(s.prompt) + len(s.out_ids) < self.max_seq - 1
        )
        if budgeted:
            s.pending += 1
        return i, s, budgeted

    def _prefill_chunks(self, todo, acct: Dict, params=None):
        """Chunked-admission prefill dispatch (docs/serving.md
        "Continuous batching"): spend at most ``prefill_chunk_tokens``
        prompt tokens this tick across the not-yet-prefilled rows, FIFO
        by arrival time so chunk scheduling preserves admission order at
        chunk granularity. Every chunk goes through the suffix prefill
        (`llama.paged_prefill_from`) at the row's committed position;
        intermediate chunks are block-aligned (no KV block is ever
        written by two dispatches) and touch nothing but the pool and
        the pos mirror, so decode segments keep dispatching between
        them. Only rows whose FINAL chunk lands this tick sample a
        first token, join the device chain, and become decoding rows.
        When the budget runs out mid-prompt the FIFO head keeps the
        leftover — later arrivals never overtake it. Returns the
        ``(pre, prefill_ids)`` pair the caller's deferred
        `_harvest_prefill` consumes (final rows only)."""
        bs = self.kv_block_size
        left = self.prefill_chunk_tokens
        sched = []  # (row, slot, base, take, final)
        for i, s in sorted(todo, key=lambda t: t[1].t0):
            if left <= 0:
                break
            base = s.prefill_pos if s.prefill_pos >= 0 else s.cached_len
            rem = max(0, len(s.prompt) - base)
            take = min(rem, left)
            if take < rem:
                take = (take // bs) * bs
                if take <= 0:
                    break
            sched.append((i, s, base, take, base + take >= len(s.prompt)))
            left -= take
        if not sched:
            return [], None
        with self._cv:
            for i, _s, base, take, _final in sched:
                self._reserve_window_locked(i, base + take)
        # injected chunk-dispatch fault: the scheduler must recover
        # (fail in-flight slots, rebuild the donated cache, keep
        # serving) exactly as for a decode-segment fault
        chaos.check("serving.chunk_admit")
        prefill_ids = self._dispatch_prefill(
            sched, acct, self.params if params is None else params,
            suffix=True,
        )
        self.metrics.admission_chunks.inc(len(sched))
        pre = []
        with self._cv:
            for i, s, base, take, final in sched:
                # mirror the device's pos advance for dispatched rows
                # (vacated rows get reset at readmission)
                self._advance_pos_locked(i, base + take)
                if self._slots[i] is not s:
                    continue  # vacated (request timeout) mid-chunk
                s.prefill_pos = base + take
                if final:
                    pre.append(self._prompt_fed_locked(i, s))
        return pre, (prefill_ids if pre else None)

    def _spec_tick(self, decoding, acct: Dict, params=None) -> None:
        """One draft-k/verify-1 round over every greedy decoding row.

        Per row: the pluggable draft proposes k tokens from the full host
        context; the verify forward consumes ``[next_input, d1..dk]`` in
        ONE batched call (`llama.paged_verify`) and returns the target's
        greedy argmax after each input. The longest prefix where drafts
        agree with those argmaxes is accepted, plus one bonus token —
        every emitted token is the target's own greedy choice given only
        accepted history, so output is bit-identical to plain decode (the
        tier-1 gate); speculation only changes how many sequential
        forwards it takes. The pos mirror then rewinds past the rejected
        suffix and `_trim_row_locked` frees its KV blocks in place.

        With ``spec_candidates > 1`` the draft proposes N candidate
        continuations per row (`propose_candidates`; candidate 0 is
        always the plain greedy proposal). A READ-ONLY scoring forward
        (`llama.paged_verify_multi`) ranks all N against the target in
        one batched call, the longest-agreeing candidate is swapped into
        the verify window, and the standard write-path verify runs on
        the winner — so multi-candidate never emits anything but target
        argmaxes, and never accepts fewer tokens than candidate 0 would
        have. Draft proposal wall time is measured per round
        (`spec_draft_ms`) so dashboards can attribute decode time to
        draft vs verify."""
        import numpy as np
        import jax.numpy as jnp

        from kubedl_tpu.serving.speculative import accept_length, build_tree

        if params is None:
            params = self.params
        k = self.spec_k
        S = k + 1
        N = self.spec_candidates
        multi = N > 1
        tree = multi and self.spec_tree
        draft_kind = getattr(self._draft, "name", self.spec_draft)
        # phase 1 — snapshot contexts under the lock, DRAFT OUTSIDE IT:
        # a model draft's forward must not stall admission/finalize.
        # Only this scheduler thread mutates prompt/out_ids/fed, so the
        # snapshot stays coherent; vacated rows are re-checked by slot
        # identity before anything is committed.
        with self._cv:
            cand = [
                (i, s, list(s.prompt) + list(s.out_ids), s.next_input())
                for i, s in decoding if self._slots[i] is s
            ]
        if not cand:
            return
        t_d = time.perf_counter()  # start of the rows' engine.spec_round
        with TRACER.phase("engine.spec_draft", rows=len(cand)) as ph:
            if multi:
                cand_lists = [
                    self._draft.propose_candidates(ctx, k, N)
                    for _, _, ctx, _ in cand
                ]
            else:
                cand_lists = [
                    [p] for p in self._draft.propose_batch(
                        [ctx for _, _, ctx, _ in cand], k
                    )
                ]
        self._spec_stats.record_draft_ms(ph.ms)
        self.metrics.spec_draft_ms.observe(ph.ms, draft=draft_kind)

        def _pad(drafts, ctx):
            d = [int(t) for t in drafts][:k]
            if len(d) < k:
                pad = d[-1] if d else int(ctx[-1])
                d = d + [pad] * (k - len(d))
            return d

        toks = np.zeros((self.max_batch, S), np.int32)
        lens = np.zeros((self.max_batch,), np.int32)
        starts = np.zeros((self.max_batch,), np.int32)
        cand_toks = (
            np.zeros((self.max_batch, N, S), np.int32) if multi else None
        )
        with self._cv:
            rows = []
            for (i, s, ctx, nxt), clists in zip(cand, cand_lists):
                if self._slots[i] is not s:
                    continue
                dl = [_pad(d, ctx) for d in clists[:N]]
                if not dl:
                    dl = [[int(ctx[-1])] * k]
                while len(dl) < N:
                    dl.append(dl[0])
                toks[i, 0] = nxt
                toks[i, 1:] = dl[0]
                lens[i] = S
                starts[i] = self._pos_host[i]
                if multi:
                    cand_toks[i, :, 0] = nxt
                    for c_n, c_d in enumerate(dl):
                        cand_toks[i, c_n, 1:] = c_d
                rows.append((i, s, dl))
            # coverage for S appends per row, preempting on exhaustion;
            # rows the reserve drops sit this verify out entirely
            surviving = self._reserve_decode_locked(
                [(i, s) for i, s, _ in rows], S
            )
            dmap = {i: d for i, _, d in rows}
            rows = [(i, s, dmap[i]) for i, s in surviving]
            for i in set(dmap) - {i for i, _, _ in rows}:
                lens[i] = 0  # dropped/preempted: inactive in the verify
        if not rows:
            return
        chaos.check("serving.dispatch")
        with TRACER.phase(
            "engine.spec_dispatch", k=k, rows=len(rows),
            slots=self.max_batch,
        ) as ph:
            self._upload_mirrors()
            if tree:
                # trie ranking pass (read-only, like multi): candidates
                # sharing a prefix share trie nodes, one forward scores
                # every node under its ancestor mask, and the deepest
                # accepted root path becomes the write-path verify's draft
                M = self._spec_tree_m
                toks_tr = np.zeros((self.max_batch, M), np.int32)
                pos_tr = np.zeros((self.max_batch, M), np.int32)
                mask_tr = np.zeros((self.max_batch, M, M), bool)
                mask_tr[:, np.arange(M), np.arange(M)] = True  # inactive rows
                lens_tr = np.zeros((self.max_batch,), np.int32)
                trees = {}
                for i, s, dl in rows:
                    tr = build_tree(int(toks[i, 0]), dl, k, M)
                    trees[i] = tr
                    t_toks, t_dep, t_mask = tr.arrays(M)
                    toks_tr[i] = t_toks
                    pos_tr[i] = int(starts[i]) + t_dep
                    mask_tr[i] = t_mask
                    lens_tr[i] = tr.size
                ids_tree = _to_host(self._runner.verify_tree(
                    params, jnp.asarray(toks_tr),
                    jnp.asarray(pos_tr), jnp.asarray(mask_tr),
                    jnp.asarray(lens_tr), jnp.asarray(starts),
                ))  # [B, M]
                for i, s, dl in rows:
                    path = trees[i].walk(ids_tree[i])
                    # the walk follows unique-token children, so it only
                    # leaves the greedy chain where that chain already
                    # mismatched — switching can never shorten acceptance
                    switched = bool(path) and path != dl[0][:len(path)]
                    self._spec_stats.record_candidates(trees[i].size, switched)
                    if switched:
                        dl[0] = _pad(path, [toks[i, 0]])
                        toks[i, 1:] = dl[0]
            elif multi:
                # read-only ranking pass (cache neither donated nor written)
                ids_multi = _to_host(self._runner.verify_multi(
                    params, jnp.asarray(cand_toks),
                    jnp.asarray(lens), jnp.asarray(starts),
                ))  # [B, N, S]
                for i, s, dl in rows:
                    best = 0
                    best_a = accept_length(dl[0], ids_multi[i, 0][:k])
                    for c_n in range(1, N):
                        a_n = accept_length(dl[c_n], ids_multi[i, c_n][:k])
                        if a_n > best_a:
                            best, best_a = c_n, a_n
                    self._spec_stats.record_candidates(N, best != 0)
                    if best:
                        dl[0] = dl[best]  # the accept loop reads dl[0]
                        toks[i, 1:] = dl[0]
            ids_dev = self._runner.verify(
                params, jnp.asarray(toks),
                jnp.asarray(lens), jnp.asarray(starts),
            )
        acct["dispatch_ms"] += ph.ms
        with TRACER.phase("engine.harvest_wait", what="verify") as ph:
            ids = _to_host(ids_dev)  # [B, S]
        acct["harvest_ms"] += ph.ms
        with TRACER.phase("engine.harvest_host") as ph, self._cv:
            for i, s, dl in rows:
                drafts = dl[0]
                a = accept_length(drafts, ids[i][:k])
                if self._slots[i] is not s:
                    continue  # vacated mid-verify; writes land in trash
                take = min(a + 1, self._rem(s))
                s.out_ids.extend(int(t) for t in ids[i][:take])
                s.fed += take
                # rewind past the rejected suffix: the device advanced
                # pos by S, the mirror keeps only accepted history and
                # the next upload makes it so
                self._pos_host[i] = min(
                    int(starts[i]) + take, self.max_seq - 1
                )
                self._trim_row_locked(i, int(self._pos_host[i]))
                self._spec_stats.record(k, a, take)
                self.metrics.spec_proposed.inc(k, draft=draft_kind)
                self.metrics.spec_accepted.inc(a, draft=draft_kind)
                if s.span_id:
                    TRACER.record("engine.spec_round", start=t_d,
                                  duration=time.perf_counter() - t_d,
                                  trace=s.trace, parent_id=s.span_id,
                                  k=k, accepted=int(a), emitted=take)
                self._maybe_finalize_locked(i, s)
            self._admit_locked()
            self._cv.notify_all()
        # the verify consumed host-fed tokens: any device chain is stale
        self._chain = None
        acct["segments"] += 1
        acct["host_ms"] += ph.ms

    def _commit_tick(self, acct: Dict, tick_ms: float) -> None:
        """Fold one tick's accounting into the pipeline stats + metrics."""
        overlap_ms = (
            acct["dispatch_ms"] + acct["host_ms"] if acct["overlapped"]
            else 0.0
        )
        with self._cv:
            p = self._pipe
            p["ticks"] += 1
            p["segments"] += acct["segments"]
            if acct["segment_k"]:
                p[f"segments_k{acct['segment_k']}"] += 1
            if acct["short"]:
                p[f"short_{acct['short']}"] += 1
            p["deferred_harvests"] += acct["deferred"]
            p["flushes"] += acct["flushes"]
            p["chain_rebuilds"] += acct["rebuilds"]
            p["dispatch_ms_sum"] += acct["dispatch_ms"]
            p["harvest_ms_sum"] += acct["harvest_ms"]
            p["host_ms_sum"] += acct["host_ms"]
            p["tick_ms_sum"] += tick_ms
            p["overlap_ms_sum"] += overlap_ms
            self._pipe_recent.append(
                (acct["dispatch_ms"], acct["harvest_ms"], acct["host_ms"],
                 tick_ms)
            )
            queued = len(self._waiting)
            ratio = p["overlap_ms_sum"] / max(p["tick_ms_sum"], 1e-9)
        m = self.metrics
        if acct["segments"]:
            m.segments.inc(acct["segments"])
        if acct["segment_k"]:
            m.segment_lengths.inc(k=str(acct["segment_k"]),
                                  short=acct["short"] or "no")
        if acct["deferred"]:
            m.deferred_harvests.inc(acct["deferred"])
        if acct["flushes"]:
            m.pipeline_flushes.inc(acct["flushes"])
        if acct["rebuilds"]:
            m.chain_rebuilds.inc(acct["rebuilds"])
        m.dispatch_ms.observe(acct["dispatch_ms"])
        m.harvest_ms.observe(acct["harvest_ms"])
        m.host_ms.observe(acct["host_ms"])
        m.overlap_ratio.set(ratio)
        m.queue_depth.set(float(queued))
        if self._paged:
            st = self._alloc.stats()
            kern = {"attention_kernel": self.kv_attention,
                    "role": self.role}
            m.kv_blocks_total.set(float(st["total"]), **kern)
            m.kv_blocks_free.set(float(st["free"]), **kern)
            m.kv_blocks_shared.set(float(st["shared"]), **kern)
            if self._wtable is not None:
                wst = self._wtable.alloc.stats()
                m.kv_window_blocks_total.set(float(wst["total"]))
                m.kv_window_blocks_free.set(float(wst["free"]))
                released = self._wtable.released
                m.kv_window_blocks_released.inc(
                    released - self._window_released_seen)
                self._window_released_seen = released
        if self._spec_stats is not None:
            m.spec_acceptance_rate.set(self._spec_stats.acceptance_rate())

    def _loop_once(self) -> bool:
        """One tick of the DOUBLE-BUFFERED decode pipeline; returns True
        when the engine is stopping.

        The old tick was synchronous — dispatch segment, block in
        `device_get` for its tokens, do host bookkeeping, dispatch the
        next — so the chip idled through every copy-out + host round trip
        (~4 ms/token of the r5 b1 engine overhead). Now a tick in steady
        state (segment N-1 already in flight on device):

            dispatch prefill (new rows)        } async: queue behind N-1,
            dispatch decode segment N          } tokens chained ON DEVICE
            harvest segment N-1 (device_get)   — blocks until N-1 done...
            bookkeeping/finalize/admission     } ...then everything here
            harvest prefill first tokens       } overlaps N's device time

        Freshly prefilled rows join segment N in the SAME tick: their
        first sampled ids are grafted into the device chain
        (`llama.merge_chain_tokens`) before the segment is dispatched, so
        TTFT never serializes behind an in-flight segment's harvest.
        Scheduling is count-based (``_rem`` includes in-flight tokens);
        values land one tick later and completed slots finalize at
        harvest, when their token values exist host-side."""
        def nothing_to_run() -> bool:
            return (
                not self._stop and self._pending is None
                and not self._export_q and not self._handoffs
                and not any(s is not None for s in self._slots)
            )

        with self._cv:
            self._admit_locked()
            if nothing_to_run():
                with TRACER.phase("engine.idle_wait"):
                    while nothing_to_run():
                        self._cv.wait(timeout=0.2)
                        self._admit_locked()
            stop = self._stop
            waiting = bool(self._waiting)
        # handoff exports run on THIS thread (sole owner of the donated
        # cache between dispatches) before the tick's own dispatches
        self._service_exports()
        if stop:
            # flush: deliver in-flight tokens (no tick to account them to)
            self._harvest_segment({"harvest_ms": 0.0, "host_ms": 0.0})
            return True

        # the tick's times ARE its phase spans' durations: one measurement
        # feeds pipeline_stats(), /metrics and a profiler capture alike
        with TRACER.phase("engine.tick") as tick:
            compiles = _COMPILES[0]
            acct = self._run_tick(waiting)
            tick.set(segments=acct["segments"], waiting=int(waiting),
                     rebuilds=acct["rebuilds"],
                     compiles=_COMPILES[0] - compiles)
        self._commit_tick(acct, tick.ms)
        return False

    def _run_tick(self, waiting: bool) -> Dict:
        """The body of one tick (see `_loop_once`); returns its accounting
        for `_commit_tick`."""
        import numpy as np

        acct = {"dispatch_ms": 0.0, "harvest_ms": 0.0, "host_ms": 0.0,
                "overlapped": False, "segments": 0, "deferred": 0,
                "flushes": 0, "rebuilds": 0, "segment_k": 0, "short": ""}

        if waiting and self._pending is not None:
            # requests queued: harvest FIRST so finished rows free up and
            # admission waits for at most ONE (small) segment instead of
            # queueing behind a freshly dispatched one — trades this
            # tick's overlap for bounded admission latency
            self._harvest_segment(acct)
            acct["flushes"] += 1

        with self._cv:
            self._admit_locked()
            active = list(self._slots)
            # one weight version per tick: every dispatch below (prefill
            # group, decode segment, spec round) uses THIS tree only;
            # rows of co-resident versions sit the tick out (round-robin
            # alternation — a host-mirror no-op for them) so a forward
            # never mixes parameter trees
            tick_version = self._pick_tick_version_locked(active)
            vp = self._versions[tick_version]

        if tick_version != self._default_version:
            # seeded canary degradation (``serving.canary_dispatch``):
            # hits ONLY non-default-version ticks, so a drill can make
            # a deliberately-degraded canary burn its own SLO partition
            # while baseline traffic on the same replica stays healthy
            chaos.check("serving.canary_dispatch")

        def _mine(s: _Slot) -> bool:
            return (s.version or self._default_version) == tick_version

        # ---- prefill DISPATCH: newly admitted rows consume their WHOLE
        # prompt in one batched forward (TTFT = one forward, not
        # prompt_len decode steps); the first token is sampled on device
        # and its copy-out DEFERRED until after the next segment dispatch
        pre: list = []
        prefill_ids = None
        todo = [(i, s) for i, s in enumerate(active)
                if s is not None and s.fed == 0 and _mine(s)]
        if todo and self.prefill_chunk_tokens:
            # chunked admission: bounded prefill work per tick, rows
            # join the running decode batch chunk by chunk
            pre, prefill_ids = self._prefill_chunks(todo, acct, vp)
            with self._cv:
                active = list(self._slots)
        elif todo:
            # suffix-only prefill: rows with a grafted prefix consume only
            # prompt[cached_len:]. A paged engine needs no overflow fixup:
            # its suffix prefill routes pad/clamped writes to the trash
            # block, so a graft whose start + bucket spills past max_seq
            # is harmless by construction (proven in test_kv_blocks). The
            # contiguous bucket is sized by the LONGEST suffix, and
            # `lax.dynamic_update_slice` CLAMPS out-of-bounds starts, so
            # any graft whose start + bucket would spill past max_seq is
            # dropped (full prefill for that row) and the bucket
            # recomputed — terminates because starts=0 always fits.
            while not self._paged:
                bucket = self._prefill_bucket(
                    max(len(s.prompt) - s.cached_len for _, s in todo)
                )
                bad = [(i, s) for i, s in todo
                       if s.cached_len and s.cached_len + bucket > self.max_seq]
                if not bad:
                    break
                with self._cv:
                    for _, s in bad:
                        s.cached_len = 0
                        self._release_prefix_locked(s)
            with self._cv:
                for i, s in todo:
                    self._reserve_window_locked(i, len(s.prompt))
            prefill_ids = self._dispatch_prefill(
                [(i, s, s.cached_len, len(s.prompt) - s.cached_len, True)
                 for i, s in todo],
                acct, vp, suffix=False,
            )
            with self._cv:
                for i, s in todo:
                    if self._paged:
                        # mirror the device's pos update for dispatched
                        # rows (vacated rows get reset at readmission)
                        self._advance_pos_locked(i, len(s.prompt))
                    if self._slots[i] is not s:
                        continue  # vacated (request timeout) mid-prefill
                    pre.append(self._prompt_fed_locked(i, s))
                active = list(self._slots)

        if self.spec_k and pre:
            # speculative ticks feed the verify window from HOST context
            # (prompt + harvested tokens), so the deferred prefill
            # harvest has nothing to overlap — collect first tokens now
            # and let fresh rows join this tick's verify
            self._harvest_prefill(pre, prefill_ids, acct)
            pre = []
            prefill_ids = None
            with self._cv:
                active = list(self._slots)

        # ---- decode segment DISPATCH: K steps in one jitted call with
        # on-device sampling (llama.decode_segment); rows whose budget
        # ends mid-segment discard the overshoot — they are finished and
        # re-prefilled (pos reset) on slot reuse, so the garbage the
        # extra steps wrote to their cache rows is dead
        decoding = [
            (i, s) for i, s in enumerate(active)
            if s is not None and s.fed >= len(s.prompt) and self._rem(s) > 0
            and _mine(s)
        ]

        # ---- speculative verify (draft-k/verify-1): when every decoding
        # row is greedy, one batched forward scores k drafted tokens +
        # the next input per row; the longest draft/argmax agreement is
        # accepted and the pos mirror simply rewinds past any rejected
        # suffix (its blocks are freed in place). Mixed-temperature
        # traffic falls through to the segment path unchanged.
        if decoding and self.spec_k and all(
            float(s.temperature) <= 0.0 for _, s in decoding
        ):
            if self._pending is not None:
                # a deferred segment still owes tokens the verify's host-
                # side draft context needs — flush it first
                self._harvest_segment(acct)
                acct["flushes"] += 1
                with self._cv:
                    decoding = [
                        (i, s) for i, s in decoding
                        if self._slots[i] is s and self._rem(s) > 0
                    ]
            if decoding:
                self._spec_tick(decoding, acct, vp)
            decoding = []

        new_pending = None
        backlog, short = 0, ""
        if decoding:
            need = max(self._rem(s) for _, s in decoding)
            with self._cv:
                # the prefill work still owed now that this tick's budget
                # is spent. Rows of every version count: another
                # version's chunk is dispatched by its own tick, behind
                # this tick's segment, and `_waiting` knows no version
                waiting = len(self._waiting)
                mid_prefill = sum(
                    1 for s in self._slots if s is not None and s.fed == 0
                )
            backlog = waiting + mid_prefill
            k, short = self.choose_segment(need, waiting, mid_prefill,
                                           len(decoding))
            temps = np.zeros((self.max_batch,), np.float32)
            for i, s in decoding:
                temps[i] = max(float(s.temperature), 0.0)
            greedy = not np.any(temps > 0.0)
            # feed from the DEVICE chain whenever it covers the decoding
            # rows: long generations never ship tokens host->device
            tokens_dev = None
            if (
                self._chain is not None
                and self._chain[0] == self._prefill_gen
                and {i for i, _ in decoding} <= set(self._chain[1])
            ):
                tokens_dev = self._chain[2]
            else:
                # stale/absent chain (post-error recovery): rebuild the
                # feed from HOST tokens. In-flight values must land
                # first — s.next_input() indexes into out_ids the
                # deferred segment has not delivered yet.
                self._harvest_segment(acct)
                if pre:
                    self._harvest_prefill(pre, prefill_ids, acct)
                    pre = []
                acct["flushes"] += 1
                acct["rebuilds"] += 1
                decoding = [
                    (i, s) for i, s in decoding
                    if self._slots[i] is s and self._rem(s) > 0
                ]
        if decoding:
            why = {"short": short} if short else {}
            with TRACER.phase("engine.decode_dispatch", backlog=backlog,
                              **why) as ph:
                new_pending = self._dispatch_segment(
                    decoding, k, temps, greedy, tokens_dev, vp, ph
                )
            acct["dispatch_ms"] += ph.ms
            if new_pending is not None:
                acct["segments"] += 1
                acct["segment_k"], acct["short"] = k, short

        # ---- harvest: segment N-1's ids (then prefill's first tokens)
        # while segment N runs on device — the overlap window
        if self._pending is not None:
            if new_pending is not None:
                acct["overlapped"] = True
                acct["deferred"] += 1
            else:
                acct["flushes"] += 1  # pipeline drains this tick
            self._harvest_segment(acct)
        if pre:
            self._harvest_prefill(pre, prefill_ids, acct)
        self._pending = new_pending
        return acct

    def _dispatch_segment(self, decoding, k: int, temps, greedy: bool,
                          tokens_dev, params, phase) -> Optional[Dict]:
        """Dispatch one ``k``-step decode segment over ``decoding`` rows:
        the token feed (``tokens_dev``, the device chain, or rebuilt here
        from host tokens when it is None), KV block growth, the mirror
        upload, the jitted call, and the rows' scheduling bookkeeping.
        Returns the pending-segment record `_harvest_segment` consumes, or
        None when the block reserve left no row to run. ``phase`` is the
        caller's ``engine.decode_dispatch`` span; it gets what was run:
        ``k``, ``rows``, ``take`` (sum over rows of min(k, remaining): the
        tokens the segment will deliver) and ``slots`` (rows computed)."""
        import numpy as np
        import jax.numpy as jnp

        if tokens_dev is None:
            tokens = np.zeros((self.max_batch, 1), np.int32)
            for i, s in decoding:
                tokens[i, 0] = s.next_input()
            tokens_dev = jnp.asarray(tokens)
        with self._cv:
            if self._paged:
                # block growth for the segment's k appends; on exhaustion the
                # reserve preempts-and-requeues victims, and rows that still
                # cannot grow sit this dispatch out (their device pos mirror
                # stays put, so the skipped steps never happened for them)
                decoding = self._reserve_decode_locked(decoding, k)
            # the tokens of the segment each row keeps: its steps up to its
            # budget
            takes = [min(k, self._rem(s)) for _, s in decoding]
        if not decoding:
            return None
        # injected device fault mid-flight: raising here exercises the
        # _loop recovery contract (fail in-flight slots, rebuild the
        # donated cache, reset the pipeline, keep serving)
        chaos.check("serving.dispatch")
        fp = temps.tobytes()
        if self._temps_cache is None or self._temps_cache[0] != fp:
            self._temps_cache = (fp, jnp.asarray(temps))
        if self._paged:
            self._upload_mirrors()
        # the reserve above has grown every scheduled row to pos + k; rows
        # it left out run too, and nobody reads what they compute
        live_to = None
        if self._paged:
            live_to = min(
                max(int(self._pos_host[i]) for i, _ in decoding) + k,
                self.max_seq,
            )
        t0 = time.perf_counter()  # start of the rows' engine.decode_segment
        toks, last, self._key = self._runner.decode_segment(
            k, greedy, params, tokens_dev, self._temps_cache[1], self._key,
            live_to=live_to, rows=[i for i, _ in decoding], takes=takes,
        )
        self._chain = (
            self._prefill_gen, tuple(i for i, _ in decoding), last
        )
        sched = []
        attrs = {}
        with self._cv:
            if self._paged:
                # keys the scheduled rows hold as the segment starts
                held = [int(self._pos_host[i]) for i, _ in decoding]
                attrs["keys"] = sum(held)
                attrs["span"] = span = self._runner.span_for(live_to)
                read = self._runner.keys_read(held, k)
                if read is None:  # the gathered view: every row, at the span
                    read = span * k * self.max_batch
                else:  # the kernel: what the scheduled rows hold, in whole
                    attrs["read"] = read  # compute blocks
                self._count_view_keys_locked(read, k * self.max_batch)
            if self._window:
                # and those of them a window layer's query still sees
                attrs["wkeys"] = sum(
                    min(int(self._pos_host[i]), self._window)
                    for i, _ in decoding)
            for (i, s), take in zip(decoding, takes):
                s.pending += take
                s.fed += take
                sched.append((i, s, take))
                if self._paged:
                    # scheduled rows advance k steps on device; rows
                    # NOT scheduled keep their mirror (the upload
                    # before the next dispatch rewinds device pos)
                    self._advance_pos_locked(i, int(self._pos_host[i]) + k)
            self._pipe["inflight"] = 1
            self._segment_seq += 1
            self._steps_dispatched += k
        # `seq` joins this span to the harvest span of the same segment
        phase.set(k=k, rows=len(sched), slots=self.max_batch,
                  take=sum(takes), seq=self._segment_seq, **attrs)
        return {"toks": toks, "sched": sched, "k": k, "t0": t0,
                "seq": self._segment_seq,
                "counters": self._runner.segment_counters}


def make_handler(engine: LlamaEngine, model_name: str):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # quiet
            log.debug(fmt, *args)

        def _json(self, code: int, payload: dict,
                  headers: Optional[Dict[str, str]] = None) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            path, _, qs = self.path.partition("?")
            if path == "/healthz":
                self._json(200, {"status": "ok"})
            elif path == "/v1/stats":
                self._json(200, engine.stats())
            elif path == "/v1/trace":
                # flight-recorder pull: this replica's retained spans,
                # optionally filtered to one trace (the router's
                # _flight_record and scripts/tracemerge.py read this)
                q = urllib.parse.parse_qs(qs)
                tid = (q.get("trace_id") or [""])[0]
                limit = int((q.get("limit") or ["0"])[0] or 0)
                spans = TRACER.trace_spans(tid) if tid else TRACER.spans()
                if limit > 0:
                    spans = spans[-limit:]
                self._json(200, {
                    "enabled": TRACER.enabled,
                    "spans": [span_to_dict(s) for s in spans],
                })
            elif path == "/metrics":
                body = engine.metrics.registry.render().encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif path == "/v1/models":
                self._json(200, {
                    "models": [{
                        "name": model_name,
                        "max_seq": engine.max_seq,
                        "params": engine.cfg.num_params(),
                        "versions": engine.versions(),
                    }]
                })
            else:
                self._json(404, {"error": "not found"})

        def _shed(self, e: EngineOverloaded) -> None:
            self._json(
                503, {"error": str(e), "shed": True, "reason": e.reason},
                headers={"Retry-After": str(int(e.retry_after_s + 0.999))},
            )

        def _read_json(self) -> dict:
            length = int(self.headers.get("Content-Length", "0"))
            return json.loads(self.rfile.read(length) or b"{}")

        def do_POST(self):
            if self.path == "/v1/cancel":
                # hedge-loser cancellation (router): vacate the request's
                # queue entry / batch row so the loser never holds a slot
                try:
                    req = self._read_json()
                    ok = engine.cancel(str(req.get("request_id", "")))
                    self._json(200, {"cancelled": ok})
                except Exception as e:
                    self._json(400, {"error": str(e)})
                return
            if self.path == "/admin/drain":
                # stop admission, finish in-flight; the router/controller
                # polls /v1/stats "draining" + active_slots to know when
                # deleting the pod severs nothing
                engine.drain()
                self._json(200, {"draining": True})
                return
            if self.path == "/admin/load_version":
                # weight hot-swap: build v(N+1) off to the side, commit
                # only a complete tree; a failed load leaves the serving
                # versions untouched (never a torn state)
                try:
                    req = self._read_json()
                    engine.load_version(
                        str(req.get("version", "")),
                        str(req.get("ckpt_dir", "")),
                    )
                    self._json(200, engine.versions())
                except ValueError as e:
                    self._json(400, {"error": str(e), "load_failed": True})
                except Exception as e:
                    self._json(500, {"error": str(e), "load_failed": True})
                return
            if self.path == "/admin/activate_version":
                try:
                    req = self._read_json()
                    prev = engine.activate_version(
                        str(req.get("version", ""))
                    )
                    out = engine.versions()
                    out["previous"] = prev
                    self._json(200, out)
                except Exception as e:
                    self._json(400, {"error": str(e)})
                return
            if self.path == "/admin/retire_version":
                try:
                    req = self._read_json()
                    known = engine.retire_version(
                        str(req.get("version", ""))
                    )
                    out = engine.versions()
                    out["retired"] = known
                    self._json(200, out)
                except Exception as e:
                    self._json(400, {"error": str(e)})
                return
            if self.path == "/v1/prefill":
                # prefill-pool leg of a disaggregated request: runs the
                # whole-prompt prefill + first-token sample and answers
                # with the serialized KVHandoff (octet-stream)
                from kubedl_tpu.serving.disagg import HandoffError

                try:
                    req = self._read_json()
                    timeout_s = 600.0
                    deadline_hdr = self.headers.get("X-Deadline-Ms")
                    if deadline_hdr is not None:
                        timeout_s = float(deadline_hdr) / 1000.0
                        if timeout_s <= 0:
                            self._json(504, {"error": "deadline exceeded"})
                            return
                    h = engine.prefill_handoff(
                        req.get("prompt_ids", []),
                        int(req.get("max_tokens", 16)),
                        float(req.get("temperature", 0.0)),
                        timeout_s=timeout_s,
                        cache_prefix=bool(req.get("cache_prefix", False)),
                        request_id=str(req.get("request_id", "")),
                        trace=parse_trace_header(
                            self.headers.get(TRACE_HEADER)
                        ),
                        model_version=str(req.get("model_version", "")),
                    )
                    body = h.to_bytes()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "application/octet-stream"
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                except EngineOverloaded as e:
                    self._shed(e)
                except HandoffError as e:
                    self._json(
                        502, {"error": str(e), "handoff_failed": True}
                    )
                except Exception as e:
                    self._json(400, {"error": str(e)})
                return
            if self.path == "/v1/adopt":
                # decode-pool leg: body is the serialized KVHandoff; the
                # response is a standard generate() result
                from kubedl_tpu.serving.disagg import KVHandoff

                try:
                    length = int(self.headers.get("Content-Length", "0"))
                    h = KVHandoff.from_bytes(self.rfile.read(length))
                    timeout_s = 600.0
                    deadline_hdr = self.headers.get("X-Deadline-Ms")
                    if deadline_hdr is not None:
                        timeout_s = float(deadline_hdr) / 1000.0
                        if timeout_s <= 0:
                            self._json(504, {"error": "deadline exceeded"})
                            return
                    result = engine.adopt_handoff(
                        h, timeout_s=timeout_s,
                        trace=parse_trace_header(
                            self.headers.get(TRACE_HEADER)
                        ),
                    )
                    if result.get("handoff_failed"):
                        self._json(502, result)
                        return
                    if result.get("timed_out") and deadline_hdr is not None:
                        self._json(504, {"error": "deadline exceeded"})
                        return
                    self._json(200, result)
                except EngineOverloaded as e:
                    self._shed(e)
                except Exception as e:
                    self._json(400, {"error": str(e)})
                return
            if self.path != "/v1/generate":
                self._json(404, {"error": "not found"})
                return
            try:
                req = self._read_json()
                # end-to-end deadline propagation: the router forwards the
                # client's REMAINING budget in X-Deadline-Ms; an already-
                # expired budget is a 504 without touching the engine
                timeout_s = 600.0
                deadline_hdr = self.headers.get("X-Deadline-Ms")
                if deadline_hdr is not None:
                    timeout_s = float(deadline_hdr) / 1000.0
                    if timeout_s <= 0:
                        self._json(504, {"error": "deadline exceeded"})
                        return
                dbg = req.get("debug")
                result = engine.generate(
                    req.get("prompt_ids", []),
                    int(req.get("max_tokens", 16)),
                    float(req.get("temperature", 0.0)),
                    timeout_s=timeout_s,
                    cache_prefix=bool(req.get("cache_prefix", False)),
                    request_id=str(req.get("request_id", "")),
                    trace=parse_trace_header(
                        self.headers.get(TRACE_HEADER)
                    ),
                    debug_trace=bool(
                        isinstance(dbg, dict) and dbg.get("trace")
                    ),
                    model_version=str(req.get("model_version", "")),
                )
                if result.get("timed_out") and deadline_hdr is not None:
                    self._json(504, {"error": "deadline exceeded"})
                    return
                self._json(200, result)
            except UnknownModelVersion as e:
                self._json(400, {"error": str(e), "unknown_version": True})
            except EngineOverloaded as e:
                self._shed(e)
            except Exception as e:  # serving must not die on a bad request
                self._json(400, {"error": str(e)})

    return Handler


def engine_kwargs(cfg: Dict, ckpt_dir: str) -> Dict:
    """How KUBEDL_SERVE_CONFIG maps onto the engine (kept separate so the
    config->engine plumbing is testable without binding a server)."""
    return {
        "preset": cfg.get(
            "preset", os.environ.get("KUBEDL_SERVE_PRESET", "tiny")
        ),
        "ckpt_dir": ckpt_dir,
        "max_batch": int(cfg.get("max_batch", 4)),
        "quantize": cfg.get(
            "quantize", os.environ.get("KUBEDL_SERVE_QUANTIZE", "")
        ),
        "mesh_axes": cfg.get("mesh") or None,
        "max_queue_depth": int(cfg.get("max_queue_depth", 64)),
        "max_queue_age_s": float(cfg.get("max_queue_age_s", 30.0)),
        "prefix_cache_mb": float(cfg.get("prefix_cache_mb", 64.0)),
        "kv_layout": cfg.get(
            "kv_layout", os.environ.get("KUBEDL_SERVE_KV_LAYOUT", "paged")
        ),
        "kv_block_size": int(cfg.get("kv_block_size", 16)),
        "kv_blocks": int(cfg.get("kv_blocks", 0)),
        "spec_k": int(
            cfg.get("spec_k", os.environ.get("KUBEDL_SERVE_SPEC_K", "0"))
        ),
        "spec_draft": cfg.get(
            "spec_draft", os.environ.get("KUBEDL_SERVE_SPEC_DRAFT", "ngram")
        ),
        "kv_attention": cfg.get(
            "kv_attention",
            os.environ.get("KUBEDL_SERVE_KV_ATTENTION", "gather"),
        ),
        "spec_candidates": int(
            cfg.get(
                "spec_candidates",
                os.environ.get("KUBEDL_SERVE_SPEC_CANDIDATES", "1"),
            )
        ),
        "spec_draft_layers": int(cfg.get("spec_draft_layers", 0)),
        "spec_tree": bool(
            cfg.get(
                "spec_tree",
                os.environ.get("KUBEDL_SERVE_SPEC_TREE", "") == "1",
            )
        ),
        "prefill_chunk_tokens": int(
            cfg.get(
                "prefill_chunk_tokens",
                os.environ.get("KUBEDL_SERVE_PREFILL_CHUNK", "0"),
            )
        ),
        "role": cfg.get(
            "role", os.environ.get("KUBEDL_SERVE_ROLE", "colocated")
        ),
        "advertise_prefix_len": int(cfg.get("advertise_prefix_len", 8)),
        "model_version": cfg.get(
            "model_version",
            os.environ.get("KUBEDL_SERVE_MODEL_VERSION", "base"),
        ),
    }


def serve_main(env: Optional[Dict[str, str]] = None) -> int:
    """Container entrypoint (ThreadRuntime-compatible)."""
    from kubedl_tpu.utils.envguard import apply_env

    # changed-vars only: unconditional environ writes race native getenv
    # from XLA threads on gang restart (utils/envguard.py, rule KTL003)
    apply_env(env)
    from kubedl_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    cfg = json.loads(os.environ.get("KUBEDL_SERVE_CONFIG", "{}"))
    ckpt = os.environ.get("KUBEDL_MODEL_PATH", "")
    if ckpt:
        from kubedl_tpu.remote.client import is_remote_root

        if is_remote_root(ckpt):
            # remote artifact: mirror the blob prefix locally, serve that
            # (predictors may run on any host — VERDICT r2 missing #6)
            import hashlib
            import tempfile

            cache = os.path.join(
                tempfile.gettempdir(),
                f"kubedl-serve-cache-{os.getuid()}",
                hashlib.sha256(ckpt.encode()).hexdigest()[:16],
            )
            os.makedirs(cache, exist_ok=True)
            from kubedl_tpu.remote.client import download_tree

            n = download_tree(ckpt, cache)
            log.info("fetched %d blobs from %s", n, ckpt)
            ckpt = cache
    port = int(cfg.get("port", 8080))
    # bind address: loopback by default (process pods), configurable for
    # cross-host deployments (round-2 weak #6: a hard-coded 127.0.0.1
    # contradicted the k8s deployment story)
    host = cfg.get("host") or os.environ.get("KUBEDL_SERVE_HOST", "127.0.0.1")
    if cfg.get("chaos"):
        # seeded fault schedule for THIS replica (chaos drills against
        # subprocess fleets can't share an in-process context manager);
        # same seed -> same fault trace, like every armed plan
        plan = chaos.plan_from_config(cfg["chaos"])
        chaos.arm(plan)
        log.info("armed chaos plan seed=%d sites=%s", plan.seed,
                 sorted(cfg["chaos"].get("sites") or {}))
    kwargs = engine_kwargs(cfg, ckpt)
    engine = LlamaEngine(**kwargs)
    model_name = cfg.get("model_name", kwargs["preset"])
    server = ThreadingHTTPServer(
        (host, port), make_handler(engine, model_name)
    )
    log.info("serving %s on :%d", model_name, port)

    drain_grace = float(cfg.get("drain_grace_s", 10.0))

    def graceful_stop() -> None:
        # graceful drain: stop admission (distinguishable 503), let every
        # queued/in-flight decode finish (bounded by drain_grace_s), THEN
        # stop serving — a SIGTERM from a canary shift or scale-down never
        # severs an in-flight stream
        engine.drain()
        engine.wait_drained(drain_grace)
        server.shutdown()

    try:
        import signal

        signal.signal(
            signal.SIGTERM,
            lambda *_: threading.Thread(
                target=graceful_stop, daemon=True
            ).start(),
        )
    except (ValueError, OSError):
        pass  # not the main thread (ThreadRuntime): cancel event below

    cancel = (env or {}).get("_KUBEDL_CANCEL")
    if cancel is not None:
        def watch():
            cancel.wait()
            graceful_stop()

        threading.Thread(target=watch, daemon=True).start()
    try:
        server.serve_forever(poll_interval=0.2)
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
        engine.close()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(serve_main())
