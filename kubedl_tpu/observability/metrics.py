"""Prometheus-style metrics, dependency-free.

Reference: pkg/metrics/job_metrics.go:32-194 + status_counter.go:22-81 —
counters kubedl_jobs_{created,deleted,successful,failed,restarted}{kind},
live running/pending gauges, and first/all-pods launch-delay histograms;
exposed on :8443/metrics (monitor.go:27-36). Same metric family names here
(prefix `kubedl_tpu_`), exported in Prometheus text format by
:meth:`MetricsRegistry.render` (served by the console API).
"""

from __future__ import annotations

import threading
import time
from bisect import bisect_left
from typing import Callable, Dict, List, Optional, Tuple

LabelKV = Tuple[Tuple[str, str], ...]


def _labels(labels: Dict[str, str]) -> LabelKV:
    return tuple(sorted(labels.items()))


def _fmt_labels(kv: LabelKV) -> str:
    if not kv:
        return ""
    return "{" + ",".join(f'{k}="{v}"' for k, v in kv) + "}"


class Counter:
    def __init__(self, name: str, help_: str) -> None:
        self.name, self.help = name, help_
        self._lock = threading.Lock()
        self._values: Dict[LabelKV, float] = {}

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        kv = _labels(labels)
        with self._lock:
            self._values[kv] = self._values.get(kv, 0.0) + amount

    def value(self, **labels: str) -> float:
        with self._lock:
            return self._values.get(_labels(labels), 0.0)

    def snapshot(self) -> List[dict]:
        with self._lock:
            return [
                {"labels": dict(kv), "value": v}
                for kv, v in sorted(self._values.items())
            ]

    def render(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} counter"]
        with self._lock:
            for kv, v in sorted(self._values.items()):
                out.append(f"{self.name}{_fmt_labels(kv)} {v}")
        return out


class Gauge:
    """A gauge whose value may be a live callback (the reference's
    running/pending gauges list-and-count on scrape, status_counter.go)."""

    def __init__(self, name: str, help_: str) -> None:
        self.name, self.help = name, help_
        self._lock = threading.Lock()
        self._values: Dict[LabelKV, float] = {}
        self._callbacks: Dict[LabelKV, Callable[[], float]] = {}

    def set(self, value: float, **labels: str) -> None:
        with self._lock:
            self._values[_labels(labels)] = value

    def set_function(self, fn: Callable[[], float], **labels: str) -> None:
        with self._lock:
            self._callbacks[_labels(labels)] = fn

    def value(self, **labels: str) -> float:
        kv = _labels(labels)
        with self._lock:
            if kv in self._callbacks:
                return self._callbacks[kv]()
            return self._values.get(kv, 0.0)

    def snapshot(self) -> List[dict]:
        with self._lock:
            items = dict(self._values)
            callbacks = dict(self._callbacks)
        for kv, fn in callbacks.items():
            try:
                items[kv] = fn()
            except Exception:
                continue
        return [
            {"labels": dict(kv), "value": v} for kv, v in sorted(items.items())
        ]

    def render(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} gauge"]
        with self._lock:
            items = dict(self._values)
            for kv, fn in self._callbacks.items():
                try:
                    items[kv] = fn()
                except Exception:
                    continue
        for kv, v in sorted(items.items()):
            out.append(f"{self.name}{_fmt_labels(kv)} {v}")
        return out


_DEFAULT_BUCKETS = (0.01, 0.05, 0.1, 0.5, 1, 2.5, 5, 10, 30, 60, 120, 300, 600)


class Histogram:
    def __init__(
        self, name: str, help_: str, buckets: Tuple[float, ...] = _DEFAULT_BUCKETS
    ) -> None:
        self.name, self.help = name, help_
        self.buckets = tuple(sorted(buckets))
        self._lock = threading.Lock()
        self._counts: Dict[LabelKV, List[int]] = {}
        self._sum: Dict[LabelKV, float] = {}
        self._total: Dict[LabelKV, int] = {}
        #: label-set -> (le-or-"+Inf", trace_id, value, wall ts) — the LAST
        #: exemplar observed, attached to the bucket its value fell into
        #: (OpenMetrics-style: a burning latency histogram links straight
        #: to an offending trace retrievable via /v1/trace)
        self._exemplars: Dict[LabelKV, Tuple[str, str, float, float]] = {}

    def observe(
        self, value: float, exemplar: Optional[str] = None, **labels: str
    ) -> None:
        kv = _labels(labels)
        with self._lock:
            counts = self._counts.setdefault(kv, [0] * len(self.buckets))
            i = bisect_left(self.buckets, value)  # first bucket with value <= le
            if i < len(self.buckets):
                counts[i] += 1
            self._sum[kv] = self._sum.get(kv, 0.0) + value
            self._total[kv] = self._total.get(kv, 0) + 1
            if exemplar:
                le = repr(self.buckets[i]) if i < len(self.buckets) else "+Inf"
                self._exemplars[kv] = (le, str(exemplar), value, time.time())

    def summary(self, **labels: str) -> Tuple[int, float]:
        kv = _labels(labels)
        with self._lock:
            return self._total.get(kv, 0), self._sum.get(kv, 0.0)

    def snapshot(self) -> List[dict]:
        """Structured view for dashboards: per label-set bucket counts
        (non-cumulative), sum and total."""
        with self._lock:
            return [
                {
                    "labels": dict(kv),
                    "buckets": list(self.buckets),
                    "counts": list(counts),
                    "sum": self._sum.get(kv, 0.0),
                    "total": self._total.get(kv, 0),
                }
                for kv, counts in sorted(self._counts.items())
            ]

    def render(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        with self._lock:
            for kv, counts in sorted(self._counts.items()):
                ex = self._exemplars.get(kv)
                cum = 0
                for b, c in zip(self.buckets, counts):
                    cum += c
                    lbl = dict(kv)
                    lbl["le"] = repr(b)
                    line = f"{self.name}_bucket{_fmt_labels(_labels(lbl))} {cum}"
                    if ex is not None and ex[0] == repr(b):
                        line += (f' # {{trace_id="{ex[1]}"}} {ex[2]} '
                                 f"{ex[3]:.3f}")
                    out.append(line)
                lbl = dict(kv)
                lbl["le"] = "+Inf"
                line = (
                    f"{self.name}_bucket{_fmt_labels(_labels(lbl))} {self._total[kv]}"
                )
                if ex is not None and ex[0] == "+Inf":
                    line += f' # {{trace_id="{ex[1]}"}} {ex[2]} {ex[3]:.3f}'
                out.append(line)
                out.append(f"{self.name}_sum{_fmt_labels(kv)} {self._sum[kv]}")
                out.append(f"{self.name}_count{_fmt_labels(kv)} {self._total[kv]}")
        return out


class MetricsRegistry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: List[object] = []

    def counter(self, name: str, help_: str) -> Counter:
        m = Counter(name, help_)
        with self._lock:
            self._metrics.append(m)
        return m

    def gauge(self, name: str, help_: str) -> Gauge:
        m = Gauge(name, help_)
        with self._lock:
            self._metrics.append(m)
        return m

    def histogram(self, name: str, help_: str, **kw) -> Histogram:
        m = Histogram(name, help_, **kw)
        with self._lock:
            self._metrics.append(m)
        return m

    def render(self) -> str:
        lines: List[str] = []
        with self._lock:
            metrics = list(self._metrics)
        for m in metrics:
            lines.extend(m.render())  # type: ignore[attr-defined]
        return "\n".join(lines) + "\n"


class JobMetrics:
    """The job-controller metric family (reference:
    pkg/metrics/job_metrics.go:64-117)."""

    def __init__(self, registry: Optional[MetricsRegistry] = None) -> None:
        self.registry = registry or MetricsRegistry()
        r = self.registry
        self.created = r.counter("kubedl_tpu_jobs_created", "Jobs created")
        self.deleted = r.counter("kubedl_tpu_jobs_deleted", "Jobs deleted")
        self.successful = r.counter("kubedl_tpu_jobs_successful", "Jobs succeeded")
        self.failed = r.counter("kubedl_tpu_jobs_failed", "Jobs failed")
        self.restarted = r.counter("kubedl_tpu_jobs_restarted", "Job gang restarts")
        self.running = r.gauge("kubedl_tpu_jobs_running", "Jobs currently running")
        self.pending = r.gauge("kubedl_tpu_jobs_pending", "Jobs currently pending")
        self.first_pod_launch_delay = r.histogram(
            "kubedl_tpu_jobs_first_pod_launch_delay_seconds",
            "Job created -> first pod running",
        )
        self.all_pods_launch_delay = r.histogram(
            "kubedl_tpu_jobs_all_pods_launch_delay_seconds",
            "Job created -> all pods running",
        )
        # TPU north-star additions (BASELINE.md):
        self.first_step_delay = r.histogram(
            "kubedl_tpu_jobs_first_step_delay_seconds",
            "Job created -> first training step reported",
        )
        self.tokens_per_sec_per_chip = r.gauge(
            "kubedl_tpu_tokens_per_sec_per_chip", "Training throughput per chip"
        )
        self.quarantined = r.counter(
            "kubedl_tpu_jobs_quarantined",
            "Jobs parked with a Quarantined condition after their reconcile "
            "retry budget (poison-pill protection for the workqueue)",
        )
        # Elastic slice scaling (kubedl_tpu/elastic/):
        self.resizes = r.counter(
            "kubedl_tpu_jobs_resized",
            "In-place elastic gang resizes (grow or shrink) executed by "
            "the engine; coarse tear-down resizes count as restarts",
        )
        # Auto-parallelism planner (kubedl_tpu/planner/, docs/planning.md):
        self.plans = r.counter(
            "kubedl_tpu_planner_plans_total",
            "Mesh plans computed (first admission + every elastic re-plan)",
        )
        self.planner_candidates = r.counter(
            "kubedl_tpu_planner_candidates_evaluated",
            "Candidate layouts priced by the planner's cost model",
        )
        self.planner_plan_ms = r.histogram(
            "kubedl_tpu_planner_plan_ms",
            "Host wall time per plan() call, milliseconds",
            buckets=(1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, float("inf")),
        )
        self.preemption_notices = r.counter(
            "kubedl_tpu_preemption_notices",
            "Node preemption/maintenance notices that marked a slice "
            "draining",
        )
        self.slices_draining = r.gauge(
            "kubedl_tpu_slices_draining",
            "Slices currently draining under a preemption notice",
        )
        self.goodput = r.gauge(
            "kubedl_tpu_training_goodput",
            "Step-time-weighted fraction of wall clock spent training "
            "over the last measured window (1 - overhead of checkpoints, "
            "restarts and resizes)",
        )
        # Crash recovery (core/wal.py + docs/robustness.md "Crash recovery"):
        self.recovery_duration = r.gauge(
            "kubedl_tpu_recovery_duration_seconds",
            "Time the last cold start spent rehydrating the store "
            "(snapshot+WAL replay) plus re-adopting gangs and pods",
        )
        self.replayed_records = r.counter(
            "kubedl_tpu_wal_replayed_records",
            "WAL records replayed into the store at the last cold start",
        )
        self.adopted_pods = r.counter(
            "kubedl_tpu_pods_adopted",
            "Running pods re-attached by the kubelet after an operator "
            "restart instead of being re-created",
        )
        self.wal_appends = r.gauge(
            "kubedl_tpu_wal_appends",
            "Records appended to the write-ahead log by this incarnation",
        )
        self.wal_fsyncs = r.gauge(
            "kubedl_tpu_wal_fsyncs",
            "fsync calls issued by the write-ahead log",
        )
        self.wal_batch_size = r.histogram(
            "kubedl_tpu_wal_batch_size",
            "Records covered by each group-commit fsync (fsync='group'): "
            "batch size 1 means no writers overlapped the window, the "
            "right tail is the amortization collapsing fsyncs-per-append",
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
                     float("inf")),
        )
        self.coalesced_reconciles = r.gauge(
            "kubedl_tpu_coalesced_reconciles",
            "Watch events absorbed by workqueue burst coalescing, by "
            "controller — reconcile passes the control plane did not run",
        )
        self.watch_gaps = r.gauge(
            "kubedl_tpu_store_watch_gaps",
            "Watchers registered with a since_revision older than "
            "replayable history (missed DELETED events)",
        )
        # Sharded control plane (kubedl_tpu/shards/, docs/architecture.md
        # "Sharded control plane"): per-reconcile-domain visibility. The
        # WAL gauges above also carry per-shard series (shard=<i>) next to
        # their unlabeled process totals.
        self.reconciles = r.counter(
            "kubedl_tpu_reconcile_total",
            "Reconciles executed, by controller and reconcile-domain shard",
        )
        self.reconcile_latency = r.histogram(
            "kubedl_tpu_reconcile_latency_seconds",
            "Workqueue wait + reconcile duration, by controller and shard",
            buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                     5.0, float("inf")),
        )
        self.workqueue_depth = r.gauge(
            "kubedl_tpu_workqueue_depth",
            "Items pending in each controller's per-shard workqueue",
        )
        self.shards_owned = r.gauge(
            "kubedl_tpu_shards_owned",
            "Reconcile-domain shards this operator currently owns (equals "
            "the shard count unless a standby or deposed owner)",
        )
        # Multi-operator federation (kubedl_tpu/federation/,
        # docs/architecture.md "Multi-operator federation"): one series
        # per member process; the operator wires these as set_function
        # gauges over the FederationMember counters.
        self.federation_heartbeats = r.gauge(
            "kubedl_tpu_federation_heartbeats",
            "Successful lease-root heartbeat round trips (probe write + "
            "fsync + readback) by this federation member",
        )
        self.federation_heartbeat_misses = r.gauge(
            "kubedl_tpu_federation_heartbeat_misses",
            "Failed or chaos-skipped federation heartbeats — the "
            "partition-detector input that drives demotion",
        )
        self.federation_demotions = r.gauge(
            "kubedl_tpu_federation_demotions",
            "Times this member demoted itself to read-only after losing "
            "the lease root for longer than the demotion deadline",
        )
        self.federation_read_only = r.gauge(
            "kubedl_tpu_federation_read_only",
            "1 while this member is demoted to read-only (serving tails, "
            "rejecting actuations), 0 while it may own shards",
        )
        self.expectations_expired = r.counter(
            "kubedl_tpu_expectations_expired",
            "Reconciles that proceeded past timed-out controller "
            "expectations (the dead-incarnation / lost-watch-event signal)",
        )
        # Progress watchdog (kubedl_tpu/watchdog/, docs/robustness.md
        # "Hang detection"): restarts it triggered, labeled by the failure
        # class it classified — reason="hang" (beacons fresh, step frozen)
        # or reason="silent_death" (beacons stopped, pod still RUNNING)
        self.watchdog_restarts = r.counter(
            "kubedl_tpu_watchdog_restarts",
            "Gang restarts triggered by the progress watchdog, by reason",
        )
        self.watchdog_stragglers = r.gauge(
            "kubedl_tpu_watchdog_stragglers",
            "Replicas CURRENTLY flagged as stragglers (step rate far "
            "below the gang median); observational — no restart is "
            "triggered, but PS-mode decay-weighting reads this signal "
            "(a StragglerDetected job event fires once per track)",
        )
        self.watchdog_tracked = r.gauge(
            "kubedl_tpu_watchdog_tracked_replicas",
            "Replicas currently tracked by the progress watchdog "
            "(a replica opts in by emitting its first beacon)",
        )


class PSMetrics:
    """The parameter-service metric family (kubedl_tpu/ps/,
    docs/elasticity.md "Parameter-service mode"): asynchronous push/pull
    aggregation accounting — push outcomes by staleness handling, member
    churn, and shard failovers."""

    def __init__(self, registry: Optional[MetricsRegistry] = None) -> None:
        self.registry = registry or MetricsRegistry()
        r = self.registry
        self.ps_pushes = r.counter(
            "kubedl_tpu_ps_pushes",
            "Worker delta pushes, by outcome: fresh (staleness 0, full "
            "weight), decayed (in-bound staleness, decay-weighted), "
            "rejected (beyond max_staleness — the worker must re-pull)",
        )
        self.ps_pulls = r.counter(
            "kubedl_tpu_ps_pulls",
            "Shard snapshot pulls served (registration warm-starts "
            "included)",
        )
        self.ps_members = r.gauge(
            "kubedl_tpu_ps_members",
            "Workers currently registered in the aggregation group",
        )
        self.ps_shard_failovers = r.counter(
            "kubedl_tpu_ps_shard_failovers",
            "Shard ownership transfers (lease re-acquired with a bumped "
            "fencing token, state replayed from the shard WAL)",
        )
        self.ps_evictions = r.counter(
            "kubedl_tpu_ps_evictions",
            "Members removed from the aggregation group, by reason: "
            "preemption (notice — in-flight contribution committed), "
            "silent_death (watchdog — in-flight contribution discarded), "
            "departed (clean deregister)",
        )
        self.ps_push_staleness = r.histogram(
            "kubedl_tpu_ps_push_staleness_steps",
            "Aggregate-steps of staleness per accepted push (shard head "
            "version minus the worker's pulled version)",
            buckets=(0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0),
        )


#: ms-scale buckets for the decode pipeline's per-tick timings (the
#: default seconds-scale buckets would dump every tick into the first one)
_TICK_MS_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
                    100.0, 250.0, 500.0)

#: TTFT spans queue wait + prefill + one harvest — ms to seconds scale
_TTFT_MS_BUCKETS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
                    1000.0, 2500.0, 5000.0, 10000.0, 30000.0)


class ServingMetrics:
    """The serving-engine metric family: decode-pipeline accounting
    (dispatch/harvest/host per-tick timings, segment + deferred-harvest
    counters, overlap ratio) plus queue depth — what `/metrics` on a
    predictor pod exports and what `LlamaEngine.stats()` summarizes."""

    def __init__(self, registry: Optional[MetricsRegistry] = None) -> None:
        self.registry = registry or MetricsRegistry()
        r = self.registry
        self.segments = r.counter(
            "kubedl_tpu_serving_segments", "Decode segments dispatched"
        )
        self.segment_lengths = r.counter(
            "kubedl_tpu_serving_segment_lengths",
            "Decode segments dispatched, by length (k: 32, 4 or 1 steps) "
            "and by whether the prefill work the tick still owed made "
            "the segment shorter than the rows' budgets asked for "
            "(short: waiting = a request had no row, prefill = a row's "
            "prompt was not wholly fed, no = neither)",
        )
        self.deferred_harvests = r.counter(
            "kubedl_tpu_serving_deferred_harvests",
            "Segment harvests that overlapped the next in-flight segment",
        )
        self.pipeline_flushes = r.counter(
            "kubedl_tpu_serving_pipeline_flushes",
            "Segment harvests with nothing left in flight (pipeline drains)",
        )
        self.chain_rebuilds = r.counter(
            "kubedl_tpu_serving_chain_rebuilds",
            "Device token chain rebuilt from host tokens",
        )
        self.scheduler_errors = r.counter(
            "kubedl_tpu_serving_scheduler_errors",
            "Scheduler ticks that failed and were recovered",
        )
        self.dispatch_ms = r.histogram(
            "kubedl_tpu_serving_dispatch_ms",
            "Per-tick host time of the prefill/segment/verify dispatch phases: "
            "array build, mirror upload, KV reserve, the jitted calls and "
            "their scheduling bookkeeping (ms)",
            buckets=_TICK_MS_BUCKETS,
        )
        self.harvest_ms = r.histogram(
            "kubedl_tpu_serving_harvest_ms",
            "Per-tick time blocked in device_get for sampled ids (ms)",
            buckets=_TICK_MS_BUCKETS,
        )
        self.host_ms = r.histogram(
            "kubedl_tpu_serving_host_ms",
            "Per-tick host bookkeeping time (slots/finalize/admission, ms)",
            buckets=_TICK_MS_BUCKETS,
        )
        self.overlap_ratio = r.gauge(
            "kubedl_tpu_serving_overlap_ratio",
            "Fraction of scheduler wall time overlapped with device compute",
        )
        self.queue_depth = r.gauge(
            "kubedl_tpu_serving_queue_depth", "Requests waiting for a slot"
        )
        self.shed_requests = r.counter(
            "kubedl_tpu_serving_shed_requests",
            "Requests rejected 503 by the queue-depth/age load-shedding "
            "budget (the autoscaler treats shed load as backlog)",
        )
        # prefix KV cache family (kubedl_tpu/serving/prefix_cache.py):
        # suffix-only prefill for shared-prompt traffic
        self.prefix_hits = r.counter(
            "kubedl_tpu_serving_prefix_cache_hits",
            "Admissions whose prompt matched a cached prefix (grafted KV)",
        )
        self.prefix_misses = r.counter(
            "kubedl_tpu_serving_prefix_cache_misses",
            "Admissions with no usable cached prefix",
        )
        self.prefix_inserts = r.counter(
            "kubedl_tpu_serving_prefix_cache_inserts",
            "Prefix entries stored after prefill (shared >= min_seen "
            "times, or request-tagged cacheable)",
        )
        self.prefix_evictions = r.counter(
            "kubedl_tpu_serving_prefix_cache_evictions",
            "Prefix entries LRU-evicted to stay under the byte budget",
        )
        self.prefix_tokens_saved = r.counter(
            "kubedl_tpu_serving_prefix_cache_tokens_saved",
            "Prompt tokens NOT prefilled because their KV came from the "
            "prefix cache (counted at suffix-prefill dispatch)",
        )
        self.prefix_bytes = r.gauge(
            "kubedl_tpu_serving_prefix_cache_bytes",
            "Device bytes held by prefix-cache entries (k+v payloads)",
        )
        self.prefix_entries = r.gauge(
            "kubedl_tpu_serving_prefix_cache_entries",
            "Prefix entries currently resident",
        )
        # paged KV family (kubedl_tpu/serving/kv_blocks.py): block-pool
        # occupancy — the autoscaler/router see MEMORY pressure, not
        # just queue depth
        self.kv_blocks_total = r.gauge(
            "kubedl_tpu_serving_kv_blocks_total",
            "Usable KV blocks in the paged pool (excludes the trash block)",
        )
        self.kv_blocks_free = r.gauge(
            "kubedl_tpu_serving_kv_blocks_free",
            "KV blocks on the free list",
        )
        self.kv_blocks_shared = r.gauge(
            "kubedl_tpu_serving_kv_blocks_shared",
            "KV blocks referenced by >= 2 owners (prefix sharing)",
        )
        self.kv_preemptions = r.counter(
            "kubedl_tpu_serving_kv_preemptions",
            "Decoding rows preempted-and-requeued under block exhaustion",
        )
        self.kv_block_sheds = r.counter(
            "kubedl_tpu_serving_kv_block_sheds",
            "Requests rejected 503 because free blocks fell below the "
            "low watermark (hysteresis reopens at the high watermark)",
        )
        # speculative decoding family (kubedl_tpu/serving/speculative.py)
        self.spec_proposed = r.counter(
            "kubedl_tpu_serving_spec_tokens_proposed",
            "Draft tokens proposed to verify forwards",
        )
        self.spec_accepted = r.counter(
            "kubedl_tpu_serving_spec_tokens_accepted",
            "Draft tokens accepted (agreed with the target's greedy argmax)",
        )
        self.spec_acceptance_rate = r.gauge(
            "kubedl_tpu_serving_spec_acceptance_rate",
            "Lifetime accepted/proposed draft-token ratio",
        )
        self.spec_draft_ms = r.histogram(
            "kubedl_tpu_serving_spec_draft_ms",
            "Per-round draft proposal wall time (host ngram lookup or "
            "draft-model forward), ms — labeled by draft kind so model "
            "drafts can be costed against their acceptance gain",
            buckets=_TICK_MS_BUCKETS,
        )
        # disaggregated prefill/decode family (kubedl_tpu/serving/disagg.py):
        # KV-block handoff traffic, labeled direction="export"|"adopt"
        self.handoff_total = r.counter(
            "kubedl_tpu_serving_handoff_total",
            "KV handoffs completed, by direction (export on the prefill "
            "pool, adopt on the decode pool)",
        )
        self.handoff_bytes = r.counter(
            "kubedl_tpu_serving_handoff_bytes",
            "KV payload bytes moved across the prefill->decode handoff "
            "seam, by direction",
        )
        self.handoff_ms = r.histogram(
            "kubedl_tpu_serving_handoff_ms",
            "Per-handoff wall time (export: block gather + device_get + "
            "serialize; adopt: admission + scatter into the local pool), "
            "ms, by direction",
            buckets=_TICK_MS_BUCKETS,
        )
        self.ttft_ms = r.histogram(
            "kubedl_tpu_serving_ttft_ms",
            "Per-request time to first token (admission queue + prefill "
            "+ first sampled id harvested), ms",
            buckets=_TTFT_MS_BUCKETS,
        )
        self.queue_wait_ms = r.histogram(
            "kubedl_tpu_serving_queue_wait_ms",
            "Per-request admission queue wait (enqueue -> batch row "
            "assigned), ms — the TTFT component chunked prefill bounds",
            buckets=_TTFT_MS_BUCKETS,
        )
        self.ttft_part_ms = r.histogram(
            "kubedl_tpu_serving_ttft_part_ms",
            "Per-request time to first token by part, ms: queue (enqueue "
            "-> batch row assigned), backlog (-> its first prefill "
            "program dispatched), chunks (-> its final one dispatched), "
            "first (-> first sampled id harvested); the four sum to "
            "ttft_ms",
            buckets=_TTFT_MS_BUCKETS,
        )
        self.admission_chunks = r.counter(
            "kubedl_tpu_serving_admission_chunks",
            "Prefill chunk dispatches under chunked admission (one "
            "count per row per chunk, so chunks/rows ~= prompt_len / "
            "prefill_chunk_tokens)",
        )
        self.prefill_tokens = r.counter(
            "kubedl_tpu_serving_prefill_tokens",
            "Prompt tokens fed to prefill programs (grafted prefix "
            "tokens are not: they are never computed)",
        )
        self.prefill_positions = r.counter(
            "kubedl_tpu_serving_prefill_positions",
            "Positions prefill programs computed: rows computed x "
            "bucket length, summed over dispatches. prefill_tokens over "
            "this is the share of prefill compute that held a prompt "
            "token (the rest is bucket padding and, on a contiguous "
            "cache, rows with nothing to prefill)",
        )
        self.view_keys = r.counter(
            "kubedl_tpu_serving_view_keys",
            "Keys of the paged pool the decode and suffix-prefill "
            "programs attended over, a layer: span x rows computed, once "
            "a decode step and once a prefill program, over a gathered "
            "view; the keys fetched (scheduled rows' lengths in whole "
            "compute blocks) where a decode step runs the decode kernel",
        )
        self.view_keys_full = r.counter(
            "kubedl_tpu_serving_view_keys_full",
            "The same count had every view spanned max_seq keys. "
            "view_keys over this is the share of the full view that was "
            "gathered and scored (1.0 for an engine with one span)",
        )
        self.state_resets = r.counter(
            "kubedl_tpu_serving_state_resets",
            "Rows whose slab of recurrent state a prefill program zeroed "
            "(the row's tokens began at position 0: an admission, or a "
            "re-admission after preemption). 0 for a model whose rows "
            "are K/V blocks alone; state_rows and state_bytes are in "
            "/v1/stats",
        )
        # what a runner's decode segments count beside their tokens, by the
        # name the runner gives it (serving/model_runner.py
        # `segment_counters`); the table by layer and expert is in /v1/stats
        self.segment_counters = {
            "experts_touched": r.counter(
                "kubedl_tpu_serving_experts_touched",
                "Experts that computed at least one kept token, summed over "
                "decode steps and layers: what a step has to read of its "
                "expert weights. 0 for a model without routed experts",
            ),
            "expert_steps": r.counter(
                "kubedl_tpu_serving_expert_steps",
                "Layers times the decode steps that kept a token: "
                "experts_touched over this is the experts a layer's step "
                "touches",
            ),
            "expert_tiles": r.counter(
                "kubedl_tpu_serving_expert_tiles",
                "Row tiles the expert kernel ran in decode steps: one for "
                "each expert and each tile of the order by expert in which "
                "it has a row. expert_tiles over experts_touched is the "
                "tiles a fetched expert fed. 0 where no kernel runs",
            ),
            "slabs_stepped": r.counter(
                "kubedl_tpu_serving_slabs_stepped",
                "Rows whose slab of recurrent state a decode step fetched, "
                "summed over decode steps: the scheduled rows' where the "
                "step lists them for its kernel (a TPU), every row's where "
                "it sweeps them all. 0 for a model without such state",
            ),
            "slabs_held": r.counter(
                "kubedl_tpu_serving_slabs_held",
                "Rows times decode steps: slabs_stepped over this is the "
                "share of the recurrent state a step moves",
            ),
        }
        # the second kind of K/V block (kv_blocks.WindowTable): the pool of
        # the layers that read only a window of the context
        self.kv_window_blocks_total = r.gauge(
            "kubedl_tpu_serving_kv_window_blocks_total",
            "Usable blocks in the windowed K/V pool (0: one kind of block)",
        )
        self.kv_window_blocks_free = r.gauge(
            "kubedl_tpu_serving_kv_window_blocks_free",
            "Windowed K/V blocks on the free list",
        )
        self.kv_window_blocks_released = r.counter(
            "kubedl_tpu_serving_kv_window_blocks_released",
            "Windowed K/V blocks rows gave back while they ran, because "
            "the blocks had fallen wholly behind the window",
        )
        # controller-side replica health (the probe-failure satellite:
        # a replica that stops answering its stats probe must SURFACE,
        # not silently drop out of the QPS math)
        self.probe_failures = r.counter(
            "kubedl_tpu_serving_probe_failures",
            "Autoscaler stats-probe failures, by predictor pod",
        )
        self.replicas_not_ready = r.gauge(
            "kubedl_tpu_serving_replicas_not_ready",
            "RUNNING predictor pods whose stats probe has failed "
            "consecutively past the NotReady threshold",
        )


class RouterMetrics:
    """The routing-tier metric family (kubedl_tpu/serving/router.py):
    per-replica health (ejections/readmissions/probe failures, labeled by
    replica), the tail-tolerance mechanisms (retries, hedges + wins,
    cancellations, deadline misses), and fleet availability gauges —
    what `/metrics` on the router exports."""

    def __init__(self, registry: Optional[MetricsRegistry] = None) -> None:
        self.registry = registry or MetricsRegistry()
        r = self.registry
        self.requests = r.counter(
            "kubedl_tpu_router_requests", "Requests accepted by the router"
        )
        self.retries = r.counter(
            "kubedl_tpu_router_retries",
            "Failover re-dispatches after a replica error/shed "
            "(budget-gated: never more than ~ratio of offered load)",
        )
        self.hedges = r.counter(
            "kubedl_tpu_router_hedges",
            "Duplicate dispatches fired after the p95-based hedge delay",
        )
        self.hedge_wins = r.counter(
            "kubedl_tpu_router_hedge_wins",
            "Requests whose hedge answered before the primary",
        )
        self.cancellations = r.counter(
            "kubedl_tpu_router_cancellations",
            "Loser attempts cancelled after another attempt won",
        )
        self.ejections = r.counter(
            "kubedl_tpu_router_ejections",
            "Circuit-breaker ejections (K consecutive failures), by replica",
        )
        self.readmissions = r.counter(
            "kubedl_tpu_router_readmissions",
            "Half-open probes that readmitted an ejected replica, by replica",
        )
        self.probe_failures = r.counter(
            "kubedl_tpu_router_probe_failures",
            "Active health-probe failures, by replica",
        )
        self.transport_errors = r.counter(
            "kubedl_tpu_router_transport_errors",
            "Request forwards that failed at the transport, by replica",
        )
        self.upstream_sheds = r.counter(
            "kubedl_tpu_router_upstream_sheds",
            "503 + Retry-After shed responses received from replicas",
        )
        self.deadline_exceeded = r.counter(
            "kubedl_tpu_router_deadline_exceeded",
            "Requests that ran out of deadline budget (504 to the client)",
        )
        self.no_replica = r.counter(
            "kubedl_tpu_router_no_replica",
            "Requests rejected because no replica was routable",
        )
        self.drain_rejects = r.counter(
            "kubedl_tpu_router_drain_rejects",
            "Requests rejected 503 while the router itself drains",
        )
        self.replicas_available = r.gauge(
            "kubedl_tpu_router_replicas_available",
            "Replicas currently routable (breaker closed, not draining)",
        )
        self.replicas_draining = r.gauge(
            "kubedl_tpu_router_replicas_draining",
            "Replicas currently refusing admission to drain",
        )
        self.request_ms = r.histogram(
            "kubedl_tpu_router_request_ms",
            "End-to-end router latency per request (all attempts), ms",
            buckets=_TTFT_MS_BUCKETS,
        )
        # per-tenant QoS family (kubedl_tpu/serving/disagg.py
        # WeightedFairQueue), labeled qos_class="..."
        self.qos_queue_depth = r.gauge(
            "kubedl_tpu_router_qos_queue_depth",
            "Requests waiting in the weighted-fair dispatch queue, "
            "by QoS class",
        )
        self.qos_sheds = r.counter(
            "kubedl_tpu_router_qos_sheds",
            "Requests shed by the QoS arbiter (queue overflow evicts the "
            "lowest class first; queue-deadline expiry counts), by class",
        )
        # disaggregated dispatch family
        self.disagg_requests = r.counter(
            "kubedl_tpu_router_disagg_requests",
            "Requests dispatched as two-leg prefill->adopt flows",
        )
        self.disagg_fallbacks = r.counter(
            "kubedl_tpu_router_disagg_fallbacks",
            "Disagg-eligible requests that fell back to role-blind "
            "colocated dispatch (a leg failed or a pool was empty)",
        )
        # model-version canary family (kubedl_tpu/serving/rollout.py):
        # per-version routing outcomes plus the rollout controller's
        # weight/burn/decision surfaces
        self.version_requests = r.counter(
            "kubedl_tpu_router_version_requests",
            "Requests routed per model version (result=ok|error) — the "
            "canary's request split observed, not configured",
        )
        self.rollout_weight = r.gauge(
            "kubedl_tpu_router_rollout_weight",
            "Configured canary traffic weight per model version (the "
            "router's version WRR input, 0-100)",
        )
        self.version_burning = r.gauge(
            "kubedl_tpu_router_version_burning",
            "1 when a model version's own SLO partition has BOTH burn "
            "windows above threshold, by version+severity, else 0",
        )
        self.rollout_events = r.counter(
            "kubedl_tpu_router_rollout_events",
            "Rollout controller decisions (event=advance|promote|"
            "rollback|fence_cleared)",
        )


class SLOMetrics:
    """The SLO tracker family (kubedl_tpu/observability/slo.py): rolling
    good/bad request counts, multi-window error-budget burn rates (SRE
    burn-rate alerting: page when BOTH the short and long window burn
    above threshold), and the request-latency histogram whose exemplars
    carry the last trace id so a burning SLO links directly to an
    offending trace via /v1/trace."""

    def __init__(self, registry: Optional[MetricsRegistry] = None) -> None:
        self.registry = registry or MetricsRegistry()
        r = self.registry
        self.slo_requests = r.counter(
            "kubedl_tpu_slo_requests",
            "Requests classified against the SLO (result=good|bad: bad is "
            "a non-200 outcome OR latency above the objective)",
        )
        self.slo_burn_rate = r.gauge(
            "kubedl_tpu_slo_error_budget_burn_rate",
            "Error-budget burn rate per rolling window (1.0 = burning "
            "exactly the budget; 14.4 over 5m+1h pages), by window",
        )
        self.slo_burning = r.gauge(
            "kubedl_tpu_slo_burning",
            "1 when BOTH windows of a burn-rate alert pair exceed their "
            "threshold (severity=page|ticket), else 0",
        )
        self.slo_latency_ms = r.histogram(
            "kubedl_tpu_slo_latency_ms",
            "End-to-end request latency classified against the SLO, ms; "
            "buckets carry last-trace-id exemplars",
            buckets=_TTFT_MS_BUCKETS,
        )


#: Process-wide default, mirroring the reference's promauto default registry.
DEFAULT_JOB_METRICS = JobMetrics()

#: Process-wide default for the parameter-service tier (kubedl_tpu/ps/).
DEFAULT_PS_METRICS = PSMetrics()
