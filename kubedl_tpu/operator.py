"""Operator: single-binary assembly of the whole control plane.

Reference: main.go:54-118 — flags -> manager (leader election) -> scheme ->
gang registry -> workload-gated controller setup -> storage backends ->
persist controllers -> metrics endpoint -> start. Same shape here, minus
the parts the self-hosted substrate makes moot (scheme registration,
leader election across replicas).
"""

from __future__ import annotations

import logging
import os
import tempfile
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from kubedl_tpu.api.interface import JobObject, WorkloadController
from kubedl_tpu.core.manager import ControllerManager, owner_mapper
from kubedl_tpu.core.store import ObjectStore
from kubedl_tpu.engine.job_controller import JobEngine
from kubedl_tpu.gang.slice_scheduler import SliceGangScheduler, SliceInventory
from kubedl_tpu.lineage.builder import ArtifactRegistry
from kubedl_tpu.lineage.controller import ModelVersionController
from kubedl_tpu.observability.metrics import JobMetrics, MetricsRegistry
from kubedl_tpu.runtime.executor import ContainerRuntime, Kubelet, SubprocessRuntime
from kubedl_tpu.shards.store import ShardedObjectStore
from kubedl_tpu.utils.compile_cache import DEFAULT_CACHE_DIR
from kubedl_tpu.utils.features import FeatureGates
from kubedl_tpu.workloads.registry import WORKLOAD_REGISTRY, parse_workload_gate

log = logging.getLogger("kubedl_tpu.operator")


@dataclass
class OperatorOptions:
    """Startup flags (reference: cmd/options/options.go:24-49 +
    docs/startup_flags.md)."""

    workloads: str = "*"
    max_concurrent_reconciles: int = 2
    feature_gates: str = ""
    cluster_domain: str = ""
    artifact_registry_root: str = "/tmp/kubedl-tpu-registry"
    pod_log_dir: str = ""
    #: emit loopback addresses instead of svc DNS (local process runtime)
    local_addresses: bool = False
    #: workload-controller construction kwargs per kind
    controller_kwargs: Dict[str, dict] = field(default_factory=dict)
    #: durable metadata mirror (reference: --meta-storage flag,
    #: persist_controller.go:30-34). "" disables; "sqlite" enables.
    meta_storage: str = ""
    #: durable event sink (reference: --event-storage flag)
    event_storage: str = ""
    #: SQLite database path for the built-in backend (":memory:" or a file)
    storage_db_path: str = ":memory:"
    #: region stamped on mirrored rows (reference: REGION env)
    region: str = ""
    #: node identity of this operator/builder process — node-local
    #: ModelVersion artifacts (storage_provider="local") must be built
    #: co-located with their node_name; "" disables the guard (single-host)
    node_name: str = ""
    #: QPS probe for serving autoscale: callable(pod) -> float | None
    #: (e.g. kubedl_tpu.serving.controller.http_qps_probe). None disables
    #: load-driven scaling (autoscale min/max clamping still applies).
    serving_qps_probe: Optional[object] = None
    #: graceful-drain window (s) for retiring predictor pods: scale-down
    #: and predictor GC first tell the engine to drain (503 reason:
    #: draining, in-flight decodes finish) and delete only once idle or
    #: past the grace. 0 preserves delete-on-sight.
    serving_drain_grace_s: float = 0.0
    #: drain trigger: callable(pod) -> None (e.g.
    #: kubedl_tpu.serving.controller.http_drain_hook). None still delays
    #: deletion by the idle-probe/grace when serving_drain_grace_s > 0.
    serving_drain_hook: Optional[object] = None
    #: persistent XLA compilation-cache dir injected into every training/
    #: serving pod (KUBEDL_COMPILE_CACHE_DIR) so gang restarts, resizes,
    #: and resumes deserialize compiled programs instead of re-lowering
    #: them (round-2 startup regression, VERDICT.md). Default is one
    #: fixed directory inside the checkout (the path is part of the
    #: cache's key); a JAX_COMPILATION_CACHE_DIR the pods inherit wins
    #: over it (utils/compile_cache.py). "" injects nothing.
    compile_cache_dir: str = DEFAULT_CACHE_DIR
    #: lease-based leader election (reference: main.go:76-84
    #: "kubedl-election"): with True, this operator campaigns for the
    #: lease in its store and reconciles ONLY while holding it; losing
    #: the lease stops the operator (crash-only — restart to re-campaign)
    leader_elect: bool = False
    #: candidate identity; defaults to hostname-pid
    leader_identity: str = ""
    leader_lease_ttl: float = 5.0
    #: base URL of a remote store (kubedl_tpu.remote.RemoteStoreServer);
    #: enables meta_storage/event_storage="http" (network persist mirror)
    remote_storage_url: str = ""
    #: node-failure detection: a Node object that misses heartbeats this
    #: long flips NotReady and its pods fail RETRYABLY (gang restart).
    #: Pods on hosts without a registered Node object are untouched.
    node_grace_seconds: float = 15.0
    #: node names THIS process's kubelet heartbeats (opt-in; defaults to
    #: [node_name] when node_name is set)
    heartbeat_nodes: List[str] = field(default_factory=list)
    #: progress watchdog (kubedl_tpu/watchdog/, docs/robustness.md "Hang
    #: detection"): classify hung / silently-dead / straggling replicas
    #: from per-step beacons and drive the normal gang-restart path
    watchdog_enabled: bool = True
    #: hang budget multiplier over the observed step-time EWMA
    watchdog_multiplier: float = 4.0
    #: floor under every watchdog budget (seconds)
    watchdog_min_budget_seconds: float = 30.0
    #: budget before the first observed step advance (covers compilation)
    watchdog_startup_grace_seconds: float = 300.0
    #: straggler flag: step rate below this fraction of the gang median
    watchdog_straggler_ratio: float = 0.25
    #: directory for per-pod progress-beacon files (KUBEDL_BEACON_FILE).
    #: Per-user default for the same poisoning reason as the compile
    #: cache; "" disables beacon injection (watchdog then only sees
    #: in-process announce_progress traffic).
    beacon_dir: str = field(default_factory=lambda: os.path.join(
        tempfile.gettempdir(), f"kubedl-tpu-beacons-{os.getuid()}"
    ))
    #: elastic slice scaling: minimum seconds between GROW resizes per job
    #: (shrinks away from draining slices bypass the cooldown). See
    #: kubedl_tpu/elastic/policy.py and docs/elasticity.md.
    elastic_cooldown_seconds: float = 30.0
    #: crash recovery (docs/robustness.md "Crash recovery"): directory for
    #: the store's write-ahead log + snapshot. "" keeps the store purely
    #: in-memory; set it and a restarted operator rehydrates the whole
    #: object world, re-reserves gang slices and adopts running pods.
    #: Ignored when an explicit ``store`` is passed to the constructor.
    wal_dir: str = ""
    #: WAL fsync policy: "always" | "group" | "batch" | "off"
    #: (core/wal.py). "group" group-commits: appends stage and a
    #: per-segment committer fsyncs once per batch window with identical
    #: ack-durability to "always" — O(batches) fsyncs instead of
    #: O(appends) under write bursts.
    wal_fsync: str = "always"
    #: group-commit batch window in milliseconds (wal_fsync="group"):
    #: how long the committer lets appends pile up before the one fsync
    #: that acknowledges them all. Bounds a writer's ack latency;
    #: bigger windows = fewer, larger batches.
    wal_group_window_ms: float = 5.0
    #: WAL records between snapshot+compaction passes
    wal_snapshot_every: int = 1000
    #: workqueue burst-coalescing window in milliseconds (0 = off): a
    #: storm of watch events on one key within the window costs one
    #: follow-up reconcile instead of one per event; the re-add always
    #: fires after the last absorbed event, so the final state is never
    #: dropped (core/workqueue.py). On by default with a window well
    #: under any reconcile SLO: besides cutting redundant passes under
    #: gang churn, it lets a burst SETTLE before the controller acts —
    #: a job's success transition observes every worker's final phase
    #: instead of racing the last in-flight update and reaping a pod
    #: whose terminal state was milliseconds from landing.
    reconcile_coalesce_ms: float = 10.0
    #: sharded control plane (kubedl_tpu/shards/, docs/architecture.md
    #: "Sharded control plane"): number of reconcile domains. 1 keeps
    #: today's single-domain operator — and its on-disk WAL layout —
    #: byte-for-byte; N>1 splits objects across N shard-local stores
    #: (WAL segments under wal_dir/shard-<i>) with per-shard workqueues.
    control_plane_shards: int = 1
    #: directory of cross-process shard lease files
    #: (shards.fencing.FileLeaseStore). "" runs unfenced: this process
    #: owns every shard and no elector threads exist.
    shard_lease_dir: str = ""
    #: fenced mode: shard ids to acquire at startup (None -> all)
    shard_own: Optional[List[int]] = None
    #: fenced mode: shard ids to stand by for — campaign in the
    #: background and take over (rehydrate-then-adopt) on lease expiry
    shard_standby: List[int] = field(default_factory=list)
    #: per-shard lease TTL: a standby takes a dead owner's shard within
    #: about this many seconds
    shard_lease_ttl: float = 2.0
    #: multi-operator federation (kubedl_tpu/federation/,
    #: docs/architecture.md "Multi-operator federation"): N operator
    #: PROCESSES share one lease/WAL root, each owning the shards the
    #: deterministic rebalancer assigns it and standing by — with
    #: rank-staggered campaigns — for everything else. Requires
    #: shard_lease_dir + wal_dir + control_plane_shards > 1 and a unique
    #: leader_identity per process. Overrides shard_own/shard_standby.
    federation: bool = False
    #: full configured membership (identities, including this process);
    #: succession order ranks over THIS list, so every member must agree
    federation_peers: List[str] = field(default_factory=list)
    #: seconds between lease-root heartbeat probes
    federation_heartbeat_interval: float = 0.25
    #: lease-root unreachable this long -> demote to read-only. 0 picks
    #: the default (half the shard lease TTL); must stay < the TTL.
    federation_demotion_deadline: float = 0.0
    #: seconds between WAL-tail refreshes for remote-shard reads
    federation_tail_interval: float = 0.25


class ValidationError(ValueError):
    """Admission rejection (reference: validating webhook deny)."""

    def __init__(self, kind: str, errors: List[str]) -> None:
        super().__init__(f"{kind} rejected: " + "; ".join(errors))
        self.errors = errors


class Operator:
    def __init__(
        self,
        options: Optional[OperatorOptions] = None,
        runtime: Optional[ContainerRuntime] = None,
        inventory: Optional[SliceInventory] = None,
        store: Optional[ObjectStore] = None,
    ) -> None:
        self.options = options or OperatorOptions()
        #: pass an existing store to run several operators against one
        #: object world (HA deployments — pair with leader_elect=True)
        lease_backend = None
        if store is not None:
            self.store = store
        else:
            if self.options.shard_lease_dir:
                from kubedl_tpu.shards.fencing import FileLeaseStore

                lease_backend = FileLeaseStore(self.options.shard_lease_dir)
            own = self.options.shard_own
            standby = list(self.options.shard_standby)
            if self.options.federation:
                # federation: EVERY shard is a standby campaign — the
                # member's rank-staggered delays (FederationMember.
                # standby_delays, delay 0 for planned shards) resolve each
                # lease to its planned owner without a synchronous ctor
                # acquisition, so a member restarting into a fleet where a
                # survivor already took its shards queues behind the live
                # holder instead of failing startup
                if lease_backend is None:
                    raise ValueError(
                        "federation=True requires shard_lease_dir (the "
                        "shared lease root is the arbitration surface)"
                    )
                own = []
                standby = list(range(self.options.control_plane_shards))
            self.store = ShardedObjectStore(
                shards=self.options.control_plane_shards,
                wal_dir=self.options.wal_dir or None,
                wal_fsync=self.options.wal_fsync,
                wal_snapshot_every=self.options.wal_snapshot_every,
                wal_group_window=self.options.wal_group_window_ms / 1e3,
                lease_backend=lease_backend,
                identity=self.options.leader_identity,
                lease_ttl=self.options.shard_lease_ttl,
                own=own,
                standby=standby,
                fence_verify_interval=0.05,
            )
        self._owns_store = store is None
        self.federation = None
        if self.options.federation and lease_backend is not None:
            from kubedl_tpu.federation import FederationMember

            self.federation = FederationMember(
                self.store,
                lease_backend,
                identity=self.store.identity,
                peers=self.options.federation_peers,
                lease_ttl=self.options.shard_lease_ttl,
                heartbeat_interval=self.options.federation_heartbeat_interval,
                demotion_deadline=(
                    self.options.federation_demotion_deadline or None
                ),
                tail_interval=self.options.federation_tail_interval,
            )
        self.metrics_registry = MetricsRegistry()
        self.metrics = JobMetrics(self.metrics_registry)
        self.manager = ControllerManager(self.store, metrics=self.metrics)
        self.features = FeatureGates()
        if self.options.feature_gates:
            self.features.set_from_string(self.options.feature_gates)
        self.inventory = inventory or SliceInventory()
        self.gang = SliceGangScheduler(self.store, self.inventory)
        self.engines: Dict[str, JobEngine] = {}
        self.controllers: Dict[str, WorkloadController] = {}

        # workload-gated controller setup (reference: controllers.go:29-45)
        enabled = parse_workload_gate(self.options.workloads, list(WORKLOAD_REGISTRY))
        for kind in enabled:
            kwargs = dict(self.options.controller_kwargs.get(kind, {}))
            factory = WORKLOAD_REGISTRY[kind]
            try:
                controller = factory(
                    cluster_domain=self.options.cluster_domain,
                    local_addresses=self.options.local_addresses,
                    **kwargs,
                )
            except TypeError:
                controller = factory(**kwargs)
            engine = JobEngine(
                store=self.store,
                controller=controller,
                recorder=self.manager.recorder,
                gang_scheduler=self.gang,
                metrics=self.metrics,
                features=self.features,
                cluster_domain=self.options.cluster_domain,
                compile_cache_dir=self.options.compile_cache_dir,
                beacon_dir=self.options.beacon_dir,
            )
            self.engines[kind] = engine
            self.controllers[kind] = controller
            self.manager.register(
                f"{kind.lower()}-controller",
                engine.reconcile,
                watch_kinds=[kind, "Pod", "Service", "PodGroup"],
                mapper=self._engine_mapper(kind),
                workers=self.options.max_concurrent_reconciles,
                coalesce_window=self.options.reconcile_coalesce_ms / 1e3,
                # list-then-watch: rehydrated jobs are re-enqueued at start
                # instead of waiting for their next mutation
                resync_on_start=True,
            )
            # live running/pending gauges (reference: status_counter.go:22-81)
            self._register_status_gauges(kind)

        # pod runtime
        self.kubelet = Kubelet(
            self.store, runtime or SubprocessRuntime(self.options.pod_log_dir),
            metrics=self.metrics,
        )
        self.kubelet.setup(self.manager)

        # crash-recovery observability (core/wal.py; gauges read live)
        self.metrics.wal_appends.set_function(
            lambda: float(self.store.wal_appends)
        )
        self.metrics.wal_fsyncs.set_function(
            lambda: float(self.store.wal_fsyncs)
        )
        self.metrics.watch_gaps.set_function(
            lambda: float(getattr(self.store, "watch_gaps", 0))
        )
        # group commit: per-batch record counts feed the batch-size
        # histogram straight from each segment's committer thread
        if hasattr(self.store, "set_wal_batch_observer"):
            self.store.set_wal_batch_observer(
                lambda n: self.metrics.wal_batch_size.observe(float(n))
            )
        # sharded control plane: per-domain WAL series beside the process
        # totals above, ownership gauge, and the per-shard failover hook
        num_shards = getattr(self.store, "num_shards", 1)
        if num_shards > 1:
            for i in range(num_shards):
                self.metrics.wal_appends.set_function(
                    lambda i=i: float(self.store.wal_appends_for(i)),
                    shard=str(i),
                )
                self.metrics.wal_fsyncs.set_function(
                    lambda i=i: float(self.store.wal_fsyncs_for(i)),
                    shard=str(i),
                )
                self.metrics.watch_gaps.set_function(
                    lambda i=i: float(self.store.watch_gaps_for(i)),
                    shard=str(i),
                )
        if hasattr(self.store, "owned_shards"):
            self.metrics.shards_owned.set_function(
                lambda: float(len(self.store.owned_shards()))
            )
        else:
            self.metrics.shards_owned.set_function(lambda: 1.0)
        if hasattr(self.store, "on_shard_acquired"):
            self.store.on_shard_acquired = self._on_shard_acquired
        if self.federation is not None:
            member = self.federation
            self.metrics.federation_heartbeats.set_function(
                lambda: float(member.heartbeats)
            )
            self.metrics.federation_heartbeat_misses.set_function(
                lambda: float(member.heartbeat_misses)
            )
            self.metrics.federation_demotions.set_function(
                lambda: float(member.demotions)
            )
            self.metrics.federation_read_only.set_function(
                lambda: 1.0 if member.read_only else 0.0
            )

        # node lifecycle: heartbeat-driven failure detection (the k8s
        # node-controller analogue the reference delegates to the cluster)
        from kubedl_tpu.core.nodes import NodeHeartbeater, NodeLifecycleController

        self.node_lifecycle = NodeLifecycleController(
            self.store, self.manager.recorder,
            grace=self.options.node_grace_seconds,
        )
        self.node_lifecycle.setup(self.manager)
        beat_names = self.options.heartbeat_nodes or (
            [self.options.node_name] if self.options.node_name else []
        )
        self.node_heartbeater = NodeHeartbeater(
            self.store, beat_names,
            interval=max(self.options.node_grace_seconds / 3.0, 0.5),
        )

        # progress watchdog: beacons ride the heartbeat onto Node objects;
        # the controller classifies hang / silent-death / straggler and
        # fails wedged pods retryably (kubedl_tpu/watchdog/)
        self.watchdog = None
        if self.options.watchdog_enabled:
            from kubedl_tpu.watchdog import (
                FileBeaconSource,
                WatchdogConfig,
                WatchdogController,
            )

            if self.options.beacon_dir:
                self.node_heartbeater.beacon_source = FileBeaconSource(
                    self.options.beacon_dir, self.store
                )
            self.watchdog = WatchdogController(
                self.store, self.manager.recorder, metrics=self.metrics,
                config=WatchdogConfig(
                    multiplier=self.options.watchdog_multiplier,
                    min_budget_seconds=self.options.watchdog_min_budget_seconds,
                    startup_grace_seconds=(
                        self.options.watchdog_startup_grace_seconds
                    ),
                    straggler_ratio=self.options.watchdog_straggler_ratio,
                ),
            )
            self.watchdog.setup(self.manager)
            self.metrics.watchdog_tracked.set_function(
                lambda: float(self.watchdog.tracked())
            )

        # elastic slice scaling: preemption notices -> draining slices ->
        # policy-driven grow/shrink (kubedl_tpu/elastic/, docs/elasticity.md)
        from kubedl_tpu.elastic import ElasticPolicy, PreemptionController

        self.preemption = PreemptionController(
            self.store, self.inventory, self.manager.recorder,
            metrics=self.metrics,
        )
        self.preemption.setup(self.manager)
        self.elastic_policy = ElasticPolicy(
            self.store, self.inventory, self.gang, self.controllers,
            self.manager.recorder,
            cooldown=self.options.elastic_cooldown_seconds,
        )
        self.elastic_policy.setup(self.manager)
        self.metrics.slices_draining.set_function(
            lambda: float(len(self.inventory.draining_slices()))
        )

        # model lineage
        self.artifact_registry = ArtifactRegistry(self.options.artifact_registry_root)
        self.lineage = ModelVersionController(
            self.store, self.artifact_registry, self.manager.recorder,
            local_node=self.options.node_name,
        )
        self.lineage.setup(self.manager)

        # cron workflows over every enabled kind (reference: controllers/apps)
        from kubedl_tpu.cron.controller import CronController

        self.cron = CronController(
            self.store, list(self.engines), self.manager.recorder,
            submitter=self.submit,
        )
        self.cron.setup(self.manager)

        # persistence: storage backends + persist controllers
        # (reference: main.go:104-107 — RegisterStorageBackends then
        # persist.SetupWithManager)
        self.object_backend = None
        self.event_backend = None
        if self.options.meta_storage or self.options.event_storage:
            from kubedl_tpu.persist import PersistControllers, default_registry

            registry = default_registry(
                self.options.storage_db_path,
                remote_url=self.options.remote_storage_url,
            )
            if self.options.meta_storage:
                self.object_backend = registry.object_backend(
                    self.options.meta_storage
                )
            if self.options.event_storage:
                self.event_backend = registry.event_backend(
                    self.options.event_storage
                )
            self.persist = PersistControllers(
                self.store,
                kinds=list(self.engines),
                object_backend=self.object_backend,
                event_backend=self.event_backend,
                region=self.options.region,
            )
            self.persist.setup(self.manager)

        # inference serving (reference: controllers/serving)
        from kubedl_tpu.serving.controller import InferenceController

        self.serving = InferenceController(
            self.store,
            self.manager.recorder,
            local_addresses=self.options.local_addresses,
            cluster_domain=self.options.cluster_domain,
            qps_probe=self.options.serving_qps_probe,
            compile_cache_dir=self.options.compile_cache_dir,
            drain_grace_s=self.options.serving_drain_grace_s,
            drain_hook=self.options.serving_drain_hook,
        )
        self.serving.setup(self.manager)

    def _engine_mapper(self, kind: str):
        """owner_mapper plus the gang-release nudge: a PodGroup deletion
        frees slices, so every QUEUED job of this kind is requeued
        immediately instead of waiting out its admission poll (round-1
        weakness: gang admission busy-polled at 1s forever)."""
        from kubedl_tpu.api.types import JobConditionType

        base = owner_mapper(kind)

        def mapper(event, obj, old):
            keys = base(event, obj, old)
            if obj.kind == "PodGroup" and event == "DELETED":
                for j in self.store.list(kind, None):  # every namespace
                    if (
                        j.status.phase == JobConditionType.QUEUED
                        and (j.metadata.namespace, j.metadata.name) not in keys
                    ):
                        keys.append((j.metadata.namespace, j.metadata.name))
            return keys

        return mapper

    def _register_status_gauges(self, kind: str) -> None:
        from kubedl_tpu.api.types import JobConditionType

        def count(phase: JobConditionType) -> float:
            n = 0
            for obj in self.store.list(kind, namespace=None):
                if isinstance(obj, JobObject) and obj.status.phase == phase:
                    n += 1
            return float(n)

        self.metrics.running.set_function(
            lambda: count(JobConditionType.RUNNING), kind=kind
        )
        self.metrics.pending.set_function(
            lambda: count(JobConditionType.CREATED)
            + count(JobConditionType.QUEUED),
            kind=kind,
        )

    # ------------------------------------------------------------------

    def start(self) -> None:
        self.node_heartbeater.start()
        if not self.options.leader_elect:
            self._recover()
            self.manager.start()
            if self.federation is not None:
                # federation: the member starts campaigns (with rank-
                # staggered standby delays), tails remote shards, and
                # runs the heartbeat/demotion loop
                self.federation.start()
            elif hasattr(self.store, "start_campaigns"):
                # fenced sharding: begin renewing owned shard leases and
                # campaigning for standby shards (unfenced stores: no-op)
                self.store.start_campaigns()
            return
        # HA mode (reference: main.go:76-84): reconcile only while holding
        # the lease. The follower builds everything but starts nothing;
        # on acquisition it runs the SAME rehydrate-then-adopt recovery a
        # cold restart does (the previous leader's world — gangs, running
        # pods — is in the shared/replayed store, not in this process),
        # then resyncs (kick_all) and runs; on LOSS it stops for good
        # (crash-only — the process restarts to re-campaign).
        from kubedl_tpu.core.leases import LeaderElector

        self.elector = LeaderElector(
            self.store,
            identity=self.options.leader_identity,
            ttl=self.options.leader_lease_ttl,
        )

        def on_started() -> None:
            self._recover(takeover=True)
            self.manager.start()
            self.manager.kick_all()

        self.elector.start(on_started=on_started, on_stopped=self._on_deposed)

    def _recover(self, takeover: bool = False) -> None:
        """Cold-start / takeover recovery (docs/robustness.md): drop the
        dead incarnation's expectations, re-reserve recorded gang slice
        assignments into this inventory, arm pod adoption, and re-enqueue
        every key. Runs BEFORE controllers start; a fresh empty store makes
        every step a no-op."""
        rehydrated = getattr(self.store, "rehydrated", False)
        if not (rehydrated or takeover):
            return
        import time as _time

        t0 = _time.perf_counter()
        for engine in self.engines.values():
            engine.expectations.clear()
        adopted_gangs = self.gang.adopt_reservations()
        adoptable_pods = self.kubelet.begin_recovery()
        if rehydrated:
            self.metrics.replayed_records.inc(self.store.replayed_records)
            # relist/resync: controllers registered without resync_on_start
            # (serving, lineage, cron, ...) still see every existing key
            self.manager.kick_all()
        self.metrics.recovery_duration.set(
            getattr(self.store, "recovery_seconds", 0.0)
            + (_time.perf_counter() - t0)
        )
        log.info(
            "recovery: %d WAL records replayed, %d gangs re-reserved, "
            "%d pods adoptable (takeover=%s)",
            getattr(self.store, "replayed_records", 0), adopted_gangs,
            adoptable_pods, takeover,
        )

    def _on_shard_acquired(self, shard: int, objs) -> None:
        """Shard failover: the PR 5 rehydrate-then-adopt path scoped to
        ONE reconcile domain. Runs on the standby's elector thread right
        after the dead owner's WAL segment rehydrated, BEFORE the
        rehydrated ADDED events reach the controllers: the dead owner's
        expectations for this domain are dropped (sharded caches drop one
        domain; flat caches drop everything — strictly safe), recorded
        gang reservations re-pin, and the kubelet arms adoption so
        surviving pods re-attach by (name, uid, pid) instead of being
        double-launched."""
        for engine in self.engines.values():
            exps = engine.expectations
            if hasattr(exps, "clear_shard"):
                exps.clear_shard(shard)
            else:
                exps.clear()
        adopted_gangs = self.gang.adopt_reservations()
        adoptable_pods = self.kubelet.begin_recovery()
        log.info(
            "shard %d takeover: %d objects rehydrated, %d gangs "
            "re-reserved, %d pods adoptable",
            shard, len(objs), adopted_gangs, adoptable_pods,
        )

    def _on_deposed(self) -> None:
        self.kubelet.shutdown()
        self.manager.stop()

    def stop(self) -> None:
        # The order is load-bearing (pinned by tests/test_federation.py::
        # TestStopOrdering): federation loops and shard campaigns halt
        # FIRST, so no standby takeover can mount a shard — and no lease
        # renewal can extend ownership — into a process that is already
        # tearing down workers; the store (and its group-commit committer
        # threads) closes LAST, after the manager has drained reconciles,
        # so an in-flight commit window is fsynced, never appended to a
        # closed WAL.
        if self.federation is not None:
            self.federation.stop()
        if hasattr(self.store, "stop_campaigns"):
            self.store.stop_campaigns()
        elector = getattr(self, "elector", None)
        if elector is not None:
            elector.stop()
        self.node_heartbeater.stop()
        self.kubelet.shutdown()
        self.manager.stop()
        if self._owns_store:
            self.store.close()  # flush + detach the WAL (no-op without one)
        for backend in (self.object_backend, self.event_backend):
            if backend is not None:
                backend.close()

    def __enter__(self) -> "Operator":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------- submit

    def submit(self, job: JobObject) -> JobObject:
        """Admission + create (the reference's defaulting/validating
        webhook chain runs in-process here): defaults are applied, the
        kind's validation rules run, then the object lands in the store."""
        engine = self.engines.get(job.kind)
        if engine is None:
            raise ValidationError(
                job.kind, [f"workload kind {job.kind!r} is not enabled"]
            )
        # validate BEFORE defaulting: the user must get a 400 for a
        # disallowed replica group, not have it silently pruned (defaulting
        # still degrades gracefully on the reconcile path)
        errs = engine.controller.validate(job)
        if errs:
            raise ValidationError(job.kind, errs)
        engine.controller.apply_defaults(job)
        return self.store.create(job)  # type: ignore[return-value]

    def wait_for_phase(
        self, kind: str, name: str, phases, timeout: float = 30.0, namespace: str = "default"
    ) -> JobObject:
        if not isinstance(phases, (list, tuple, set)):
            phases = [phases]

        def check() -> bool:
            obj = self.store.try_get(kind, name, namespace)
            return obj is not None and obj.status.phase in phases  # type: ignore[attr-defined]

        self.manager.wait(check, timeout=timeout)
        obj = self.store.try_get(kind, name, namespace)
        if obj is None:
            raise LookupError(f"{kind} {namespace}/{name} vanished")
        return obj  # type: ignore[return-value]

    def render_metrics(self) -> str:
        return self.metrics_registry.render()
