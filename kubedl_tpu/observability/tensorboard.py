"""TensorBoard sidecar lifecycle, annotation-driven.

Reference analogue: pkg/tensorboard/tensorboard.go:34-447 — a job annotated
with `kubedl.io/tensorboard-config` gets a TensorBoard pod (mirroring the
master replica's volumes so the logDir is reachable) plus a service and an
optional ingress; after the job finishes the whole set is torn down once a
TTL keyed off CompletionTime (or the config's UpdateTimestamp) expires
(tensorboard.go:382-447). Invoked per-reconcile from the TF controller in
the reference (tfjob_controller.go:171-177); here the engine invokes it for
every workload kind carrying the annotation.

TPU-first notes: the same machinery also serves the XLA/TPU profiler
(SURVEY.md §5 "surface XLA/TPU profiler the same annotation-driven way") —
`profile: true` in the config points TensorBoard at the job's xprof trace
dir and sets the env the tensorboard-plugin-profile expects. Nothing in the
repo writes that directory today: a worker has no switch that starts a
`jax.profiler` capture (ROADMAP, Design). A capture someone does start in a
worker holds the trainer's `train.*` phases beside the device plane
(docs/observability.md "Device profiles").
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import List, Optional

from kubedl_tpu.api import constants
from kubedl_tpu.api.interface import JobObject
from kubedl_tpu.core.objects import (
    Container,
    ObjectMeta,
    OwnerRef,
    Pod,
    PodSpec,
    Port,
    Service,
    ServiceSpec,
    Volume,
)
from kubedl_tpu.core.store import AlreadyExists, NotFound, ObjectStore

TB_PORT = 6006
#: stamped on the job so the console can link to the board
ANNOTATION_TB_URL = constants.API_GROUP + "/tensorboard-url"
TB_DEFAULT_IMAGE = "tensorflow/tensorflow:latest"
#: default time-to-live after job completion (reference keeps the pod until
#: TTL expiry so users can still inspect curves post-mortem)
TB_DEFAULT_TTL = 60 * 60


@dataclass
class TensorBoardSpec:
    """Parsed `kubedl-tpu.io/tensorboard-config` annotation value.

    Mirrors the reference's TensorBoard config struct
    (pkg/tensorboard/tensorboard.go:34-57): logDir, image, ingress spec and
    TTL, plus `updateTimestamp` which forces pod re-creation when the user
    edits the config mid-flight (tensorboard.go:142-229).
    """

    log_dir: str = "/kubedl-model/logs"
    image: str = TB_DEFAULT_IMAGE
    ttl_seconds_after_job_finished: int = TB_DEFAULT_TTL
    ingress_path: str = ""
    update_timestamp: float = 0.0
    #: TPU addition: serve the xprof profiler plugin over the job's trace dir
    profile: bool = False
    #: Python entrypoint override ("pkg.mod:fn") for the in-process runtime
    entrypoint: str = ""

    @classmethod
    def from_annotation(cls, raw: str) -> "TensorBoardSpec":
        data = json.loads(raw)
        if not isinstance(data, dict):
            raise ValueError(f"tensorboard-config must be a JSON object, got {type(data).__name__}")
        return cls(
            log_dir=data.get("logDir", cls.log_dir),
            image=data.get("image", TB_DEFAULT_IMAGE),
            ttl_seconds_after_job_finished=int(
                data.get("ttlSecondsAfterJobFinished", TB_DEFAULT_TTL)
            ),
            ingress_path=data.get("ingressPath", ""),
            update_timestamp=float(data.get("updateTimestamp", 0.0)),
            profile=bool(data.get("profile", False)),
            entrypoint=data.get("entrypoint", ""),
        )


def parse_tensorboard_spec(job: JobObject) -> Optional[TensorBoardSpec]:
    raw = job.metadata.annotations.get(constants.ANNOTATION_TENSORBOARD_CONFIG)
    if not raw:
        return None
    try:
        return TensorBoardSpec.from_annotation(raw)
    except (ValueError, TypeError):
        return None


def tb_name(job: JobObject) -> str:
    return f"{job.metadata.name}-tensorboard"


class TensorBoardReconciler:
    """Sync/teardown of the per-job TensorBoard pod + service.

    Returns a requeue-after (seconds) when a TTL deadline is pending, the
    same contract the engine's own TTL handling uses.
    """

    def __init__(self, store: ObjectStore, cluster_domain: str = "") -> None:
        self.store = store
        self.cluster_domain = cluster_domain

    # ------------------------------------------------------------------

    def reconcile(self, job: JobObject) -> Optional[float]:
        spec = parse_tensorboard_spec(job)
        if spec is None:
            # annotation removed -> tear down (tensorboard.go:59-86)
            self.delete(job)
            return None

        if job.status.is_terminal():
            anchor = job.status.completion_time or job.status.last_reconcile_time
            anchor = max(anchor or 0.0, spec.update_timestamp)
            remaining = anchor + spec.ttl_seconds_after_job_finished - time.time()
            if remaining <= 0:
                self.delete(job)
                return None
            self._sync(job, spec)
            return remaining

        self._sync(job, spec)
        return None

    def delete(self, job: JobObject) -> None:
        """Tear down pod + service (reference: tensorboard.go:382-447)."""
        from kubedl_tpu.federation.actuation import assert_fenced_actuation

        # fenced actuation (KTL011): the tb pod reap kills a process
        assert_fenced_actuation(
            self.store, job.metadata.namespace, job.metadata.name,
            action="pod delete",
        )
        name = tb_name(job)
        self.store.try_delete("Pod", name, job.metadata.namespace)
        self.store.try_delete("Service", name, job.metadata.namespace)

    # ------------------------------------------------------------------

    def _sync(self, job: JobObject, spec: TensorBoardSpec) -> None:
        self._sync_pod(job, spec)
        self._sync_service(job)
        # Surface the browse address on the job (the Mars pattern —
        # status.WebServiceAddresses, marsjob_types.go:53-56 — instead of a
        # separate Ingress object; the console reads this annotation).
        job.metadata.annotations[ANNOTATION_TB_URL] = self.url(job, spec)

    def _labels(self, job: JobObject) -> dict:
        # Deliberately NOT the engine's claim label set (no job-kind label):
        # the tb pod must not be adopted as a job replica — the reference
        # keeps tb pods outside GetPodsForJob's selector the same way.
        return {
            constants.LABEL_GROUP_NAME: constants.API_GROUP,
            constants.LABEL_JOB_NAME: job.metadata.name,
            constants.LABEL_REPLICA_TYPE: "tensorboard",
        }

    def _owner(self, job: JobObject) -> OwnerRef:
        return OwnerRef(kind=job.kind, name=job.metadata.name, uid=job.metadata.uid)

    def _master_volumes(self, job: JobObject) -> List[Volume]:
        """Mirror the master replica's volumes so the tb pod sees the same
        logDir mount (reference: syncPod copies the master's volumes,
        tensorboard.go:142-229)."""
        from kubedl_tpu.api.types import ReplicaType

        order = (
            ReplicaType.MASTER,
            ReplicaType.CHIEF,
            ReplicaType.LAUNCHER,
            ReplicaType.WORKER,
        )
        for rtype in order:
            rspec = job.spec.replica_specs.get(rtype)
            if rspec is not None and rspec.template.spec.volumes:
                import copy

                return copy.deepcopy(rspec.template.spec.volumes)
        return []

    def _sync_pod(self, job: JobObject, spec: TensorBoardSpec) -> None:
        from kubedl_tpu.federation.actuation import assert_fenced_actuation

        # fenced actuation (KTL011): may recreate the tb pod below
        assert_fenced_actuation(
            self.store, job.metadata.namespace, job.metadata.name,
            action="pod launch",
        )
        name = tb_name(job)
        existing = self.store.try_get("Pod", name, job.metadata.namespace)
        if existing is not None:
            assert isinstance(existing, Pod)
            stamped = existing.metadata.annotations.get("tb-update-timestamp", "0")
            if float(stamped) >= spec.update_timestamp:
                return
            # config changed underneath us -> recreate (tensorboard.go:142-170)
            self.store.try_delete("Pod", name, job.metadata.namespace)

        container = Container(
            name="tensorboard",
            image=spec.image,
            command=[
                "tensorboard",
                f"--logdir={spec.log_dir}",
                "--host=0.0.0.0",
                f"--port={TB_PORT}",
            ],
            entrypoint=spec.entrypoint,
            ports=[Port(name="http", port=TB_PORT)],
        )
        if spec.profile:
            # tensorboard-plugin-profile reads traces from the job's xprof
            # dir; exposed via env for the in-process server path too
            container.set_env("KUBEDL_XPROF_LOGDIR", spec.log_dir)
        pod = Pod(
            metadata=ObjectMeta(
                name=name,
                namespace=job.metadata.namespace,
                labels=self._labels(job),
                annotations={"tb-update-timestamp": str(spec.update_timestamp)},
                owner_refs=[self._owner(job)],
            ),
            spec=PodSpec(
                containers=[container],
                volumes=self._master_volumes(job),
                restart_policy="Always",
            ),
        )
        try:
            self.store.create(pod)
        except AlreadyExists:
            pass

    def _sync_service(self, job: JobObject) -> None:
        name = tb_name(job)
        if self.store.try_get("Service", name, job.metadata.namespace) is not None:
            return
        svc = Service(
            metadata=ObjectMeta(
                name=name,
                namespace=job.metadata.namespace,
                labels=self._labels(job),
                owner_refs=[self._owner(job)],
            ),
            spec=ServiceSpec(
                selector=self._labels(job),
                ports=[Port(name="http", port=TB_PORT)],
                cluster_ip="",  # ClusterIP (not headless): users browse it
            ),
        )
        try:
            self.store.create(svc)
        except AlreadyExists:
            pass

    # ------------------------------------------------------------------

    def url(self, job: JobObject, spec: Optional[TensorBoardSpec] = None) -> str:
        """Browse address for the tb service (console surfaces this the way
        the reference's console tensorboard API does,
        console/backend/pkg/routers/api/tensorboard.go). An `ingressPath`
        in the config becomes the URL path (reference: syncIngress,
        tensorboard.go:282-381)."""
        svc = Service(
            metadata=ObjectMeta(name=tb_name(job), namespace=job.metadata.namespace)
        )
        base = f"http://{svc.dns_name(self.cluster_domain)}:{TB_PORT}"
        if spec is None:
            spec = parse_tensorboard_spec(job)
        if spec is not None and spec.ingress_path:
            return base + "/" + spec.ingress_path.lstrip("/")
        return base
