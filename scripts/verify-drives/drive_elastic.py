"""Drive elastic slice scaling end to end through the PUBLIC surface: a
real Operator under an armed `elastic.preempt` FaultPlan. The injected
preemption notice drains a slice, the ElasticPolicy shrinks the gang off
it (in-place resize + Resizing condition + restart), clearing the notice
grows it back, and the job still finishes clean — with restart count,
resize/notice metrics, drain gauge and events all matching the plan.
Plus: draining slices are unreservable, grad-accum rescaling preserves
the effective global batch, and goodput math clamps sanely."""
import os
import sys
import tempfile
import shutil
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
os.environ["JAX_PLATFORMS"] = "cpu"

ok = []
def check(name, cond, detail=""):
    ok.append(bool(cond))
    print(("PASS" if cond else "FAIL"), name, detail)

from kubedl_tpu.api.topology import get_slice
from kubedl_tpu.api.types import (
    ElasticSpec, JobConditionType, ReplicaSpec, ReplicaType, RestartPolicy)
from kubedl_tpu.chaos import FaultPlan, FaultSpec
from kubedl_tpu.core.objects import Container
from kubedl_tpu.elastic.resize import goodput, grad_accum_for_world
from kubedl_tpu.gang.slice_scheduler import SliceInventory
from kubedl_tpu.operator import Operator, OperatorOptions
from kubedl_tpu.runtime.executor import ThreadRuntime
from kubedl_tpu.workloads.tpujob import TPUJob

tmp = tempfile.mkdtemp(prefix="kdl-elastic-drive-")

# 1. batch-semantics math: effective global batch is world-invariant
check("grad accum rescales inversely with world",
      grad_accum_for_world(1, 4, 2, 8) == 2
      and grad_accum_for_world(2, 4, 1, 8) == 8
      and grad_accum_for_world(4, 2, 4, 8) == 2)
check("grad accum clamps to a divisor of global batch",
      grad_accum_for_world(8, 3, 4, 8) == 4
      and grad_accum_for_world(64, 8, 1, 16) == 16)
check("goodput clamps to [0, 1]",
      goodput(8.0, 10.0) == 0.8 and goodput(12.0, 10.0) == 1.0
      and goodput(1.0, 0.0) == 0.0)

# 2. draining slices leave the allocatable pool
inv0 = SliceInventory()
inv0.add_slice("da", "cpu-1")
inv0.mark_draining("da", "drill")
check("draining slice is unreservable and visible in detail()",
      inv0.try_reserve("cpu-1", 1, "x/y-gang") == []
      and inv0.detail()[0]["draining"] is True
      and inv0.detail()[0]["drain_reason"] == "drill")
inv0.clear_draining("da")
check("cleared slice is reservable again",
      inv0.try_reserve("cpu-1", 1, "x/y-gang") == ["da"])

# 3. the full loop under seeded chaos: notice -> drain -> shrink ->
#    clear -> grow -> clean finish
_STOP = {"path": os.path.join(tmp, "stop")}

def _gated_worker(env):
    cancel = (env or {}).get("_KUBEDL_CANCEL")
    while not os.path.exists(_STOP["path"]):
        if cancel is not None and cancel.is_set():
            raise SystemExit(137)
        time.sleep(0.02)
    return 0

sys.modules["__drive_elastic__"] = sys.modules[__name__]

inv = SliceInventory()
inv.add_slice("sa", "cpu-1")  # host sa-host-0
inv.add_slice("sb", "cpu-1")  # host sb-host-0
opts = OperatorOptions(
    local_addresses=True,
    artifact_registry_root=os.path.join(tmp, "reg"),
    heartbeat_nodes=["sa-host-0", "sb-host-0"],
    node_grace_seconds=2.0,
)
# beats visit nodes in heartbeat_nodes order: nth(2) deterministically
# notices sb-host-0 on the first armed beat
plan = FaultPlan(23, sites={"elastic.preempt": [FaultSpec.nth(2)]})
with Operator(opts, runtime=ThreadRuntime(), inventory=inv) as op:
    job = TPUJob()
    job.metadata.name = "drill"
    spec = ReplicaSpec(replicas=2, topology=get_slice("cpu-1"),
                       restart_policy=RestartPolicy.ON_FAILURE_SLICE)
    spec.template.spec.containers.append(
        Container(entrypoint="__drive_elastic__:_gated_worker"))
    job.spec.replica_specs[ReplicaType.WORKER] = spec
    job.num_slices = 2
    job.elastic = ElasticSpec(min_slices=1, max_slices=2,
                              cooldown_seconds=0.2)
    op.submit(job)
    op.wait_for_phase("TPUJob", "drill", JobConditionType.RUNNING,
                      timeout=60)

    with plan:
        def shrunk():
            got = op.store.try_get("TPUJob", "drill")
            return (got is not None and got.num_slices == 1
                    and len(list(op.store.list("Pod", "default"))) == 1)
        check("injected notice shrinks the gang off the draining slice",
              op.manager.wait(shrunk, timeout=60))
        detail = {d["name"]: d for d in inv.detail()}
        check("victim slice draining; survivor keeps the gang",
              detail["sb"]["draining"] is True
              and detail["sa"]["allocated_to"] == "default/drill-gang")
        got = op.store.get("TPUJob", "drill")
        check("Resizing condition recorded",
              any(c.type == JobConditionType.RESIZING
                  for c in got.status.conditions))
        check("drain gauge reflects the notice",
              op.metrics.slices_draining.value() == 1.0)

        op.node_heartbeater.clear_preemption("sb-host-0")

        def grown():
            got = op.store.try_get("TPUJob", "drill")
            return (got is not None and got.num_slices == 2
                    and len(list(op.store.list("Pod", "default"))) == 2)
        check("cleared notice grows the gang back",
              op.manager.wait(grown, timeout=60))

        with open(_STOP["path"], "w") as f:
            f.write("done")
        got = op.wait_for_phase(
            "TPUJob", "drill",
            [JobConditionType.SUCCEEDED, JobConditionType.FAILED],
            timeout=60)
    check("job finishes clean at the grown shape",
          got.status.phase == JobConditionType.SUCCEEDED
          and got.num_slices == 2,
          f"phase={got.status.phase} slices={got.num_slices}")
    check("exactly the planned single notice was injected",
          plan.faults("elastic.preempt") == 1
          and got.status.restart_count == 2,
          f"faults={plan.faults('elastic.preempt')} "
          f"restarts={got.status.restart_count}")
    reasons = {e.reason for e in op.store.list("Event", None)}
    check("observable: metrics + events",
          op.metrics.resizes.value(kind="TPUJob") == 2.0
          and op.metrics.preemption_notices.value() == 1.0
          and op.metrics.slices_draining.value() == 0.0
          and {"PreemptionNotice", "PreemptionCleared",
               "ElasticResize", "SliceResize"} <= reasons,
          f"reasons={sorted(reasons)}")
    check("drain gauge exported",
          "kubedl_tpu_slices_draining" in op.render_metrics())

shutil.rmtree(tmp, ignore_errors=True)
print(f"\n{sum(ok)}/{len(ok)} checks passed")
sys.exit(0 if all(ok) else 1)
