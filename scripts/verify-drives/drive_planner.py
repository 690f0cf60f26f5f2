"""Drive the auto-parallelism planner end to end through the PUBLIC
surface: a real Operator plans a `mesh: auto` TPUJob at admission (the
chosen layout reaches live workers via KUBEDL_MESH_AXES, the verdict is
visible as annotation + status.plan + Planned condition/event/metrics),
fails an impossible model with PlanInfeasible instead of admitting an
OOM loop, validates explicit mesh blocks at submit, and RE-PLANS a live
elastic job when its num_slices changes mid-run (docs/planning.md)."""
import json
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

from kubedl_tpu.api import constants
from kubedl_tpu.api.topology import MeshSpec, get_slice
from kubedl_tpu.api.types import (
    ElasticSpec, JobConditionType, ReplicaSpec, ReplicaType, RestartPolicy)
from kubedl_tpu.core.objects import Container
from kubedl_tpu.gang.slice_scheduler import SliceInventory
from kubedl_tpu.operator import Operator, OperatorOptions, ValidationError
from kubedl_tpu.planner import MODEL_ZOO, ModelDesc, PlanError, plan
from kubedl_tpu.runtime.executor import ThreadRuntime
from kubedl_tpu.utils.invariants import check_invariants
from kubedl_tpu.workloads.tpujob import TPUJob

tmp = tempfile.mkdtemp(prefix="kdl-planner-drive-")

# 1. the planner library itself: a REPLICATED update could not pure-DP
#    llama-1b on 16 GiB v5e chips (~15 GiB of optimizer state per chip);
#    the cross-replica sharded update divides that state by the data axis,
#    so plain DP fits and the simplicity tie-break keeps it
p = plan(MODEL_ZOO["llama-1b"], get_slice("v5e-8"))
check("llama-1b on v5e-8 fits pure DP under the sharded update",
      p.baseline_dp_ms is not None and p.mesh.axes == {"data": 8},
      p.mesh.to_env())
try:
    plan(MODEL_ZOO["llama-1b"], get_slice("cpu-1"))
    check("impossible shape raises PlanError", False)
except PlanError as e:
    check("impossible shape raises PlanError",
          "no memory-feasible layout" in str(e))

SEEN = {"auto": [], "elastic": []}

def _auto_worker(env):
    SEEN["auto"].append((env.get("KUBEDL_MESH_AXES"),
                         env.get("KUBEDL_PROCESS_ID")))
    return 0

_GATE = {"path": os.path.join(tmp, "release")}

def _gated_worker(env):
    SEEN["elastic"].append((env.get("KUBEDL_MESH_AXES"),
                            env.get("KUBEDL_ELASTIC_BASE_DP")))
    cancel = (env or {}).get("_KUBEDL_CANCEL")
    while not os.path.exists(_GATE["path"]):
        if cancel is not None and cancel.is_set():
            raise SystemExit(137)
        time.sleep(0.02)
    return 0

sys.modules["__drive_planner__"] = sys.modules[__name__]

LLAMA_1B = MODEL_ZOO["llama-1b"]

def _auto_job(name, topo_name, workers, entrypoint, model=None):
    job = TPUJob()
    job.metadata.name = name
    spec = ReplicaSpec(replicas=workers, topology=get_slice(topo_name),
                       restart_policy=RestartPolicy.ON_FAILURE_SLICE)
    spec.template.spec.containers.append(Container(entrypoint=entrypoint))
    job.spec.replica_specs[ReplicaType.WORKER] = spec
    job.mesh = "auto"
    m = model or LLAMA_1B
    job.model_desc = ModelDesc(
        layers=m.layers, hidden=m.hidden, ffn=m.ffn, vocab=m.vocab,
        seq_len=m.seq_len, global_batch=m.global_batch)
    return job

inv = SliceInventory()
inv.add_slice("v8a", "v5e-8")
inv.add_slice("ca", "cpu-1")
inv.add_slice("cb", "cpu-1")
opts = OperatorOptions(
    local_addresses=True,
    artifact_registry_root=os.path.join(tmp, "reg"),
)
with Operator(opts, runtime=ThreadRuntime(), inventory=inv) as op:
    # 2. admission validation: a bad explicit mesh fails the SUBMIT
    bad = _auto_job("bad", "v5e-8", 2, "__drive_planner__:_auto_worker")
    bad.mesh = MeshSpec({"data": 4})  # v5e-8 has 8 chips
    try:
        op.submit(bad)
        check("wrong-product mesh rejected at submit", False)
    except ValidationError as e:
        check("wrong-product mesh rejected at submit", "devices" in str(e))
    noauto = _auto_job("noauto", "v5e-8", 2, "__drive_planner__:_auto_worker")
    noauto.model_desc = None
    try:
        op.submit(noauto)
        check("mesh auto without modelDesc rejected", False)
    except ValidationError as e:
        check("mesh auto without modelDesc rejected", "modelDesc" in str(e))

    # 3. mesh: auto end to end — the planned layout reaches live workers
    op.submit(_auto_job("auto", "v5e-8", 2, "__drive_planner__:_auto_worker"))
    got = op.wait_for_phase("TPUJob", "auto",
                            [JobConditionType.SUCCEEDED,
                             JobConditionType.FAILED], timeout=60)
    ann = json.loads(got.metadata.annotations[constants.ANNOTATION_PLANNED_MESH])
    check("auto job succeeds with the planned annotation",
          got.status.phase == JobConditionType.SUCCEEDED
          and ann["topology"] == "v5e-8" and ann["slices"] == 1
          and ann["axes"] == p.mesh.to_env(), json.dumps(ann))
    check("workers saw exactly the planned KUBEDL_MESH_AXES",
          len(SEEN["auto"]) == 2
          and all(m == ann["axes"] for m, _ in SEEN["auto"]),
          str(SEEN["auto"]))
    check("status.plan + Planned condition carry the verdict",
          got.status.plan is not None
          and got.status.plan.mesh == ann["axes"]
          and got.status.plan.candidates_evaluated > 0
          and any(c.type == JobConditionType.PLANNED
                  for c in got.status.conditions))
    check("Planned event + planner metrics exported",
          any(e.reason == "Planned" for e in op.store.list("Event", None))
          and "kubedl_tpu_planner_plans" in op.render_metrics()
          and "kubedl_tpu_planner_plan_ms" in op.render_metrics())

    # 4. an impossible model FAILS at admission — zero pods, no OOM loop
    op.submit(_auto_job("oom", "cpu-1", 1, "__drive_planner__:_auto_worker"))
    got = op.wait_for_phase("TPUJob", "oom",
                            [JobConditionType.SUCCEEDED,
                             JobConditionType.FAILED], timeout=60)
    check("infeasible model fails with PlanInfeasible and zero pods",
          got.status.phase == JobConditionType.FAILED
          and any(c.reason == "PlanInfeasible"
                  for c in got.status.conditions)
          and not [pp for pp in op.store.list("Pod", "default")
                   if pp.metadata.name.startswith("oom-")])

    # 5. live elastic resize re-plans: tiny model on cpu-1 slices, grow
    #    1 -> 2 mid-run; the new gang must carry the re-planned mesh
    # max_slices starts at 1 so the ElasticPolicy cannot auto-grow into
    # the free second slice before we read the 1-slice plan (a fresh job
    # has no cooldown stamp, so grow-at-RUNNING is otherwise immediate);
    # the explicit grow below raises the ceiling and the size together
    el = _auto_job("el", "cpu-1", 1, "__drive_planner__:_gated_worker",
                   model=MODEL_ZOO["tiny"])
    el.elastic = ElasticSpec(min_slices=1, max_slices=1,
                             cooldown_seconds=0.1)
    op.submit(el)
    op.wait_for_phase("TPUJob", "el", JobConditionType.RUNNING, timeout=60)
    got = op.store.get("TPUJob", "el")
    ann1 = json.loads(got.metadata.annotations[constants.ANNOTATION_PLANNED_MESH])
    base_dp = got.metadata.annotations[constants.ANNOTATION_ELASTIC_BASE_DP]
    check("elastic auto job planned at 1 slice",
          ann1["slices"] == 1 and base_dp == "1", json.dumps(ann1))

    def grow(j):
        j.elastic = ElasticSpec(min_slices=1, max_slices=2,
                                cooldown_seconds=0.1)
        j.num_slices = 2
    op.store.update_with_retry("TPUJob", "el", "default", grow)

    def replanned():
        g = op.store.try_get("TPUJob", "el")
        if g is None:
            return False
        a = json.loads(g.metadata.annotations.get(
            constants.ANNOTATION_PLANNED_MESH, "{}"))
        return (a.get("slices") == 2
                and len([pp for pp in op.store.list("Pod", "default")
                         if pp.metadata.name.startswith("el-")]) == 2)
    check("grow re-plans for 2 slices and restarts the gang",
          op.manager.wait(replanned, timeout=60))
    got = op.store.get("TPUJob", "el")
    ann2 = json.loads(got.metadata.annotations[constants.ANNOTATION_PLANNED_MESH])
    check("re-planned mesh spans the slices via the replica axis",
          ann2["axes"].startswith("replica=2") and ann2["axes"] != ann1["axes"]
          and got.status.plan.mesh == ann2["axes"], ann2["axes"])
    check("base DP degree pinned from the FIRST plan",
          got.metadata.annotations[constants.ANNOTATION_ELASTIC_BASE_DP]
          == base_dp)

    with open(_GATE["path"], "w") as f:
        f.write("done")
    got = op.wait_for_phase("TPUJob", "el",
                            [JobConditionType.SUCCEEDED,
                             JobConditionType.FAILED], timeout=60)
    planned_event = [e for e in op.store.list("Event", None)
                     if e.reason == "Planned"
                     and e.involved_name == "el"][0]
    check("job finishes clean; Planned event aggregated the re-plan",
          got.status.phase == JobConditionType.SUCCEEDED
          and planned_event.count == 2
          and "2xcpu-1" in planned_event.message,
          f"count={planned_event.count}")
    restarted = [m for m, _ in SEEN["elastic"]]
    check("restarted workers ran the re-planned mesh in DP units",
          ann2["axes"] in restarted
          and all(d == base_dp for _, d in SEEN["elastic"]),
          str(SEEN["elastic"]))
    probs = check_invariants(op)
    check("invariants hold after plan/fail/resize traffic", probs == [],
          str(probs))

# 6. the reconcile-loop overhead budget (same sweep tier-1 pins)
from scripts.scheduler_microbench import run_planner_microbench
mb = run_planner_microbench()
check("full catalog x zoo sweep within the 50 ms p95 budget",
      mb["within_budget"] and mb["plans"] > 0,
      f"p95={mb['plan_ms_p95']}ms over {mb['plans']} plans")

shutil.rmtree(tmp, ignore_errors=True)
print(f"\n{sum(ok)}/{len(ok)} checks passed")
sys.exit(0 if all(ok) else 1)
