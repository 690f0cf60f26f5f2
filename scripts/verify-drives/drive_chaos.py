"""Drive the chaos layer end to end through the PUBLIC surface: a real
Operator under an armed FaultPlan (injected worker crashes -> gang
restarts, restart count == plan), seeded determinism, store-conflict
retries through the shared RetryPolicy, poison-pill quarantine
(Quarantined condition + metric + event), serving load shedding over
real HTTP (503 + Retry-After + shed counter on /metrics), and a torn
checkpoint save falling back to the last good step."""
import json
import os
import shutil
import sys
import tempfile
import threading
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
os.environ["JAX_PLATFORMS"] = "cpu"

ok = []
def check(name, cond, detail=""):
    ok.append(bool(cond))
    print(("PASS" if cond else "FAIL"), name, detail)

from kubedl_tpu import chaos
from kubedl_tpu.chaos import FaultPlan, FaultSpec

tmp = tempfile.mkdtemp(prefix="kdl-chaos-drive-")

# 1. determinism: same seed -> identical trace
def run_trace(seed):
    plan = FaultPlan(seed, sites={"x": [FaultSpec.prob(0.4, 30)]})
    with plan:
        for _ in range(30):
            try:
                chaos.check("x")
            except chaos.FaultInjected:
                pass
    return plan.trace_tuples()
check("same seed -> identical fault trace", run_trace(7) == run_trace(7))
check("different seed -> different trace", run_trace(7) != run_trace(8))

# 2. store conflicts ride the shared retry policy
from kubedl_tpu.core.store import Conflict, ObjectStore
from kubedl_tpu.workloads.tpujob import TPUJob
store = ObjectStore()
j = TPUJob(); j.metadata.name = "drive"
store.create(j)
with FaultPlan(1, sites={"store.update": [
        FaultSpec.first(3, exc=lambda s: Conflict(s))]}) as plan:
    got = store.update_with_retry(
        "TPUJob", "drive", "default",
        lambda o: o.metadata.labels.update({"hit": "yes"}))
check("update_with_retry survives 3 injected conflicts",
      got.metadata.labels.get("hit") == "yes"
      and plan.faults("store.update") == 3)

# 3. armed plan through a REAL operator: injected worker crashes ->
#    slice-granular gang restarts; restart count matches the plan
from kubedl_tpu.api.types import JobConditionType, ReplicaSpec, ReplicaType, RestartPolicy
from kubedl_tpu.core.objects import Container
from kubedl_tpu.operator import Operator, OperatorOptions
from kubedl_tpu.runtime.executor import ThreadRuntime

def _crashy(env):
    if chaos.should_fail("worker.crash"):
        raise SystemExit(137)
    return 0

sys.modules["__drive_chaos__"] = sys.modules[__name__]
opts = OperatorOptions(local_addresses=True,
                       artifact_registry_root=os.path.join(tmp, "reg"))
plan = FaultPlan(11, sites={"worker.crash": [FaultSpec.first(2)]})
with plan, Operator(opts, runtime=ThreadRuntime()) as op:
    job = TPUJob(); job.metadata.name = "chaos-e2e"
    spec = ReplicaSpec(replicas=1,
                       restart_policy=RestartPolicy.ON_FAILURE_SLICE)
    spec.template.spec.containers.append(
        Container(entrypoint="__drive_chaos__:_crashy"))
    job.spec.replica_specs[ReplicaType.WORKER] = spec
    op.submit(job)
    got = op.wait_for_phase(
        "TPUJob", "chaos-e2e",
        [JobConditionType.SUCCEEDED, JobConditionType.FAILED], timeout=60)
    check("job terminal under injected crash plan",
          got.status.phase == JobConditionType.SUCCEEDED,
          f"phase={got.status.phase}")
    check("restart count matches the plan",
          got.status.restart_count == 2 == plan.faults("worker.crash"),
          f"restarts={got.status.restart_count} faults={plan.faults('worker.crash')}")

# 4. poison-pill quarantine through a real operator's engine
opts2 = OperatorOptions(local_addresses=True,
                        artifact_registry_root=os.path.join(tmp, "reg2"))
with Operator(opts2, runtime=ThreadRuntime()) as op:
    job = TPUJob(); job.metadata.name = "poison"
    spec = ReplicaSpec(replicas=1,
                       restart_policy=RestartPolicy.ON_FAILURE_SLICE)
    spec.template.spec.containers.append(
        Container(entrypoint="__drive_chaos__:_crashy"))
    job.spec.replica_specs[ReplicaType.WORKER] = spec
    engine = op.engines["TPUJob"]
    engine.quarantine_budget = 3
    engine.reconcile_job = lambda j: (_ for _ in ()).throw(
        RuntimeError("poison pill"))
    op.submit(job)
    got = op.wait_for_phase(
        "TPUJob", "poison", [JobConditionType.QUARANTINED], timeout=30)
    check("poison job parked Quarantined",
          got.status.phase == JobConditionType.QUARANTINED
          and got.status.conditions[-1].reason == "ReconcileBudgetExhausted")
    check("quarantine observable (metric + event)",
          op.metrics.quarantined.value(kind="TPUJob") == 1.0
          and any(e.reason == "Quarantined"
                  for e in op.store.list("Event", None))
          and "kubedl_tpu_jobs_quarantined" in op.render_metrics())

# 5. serving load shedding over REAL HTTP: 503 + Retry-After + counter
from http.server import ThreadingHTTPServer
from kubedl_tpu.serving.server import LlamaEngine, make_handler
eng = LlamaEngine(preset="tiny", max_batch=2, max_seq=64, max_queue_depth=2)
srv = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(eng, "tiny"))
port = srv.server_address[1]
threading.Thread(target=srv.serve_forever, daemon=True).start()

codes, retry_afters = [], []
lock = threading.Lock()
barrier = threading.Barrier(12)
def hit(i):
    barrier.wait()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps({"prompt_ids": [i + 1], "max_tokens": 40}).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            with lock:
                codes.append(r.status)
    except urllib.error.HTTPError as e:
        with lock:
            codes.append(e.code)
            retry_afters.append(e.headers.get("Retry-After"))
threads = [threading.Thread(target=hit, args=(i,)) for i in range(12)]
for t in threads: t.start()
for t in threads: t.join(timeout=120)
shed = codes.count(503)
check("burst sheds boundedly over HTTP",
      len(codes) == 12 and shed >= 1 and codes.count(200) >= 1,
      f"200s={codes.count(200)} 503s={shed}")
check("503 carries Retry-After",
      retry_afters and all(ra and int(ra) >= 1 for ra in retry_afters),
      f"retry_afters={retry_afters[:3]}")
with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
    metrics_text = r.read().decode()
check("shed counter exported on /metrics",
      f"kubedl_tpu_serving_shed_requests {float(shed)}" in metrics_text
      and eng.stats()["shed"] == shed)
r = eng.generate([5], max_tokens=3)
check("engine alive after the storm", len(r["token_ids"]) == 3)
srv.shutdown(); eng.close()

# 6. torn checkpoint save -> restore falls back to last good step
import jax.numpy as jnp
import numpy as np
from kubedl_tpu.training.checkpoint import (
    latest_step, restore_checkpoint, save_checkpoint)
ckpt = os.path.join(tmp, "ckpt")
save_checkpoint(ckpt, {"step": jnp.asarray(1), "w": jnp.arange(4.0)}, 1)
try:
    with FaultPlan(3, sites={"checkpoint.torn": [FaultSpec.nth(1)]}):
        save_checkpoint(ckpt, {"step": jnp.asarray(2),
                               "w": jnp.arange(4.0) * 2}, 2)
    torn_raised = False
except chaos.FaultInjected:
    torn_raised = True
restored = restore_checkpoint(ckpt, {"step": jnp.asarray(0),
                                     "w": jnp.zeros(4)})
check("torn save detected; restore falls back to step 1",
      torn_raised and latest_step(ckpt) == 1
      and int(restored["step"]) == 1
      and np.allclose(np.asarray(restored["w"]), np.arange(4.0)))

shutil.rmtree(tmp, ignore_errors=True)
print(f"\n{sum(ok)}/{len(ok)} checks passed")
sys.exit(0 if all(ok) else 1)
