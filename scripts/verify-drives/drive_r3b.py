"""Drive: round-3 batch 2 — console static assets + charts over real HTTP,
HA leader election failover, remote blob store, Mars IngressRoute."""
import json, os, sys, tempfile, time, urllib.request

os.environ["JAX_PLATFORMS"] = "cpu"
import pathlib; sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from kubedl_tpu.api.types import JobConditionType, ReplicaSpec, ReplicaType, RestartPolicy
from kubedl_tpu.console import ConsoleServer
from kubedl_tpu.core.objects import Container
from kubedl_tpu.core.store import ObjectStore
from kubedl_tpu.operator import Operator, OperatorOptions
from kubedl_tpu.remote import RemoteStoreServer, list_blobs, put_blob
from kubedl_tpu.runtime.executor import SubprocessRuntime
from kubedl_tpu.utils.invariants import check_invariants
from kubedl_tpu.workloads.marsjob import MarsJob
from kubedl_tpu.workloads.registry import WORKLOAD_REGISTRY

checks = []
def check(name, ok, detail=""):
    checks.append((name, ok))
    print(("PASS " if ok else "FAIL ") + name + (f" — {detail}" if detail else ""))

tmp = tempfile.mkdtemp(prefix="kdl-r3b-")
logs = os.path.join(tmp, "logs")
store = ObjectStore()

def mkop(ident):
    return Operator(OperatorOptions(
        local_addresses=True, pod_log_dir=logs,
        artifact_registry_root=os.path.join(tmp, f"reg-{ident}"),
        leader_elect=True, leader_identity=ident, leader_lease_ttl=0.6,
    ), runtime=SubprocessRuntime(logs), store=store)

op1, op2 = mkop("op1"), mkop("op2")
op1.start()
t0 = time.time()
while time.time() - t0 < 5 and not op1.elector.is_leader:
    time.sleep(0.02)
op2.start()
time.sleep(0.8)
check("op1 leads, op2 follows",
      op1.elector.is_leader and not op2.elector.is_leader)
check("only leader reconciles", op1.manager._running and not op2.manager._running)

srv = ConsoleServer(op1)
srv.start()
host, port = srv.address

def get(path, raw=False):
    with urllib.request.urlopen(f"http://{host}:{port}{path}", timeout=10) as r:
        body = r.read()
        return body if raw else json.loads(body)

def submit(op, name):
    job = WORKLOAD_REGISTRY["TPUJob"]().object_factory()
    job.metadata.name = name
    spec = ReplicaSpec(replicas=1, restart_policy=RestartPolicy.ON_FAILURE)
    spec.template.spec.containers.append(Container(command=["true"]))
    job.spec.replica_specs[ReplicaType.WORKER] = spec
    op.submit(job)
    return op.wait_for_phase("TPUJob", name,
        [JobConditionType.SUCCEEDED, JobConditionType.FAILED], timeout=60)

got = submit(op1, "d1")
check("job under leader SUCCEEDED", got.status.phase == JobConditionType.SUCCEEDED)

# console: static split + charts fed by real launch metrics
idx = get("/", raw=True).decode()
check("index references static bundle",
      "/static/app.js" in idx and "/static/style.css" in idx)
app = get("/static/app.js", raw=True).decode()
check("charts view shipped", "VIEWS.charts" in app and "data/charts" in app)
charts = get("/api/v1/data/charts")["data"]
fp = charts["launch_delay"]["first_pod"]
check("launch-delay histogram populated",
      bool(fp) and fp[0]["total"] >= 1 and sum(fp[0]["counts"]) >= 1)
created = {r["labels"].get("kind"): r["value"] for r in charts["counters"]["created"]}
check("created counter per kind", created.get("TPUJob", 0) >= 1)

# failover: kill leader hard; follower takes over and completes work
op1.elector._stop.set(); op1.elector._thread.join(timeout=2); op1._on_deposed()
t0 = time.time()
while time.time() - t0 < 10 and not op2.elector.is_leader:
    time.sleep(0.05)
check("follower took over within TTL", op2.elector.is_leader,
      f"{time.time()-t0:.2f}s")
got2 = submit(op2, "d2")
check("job under new leader SUCCEEDED",
      got2.status.phase == JobConditionType.SUCCEEDED)

# Mars IngressRoute object
mars = MarsJob(); mars.metadata.name = "marsd"; mars.web_host = "mars.example.com"
for rt in (ReplicaType.SCHEDULER, ReplicaType.WEBSERVICE):
    sp = ReplicaSpec(replicas=1, restart_policy=RestartPolicy.ON_FAILURE)
    sp.template.spec.containers.append(Container(command=["sleep", "5"]))
    mars.spec.replica_specs[rt] = sp
op2.submit(mars)
t0 = time.time()
route = None
while time.time() - t0 < 15 and route is None:
    route = store.try_get("IngressRoute", "marsd-web")
    time.sleep(0.1)
check("Mars IngressRoute created", route is not None and
      route.host == "mars.example.com" and route.path == "/default/marsd")

# remote blob store over real HTTP
with RemoteStoreServer(os.path.join(tmp, "blob-root")) as rs:
    put_blob(rs.base_url, "m/x.bin", b"abc")
    check("remote blob roundtrip", list_blobs(rs.base_url, "m") == ["m/x.bin"])

bad = check_invariants(op2)
check("invariants green", not bad, str(bad))

srv.stop(); op1.stop(); op2.stop()
failed = [n for n, ok in checks if not ok]
print(f"\n{len(checks) - len(failed)}/{len(checks)} checks passed")
sys.exit(1 if failed else 0)
