"""Drive the silent-hang watchdog + async replicated checkpointing end to
end through the PUBLIC surface: a real Operator under an armed
`trainer.step_stall` FaultPlan wedges a real training step loop WITHOUT
the pod exiting; the watchdog classifies the hang from beacons riding the
kubelet heartbeat, fails the pod retryably (exit 137), stamps the
HangDetected condition, and the normal gang restart resumes from the
latest ASYNC checkpoint instead of step 0. Plus: fake-clock
classification (hang vs silent death vs straggler), and peer-replicated
restore after the local shard dir is deleted."""
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
os.environ["JAX_PLATFORMS"] = "cpu"

ok = []
def check(name, cond, detail=""):
    ok.append(bool(cond))
    print(("PASS" if cond else "FAIL"), name, detail)

import json

from kubedl_tpu.api import constants
from kubedl_tpu.api.types import JobConditionType, ReplicaType
from kubedl_tpu.chaos import FaultPlan, FaultSpec
from kubedl_tpu.core.nodes import NODE_NAMESPACE, NodeHeartbeater
from kubedl_tpu.core.objects import Container, EnvVar, Pod, PodPhase
from kubedl_tpu.core.store import ObjectStore
from kubedl_tpu.watchdog import WatchdogConfig, WatchdogController

tmp = tempfile.mkdtemp(prefix="kdl-watchdog-drive-")

# 1. fake-clock classification: hang (ts fresh, step frozen) fires
#    retryably; a healthy replica and a straggler never do
store = ObjectStore()
t = {"now": 1000.0}
hb = NodeHeartbeater(store, ["hostX"], clock=lambda: t["now"])
wd = WatchdogController(
    store, clock=lambda: t["now"],
    config=WatchdogConfig(multiplier=3.0, min_budget_seconds=5.0,
                          startup_grace_seconds=50.0),
)
for name in ("w0", "w1"):
    p = Pod()
    p.metadata.name = name
    p.metadata.labels = {constants.LABEL_JOB_NAME: "drill",
                         constants.LABEL_JOB_KIND: "TPUJob"}
    p.spec.containers.append(Container())
    p.spec.node_name = "hostX"
    p.status.phase = PodPhase.RUNNING
    store.create(p)
steps = {"w0": 0, "w1": 0}
def tick(advance, stamp=("w0", "w1")):
    """1s of fake time; advance some counters, re-stamp fresh ts for
    every name in `stamp` (a wedged loop's beacon thread keeps stamping)."""
    t["now"] += 1.0
    for name in advance:
        steps[name] += advance[name]
    for name in stamp:
        hb.announce_progress("hostX", f"default/{name}",
                             step=steps[name], ts=t["now"])
    hb.beat_once()
    wd.reconcile(NODE_NAMESPACE, "hostX")
for _ in range(8):          # both advance: w0 10 steps/s, w1 1 step/s
    tick({"w0": 10, "w1": 1})
check("straggler flagged observationally (no restart)",
      any(tr.straggler for tr in wd._tracks.values())
      and store.get("Pod", "w1").status.phase == PodPhase.RUNNING
      and wd.fired == {"hang": 0, "silent_death": 0})
for _ in range(8):          # w0 wedges: ts stays fresh, step frozen
    tick({"w1": 1}, stamp=("w0", "w1"))
w0 = store.get("Pod", "w0")
check("hang fires retryably past the EWMA budget",
      w0.status.phase == PodPhase.FAILED
      and w0.status.reason == "HangDetected"
      and w0.status.container_statuses[0].exit_code == 137
      and wd.fired["hang"] == 1)
for _ in range(8):          # w1's beacons stop entirely, pod still RUNNING
    t["now"] += 1.0
    wd.reconcile(NODE_NAMESPACE, "hostX")
check("silent death fires when beacons stop",
      store.get("Pod", "w1").status.phase == PodPhase.FAILED
      and wd.fired["silent_death"] == 1)

# 2. the acceptance drill: injected hang -> HangDetected -> gang restart
#    resumes from the latest ASYNC checkpoint
from kubedl_tpu.operator import Operator, OperatorOptions
from kubedl_tpu.runtime.executor import ThreadRuntime
from kubedl_tpu.training import entry as entry_mod
from tests.helpers import make_tpujob

opts = OperatorOptions(
    local_addresses=True,
    artifact_registry_root=os.path.join(tmp, "reg"),
    node_grace_seconds=3.0,              # heartbeat/beacon publish ~1s
    heartbeat_nodes=["hostX"],
    beacon_dir=os.path.join(tmp, "beacons"),
    watchdog_multiplier=3.0,
    watchdog_min_budget_seconds=1.0,
    watchdog_startup_grace_seconds=300.0,  # compile never trips it
)
cfg = {"model": "tiny", "steps": 6, "global_batch": 8, "seq_len": 32,
       "ckpt_every": 2}
# call 3 (step 3 of attempt 1, after the step-2 async save) wedges the
# loop without exiting; every other call pays 700ms so the watchdog
# observes real step spacing (the EWMA its hang budget derives from)
plan = FaultPlan(7, sites={"trainer.step_stall": [
    FaultSpec.nth(3), FaultSpec.latency(700.0, every=1),
]})
with plan, Operator(opts, runtime=ThreadRuntime()) as op:
    job = make_tpujob("hangjob", workers=1,
                      entrypoint="kubedl_tpu.training.entry:train_main")
    spec = job.spec.replica_specs[ReplicaType.WORKER]
    spec.template.spec.node_name = "hostX"
    main = spec.template.spec.containers[0]
    main.env.append(EnvVar("KUBEDL_TRAIN_CONFIG", json.dumps(cfg)))
    main.env.append(EnvVar(constants.ENV_CKPT_DIR, os.path.join(tmp, "ck")))
    op.submit(job)
    got = op.wait_for_phase(
        "TPUJob", "hangjob",
        [JobConditionType.SUCCEEDED, JobConditionType.FAILED], timeout=180)
    check("hung job recovers and SUCCEEDS",
          got.status.phase == JobConditionType.SUCCEEDED,
          f"phase={got.status.phase}")
    check("watchdog drove a gang restart",
          got.status.restart_count >= 1
          and op.metrics.watchdog_restarts.value(reason="hang") >= 1,
          f"restarts={got.status.restart_count}")
    check("HangDetected condition + event recorded",
          any(c.type == JobConditionType.HANG_DETECTED
              for c in got.status.conditions)
          and any(e.reason == "HangDetected"
                  for e in op.store.list("Event", None)))
    check("exactly the planned single wedge was injected",
          plan.faults("trainer.step_stall") == 1)
summary = entry_mod.LAST_SUMMARY or {}
check("retry resumed from the async checkpoint, not step 0",
      summary.get("start_step", 0) >= 2
      and summary.get("ckpt_async") is True,
      f"start_step={summary.get('start_step')}")

# 3. peer-replicated restore: local shard dir deleted, replica saves it
import jax

from kubedl_tpu.remote import RemoteStoreServer
from kubedl_tpu.training.checkpoint import (
    AsyncCheckpointer, restore_from_best)
from kubedl_tpu.api.topology import MeshSpec
from kubedl_tpu.models import llama
from kubedl_tpu.parallel.mesh import build_mesh
from kubedl_tpu.training.data import SyntheticTokens
from kubedl_tpu.training.trainer import TrainConfig, Trainer

mesh = build_mesh(MeshSpec({"data": 1}), jax.devices()[:1])
tcfg = TrainConfig(model=llama.TINY, global_batch=4, seq_len=16, steps=2)
trainer = Trainer(tcfg, mesh)
state, _ = trainer.fit(iter(SyntheticTokens(4, 16, llama.TINY.vocab_size)))
local = os.path.join(tmp, "peer-ck")
with RemoteStoreServer(os.path.join(tmp, "peer-root")) as srv:
    peer = f"{srv.base_url}/blobs/replicas/w0"
    with AsyncCheckpointer(local, peer_url=peer) as acp:
        acp.save(state, 2)
    check("completed save mirrored to the peer", acp.peer_pushes == 1)
    shutil.rmtree(local)  # the owning host's disk is gone
    restored = restore_from_best(local, trainer.init_state(), sources=[peer])
    check("restore succeeds from the peer replica after local loss",
          restored is not None
          and int(jax.device_get(restored["step"])) == 2)

shutil.rmtree(tmp, ignore_errors=True)
print(f"\n{sum(ok)}/{len(ok)} checks passed")
sys.exit(0 if all(ok) else 1)
