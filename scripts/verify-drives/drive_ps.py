"""Drive the parameter-service tier across REAL process boundaries
(docs/elasticity.md "Parameter-service mode"):

1. the parent hosts a real ParameterService (WAL-backed shards) behind a
   real PSServer (HTTP); three SUBPROCESS workers run
   `python -m kubedl_tpu.training.entry` in ``train_mode: ps``, each
   writing progress beacons;
2. mid-run, worker-2 is SIGKILLed with NO notice and evicted the way a
   watchdog fire would evict it (in-flight discarded) — the surviving
   workers' beacons must KEEP ADVANCING, no gang restart, no stall;
3. then PS shard 0 is killed through the admin surface; the next push
   drives a lease-fenced failover (TTL wait + fencing-token bump + WAL
   replay) — survivors must advance straight through it;
4. at the end both survivors must have finished every step, trained
   (finite final loss, below the first loss), agree with each other
   within the pinned tolerance, and the service must report exactly one
   silent-death eviction and at least one shard failover.

Run with `python scripts/verify-drives/drive_ps.py`
(CPU only; sets JAX_PLATFORMS=cpu itself).
"""
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
os.environ["JAX_PLATFORMS"] = "cpu"

ok = []
def check(name, cond, detail=""):
    ok.append(bool(cond))
    print(("PASS" if cond else "FAIL"), name, detail)

from kubedl_tpu.api import constants
from kubedl_tpu.core.store import ObjectStore
from kubedl_tpu.models import llama
from kubedl_tpu.observability.metrics import PSMetrics
from kubedl_tpu.ps import ParameterService, PSConfig
from kubedl_tpu.ps.server import PSServer
from kubedl_tpu.training.trainer import TrainConfig, Trainer
from kubedl_tpu.watchdog.beacon import read_beacon

STEPS = 600
PUSH_EVERY = 5
#: survivors' final losses must agree within this band (the asynchrony
#: tolerance the bench pins against the sync baseline — bench.py PS_LOSS_TOL)
LOSS_BAND = 0.5
#: after each injected failure, every survivor must advance within this
STALL_BUDGET_S = 15.0

tmp = tempfile.mkdtemp(prefix="kdl-ps-drive-")
beacon_of = {i: os.path.join(tmp, f"beacon-{i}.json") for i in range(3)}
log_of = {i: os.path.join(tmp, f"worker-{i}.log") for i in range(3)}


def beacon_step(i):
    b = read_beacon(beacon_of[i])
    return int(b["step"]) if b else -1


def wait_until(cond, budget, what):
    deadline = time.time() + budget
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.2)
    print(f"TIMEOUT waiting for {what}")
    return False


def assert_survivors_advance(tag):
    """Both survivors' beacon step counters must strictly advance —
    the 'survivors never stall' contract."""
    marks = {i: beacon_step(i) for i in (0, 1)}
    for i in (0, 1):
        moved = wait_until(
            lambda i=i: beacon_step(i) > marks[i] or not (procs[i].poll() is None and beacon_step(i) < STEPS),
            STALL_BUDGET_S, f"worker-{i} advance after {tag}",
        )
        done = beacon_step(i) >= STEPS or procs[i].poll() is not None
        check(f"worker-{i} advances after {tag}",
              moved and (beacon_step(i) > marks[i] or done),
              f"step {marks[i]} -> {beacon_step(i)}")


# -- the service: WAL-backed shards, short lease so failover is quick ----
seed_trainer = Trainer(TrainConfig(
    model=llama.TINY, global_batch=4, seq_len=16, steps=1, seed=0,
))
init_params = Trainer._host_params(seed_trainer.init_state()["params"])
svc = ParameterService(
    init_params,
    PSConfig(num_shards=2, max_staleness=4, decay=0.5,
             wal_root=os.path.join(tmp, "wal"), fsync="off",
             lease_ttl=0.5),
    store=ObjectStore(), metrics=PSMetrics(),
)
server = PSServer(svc).start()
print(f"ps server at {server.addr}, params={len(init_params)} tensors")

train_cfg = {
    "model": "tiny", "global_batch": 4, "seq_len": 16, "steps": STEPS,
    "learning_rate": 3e-3, "train_mode": "ps",
}

procs = {}
for i in range(3):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "KUBEDL_TRAIN_CONFIG": json.dumps(train_cfg),
        constants.ENV_PS_ADDR: server.addr,
        constants.ENV_PROCESS_ID: str(i),
        constants.ENV_PS_PUSH_EVERY: str(PUSH_EVERY),
        constants.ENV_BEACON_FILE: beacon_of[i],
    })
    procs[i] = subprocess.Popen(
        [sys.executable, "-m", "kubedl_tpu.training.entry"],
        env=env, stdout=open(log_of[i], "w"), stderr=subprocess.STDOUT,
    )

try:
    # every worker past compile and into the loop, mid-run
    check("all workers reach step 20",
          wait_until(lambda: all(beacon_step(i) >= 20 for i in range(3)),
                     180.0, "all workers at step 20"),
          f"steps={[beacon_step(i) for i in range(3)]}")

    # -- failure 1: silent worker death (SIGKILL, no notice) -------------
    procs[2].send_signal(signal.SIGKILL)
    procs[2].wait(timeout=30)
    # the watchdog-fire path: evict the silently-dead member; its staged
    # in-flight contribution is discarded, survivors untouched
    svc.evict_silent_death("worker-2")
    assert_survivors_advance("worker-2 SIGKILL + eviction")

    # -- failure 2: PS shard death -> lease-fenced failover --------------
    req = urllib.request.Request(
        f"http://{server.addr}/ps/admin",
        data=json.dumps({"op": "fail_shard", "shard": 0}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        check("admin fail_shard accepted", resp.status == 200)
    assert_survivors_advance("shard-0 failover")

    # -- drain to completion --------------------------------------------
    for i in (0, 1):
        rc = None
        try:
            rc = procs[i].wait(timeout=300)
        except subprocess.TimeoutExpired:
            procs[i].kill()
        check(f"worker-{i} exits 0", rc == 0, f"rc={rc}")

    summaries = {}
    for i in (0, 1):
        with open(log_of[i]) as f:
            for line in f:
                if '"worker_summary"' in line:
                    try:
                        summaries[i] = json.loads(line)["worker_summary"]
                    except json.JSONDecodeError:
                        continue
    check("both survivors report a summary", set(summaries) == {0, 1})
    for i, s in sorted(summaries.items()):
        check(f"worker-{i} finished all steps",
              s.get("steps") == STEPS, f"steps={s.get('steps')}")
        check(f"worker-{i} pushed through both failures",
              s.get("ps_pushes", 0) > 0 and s.get("train_mode") == "ps",
              f"pushes={s.get('ps_pushes')} dropped={s.get('ps_dropped')} "
              f"rejected={s.get('ps_rejected')}")
        fl, ll = s.get("first_loss"), s.get("final_loss")
        check(f"worker-{i} trained",
              fl is not None and ll is not None and ll == ll and ll < fl,
              f"loss {fl} -> {ll}")
    if set(summaries) == {0, 1}:
        gap = abs(summaries[0]["final_loss"] - summaries[1]["final_loss"])
        check("survivor losses within pinned band",
              gap <= LOSS_BAND, f"gap={gap:.4f} tol={LOSS_BAND}")

    stats = svc.stats()
    check("exactly one silent-death eviction",
          svc.metrics.ps_evictions.value(reason="silent_death") == 1.0,
          f"evicted={stats['evicted']}")
    check("shard failover happened", stats["failovers"] >= 1,
          f"failovers={stats['failovers']}")
    check("shard versions advanced past the failover",
          all(v > 0 for v in stats["versions"]),
          f"versions={stats['versions']}")
finally:
    for p in procs.values():
        if p.poll() is None:
            p.kill()
    server.stop()
    shutil.rmtree(tmp, ignore_errors=True)

print(("OK" if all(ok) else "FAILED"), f"{sum(ok)}/{len(ok)} checks passed")
sys.exit(0 if all(ok) else 1)
