"""Drive control-plane crash recovery with a REAL SIGKILL across process
boundaries (docs/robustness.md "Crash recovery"):

1. a child process runs a WAL-backed Operator, brings two gang jobs to
   RUNNING (every pod appends its name to a shared launches.log), stages a
   third job mid-gang-create (PodGroup admitted, zero pods), then
   SIGKILLs ITSELF — no atexit, no cleanup, pods orphaned alive;
2. the parent restarts an Operator on the same WAL dir and asserts full
   convergence: every surviving pod adopted by (name, uid, pid) with ZERO
   duplicate launches (kubelet launch log), identical gang slice
   re-reservation, the mid-create job's pods created exactly once, and
   the whole recovery inside the time budget.

Run with `python scripts/verify-drives/drive_crash_recovery.py`
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

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
os.environ["JAX_PLATFORMS"] = "cpu"

from kubedl_tpu.api.topology import get_slice
from kubedl_tpu.api.types import JobConditionType
from kubedl_tpu.core.objects import PodPhase
from kubedl_tpu.gang.slice_scheduler import SliceInventory
from kubedl_tpu.operator import Operator, OperatorOptions
from kubedl_tpu.runtime.executor import SubprocessRuntime

RECOVERY_BUDGET_S = 30.0


def inventory():
    inv = SliceInventory()
    for s in ("s1", "s2", "s3"):
        inv.add_slice(s, "v5e-8")
    return inv


def sleep_cmd(launch_log):
    # every launch leaves a fingerprint: duplicate creates are visible as
    # duplicate lines no matter which operator incarnation launched them
    body = (
        "import os,time;"
        f"open({launch_log!r},'a').write(os.environ['KUBEDL_POD_NAME']+'\\n');"
        "time.sleep(180)"
    )
    return [sys.executable, "-c", body]


def running_pods(store):
    return {
        f"{p.metadata.namespace}/{p.metadata.name}": p.metadata.uid
        for p in store.list("Pod")
        if p.status.phase == PodPhase.RUNNING
    }


def child_main(wal_dir, launch_log, log_dir):
    opts = OperatorOptions(
        local_addresses=True, wal_dir=wal_dir, pod_log_dir=log_dir,
        artifact_registry_root=os.path.join(wal_dir, "..", "reg"),
    )
    op = Operator(opts, runtime=SubprocessRuntime(log_dir),
                  inventory=inventory())
    op.start()
    from tests.helpers import make_tpujob

    topo = get_slice("v5e-8")
    for name in ("job1", "job2"):
        op.submit(make_tpujob(name, workers=2, command=sleep_cmd(launch_log),
                              topology=topo))
        op.wait_for_phase("TPUJob", name, JobConditionType.RUNNING, timeout=30)
    assert op.manager.wait(lambda: len(running_pods(op.store)) == 4,
                           timeout=20)
    # stage job3 mid-gang-create: admitted PodGroup in the WAL, no pods
    op.manager.stop()
    job3 = make_tpujob("job3", workers=2, command=sleep_cmd(launch_log),
                       topology=topo)
    op.submit(job3)
    gang3 = op.gang.create_gang(job3)
    assert op.gang.try_admit(gang3)
    state = {
        "pods": running_pods(op.store),
        "gangs": {g.metadata.name: sorted(g.assigned_slices)
                  for g in op.store.list("PodGroup")},
        "launch_count": op.kubelet.launch_count,
    }
    print("STATE " + json.dumps(state), flush=True)
    os.kill(os.getpid(), signal.SIGKILL)  # the real thing: no teardown


def parent_main():
    ok = []

    def check(name, cond, detail=""):
        ok.append(bool(cond))
        print(("PASS" if cond else "FAIL"), name, detail)

    tmp = tempfile.mkdtemp(prefix="kdl-crash-drive-")
    wal_dir = os.path.join(tmp, "wal")
    launch_log = os.path.join(tmp, "launches.log")
    log_dir = os.path.join(tmp, "logs")
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", wal_dir,
         launch_log, log_dir],
        capture_output=True, text=True, timeout=120,
    )
    check("child died by SIGKILL", child.returncode == -signal.SIGKILL,
          f"rc={child.returncode} stderr={child.stderr[-400:]}")
    state_lines = [l for l in child.stdout.splitlines()
                   if l.startswith("STATE ")]
    check("child reported pre-kill state", len(state_lines) == 1)
    if not state_lines:
        return finish(ok, tmp)
    state = json.loads(state_lines[0][6:])
    check("child had 4 running pods, one gang staged mid-create",
          len(state["pods"]) == 4 and state["gangs"].get("job3-gang"))

    t0 = time.perf_counter()
    op = Operator(
        OperatorOptions(local_addresses=True, wal_dir=wal_dir,
                        pod_log_dir=log_dir,
                        artifact_registry_root=os.path.join(tmp, "reg2")),
        runtime=SubprocessRuntime(log_dir), inventory=inventory(),
    )
    check("store rehydrated from WAL",
          op.store.rehydrated and op.store.replayed_records > 0,
          f"{op.store.replayed_records} records")
    op.start()
    try:
        op.wait_for_phase("TPUJob", "job3", JobConditionType.RUNNING,
                          timeout=RECOVERY_BUDGET_S)
        converged = op.manager.wait(
            lambda: len(running_pods(op.store)) == 6,
            timeout=RECOVERY_BUDGET_S)
        elapsed = time.perf_counter() - t0
        check("reconverged to 6 running pods", converged)
        check(f"time-to-reconverge under {RECOVERY_BUDGET_S:.0f}s",
              elapsed < RECOVERY_BUDGET_S, f"{elapsed:.2f}s")
        after = running_pods(op.store)
        check("every surviving pod adopted with its original uid",
              all(after.get(k) == uid for k, uid in state["pods"].items()),
              str({k: (state["pods"][k], after.get(k))
                   for k in state["pods"] if after.get(k) != state["pods"][k]}))
        check("adopted_count == 4", op.kubelet.adopted_count == 4,
              str(op.kubelet.adopted_count))
        check("new incarnation launched ONLY job3's pods",
              op.kubelet.launch_count == 2, str(op.kubelet.launch_count))
        # a pod is RUNNING the moment its process spawns, but the
        # fingerprint line lands only once the subprocess executes its
        # first statement — poll for all 6 before judging uniqueness
        # (the invariant under test is ZERO DUPLICATES, not exec speed)
        deadline = time.perf_counter() + 15.0
        lines = []
        while time.perf_counter() < deadline:
            lines = open(launch_log).read().split()
            if len(lines) >= 6:
                break
            time.sleep(0.1)
        check("zero duplicate launches across both incarnations",
              len(lines) == 6 and len(set(lines)) == 6, str(sorted(lines)))
        gangs = {g.metadata.name: sorted(g.assigned_slices)
                 for g in op.store.list("PodGroup")}
        check("identical gang slice assignments", gangs == state["gangs"],
              f"{gangs} vs {state['gangs']}")
        repinned = all(
            sorted(op.inventory.owned_slices(
                f"{g.metadata.namespace}/{g.metadata.name}"))
            == sorted(g.assigned_slices)
            for g in op.store.list("PodGroup"))
        check("slices re-reserved in the fresh inventory", repinned)
        phases = {n: op.store.get("TPUJob", n).status.phase
                  for n in ("job1", "job2", "job3")}
        check("all jobs RUNNING after recovery",
              all(p == JobConditionType.RUNNING for p in phases.values()),
              str(phases))
        rendered = op.render_metrics()
        check("recovery metrics exported",
              "kubedl_tpu_pods_adopted 4.0" in rendered
              and "kubedl_tpu_wal_replayed_records" in rendered
              and "kubedl_tpu_recovery_duration_seconds" in rendered)
    finally:
        op.stop()  # kills the adopted orphans too
    return finish(ok, tmp)


def finish(ok, tmp):
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"\n{sum(ok)}/{len(ok)} checks passed")
    return 0 if all(ok) and ok else 1


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child_main(*sys.argv[2:5])
    else:
        sys.exit(parent_main())
