"""Drive: cross-replica sharded weight update through the real operator path.

Two single-worker TPUJobs run `python -m kubedl_tpu.training.entry` as
real subprocesses on an 8-virtual-device CPU mesh (pods inherit the
operator env's XLA_FLAGS): one with the default sharded update + overlap,
one pinned to the seed replicated path (shard_update=false). The worker
summaries must show the scattered layout compiled (shard_update true,
grad buckets planned, per-device optimizer-state bytes reduced vs the
replicated job) and the two loss trajectories must agree — same math,
placement-only change — end to end through entry.py's config plumbing.
"""
import json, os, sys, tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import pathlib; sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from kubedl_tpu.api.types import (
    JobConditionType, ReplicaSpec, ReplicaType, RestartPolicy,
)
from kubedl_tpu.core.objects import Container, EnvVar
from kubedl_tpu.operator import Operator, OperatorOptions
from kubedl_tpu.runtime.executor import SubprocessRuntime
from kubedl_tpu.utils.invariants import check_invariants
from kubedl_tpu.workloads.tpujob import TPUJob

checks = []
def check(name, ok, detail=""):
    checks.append((name, ok))
    print(("PASS " if ok else "FAIL ") + name + (f" — {detail}" if detail else ""))

tmp = tempfile.mkdtemp(prefix="kdl-shupd-drive-")
logs = os.path.join(tmp, "logs")
base_cfg = {"model": "tiny", "steps": 4, "global_batch": 8, "seq_len": 16,
            "grad_accum": 2, "log_every": 2}

def run(op, name, extra):
    cfg = dict(base_cfg); cfg.update(extra)
    job = TPUJob(); job.metadata.name = name
    spec = ReplicaSpec(replicas=1, restart_policy=RestartPolicy.ON_FAILURE_SLICE)
    spec.template.spec.containers.append(Container(
        command=[sys.executable, "-m", "kubedl_tpu.training.entry"],
        env=[EnvVar("KUBEDL_TRAIN_CONFIG", json.dumps(cfg)),
             EnvVar("PYTHONPATH", "/root/repo")],
    ))
    job.spec.replica_specs[ReplicaType.WORKER] = spec
    op.submit(job)
    got = op.wait_for_phase("TPUJob", name,
        [JobConditionType.SUCCEEDED, JobConditionType.FAILED], timeout=300)
    summary = None
    with open(os.path.join(logs, "default", f"{name}-worker-0.log")) as f:
        for line in f:
            if '"worker_summary"' in line:
                summary = json.loads(line)["worker_summary"]
    return got, summary

opts = OperatorOptions(
    local_addresses=True, pod_log_dir=logs,
    artifact_registry_root=os.path.join(tmp, "reg"),
)
with Operator(opts, runtime=SubprocessRuntime(logs)) as op:
    got_s, ss = run(op, "sharded", {})
    check("sharded-update job SUCCEEDED",
          got_s.status.phase == JobConditionType.SUCCEEDED)
    check("summary shows the scattered layout compiled",
          ss is not None and ss["shard_update"] and ss["overlap_comm"],
          json.dumps({k: ss.get(k) for k in
                      ("shard_update", "overlap_comm")} if ss else {}))
    check("grad buckets planned", ss["grad_buckets"] >= 1,
          f"{ss['grad_buckets']} buckets")
    check("loss logged on the log_every cadence (no per-step sync)",
          ss["log_every"] == 2 and len(ss["loss_log"]) >= 1,
          json.dumps(ss["loss_log"]))

    got_r, sr = run(op, "replicated", {"shard_update": False})
    check("replicated-baseline job SUCCEEDED",
          got_r.status.phase == JobConditionType.SUCCEEDED
          and sr is not None and not sr["shard_update"])
    check("optimizer state per device reduced vs replicated",
          ss["opt_state_bytes_per_device"] < sr["opt_state_bytes_per_device"],
          f"{ss['opt_state_bytes_per_device']} < "
          f"{sr['opt_state_bytes_per_device']} bytes")
    check("loss trajectory matches the replicated path",
          abs(ss["final_loss"] - sr["final_loss"]) < 1e-4
          and abs(ss["first_loss"] - sr["first_loss"]) < 1e-4,
          f"final {ss['final_loss']:.6f} vs {sr['final_loss']:.6f}")
    bad = check_invariants(op)
    check("invariants green", not bad, str(bad))

failed = [n for n, ok in checks if not ok]
print(f"\n{len(checks) - len(failed)}/{len(checks)} checks passed")
sys.exit(1 if failed else 0)
