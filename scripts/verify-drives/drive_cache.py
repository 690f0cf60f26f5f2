"""Drive: persistent compile cache through the real operator path.

Two sequential single-worker TPUJobs run `python -m kubedl_tpu.training.entry`
as real subprocesses with the operator-injected KUBEDL_COMPILE_CACHE_DIR.
Job 1 (cold) populates the cache; job 2 (warm — the gang-restart shape)
must add zero entries and compile faster.
"""
import json, os, sys, tempfile, time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import pathlib; sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from kubedl_tpu.api.types import (
    JobConditionType, ReplicaSpec, ReplicaType, RestartPolicy,
)
from kubedl_tpu.core.objects import Container, EnvVar
from kubedl_tpu.operator import Operator, OperatorOptions
from kubedl_tpu.runtime.executor import SubprocessRuntime
from kubedl_tpu.utils.compile_cache import cache_entry_count
from kubedl_tpu.utils.invariants import check_invariants
from kubedl_tpu.workloads.tpujob import TPUJob

checks = []
def check(name, ok, detail=""):
    checks.append((name, ok))
    print(("PASS " if ok else "FAIL ") + name + (f" — {detail}" if detail else ""))

tmp = tempfile.mkdtemp(prefix="kdl-cache-drive-")
logs = os.path.join(tmp, "logs")
cache = os.path.join(tmp, "compile-cache")
cfg = {"model": "tiny", "steps": 3, "global_batch": 4, "seq_len": 32}

def run(op, name):
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
    log = os.path.join(logs, "default", f"{name}-worker-0.log")
    summary = None
    with open(log) as f:
        for line in f:
            if '"worker_summary"' in line:
                summary = json.loads(line)["worker_summary"]
    return got, summary

opts = OperatorOptions(
    local_addresses=True, pod_log_dir=logs,
    artifact_registry_root=os.path.join(tmp, "reg"),
    compile_cache_dir=cache,
)
with Operator(opts, runtime=SubprocessRuntime(logs)) as op:
    got1, s1 = run(op, "cold")
    check("cold job SUCCEEDED", got1.status.phase == JobConditionType.SUCCEEDED)
    check("cold summary parsed", s1 is not None)
    n1 = cache_entry_count(cache)
    check("cache populated by cold run", n1 > 0, f"{n1} entries")
    got2, s2 = run(op, "warm")
    check("warm job SUCCEEDED", got2.status.phase == JobConditionType.SUCCEEDED)
    n2 = cache_entry_count(cache)
    check("warm run added no cache entries", n2 == n1, f"{n1} -> {n2}")
    # tolerance: on the tiny CPU model both first steps are ~0.1s and the
    # comparison is scheduler noise (0.09 vs 0.10 observed on a loaded
    # 1-core box); the structural proof is the zero-new-entries check
    # above — this one only guards against gross recompiles
    check("warm first-step not slower (50ms tolerance)",
          s2["first_step_seconds"] < s1["first_step_seconds"] + 0.05,
          f"{s1['first_step_seconds']:.2f}s -> {s2['first_step_seconds']:.2f}s")
    bad = check_invariants(op)
    check("invariants green", not bad, str(bad))

failed = [n for n, ok in checks if not ok]
print(f"\n{len(checks) - len(failed)}/{len(checks)} checks passed")
sys.exit(1 if failed else 0)
