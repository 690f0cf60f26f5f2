"""End-to-end: operator + real pod processes (reference analogue: the kind
e2e running a distributed TF mnist job, scripts/run_tf_test_job.sh)."""

import os
import sys
import time

import pytest

from kubedl_tpu.api import constants
from kubedl_tpu.api.types import JobConditionType, ModelVersionSpecRef, ReplicaType
from kubedl_tpu.lineage.types import ModelVersionPhase
from kubedl_tpu.operator import Operator, OperatorOptions
from kubedl_tpu.runtime.executor import SubprocessRuntime, ThreadRuntime

from tests.helpers import make_tpujob

def _phase_deadline(base: float) -> float:
    """CPU-adaptive wait_for_phase deadline: multi-process worker gangs
    (each a full python + jax import + compile) serialize on starved
    boxes, so a deadline sized for a multi-core CI host times out on a
    1-core one while the gang is still making progress. Scale the base
    deadline by how far below 4 cores the box sits (measured: the
    2-worker jax.distributed jobs finish in ~100s at 4 cores but need
    ~5x that wall time at 1 core)."""
    try:  # cgroup/affinity-aware (cpu_count ignores container quotas)
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    return base * (5 if cores < 2 else (2 if cores < 4 else 1))


CHECK_ENV = (
    "import os,sys;"
    "req=['KUBEDL_COORDINATOR_ADDRESS','KUBEDL_NUM_PROCESSES','KUBEDL_PROCESS_ID',"
    "'TPU_WORKER_HOSTNAMES','TPU_WORKER_ID'];"
    "missing=[k for k in req if k not in os.environ];"
    "sys.exit(1 if missing else 0)"
)


def test_subprocess_job_lifecycle(tmp_path):
    opts = OperatorOptions(
        local_addresses=True,
        pod_log_dir=str(tmp_path / "logs"),
        artifact_registry_root=str(tmp_path / "registry"),
    )
    with Operator(opts, runtime=SubprocessRuntime(str(tmp_path / "logs"))) as op:
        job = make_tpujob("e2e", workers=2, command=["python", "-c", CHECK_ENV])
        op.submit(job)
        got = op.wait_for_phase(
            "TPUJob", "e2e", [JobConditionType.SUCCEEDED, JobConditionType.FAILED],
            timeout=_phase_deadline(30),
        )
        assert got.status.phase == JobConditionType.SUCCEEDED, got.status.conditions
        # launch-delay metrics observed
        count, _ = op.metrics.first_pod_launch_delay.summary(kind="TPUJob")
        assert count == 1
        rendered = op.render_metrics()
        assert "kubedl_tpu_jobs_successful" in rendered


def _train_entry(env):
    """Thread-runtime entrypoint: writes a fake checkpoint to the model path."""
    import os
    import pathlib

    out = env.get(constants.ENV_MODEL_PATH, "")
    if out:
        pathlib.Path(out).mkdir(parents=True, exist_ok=True)
        (pathlib.Path(out) / f"shard-{env['KUBEDL_PROCESS_ID']}.bin").write_bytes(
            b"\x00" * 128
        )
    return 0


def test_thread_job_builds_model_version(tmp_path):
    out_dir = tmp_path / "model-out"
    opts = OperatorOptions(
        local_addresses=True, artifact_registry_root=str(tmp_path / "registry")
    )
    with Operator(opts, runtime=ThreadRuntime()) as op:
        job = make_tpujob(
            "train", workers=2, entrypoint=f"{__name__}:_train_entry"
        )
        job.spec.model_version = ModelVersionSpecRef(
            model_name="flagship", image_repo="models/flagship",
            storage_root=str(out_dir),
        )
        op.submit(job)
        got = op.wait_for_phase(
            "TPUJob", "train", [JobConditionType.SUCCEEDED, JobConditionType.FAILED],
            timeout=_phase_deadline(30),
        )
        assert got.status.phase == JobConditionType.SUCCEEDED
        # lineage: ModelVersion built into the artifact registry
        assert op.manager.wait(
            lambda: any(
                mv.phase == ModelVersionPhase.SUCCEEDED
                for mv in op.store.list("ModelVersion")
            ),
            timeout=10,
        )
        mv = op.store.list("ModelVersion")[0]
        assert op.artifact_registry.exists("models/flagship", mv.image_tag())
        model = op.store.get("Model", "flagship")
        assert model.latest_version == mv.metadata.name


def test_failed_process_marks_job_failed(tmp_path):
    opts = OperatorOptions(local_addresses=True,
                           artifact_registry_root=str(tmp_path / "r"))
    from kubedl_tpu.api.types import RestartPolicy

    with Operator(opts, runtime=SubprocessRuntime()) as op:
        job = make_tpujob(
            "boom", workers=1,
            command=["python", "-c", "import sys; sys.exit(7)"],
            restart_policy=RestartPolicy.EXIT_CODE,  # exit 7 = permanent
        )
        op.submit(job)
        got = op.wait_for_phase(
            "TPUJob", "boom", [JobConditionType.FAILED, JobConditionType.SUCCEEDED],
            timeout=_phase_deadline(30),
        )
        assert got.status.phase == JobConditionType.FAILED
        assert op.metrics.failed.value(kind="TPUJob") == 1


def test_workload_gate_parsing():
    from kubedl_tpu.workloads.registry import parse_workload_gate

    known = ["TPUJob", "TorchXLAJob", "MPIJob"]
    assert parse_workload_gate("*", known) == known
    assert parse_workload_gate("TPUJob", known) == ["TPUJob"]
    assert parse_workload_gate("-MPIJob", known) == ["TPUJob", "TorchXLAJob"]
    assert parse_workload_gate("TPUJob,MPIJob", known) == ["TPUJob", "MPIJob"]


def test_gang_restart_resumes_from_checkpoint(tmp_path):
    """VERDICT r1 #3 done-criterion: a worker dies retryably mid-training,
    the gang restarts, and the job completes having RESUMED (total trained
    steps < 2x the budget), proving slice-granular restart-from-checkpoint
    (SURVEY.md §7 hard-part b; reference restart machinery analogue:
    pkg/job_controller/pod.go:305-317)."""
    import json

    from kubedl_tpu.core.objects import EnvVar
    from kubedl_tpu.training import entry as entry_mod

    ckpt_dir = tmp_path / "ckpts"
    marker = tmp_path / "fault-fired"
    opts = OperatorOptions(
        local_addresses=True,
        pod_log_dir=str(tmp_path / "logs"),
        artifact_registry_root=str(tmp_path / "registry"),
    )
    cfg = {"model": "tiny", "steps": 8, "global_batch": 8, "seq_len": 32,
           "ckpt_every": 2}
    with Operator(opts, runtime=ThreadRuntime()) as op:
        job = make_tpujob(
            "resume", workers=1,
            entrypoint="kubedl_tpu.training.entry:train_main",
        )
        spec = job.spec.replica_specs[ReplicaType.WORKER]
        spec.template.spec.containers[0].env = [
            EnvVar("KUBEDL_TRAIN_CONFIG", json.dumps(cfg)),
            EnvVar("KUBEDL_CKPT_DIR", str(ckpt_dir)),
            EnvVar("KUBEDL_FAULT_ONCE_AT_STEP", "5"),
            EnvVar("KUBEDL_FAULT_MARKER", str(marker)),
        ]
        op.submit(job)
        got = op.wait_for_phase(
            "TPUJob", "resume",
            [JobConditionType.SUCCEEDED, JobConditionType.FAILED],
            timeout=120,
        )
        assert got.status.phase == JobConditionType.SUCCEEDED, got.status.conditions
        assert got.status.restart_count >= 1  # the fault actually fired
        assert marker.exists()
    summary = entry_mod.LAST_SUMMARY
    # the restarted attempt resumed from a saved step, not from 0
    assert summary["start_step"] >= 2, summary
    # and trained only the remainder: resumed steps + pre-fault steps < 2x
    assert summary["steps"] <= 8 - summary["start_step"], summary


REPO_ROOT = str(__import__("pathlib").Path(__file__).resolve().parents[1])

DIST_PSUM = (
    "import os, sys\n"
    f"sys.path.insert(0, {REPO_ROOT!r})\n"
    "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
    "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=1'\n"
    "from kubedl_tpu.parallel.mesh import initialize_from_env\n"
    "initialize_from_env()\n"
    "import jax\n"
    "import jax.numpy as jnp\n"
    "assert jax.process_count() == 2, jax.process_count()\n"
    "assert jax.device_count() == 2, jax.device_count()\n"
    "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
    "mesh = Mesh(jax.devices(), ('data',))\n"
    "rank = jax.process_index()\n"
    "local = jnp.ones((1,), jnp.float32) * (rank + 1)\n"
    "garr = jax.make_array_from_process_local_data(\n"
    "    NamedSharding(mesh, P('data')), local, global_shape=(2,))\n"
    "total = jax.jit(lambda x: x.sum(),\n"
    "    out_shardings=NamedSharding(mesh, P()))(garr)\n"
    "assert float(total) == 3.0, float(total)\n"
    "print('psum-ok rank', rank)\n"
)


def test_two_process_jax_distributed_rendezvous(tmp_path):
    """VERDICT r1 #7: two real OS processes do a jax.distributed.initialize
    rendezvous off the operator-injected env (coordinator address, process
    count/id) and run a cross-process global reduction — the operator's
    bootstrap wiring proven end to end, not just env-presence-checked
    (reference e2e bar: scripts/run_tf_test_job.sh)."""
    script = tmp_path / "dist_psum.py"
    script.write_text(DIST_PSUM)
    opts = OperatorOptions(
        local_addresses=True,
        pod_log_dir=str(tmp_path / "logs"),
        artifact_registry_root=str(tmp_path / "registry"),
    )
    with Operator(opts, runtime=SubprocessRuntime(str(tmp_path / "logs"))) as op:
        job = make_tpujob("dist2", workers=2,
                          command=[sys.executable, str(script)])
        op.submit(job)
        got = op.wait_for_phase(
            "TPUJob", "dist2",
            [JobConditionType.SUCCEEDED, JobConditionType.FAILED],
            timeout=_phase_deadline(120),
        )
        assert got.status.phase == JobConditionType.SUCCEEDED, [
            c.message for c in got.status.conditions
        ]
    logs = tmp_path / "logs" / "default"
    merged = "".join(p.read_text() for p in logs.glob("dist2-worker-*.log"))
    assert "psum-ok rank 0" in merged and "psum-ok rank 1" in merged, merged


def test_gang_release_nudges_queued_job(tmp_path):
    """VERDICT r1 #8: a queued job admits within one reconcile of a slice
    freeing (PodGroup-deletion nudge), not via the slow fallback poll."""
    from kubedl_tpu.api.topology import get_slice
    from kubedl_tpu.gang.slice_scheduler import SliceInventory

    inv = SliceInventory()
    inv.add_slice("s1", "v5e-8")
    opts = OperatorOptions(
        local_addresses=True,
        pod_log_dir=str(tmp_path / "logs"),
        artifact_registry_root=str(tmp_path / "registry"),
    )
    topo = get_slice("v5e-8")
    with Operator(opts, runtime=SubprocessRuntime(str(tmp_path / "logs")),
                  inventory=inv) as op:
        j1 = make_tpujob("holder", workers=2,
                         command=[sys.executable, "-c", "import time; time.sleep(4)"],
                         topology=topo)
        op.submit(j1)
        deadline = time.time() + 20
        while time.time() < deadline:
            pods = [p for p in op.store.list("Pod")
                    if p.metadata.labels.get(
                        "kubedl-tpu.io/job-name") == "holder"]
            if len(pods) == 2:
                break
            time.sleep(0.1)
        assert len(pods) == 2
        j2 = make_tpujob("waiter", workers=2,
                         command=[sys.executable, "-c", "print('ok')"],
                         topology=topo)
        op.submit(j2)
        time.sleep(1.0)
        w = op.store.get("TPUJob", "waiter")
        assert w.status.phase == JobConditionType.QUEUED
        got1 = op.wait_for_phase("TPUJob", "holder",
                                 [JobConditionType.SUCCEEDED], timeout=30)
        t_free = time.time()
        # admitted well inside the 5s fallback poll -> the nudge fired
        deadline = time.time() + 3.0
        admitted = False
        while time.time() < deadline:
            w = op.store.get("TPUJob", "waiter")
            if w.status.phase != JobConditionType.QUEUED:
                admitted = True
                break
            time.sleep(0.05)
        assert admitted, f"waiter still QUEUED {time.time() - t_free:.1f}s after slice freed"
        op.wait_for_phase("TPUJob", "waiter", [JobConditionType.SUCCEEDED],
                          timeout=30)


TRAIN_DIST = (
    "import os, sys\n"
    f"sys.path.insert(0, {REPO_ROOT!r})\n"
    "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
    "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=1'\n"
    "from kubedl_tpu.training.entry import train_main\n"
    "sys.exit(train_main())\n"
)


def test_shared_storage_two_worker_train_build_serve(tmp_path):
    """VERDICT r1 #6 done-criterion: a 2-worker (2-process jax.distributed)
    job writes sharded checkpoint output to a SHARED storage root, the
    ModelVersion build consumes it, and the serving engine loads the
    restored weights (reference union: storage_provider.go:1-35)."""
    import json

    import numpy as np

    from kubedl_tpu.core.objects import EnvVar
    from kubedl_tpu.lineage.types import ModelVersionPhase

    shared_root = tmp_path / "shared" / "out"
    script = tmp_path / "train_dist.py"
    script.write_text(TRAIN_DIST)
    opts = OperatorOptions(
        local_addresses=True,
        pod_log_dir=str(tmp_path / "logs"),
        artifact_registry_root=str(tmp_path / "registry"),
    )
    cfg = {"model": "tiny", "steps": 2, "global_batch": 4, "seq_len": 32}
    with Operator(opts, runtime=SubprocessRuntime(str(tmp_path / "logs"))) as op:
        job = make_tpujob("shared2", workers=2,
                          command=[sys.executable, str(script)])
        spec = job.spec.replica_specs[ReplicaType.WORKER]
        spec.template.spec.containers[0].env = [
            EnvVar("KUBEDL_TRAIN_CONFIG", json.dumps(cfg)),
        ]
        job.spec.model_version = ModelVersionSpecRef(
            model_name="shared-model", storage_root=str(shared_root),
            storage_provider="shared",
        )
        op.submit(job)
        got = op.wait_for_phase(
            "TPUJob", "shared2",
            [JobConditionType.SUCCEEDED, JobConditionType.FAILED],
            timeout=_phase_deadline(120),
        )
        assert got.status.phase == JobConditionType.SUCCEEDED, [
            c.message for c in got.status.conditions
        ]
        # both processes wrote their shard files into the shared root
        import glob as _glob

        shard_files = _glob.glob(str(shared_root / "step-*" / "shards-p*.npz"))
        pids = {f.rsplit("shards-", 1)[1] for f in shard_files}
        assert {"p0.npz", "p1.npz"} <= pids, shard_files
        # MV build consumed the shared artifact (re-read the job each
        # poll: the MV name now rides the success status write, but a
        # hedge against any stale snapshot keeps this loop robust)
        deadline = time.time() + 30
        mv = None
        while time.time() < deadline:
            mv_name = op.store.get(
                "TPUJob", "shared2", "default"
            ).status.model_version
            mv = (
                op.store.try_get("ModelVersion", mv_name, "default")
                if mv_name else None
            )
            if mv is not None and mv.phase in (
                ModelVersionPhase.SUCCEEDED, ModelVersionPhase.FAILED
            ):
                break
            time.sleep(0.3)
        assert mv is not None and mv.phase == ModelVersionPhase.SUCCEEDED, (
            getattr(mv, "message", None)
        )
        assert mv.storage_provider == "shared"
    # serving loads the trained weights from the shared root
    from kubedl_tpu.serving.server import LlamaEngine

    eng = LlamaEngine(preset="tiny", ckpt_dir=str(shared_root))
    import jax as _jax

    from kubedl_tpu.models import llama as _llama

    fresh = _llama.llama_init(_jax.random.PRNGKey(0), _llama.TINY)
    trained = eng.params
    diff = np.abs(
        np.asarray(_jax.device_get(trained["embed"]))
        - np.asarray(_jax.device_get(fresh["embed"]))
    ).max()
    assert diff > 0  # engine serves TRAINED weights, not the fresh init


def test_node_local_storage_rejects_cross_node_build(tmp_path):
    """Node-pinned artifacts must fail the build with a clear error when
    the builder is not co-located (the LocalStorage nodeName contract)."""
    from kubedl_tpu.lineage.storage import (
        NodeLocalProvider, SharedDirProvider, StorageError,
        get_storage_provider,
    )
    from kubedl_tpu.lineage.types import ModelVersion

    mv = ModelVersion(storage_root="/data/m", storage_provider="local",
                      node_name="host-7")
    with pytest.raises(StorageError):
        NodeLocalProvider().artifact_dir(mv, local_node="host-1")
    assert NodeLocalProvider().artifact_dir(mv, local_node="host-7") == "/data/m"
    # registry + aliases
    assert isinstance(get_storage_provider("nfs"), SharedDirProvider)
    assert isinstance(get_storage_provider("efs"), SharedDirProvider)
    assert isinstance(get_storage_provider(""), SharedDirProvider)
    with pytest.raises(StorageError):
        get_storage_provider("bogus")


def test_invariants_hold_through_full_lifecycle(tmp_path):
    """The consistency checker (control-plane sanitizer the reference
    lacks, SURVEY.md §5 'no -race') finds nothing after a busy scenario:
    gang contention + restart + success + deletion."""
    from kubedl_tpu.api.topology import get_slice
    from kubedl_tpu.gang.slice_scheduler import SliceInventory
    from kubedl_tpu.utils.invariants import check_invariants

    inv = SliceInventory()
    inv.add_slice("s1", "v5e-8")
    opts = OperatorOptions(
        local_addresses=True,
        pod_log_dir=str(tmp_path / "logs"),
        artifact_registry_root=str(tmp_path / "reg"),
    )
    topo = get_slice("v5e-8")
    with Operator(opts, runtime=SubprocessRuntime(str(tmp_path / "logs")),
                  inventory=inv) as op:
        marker = tmp_path / "flaky"
        j1 = make_tpujob("busy1", workers=2, topology=topo, command=[
            sys.executable, "-c",
            f"import os,sys; m={str(marker)!r}; d=os.path.exists(m); "
            "open(m,'w').write('x'); sys.exit(0 if d else 137)"])
        j2 = make_tpujob("busy2", workers=2, topology=topo,
                         command=[sys.executable, "-c", "print('ok')"])
        op.submit(j1)
        op.submit(j2)
        for name in ("busy1", "busy2"):
            got = op.wait_for_phase("TPUJob", name,
                                    [JobConditionType.SUCCEEDED,
                                     JobConditionType.FAILED], timeout=60)
            assert got.status.phase == JobConditionType.SUCCEEDED
        op.store.delete("TPUJob", "busy1")
        deadline = time.time() + 15
        while time.time() < deadline:
            violations = check_invariants(op)
            if not violations:
                break
            time.sleep(0.5)  # GC pass may still be collecting
        assert violations == [], violations


def test_invariants_catch_planted_inconsistencies(tmp_path):
    from kubedl_tpu.core.objects import OwnerRef, Pod
    from kubedl_tpu.utils.invariants import check_invariants

    opts = OperatorOptions(
        local_addresses=True,
        artifact_registry_root=str(tmp_path / "reg"),
    )
    from kubedl_tpu.runtime.executor import FakeRuntime

    op = Operator(opts, runtime=FakeRuntime())
    # plant: pod owned by a job that doesn't exist
    p = Pod()
    p.metadata.name = "ghost-pod"
    p.metadata.owner_refs.append(
        OwnerRef(kind="TPUJob", name="never-existed", uid="uid-x"))
    op.store.create(p)
    violations = check_invariants(op)
    assert any(v.startswith("I1") for v in violations), violations


TORCH_DDP = (
    "import os, sys\n"
    f"sys.path.insert(0, {REPO_ROOT!r})\n"
    "import torch\n"
    "import torch.distributed as dist\n"
    "rank = int(os.environ['RANK']); world = int(os.environ['WORLD_SIZE'])\n"
    "dist.init_process_group('gloo', init_method='env://',\n"
    "                        rank=rank, world_size=world)\n"
    "model = torch.nn.Linear(4, 1)\n"
    "for p in model.parameters():\n"
    "    dist.broadcast(p.data, src=0)\n"
    "opt = torch.optim.SGD(model.parameters(), lr=0.1)\n"
    "torch.manual_seed(rank)\n"
    "for _ in range(3):\n"
    "    x = torch.randn(8, 4); y = x.sum(dim=1, keepdim=True)\n"
    "    loss = ((model(x) - y) ** 2).mean()\n"
    "    opt.zero_grad(); loss.backward()\n"
    "    for p in model.parameters():\n"
    "        dist.all_reduce(p.grad); p.grad /= world\n"
    "    opt.step()\n"
    "flat = torch.cat([p.data.flatten() for p in model.parameters()])\n"
    "gathered = [torch.zeros_like(flat) for _ in range(world)]\n"
    "dist.all_gather(gathered, flat)\n"
    "assert all(torch.allclose(g, flat) for g in gathered), 'replicas diverged'\n"
    "print('ddp-ok rank', rank)\n"
    "dist.destroy_process_group()\n"
)


def test_pytorchjob_runs_real_torch_ddp(tmp_path):
    """BASELINE.md target 2 wiring proven with REAL torch.distributed:
    the operator-injected MASTER_ADDR/PORT/RANK/WORLD_SIZE drives a gloo
    process group across master + workers; allreduce keeps replicas in
    lockstep (asserted in-job via all_gather)."""
    pytest.importorskip("torch")  # torch is optional for the framework
    from kubedl_tpu.api.types import ReplicaSpec, RestartPolicy
    from kubedl_tpu.core.objects import Container
    from kubedl_tpu.workloads.pytorchjob import PyTorchJob

    script = tmp_path / "ddp.py"
    script.write_text(TORCH_DDP)
    opts = OperatorOptions(
        local_addresses=True,
        pod_log_dir=str(tmp_path / "logs"),
        artifact_registry_root=str(tmp_path / "reg"),
    )
    with Operator(opts, runtime=SubprocessRuntime(str(tmp_path / "logs"))) as op:
        job = PyTorchJob()
        job.metadata.name = "ddp"
        for rtype, n in ((ReplicaType.MASTER, 1), (ReplicaType.WORKER, 2)):
            spec = ReplicaSpec(replicas=n, restart_policy=RestartPolicy.ON_FAILURE)
            spec.template.spec.containers.append(
                Container(command=[sys.executable, str(script)])
            )
            job.spec.replica_specs[rtype] = spec
        op.submit(job)
        got = op.wait_for_phase(
            "PyTorchJob", "ddp",
            [JobConditionType.SUCCEEDED, JobConditionType.FAILED],
            timeout=120,
        )
        assert got.status.phase == JobConditionType.SUCCEEDED, [
            c.message for c in got.status.conditions
        ]
    logs = tmp_path / "logs" / "default"
    merged = "".join(p.read_text() for p in logs.glob("ddp-*.log"))
    for rank in (0, 1, 2):
        assert f"ddp-ok rank {rank}" in merged, merged[-2000:]


def test_suspend_resume_preserves_training_progress(tmp_path):
    """Suspend a LIVE training job mid-run (kueue-style preemption), then
    resume: the job completes having restored from its checkpoint rather
    than retraining (start_step > 0, total trained < 2x budget)."""
    import json

    from kubedl_tpu.core.objects import EnvVar
    from kubedl_tpu.training import entry as entry_mod

    ckpt_dir = tmp_path / "ckpts"
    opts = OperatorOptions(
        local_addresses=True,
        pod_log_dir=str(tmp_path / "logs"),
        artifact_registry_root=str(tmp_path / "reg"),
    )
    cfg = {"model": "tiny", "steps": 200, "global_batch": 8, "seq_len": 32,
           "ckpt_every": 2}
    with Operator(opts, runtime=ThreadRuntime()) as op:
        job = make_tpujob(
            "presus", workers=1,
            entrypoint="kubedl_tpu.training.entry:train_main",
        )
        spec = job.spec.replica_specs[ReplicaType.WORKER]
        spec.template.spec.containers[0].env = [
            EnvVar("KUBEDL_TRAIN_CONFIG", json.dumps(cfg)),
            EnvVar("KUBEDL_CKPT_DIR", str(ckpt_dir)),
        ]
        op.submit(job)
        # wait until at least one periodic checkpoint landed
        deadline = time.time() + 60
        while time.time() < deadline:
            if (ckpt_dir / "latest").exists():
                break
            time.sleep(0.2)
        assert (ckpt_dir / "latest").exists()

        def suspend(j):
            j.spec.run_policy.suspend = True

        op.store.update_with_retry("TPUJob", "presus", "default", suspend)
        got = op.wait_for_phase("TPUJob", "presus",
                                [JobConditionType.SUSPENDED], timeout=30)
        assert got.status.phase == JobConditionType.SUSPENDED
        pods = [p for p in op.store.list("Pod")
                if p.metadata.labels.get("kubedl-tpu.io/job-name") == "presus"]
        assert pods == []

        # shrink the remaining budget so the resumed run finishes quickly,
        # then unsuspend
        short = dict(cfg, steps=30)

        def resume(j):
            j.spec.run_policy.suspend = False
            j.spec.replica_specs[ReplicaType.WORKER].template.spec.\
                containers[0].set_env("KUBEDL_TRAIN_CONFIG", json.dumps(short))

        op.store.update_with_retry("TPUJob", "presus", "default", resume)
        got = op.wait_for_phase(
            "TPUJob", "presus",
            [JobConditionType.SUCCEEDED, JobConditionType.FAILED],
            timeout=120,
        )
        assert got.status.phase == JobConditionType.SUCCEEDED, [
            c.message for c in got.status.conditions
        ]
    summary = entry_mod.LAST_SUMMARY
    assert summary["start_step"] >= 2, summary  # resumed, not retrained
    assert summary["steps"] <= 30 - summary["start_step"], summary
