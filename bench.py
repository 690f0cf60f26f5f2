"""Benchmark: end-to-end TPUJob through the operator on real hardware.

Measures the BASELINE.md north stars in one run:
- tokens/sec/chip of the flagship Llama trainer (headline metric), and
- job-startup-to-first-step latency through the full control plane
  (submit -> gang admission -> pod launch -> first optimizer step).

The reference publishes no numbers (BASELINE.md): vs_baseline is therefore
reported against the explicit target we set ourselves — 10% MFU on the
bench model (vs_baseline = achieved_MFU / 0.10); on CPU (no TPU attached)
it falls back to 1.0.

Hard sanity gates (round-1 lesson: the bench printed a physically
impossible MFU of 538% — VERDICT.md): the run FAILS if MFU > 1, if the
step time beats the HBM param-read floor, if loss didn't decrease, or if
the TPU run didn't actually trace the pallas flash kernel into the hot
path. A failed gate exits nonzero rather than printing a lying number.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
"""

from __future__ import annotations

import json
import math
import os
import sys
import time


def bench_control_plane() -> dict:
    """BASELINE.md targets 1-3: launch-delay latency through the full
    control plane for the reference's own workload kinds, measured by the
    same first/all-pods histograms the reference instruments
    (pkg/metrics/job_metrics.go:139-194) — and the jobs run REAL
    frameworks, matching the reference's e2e bar (a real distributed TF
    mnist job, scripts/run_tf_test_job.sh), not env asserts:

    - TFJob: 2 workers each training the MNIST-class convnet to >=90%
      held-out accuracy, consuming the injected TF_CONFIG
      (examples/mnist_convnet.py --require-tf-config). Forced onto CPU
      JAX so the pods never contend for the chip the headline holds.
    - PyTorchJob: master + 3 workers training a REAL ResNet-class conv
      net under torch DistributedDataParallel (gloo) — loss-decrease and
      bit-identical-replica assertions in-job
      (examples/torch_ddp_resnet.py; BASELINE target 2's shape).
    - MPIJob: the launcher does what mpirun would — parses the
      materialized hostfile, fans one process per slot out through the
      rsh agent, and a REAL gloo allreduce runs across them with the
      reduced value asserted (examples/mpi_allreduce.py; BASELINE
      target 3's Horovod-shape contract). Workers idle as the rsh
      targets, exactly like the reference's sshd-style worker pods.
    """
    import tempfile

    from kubedl_tpu.api.types import (
        JobConditionType, ReplicaSpec, ReplicaType, RestartPolicy,
    )
    from kubedl_tpu.core.objects import Container, EnvVar
    from kubedl_tpu.operator import Operator, OperatorOptions
    from kubedl_tpu.runtime.executor import SubprocessRuntime
    from kubedl_tpu.workloads.mpijob import MPIJob
    from kubedl_tpu.workloads.pytorchjob import PyTorchJob
    from kubedl_tpu.workloads.tfjob import TFJob

    repo = os.path.dirname(os.path.abspath(__file__))

    def add(job, rtype, n, argv, env=()):
        spec = ReplicaSpec(replicas=n, restart_policy=RestartPolicy.ON_FAILURE)
        c = Container(command=argv)
        c.env.extend(EnvVar(k, v) for k, v in env)
        spec.template.spec.containers.append(c)
        job.spec.replica_specs[rtype] = spec

    py = sys.executable
    # subprocess pods inherit this process's env; pin them to CPU JAX so
    # real training in the control-plane bench never touches the chip
    cpu_env = (("JAX_PLATFORMS", "cpu"),)
    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        logs = os.path.join(tmp, "logs")
        opts = OperatorOptions(
            local_addresses=True, pod_log_dir=logs,
            artifact_registry_root=os.path.join(tmp, "reg"),
        )
        mnist = os.path.join(repo, "examples", "mnist_convnet.py")
        ddp_py = os.path.join(repo, "examples", "torch_ddp_resnet.py")
        mpi_py = os.path.join(repo, "examples", "mpi_allreduce.py")
        import importlib.util

        have_torch = importlib.util.find_spec("torch") is not None
        workloads = {}
        with Operator(opts, runtime=SubprocessRuntime(logs)) as op:
            tf = TFJob(); tf.metadata.name = "b-tf"
            if os.path.exists(mnist):
                workloads["TFJob"] = "mnist-convnet>=90%acc"
                add(tf, ReplicaType.WORKER, 2,
                    [py, mnist, "--steps", "80", "--require-tf-config"],
                    env=cpu_env)
            else:  # installed-wheel/image runs without examples/ on disk
                workloads["TFJob"] = "env-assert (examples/ not shipped)"
                add(tf, ReplicaType.WORKER, 2,
                    [py, "-c",
                     "import os, json;"
                     "json.loads(os.environ['TF_CONFIG'])['cluster']['worker']"])
            pt = PyTorchJob(); pt.metadata.name = "b-pt"
            if have_torch and os.path.exists(ddp_py):
                workloads["PyTorchJob"] = "torch-ddp-resnet loss-decrease"
                ddp = [py, ddp_py]
            else:
                workloads["PyTorchJob"] = "env-assert (torch/examples absent)"
                ddp = [py, "-c",
                       "import os; os.environ['MASTER_ADDR']; os.environ['RANK']"]
            add(pt, ReplicaType.MASTER, 1, ddp)
            add(pt, ReplicaType.WORKER, 3, ddp)
            mpi = MPIJob(); mpi.metadata.name = "b-mpi"
            if have_torch and os.path.exists(mpi_py):
                workloads["MPIJob"] = "rsh-fanout gloo-allreduce"
                add(mpi, ReplicaType.LAUNCHER, 1, [py, mpi_py])
            else:
                workloads["MPIJob"] = "hostfile-contract (torch absent)"
                add(mpi, ReplicaType.LAUNCHER, 1,
                    ["bash", "-c", 'test -s "$OMPI_MCA_orte_default_hostfile"'])
            add(mpi, ReplicaType.WORKER, 2, ["sleep", "30"])
            for job in (tf, pt, mpi):
                op.submit(job)
            for job in (tf, pt, mpi):
                got = op.wait_for_phase(
                    job.KIND, job.metadata.name,
                    [JobConditionType.SUCCEEDED, JobConditionType.FAILED],
                    timeout=300,
                )
                ok = got.status.phase == JobConditionType.SUCCEEDED
                n1, s1 = op.metrics.first_pod_launch_delay.summary(kind=job.KIND)
                na, sa = op.metrics.all_pods_launch_delay.summary(kind=job.KIND)
                out[job.KIND] = {
                    "succeeded": ok,
                    "workload": workloads[job.KIND],
                    "first_pod_launch_s": round(s1 / n1, 3) if n1 else None,
                    "all_pods_launch_s": round(sa / na, 3) if na else None,
                }
    return out


def bench_shards() -> dict:
    """Control-plane scale round (BENCH_r18_shards.json): the 10k-job /
    100k-pod churn replay from kubedl_tpu/shards/churn.py, 1-shard vs
    4-shard arms with the PER-SHARD worker pool held fixed (2 — the
    scale-out comparison: adding a shard adds an owner with the standard
    worker config, exactly like adding an operator replica), measuring
    end-to-end p99 reconcile latency (watch event enqueued -> reconcile
    done, steady-state window; execution duration and queue wait are
    broken out per arm) and submit->pod_launch time-to-launch straight
    off the PR 14 milestone traces. Arms run with a 2ms WAL commit
    floor modeling an etcd-class durable medium (this host's
    page-cache-backed fsync commits in ~0.1ms, which no production
    control plane gets to assume): commit cost is exactly what a
    sharded log parallelizes — one WAL serializes every write in the
    process behind one fsync stream, four fenced WALs overlap four. A
    third equal-total-threads control arm (1 shard x 8 workers) is
    reported but not gated: it shows threads cannot buy back a
    serialized log (same jobs/s as 1x2) — the log itself has to shard,
    and with it come the separate owners, fencing, and independent
    failure domains scripts/verify-drives/drive_shards.py exercises.
    Gates: the 4-shard arm must beat the fixed-config 1-shard arm on
    BOTH p99 reconcile latency and median time-to-launch, and every arm
    must complete every job."""
    import shutil
    import tempfile

    from kubedl_tpu.shards.churn import run_churn

    jobs = int(os.environ.get("KUBEDL_BENCH_SHARD_JOBS", "10000"))
    pods_per_job = 10
    arms = {}
    for label, shards, workers_per_shard in (
        ("1_shard", 1, 2),
        ("4_shard", 4, 2),
        ("1_shard_equal_threads", 1, 8),
    ):
        wal = tempfile.mkdtemp(prefix=f"kubedl-bench-shards{shards}-")
        try:
            arms[label] = run_churn(
                shards=shards, jobs=jobs, pods_per_job=pods_per_job,
                wal_dir=wal, workers_per_shard=workers_per_shard,
                wave=500, fsync_floor_ms=2.0, stall_timeout=300.0,
            )
        finally:
            shutil.rmtree(wal, ignore_errors=True)
    one, four = arms["1_shard"], arms["4_shard"]
    complete = all(a["completed"] == jobs for a in arms.values())
    p99_better = four["reconcile_p99_ms"] < one["reconcile_p99_ms"]
    launch_better = four["launch_p50_ms"] < one["launch_p50_ms"]
    return {
        "jobs": jobs,
        "pod_churn": jobs * pods_per_job,
        "arms": arms,
        "reconcile_p99_speedup": round(
            one["reconcile_p99_ms"] / max(four["reconcile_p99_ms"], 1e-9), 2
        ),
        "median_launch_speedup": round(
            one["launch_p50_ms"] / max(four["launch_p50_ms"], 1e-9), 2
        ),
        "throughput_speedup": round(
            four["jobs_per_s"] / max(one["jobs_per_s"], 1e-9), 2
        ),
        "gates": {
            "all_jobs_complete": complete,
            "p99_reconcile_improves": p99_better,
            "median_launch_improves": launch_better,
        },
        "ok": complete and p99_better and launch_better,
    }


def bench_cp_scale() -> dict:
    """Control-plane scaling-efficiency round (BENCH_r19_cp_scale.json):
    the same 10k-job / 100k-pod churn replay as bench_shards, with the
    PR 19 machinery on — WAL group commit (``fsync="group"`` with an 18ms
    batch window, identical ack-durability to ``"always"``: a writer is
    only acknowledged after the batched fsync covering its record),
    workqueue burst coalescing (20ms window), and batched gang
    create/delete — run
    at 1/2/4/8 shards with offered load and per-shard worker pool held
    fixed (wave=80, 2 workers/shard, 2ms commit floor). BENCH_r18
    measured the ceiling this round removes: per-append fsyncs made every
    arm complete at the same 88.8 jobs/s (220,000 fsyncs for 220,000
    appends) and queue wait was 99.9% of reconcile latency. Gates, all on
    the 4-shard arm vs r18's measured values: >= 2x the 1-shard arm's
    jobs/s at equal offered load (r18: 1.0x), queue_wait_p99 <= 1/5 of
    r18's 10844.998ms, wal_fsyncs <= wal_appends/20 (r18: ratio 1), and
    every arm completes every job. The 8-shard arm is reported (not
    gated) to place the next ceiling honestly: one-process shards share
    the GIL, so scaling flattens once reconcile CPU saturates a core —
    beyond that the shards have to leave the process (ROADMAP multi-
    operator federation)."""
    import shutil
    import tempfile

    from kubedl_tpu.shards.churn import run_churn

    jobs = int(os.environ.get("KUBEDL_BENCH_CP_JOBS", "10000"))
    pods_per_job = 10
    r18_queue_wait_p99_ms = 10844.998  # BENCH_r18_shards.json, 4_shard arm
    arms = {}
    for shards in (1, 2, 4, 8):
        wal = tempfile.mkdtemp(prefix=f"kubedl-bench-cp{shards}-")
        try:
            arms[f"{shards}_shard"] = run_churn(
                shards=shards, jobs=jobs, pods_per_job=pods_per_job,
                wal_dir=wal, workers_per_shard=2,
                wave=80, fsync_floor_ms=2.0, stall_timeout=300.0,
                wal_fsync="group", group_window_ms=18.0, coalesce_ms=20.0,
            )
        finally:
            shutil.rmtree(wal, ignore_errors=True)
    one, four = arms["1_shard"], arms["4_shard"]
    complete = all(a["completed"] == jobs for a in arms.values())
    speedup = four["jobs_per_s"] / max(one["jobs_per_s"], 1e-9)
    fsync_ratio = four["wal_appends"] / max(four["wal_fsyncs"], 1)
    gates = {
        "all_jobs_complete": complete,
        "throughput_4x1_at_least_2x": speedup >= 2.0,
        "queue_wait_p99_fifth_of_r18": (
            four["queue_wait_p99_ms"] <= r18_queue_wait_p99_ms / 5.0
        ),
        "fsyncs_at_most_appends_over_20": fsync_ratio >= 20.0,
    }
    return {
        "jobs": jobs,
        "pod_churn": jobs * pods_per_job,
        "arms": arms,
        "throughput_speedup_4x1": round(speedup, 2),
        "scaling_efficiency": {
            label: round(
                a["jobs_per_s"] / max(one["jobs_per_s"], 1e-9)
                / a["shards"], 2,
            )
            for label, a in arms.items()
        },
        "fsync_amortization_4_shard": round(fsync_ratio, 1),
        "r18_queue_wait_p99_ms": r18_queue_wait_p99_ms,
        "gates": gates,
        "ok": all(gates.values()),
    }


def bench_federation() -> dict:
    """Multi-operator federation round (BENCH_r20_federation.json): the
    cp_scale churn replay with the shards spread across real operator
    PROCESSES instead of one GIL. Two arms:

    - ``fed_4proc``: 4 member processes share one 8-shard WAL/lease root
      (2 shards each, disjoint static plan, per-shard file leases +
      fenced WAL writers); each submits only the jobs out of the same
      global 10k-job sequence that route to its shards, with cp_scale's
      offered load and worker pool held fixed fleet-wide (wave 80 -> 20
      per process, 2 workers/shard, 2ms commit floor, 18ms group window,
      20ms coalesce). Gate: aggregate jobs/s beats BENCH_r19's 8-shard
      in-process arm (128.9 — the measured GIL ceiling cp_scale's
      docstring promised federation would remove), and every member
      completes every one of its jobs.
    - ``member_kill``: 3 full FederationMember processes (heartbeats,
      staggered standby campaigns, WAL tails) over a 6-shard root churn
      a smaller job set; once the seeded victim has made progress the
      parent SIGKILLs it mid-churn. Gates: every shard lease lands on a
      survivor within the takeover budget (ttl + rank-staggered standby
      delay + retry beat, with slop), the survivors drain the ENTIRE
      churn including the victim's orphaned jobs (remaining==0 across
      owned shards), and the shared launch ledger — a line per pod
      appended only after the durable create — contains zero duplicate
      pod names: rehydrate-then-adopt meant takeover never relaunched a
      durably-created pod, and fencing meant the dead member's half-sent
      wave could not land after its lease expired.
    """
    import shutil
    import subprocess
    import tempfile

    from kubedl_tpu.federation.rebalance import plan_assignment
    from kubedl_tpu.shards.fencing import (
        SHARD_LEASE_NAMESPACE,
        FileLeaseStore,
        shard_lease_name,
    )

    jobs = int(os.environ.get("KUBEDL_BENCH_FED_JOBS", "10000"))
    pods_per_job = 10
    r19_8shard_jobs_per_s = 128.9  # BENCH_r19_cp_scale.json, 8_shard arm

    def _spawn(cfg: dict) -> subprocess.Popen:
        return subprocess.Popen(
            [sys.executable, "-m", "kubedl_tpu.federation.bench_worker",
             json.dumps(cfg)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )

    # --- arm 1: 4-process federated churn over one 8-shard root -------
    shards = 8
    members = [f"fed-{c}" for c in "abcd"]
    plan = plan_assignment(shards, members)
    root = tempfile.mkdtemp(prefix="kubedl-bench-fed4-")
    procs = []
    try:
        procs = [
            _spawn({
                "mode": "churn",
                "churn": {
                    "shards": shards, "jobs": jobs,
                    "pods_per_job": pods_per_job,
                    "wal_dir": os.path.join(root, "wal"),
                    "workers_per_shard": 2, "wave": 20,
                    "fsync_floor_ms": 2.0, "stall_timeout": 300.0,
                    "wal_fsync": "group", "group_window_ms": 18.0,
                    "coalesce_ms": 20.0,
                    "lease_dir": os.path.join(root, "leases"),
                    "identity": m, "own": plan[m], "standby": [],
                    "lease_ttl": 5.0, "only_owned_jobs": True,
                },
            })
            for m in members
        ]
        outs = [p.communicate(timeout=600)[0] for p in procs]
        rcs = [p.returncode for p in procs]
        member_results = [json.loads(o.strip().splitlines()[-1]) for o in outs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        shutil.rmtree(root, ignore_errors=True)
    fed_completed = sum(r["completed"] for r in member_results)
    fed_elapsed = max(r["elapsed_s"] for r in member_results)
    fed_jobs_per_s = round(fed_completed / max(fed_elapsed, 1e-9), 1)
    fed = {
        "processes": len(members),
        "shards": shards,
        "plan": plan,
        "jobs": jobs,
        "pod_churn": jobs * pods_per_job,
        "completed": fed_completed,
        "elapsed_s": round(fed_elapsed, 3),
        "jobs_per_s": fed_jobs_per_s,
        "reconcile_p99_ms": max(
            r["reconcile_p99_ms"] for r in member_results
        ),
        "queue_wait_p99_ms": max(
            r["queue_wait_p99_ms"] for r in member_results
        ),
        "members": member_results,
        "worker_exit_codes": rcs,
    }

    # --- arm 2: seeded member SIGKILL under churn ----------------------
    kill_jobs = int(os.environ.get(
        "KUBEDL_BENCH_FED_KILL_JOBS", str(max(300, jobs // 10))
    ))
    kshards = 6
    lease_ttl = 1.0
    kill_members = ["fed-ka", "fed-kb", "fed-kc"]
    seed = 20
    victim = kill_members[seed % len(kill_members)]
    # replicate each member's static share of the global job sequence so
    # the drain gate knows how many jobs SHOULD exist: survivors submit
    # their full planned shares; the victim's share is frozen at the
    # kill point (nobody resubmits for the dead — takeover only drains
    # what the victim durably created)
    from kubedl_tpu.shards.shardmap import ShardMap

    kplan = plan_assignment(kshards, kill_members)
    shard_owner = {i: m for m, ss in kplan.items() for i in ss}
    smap = ShardMap(kshards)
    share = {m: 0 for m in kill_members}
    for i in range(kill_jobs):
        share[shard_owner[smap.lookup(f"default/fed-{i:05d}")]] += 1
    takeover_budget_s = lease_ttl * 4 + 2.0
    root = tempfile.mkdtemp(prefix="kubedl-bench-fedkill-")
    kprocs = {}
    try:
        lease_dir = os.path.join(root, "leases")
        launch_log = os.path.join(root, "launches.log")
        stop_path = os.path.join(root, "stop")
        status = {m: os.path.join(root, f"status-{m}.json") for m in kill_members}
        for m in kill_members:
            kprocs[m] = _spawn({
                "mode": "member", "identity": m, "peers": kill_members,
                "shards": kshards, "lease_ttl": lease_ttl,
                "jobs": kill_jobs, "pods_per_job": pods_per_job,
                "wal_dir": os.path.join(root, "wal"),
                "lease_dir": lease_dir, "launch_log": launch_log,
                "status_path": status[m], "stop_path": stop_path,
                "wave": 25, "group_window_ms": 5.0, "coalesce_ms": 10.0,
            })

        def _read_status(m):
            try:
                with open(status[m]) as fh:
                    return json.loads(fh.read())
            except (OSError, ValueError):
                return None

        def _holders():
            backend = FileLeaseStore(lease_dir)
            out = {}
            for i in range(kshards):
                lease = backend.try_get(
                    "Lease", shard_lease_name(i), SHARD_LEASE_NAMESPACE
                )
                out[i] = lease.holder if lease is not None else None
            return out

        # wait for the victim to own its planned shards and make real
        # progress — the seeded kill point is mid-churn, not at startup
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            st = _read_status(victim)
            if st and st["completed"] >= max(10, kill_jobs // 20):
                break
            time.sleep(0.05)
        victim_frozen = _read_status(victim) or {}
        kprocs[victim].kill()  # SIGKILL: no release, leases must EXPIRE
        t_kill = time.monotonic()
        kprocs[victim].wait()

        survivors = [m for m in kill_members if m != victim]
        reconverge_s = None
        while time.monotonic() - t_kill < 60.0:
            h = _holders()
            if all(h[i] in survivors for i in range(kshards)):
                reconverge_s = round(time.monotonic() - t_kill, 3)
                break
            time.sleep(0.02)

        # survivors must drain the whole churn, the victim's durably
        # created orphans included: full planned shares submitted, every
        # shard owned by a survivor, zero live jobs left anywhere
        drained = False
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            sts = {m: _read_status(m) for m in survivors}
            if all(st is not None for st in sts.values()):
                owned = set()
                for st in sts.values():
                    owned.update(st["owned"])
                if (all(sts[m]["submitted"] >= share[m] for m in survivors)
                        and owned == set(range(kshards))
                        and sum(st["remaining_jobs"]
                                for st in sts.values()) == 0):
                    drained = True
                    break
            time.sleep(0.1)
        final = {m: _read_status(m) for m in survivors}

        with open(stop_path, "w") as fh:
            fh.write("stop\n")
        for m in survivors:
            try:
                kprocs[m].wait(timeout=30)
            except subprocess.TimeoutExpired:
                kprocs[m].kill()

        launched = []
        try:
            with open(launch_log) as fh:
                launched = [ln.split()[0] for ln in fh if ln.strip()]
        except OSError:
            pass
        # the name ledger over-counts: a member SIGKILLed with a
        # half-durable teardown batch makes the successor's relaunch of
        # a durably-DELETED pod look like a double launch. The WAL is
        # ground truth — a true duplicate is a create of a still-live
        # name (different uid, no durable delete between)
        from kubedl_tpu.federation.tail import duplicate_creates

        dup_launches = len(
            duplicate_creates(os.path.join(root, "wal"), kshards)
        )
        ledger_relaunches = len(launched) - len(set(launched))
        survivor_completed = sum(
            (final[m] or {}).get("completed", 0) for m in survivors
        )
        kill = {
            "members": kill_members,
            "victim": victim,
            "shards": kshards,
            "lease_ttl_s": lease_ttl,
            "jobs": kill_jobs,
            "victim_completed_at_kill": victim_frozen.get("completed", 0),
            "victim_submitted_at_kill": victim_frozen.get("submitted", 0),
            "reconverge_s": reconverge_s,
            "takeover_budget_s": takeover_budget_s,
            "survivor_completed": survivor_completed,
            "survivor_takeovers": {
                m: (final[m] or {}).get("takeovers", 0) for m in survivors
            },
            "pods_launched": len(set(launched)),
            "duplicate_launches": dup_launches,
            "ledger_relaunches_after_durable_delete": ledger_relaunches,
            "drained": drained,
        }
    finally:
        for p in kprocs.values():
            if p.poll() is None:
                p.kill()
        shutil.rmtree(root, ignore_errors=True)

    gates = {
        "fed_all_jobs_complete": (
            fed_completed == jobs and all(rc == 0 for rc in rcs)
        ),
        "fed_beats_r19_8shard_inprocess": (
            fed_jobs_per_s > r19_8shard_jobs_per_s
        ),
        "kill_reconverged_within_budget": (
            reconverge_s is not None and reconverge_s <= takeover_budget_s
        ),
        "kill_survivors_drained_all_jobs": drained,
        "kill_zero_duplicate_launches": dup_launches == 0,
    }
    return {
        "jobs": jobs,
        "r19_8shard_jobs_per_s": r19_8shard_jobs_per_s,
        "fed_speedup_vs_inprocess_8shard": round(
            fed_jobs_per_s / r19_8shard_jobs_per_s, 2
        ),
        "fed_4proc": fed,
        "member_kill": kill,
        "gates": gates,
        "ok": all(gates.values()),
    }


def bench_serving(on_tpu: bool) -> dict:
    """BASELINE.md target 5: Gemma-2B decode on the chip (tiny on CPU
    smoke). Measures the jitted continuous-batching decode step under the
    async-dispatch / scalar-sync discipline — per-token latency at batch 1
    and throughput at batch 8, plus time-to-first-token for a 64-token
    prompt."""
    import jax
    import jax.numpy as jnp

    from kubedl_tpu.models import llama

    preset = "gemma-2b" if on_tpu else "tiny"
    cfg = llama.preset(preset)
    max_seq = 512 if on_tpu else 64
    params = llama.llama_init(jax.random.PRNGKey(0), cfg)
    decode = jax.jit(lambda p, c, t: llama.decode_step_batched(p, c, t, cfg))
    out = {"model": preset, "n_params": cfg.num_params()}
    # 64 dispatched steps per trial: the per-dispatch host cost amortizes
    # over the async queue; min of trials kills the +-15% swings
    # (round-4: int8 b1 measured 175 once, 190-198 steady)
    steps = 64 if on_tpu else 8
    trials = 3 if on_tpu else 1

    def measure(p, suffix):
        for B in (1, 8):
            cache = llama.init_batched_cache(cfg, B, max_seq)
            toks = jnp.ones((B, 1), jnp.int32)
            logits, cache = decode(p, cache, toks)  # compile
            float(jax.device_get(jnp.sum(logits)))  # true barrier
            dt = float("inf")
            for _ in range(trials):
                t0 = time.perf_counter()
                for _ in range(steps):
                    logits, cache = decode(p, cache, toks)
                float(jax.device_get(jnp.sum(logits)))
                dt = min(dt, (time.perf_counter() - t0) / steps)
            out[f"decode_ms_per_token_b{B}{suffix}"] = round(dt * 1e3, 3)
            out[f"decode_tokens_per_sec_b{B}{suffix}"] = round(B / dt, 1)

    measure(params, "")
    # time-to-first-token: 64-token prompt via batched prefill (ONE
    # forward fills the cache and yields the first token's logits —
    # round 2 paid 64 sequential decode steps here: 633ms on v5e)
    prefill = jax.jit(lambda p, c, t, l: llama.prefill_batched(p, c, t, l, cfg))
    toks = jnp.ones((1, 64), jnp.int32)
    lens = jnp.full((1,), 64, jnp.int32)
    cache = llama.init_batched_cache(cfg, 1, max_seq)
    logits, cache = prefill(params, cache, toks, lens)  # compile
    float(jax.device_get(jnp.sum(logits)))
    best = float("inf")
    for _ in range(trials):
        cache = llama.init_batched_cache(cfg, 1, max_seq)
        t0 = time.perf_counter()
        logits, cache = prefill(params, cache, toks, lens)
        float(jax.device_get(jnp.sum(logits)))
        best = min(best, (time.perf_counter() - t0) * 1e3)
    out["ttft_64_prompt_ms"] = round(best, 1)
    if on_tpu:
        # weight-only int8: decode is HBM-bound, halved weight bytes.
        # Measured LAST with the bf16 weights freed first, so the two
        # variants are never co-resident (round-4 measured 141 vs 198
        # tok/s b1 with both held, on another stack)
        qp = llama.quantize_params(params, cfg)
        del params, cache, logits
        measure(qp, "_int8")
    return out


def bench_long_context(on_tpu: bool) -> dict:
    """Long-context training throughput: the flash kernel's O(S) memory is
    what makes S=8192 trainable on one 16GB chip at all (dense attention
    would materialize 8 GiB of scores per layer). Measures tokens/s and
    step time at long sequence length (CPU smoke uses a tiny shape)."""
    from kubedl_tpu.models import llama
    from kubedl_tpu.training.data import SyntheticTokens
    from kubedl_tpu.training.trainer import TrainConfig, Trainer

    if on_tpu:
        import dataclasses

        model = dataclasses.replace(llama.BENCH_350M, max_seq=8192)
        batch, seq, steps = 2, 8192, 6
    else:
        model = llama.TINY
        batch, seq, steps = 2, 128, 3
    cfg = TrainConfig(model=model, global_batch=batch, seq_len=seq,
                      steps=steps, opt_moment_dtype="bfloat16")
    trainer = Trainer(cfg)
    data = SyntheticTokens(batch, seq, model.vocab_size)
    _, s = trainer.fit(iter(data))
    return {
        "seq_len": seq,
        "global_batch": batch,
        "attn_impl": s["attn_impl"],
        "tokens_per_sec_per_chip": round(s["tokens_per_sec_per_chip"], 1),
        "step_time_ms": round(s["step_time_ms"], 1),
        "mfu": round(s["mfu"], 4),
    }


_GOODPUT = {"stop": "", "step_time": 0.0}
_GOODPUT_LOCK = None  # created lazily; bench import must stay side-effect-free


def _goodput_worker(env):
    """ThreadRuntime entrypoint for the preemption-goodput drill: spins
    synthetic training steps until the stop file appears; a resize restart
    cancels it mid-run (the time lost to the restart is exactly what the
    goodput number charges)."""
    import threading as _th
    import time as _t

    global _GOODPUT_LOCK
    if _GOODPUT_LOCK is None:
        _GOODPUT_LOCK = _th.Lock()
    cancel = (env or {}).get("_KUBEDL_CANCEL")
    me = (env or {}).get("KUBEDL_POD_NAME", "")
    while not os.path.exists(_GOODPUT["stop"]):
        if cancel is not None and cancel.is_set():
            raise SystemExit(137)
        t0 = _t.time()
        _t.sleep(0.02)  # one synthetic "step"
        if me.endswith("-worker-0"):  # one lens, not world-size-weighted
            with _GOODPUT_LOCK:
                _GOODPUT["step_time"] += _t.time() - t0
    return 0


def bench_goodput_under_preemption() -> dict:
    """Training goodput through a full preemption drill (docs/elasticity.md):
    a 2-slice elastic TPUJob takes a preemption notice, shrinks off the
    draining slice, grows back when the notice clears, and finishes —
    goodput = worker-0's productive step time / drill wall time, i.e. the
    fraction NOT lost to the two resize restarts. Runs on the in-process
    control plane (ThreadRuntime), so it measures orchestration overhead,
    not device speed."""
    import tempfile
    import time as _t

    from kubedl_tpu.api.topology import get_slice
    from kubedl_tpu.api.types import (
        ElasticSpec, JobConditionType, ReplicaSpec, ReplicaType,
        RestartPolicy,
    )
    from kubedl_tpu.core.objects import Container
    from kubedl_tpu.elastic.resize import goodput
    from kubedl_tpu.gang.slice_scheduler import SliceInventory
    from kubedl_tpu.operator import Operator, OperatorOptions
    from kubedl_tpu.runtime.executor import ThreadRuntime

    sys.modules["__bench_goodput__"] = sys.modules[
        bench_goodput_under_preemption.__module__
    ]
    inv = SliceInventory()
    inv.add_slice("ga", "cpu-1")
    inv.add_slice("gb", "cpu-1")
    with tempfile.TemporaryDirectory() as tmp:
        _GOODPUT["stop"] = os.path.join(tmp, "stop")
        _GOODPUT["step_time"] = 0.0
        opts = OperatorOptions(
            local_addresses=True,
            artifact_registry_root=os.path.join(tmp, "reg"),
            heartbeat_nodes=["ga-host-0", "gb-host-0"],
            node_grace_seconds=2.0,
        )
        with Operator(opts, runtime=ThreadRuntime(), inventory=inv) as op:
            job_kind = "TPUJob"
            from kubedl_tpu.workloads.tpujob import TPUJob

            job = TPUJob()
            job.metadata.name = "goodput"
            spec = ReplicaSpec(
                replicas=2, topology=get_slice("cpu-1"),
                restart_policy=RestartPolicy.ON_FAILURE_SLICE,
            )
            spec.template.spec.containers.append(
                Container(entrypoint="__bench_goodput__:_goodput_worker")
            )
            job.spec.replica_specs[ReplicaType.WORKER] = spec
            job.num_slices = 2
            job.elastic = ElasticSpec(min_slices=1, max_slices=2,
                                      cooldown_seconds=0.2)
            op.submit(job)
            op.wait_for_phase(job_kind, "goodput",
                              JobConditionType.RUNNING, timeout=60)
            t0 = _t.time()
            op.node_heartbeater.announce_preemption("gb-host-0", "drill")
            op.manager.wait(
                lambda: (lambda g: g is not None and g.num_slices == 1)(
                    op.store.try_get(job_kind, "goodput")),
                timeout=60,
            )
            op.node_heartbeater.clear_preemption("gb-host-0")
            op.manager.wait(
                lambda: (lambda g: g is not None and g.num_slices == 2
                         and g.status.phase == JobConditionType.RUNNING)(
                    op.store.try_get(job_kind, "goodput")),
                timeout=60,
            )
            _t.sleep(0.5)  # some steady-state steps at the grown shape
            with open(_GOODPUT["stop"], "w") as f:
                f.write("done")
            got = op.wait_for_phase(
                job_kind, "goodput",
                [JobConditionType.SUCCEEDED, JobConditionType.FAILED],
                timeout=60,
            )
            wall = _t.time() - t0
            g = goodput(_GOODPUT["step_time"], wall)
            op.metrics.goodput.set(g)
            return {
                "succeeded": got.status.phase == JobConditionType.SUCCEEDED,
                "goodput": round(g, 3),
                "wall_s": round(wall, 2),
                "productive_step_s": round(_GOODPUT["step_time"], 2),
                "resizes": got.status.restart_count,
                "notices": int(op.metrics.preemption_notices.value()),
            }


#: pinned convergence-equivalence tolerance for the PS arm: mean final
#: worker loss vs the synchronous baseline's final loss. Asynchrony
#: (decay-weighted stale pushes, one mid-run eviction+rejoin) is allowed
#: to perturb the trajectory, not to break training.
PS_LOSS_TOL = 0.5


def bench_ps() -> dict:
    """Preemption-storm bench (docs/elasticity.md "Parameter-service
    mode", BENCH_r15_ps.json): restart-based elastic vs the PS tier under
    the SAME seeded storm schedule, plus a convergence-equivalence gate.

    Two sections, two gates:

    - **convergence** — a real synchronous ``fit`` vs two real ``fit_ps``
      workers racing through a shared ``ParameterService`` (one worker
      silently evicted mid-run, forcing the MemberEvicted -> re-register
      -> warm-start path). Gate: mean final worker loss within
      ``PS_LOSS_TOL`` of the sync baseline. The "asynchrony didn't break
      training" side of the trade.
    - **storm goodput** — event-driven accounting over a seeded storm
      schedule, parameterized ONLY by costs measured in this run (steady
      step time, cold restore = compile+first-step, PS rejoin RTT). Per
      event the restart arm stalls the WHOLE gang (restore + redo of
      work since the last checkpoint); the PS arm pays the victim's
      outage + warm rejoin while survivors keep stepping. Both arms
      accumulate into ``GoodputBreakdown`` so the delta is attributable
      per bucket. Gate: PS goodput strictly above the restart arm.
    """
    import random
    import threading as _th

    import jax

    from kubedl_tpu.api.topology import MeshSpec
    from kubedl_tpu.core.store import ObjectStore
    from kubedl_tpu.elastic.resize import GoodputBreakdown
    from kubedl_tpu.models import llama
    from kubedl_tpu.observability.metrics import PSMetrics
    from kubedl_tpu.parallel.mesh import build_mesh
    from kubedl_tpu.ps import ParameterService, PSConfig
    from kubedl_tpu.training.data import SyntheticTokens
    from kubedl_tpu.training.trainer import TrainConfig, Trainer

    STEPS = 24

    def mk_trainer():
        mesh = build_mesh(MeshSpec({"data": 2}), jax.devices()[:2])
        cfg = TrainConfig(model=llama.TINY, global_batch=4, seq_len=16,
                          steps=STEPS, seed=0)
        return Trainer(cfg, mesh)

    def mk_data(seed):
        return iter(SyntheticTokens(4, 16, llama.TINY.vocab_size, seed=seed))

    # ---- section 1: convergence equivalence (real training) ----------
    t_sync = mk_trainer()
    st0 = t_sync.init_state()
    _, sync = t_sync.fit(mk_data(1), state=st0, steps=STEPS)

    svc = ParameterService(
        Trainer._host_params(t_sync.init_state()["params"]),
        PSConfig(num_shards=2, max_staleness=4, decay=0.5),
        store=ObjectStore(), metrics=PSMetrics(),
    )
    summaries: dict = {}
    evict_once = _th.Event()

    def ps_worker(wid: str, data_seed: int) -> None:
        t = mk_trainer()
        st = t.init_state()

        def on_step(i, _metrics):
            # the storm, in miniature: halfway through, w1 is declared
            # silently dead ONCE (the watchdog-fire path); its next push
            # hits MemberEvicted and fit_ps re-registers + warm-starts
            # from the aggregate. Tripped from the victim's own step
            # callback so the rejoin is exercised deterministically, not
            # subject to which thread finishes first.
            if wid == "w1" and i == STEPS // 2 and not evict_once.is_set():
                evict_once.set()
                svc.evict_silent_death("w1")

        _, summaries[wid] = t.fit_ps(
            mk_data(data_seed), svc, wid, state=st, steps=STEPS,
            push_every=2, on_step=on_step,
        )

    threads = [
        _th.Thread(target=ps_worker, args=("w0", 1)),
        _th.Thread(target=ps_worker, args=("w1", 2)),
    ]
    t0 = time.time()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    ps_wall = time.time() - t0
    losses = [summaries[w]["final_loss"] for w in ("w0", "w1")]
    ps_loss = sum(losses) / len(losses)
    loss_gap = abs(ps_loss - sync["final_loss"])
    converged = math.isfinite(ps_loss) and loss_gap <= PS_LOSS_TOL

    # ---- section 2: storm goodput, costs measured above --------------
    step_s = max(sync["step_time_ms"] / 1000.0, 1e-4)
    # what one gang restart costs a replica before it trains again:
    # process cold start = compile + first step (measured, this run)
    restore_s = max(sync["first_step_seconds"], step_s)
    # what a PS rejoin costs the victim: a real register+pull RTT
    t0 = time.time()
    svc.register("bench-rejoin-probe")
    rejoin_s = time.time() - t0
    svc.deregister("bench-rejoin-probe")

    WORKERS, HORIZON, CKPT_EVERY = 8, 600.0, 120.0
    rng = random.Random(15)
    # deterministic: a seeded schedule, identical for both arms
    storm = sorted(
        (
            {
                "t": round(rng.uniform(0.0, HORIZON), 1),
                "victim": rng.randrange(WORKERS),
                "outage_s": round(rng.uniform(20.0, 60.0), 1),
            }
            for _ in range(8)
        ),
        key=lambda e: e["t"],
    )

    wall = WORKERS * HORIZON
    restart_bd = GoodputBreakdown()
    ps_bd = GoodputBreakdown()
    for ev in storm:
        # restart arm: one preemption serializes the WHOLE gang — every
        # worker pays the cold restore, then redoes the (expected) half
        # checkpoint interval of work the restore rewound
        restart_bd.restart_seconds += WORKERS * restore_s
        restart_bd.checkpoint_seconds += WORKERS * (CKPT_EVERY / 2.0)
        # PS arm: only the victim is out (its staged in-flight handled
        # per the failure matrix); survivors never stall. Rejoin is a
        # warm-start pull, measured against the live service above.
        ps_bd.readmission_seconds += ev["outage_s"] + rejoin_s
    restart_bd.productive_seconds = max(wall - restart_bd.lost_seconds, 0.0)
    ps_bd.productive_seconds = max(wall - ps_bd.lost_seconds, 0.0)

    stats = svc.stats()
    rejoins = int(sum(s["ps_rejoins"] for s in summaries.values()))
    ok = bool(
        converged
        and ps_bd.goodput() > restart_bd.goodput()
        and rejoins >= 1  # the eviction->warm-rejoin path actually ran
    )
    return {
        "ok": ok,
        "storm": {
            "seed": 15, "workers": WORKERS, "horizon_s": HORIZON,
            "ckpt_every_s": CKPT_EVERY, "events": storm,
        },
        "measured": {
            "step_ms": round(sync["step_time_ms"], 2),
            "restore_s": round(restore_s, 3),
            "rejoin_ms": round(rejoin_s * 1000.0, 3),
        },
        "restart_goodput": round(restart_bd.goodput(), 3),
        "ps_goodput": round(ps_bd.goodput(), 3),
        "restart_arm": restart_bd.to_dict(),
        "ps_arm": ps_bd.to_dict(),
        "sync_final_loss": round(sync["final_loss"], 4),
        "ps_final_loss": round(ps_loss, 4),
        "loss_gap": round(loss_gap, 4),
        "loss_tol": PS_LOSS_TOL,
        "ps_wall_s": round(ps_wall, 2),
        "ps_counters": {
            "pushes": int(sum(s["ps_pushes"] for s in summaries.values())),
            "decayed": int(sum(s["ps_decayed"] for s in summaries.values())),
            "rejected": int(sum(s["ps_rejected"] for s in summaries.values())),
            "rejoins": rejoins,
            # the metrics counter, not stats()["evicted"]: a rejoin
            # clears the evicted entry, the counter keeps the history
            "evictions": int(
                svc.metrics.ps_evictions.value(reason="silent_death")
            ),
            "shard_versions": stats["versions"],
        },
    }


def bench_crash_recovery() -> dict:
    """Crash-recovery costs (docs/robustness.md): per-write WAL overhead
    for each fsync policy vs the pure-memory store, snapshot-bounded
    rehydration latency, and end-to-end time-to-reconverge after a
    simulated operator SIGKILL (restart on the same WAL dir, adopt every
    running pod, launch nothing twice)."""
    import tempfile
    import time as _t

    from kubedl_tpu.core.objects import Pod, PodPhase
    from kubedl_tpu.core.store import ObjectStore

    def pod(i):
        p = Pod()
        p.metadata.name = f"bench-{i}"
        return p

    def writes_per_sec(store, n=400):
        t0 = _t.perf_counter()
        pods = [store.create(pod(i)) for i in range(n)]
        for p in pods:
            p.status.phase = PodPhase.RUNNING
            store.update(p)
        return (2 * n) / (_t.perf_counter() - t0)

    out: dict = {}
    with tempfile.TemporaryDirectory() as tmp:
        out["no_wal_writes_per_s"] = round(writes_per_sec(ObjectStore()))
        for policy in ("off", "batch", "always"):
            s = ObjectStore(wal_dir=os.path.join(tmp, f"w-{policy}"),
                            wal_fsync=policy)
            out[f"wal_fsync_{policy}_writes_per_s"] = round(writes_per_sec(s))
            s.close()
        # slowdown of a WAL'd (no-fsync) write vs the pure-memory store
        out["wal_overhead_pct_no_fsync"] = round(max(
            0.0,
            (out["no_wal_writes_per_s"]
             / out["wal_fsync_batch_writes_per_s"] - 1.0) * 100.0,
        ), 1)

        # rehydration: snapshot + tail replay of 500 live objects
        d = os.path.join(tmp, "rehydrate")
        s = ObjectStore(wal_dir=d, wal_fsync="off")
        for i in range(500):
            s.create(pod(i))
        s.compact()
        s.close()
        s2 = ObjectStore(wal_dir=d)
        out["rehydrate_objects"] = len(s2.list("Pod"))
        out["rehydrate_ms"] = round(s2.recovery_seconds * 1e3, 1)
        s2.close()

        # e2e: kill-recover-adopt with real subprocess pods
        from kubedl_tpu.api.topology import get_slice
        from kubedl_tpu.api.types import JobConditionType
        from kubedl_tpu.gang.slice_scheduler import SliceInventory
        from kubedl_tpu.operator import Operator, OperatorOptions
        from kubedl_tpu.runtime.executor import SubprocessRuntime
        from tests.helpers import make_tpujob

        def inv():
            v = SliceInventory()
            v.add_slice("s1", "v5e-8")
            v.add_slice("s2", "v5e-8")
            return v

        def running(store):
            return [p for p in store.list("Pod")
                    if p.status.phase == PodPhase.RUNNING]

        opts = OperatorOptions(
            local_addresses=True, wal_dir=os.path.join(tmp, "e2e-wal"),
            artifact_registry_root=os.path.join(tmp, "reg"),
        )
        op1 = Operator(opts, runtime=SubprocessRuntime(), inventory=inv())
        op1.start()
        topo = get_slice("v5e-8")
        for name in ("cr1", "cr2"):
            op1.submit(make_tpujob(
                name, workers=2, topology=topo,
                command=[sys.executable, "-c", "import time; time.sleep(60)"],
            ))
            op1.wait_for_phase("TPUJob", name, JobConditionType.RUNNING,
                               timeout=30)
        op1.manager.wait(lambda: len(running(op1.store)) == 4, timeout=20)
        # simulated SIGKILL: no teardown, pods stay alive, WAL detaches
        op1.manager.stop()
        op1.node_heartbeater.stop()
        op1.kubelet._running.clear()
        op1.kubelet._running_uid.clear()
        op1.store.close()

        t0 = _t.perf_counter()
        op2 = Operator(opts, runtime=SubprocessRuntime(), inventory=inv())
        op2.start()
        op2.manager.wait(
            lambda: op2.kubelet.adopted_count == 4
            and len(running(op2.store)) == 4,
            timeout=30,
        )
        out["reconverge_s"] = round(_t.perf_counter() - t0, 3)
        out["adopted_pods"] = op2.kubelet.adopted_count
        out["relaunched_pods"] = op2.kubelet.launch_count
        out["replayed_records"] = op2.store.replayed_records
        op2.stop()
    return out


def bench_checkpoint_overhead() -> dict:
    """Async-checkpoint stall budget (docs/robustness.md "Async
    checkpointing"): the step loop's blocking cost per save must be <10%
    of a synchronous save of the same state. A single ~64MB leaf makes
    the npz/disk write the dominant sync cost (like a real shard), so
    the ratio isolates what the async split actually buys — the loop
    pays only the device->host snapshot while the writer thread eats
    the IO."""
    import statistics
    import tempfile
    import time as _t

    import jax.numpy as jnp

    from kubedl_tpu.training.checkpoint import (
        AsyncCheckpointer, save_checkpoint,
    )

    state = {
        "step": jnp.zeros((), jnp.int32),
        "params": {"w": jnp.arange(16 << 20, dtype=jnp.float32)},  # 64 MB
    }
    trials = 5
    sync_s, stall_s = [], []
    with tempfile.TemporaryDirectory() as tmp:
        for i in range(trials):
            t0 = _t.perf_counter()
            save_checkpoint(os.path.join(tmp, "sync"), state, i + 1)
            sync_s.append(_t.perf_counter() - t0)
        acp = AsyncCheckpointer(os.path.join(tmp, "async"))
        for i in range(trials):
            t0 = _t.perf_counter()
            acp.save(state, i + 1)
            stall_s.append(_t.perf_counter() - t0)
            # drain OUTSIDE the timed window: each trial measures the
            # steady-state stall, not a backpressure pile-up
            acp.wait_for_pending()
    sync_med = statistics.median(sync_s)
    stall_med = statistics.median(stall_s)
    return {
        "payload_mb": 64,
        "sync_save_median_s": round(sync_med, 4),
        "async_stall_median_s": round(stall_med, 4),
        "stall_pct_of_sync": round(stall_med / sync_med * 100.0, 1),
        "async_total_stall_s": round(acp.stall_seconds, 4),
        "pass": stall_med < 0.10 * sync_med,
    }


def bench_serving_engine(on_tpu: bool, raw: dict) -> dict:
    """BASELINE.md target 5 through the PRODUCTION path (VERDICT r4
    missing #3): the raw-decode microbench never exercised the
    continuous-batching engine loop, its slot admission, or the HTTP
    handler — the reference's inference numbers would come through the
    deployed predictor (controllers/serving/predictor.go:37-115). Drives
    `LlamaEngine.generate` and the real HTTP server for b1/b8 decode and
    TTFT, reports engine overhead vs the raw jitted decode, and measures
    slot churn under mixed-length concurrent requests."""
    import threading

    from kubedl_tpu.serving.server import LlamaEngine, make_handler

    preset = "gemma-2b" if on_tpu else "tiny"
    n = 128 if on_tpu else 8
    # prefix cache OFF: this section's TTFT row means FULL prefill cost
    # (the prefix_reuse section measures the cached path against it)
    eng = LlamaEngine(preset=preset, max_seq=512 if on_tpu else 64,
                      max_batch=8, prefix_cache_mb=0)
    out = {"model": preset, "max_batch": 8}
    try:
        # warm every segment bucket + the prefill buckets the runs below
        # touch, so timed numbers measure the loop, not XLA compiles
        for mt in (1, 5, 37):
            eng.generate([1, 2, 3], max_tokens=mt)
        eng.generate(list(range(1, 65)), max_tokens=1)

        # b1 ms/token as the MEDIAN of 5 runs: the overhead acceptance bar
        # (<= 15% of raw decode) is too tight for a single sample to be
        # trustworthy against scheduler-thread jitter
        import statistics

        b1_ms = []
        for _ in range(5):
            t0 = time.perf_counter()
            r = eng.generate([1], max_tokens=n)
            dt = time.perf_counter() - t0
            got = len(r.get("token_ids", []))
            b1_ms.append(dt / max(got, 1) * 1e3)
        med = statistics.median(b1_ms)
        out["engine_decode_ms_per_token_b1"] = round(med, 3)
        out["engine_decode_ms_per_token_b1_runs"] = [
            round(v, 3) for v in b1_ms
        ]
        out["engine_decode_tokens_per_sec_b1"] = round(1e3 / med, 1)

        ttft = []
        for _ in range(5):
            t0 = time.perf_counter()
            eng.generate(list(range(1, 65)), max_tokens=1)
            ttft.append((time.perf_counter() - t0) * 1e3)
        out["engine_ttft_64_prompt_ms"] = round(statistics.median(ttft), 1)

        def one(tokens: int, results: list):
            t = time.perf_counter()
            rr = eng.generate([1, 2], max_tokens=tokens)
            results.append((len(rr.get("token_ids", [])),
                            time.perf_counter() - t))

        # b8: saturate every slot with equal-length requests
        results: list = []
        threads = [
            threading.Thread(target=one, args=(n, results)) for _ in range(8)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        total = sum(g for g, _ in results)
        out["engine_decode_tokens_per_sec_b8"] = round(total / wall, 1)

        # mixed-length churn: 16 requests over 8 slots, lengths cycling —
        # short requests finish, vacate, and waiting ones must be admitted
        # mid-flight (the continuous-batching property itself)
        lengths = [4, 8, 16, 48] * 4
        results = []
        threads = [
            threading.Thread(target=one, args=(ln, results))
            for ln in lengths
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        total = sum(g for g, _ in results)
        out["mixed_requests"] = len(lengths)
        out["mixed_tokens_per_sec"] = round(total / wall, 1)
        out["mixed_all_completed"] = (
            sorted(g for g, _ in results) == sorted(lengths)
        )

        # HTTP handler on top of the same engine (the deployed surface)
        import http.server
        import json as _json
        import urllib.request

        handler = make_handler(eng, preset)
        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), handler)
        port = srv.server_address[1]
        st = threading.Thread(target=srv.serve_forever, daemon=True)
        st.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate",
                data=_json.dumps(
                    {"prompt_ids": [1], "max_tokens": n}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=300) as resp:
                body = _json.loads(resp.read())
            dt = time.perf_counter() - t0
            got = len(body.get("token_ids") or body.get("data", {}).get(
                "token_ids", []
            ))
            out["http_decode_tokens_per_sec_b1"] = round(got / dt, 1)
        finally:
            srv.shutdown()

        raw_b1 = raw.get("decode_ms_per_token_b1")
        if raw_b1:
            out["engine_overhead_vs_raw_b1_pct"] = round(
                (out["engine_decode_ms_per_token_b1"] / raw_b1 - 1) * 100, 1
            )

        # per-tick pipeline medians from the engine's own accounting
        # (LlamaEngine.pipeline_stats): how much of the tick the
        # double-buffered scheduler spent enqueueing vs blocked vs on
        # host bookkeeping, and the fraction overlapped with device time
        pipe = eng.pipeline_stats()
        out["pipeline"] = {
            k: pipe[k] for k in (
                "ticks", "segments", "deferred_harvests", "flushes",
                "chain_rebuilds", "overlap_ratio", "dispatch_ms_p50",
                "harvest_ms_p50", "host_ms_p50", "tick_ms_p50",
            ) if k in pipe
        }
        # the headline medians in one place (acceptance: engine b1/b8/
        # TTFT/overhead must be present in the committed summary)
        out["engine_summary"] = {
            "decode_ms_per_token_b1_median": out[
                "engine_decode_ms_per_token_b1"
            ],
            "decode_tokens_per_sec_b8": out[
                "engine_decode_tokens_per_sec_b8"
            ],
            "ttft_64_prompt_ms_median": out["engine_ttft_64_prompt_ms"],
            "overhead_vs_raw_b1_pct": out.get(
                "engine_overhead_vs_raw_b1_pct"
            ),
        }
    finally:
        eng.close()
    return out


def bench_prefix_reuse(on_tpu: bool) -> dict:
    """Prefix KV cache (docs/serving.md "Prefix cache") on a shared-
    system-prompt fleet: every request = one shared prefix + a unique
    tail, the dominant real serving shape. Two arms on identical
    workloads — cache OFF (full prefill per request) vs cache ON
    (suffix-only prefill after the first two requests teach the
    observation trie). Acceptance: tokens_saved > 0 and the cache-on
    arm's median TTFT beats cache-off; greedy outputs must be
    bit-identical across arms (the reuse is exact, not approximate)."""
    import statistics

    from kubedl_tpu.serving.server import LlamaEngine

    preset = "gemma-2b" if on_tpu else "tiny"
    max_seq = 512 if on_tpu else 128
    # the shared prefix dominates the prompt (full-prefill bucket 8x the
    # suffix bucket) — the realistic shape, and what keeps the TTFT
    # delta above host-scheduling noise on the CPU tiny model
    sys_len = 256 if on_tpu else 96
    n_req = 16
    max_tokens = 8
    shared = list(range(3, 3 + sys_len))
    prompts = [shared + [500 + j, 600 + j] for j in range(n_req)]

    def arm(cache_mb: float) -> dict:
        eng = LlamaEngine(preset=preset, max_seq=max_seq, max_batch=4,
                          prefix_cache_mb=cache_mb, prefix_min_len=8)
        try:
            # warm every compile this arm touches (full-prefill bucket,
            # suffix bucket, graft/extract, segment) AND — cache-on —
            # teach the observation trie so the timed phase is all hits
            for p in prompts[:2]:
                eng.generate(p, max_tokens=max_tokens)
            ttfts, outs = [], []
            for p in prompts:
                r = eng.generate(p, max_tokens=max_tokens)
                outs.append(r.get("token_ids", []))
                if r.get("ttft_ms") is not None:
                    ttfts.append(r["ttft_ms"])
            res = {
                "ttft_ms_p50": round(statistics.median(ttfts), 3),
                "ttft_ms_runs": [round(v, 3) for v in ttfts],
                "outputs": outs,
            }
            st = eng.stats()
            if "prefix_cache" in st:
                pc = st["prefix_cache"]
                res["prefix_cache"] = {
                    k: pc[k] for k in (
                        "hits", "misses", "inserts", "evictions",
                        "tokens_saved", "entries", "bytes", "hit_rate",
                    )
                }
            return res
        finally:
            eng.close()

    off = arm(0)
    on = arm(64)
    equal = off["outputs"] == on["outputs"]
    out = {
        "model": preset,
        "shared_prefix_len": sys_len,
        "requests": n_req,
        "ttft_ms_p50_cache_off": off["ttft_ms_p50"],
        "ttft_ms_p50_cache_on": on["ttft_ms_p50"],
        "ttft_speedup": round(
            off["ttft_ms_p50"] / max(on["ttft_ms_p50"], 1e-9), 2
        ),
        "tokens_saved": on["prefix_cache"]["tokens_saved"],
        "hit_rate": on["prefix_cache"]["hit_rate"],
        "prefix_cache": on["prefix_cache"],
        "greedy_outputs_identical": equal,
    }
    return out


def bench_paged_kv(on_tpu: bool) -> dict:
    """Paged KV occupancy at FIXED KV HBM (docs/serving.md "Paged KV"):
    the contiguous layout must reserve max_seq slots per batch row up
    front, so a given KV budget caps concurrency at budget/max_seq rows
    no matter how short requests actually are. The paged arm gets the
    SAME token-slot budget as a block pool and admits by actual usage.
    Workload: a burst of short concurrent requests (one block each).
    Acceptance: peak concurrent occupancy >= 2x the contiguous arm's,
    zero blocks leaked, and greedy outputs bit-identical across arms."""
    import threading as _threading
    import time as _time

    import numpy as np

    from kubedl_tpu.serving.server import LlamaEngine

    preset = "gemma-2b" if on_tpu else "tiny"
    max_seq = 128
    block_size = 16
    contig_batch = 3  # KV budget: 3 rows x 128 slots = 384 token-slots
    paged_batch = 12
    # same budget as blocks: 24 usable x 16 = 384 slots (+1 trash block)
    kv_blocks = 1 + contig_batch * (max_seq // block_size)
    n_req = 12
    max_tokens = 8
    # short prompts: prompt+output fit ONE block, so the pool can hold
    # 24 concurrent requests even though contiguous capacity is 3 rows
    prompts = [[3 + j, 11, 7 + j] for j in range(n_req)]

    def arm(layout: str) -> dict:
        kw = dict(preset=preset, max_seq=max_seq, prefix_cache_mb=0)
        if layout == "paged":
            kw.update(kv_layout="paged", kv_block_size=block_size,
                      kv_blocks=kv_blocks, max_batch=paged_batch)
        else:
            kw.update(kv_layout="contiguous", max_batch=contig_batch)
        eng = LlamaEngine(**kw)
        try:
            eng.generate(prompts[0], max_tokens=max_tokens)  # warm compiles
            peak = 0
            stop = _threading.Event()

            def sampler():
                nonlocal peak
                while not stop.is_set():
                    with eng._cv:
                        n = sum(s is not None for s in eng._slots)
                    peak = max(peak, n)
                    _time.sleep(0.001)

            outs: list = [None] * n_req

            def worker(i):
                r = eng.generate(prompts[i], max_tokens=max_tokens)
                outs[i] = r.get("token_ids", [])

            smp = _threading.Thread(target=sampler, daemon=True)
            smp.start()
            t0 = _time.perf_counter()
            threads = [_threading.Thread(target=worker, args=(i,))
                       for i in range(n_req)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            wall_ms = (_time.perf_counter() - t0) * 1e3
            stop.set()
            smp.join(timeout=5)
            res = {
                "peak_concurrent": peak,
                "wall_ms": round(wall_ms, 1),
                "outputs": outs,
            }
            if layout == "paged":
                st = eng.stats()["kv_blocks"]
                res["kv_blocks"] = {k: st[k] for k in
                                    ("total", "free", "used", "block_size")}
            return res
        finally:
            eng.close()

    contig = arm("contiguous")
    paged = arm("paged")
    # both arms hold the same number of KV token-slots in HBM
    cfg_probe = LlamaEngine(preset=preset, max_seq=32, max_batch=1)
    try:
        cfg = cfg_probe.cfg
        slot_bytes = int(2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim
                         * np.dtype(cfg.dtype).itemsize)
    finally:
        cfg_probe.close()
    return {
        "model": preset,
        "requests": n_req,
        "max_tokens": max_tokens,
        "kv_slot_budget": contig_batch * max_seq,
        "kv_hbm_mb_contiguous": round(
            contig_batch * max_seq * slot_bytes / 1e6, 3
        ),
        "kv_hbm_mb_paged": round(
            (kv_blocks - 1) * block_size * slot_bytes / 1e6, 3
        ),
        "peak_concurrent_contiguous": contig["peak_concurrent"],
        "peak_concurrent_paged": paged["peak_concurrent"],
        "occupancy_gain": round(
            paged["peak_concurrent"]
            / max(contig["peak_concurrent"], 1), 2
        ),
        "wall_ms_contiguous": contig["wall_ms"],
        "wall_ms_paged": paged["wall_ms"],
        "blocks_leaked": paged["kv_blocks"]["used"],
        "greedy_outputs_identical": contig["outputs"] == paged["outputs"],
    }


def bench_speculative(on_tpu: bool) -> dict:
    """Speculative decoding single-stream latency (docs/serving.md
    "Speculative decoding"): one long greedy generation, spec OFF (plain
    multi-step segments) vs spec ON (ngram draft-k/verify-1 on the paged
    cache). The tiny model's greedy continuations fall into repetition
    quickly, which is exactly the regime an ngram draft exploits — the
    same structure real LLM output has in code/templated text.
    Acceptance: outputs bit-identical across arms (the exactness gate),
    acceptance rate > 0, and the artifact records tokens/verify + wall
    time for both arms so regressions in either direction are visible."""
    import time as _time

    from kubedl_tpu.serving.server import LlamaEngine

    preset = "gemma-2b" if on_tpu else "tiny"
    max_seq = 256
    max_tokens = 192
    # a repetitive prompt puts the tiny model's greedy continuation in
    # the loopy regime where the ngram draft actually lands proposals
    prompt = [7, 7, 7]
    k = 4

    def arm(spec_k: int) -> dict:
        eng = LlamaEngine(preset=preset, max_batch=1, max_seq=max_seq,
                          kv_layout="paged", spec_k=spec_k,
                          spec_draft="ngram", prefix_cache_mb=0)
        try:
            eng.generate(prompt, max_tokens=8)  # warm compiles
            t0 = _time.perf_counter()
            r = eng.generate(prompt, max_tokens=max_tokens)
            wall_ms = (_time.perf_counter() - t0) * 1e3
            res = {"outputs": r.get("token_ids", []),
                   "wall_ms": round(wall_ms, 1)}
            st = eng.stats()
            if "speculative" in st:
                res["speculative"] = st["speculative"]
            return res
        finally:
            eng.close()

    off = arm(0)
    on = arm(k)
    spec = on.get("speculative") or {}
    return {
        "model": preset,
        "max_tokens": max_tokens,
        "spec_k": k,
        "draft": "ngram",
        "wall_ms_spec_off": off["wall_ms"],
        "wall_ms_spec_on": on["wall_ms"],
        "latency_speedup": round(
            off["wall_ms"] / max(on["wall_ms"], 1e-9), 2
        ),
        "acceptance_rate": spec.get("acceptance_rate", 0.0),
        "tokens_per_verify": spec.get("tokens_per_verify", 0.0),
        "verifies": spec.get("verifies", 0),
        "greedy_outputs_identical": off["outputs"] == on["outputs"],
        # the off arm rides the double-buffered segment path (deferred
        # harvest, one tick of latency per segment) while verify ticks
        # harvest synchronously — part of the measured speedup is that
        # pipeline-shape difference, not pure draft acceptance
        "note": "single-stream wall time, all else equal; speedup = "
                "pipeline shape + acceptance, see acceptance_rate",
    }


def bench_decode(on_tpu: bool) -> dict:
    """Blocked paged-attention decode + model-draft speculation
    (docs/serving.md "Blocked paged attention" / "Model drafts").

    Raw sweep: greedy `paged_decode_segment` at 1/4/12-way concurrency
    over a 512-slot block table, gather vs blocked kernels INTERLEAVED
    (alternating which goes first each trial, min-of-trials per kernel)
    so neither systematically rides a warmer allocator. Acceptance:
    greedy token streams bit-identical between kernels at every width,
    the blocked path actually traced into the compiled graph, and
    blocked tokens/s strictly above gather at 12-way (the CPU proxy for
    the gather's O(max_seq) data movement dominating wide decode).

    Spec arms: one long greedy generation on the tiny-deep pairing
    (2-layer early-exit draft == 4-layer target at init — the honest CPU
    stand-in for a trained draft/target pair), ngram vs model drafts and
    single- vs multi-candidate verification. Acceptance: all arms emit
    the no-spec oracle stream, model-draft acceptance > 0.5, and
    multi-candidate accepts at least as many draft tokens as single.

    Open-loop arms: a seeded Poisson arrival stream against a 12-way
    engine, slot-granularity vs chunked admission vs chunked +
    tree-speculation, reading per-request TTFT and queue wait.
    Acceptance: every arm bit-identical to the non-speculative
    contiguous engine, chunked p95 TTFT below slot granularity, and the
    12-way blocked speedup >= 1.25x."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubedl_tpu.models import llama
    from kubedl_tpu.models import paged_attention as pa

    preset = "gemma-2b" if on_tpu else "tiny"
    cfg = llama.preset(preset)
    max_seq = 512
    bs = 16
    mb = max_seq // bs
    steps = 32
    trials = 8
    params = llama.llama_init(jax.random.PRNGKey(0), cfg)
    out = {"model": preset, "max_seq": max_seq, "kv_block_size": bs,
           "segment_steps": steps}
    gates = {}
    raw = {}
    trace0 = pa.TRACE_COUNT["lax"] + pa.TRACE_COUNT["pallas"]
    for B in (1, 4, 12):
        nb = 1 + B * mb
        cache0 = llama.init_paged_cache(cfg, B, max_seq, nb, bs)
        cache0["bt"] = jnp.arange(
            1, 1 + B * mb, dtype=jnp.int32
        ).reshape(B, mb)
        toks = np.tile(np.array([[5, 9, 13]], np.int32), (B, 1))
        toks[:, 2] += np.arange(B)  # distinct rows
        lens = jnp.full((B,), 3, jnp.int32)
        logits, cache0 = llama.paged_prefill_batched(
            params, cache0, jnp.asarray(toks), lens, cfg
        )
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        temps = jnp.zeros((B,), jnp.float32)
        key = jax.random.PRNGKey(1)
        fns, ids = {}, {}
        for kern in ("gather", "blocked"):
            fn = jax.jit(functools.partial(
                llama.paged_decode_segment, cfg=cfg, n_steps=steps,
                greedy=True, kv_attention=kern,
            ))
            t, _, _, _ = fn(params, cache0, nxt, temps, key)  # compile
            ids[kern] = np.asarray(t)
            fns[kern] = fn
        gates[f"greedy_identical_b{B}"] = bool(
            np.array_equal(ids["gather"], ids["blocked"])
        )
        best = {"gather": float("inf"), "blocked": float("inf")}
        for trial in range(trials):
            order = (("gather", "blocked") if trial % 2 == 0
                     else ("blocked", "gather"))
            for kern in order:
                t0 = time.perf_counter()
                t, _, _, _ = fns[kern](params, cache0, nxt, temps, key)
                jax.block_until_ready(t)
                best[kern] = min(best[kern], time.perf_counter() - t0)
        raw[f"b{B}"] = {
            "gather_tokens_per_sec": round(B * steps / best["gather"], 1),
            "blocked_tokens_per_sec": round(B * steps / best["blocked"], 1),
            "blocked_speedup": round(best["gather"] / best["blocked"], 3),
        }
    out["raw"] = raw
    out["blocked_traced"] = (
        pa.TRACE_COUNT["lax"] + pa.TRACE_COUNT["pallas"] - trace0
    )
    gates["blocked_traced"] = out["blocked_traced"] > 0
    gates["blocked_faster_b12"] = raw["b12"]["blocked_speedup"] > 1.0

    # --- speculation arms (engine path) --------------------------------
    from kubedl_tpu.serving.server import LlamaEngine

    spec_preset = preset if on_tpu else "tiny-deep"
    prompt = [7, 7, 7]
    max_tokens = 96

    def spec_arm(**kw):
        eng = LlamaEngine(preset=spec_preset, max_batch=1, max_seq=256,
                          kv_layout="paged", prefix_cache_mb=0, **kw)
        try:
            eng.generate(prompt, max_tokens=8)  # warm compiles
            t0 = time.perf_counter()
            r = eng.generate(prompt, max_tokens=max_tokens)
            wall_ms = (time.perf_counter() - t0) * 1e3
            st = eng.stats().get("speculative") or {}
            return r.get("token_ids", []), st, round(wall_ms, 1)
        finally:
            eng.close()

    base_ids, _, base_wall = spec_arm(kv_attention="blocked")
    ng_ids, ng, ng_wall = spec_arm(spec_k=4, spec_draft="ngram",
                                   kv_attention="blocked")
    md_ids, md, md_wall = spec_arm(spec_k=4, spec_draft="model",
                                   spec_draft_layers=2,
                                   kv_attention="blocked")
    mc_ids, mc, mc_wall = spec_arm(spec_k=4, spec_draft="model",
                                   spec_draft_layers=2, spec_candidates=2,
                                   kv_attention="blocked")
    out["spec"] = {
        "model": spec_preset,
        "max_tokens": max_tokens,
        "wall_ms_no_spec": base_wall,
        "wall_ms_ngram": ng_wall,
        "wall_ms_model": md_wall,
        "wall_ms_model_multi": mc_wall,
        "ngram_acceptance": ng.get("acceptance_rate", 0.0),
        "model_acceptance": md.get("acceptance_rate", 0.0),
        "model_draft_ms_p50": md.get("draft_ms_p50"),
        "single_accepted": md.get("accepted", 0),
        "multi_accepted": mc.get("accepted", 0),
        "multi_candidates_scored": mc.get("candidates_scored", 0),
        "outputs_identical": base_ids == ng_ids == md_ids == mc_ids,
    }
    gates["spec_outputs_identical"] = out["spec"]["outputs_identical"]
    gates["model_acceptance_gt_half"] = (
        md.get("acceptance_rate", 0.0) > 0.5
    )
    gates["multi_accepts_ge_single"] = (
        mc.get("accepted", 0) >= md.get("accepted", 0)
    )

    # --- open-loop Poisson admission arms (continuous batching) --------
    # Closed-loop width sweeps hide admission latency entirely: every
    # "request" is already in the batch. This arm offers a seeded
    # Poisson arrival stream (mostly short prompts + periodic 128-token
    # ones) at ~60% utilization to a 12-way engine and reads each
    # request's OWN ttft_ms / queue wait. Slot-granularity admission
    # pays the long prefills as ticks nothing else can ride; chunked
    # admission (prefill_chunk_tokens) bounds that stall at one chunk,
    # which is exactly what the TTFT gap of the SHORT-request class
    # (the requests that queue behind a long prefill) measures.
    # Acceptance: every arm (slot, chunked, chunked+tree-speculation)
    # emits tokens bit-identical to a non-speculative CONTIGUOUS
    # engine, and the chunked arm's short-request p95 TTFT beats slot
    # granularity.
    import threading

    rng = np.random.RandomState(16)
    n_req = 48
    ol_prompts, ol_mt = [], []
    for j in range(n_req):
        if j % 6 == 3:
            ol_prompts.append(
                [int(x) for x in rng.randint(1, 250, size=128)]
            )
            ol_mt.append(8)
        else:
            ol_prompts.append(
                [int(x) for x in rng.randint(1, 250,
                                             size=rng.randint(3, 9))]
            )
            ol_mt.append(12)
    mean_gap_s = 0.040
    arrivals = np.cumsum(rng.exponential(scale=mean_gap_s, size=n_req))

    ref = LlamaEngine(preset=preset, max_batch=12, max_seq=160,
                      kv_layout="contiguous", prefix_cache_mb=0)
    try:
        want_ol = [
            ref.generate(p, max_tokens=m)["token_ids"]
            for p, m in zip(ol_prompts, ol_mt)
        ]
    finally:
        ref.close()

    def _pct(vals, q):
        srt = sorted(vals)
        return round(srt[min(len(srt) - 1, int(q * len(srt)))], 1)

    def openloop_arm(**kw):
        eng = LlamaEngine(preset=preset, max_batch=12, max_seq=160,
                          kv_layout="paged", kv_attention="blocked",
                          prefix_cache_mb=0, max_queue_depth=256,
                          max_queue_age_s=120.0, **kw)
        try:
            # warm EVERY bucket the stream will hit (short + 128-token
            # prefill, first decode segments) so measured TTFT is
            # steady-state dispatch cost, not one-time jit compiles
            eng.generate(ol_prompts[0], max_tokens=4)
            eng.generate(ol_prompts[3], max_tokens=4)
            results = [None] * n_req
            t0 = time.perf_counter()

            def worker(j):
                dt = arrivals[j] - (time.perf_counter() - t0)
                if dt > 0:
                    time.sleep(dt)
                results[j] = eng.generate(
                    ol_prompts[j], max_tokens=ol_mt[j], timeout_s=120
                )

            threads = [threading.Thread(target=worker, args=(j,))
                       for j in range(n_req)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=180)
            wall = time.perf_counter() - t0
            ttfts = [r["ttft_ms"] for r in results]
            # the short-request class is what chunked admission exists
            # for: requests that queue BEHIND a long prefill; the long
            # prompts themselves trade a bounded TTFT increase for it
            short = [t for j, t in enumerate(ttfts) if j % 6 != 3]
            toks = sum(len(r["token_ids"]) for r in results)
            st = eng.stats()
            return {
                "ttft_ms_p50": _pct(ttfts, 0.5),
                "ttft_ms_p95": _pct(ttfts, 0.95),
                "short_ttft_ms_p50": _pct(short, 0.5),
                "short_ttft_ms_p95": _pct(short, 0.95),
                "queue_wait_ms_p50": st.get("queue_wait_ms_p50"),
                "queue_wait_ms_p95": st.get("queue_wait_ms_p95"),
                "tokens_per_sec": round(toks / wall, 1),
                "outputs": [r["token_ids"] for r in results],
            }
        finally:
            eng.close()

    def _best(arms):
        # two interleaved rounds per arm (scheduler noise on a shared
        # box dwarfs the effect size): keep each arm's better round by
        # p95 TTFT, like min-of-trials in the raw sweep
        out = dict(arms[0])
        for a in arms[1:]:
            assert a["outputs"] == out["outputs"]
            if a["ttft_ms_p95"] < out["ttft_ms_p95"]:
                keep = out["outputs"]
                out = dict(a)
                out["outputs"] = keep
        return out

    ol_slot = _best([openloop_arm(), openloop_arm()])
    ol_chunk = _best([openloop_arm(prefill_chunk_tokens=32),
                      openloop_arm(prefill_chunk_tokens=32)])
    ol_tree = openloop_arm(prefill_chunk_tokens=32, spec_k=4,
                           spec_candidates=2, spec_tree=True)
    gates["openloop_slot_exact"] = ol_slot.pop("outputs") == want_ol
    gates["openloop_chunked_exact"] = ol_chunk.pop("outputs") == want_ol
    gates["openloop_tree_exact"] = ol_tree.pop("outputs") == want_ol
    gates["chunked_ttft_p95_lower"] = (
        ol_chunk["short_ttft_ms_p95"] < ol_slot["short_ttft_ms_p95"]
    )
    gates["blocked_speedup_b12_ge_1p25"] = (
        raw["b12"]["blocked_speedup"] >= 1.25
    )
    out["openloop"] = {
        "requests": n_req,
        "mean_gap_ms": mean_gap_s * 1e3,
        "max_batch": 12,
        "chunk_tokens": 32,
        "slot": ol_slot,
        "chunked": ol_chunk,
        "chunked_tree": ol_tree,
    }
    out["gates"] = gates
    out["ok"] = all(gates.values())
    return out


def bench_disagg(on_tpu: bool) -> dict:
    """Disaggregated prefill/decode fleet vs colocated at equal total
    chips (docs/serving.md "Disaggregated serving").

    Arms at 1/4/12-way concurrency, two engines each: colocated runs two
    full engines splitting the streams (every replica interleaves prefill
    forwards between decode segments — waiting admissions cap segments at
    4 steps); disagg runs one prefill + one decode engine pumped by
    DisaggCoordinator (the wire format roundtrips on every request). The
    decode pool never executes a prefill forward, so its segments stay at
    full depth — that separation, not kernel magic, is the measured win.
    TTFT is the prefill-side first-token latency in both arms.

    QoS burst: a scripted overload against the weighted-fair arbiter
    (capacity 2, queue 4): 4 bronze + 4 gold arrivals contend; overflow
    must shed ONLY bronze (gold evicts queued bronze, never the reverse).

    Acceptance: disagg greedy output bit-identical to colocated, decode
    tokens/s ratio >= 1.2x at 12-way, gold sheds == 0 while bronze
    absorbs the burst."""
    import threading as _th

    import numpy as _np

    from kubedl_tpu.serving.disagg import (
        DisaggCoordinator,
        QoSClassSpec,
        QoSShed,
        WeightedFairQueue,
    )
    from kubedl_tpu.serving.server import LlamaEngine

    preset = "gemma-2b" if on_tpu else "tiny"
    max_seq = 256
    bs = 8
    gen = 96
    prompt_len = 12
    out = {"model": preset, "max_seq": max_seq, "kv_block_size": bs,
           "gen_tokens": gen, "prompt_len": prompt_len}
    gates = {}

    def mk(role="colocated", max_batch=4):
        return LlamaEngine(preset=preset, max_batch=max_batch,
                           max_seq=max_seq, kv_block_size=bs,
                           prefix_cache_mb=0, role=role)

    # --- bit-identity gate (the tier-1 oracle, re-proven in the artifact)
    ref, pre, dec = mk(), mk("prefill"), mk("decode")
    co = DisaggCoordinator(pre, dec)
    ident = True
    for p in ([1, 2, 3, 4, 5], [9, 8, 7], list(range(2, 18))):
        a = ref.generate(list(p), max_tokens=8, temperature=0.0)
        b = co.generate(list(p), max_tokens=8, temperature=0.0)
        ident = ident and a["token_ids"] == b["token_ids"]
    gates["greedy_identical"] = ident
    for e in (ref, pre, dec):
        e.close()

    def drive(gen_fn, n_workers, prompts):
        results: list = []
        lock = _th.Lock()
        nxt = [0]

        def worker():
            while True:
                with lock:
                    if nxt[0] >= len(prompts):
                        return
                    i = nxt[0]
                    nxt[0] += 1
                r = gen_fn(i, prompts[i])
                with lock:
                    results.append(r)

        ths = [_th.Thread(target=worker, daemon=True)
               for _ in range(n_workers)]
        t0 = time.perf_counter()
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        return results, time.perf_counter() - t0

    def arm_stats(results, wall):
        toks = sum(len(r["token_ids"]) for r in results)
        ttfts = sorted(r["ttft_ms"] for r in results if r.get("ttft_ms"))
        p = lambda q: round(ttfts[min(len(ttfts) - 1,
                                      int(len(ttfts) * q))], 1)
        return {
            "requests": len(results),
            "decode_tokens_per_sec": round(toks / wall, 1),
            "ttft_ms_p50": p(0.50),
            "ttft_ms_p95": p(0.95),
        }

    rng = _np.random.default_rng(0)
    raw = {}
    for B in (1, 4, 12):
        # the gated width gets best-of-3 per arm (bench_decode's
        # min-of-trials idiom: capability, not scheduler-noise, decides)
        # and a longer sustained run for signal over host jitter
        trials = 3 if B == 12 else 2
        n_req = (6 if B == 12 else 4) * B
        prompts = [
            [int(t) for t in rng.integers(1, 200, size=prompt_len)]
            for _ in range(n_req)
        ]

        def best_of(gen_fn):
            arms = []
            for _ in range(trials):
                res, wall = drive(gen_fn, B, prompts)
                arms.append(arm_stats(res, wall))
            return max(arms, key=lambda a: a["decode_tokens_per_sec"])

        # colocated: two full engines split the streams round-robin
        e1, e2 = mk(max_batch=B), mk(max_batch=B)
        try:
            e1.generate(prompts[0], max_tokens=gen, temperature=0.0)  # warm
            e2.generate(prompts[0], max_tokens=gen, temperature=0.0)
            colo = best_of(
                lambda i, p: (e1 if i % 2 == 0 else e2).generate(
                    list(p), max_tokens=gen, temperature=0.0,
                    timeout_s=600))
        finally:
            e1.close()
            e2.close()

        # disagg: one prefill + one decode engine, handoff per request
        pre, dec = mk("prefill", max_batch=B), mk("decode", max_batch=B)
        co = DisaggCoordinator(pre, dec)
        try:
            co.generate(prompts[0], max_tokens=gen, temperature=0.0)  # warm
            dis = best_of(
                lambda i, p: co.generate(list(p), max_tokens=gen,
                                         temperature=0.0, timeout_s=600))
            dis["handoff_bytes"] = int(
                pre.metrics.handoff_bytes.value(direction="export"))
        finally:
            pre.close()
            dec.close()

        raw[f"b{B}"] = {
            "colocated": colo,
            "disagg": dis,
            "disagg_speedup": round(
                dis["decode_tokens_per_sec"]
                / colo["decode_tokens_per_sec"], 3),
        }
    out["raw"] = raw
    gates["disagg_faster_b12"] = raw["b12"]["disagg_speedup"] >= 1.2

    # --- QoS burst: overflow sheds bronze only -------------------------
    q = WeightedFairQueue(
        {"gold": QoSClassSpec(weight=8, priority=0),
         "bronze": QoSClassSpec(weight=1, priority=2)},
        capacity=2, max_queue=4,
    )
    holders = [q.acquire("bronze", timeout_s=1) for _ in range(2)]

    def contend(cls):
        try:
            q.release(q.acquire(cls, timeout_s=10))
        except QoSShed:
            pass

    bronze_ts = [_th.Thread(target=contend, args=("bronze",), daemon=True)
                 for _ in range(4)]
    for t in bronze_ts:
        t.start()
    time.sleep(0.2)  # bronze fills the queue before the gold burst
    gold_ts = [_th.Thread(target=contend, args=("gold",), daemon=True)
               for _ in range(4)]
    for t in gold_ts:
        t.start()
    time.sleep(0.3)
    for h in holders:
        q.release(h)
    for t in bronze_ts + gold_ts:
        t.join(timeout=15)
    out["qos_burst"] = {"sheds": dict(q.sheds), "admits": dict(q.admits)}
    gates["qos_gold_zero_sheds"] = q.sheds["gold"] == 0
    gates["qos_bronze_absorbs"] = q.sheds["bronze"] >= 1

    out["gates"] = gates
    out["ok"] = all(gates.values())
    return out


def bench_tracing(on_tpu: bool) -> dict:
    """Tracing overhead under load (docs/observability.md): decode
    tokens/s at 12-way concurrency on one engine, disarmed
    (``TRACER.enabled = False`` — the production default until armed)
    vs armed with EVERY request carrying a trace context, so the full
    span set (queue_wait, admission, request, prefill, per-row decode
    segments) is recorded into the ring buffer.

    Best-of-3 per arm (capability, not scheduler noise, decides).
    Acceptance: armed throughput >= 97% of disarmed — tracing must cost
    under 3% decode tokens/s or it can't stay on in production. A
    disarmed per-call microstat rides along for the README."""
    import threading as _th

    import numpy as _np

    from kubedl_tpu.observability.tracing import (
        TRACER,
        TraceContext,
        new_span_id,
        new_trace_id,
    )
    from kubedl_tpu.serving.server import LlamaEngine

    preset = "gemma-2b" if on_tpu else "tiny"
    max_seq = 256
    gen = 96
    prompt_len = 12
    B = 12
    n_req = 6 * B
    out = {"model": preset, "max_seq": max_seq, "gen_tokens": gen,
           "prompt_len": prompt_len, "concurrency": B}
    gates = {}

    rng = _np.random.default_rng(0)
    prompts = [
        [int(t) for t in rng.integers(1, 200, size=prompt_len)]
        for _ in range(n_req)
    ]

    def drive(gen_fn):
        done = []
        lock = _th.Lock()
        nxt = [0]

        def worker():
            while True:
                with lock:
                    if nxt[0] >= len(prompts):
                        return
                    i = nxt[0]
                    nxt[0] += 1
                r = gen_fn(prompts[i])
                with lock:
                    done.append(r)

        ths = [_th.Thread(target=worker, daemon=True) for _ in range(B)]
        t0 = time.perf_counter()
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        wall = time.perf_counter() - t0
        toks = sum(len(r["token_ids"]) for r in done)
        return round(toks / wall, 1)

    def best_of(gen_fn, trials=3):
        return max(drive(gen_fn) for _ in range(trials))

    was_enabled = TRACER.enabled
    eng = LlamaEngine(preset=preset, max_batch=B, max_seq=max_seq,
                      prefix_cache_mb=0)
    try:
        # full untimed warm pass: both arms must see an equally hot
        # engine, or the first-measured arm eats the warm-up bias
        TRACER.enabled = False
        drive(lambda p: eng.generate(
            list(p), max_tokens=gen, temperature=0.0, timeout_s=600))

        disarmed = best_of(lambda p: eng.generate(
            list(p), max_tokens=gen, temperature=0.0, timeout_s=600))

        TRACER.enabled = True
        TRACER.clear()
        armed = best_of(lambda p: eng.generate(
            list(p), max_tokens=gen, temperature=0.0, timeout_s=600,
            trace=TraceContext(new_trace_id(), new_span_id())))
        out["armed_spans_sample"] = len(TRACER.spans())
    finally:
        TRACER.enabled = was_enabled
        TRACER.clear()
        eng.close()

    out["disarmed_decode_tokens_per_sec"] = disarmed
    out["armed_decode_tokens_per_sec"] = armed
    out["armed_over_disarmed"] = round(armed / disarmed, 4)

    from scripts.scheduler_microbench import run_tracing_microbench

    out["disarmed_call"] = run_tracing_microbench(calls=100_000)

    gates["armed_within_3pct"] = armed >= 0.97 * disarmed
    gates["disarmed_call_within_budget"] = (
        out["disarmed_call"]["within_budget"]
    )
    out["gates"] = gates
    out["ok"] = all(gates.values())
    return out


def bench_rollout(on_tpu: bool) -> dict:
    """Model-lifecycle round (docs/serving.md "Model lifecycle"): weight
    hot-swap cost and two-version co-residency overhead on one engine.

    Arms: (1) hot-load a second version while measuring nothing — the
    build runs off the dispatch path, and the serving outputs before/
    after must stay bit-identical; (2) single-version decode tokens/s at
    B-way concurrency vs the SAME offered load split 50/50 across the
    two co-resident versions. The scheduler dispatches one version per
    tick (a mixed batch would blend weights), so the mix pays a real
    throughput price — this bench pins how much, and the gate keeps it
    from silently regressing into unusability. Best-of-2 per arm.

    Gates: outputs bit-identical through load and retire; mixed-version
    throughput >= 25% of single-version (per-tick alternation costs
    about half at small batch; below a quarter the canary path would be
    too slow to actually roll out through)."""
    import tempfile as _tf
    import threading as _th

    import numpy as _np

    from kubedl_tpu.serving.server import LlamaEngine

    preset = "gemma-2b" if on_tpu else "tiny"
    max_seq = 256
    gen = 48
    prompt_len = 12
    B = 8
    n_req = 3 * B
    out = {"model": preset, "max_seq": max_seq, "gen_tokens": gen,
           "prompt_len": prompt_len, "concurrency": B}
    gates = {}

    rng = _np.random.default_rng(0)
    prompts = [
        [int(t) for t in rng.integers(1, 200, size=prompt_len)]
        for _ in range(n_req)
    ]

    def drive(eng, versions):
        done = []
        lock = _th.Lock()
        nxt = [0]

        def worker():
            while True:
                with lock:
                    if nxt[0] >= len(prompts):
                        return
                    i = nxt[0]
                    nxt[0] += 1
                r = eng.generate(list(prompts[i]), max_tokens=gen,
                                 temperature=0.0, timeout_s=600,
                                 model_version=versions[i])
                with lock:
                    done.append((versions[i], r))

        ths = [_th.Thread(target=worker, daemon=True) for _ in range(B)]
        t0 = time.perf_counter()
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        wall = time.perf_counter() - t0
        toks = sum(len(r["token_ids"]) for _, r in done)
        return round(toks / wall, 1), done

    single_vers = [""] * n_req
    mixed_vers = ["" if i % 2 == 0 else "v2" for i in range(n_req)]

    eng = LlamaEngine(preset=preset, max_batch=B, max_seq=max_seq,
                      prefix_cache_mb=0)
    with _tf.TemporaryDirectory() as tmp:
        try:
            import jax as _jax

            from kubedl_tpu.models import llama as _llama
            from kubedl_tpu.training.checkpoint import save_checkpoint

            drive(eng, single_vers)  # untimed warm pass
            ref = eng.generate(list(prompts[0]), max_tokens=gen,
                               temperature=0.0, timeout_s=600)

            # arm 1: the hot swap itself (restore -> quantize -> commit)
            p2 = _llama.llama_init(_jax.random.PRNGKey(0), eng.cfg)
            p2 = _jax.tree_util.tree_map(lambda x: x * 1.5, p2)
            save_checkpoint(tmp, {"params": p2}, 1)
            t0 = time.perf_counter()
            eng.load_version("v2", tmp)
            out["hot_swap_load_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 1)
            after = eng.generate(list(prompts[0]), max_tokens=gen,
                                 temperature=0.0, timeout_s=600)
            gates["bit_identical_through_load"] = (
                after["token_ids"] == ref["token_ids"]
            )
            ref_v2 = eng.generate(list(prompts[0]), max_tokens=gen,
                                  temperature=0.0, timeout_s=600,
                                  model_version="v2")

            # arm 2: single-version vs 50/50 two-version mix
            single = max(drive(eng, single_vers)[0] for _ in range(2))
            mixed_best = max(drive(eng, mixed_vers)[0] for _ in range(2))
            # bit-identity under mixed traffic: after co-resident load,
            # each version still reproduces its own reference output
            mix0 = eng.generate(list(prompts[0]), max_tokens=gen,
                                temperature=0.0, timeout_s=600)
            mix2 = eng.generate(list(prompts[0]), max_tokens=gen,
                                temperature=0.0, timeout_s=600,
                                model_version="v2")
            mix_identical = (mix0["token_ids"] == ref["token_ids"]
                             and mix2["token_ids"] == ref_v2["token_ids"])

            # drain-then-evict: retire v2, base still bit-identical
            eng.retire_version("v2")
            eng.generate([2], max_tokens=1)  # admission pass evicts
            final = eng.generate(list(prompts[0]), max_tokens=gen,
                                 temperature=0.0, timeout_s=600)
            gates["bit_identical_through_retire"] = (
                final["token_ids"] == ref["token_ids"]
            )
            gates["mix_bit_identical"] = mix_identical
        finally:
            eng.close()

    out["single_version_tokens_per_sec"] = single
    out["mixed_version_tokens_per_sec"] = mixed_best
    out["mixed_over_single"] = round(mixed_best / single, 4)
    gates["mix_at_least_quarter"] = mixed_best >= 0.25 * single
    out["gates"] = gates
    out["ok"] = all(gates.values())
    return out


def bench_router_availability(on_tpu: bool) -> dict:
    """Serving-router availability through a replica kill (docs/serving.md
    "Router"): three engine replicas behind the router under steady client
    load; one replica is hard-stopped mid-run (sockets severed — the
    router sees exactly what a SIGKILL looks like) and restarted later.
    Acceptance: zero lost requests (every one completes via failover, at
    most one retry each), the breaker ejects then readmits the restarted
    replica, and greedy outputs stay bit-identical to a direct engine
    call through the whole drill."""
    import statistics
    import threading as _threading
    import time as _time
    from http.server import ThreadingHTTPServer

    from kubedl_tpu.serving import router_policy as _policy
    from kubedl_tpu.serving.router import ServingRouter
    from kubedl_tpu.serving.server import LlamaEngine, make_handler

    preset = "gemma-2b" if on_tpu else "tiny"

    def spawn(port=0):
        eng = LlamaEngine(preset=preset, max_batch=2, max_seq=64)
        srv = ThreadingHTTPServer(("127.0.0.1", port),
                                  make_handler(eng, preset))
        _threading.Thread(target=srv.serve_forever, daemon=True).start()
        return eng, srv

    fleet = {f"r{i}": spawn() for i in range(3)}
    victim = "r1"
    router = ServingRouter(
        [(n, "127.0.0.1", s.server_port) for n, (e, s) in
         sorted(fleet.items())],
        probe_interval_s=0.1, probe_timeout_s=1.0,
        eject_threshold=3, readmit_cooldown_s=0.5,
        hedge_enabled=True, hedge_default_ms=3000.0, max_retries=1,
    )
    router.start()
    router.probe_once()
    try:
        # bit-identity reference, measured direct on one engine
        ref_prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        direct = fleet["r0"][0].generate(list(ref_prompt), max_tokens=8)
        code, via, _ = router.handle_generate(
            {"prompt_ids": list(ref_prompt), "max_tokens": 8}, 30_000)
        identical = (code == 200
                     and via["token_ids"] == direct["token_ids"])

        n_req, kill_at, restart_at = 60, 20, 40
        lat_ms = [None] * n_req
        codes = [None] * n_req
        marks = {}

        def client(i):
            t0 = _time.perf_counter()
            body = {"prompt_ids": [(i % 7) + 2] * 8 + [100 + i],
                    "max_tokens": 4, "temperature": 0.0}
            c, p, _h = router.handle_generate(body, deadline_ms=20_000)
            # a 200 whose payload lacks tokens (engine torn down mid-
            # request) is NOT a success — availability counts answers
            codes[i] = c if (c != 200 or "token_ids" in p) else 599
            lat_ms[i] = (_time.perf_counter() - t0) * 1e3

        threads = []
        for i in range(n_req):
            if i == kill_at:
                eng, srv = fleet[victim]
                port = srv.server_port
                srv.shutdown()
                srv.server_close()
                eng.close()
                marks["killed"] = _time.perf_counter()
            if i == restart_at:
                fleet[victim] = spawn(port)
                marks["restarted"] = _time.perf_counter()
            t = _threading.Thread(target=client, args=(i,), daemon=True)
            t.start()
            threads.append(t)
            _time.sleep(0.05)  # ~20 rps offered over 3 replicas
        for t in threads:
            t.join(timeout=30)
        # wait out the eject -> readmit arc for the recovery timings
        deadline = _time.perf_counter() + 15
        eject_ms = readmit_ms = None
        while _time.perf_counter() < deadline:
            st = router.stats()["replicas"][victim]
            if eject_ms is None and st["ejections"] >= 1:
                eject_ms = True
            if st["state"] == _policy.CLOSED and st["ejections"] >= 1:
                readmit_ms = round(
                    (_time.perf_counter() - marks["restarted"]) * 1e3, 1)
                break
            _time.sleep(0.05)
        done = [c for c in codes if c is not None]
        okc = sum(1 for c in done if c == 200)
        lats = sorted(v for v in lat_ms if v is not None)
        st = router.stats()["replicas"][victim]
        out = {
            "model": preset,
            "replicas": 3,
            "requests": n_req,
            "completed": len(done),
            "ok": okc,
            "availability_pct": round(100.0 * okc / n_req, 2),
            "lost": n_req - len(done),
            "error_burst": len(done) - okc,
            "retries": router.metrics.retries.value(),
            "hedges": router.metrics.hedges.value(),
            "latency_ms_p50": round(statistics.median(lats), 2),
            "latency_ms_p99": round(
                lats[min(len(lats) - 1, int(len(lats) * 0.99))], 2),
            "victim_ejections": st["ejections"],
            "victim_readmissions": st["readmissions"],
            "readmit_after_restart_ms": readmit_ms,
            "greedy_outputs_identical": identical,
        }
        # sanity gates, same spirit as the training bench: an availability
        # number with lost requests or divergent outputs is not a result
        if n_req - len(done) > 0 or not identical:
            out["gate_failed"] = True
        return out
    finally:
        router.stop()
        for eng, srv in fleet.values():
            try:
                srv.shutdown()
                srv.server_close()
            except Exception:
                pass
            try:
                eng.close()
            except Exception:
                pass


def bench_planner(on_tpu: bool) -> dict:
    """Auto-parallelism planner (kubedl_tpu/planner/, docs/planning.md):

    (1) plan() host overhead over the full catalog x model-zoo admission
    matrix — the same sweep the tier-1 microbench budgets, recorded here
    so the artifact carries the headline numbers; (2) predicted-vs-
    measured step time: the cost model prices the exact (model, mesh,
    batch) the Trainer then runs on this host, and both numbers land in
    the artifact so cost-model drift is visible across rounds. On CPU the
    measured side uses the cpu-1 catalog stand-in (the ratio calibrates
    the stand-in, not real ICI); on TPU the same recipe prices the tiny
    driver shape against the detected chip."""
    import jax

    from kubedl_tpu.api.topology import MeshSpec, SliceTopology
    from kubedl_tpu.planner import ModelDesc, estimate
    from kubedl_tpu.training.data import SyntheticTokens
    from kubedl_tpu.training.trainer import TrainConfig, Trainer
    from kubedl_tpu.models import llama
    from scripts.scheduler_microbench import run_planner_microbench

    out = run_planner_microbench()

    # --- predicted vs measured on the shape this host can actually run ---
    ndev = jax.device_count()
    model = llama.TINY
    batch, seq, steps = max(2, ndev), 128, 5
    desc = ModelDesc(
        layers=model.n_layers, hidden=model.dim, ffn=model.ffn_dim,
        vocab=model.vocab_size, seq_len=seq, global_batch=batch,
        dtype="float32",
    )
    if on_tpu:
        from kubedl_tpu.api.topology import SLICE_CATALOG

        kind = jax.devices()[0].device_kind.lower()
        gen = next((t.name.split("-")[0] for t in SLICE_CATALOG.values()
                    if t.name.split("-")[0] in kind), "v5e")
        base = next(t for t in SLICE_CATALOG.values()
                    if t.name.startswith(gen + "-"))
        topo = SliceTopology(f"{gen}-bench", ndev, 1, ndev, (ndev,),
                             base.peak_bf16_tflops, base.hbm_gib_per_chip,
                             base.hbm_gbps, base.ici_gbps, base.dcn_gbps)
    else:
        from kubedl_tpu.api.topology import get_slice

        cpu1 = get_slice("cpu-1")
        topo = SliceTopology("cpu-bench", ndev, 1, ndev, (ndev,),
                             cpu1.peak_bf16_tflops, cpu1.hbm_gib_per_chip,
                             cpu1.hbm_gbps, cpu1.ici_gbps, cpu1.dcn_gbps)
    mesh = MeshSpec({"data": ndev})
    predicted = estimate(desc, topo, mesh)
    cfg = TrainConfig(model=model, global_batch=batch, seq_len=seq,
                      steps=steps)
    trainer = Trainer(cfg)
    _, s = trainer.fit(iter(SyntheticTokens(batch, seq, model.vocab_size)))
    measured_ms = float(s["step_time_ms"])
    out.update({
        "predicted_step_ms": round(predicted.step_ms, 2),
        "predicted_compute_ms": round(predicted.compute_ms, 2),
        "predicted_hbm_gib": round(predicted.hbm_gib, 4),
        "measured_step_ms": round(measured_ms, 2),
        "predicted_over_measured": round(
            predicted.step_ms / measured_ms, 4
        ) if measured_ms > 0 else None,
        "pv_mesh": mesh.to_env(),
        "pv_devices": ndev,
        "pv_platform": "tpu" if on_tpu else "cpu",
    })
    return out


def bench_training(runs: int = 3) -> list:
    """Sharded weight update + comm/compute overlap (docs/performance.md
    "Sharded weight update & overlap"): per-phase step decomposition for
    the replicated / sharded / sharded_overlap arms, measured by
    kubedl_tpu/training/stepbench.py in a SUBPROCESS so the device-count
    env lands before jax initializes. Each run's flattened medians land
    in runs[].detail.targets.training; the acceptance proxies the CPU CI
    gate compares (exposed comm+update and optimizer-state bytes/replica,
    both vs the replicated baseline arm) ride every run."""
    import subprocess
    import tempfile

    out_runs = []
    for _ in range(runs):
        with tempfile.NamedTemporaryFile(suffix=".json") as f:
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)  # stepbench sets the device count
            proc = subprocess.run(
                [sys.executable, "-m", "kubedl_tpu.training.stepbench",
                 "--devices", "4", "--json", f.name],
                env=env, capture_output=True, text=True, timeout=1800,
            )
            if proc.returncode != 0:
                raise RuntimeError(
                    f"stepbench failed rc={proc.returncode}: "
                    f"{proc.stderr[-2000:]}"
                )
            r = json.loads(open(f.name).read())
        rep = r["arms"]["replicated"]
        ovl = r["arms"]["sharded_overlap"]
        best = r["arms"][r["proxy"]["best_arm"]]
        out_runs.append({
            "devices": r["devices"],
            "mesh": r["mesh"],
            "model_params": r["model_params"],
            "grad_accum": r["grad_accum"],
            "compute_ms": round(r["compute_ms"], 2),
            "step_ms_replicated": round(rep["step_ms"], 2),
            "step_ms_overlap": round(ovl["step_ms"], 2),
            "update_ms_replicated": round(rep["update_ms"], 2),
            "update_ms_overlap": round(ovl["update_ms"], 2),
            "exposed_comm_ms_replicated": round(rep["exposed_comm_ms"], 2),
            "exposed_comm_ms_overlap": round(ovl["exposed_comm_ms"], 2),
            # the proxy the acceptance gate compares: everything that is
            # NOT arm-invariant compute (collectives + optimizer apply),
            # replicated baseline vs the best sharded arm (XLA:CPU has no
            # async-collective engine, so the overlap schedule's extra
            # in-loop scatters are not free here — see stepbench.py)
            "best_arm": r["proxy"]["best_arm"],
            "noncompute_ms_replicated": round(
                rep["exposed_comm_ms"] + rep["update_ms"], 2
            ),
            "noncompute_ms_overlap": round(
                ovl["exposed_comm_ms"] + ovl["update_ms"], 2
            ),
            "noncompute_ms_best": round(
                best["exposed_comm_ms"] + best["update_ms"], 2
            ),
            "opt_state_bytes_replicated":
                rep["opt_state_bytes_per_device"],
            "opt_state_bytes_sharded":
                best["opt_state_bytes_per_device"],
            "grad_buckets": best["grad_buckets"],
            "max_loss_delta": r["proxy"]["max_loss_delta"],
            "exposed_comm_reduced": r["proxy"]["exposed_comm_reduced"],
            "opt_state_bytes_reduced":
                r["proxy"]["opt_state_bytes_reduced"],
            "arms": r["arms"],
        })
    return out_runs


def _submit_and_wait(op, name: str, container, get_summary) -> dict:
    """Shared headline scaffolding: submit a single-worker TPUJob built
    around ``container``, wait for a terminal phase, and return the worker
    summary (via ``get_summary``) stamped with startup-to-first-step."""
    from kubedl_tpu.api.types import (
        JobConditionType, ReplicaSpec, ReplicaType, RestartPolicy,
    )
    from kubedl_tpu.workloads.tpujob import TPUJob

    job = TPUJob()
    job.metadata.name = name
    spec = ReplicaSpec(replicas=1, restart_policy=RestartPolicy.ON_FAILURE_SLICE)
    spec.template.spec.containers.append(container)
    job.spec.replica_specs[ReplicaType.WORKER] = spec
    t_submit = time.time()
    op.submit(job)
    got = op.wait_for_phase(
        "TPUJob", name,
        [JobConditionType.SUCCEEDED, JobConditionType.FAILED],
        timeout=1800,
    )
    if got.status.phase != JobConditionType.SUCCEEDED:
        raise RuntimeError(
            f"bench job {name} failed: "
            + "; ".join(c.message for c in got.status.conditions)
        )
    summary = get_summary()
    summary["_startup_to_first_step"] = max(
        summary.get("first_step_wall_time", 0.0) - t_submit, 0.0
    )
    return summary


def _run_headline(op, name: str, train_cfg: dict, log_dir: str) -> dict:
    """Headline via a SUBPROCESS worker (a fresh process = exactly what a
    gang restart / resize / resume launches); summary parsed from the pod
    log."""
    from kubedl_tpu.core.objects import Container, EnvVar

    container = Container(
        command=[sys.executable, "-m", "kubedl_tpu.training.entry"],
        env=[EnvVar("KUBEDL_TRAIN_CONFIG", json.dumps(train_cfg))],
    )
    from kubedl_tpu.runtime.executor import read_worker_summary

    return _submit_and_wait(op, name, container, lambda: read_worker_summary(
        os.path.join(log_dir, "default", f"{name}-worker-0.log")
    ))


def main() -> int:
    if "--planner" in sys.argv[1:]:
        # standalone planner round (BENCH_r09_planner.json): no training
        # driver, no warm/cold gates — just the planner targets in the
        # same runs[] shape check_readme_numbers reads
        import jax as _jax

        _on_tpu = _jax.default_backend() == "tpu"
        print(json.dumps({
            "runs": [{"detail": {"targets": {
                "planner": bench_planner(_on_tpu)
            }}}],
        }, indent=2))
        return 0
    if "--decode" in sys.argv[1:]:
        # standalone decode round (BENCH_r16_decode.json): blocked vs
        # gather kernel sweep + draft-speculation arms + open-loop
        # Poisson admission arms in the same runs[] shape
        # check_readme_numbers reads; its own gates decide the exit
        # code (a blocked kernel that loses to the gather, any arm
        # diverging from the oracle stream, or chunked admission losing
        # the TTFT race it exists to win, fails loudly)
        import jax as _jax

        d = bench_decode(_jax.default_backend() == "tpu")
        print(json.dumps({
            "runs": [{"detail": {"targets": {"decode": d}}}],
        }, indent=2))
        return 0 if d["ok"] else 1
    if "--shards" in sys.argv[1:]:
        # standalone sharded-control-plane round (BENCH_r18_shards.json):
        # 10k-job / 100k-pod churn replay, 1-shard vs 4-shard arms in the
        # same runs[] shape check_readme_numbers reads; the
        # 4-beats-1-on-p99-and-median-launch gates decide the exit code.
        # Pure control plane — no accelerator in the loop.
        d = bench_shards()
        print(json.dumps({
            "runs": [{"detail": {"targets": {"shards": d}}}],
        }, indent=2))
        return 0 if d["ok"] else 1
    if "--cp-scale" in sys.argv[1:]:
        # standalone control-plane scaling round (BENCH_r19_cp_scale.json):
        # the churn replay at 1/2/4/8 shards with WAL group commit, event
        # coalescing, and batched gang writes on, in the same runs[] shape
        # check_readme_numbers reads; gates (4-shard >= 2x 1-shard jobs/s,
        # queue wait p99 <= 1/5 of r18, fsyncs <= appends/20) decide the
        # exit code. Pure control plane — no accelerator in the loop.
        d = bench_cp_scale()
        print(json.dumps({
            "runs": [{"detail": {"targets": {"cp_scale": d}}}],
        }, indent=2))
        return 0 if d["ok"] else 1
    if "--federation" in sys.argv[1:]:
        # standalone federation round (BENCH_r20_federation.json): the
        # churn replay spread across 4 real operator processes over one
        # 8-shard WAL/lease root, plus the seeded member-SIGKILL arm
        # (lease reconvergence, orphan drain, zero duplicate launches in
        # the shared ledger), in the same runs[] shape
        # check_readme_numbers reads; gates decide the exit code. Pure
        # control plane — no accelerator in the loop.
        d = bench_federation()
        print(json.dumps({
            "runs": [{"detail": {"targets": {"federation": d}}}],
        }, indent=2))
        return 0 if d["ok"] else 1
    if "--disagg" in sys.argv[1:]:
        # standalone disaggregation round (BENCH_r12_disagg.json):
        # colocated vs prefill/decode-split arms at 1/4/12-way plus the
        # QoS overload burst, in the same runs[] shape
        # check_readme_numbers reads; gates (bit-identity, >=1.2x at
        # 12-way, gold-never-sheds) decide the exit code
        import jax as _jax

        d = bench_disagg(_jax.default_backend() == "tpu")
        print(json.dumps({
            "runs": [{"detail": {"targets": {"disagg": d}}}],
        }, indent=2))
        return 0 if d["ok"] else 1
    if "--tracing" in sys.argv[1:]:
        # standalone tracing-overhead round (BENCH_r13_tracing.json):
        # armed vs disarmed decode throughput at 12-way plus the
        # disarmed per-call microstat, in the same runs[] shape
        # check_readme_numbers reads; the <3% gate decides the exit code
        import jax as _jax

        d = bench_tracing(_jax.default_backend() == "tpu")
        print(json.dumps({
            "runs": [{"detail": {"targets": {"tracing": d}}}],
        }, indent=2))
        return 0 if d["ok"] else 1
    if "--rollout" in sys.argv[1:]:
        # standalone model-lifecycle round (BENCH_r17_rollout.json):
        # weight hot-swap wall-time plus single-version vs 50/50
        # two-version decode throughput on one engine, in the same
        # runs[] shape check_readme_numbers reads; the gates (bit-
        # identity through load/mix/retire, mix >= 25% of single)
        # decide the exit code
        import jax as _jax

        d = bench_rollout(_jax.default_backend() == "tpu")
        print(json.dumps({
            "runs": [{"detail": {"targets": {"rollout": d}}}],
        }, indent=2))
        return 0 if d["ok"] else 1
    if "--ps" in sys.argv[1:]:
        # standalone parameter-service round (BENCH_r15_ps.json): the
        # preemption-storm restart-vs-PS arms + the convergence-
        # equivalence gate, in the same runs[] shape
        # check_readme_numbers reads; the gates (PS goodput strictly
        # above the restart arm at equal storm schedule, final loss
        # within PS_LOSS_TOL of sync) decide the exit code
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            # the PS arms want a 2-way data mesh even on a 1-CPU host
            # (same virtual-device trick as tests/conftest.py); set
            # before the first jax import so it lands pre-backend-init
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2"
            ).strip()
        d = bench_ps()
        print(json.dumps({
            "runs": [{"detail": {"targets": {"ps": d}}}],
        }, indent=2))
        return 0 if d["ok"] else 1
    if "--training" in sys.argv[1:]:
        # standalone training-update round (BENCH_r10_training.json):
        # per-phase sharded-update/overlap medians in the same runs[]
        # shape check_readme_numbers reads
        print(json.dumps({
            "runs": [
                {"detail": {"targets": {"training": r}}}
                for r in bench_training()
            ],
        }, indent=2))
        return 0
    from kubedl_tpu.operator import Operator, OperatorOptions
    from kubedl_tpu.runtime.executor import SubprocessRuntime
    from tempfile import TemporaryDirectory

    summary_warm = None
    warm_error = ""  # why warm is missing: gate-relevant
    # this parent stays off jax until both headline workers are done (a
    # chip belongs to one process), so the size is picked from what the
    # environment asks for and the platform comes from the worker itself
    cpu_requested = os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"
    with TemporaryDirectory() as tmp:
        # Bench model: sized for one chip; scaled down for CPU smoke runs.
        if not cpu_requested:
            train_cfg = {
                "model": "bench-350m",
                "global_batch": 8,
                "seq_len": 2048,
                "steps": 20,
                # bf16 adam first moment: frees 0.9GB of HBM, measured
                # fastest in the round-4 full-step sweep (601 -> 597ms)
                "opt_moment_dtype": "bfloat16",
            }
        else:
            # 32 steps: the tiny model's only learnable signal is the
            # init-loss gap above ln(vocab); at 8 steps (inside the lr
            # warmup) the loss-decrease sanity gate is a coin flip
            train_cfg = {
                "model": "tiny", "global_batch": 8, "seq_len": 128,
                "steps": 32, "learning_rate": 3e-3,
            }

        logs = os.path.join(tmp, "logs")
        # cold AND warm startup measured against the SAME compile cache at
        # its fixed place (OperatorOptions' default, or where
        # JAX_COMPILATION_CACHE_DIR says): job 1 populates it (cold only
        # where the directory starts empty), job 2 (a brand-new process,
        # the gang-restart shape) must deserialize instead of recompile
        opts = OperatorOptions(
            local_addresses=True,
            artifact_registry_root=os.path.join(tmp, "reg"),
            pod_log_dir=logs,
        )
        with Operator(opts, runtime=SubprocessRuntime(logs)) as op:
            summary = _run_headline(op, "bench-cold", train_cfg, logs)
            try:
                summary_warm = _run_headline(op, "bench-warm", train_cfg, logs)
            except Exception as e:
                warm_error = str(e)
                print(json.dumps({"warm_run_error": warm_error}),
                      file=sys.stderr)
    platform = summary["device"]["platform"]
    on_tpu = platform == "tpu"
    if not (on_tpu or cpu_requested):
        raise RuntimeError(
            f"headline worker ran on {summary['device']}, and the "
            "environment did not ask for the CPU"
        )

    # ---- hard sanity gates --------------------------------------------
    violations = list(summary.get("sanity_violations") or [])
    if on_tpu:
        if summary.get("attn_impl") != "flash":
            violations.append(
                f"TPU bench ran attn_impl={summary.get('attn_impl')!r}, "
                "expected the pallas flash kernel"
            )
        elif not summary.get("flash_trace_count"):
            violations.append(
                "attn_impl claims flash but the pallas kernel was never traced"
            )
        if summary_warm is not None:
            # the round-trip proof is the warm worker's own cache events:
            # every compile it asked for was served from the directory
            cold_s = summary.get("_startup_to_first_step", 0.0)
            warm_s = summary_warm.get("_startup_to_first_step", 0.0)
            warm_cc = summary_warm["compile_cache"]
            if not (warm_cc["cache_hits"] > 0 and warm_cc["cache_misses"] == 0):
                violations.append(
                    f"warm job recompiled — compile cache not hitting "
                    f"({warm_cc}; cold {summary['compile_cache']})"
                )
            elif summary["compile_cache"]["cache_misses"] and warm_s >= cold_s:
                # the FULL warm summary rides the violation (round-4
                # VERDICT: the payload omitted first_step/pre_loop_sync,
                # so the one failing artifact could not be diagnosed)
                violations.append(
                    f"warm startup {warm_s:.1f}s not better than cold "
                    f"{cold_s:.1f}s though every compile hit the cache "
                    f"(cold summary {summary}; warm summary {summary_warm})"
                )
        else:
            # cold produced a summary but warm did not: the feature this
            # gate validates is broken
            violations.append(f"warm run missing: {warm_error or 'unknown'}")
    flash_numerics = None
    if on_tpu:
        from kubedl_tpu.ops import kernel_check

        # GQA group of 2, four k-tiles: dense, fused-vs-split, rope
        flash_numerics = kernel_check.flash_check(1, 1024, 4, 2, 64, block=256)
        if not (flash_numerics["ok"] and flash_numerics["compiled"]):
            violations.append(
                f"flash kernel numerics gate failed on chip: {flash_numerics}"
            )
    if violations:
        print(
            json.dumps({"error": "bench sanity gates failed",
                        "violations": violations, "summary": summary}),
            file=sys.stderr,
        )
        return 1

    # ---- secondary BASELINE.md targets -------------------------------
    targets: dict = {}
    # kind-e2e verdict rides EVERY artifact (VERDICT #8: the real-cluster
    # e2e has never executed — keep that gap visible instead of implicit).
    # attempted=True only when a kind binary AND an e2e driver both exist.
    import shutil as _shutil

    kind_bin = _shutil.which("kind")
    e2e_driver = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "scripts", "kind_e2e.sh")
    if kind_bin is None:
        targets["kind_e2e"] = {
            "attempted": False, "verdict": "skipped",
            "reason": "no `kind` binary on PATH in this environment",
        }
    elif not os.path.exists(e2e_driver):
        targets["kind_e2e"] = {
            "attempted": False, "verdict": "skipped",
            "reason": f"kind present at {kind_bin} but no e2e driver "
                      "(scripts/kind_e2e.sh) exists in the repo yet",
        }
    else:
        import subprocess as _sp

        try:
            proc = _sp.run([e2e_driver], capture_output=True, text=True,
                           timeout=1800)
            targets["kind_e2e"] = {
                "attempted": True,
                "verdict": "passed" if proc.returncode == 0 else "failed",
                "reason": (proc.stderr or proc.stdout or "")[-2000:],
            }
        except Exception as e:
            targets["kind_e2e"] = {
                "attempted": True, "verdict": "failed", "reason": str(e),
            }
    # every target keeps printing its result or its error, and any error
    # makes the exit code nonzero
    for name, fn in (
        ("control_plane", bench_control_plane),
        ("serving", lambda: bench_serving(on_tpu)),
        ("serving_engine", lambda: bench_serving_engine(
            on_tpu, targets.get("serving") or {})),
        ("prefix_reuse", lambda: bench_prefix_reuse(on_tpu)),
        ("paged_kv", lambda: bench_paged_kv(on_tpu)),
        ("speculative", lambda: bench_speculative(on_tpu)),
        ("router_availability", lambda: bench_router_availability(on_tpu)),
        ("long_context", lambda: bench_long_context(on_tpu)),
        ("goodput_under_preemption", bench_goodput_under_preemption),
        ("crash_recovery", bench_crash_recovery),
        ("checkpoint_overhead", bench_checkpoint_overhead),
        ("planner", lambda: bench_planner(on_tpu)),
    ):
        try:
            targets[name] = fn()
        except Exception as e:
            targets[name] = {"error": str(e)}
    failed = sorted(k for k, v in targets.items() if "error" in v)

    tps_chip = summary["tokens_per_sec_per_chip"]
    mfu = summary["mfu"]
    vs_baseline = (mfu / 0.10) if on_tpu and mfu > 0 else 1.0
    print(
        json.dumps(
            {
                "metric": "tokens_per_sec_per_chip",
                "value": round(tps_chip, 2),
                "unit": "tokens/s/chip",
                "vs_baseline": round(vs_baseline, 3),
                "detail": {
                    "platform": platform,
                    "device": summary["device"],
                    "mfu": round(mfu, 4),
                    "attn_impl": summary.get("attn_impl"),
                    "first_step_seconds": round(summary["first_step_seconds"], 2),
                    "startup_to_first_step_seconds": round(
                        summary.get("_startup_to_first_step", 0.0), 2
                    ),
                    "first_step_seconds_warm": round(
                        summary_warm["first_step_seconds"], 2
                    ) if summary_warm else None,
                    "startup_to_first_step_warm_seconds": round(
                        summary_warm.get("_startup_to_first_step", 0.0), 2
                    ) if summary_warm else None,
                    "warm_speedup_pct": round(
                        100.0
                        * (1 - summary_warm["_startup_to_first_step"]
                           / summary["_startup_to_first_step"]), 1,
                    ) if summary_warm
                    and summary.get("_startup_to_first_step") else None,
                    "startup_phases_cold": summary.get("startup_phases"),
                    "startup_phases_warm": (
                        summary_warm.get("startup_phases")
                        if summary_warm else None
                    ),
                    "compile_cache_cold": summary.get("compile_cache"),
                    "compile_cache_warm": (
                        summary_warm.get("compile_cache")
                        if summary_warm else None
                    ),
                    "warm_unavailable": warm_error or None,
                    "flash_numerics": flash_numerics,
                    "step_time_ms": round(summary["step_time_ms"], 2),
                    "hbm_floor_ms": round(summary.get("hbm_floor_ms", 0.0), 2),
                    "first_loss": round(summary.get("first_loss") or 0.0, 4),
                    "final_loss": round(summary["final_loss"], 4),
                    "sanity": "all gates passed",
                    "targets": targets,
                },
            }
        )
    )
    if failed:
        print(json.dumps({"error": "bench targets failed", "targets": failed}),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
