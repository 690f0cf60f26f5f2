"""Regression tests for control-plane bugs found in review."""

import time

import pytest

from kubedl_tpu.api import constants
from kubedl_tpu.api.topology import get_slice, peak_flops_for_device_kind
from kubedl_tpu.api.types import (
    JobConditionType,
    ReplicaSpec,
    ReplicaType,
    RestartPolicy,
)
from kubedl_tpu.core.objects import Container, PodPhase
from kubedl_tpu.engine.expectations import ControllerExpectations, expectation_key
from kubedl_tpu.gang.slice_scheduler import SliceInventory
from kubedl_tpu.runtime.executor import ThreadRuntime

from tests.helpers import PodDriver, env_of, make_tpujob, pod_names
from tests.test_engine import make_engine, submit_and_reconcile


def test_evaluator_success_does_not_complete_job():
    """DEFAULT policy: only WORKER index-0 finishing succeeds a masterless
    job; a fast evaluator must not kill running workers."""
    engine, store, _ = make_engine()
    driver = PodDriver(store)
    job = make_tpujob(workers=2)
    ev = ReplicaSpec(replicas=1, restart_policy=RestartPolicy.NEVER)
    ev.template.spec.containers.append(Container())
    job.spec.replica_specs[ReplicaType.EVALUATOR] = ev
    submit_and_reconcile(engine, store, job)
    driver.run("job1-worker-0")
    driver.run("job1-worker-1")
    driver.succeed("job1-evaluator-0")
    engine.reconcile("default", "job1")
    got = store.get("TPUJob", "job1")
    assert got.status.phase != JobConditionType.SUCCEEDED
    assert "job1-worker-0" in pod_names(store)  # workers untouched
    driver.succeed("job1-worker-0")
    engine.reconcile("default", "job1")
    assert store.get("TPUJob", "job1").status.phase == JobConditionType.SUCCEEDED


def test_expectation_prefix_is_slash_bounded():
    exps = ControllerExpectations()
    exps.expect_creations(expectation_key("default/train2", "Worker", "pods"), 3)
    assert exps.all_satisfied("default/train")  # train != train2
    assert not exps.all_satisfied("default/train2")
    exps.delete_job_expectations("default/train2")
    assert exps.all_satisfied("default/train2")


def test_thread_runtime_systemexit_string_is_failure():
    import sys

    handle = ThreadRuntime.spawn(lambda env: sys.exit("fatal: bad config"), {})
    assert handle.wait() == 1


def test_thread_runtime_exit_codes():
    import sys

    assert ThreadRuntime.spawn(lambda env: None, {}).wait() == 0
    assert ThreadRuntime.spawn(lambda env: 3, {}).wait() == 3
    assert ThreadRuntime.spawn(lambda env: sys.exit(9), {}).wait() == 9
    assert ThreadRuntime.spawn(lambda env: sys.exit(None), {}).wait() == 0


def test_multislice_defaults_demand_and_env():
    """num_slices=2 on v5e-8: 4 workers over 2 slices, consistent
    MEGASCALE env, both slices reserved."""
    inventory = SliceInventory()
    inventory.add_slice("s1", "v5e-8")
    inventory.add_slice("s2", "v5e-8")
    engine, store, _ = make_engine(inventory=inventory)
    job = make_tpujob("ms", workers=1, topology=get_slice("v5e-8"))
    job.num_slices = 2
    submit_and_reconcile(engine, store, job)
    names = pod_names(store)
    assert len(names) == 4  # 2 slices x 2 hosts
    # slice assignment spans both slices
    slices = {store.get("Pod", n).spec.slice_assignment for n in names}
    assert slices == {"s1", "s2"}
    # MEGASCALE env consistent with physical binding
    for n in names:
        pod = store.get("Pod", n)
        env = env_of(pod)
        assert env[constants.ENV_MEGASCALE_NUM_SLICES] == "2"
        expected_slice = {"s1": "0", "s2": "1"}[pod.spec.slice_assignment]
        assert env[constants.ENV_MEGASCALE_SLICE_ID] == expected_slice, n
    assert inventory.describe() == {"s1": "default/ms-gang", "s2": "default/ms-gang"}


def test_evaluator_not_bound_to_slice_hosts():
    """Topology-less evaluator must not double-book slice hosts."""
    inventory = SliceInventory()
    inventory.add_slice("s1", "v5e-8")
    engine, store, _ = make_engine(inventory=inventory)
    driver = PodDriver(store)
    job = make_tpujob("j", workers=2, topology=get_slice("v5e-8"))
    from kubedl_tpu.api.types import DAGCondition, ReplicaPhase

    ev = ReplicaSpec(replicas=1, restart_policy=RestartPolicy.NEVER)
    ev.template.spec.containers.append(Container())
    job.spec.replica_specs[ReplicaType.EVALUATOR] = ev
    submit_and_reconcile(engine, store, job)
    worker_nodes = {
        store.get("Pod", n).spec.node_name
        for n in pod_names(store)
        if "worker" in n
    }
    ev_pod = store.get("Pod", "j-evaluator-0")
    assert ev_pod.spec.node_name == ""  # unconstrained, not a slice host
    assert worker_nodes == {"s1-host-0", "s1-host-1"}


def test_peak_flops_lookup_from_catalog():
    assert peak_flops_for_device_kind("TPU v5 lite") == 197e12
    assert peak_flops_for_device_kind("TPU v4") == 275e12
    assert peak_flops_for_device_kind("TPU v6 lite") == 918e12
    assert peak_flops_for_device_kind("Intel Xeon") == 0.0
    # a TPU the table does not know is an error, not a peak of 0
    with pytest.raises(ValueError, match="not in the slice catalog"):
        peak_flops_for_device_kind("TPU v9 ultra")


def test_kubelet_configmap_resync_does_not_deadlock():
    """Reconciling a RUNNING pod that mounts a ConfigMap volume must
    re-materialize without re-entering the kubelet lock (deadlock found in
    review: reconcile held self._lock while _materialize_config_volumes
    acquired it again)."""
    import threading

    from kubedl_tpu.core.objects import ConfigMap, Pod, Volume
    from kubedl_tpu.core.store import ObjectStore
    from kubedl_tpu.runtime.executor import Kubelet, _PlaceholderHandle

    store = ObjectStore()
    cm = ConfigMap(data={"hostfile": "127.0.0.1 slots=1\n"})
    cm.metadata.name = "job-config"
    store.create(cm)
    pod = Pod()
    pod.metadata.name = "p1"
    import tempfile

    mount = tempfile.mkdtemp()
    pod.spec.volumes.append(Volume(name="cfg", config_map="job-config",
                                   mount_path=mount))
    pod.status.phase = PodPhase.RUNNING
    created = store.create(pod)

    kubelet = Kubelet(store, ThreadRuntime())
    with kubelet._lock:
        pass  # sanity: lock is free
    kubelet._running["default/p1"] = _PlaceholderHandle()

    done = threading.Event()

    def run():
        kubelet.reconcile("default", "p1")
        done.set()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert done.wait(5.0), "kubelet reconcile deadlocked"
    import os

    assert os.path.exists(os.path.join(mount, "hostfile"))


def test_cron_long_outage_fires_fresh_run_once():
    """After an outage far past the missed-run warning threshold, exactly
    ONE run fires and it carries the MOST RECENT slot time (review bug:
    capped accounting returned the oldest slot, launching stale runs)."""
    from datetime import datetime

    from kubedl_tpu.core.store import ObjectStore
    from kubedl_tpu.cron.controller import CronController
    from kubedl_tpu.cron.types import Cron
    from tests.test_cron import FakeClock, make_template, ts

    store = ObjectStore()
    clock = FakeClock(ts(2026, 1, 1, 10, 0))
    ctrl = CronController(store, ["TPUJob"], clock=clock)
    cron = Cron(schedule="* * * * *", template=make_template())
    cron.metadata.name = "mn"
    cron.metadata.creation_timestamp = clock.t
    store.create(cron)
    clock.t = ts(2026, 1, 3, 10, 0)  # 2 days of missed minutes
    ctrl.reconcile("default", "mn")
    jobs = store.list("TPUJob")
    assert len(jobs) == 1
    got = store.get("Cron", "mn")
    assert got.last_schedule_time == ts(2026, 1, 3, 10, 0)  # freshest slot
    # immediate re-reconcile must NOT fire another stale run
    ctrl.reconcile("default", "mn")
    assert len(store.list("TPUJob")) == 1
