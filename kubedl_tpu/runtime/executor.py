"""Kubelet: watches pods, runs containers, reports phases back to the store."""

from __future__ import annotations

import importlib
import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time
import traceback
from typing import Callable, Dict, Optional

from kubedl_tpu.core.manager import ControllerManager
from kubedl_tpu.core.objects import BaseObject, ContainerStatus, Pod, PodPhase
from kubedl_tpu.core.store import NotFound, ObjectStore

log = logging.getLogger("kubedl_tpu.runtime")

#: OS pid of the pod's main process, stamped at launch — the handle a
#: RESTARTED kubelet needs to re-attach to (adopt) a still-running pod
#: instead of orphaning or re-creating it (docs/robustness.md)
PID_ANNOTATION = "kubedl-tpu.io/runtime-pid"


class ProcHandle:
    """One running container; wait() returns the exit code."""

    def wait(self) -> int:
        raise NotImplementedError

    def kill(self) -> None:
        raise NotImplementedError

    def pid(self) -> Optional[int]:
        """OS pid when the container is a real process (adoptable across
        operator restarts); None for thread/placeholder handles."""
        return None


class ContainerRuntime:
    def start(self, pod: Pod, env: Dict[str, str]) -> ProcHandle:
        raise NotImplementedError


class _SubprocHandle(ProcHandle):
    def __init__(self, proc: subprocess.Popen) -> None:
        self.proc = proc

    def wait(self) -> int:
        return self.proc.wait()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=3)
            except subprocess.TimeoutExpired:
                self.proc.kill()

    def pid(self) -> Optional[int]:
        return self.proc.pid


class _AttachedHandle(ProcHandle):
    """A process launched by a PREVIOUS operator incarnation, re-attached
    by pid after a restart. When the pid is still this process's child
    (in-process crash simulation) ``waitpid`` yields the real exit status;
    an orphan reparented to init can only be liveness-polled, so its exit
    reads as 0 — a non-child cannot be reaped, which is the documented
    adoption limit (real kubelets read containerd state instead)."""

    def __init__(self, pid: int) -> None:
        self._pid = pid

    def pid(self) -> Optional[int]:
        return self._pid

    def _poll(self) -> Optional[int]:
        """None while alive; exit code once gone."""
        try:
            done, status = os.waitpid(self._pid, os.WNOHANG)
            if done == self._pid:
                if os.WIFEXITED(status):
                    return os.WEXITSTATUS(status)
                if os.WIFSIGNALED(status):
                    return -os.WTERMSIG(status)
                return 1
            return None
        except ChildProcessError:
            pass  # not our child (true orphan) or already reaped elsewhere
        try:
            os.kill(self._pid, 0)
            return None
        except ProcessLookupError:
            return 0  # gone; exit code unknowable for a non-child
        except PermissionError:
            return None  # alive, different user

    def wait(self) -> int:
        while True:
            code = self._poll()
            if code is not None:
                return code
            time.sleep(0.05)

    def kill(self) -> None:
        try:
            os.kill(self._pid, signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            return
        deadline = time.time() + 3.0
        while time.time() < deadline:
            if self._poll() is not None:
                return
            time.sleep(0.05)
        try:
            os.kill(self._pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


class SubprocessRuntime(ContainerRuntime):
    """Run the main container's argv as a real OS process. `python` in the
    argv resolves to the current interpreter so env (JAX flags) carries."""

    def __init__(self, log_dir: str = "") -> None:
        self.log_dir = log_dir

    def start(self, pod: Pod, env: Dict[str, str]) -> ProcHandle:
        main = pod.spec.main_container()
        argv = list(main.command)
        if not argv:
            raise ValueError(f"pod {pod.metadata.name}: empty command")
        if argv[0] == "python":
            argv[0] = sys.executable
        full_env = {**os.environ, **env}
        # spawn timestamp: entrypoints attribute pod-spawn -> process-start
        # latency in their startup breakdown (launch-delay parity with the
        # reference's job_metrics.go:139-194, but per-phase)
        full_env.setdefault("KUBEDL_SPAWN_TS", repr(time.time()))
        stdout = None
        if self.log_dir:
            # namespaced: same-named pods in different namespaces must not
            # share (or leak) a log file
            ns_dir = os.path.join(self.log_dir, pod.metadata.namespace)
            os.makedirs(ns_dir, exist_ok=True)
            stdout = open(  # noqa: SIM115 - handle outlives this scope
                os.path.join(ns_dir, f"{pod.metadata.name}.log"), "ab"
            )
        proc = subprocess.Popen(
            argv,
            env=full_env,
            cwd=main.working_dir or None,
            stdout=stdout,
            stderr=subprocess.STDOUT if stdout else None,
        )
        return _SubprocHandle(proc)


def read_worker_summary(log_path: str) -> dict:
    """The last ``worker_summary`` line the training entry printed into a
    pod log under ``SubprocessRuntime.log_dir``. Lives here, not beside
    its writer, because what reads it back is an operator-side parent
    that must not import jax (a chip belongs to one process)."""
    summary = None
    with open(log_path, errors="replace") as f:
        for line in f:
            if '"worker_summary"' in line:
                try:
                    summary = json.loads(line)["worker_summary"]
                except json.JSONDecodeError:
                    continue
    if summary is None:
        raise RuntimeError(f"no worker_summary in {log_path}")
    return summary


#: env key under which ThreadRuntime passes the cancellation Event object
#: (entrypoints poll `env.get(CANCEL_EVENT_KEY)` between steps; cooperative
#: — threads can't be killed)
CANCEL_EVENT_KEY = "_KUBEDL_CANCEL"


class _ThreadHandle(ProcHandle):
    def __init__(self, fn: Callable[[Dict[str, str]], object], env: Dict[str, str]) -> None:
        self._exit = 0
        self._done = threading.Event()
        self._cancel = threading.Event()
        env = dict(env)
        env[CANCEL_EVENT_KEY] = self._cancel  # type: ignore[assignment]

        def run() -> None:
            try:
                rc = fn(env)
                self._exit = int(rc) if isinstance(rc, int) else 0
            except SystemExit as e:
                # sys.exit(None)=0, sys.exit(int)=int, sys.exit(str)=failure
                if e.code is None:
                    self._exit = 0
                elif isinstance(e.code, int):
                    self._exit = e.code
                else:
                    log.error("entrypoint exited with message: %s", e.code)
                    self._exit = 1
            except Exception:
                log.error("entrypoint raised:\n%s", traceback.format_exc())
                self._exit = 1
            finally:
                self._done.set()

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def wait(self) -> int:
        self._done.wait()
        return self._exit

    def kill(self) -> None:
        # threads are not killable; entrypoints poll env[CANCEL_EVENT_KEY]
        self._cancel.set()


class ThreadRuntime(ContainerRuntime):
    """Resolve `container.entrypoint` ("pkg.mod:fn") and call fn(env) in a
    thread. fn returns an int exit code (or None == 0)."""

    def start(self, pod: Pod, env: Dict[str, str]) -> ProcHandle:
        main = pod.spec.main_container()
        if not main.entrypoint:
            raise ValueError(f"pod {pod.metadata.name}: no entrypoint for ThreadRuntime")
        mod_name, _, fn_name = main.entrypoint.partition(":")
        fn = getattr(importlib.import_module(mod_name), fn_name)
        return ThreadRuntime.spawn(fn, env)

    @staticmethod
    def spawn(fn: Callable, env: Dict[str, str]) -> ProcHandle:
        return _ThreadHandle(fn, env)


class FakeRuntime(ContainerRuntime):
    """Containers never actually run; tests drive phases via Kubelet-free
    store updates (see tests/helpers.py)."""

    def start(self, pod: Pod, env: Dict[str, str]) -> ProcHandle:  # pragma: no cover
        raise RuntimeError("FakeRuntime pods are driven manually by tests")


class Kubelet:
    """Realizes Pending pods and reports their lifecycle.

    One Kubelet instance typically serves ALL simulated nodes on this
    machine (locally it plays every TPU host); pass `nodes` to restrict it
    to a subset for multi-agent setups.
    """

    NAME = "kubelet"

    def __init__(
        self,
        store: ObjectStore,
        runtime: ContainerRuntime,
        nodes: Optional[set] = None,
        pod_ip: str = "127.0.0.1",
        metrics=None,
    ) -> None:
        self.store = store
        self.runtime = runtime
        self.nodes = nodes
        self.pod_ip = pod_ip
        self.metrics = metrics  # JobMetrics or None (adopted_pods counter)
        #: processes started by THIS incarnation — the restart e2e asserts
        #: zero duplicate creates via this count
        self.launch_count = 0
        self.adopted_count = 0
        #: (ns/name -> uid) of RUNNING pods captured at begin_recovery():
        #: exactly the pods whose processes may have outlived the previous
        #: operator. Adoption applies ONLY to these — in steady state a
        #: RUNNING pod missing from _running is a reap-in-progress race,
        #: not an orphan, and must not be failed or re-attached.
        self._recovery: Dict[str, str] = {}
        self._lock = threading.Lock()
        self._running: Dict[str, ProcHandle] = {}
        #: pod uid each running handle belongs to — a same-name replacement
        #: pod (elastic resize deletes RUNNING pods and recreates them)
        #: must not be mistaken for the pod whose process is still alive
        self._running_uid: Dict[str, str] = {}
        #: (ns, pod, volume) -> (pod uid, ConfigMap resource version) last
        #: materialized; cleared when the pod is deleted
        self._materialized: Dict[tuple, tuple] = {}
        #: ns/name -> progress-beacon file path (KUBEDL_BEACON_FILE env),
        #: recorded at launch so pod deletion can remove the file — a
        #: stale beacon from a dead pod must never be re-published
        self._beacon_files: Dict[str, str] = {}

    def setup(self, manager: ControllerManager) -> None:
        def mapper(event: str, obj: BaseObject, old):
            if obj.kind == "ConfigMap":
                # re-sync mounted ConfigMap volumes of running pods (real
                # kubelet semantics; e.g. MPI hostfile refresh on scale)
                keys = []
                for pod in self.store.list("Pod", obj.metadata.namespace):
                    if any(
                        v.config_map == obj.metadata.name
                        for v in pod.spec.volumes  # type: ignore[union-attr]
                    ):
                        keys.append((pod.metadata.namespace, pod.metadata.name))
                return keys
            return [(obj.metadata.namespace, obj.metadata.name)]

        manager.register(
            self.NAME,
            self.reconcile,
            watch_kinds=["Pod", "ConfigMap"],
            mapper=mapper,
            workers=4,
            # list-then-watch: pods that already exist when the manager
            # starts (rehydrated store) get their launch/adoption pass
            # without waiting for a mutation
            resync_on_start=True,
        )

    # ------------------------------------------------------------------

    def _served(self, pod: Pod) -> bool:
        return self.nodes is None or pod.spec.node_name in self.nodes

    @staticmethod
    def _pod_env(pod: Pod) -> Dict[str, str]:
        env: Dict[str, str] = {}
        for c in pod.spec.init_containers + pod.spec.containers:
            for e in c.env:
                env[e.name] = e.value
        env["KUBEDL_POD_NAME"] = pod.metadata.name
        env["KUBEDL_POD_NAMESPACE"] = pod.metadata.namespace
        env["KUBEDL_NODE_NAME"] = pod.spec.node_name
        return env

    def reconcile(self, namespace: str, name: str) -> Optional[float]:
        key = f"{namespace}/{name}"
        pod = self.store.try_get("Pod", name, namespace)
        if pod is None:
            # deleted: kill the container but KEEP the _running slot — the
            # reap thread frees it (and relaunches any same-name
            # replacement) only after handle.wait() returns, i.e. after
            # the old container fully tore down. Freeing the slot here let
            # a replacement launch while the cancelled entrypoint was
            # still unwinding — two trainers sharing one device runtime,
            # one of them mid-teardown (real kubelets likewise never start
            # a same-name container before the old one is gone).
            with self._lock:
                handle = self._running.get(key)
                beacon = self._beacon_files.pop(key, None)
                for sk in [k for k in self._materialized
                           if (k[0], k[1]) == (namespace, name)]:
                    del self._materialized[sk]
            if handle is not None:
                handle.kill()
            if beacon:
                try:
                    os.unlink(beacon)
                except OSError:
                    pass
            return None
        assert isinstance(pod, Pod)
        if not self._served(pod):
            return None
        if pod.is_terminal():
            # a pod marked terminal EXTERNALLY (node-lifecycle eviction)
            # may still have a live local process: kill it, or its
            # same-name replacement can never launch (the reap thread
            # frees the slot and relaunches). In the normal flow the
            # handle is popped before the terminal phase is stamped, so a
            # live handle here always means external termination.
            with self._lock:
                handle = self._running.get(key)
                self._recovery.pop(key, None)
            if handle is not None and not isinstance(handle, _PlaceholderHandle):
                handle.kill()
            return None
        if self._recovery:
            with self._lock:
                rec_uid = self._recovery.pop(key, None)
            if (
                rec_uid is not None
                and rec_uid == pod.metadata.uid
                and pod.status.phase == PodPhase.RUNNING
            ):
                return self._adopt(pod, key)
        with self._lock:
            recorded_uid = self._running_uid.get(key)
            stale = (
                key in self._running
                and recorded_uid is not None
                and recorded_uid != pod.metadata.uid
            )
            handle = self._running.get(key)
            already_running = key in self._running and not stale
            if not already_running and not stale:
                if pod.status.phase != PodPhase.PENDING:
                    return None
                # reserve the slot before leaving the lock
                self._running[key] = _PlaceholderHandle()
                self._running_uid[key] = pod.metadata.uid
        if stale:
            # the live process belongs to a same-name pod that was deleted
            # and already replaced before its DELETED event was processed
            # (workqueue coalescing collapses DELETED+ADDED into one key).
            # Cancel it; the reap thread frees the slot and relaunches the
            # replacement.
            if handle is not None:
                handle.kill()
            return None
        if already_running:
            # keep mounted ConfigMap volumes fresh (outside self._lock —
            # materialization takes it internally)
            try:
                self._materialize_config_volumes(pod)
            except RuntimeError:
                pass  # ConfigMap deleted mid-run; keep last snapshot
            return None
        try:
            self._launch(pod, key)
        except Exception as e:
            log.error("launch %s failed: %s", key, e)
            with self._lock:
                self._running.pop(key, None)
                self._running_uid.pop(key, None)
            self._set_phase(pod, PodPhase.FAILED, reason=f"LaunchError: {e}", exit_code=1)
        return None

    def _launch(self, pod: Pod, key: str) -> None:
        env = self._pod_env(pod)
        beacon = env.get("KUBEDL_BEACON_FILE")
        if beacon:
            with self._lock:
                self._beacon_files[key] = beacon
        self._materialize_config_volumes(pod)
        # init containers run to completion first (code-sync etc.)
        for init in pod.spec.init_containers:
            if init.command:
                rc = subprocess.call(init.command, env={**os.environ, **env})
                if rc != 0:
                    raise RuntimeError(f"init container {init.name} exited {rc}")
        handle = self.runtime.start(pod, env)
        self.launch_count += 1
        with self._lock:
            self._running[key] = handle
        pid = handle.pid()
        if pid is not None:
            self._stamp_pid(pod, pid)
        self._set_phase(pod, PodPhase.RUNNING)
        # an eviction landing DURING launch (init containers etc.) found
        # only the placeholder handle and could kill nothing; now that the
        # real handle exists, honor any terminal phase stamped meanwhile
        fresh = self.store.try_get("Pod", pod.metadata.name, pod.metadata.namespace)
        if (
            fresh is None
            or fresh.metadata.uid != pod.metadata.uid
            or fresh.is_terminal()
        ):
            handle.kill()

        self._start_reaper(pod, key, handle)

    def _start_reaper(self, pod: Pod, key: str, handle: ProcHandle) -> None:
        def reap() -> None:
            code = handle.wait()
            with self._lock:
                self._running.pop(key, None)
                self._running_uid.pop(key, None)
            phase = PodPhase.SUCCEEDED if code == 0 else PodPhase.FAILED
            self._set_phase(pod, phase, exit_code=code)
            # a same-name replacement pod may have been created while this
            # process was dying (gang restart) — give it a launch pass now
            # that the _running slot is free
            self.reconcile(pod.metadata.namespace, pod.metadata.name)

        threading.Thread(target=reap, daemon=True, name=f"reap-{key}").start()

    # ---- crash recovery: pod adoption --------------------------------

    def begin_recovery(self) -> int:
        """Arm the adoption pass. Called after store rehydration, BEFORE
        controllers start: records every RUNNING pod of the dead
        incarnation so the first reconcile of each re-attaches its live
        process (by pid annotation) instead of ignoring it forever — or
        fails it retryably when the process did not survive. Returns the
        number of candidates."""
        with self._lock:
            for pod in self.store.list("Pod", namespace=None):
                if not isinstance(pod, Pod) or not self._served(pod):
                    continue
                key = f"{pod.metadata.namespace}/{pod.metadata.name}"
                if (
                    pod.status.phase == PodPhase.RUNNING
                    and not pod.is_terminal()
                    and key not in self._running
                ):
                    self._recovery[key] = pod.metadata.uid
            return len(self._recovery)

    def _adopt(self, pod: Pod, key: str) -> None:
        """First post-restart reconcile of a RUNNING pod: re-attach by
        (name, uid, pid) or fail it retryably (exit 137 -> gang restart)."""
        handle = self._attach(pod)
        if handle is None:
            log.warning(
                "pod %s (uid %s) was Running before the restart but its "
                "process is gone — failing retryably",
                key, pod.metadata.uid,
            )
            self._set_phase(
                pod, PodPhase.FAILED, reason="LostOnRestart", exit_code=137
            )
            return None
        with self._lock:
            self._running[key] = handle
            self._running_uid[key] = pod.metadata.uid
        self.adopted_count += 1
        if self.metrics is not None:
            self.metrics.adopted_pods.inc()
        log.info("adopted pod %s (uid %s, pid %s)", key, pod.metadata.uid,
                 handle.pid())
        self._start_reaper(pod, key, handle)
        return None

    def _attach(self, pod: Pod) -> Optional[ProcHandle]:
        pid_s = pod.metadata.annotations.get(PID_ANNOTATION, "")
        if not pid_s:
            return None  # thread/fake runtime pods die with the process
        try:
            pid = int(pid_s)
        except ValueError:
            return None
        try:
            os.kill(pid, 0)  # liveness (zombie children still count:
            # _AttachedHandle reaps them for the real exit code)
        except ProcessLookupError:
            return None
        except PermissionError:
            pass
        return _AttachedHandle(pid)

    def _stamp_pid(self, pod: Pod, pid: int) -> None:
        """Durably record the pod's OS pid so a restarted kubelet can
        adopt the live process (the containerd-state analogue)."""

        def mutate(obj: Pod) -> None:  # type: ignore[type-arg]
            if obj.metadata.uid != pod.metadata.uid or obj.is_terminal():
                raise Kubelet._StalePod()
            obj.metadata.annotations[PID_ANNOTATION] = str(pid)

        try:
            self.store.update_with_retry(
                "Pod", pod.metadata.name, pod.metadata.namespace, mutate
            )
        except (NotFound, Kubelet._StalePod):
            pass

    def _materialize_config_volumes(self, pod: Pod) -> None:
        """Write ConfigMap-backed volumes to their mount path (the kubelet
        side of the reference's ConfigMap volume mounts). Files are swapped
        in atomically (write-then-rename, the real kubelet's symlink-swap
        equivalent) so a running process never reads a torn hostfile, and
        unchanged ConfigMap versions are skipped."""
        from kubedl_tpu.core.objects import ConfigMap, config_mount_path

        for vol in pod.spec.volumes:
            if not vol.config_map:
                continue
            cm = self.store.try_get(
                "ConfigMap", vol.config_map, pod.metadata.namespace
            )
            if not isinstance(cm, ConfigMap):
                raise RuntimeError(f"ConfigMap {vol.config_map} not found")
            sync_key = (pod.metadata.namespace, pod.metadata.name, vol.name)
            stamp = (pod.metadata.uid, cm.metadata.resource_version)
            with self._lock:
                if self._materialized.get(sync_key) == stamp:
                    continue
            root = vol.mount_path or config_mount_path(
                pod.metadata.namespace, pod.metadata.name, vol.name
            )
            os.makedirs(root, exist_ok=True)
            for fname, content in cm.data.items():
                path = os.path.join(root, fname)
                # per-thread tmp name: concurrent materializers must never
                # interleave writes into the same tmp file
                tmp = f"{path}.tmp-{os.getpid()}-{threading.get_ident()}"
                with open(tmp, "w") as f:
                    f.write(content)
                if content.startswith("#!"):
                    os.chmod(tmp, 0o755)
                os.replace(tmp, path)
            with self._lock:
                self._materialized[sync_key] = stamp

    class _StalePod(Exception):
        pass

    def _set_phase(
        self,
        pod: Pod,
        phase: PodPhase,
        reason: str = "",
        exit_code: Optional[int] = None,
    ) -> None:
        def mutate(obj: Pod) -> None:  # type: ignore[type-arg]
            if obj.metadata.uid != pod.metadata.uid:
                # same-name pod recreated after a gang restart: the old
                # process's lifecycle must not stamp the fresh pod
                raise Kubelet._StalePod()
            if obj.is_terminal():
                # terminal is final: a pod already failed EXTERNALLY
                # (node-lifecycle eviction, exit 137 retryable) must not
                # be overwritten by the reaped kill signal (-15, which
                # would read as a permanent code-bug failure) or
                # resurrected to Running by an in-flight launch
                raise Kubelet._StalePod()
            obj.status.phase = phase
            obj.status.pod_ip = self.pod_ip
            obj.status.host_ip = self.pod_ip
            if reason:
                obj.status.reason = reason
            if phase == PodPhase.RUNNING and obj.status.start_time is None:
                obj.status.start_time = time.time()
            if phase in (PodPhase.SUCCEEDED, PodPhase.FAILED):
                obj.status.finish_time = time.time()
                obj.status.container_statuses = [
                    ContainerStatus(exit_code=exit_code if exit_code is not None else 0)
                ]

        try:
            self.store.update_with_retry(
                "Pod", pod.metadata.name, pod.metadata.namespace, mutate
            )
        except (NotFound, Kubelet._StalePod):
            pass

    def shutdown(self) -> None:
        with self._lock:
            handles = list(self._running.values())
            self._running.clear()
            self._running_uid.clear()
        for h in handles:
            h.kill()


class _PlaceholderHandle(ProcHandle):
    def wait(self) -> int:  # pragma: no cover
        return 0

    def kill(self) -> None:
        pass
