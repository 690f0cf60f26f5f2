#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user would call, on
one TPU v5e chip, at the full width and depth of Llama-3.2-1B (the
``llama3-1b`` preset: 16 layers, dim 2048, 32 Q / 8 KV heads, ffn 8192,
vocab 128256, tied embeddings; weights from a seed):

- ``kernels``: the three Pallas kernels, compiled, against plain references
  (kubedl_tpu/ops/kernel_check.py) at that model's and Gemma-2B's shapes.
- ``serve``: ``python -m kubedl_tpu.serving.server`` twice — the engine's
  defaults (paged KV, gather attention), then ``kv_attention="blocked"``
  (the Pallas decode kernels) — each answering generate requests over
  HTTP and draining on SIGTERM.
- ``train``: an ``Operator`` with ``SubprocessRuntime`` runs a one-worker
  ``TPUJob`` of ``python -m kubedl_tpu.training.entry`` to SUCCEEDED, then
  the same job again in a fresh process, which must find every program in
  the compile cache.

``--multichip`` runs instead, and alone, the four-chip path: the same job
pinned to a v5e-4 slice with the mesh ``fsdp=2,tensor=2``, compared step by
step with the same seed and batch on one chip of that host.

This parent never imports jax: a chip belongs to one process, so every
phase is a child that exits before the next starts, and each child says
which device it ran on. Any phase that fails, or any child that did not
run on a TPU, makes the exit code nonzero; then no device line is printed.
The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Where the environment holds JAX to the CPU (``JAX_PLATFORMS=cpu``) the run
cannot pass, so it rehearses: every phase's control flow at the ``tiny``
preset with the kernels interpreted, and then fails at the device check.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"

#: kernel outputs against their references: max-abs difference as a share
#: of the reference's largest magnitude — about eight bf16 ulps (2^-8)
BF16_TOL = 0.03
#: --multichip: per-step |loss(4 chips) - loss(1 chip)|. Same seed, same
#: batches, same initial weights; bf16 matmuls summed in another order
#: (largest seen: 0.0005 on losses of 12.2 down to 6.1 — my chip run, PR 21)
LOSS_TOL = 0.01

#: train job. Batch 4 at s2048 compiles to a 13.7 GiB peak on a v5e chip
#: (15.75 GiB) with the two memory knobs below; without them batch 4 is
#: refused (22.3 GiB) — see CHANGES.md, PR 21
TRAIN_CFG = {
    "model": "llama3-1b", "global_batch": 4, "seq_len": 2048, "steps": 12,
    "opt_moment_dtype": "bfloat16", "loss_chunk": 512,
    "remat_policy": "flash_rope", "log_every": 1,
}
TINY_TRAIN_CFG = {
    "model": "tiny", "global_batch": 4, "seq_len": 128, "steps": 12,
    "log_every": 1,
}
#: (prompt tokens, new tokens): the server's max_seq is 512, so the longest
#: prompt leaves room for its answer; the first four go out together
REQUESTS = [(16, 128), (64, 64), (200, 48), (448, 32)]
TINY_REQUESTS = [(4, 24), (8, 16), (16, 12), (40, 8)]
REPEATED = (96, 32)
TINY_REPEATED = (12, 8)


def train_cfg(workdir: str, **over) -> dict:
    """The job's config, with a token file made from the seed: 64 distinct
    ids in uniform random order, so there is something to learn within a
    dozen steps (uniform random ids over the whole vocabulary move the
    loss by less than its step-to-step noise — my chip run, PR 21). It
    goes through ``data_path``, i.e. the native loader."""
    import array

    vocab = 256 if REHEARSAL else 128256
    rng = random.Random(21)
    ids = [rng.randrange(vocab) for _ in range(64)]
    path = os.path.join(workdir, "tokens.bin")
    if not os.path.exists(path):
        with open(path, "wb") as f:
            array.array(
                "i", (ids[rng.randrange(64)] for _ in range(1 << 20))
            ).tofile(f)
    base = TINY_TRAIN_CFG if REHEARSAL else TRAIN_CFG
    return dict(base, data_path=path, **over)


def emit(phase: str, passed: bool, **detail) -> bool:
    """One JSON object per phase on its own stdout line."""
    print(json.dumps({"phase": phase, "passed": passed, **detail}), flush=True)
    return passed


def kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    proc.wait()


def kill_descendants() -> None:
    """SIGKILL whatever this process started and is still alive (pods are
    its direct children): nothing may hold the chip after the run."""
    parents = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                parents[int(pid)] = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
    me = os.getpid()
    for pid in parents:
        p = pid
        while p in parents and p != me:
            p = parents[p]
        if p == me and pid != me:
            try:
                os.kill(pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass


def child_env(**extra: str) -> dict:
    """Children find the compile cache on their own, all in one place:
    where JAX_COMPILATION_CACHE_DIR says, else ``<repo>/.cache/jax``
    (kubedl_tpu/utils/compile_cache.py)."""
    return dict(os.environ, PYTHONPATH=ROOT, **extra)


def on_tpu(device: dict) -> bool:
    return (device or {}).get("platform") == "tpu"


# ---- kernels ---------------------------------------------------------------

def kernels_child() -> int:
    """Runs in the child (``python -c "import chip_smoke; ..."``)."""
    spec = json.loads(sys.argv[1])
    from kubedl_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import jax.numpy as jnp

    from kubedl_tpu.ops import kernel_check

    common = {"dtype": jnp.dtype(spec["dtype"]), "tol": spec["tol"],
              "interpret": spec["interpret"]}
    results = [
        kernel_check.flash_check(*c["shape"], block=c["block"], **common)
        for c in spec["flash"]
    ] + [kernel_check.paged_check(**c, **common) for c in spec["paged"]]
    dev = jax.devices()[0]
    print(json.dumps({
        "device": {"platform": dev.platform, "device_kind": dev.device_kind,
                   "count": jax.device_count()},
        "results": results,
    }), flush=True)
    return 0


def phase_kernels(workdir: str) -> tuple:
    if REHEARSAL:
        spec = {
            "dtype": "float32", "interpret": True,
            "flash": [{"shape": [1, 256, 4, 2, 16], "block": 128}],
            "paged": [
                {"B": 3, "KV": 2, "group": 2, "hd": 16, "max_tokens": 64},
                {"B": 3, "KV": 2, "group": 2, "hd": 16, "max_tokens": 64,
                 "fused": True},
                {"B": 3, "KV": 1, "group": 4, "hd": 32, "max_tokens": 64,
                 "S": 8},
            ],
        }
    else:
        # Mistral-7B's decode heads: the paged kernel takes a pool whose heads
        # are whole 128-lane tiles (Llama-3.2-1B's 64 are refused by name, and
        # the serve arms below run that model's decode through the lax scan)
        mistral = {"B": 8, "KV": 8, "group": 4, "hd": 128}
        gemma = {"B": 8, "KV": 1, "group": 8, "hd": 256}  # Gemma-2B decode
        spec = {
            "dtype": "bfloat16", "interpret": False,
            "flash": [
                {"shape": [1, 2048, 32, 8, 64], "block": 1024},
                # one kv group of that model at S=8192: the float32 dense
                # reference of all 32 heads would not fit beside the kernel
                {"shape": [1, 8192, 4, 1, 64], "block": 1024},
            ],
            "paged": [
                mistral, {**mistral, "fused": True}, {**mistral, "S": 16},
                gemma, {**gemma, "fused": True},
            ],
        }
    spec["tol"] = BF16_TOL
    log = os.path.join(workdir, "kernels.log")
    with open(log, "wb") as err:
        proc = subprocess.Popen(
            [sys.executable, "-c",
             "import sys, chip_smoke; sys.exit(chip_smoke.kernels_child())",
             json.dumps(spec)],
            cwd=ROOT, env=child_env(), stdout=subprocess.PIPE,
            stderr=err, start_new_session=True,
        )
        try:
            out, _ = proc.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            kill_group(proc)
            return emit("kernels", False, error="timed out", log=tail(log)), {}
    if proc.returncode != 0:
        return emit("kernels", False, rc=proc.returncode, log=tail(log)), {}
    got = json.loads(out.decode().strip().splitlines()[-1])
    results = got["results"]
    checks = {
        "platform_is_tpu": on_tpu(got["device"]),
        "every_kernel_compiled": all(r["compiled"] for r in results),
        "finite": all(r["finite"] for r in results),
        f"within_{BF16_TOL}_of_reference": all(r["ok"] for r in results),
    }
    passed = emit("kernels", all(checks.values()), device=got["device"],
                  checks=checks, results=results)
    return passed, got["device"]


# ---- serve -----------------------------------------------------------------

def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")
    except OSError:
        return ""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body: dict = None, timeout: float = 600.0) -> tuple:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")[:500]}


def phase_serve(arm: str, kv_attention: str, workdir: str) -> tuple:
    """One server child answering over HTTP; returns (passed, device,
    greedy outputs by prompt) — the outputs feed the A/B agreement."""
    preset = "tiny" if REHEARSAL else "llama3-1b"
    vocab = 256 if REHEARSAL else 128256
    port = free_port()
    cfg = {"preset": preset, "port": port}
    if kv_attention != "gather":  # arm A is the engine's own defaults
        cfg["kv_attention"] = kv_attention
    log = os.path.join(workdir, f"serve-{arm}.log")
    base = f"http://127.0.0.1:{port}"
    rng = random.Random(21)
    plan = TINY_REQUESTS if REHEARSAL else REQUESTS
    rep_len, rep_new = TINY_REPEATED if REHEARSAL else REPEATED
    prompts = [[rng.randrange(vocab) for _ in range(n)] for n, _ in plan]
    repeated = [rng.randrange(vocab) for _ in range(rep_len)]
    name = f"serve[{arm}]"
    with open(log, "wb") as out:
        proc = subprocess.Popen(
            [sys.executable, "-m", "kubedl_tpu.serving.server"], cwd=ROOT,
            env=child_env(KUBEDL_SERVE_CONFIG=json.dumps(cfg)),
            stdout=out, stderr=subprocess.STDOUT, start_new_session=True,
        )
    try:
        t0 = time.time()
        while True:  # the engine answers /healthz once its warm-up is done
            if proc.poll() is not None:
                return emit(name, False, error="server exited",
                            rc=proc.returncode, log=tail(log)), {}, {}
            try:
                if http_json(base + "/healthz", timeout=2)[0] == 200:
                    break
            except OSError:
                pass
            if time.time() - t0 > 300:
                return emit(name, False, error="no /healthz in 300 s",
                            log=tail(log)), {}, {}
            time.sleep(0.5)
        ready_s = round(time.time() - t0, 1)

        answers = [None] * len(plan)

        def ask(i: int) -> None:
            answers[i] = http_json(base + "/v1/generate", {
                "prompt_ids": prompts[i], "max_tokens": plan[i][1],
                "temperature": 0.0,
            })

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(plan))]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        concurrent_s = round(time.time() - t0, 1)
        # the same greedy prompt, alone, three times: the first two take
        # the same path and must agree; by the third the prefix cache has
        # seen it twice and grafts it, so only its suffix is prefilled —
        # through the blocked kernel at S > 1 in arm B (reported, not gated)
        reps = [
            http_json(base + "/v1/generate", {
                "prompt_ids": repeated, "max_tokens": rep_new,
                "temperature": 0.0,
            }) for _ in range(3)
        ]
        _, stats = http_json(base + "/v1/stats")

        proc.send_signal(signal.SIGTERM)  # drain, then exit 0
        try:
            rc = proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            rc = None
    finally:
        kill_group(proc)

    everything = [a for a in answers if a is not None] + reps
    ids = [a[1].get("token_ids", []) for a in everything]
    device = stats.get("device", {})
    checks = {
        "platform_is_tpu": on_tpu(device),
        "every_answer_200": len(everything) == len(plan) + 3
        and all(a[0] == 200 for a in everything),
        "token_counts_as_asked": [len(x) for x in ids]
        == [n for _, n in plan] + [rep_new] * 3,
        "ids_inside_vocabulary": all(0 <= t < vocab for x in ids for t in x),
        "repeated_greedy_identical": bool(ids[-3]) and ids[-3] == ids[-2],
        "stats_name_the_kernel": stats.get("kv_blocks", {}).get(
            "attention_kernel") == kv_attention,
        "sigterm_drained_to_exit_0": rc == 0,
    }
    passed = emit(
        name, all(checks.values()), device=device, checks=checks,
        ready_seconds=ready_s, concurrent_seconds=concurrent_s,
        third_repeat_cached_prefix_len=reps[2][1].get("cached_prefix_len"),
        third_repeat_agrees=ids[-1] == ids[-2],
        requests=stats.get("requests"), tokens_out=stats.get("tokens_out"),
        **({} if all(checks.values()) else {"log": tail(log)}),
    )
    return passed, device, dict(enumerate(ids))


def agreement(a: dict, b: dict) -> dict:
    """Positions on which two arms' greedy streams agree, per request."""
    same = total = 0
    for i in sorted(set(a) & set(b)):
        total += max(len(a[i]), len(b[i]))
        same += sum(x == y for x, y in zip(a[i], b[i]))
    return {"positions_agreeing": same, "positions": total}


# ---- train (operator path) -------------------------------------------------

class TrainRig:
    """The operator the train phases share: SubprocessRuntime, an
    inventory of one v5e-4 slice (a v5e host; the catalog has no smaller
    v5e slice, so only the four-chip job can be pinned to it)."""

    def __init__(self, workdir: str) -> None:
        from kubedl_tpu.gang.slice_scheduler import SliceInventory
        from kubedl_tpu.operator import Operator, OperatorOptions
        from kubedl_tpu.runtime.executor import SubprocessRuntime

        self.logs = os.path.join(workdir, "pods")
        inventory = SliceInventory()
        inventory.add_slice("host-0", "v5e-4")
        self.op = Operator(
            OperatorOptions(
                local_addresses=True,
                artifact_registry_root=os.path.join(workdir, "registry"),
                pod_log_dir=self.logs,
                beacon_dir=os.path.join(workdir, "beacons"),
                # the first step of a cold start is a compile
                watchdog_startup_grace_seconds=900.0,
            ),
            runtime=SubprocessRuntime(self.logs), inventory=inventory,
        )

    def run(self, name: str, train_cfg: dict, *, pinned: bool = False,
            env: dict = None, timeout: float = 600.0) -> dict:
        """Submit a one-worker TPUJob of ``python -m
        kubedl_tpu.training.entry``, wait for SUCCEEDED, return the
        worker's summary from the pod log."""
        from kubedl_tpu.api.topology import MeshSpec, get_slice
        from kubedl_tpu.api.types import (
            JobConditionType, ReplicaSpec, ReplicaType, RestartPolicy,
        )
        from kubedl_tpu.core.objects import Container, EnvVar
        from kubedl_tpu.runtime.executor import read_worker_summary
        from kubedl_tpu.workloads.tpujob import TPUJob

        job = TPUJob()
        job.metadata.name = name
        spec = ReplicaSpec(
            replicas=1, restart_policy=RestartPolicy.NEVER,
            topology=get_slice("v5e-4") if pinned else None,
        )
        spec.template.spec.containers.append(Container(
            command=[sys.executable, "-m", "kubedl_tpu.training.entry"],
            working_dir=ROOT,
            env=[EnvVar("KUBEDL_TRAIN_CONFIG", json.dumps(train_cfg)),
                 EnvVar("PYTHONPATH", ROOT)]
            + [EnvVar(k, v) for k, v in (env or {}).items()],
        ))
        job.spec.replica_specs[ReplicaType.WORKER] = spec
        if pinned:
            job.mesh = MeshSpec({"fsdp": 2, "tensor": 2})
        log = os.path.join(self.logs, "default", f"{name}-worker-0.log")
        t0 = time.time()
        self.op.submit(job)
        got = self.op.wait_for_phase(
            "TPUJob", name,
            [JobConditionType.SUCCEEDED, JobConditionType.FAILED],
            timeout=timeout,
        )
        if got.status.phase != JobConditionType.SUCCEEDED:
            raise RuntimeError(
                f"job {name} is {got.status.phase}: "
                + "; ".join(c.message for c in got.status.conditions)
                + "\n" + tail(log)
            )
        summary = read_worker_summary(log)
        summary["job_seconds"] = round(time.time() - t0, 1)
        return summary


def trajectory(s: dict) -> list:
    """Per-step losses (``log_every=1``): first, the logged ones, final."""
    return [s["first_loss"]] + [v for _, v in s["loss_log"]] + [s["final_loss"]]


def train_checks(s: dict) -> dict:
    losses = trajectory(s)
    return {
        "platform_is_tpu": on_tpu(s["device"]),
        "attn_impl_flash": s["attn_impl"] == "flash",
        "losses_finite": all(math.isfinite(v) for v in losses),
        "loss_fell_over_8_or_more_steps": s["steps"] >= 8
        and s["final_loss"] < s["first_loss"],
        "no_sanity_violations": not s["sanity_violations"],
        "mfu_in_0_1": 0.0 < s["mfu"] <= 1.0,
    }


def brief(s: dict) -> dict:
    keep = ("device", "attn_impl", "steps", "first_loss", "final_loss",
            "mfu", "step_time_ms", "tokens_per_sec_per_chip",
            "first_step_seconds", "job_seconds", "compile_cache",
            "startup_phases", "opt_state_bytes_per_device", "collectives",
                "data_loader", "sanity_violations")
    return dict({k: s.get(k) for k in keep},
                losses=[round(v, 4) for v in trajectory(s)])


def phase_train(workdir: str) -> tuple:
    cfg = train_cfg(workdir)
    rig = TrainRig(workdir)
    device = {}
    ok = True
    with rig.op:
        try:
            cold = rig.run("smoke-cold", cfg)
            device = cold["device"]
            checks = train_checks(cold)
            ok &= emit("train[cold]", all(checks.values()), checks=checks,
                       summary=brief(cold))
            # the same job again, a fresh process against the same cache
            warm = rig.run("smoke-warm", cfg)
            cc = warm["compile_cache"]
            checks = train_checks(warm)
            checks["every_compile_served_from_cache"] = (
                cc["cache_hits"] > 0 and cc["cache_misses"] == 0
            )
            ok &= emit("train[warm]", all(checks.values()), checks=checks,
                       summary=brief(warm))
        except Exception as e:  # a failed job is this phase's result
            ok = emit("train", False, error=str(e)[-3000:])
    return ok, device


def phase_multichip(workdir: str) -> tuple:
    """Four chips, one worker process, mesh fsdp=2 x tensor=2 — against
    the same seed and batch on one chip of the same host."""
    cfg = train_cfg(workdir, steps=8)
    if REHEARSAL:
        # four virtual CPU devices stand in for the host's four chips
        def view(n):
            return {"XLA_FLAGS": f"--xla_force_host_platform_device_count={n}"}
        four_env, one_env = view(4), view(1)
    else:
        four_env = {}
        # nothing in the repo binds a pod to a subset of a host's chips
        # (docs/parallelism.md), so the one-chip arm's view of the host is
        # limited from its environment
        one_env = {
            "TPU_VISIBLE_CHIPS": "0", "TPU_VISIBLE_DEVICES": "0",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }
    rig = TrainRig(workdir)
    device = {}
    with rig.op:
        try:
            four = rig.run("smoke-four", cfg, pinned=True, env=four_env,
                           timeout=900.0)
            device = four["device"]
            one = rig.run("smoke-one", cfg, env=one_env, timeout=900.0)
        except Exception as e:
            return emit("multichip", False, error=str(e)[-3000:]), device

    l4, l1 = trajectory(four), trajectory(one)
    deltas = [round(abs(a - b), 5) for a, b in zip(l4, l1)]
    colls = four["collectives"] or {}
    used = four["device"].get("bytes_in_use") or []
    ratio = four["opt_state_bytes_per_device"] / max(
        one["opt_state_bytes_per_device"], 1)
    checks = dict(train_checks(four))
    checks.update({
        "four_devices": four["device"]["count"] == 4,
        "one_chip_arm_saw_one_device": one["device"]["count"] == 1
        and one["device"]["platform"] == four["device"]["platform"],
        "same_number_of_steps": len(l4) == len(l1) == cfg["steps"],
        f"per_step_loss_within_{LOSS_TOL}": bool(deltas)
        and max(deltas) <= LOSS_TOL,
        "opt_state_about_a_quarter_per_device": 0.2 <= ratio <= 0.3,
        "all_four_devices_hold_state": len(used) == 4
        and all(b and b > 0 for b in used),
        "collectives_in_compiled_step": all(
            colls.get(op, 0) > 0
            for op in ("all-gather", "all-reduce", "reduce-scatter")
        ),
    })
    passed = emit(
        "multichip", all(checks.values()), checks=checks,
        loss_four_chips=l4, loss_one_chip=l1, loss_abs_deltas=deltas,
        opt_state_ratio=round(ratio, 4), collectives=colls,
        bytes_in_use=used, four=brief(four), one=brief(one),
    )
    return passed, device


# ---- main ------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--multichip", action="store_true",
        help="run the four-chip train path and its one-chip comparison, "
             "and no other phase (needs a v5e host with four chips)",
    )
    args = ap.parse_args()
    import kubedl_tpu  # noqa: F401 — without the repo beside it, fail at once

    results = []  # (passed, device, ...) per phase
    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as workdir:
        try:
            if args.multichip:
                results.append(phase_multichip(workdir))
            else:
                results.append(phase_kernels(workdir))
                arm_a = phase_serve("A", "gather", workdir)
                arm_b = phase_serve("B", "blocked", workdir)
                results += [arm_a, arm_b]
                emit("serve[A-vs-B]", True,
                     greedy=agreement(arm_a[2], arm_b[2]))
                results.append(phase_train(workdir))
        finally:
            kill_descendants()
    assert "jax" not in sys.modules, "the parent must stay off the chip"
    devices = [r[1] for r in results]
    ok = all(r[0] for r in results) and all(
        on_tpu(d) and d.get("device_kind") == devices[0].get("device_kind")
        for d in devices
    ) and devices[0].get("count") == (4 if args.multichip else 1)
    print(json.dumps({"seconds": round(time.time() - t0, 1),
                      "rehearsal": REHEARSAL}), flush=True)
    if not ok:
        print("chip_smoke: FAILED (see the phase lines above)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0]["platform"],
        "kind": devices[0]["device_kind"], "count": devices[0]["count"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
