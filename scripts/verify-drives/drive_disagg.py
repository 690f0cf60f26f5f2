"""Drive the disaggregated prefill/decode fleet end to end: four REAL
engine replicas as subprocesses (`python -m kubedl_tpu.serving.server`)
— one prefill, two decode, one colocated — with the role-aware router in
front, and a seeded FaultPlan choosing the moment a DECODE replica is
SIGKILLed under client load. Acceptance (docs/serving.md "Disaggregated
serving"): the router partitions the fleet into role pools, two-leg
disagg dispatch produces greedy output bit-identical to a direct
colocated call, zero requests are lost when a decode replica dies
mid-load (the survivor or the colocated fallback absorbs them), and a
full decode-pool outage degrades to colocated fallback — never a
fleet-wide 503."""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
os.environ["JAX_PLATFORMS"] = "cpu"

ok = []
def check(name, cond, detail=""):
    ok.append(bool(cond))
    print(("PASS" if cond else "FAIL"), name, detail)

from kubedl_tpu import chaos
from kubedl_tpu.chaos import FaultPlan, FaultSpec
from kubedl_tpu.serving import router_policy as policy
from kubedl_tpu.serving.router import ServingRouter

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def spawn_replica(port, role):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["KUBEDL_SERVE_CONFIG"] = json.dumps({
        "preset": "tiny", "port": port, "max_batch": 2, "role": role,
        "handoff_ttl_s": 20.0,
    })
    env.pop("KUBEDL_MODEL_PATH", None)
    return subprocess.Popen(
        [sys.executable, "-m", "kubedl_tpu.serving.server"],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def wait_healthy(port, timeout=180.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=2
            ) as r:
                if r.status == 200:
                    return True
        except Exception:
            time.sleep(0.3)
    return False


def post_generate(port, body, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


ROLES = {"p0": "prefill", "d0": "decode", "d1": "decode",
         "c0": "colocated"}
ports = {n: free_port() for n in ROLES}
procs = {n: spawn_replica(ports[n], ROLES[n]) for n in ROLES}
try:
    up = all(wait_healthy(p) for p in ports.values())
    check("4 engine replicas come up (1 prefill / 2 decode / 1 colocated)",
          up)
    if not up:
        raise SystemExit(1)

    router = ServingRouter(
        [{"name": n, "host": "127.0.0.1", "port": ports[n],
          "role": ROLES[n], "model": "tiny"} for n in sorted(ROLES)],
        probe_interval_s=0.2, probe_timeout_s=1.0,
        eject_threshold=3, readmit_cooldown_s=1.0,
        max_retries=1, default_deadline_ms=30_000.0,
        disagg_enabled=True,
    )
    router.start()
    router.probe_once()

    pools = router.stats()["pools"]
    check("router partitions the fleet into role pools",
          pools == {"prefill": 1, "decode": 2, "colocated": 1},
          f"pools={pools}")

    # -- two-leg dispatch must never change RESULTS -----------------------
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    direct = post_generate(ports["c0"], {"prompt_ids": prompt,
                                         "max_tokens": 8,
                                         "temperature": 0.0})
    code, via, _ = router.handle_generate(
        {"prompt_ids": prompt, "max_tokens": 8, "temperature": 0.0})
    m = router.metrics
    check("disagg greedy output bit-identical to direct colocated call",
          code == 200 and via["token_ids"] == direct["token_ids"]
          and m.disagg_requests.value() >= 1,
          f"direct={direct['token_ids']} routed={via.get('token_ids')} "
          f"disagg_requests={m.disagg_requests.value()}")

    # -- SIGKILL one decode replica under load, moment seeded -------------
    N = 32
    plan = FaultPlan(seed=12, sites={"replica.kill": [FaultSpec.nth(7)]})
    victim = "d0"
    results = [None] * N
    killed_at = {"i": None}

    def client(i):
        body = {"prompt_ids": [(i % 5) + 2] * 8 + [100 + i],
                "max_tokens": 4, "temperature": 0.0}
        code, payload, _ = router.handle_generate(body, deadline_ms=25_000)
        results[i] = (code, payload)

    threads = []
    with plan:
        for i in range(N):
            if chaos.should_fail("replica.kill"):
                killed_at["i"] = i
                procs[victim].send_signal(signal.SIGKILL)
            t = threading.Thread(target=client, args=(i,), daemon=True)
            t.start()
            threads.append(t)
            time.sleep(0.03)
        for t in threads:
            t.join(timeout=40)
    check("seeded plan injected exactly one decode kill",
          plan.faults("replica.kill") == 1 and killed_at["i"] == 6,
          f"killed before request #{killed_at['i']}")

    codes = [r[0] for r in results if r is not None]
    lost = N - len(codes)
    failures = [c for c in codes if c != 200]
    check("zero lost requests across the decode-replica kill",
          lost == 0 and not failures,
          f"lost={lost} non200={failures[:5]}")

    # -- full decode-pool outage: degrade to colocated, never 503 ---------
    procs["d1"].send_signal(signal.SIGKILL)
    deadline = time.time() + 15
    while time.time() < deadline:
        router.probe_once()
        st = router.stats()["replicas"]
        if (st["d0"]["state"] == policy.OPEN
                and st["d1"]["state"] == policy.OPEN):
            break
        time.sleep(0.2)
    check("mid-flight adopt-leg failure fell back within the request",
          m.disagg_fallbacks.value() >= 1,
          f"fallbacks={m.disagg_fallbacks.value()}")
    disagg_before = m.disagg_requests.value()
    okc = 0
    for i in range(8):
        code, _, _ = router.handle_generate(
            {"prompt_ids": [40 + i] * 8, "max_tokens": 2,
             "temperature": 0.0}, deadline_ms=25_000)
        okc += (code == 200)
    check("decode-pool outage degrades to colocated fallback, not 503",
          okc == 8 and m.disagg_requests.value() == disagg_before,
          f"ok={okc} disagg_delta="
          f"{m.disagg_requests.value() - disagg_before}")

    router.stop()
finally:
    for p in procs.values():
        try:
            p.send_signal(signal.SIGKILL)
        except Exception:
            pass

print(f"\n{sum(ok)}/{len(ok)} checks passed")
sys.exit(0 if all(ok) else 1)
