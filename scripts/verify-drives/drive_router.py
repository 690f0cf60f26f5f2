"""Drive the fault-tolerant serving router end to end: three REAL engine
replicas as subprocesses (`python -m kubedl_tpu.serving.server`), a
seeded FaultPlan choosing the moment one is SIGKILLed under client load.
Acceptance (docs/serving.md "Router"): every queued not-yet-dispatched
request completes via failover (zero lost), only work in flight on the
dead replica is retried — at most once, inside its deadline — the
breaker ejects the dead replica and readmits it after restart, greedy
outputs through the router are bit-identical to a direct engine call,
expired deadlines never dispatch, and a draining replica stops taking
new work without dropping anything."""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
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


def spawn_replica(port):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["KUBEDL_SERVE_CONFIG"] = json.dumps({
        "preset": "tiny", "port": port, "max_batch": 2,
        "drain_grace_s": 5.0,
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


def get_json(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as r:
        return json.loads(r.read())


def post_generate(port, body, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


ports = {f"r{i}": free_port() for i in range(3)}
procs = {n: spawn_replica(p) for n, p in ports.items()}
try:
    up = all(wait_healthy(p) for p in ports.values())
    check("3 engine replicas come up", up)
    if not up:
        raise SystemExit(1)

    router = ServingRouter(
        [(n, "127.0.0.1", p) for n, p in sorted(ports.items())],
        probe_interval_s=0.2, probe_timeout_s=1.0,
        eject_threshold=3, readmit_cooldown_s=1.0,
        hedge_enabled=True, hedge_default_ms=3000.0,
        max_retries=1, default_deadline_ms=30_000.0,
    )
    router.start()
    router.probe_once()

    # -- bit-identity: the router must never change RESULTS ---------------
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    direct = post_generate(ports["r0"], {"prompt_ids": prompt,
                                         "max_tokens": 8,
                                         "temperature": 0.0})
    code, via, _ = router.handle_generate(
        {"prompt_ids": prompt, "max_tokens": 8, "temperature": 0.0})
    check("greedy outputs through router bit-identical to direct call",
          code == 200 and via["token_ids"] == direct["token_ids"],
          f"direct={direct['token_ids']} routed={via.get('token_ids')}")

    # -- expired deadline: never dispatched, not even once -----------------
    before = sum(get_json(p, "/v1/stats")["requests"]
                 for p in ports.values())
    code, _, _ = router.handle_generate({"prompt_ids": [1]}, deadline_ms=0)
    after = sum(get_json(p, "/v1/stats")["requests"]
                for p in ports.values())
    check("expired deadline is 504 with zero dispatches",
          code == 504 and after == before)

    # -- SIGKILL one replica under load, moment chosen by a seeded plan ----
    N = 36
    plan = FaultPlan(seed=11, sites={"replica.kill": [FaultSpec.nth(9)]})
    victim = "r1"
    results = [None] * N
    killed_at = {"i": None}

    def client(i):
        # deterministic greedy workload; every prompt long enough to get
        # affinity so the fleet spreads by prefix, not randomness
        body = {"prompt_ids": [(i % 7) + 2] * 8 + [100 + i],
                "max_tokens": 4, "temperature": 0.0}
        code, payload, _ = router.handle_generate(body, deadline_ms=20_000)
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
            time.sleep(0.03)  # sustained load, queue never fully drains
        for t in threads:
            t.join(timeout=30)
    check("seeded plan injected exactly one kill",
          plan.faults("replica.kill") == 1 and killed_at["i"] == 8,
          f"killed before request #{killed_at['i']}")

    codes = [r[0] for r in results if r is not None]
    lost = N - len(codes)
    failures = [c for c in codes if c != 200]
    check("zero lost requests: every queued request completed via failover",
          lost == 0 and not failures,
          f"lost={lost} non200={failures[:5]}")
    retries = router.metrics.retries.value()
    transport = sum(
        router.metrics.transport_errors.value(replica=n) for n in ports
    )
    check("only in-flight-on-dead-replica work retried, bounded burst",
          0 < retries <= transport <= 6,
          f"retries={retries} transport_errors={transport}")
    check("at most one retry per request (budget-capped)",
          retries <= router.retry_budget.spent + 0
          and router.max_retries == 1)

    deadline = time.time() + 10
    while time.time() < deadline:
        if router.stats()["replicas"][victim]["state"] == policy.OPEN:
            break
        time.sleep(0.1)
    st = router.stats()["replicas"][victim]
    check("breaker ejected the dead replica",
          st["state"] == policy.OPEN and st["ejections"] >= 1,
          f"state={st['state']} ejections={st['ejections']}")

    # -- restart the victim on the same port: the probe readmits it -------
    procs[victim].wait(timeout=10)
    procs[victim] = spawn_replica(ports[victim])
    check("victim restarted", wait_healthy(ports[victim]))
    deadline = time.time() + 30
    while time.time() < deadline:
        if router.stats()["replicas"][victim]["state"] == policy.CLOSED:
            break
        time.sleep(0.1)
    st = router.stats()["replicas"][victim]
    check("half-open probe readmitted the restarted replica",
          st["state"] == policy.CLOSED and st["readmissions"] >= 1,
          f"state={st['state']} readmissions={st['readmissions']}")

    served = set()
    for i in range(24):
        code, payload, _ = router.handle_generate(
            {"prompt_ids": [i + 2] * 9, "max_tokens": 2,
             "temperature": 0.0}, deadline_ms=20_000)
        if code == 200:
            served.add(payload.get("served_by", ""))
    # engine payloads don't carry names; infer from per-replica counters
    reqs = {n: get_json(p, "/v1/stats")["requests"]
            for n, p in ports.items()}
    check("readmitted replica takes traffic again",
          reqs[victim] > 0, f"requests={reqs}")

    # -- graceful drain: distinguishable 503, router routes around --------
    drain_target = "r2"
    req = urllib.request.Request(
        f"http://127.0.0.1:{ports[drain_target]}/admin/drain", data=b"{}")
    urllib.request.urlopen(req, timeout=5).read()
    check("engine reports draining in stats",
          get_json(ports[drain_target], "/v1/stats")["draining"] is True)
    try:
        post_generate(ports[drain_target], {"prompt_ids": [1]})
        direct_503 = None
    except urllib.error.HTTPError as e:
        direct_503 = (e.code, json.loads(e.read()))
    check("drain 503 is distinguishable (reason: draining)",
          direct_503 is not None and direct_503[0] == 503
          and direct_503[1].get("reason") == "draining")
    spent_before = router.retry_budget.spent
    okc = 0
    for i in range(12):
        code, _, _ = router.handle_generate(
            {"prompt_ids": [50 + i] * 8, "max_tokens": 2}, 20_000)
        okc += (code == 200)
    check("router routes around the draining replica, free of budget",
          okc == 12 and router.stats()["replicas"][drain_target]["draining"],
          f"ok={okc} spent_delta={router.retry_budget.spent - spent_before}")

    router.stop()
finally:
    for p in procs.values():
        try:
            p.send_signal(signal.SIGKILL)
        except Exception:
            pass

print(f"\n{sum(ok)}/{len(ok)} checks passed")
sys.exit(0 if all(ok) else 1)
