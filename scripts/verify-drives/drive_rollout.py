"""Drive the model-lifecycle rollout end to end against a REAL
subprocess fleet (`python -m kubedl_tpu.serving.server`, 2 colocated
tiny replicas per scenario), per docs/serving.md "Model lifecycle".

Scenario A — healthy canary promotes: hot-load a v2 checkpoint on every
replica over `/admin/load_version`, then let a `RolloutController` walk
the weight ladder 1→10→50→100 on a real soak timer while requests flow
through the router. Every response must be 200 and bit-identical to a
COLD-STARTED in-process engine serving that version alone (base from
init weights, v2 from its checkpoint dir) — both versions must actually
serve traffic, and promotion ends at {base: 0, v2: 100}.

Scenario B — degraded canary auto-rolls-back: a FRESH fleet arms a
seeded latency fault via `KUBEDL_SERVE_CONFIG["chaos"]` on the
`serving.canary_dispatch` site (2 s per NON-default-version dispatch
tick — baseline ticks on the same replica are untouched). The canary's
own SLO partition burns on the latency objective, the controller rolls
back in ONE weight flip mid-ladder, the RolledBack condition carries
the burning window + a trace-id exemplar, and the canary is fenced from
re-promotion. Zero requests are dropped at any point (the degradation
is latency, never errors), and baseline outputs stay bit-identical
before, during, and after the rollback."""
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
os.environ["JAX_PLATFORMS"] = "cpu"

ok = []
def check(name, cond, detail=""):
    ok.append(bool(cond))
    print(("PASS" if cond else "FAIL"), name, detail)

from kubedl_tpu.serving.rollout import (
    COMPLETE,
    ROLLED_BACK,
    RolloutController,
    RolloutFenced,
)
from kubedl_tpu.serving.router import ServingRouter

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

PROMPTS = [(3, 1, 4, 1, 5, 9), (2, 7, 1, 8, 2, 8), (1, 1, 2, 3, 5, 8)]
GEN = 8
#: the canary's own partition pages when BOTH windows burn >= 2x against
#: a 90% objective with a 2.5 s latency SLO. Decode is segment-based
#: (an 8-token generate is ~3-4 dispatch ticks), so the injected
#: 2 s/tick fault puts every v2 request past ~6 s while warmed requests
#: finish in well under a second even on a loaded 1-core box — wide
#: margin on BOTH sides of the objective.
SLO = {
    "objective": 0.9,
    "latency_objective_ms": 2500.0,
    "alerts": [{"severity": "page", "short_s": 5.0, "long_s": 20.0,
                "threshold": 2.0}],
}


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def spawn_replica(port, chaos_cfg=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    cfg = {"preset": "tiny", "port": port, "max_batch": 4}
    if chaos_cfg:
        cfg["chaos"] = chaos_cfg
    env["KUBEDL_SERVE_CONFIG"] = json.dumps(cfg)
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


def post(port, path, payload, timeout=60.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def cold_references(v2_dir):
    """Outputs from cold-started engines each serving ONE version alone —
    the bit-identity oracle for everything the fleet answers."""
    from kubedl_tpu.serving.server import LlamaEngine

    refs = {"base": {}, "v2": {}}
    eng = LlamaEngine(preset="tiny", max_batch=4)
    try:
        import jax

        from kubedl_tpu.models import llama
        from kubedl_tpu.training.checkpoint import save_checkpoint

        params = llama.llama_init(jax.random.PRNGKey(0), eng.cfg)
        params = jax.tree_util.tree_map(lambda x: x * 1.5, params)
        save_checkpoint(v2_dir, {"params": params}, 1)
        for p in PROMPTS:
            refs["base"][p] = eng.generate(
                list(p), max_tokens=GEN, temperature=0.0)["token_ids"]
    finally:
        eng.close()
    eng = LlamaEngine(preset="tiny", max_batch=4, ckpt_dir=v2_dir)
    try:
        for p in PROMPTS:
            refs["v2"][p] = eng.generate(
                list(p), max_tokens=GEN, temperature=0.0)["token_ids"]
    finally:
        eng.close()
    return refs


def build_fleet(v2_dir, chaos_cfg=None):
    ports = [free_port(), free_port()]
    procs = [spawn_replica(p, chaos_cfg) for p in ports]
    up = all(wait_healthy(p) for p in ports)
    if up:
        for p in ports:
            st, out = post(p, "/admin/load_version",
                           {"version": "v2", "ckpt_dir": v2_dir})
            assert st == 200 and out["loaded"] == ["base", "v2"], out
            # warm BOTH versions (full decode length) so drill
            # latencies are steady-state — the first generate on a
            # freshly loaded version pays its weight upload, which must
            # not be billed to the canary's SLO partition (these warm
            # requests go direct to the replica, not through the router)
            for ver in ("base", "v2"):
                post(p, "/v1/generate",
                     {"prompt_ids": list(PROMPTS[0]), "max_tokens": GEN,
                      "temperature": 0.0, "model_version": ver},
                     timeout=300.0)
    router = ServingRouter(
        [{"name": f"r{i}", "host": "127.0.0.1", "port": p,
          "model": "tiny"} for i, p in enumerate(ports)],
        probe_interval_s=0.5, probe_timeout_s=2.0,
        hedge_enabled=False, slo=SLO,
    )
    router.start()
    router.probe_once()
    return ports, procs, router, up


def run_traffic(router, refs, n, codes, mismatches, served):
    for j in range(n):
        p = PROMPTS[j % len(PROMPTS)]
        code, payload, _ = router.handle_generate(
            {"prompt_ids": list(p), "max_tokens": GEN,
             "temperature": 0.0})
        codes.append(code)
        if code != 200:
            continue
        v = payload.get("model_version", "")
        served[v] = served.get(v, 0) + 1
        if v not in refs or payload["token_ids"] != refs[v][p]:
            mismatches.append((v, p))


with tempfile.TemporaryDirectory() as tmp:
    v2_dir = os.path.join(tmp, "v2")
    refs = cold_references(v2_dir)
    check("cold per-version references differ (v2 is a real new model)",
          all(refs["base"][p] != refs["v2"][p] for p in PROMPTS))

    # ---- scenario A: healthy canary walks the ladder and promotes ----
    ports, procs, router, up = build_fleet(v2_dir)
    try:
        check("scenario A fleet up with v2 hot-loaded on every replica",
              up)
        ctrl = RolloutController(router, canary_version="v2",
                                 baseline_version="base",
                                 steps=(1, 10, 50, 100), soak_s=1.5)
        ctrl.begin()
        codes, mism, served = [], [], {}
        result, deadline = "", time.time() + 180
        while time.time() < deadline:
            run_traffic(router, refs, 3, codes, mism, served)
            result = ctrl.tick()
            if result in ("promoted", "rolled_back"):
                break
            time.sleep(0.3)
        check("healthy canary PROMOTES to 100% through the soak ladder",
              result == "promoted" and ctrl.phase == COMPLETE,
              f"result={result} status={ctrl.status()}")
        check("promotion ends at {base: 0, v2: 100}",
              router.version_weights() == {"base": 0, "v2": 100})
        check("zero dropped requests through the whole promotion",
              codes and all(c == 200 for c in codes),
              f"n={len(codes)} non200={[c for c in codes if c != 200]}")
        check("both versions actually served canary traffic",
              served.get("base", 0) > 0 and served.get("v2", 0) > 0,
              f"served={served}")
        check("every response bit-identical to its version's cold engine",
              not mism, f"mismatches={mism[:3]}")
        router.stop()
    finally:
        for pr in procs:
            try:
                pr.send_signal(signal.SIGKILL)
            except Exception:
                pass
    for pr in procs:
        pr.wait(timeout=10)

    # ---- scenario B: degraded canary burns its SLO and rolls back ----
    chaos_cfg = {"seed": 17, "sites": {"serving.canary_dispatch": [
        {"mode": "latency", "latency_ms": 2000.0, "every": 1}]}}
    ports, procs, router, up = build_fleet(v2_dir, chaos_cfg)
    try:
        check("scenario B fleet up with the seeded canary latency fault",
              up)
        ctrl = RolloutController(router, canary_version="v2",
                                 baseline_version="base",
                                 steps=(50, 100), soak_s=60.0)
        ctrl.begin()
        codes, mism, served = [], [], {}
        # a few requests before the first tick so the canary partition
        # holds real exemplars, then tick until the burn gate fires
        run_traffic(router, refs, 6, codes, mism, served)
        result, deadline = "", time.time() + 120
        while time.time() < deadline:
            result = ctrl.tick()
            if result == "rolled_back":
                break
            run_traffic(router, refs, 2, codes, mism, served)
            time.sleep(0.2)
        check("degraded canary AUTO-ROLLS-BACK on its own SLO burn",
              result == "rolled_back" and ctrl.phase == ROLLED_BACK,
              f"result={result}")
        check("rollback is one flip to {base: 100, v2: 0}",
              router.version_weights() == {"base": 100, "v2": 0})
        cond = ctrl.conditions[-1] if ctrl.conditions else {}
        check("RolledBack condition carries burning window + exemplar",
              cond.get("type") == "RolledBack"
              and cond.get("severity") == "page"
              and cond.get("short_burn", 0) >= 2.0
              and cond.get("long_burn", 0) >= 2.0
              and bool(cond.get("trace_id")),
              f"cond={cond}")
        check("baseline partition stayed healthy while the canary burned",
              not router.version_tracker("base").burning(
                  router.version_tracker("base").alerts[0]))
        fenced = False
        try:
            ctrl.begin()
        except RolloutFenced:
            fenced = True
        check("rolled-back canary is fenced from re-promotion", fenced)

        # after the flip: traffic keeps flowing on baseline only,
        # still bit-identical, still zero drops
        before_v2 = served.get("v2", 0)
        run_traffic(router, refs, 8, codes, mism, served)
        check("post-rollback traffic all lands on baseline",
              served.get("v2", 0) == before_v2
              and served.get("base", 0) >= 8, f"served={served}")
        check("zero dropped requests across the WHOLE degraded drill",
              codes and all(c == 200 for c in codes),
              f"n={len(codes)} non200={[c for c in codes if c != 200]}")
        check("baseline outputs bit-identical before/during/after",
              not mism, f"mismatches={mism[:3]}")
        router.stop()
    finally:
        for pr in procs:
            try:
                pr.send_signal(signal.SIGKILL)
            except Exception:
                pass

print(f"\n{sum(ok)}/{len(ok)} checks passed")
sys.exit(0 if all(ok) else 1)
