"""Drive end-to-end distributed tracing through the REAL two-leg
disaggregated path: one prefill + one decode engine replica as
subprocesses (`python -m kubedl_tpu.serving.server`), the role-aware
router in front, one request with the flight recorder armed
(`"debug": {"trace": true}`). Acceptance (docs/observability.md): the
request dispatches as a genuine two-leg flow (no fallback), and the
returned span tree shows BOTH legs parented under the router's root span
— `engine.request(kind=prefill)` under `router.prefill_leg` and
`engine.request(kind=adopt)` under `router.adopt_leg` — i.e. parentage,
not span counts, proves the context crossed every hop. The per-process
`/v1/trace` dumps then fuse through `scripts/tracemerge.py` into one
Chrome trace whose events carry the same parent chain."""
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

from kubedl_tpu.observability.tracing import TRACER, span_to_dict
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


def walk(nodes):
    """Flatten a flight-recorder tree, yielding every node."""
    for n in nodes:
        yield n
        yield from walk(n["children"])


def find(nodes, name, **attrs):
    for n in walk(nodes):
        if n["name"] == name and all(
            n["attrs"].get(k) == v for k, v in attrs.items()
        ):
            return n
    return None


ROLES = {"p0": "prefill", "d0": "decode"}
ports = {n: free_port() for n in ROLES}
procs = {n: spawn_replica(ports[n], ROLES[n]) for n in ROLES}
try:
    up = all(wait_healthy(p) for p in ports.values())
    check("prefill + decode replicas come up", up)
    if not up:
        raise SystemExit(1)

    router = ServingRouter(
        [{"name": n, "host": "127.0.0.1", "port": ports[n],
          "role": ROLES[n], "model": "tiny"} for n in sorted(ROLES)],
        probe_interval_s=0.2, probe_timeout_s=1.0,
        disagg_enabled=True,
    )
    router.start()
    router.probe_once()
    TRACER.clear()

    code, payload, _ = router.handle_generate(
        {"prompt_ids": [3, 1, 4, 1, 5, 9, 2, 6], "max_tokens": 6,
         "temperature": 0.0, "debug": {"trace": True}})
    m = router.metrics
    check("request rode the REAL two-leg path (no fallback)",
          code == 200 and m.disagg_requests.value() == 1
          and m.disagg_fallbacks.value() == 0,
          f"code={code} disagg={m.disagg_requests.value()} "
          f"fallbacks={m.disagg_fallbacks.value()}")

    rec = payload.get("trace") or {}
    tree = rec.get("spans") or []
    tid = rec.get("trace_id", "")
    root = tree[0] if tree else None
    check("flight recorder returned one tree rooted at router.request",
          len(tree) == 1 and root and root["name"] == "router.request",
          f"roots={[n['name'] for n in tree]}")

    # -- the tentpole assertion: PARENTAGE across every hop ---------------
    pleg = find(tree, "router.prefill_leg")
    aleg = find(tree, "router.adopt_leg")
    check("both disagg legs parent under the router root span",
          pleg is not None and aleg is not None
          and pleg["parent_id"] == root["span_id"]
          and aleg["parent_id"] == root["span_id"])

    er_pre = find(tree, "engine.request", kind="prefill")
    er_dec = find(tree, "engine.request", kind="adopt")
    check("prefill replica's engine.request parents under its leg",
          er_pre is not None and pleg is not None
          and er_pre["parent_id"] == pleg["span_id"])
    check("decode replica's engine.request parents under its leg",
          er_dec is not None and aleg is not None
          and er_dec["parent_id"] == aleg["span_id"])

    names_pre = {n["name"] for n in walk([er_pre])} if er_pre else set()
    names_dec = {n["name"] for n in walk([er_dec])} if er_dec else set()
    check("prefill-side spans (queue/admission/prefill/export) attached",
          {"engine.queue_wait", "engine.admission", "engine.prefill",
           "engine.handoff_export"} <= names_pre,
          f"prefill-side={sorted(names_pre)}")
    check("decode-side spans (adopt + decode segments) attached",
          {"engine.handoff_adopt", "engine.decode_segment"} <= names_dec,
          f"decode-side={sorted(names_dec)}")

    ids = {n["trace_id"] for n in walk(tree)}
    check("every span in the tree shares ONE trace id",
          ids == {tid} and len(tid) == 32, f"ids={ids}")

    # -- multi-process dump fusion through scripts/tracemerge.py ----------
    with tempfile.TemporaryDirectory() as tmp:
        dumps = [os.path.join(tmp, "router.json")]
        with open(dumps[0], "w") as f:
            json.dump({"spans": [span_to_dict(s)
                                 for s in TRACER.trace_spans(tid)]}, f)
        for n in sorted(ROLES):
            path = os.path.join(tmp, f"{n}.json")
            with urllib.request.urlopen(
                f"http://127.0.0.1:{ports[n]}/v1/trace?trace_id={tid}",
                timeout=5,
            ) as r:
                with open(path, "wb") as f:
                    f.write(r.read())
            dumps.append(path)
        merged_path = os.path.join(tmp, "merged.json")
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "tracemerge.py"),
             *dumps, "-o", merged_path, "--trace-id", tid],
            cwd=REPO, capture_output=True, text=True,
        )
        check("tracemerge fuses the three per-process dumps",
              res.returncode == 0, res.stderr[-200:])
        merged = json.load(open(merged_path))
        events = merged["traceEvents"]
        spans = [e for e in events if e.get("ph") == "X"]
        pids = {e["pid"] for e in spans}
        procs_named = [e for e in events
                       if e.get("ph") == "M" and e["name"] == "process_name"]
        check("merged trace renders 3 process tracks with spans from each",
              len(procs_named) == 3 and pids == {1, 2, 3},
              f"pids={pids}")
        by_id = {e["args"].get("span_id"): e for e in spans
                 if e["args"].get("span_id")}

        def parent_name(ev):
            p = by_id.get(ev["args"].get("parent_id"))
            return p["name"] if p else None

        mroot = next(e for e in spans if e["name"] == "router.request")
        legs = {e["name"]: e for e in spans
                if e["name"].startswith("router.") and e is not mroot}
        ereqs = [e for e in spans if e["name"] == "engine.request"]
        check("merged events reproduce the cross-process parent chain",
              all(parent_name(l) == "router.request"
                  for l in legs.values())
              and sorted(parent_name(e) for e in ereqs)
              == ["router.adopt_leg", "router.prefill_leg"],
              f"engine.request parents="
              f"{[parent_name(e) for e in ereqs]}")

    router.stop()
finally:
    for p in procs.values():
        try:
            p.send_signal(signal.SIGKILL)
        except Exception:
            pass

print(f"\n{sum(ok)}/{len(ok)} checks passed")
sys.exit(0 if all(ok) else 1)
