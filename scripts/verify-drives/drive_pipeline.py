"""Drive the double-buffered pipeline end to end over real HTTP:
serve_main-equivalent engine + handler, /v1/stats pipeline block,
/metrics Prometheus text, and the autoscaler consuming the REAL
dict-shaped http probe (qps + queued)."""
import json
import os
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

from http.server import ThreadingHTTPServer
from kubedl_tpu.serving.server import LlamaEngine, make_handler

eng = LlamaEngine(preset="tiny", max_batch=4, max_seq=64)
srv = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(eng, "tiny"))
port = srv.server_address[1]
threading.Thread(target=srv.serve_forever, daemon=True).start()

def post(payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())

# concurrent load so segments + deferred harvests actually happen
threads = []
results = []
def go(n):
    results.append(post({"prompt_ids": [1, 2, n], "max_tokens": 24}))
for n in range(6):
    t = threading.Thread(target=go, args=(n,))
    t.start(); threads.append(t)
for t in threads:
    t.join()
check("6 concurrent HTTP generates complete",
      len(results) == 6 and all(len(r.get("token_ids", [])) == 24 for r in results))

with urllib.request.urlopen(f"http://127.0.0.1:{port}/v1/stats", timeout=10) as r:
    st = json.loads(r.read())
p = st.get("pipeline", {})
check("/v1/stats has pipeline accounting",
      p.get("segments", 0) >= 1 and "overlap_ratio" in p and "tick_ms_p50" in p,
      json.dumps({k: p.get(k) for k in ("ticks","segments","deferred_harvests","overlap_ratio")}))
check("pipeline actually double-buffered", p.get("deferred_harvests", 0) >= 1,
      f"deferred={p.get('deferred_harvests')}")
check("queued surfaced in stats", st.get("queued") == 0)

with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
    text = r.read().decode()
check("/metrics exports serving family",
      "kubedl_tpu_serving_segments" in text
      and "kubedl_tpu_serving_harvest_ms_bucket" in text
      and "kubedl_tpu_serving_overlap_ratio" in text)

# autoscaler consumes the REAL http probe (dict: qps + queued)
from kubedl_tpu.serving.controller import http_qps_probe
probe = http_qps_probe(port=port)
class FakePod:
    class status:
        pod_ip = "127.0.0.1"
v = probe(FakePod())
check("http probe returns full stats dict",
      isinstance(v, dict) and "qps" in v and "queued" in v,
      f"qps={v.get('qps')} queued={v.get('queued')}")

# injected failure mid-service, then engine keeps serving over HTTP
orig = eng._runner._segment_fn
state = {"armed": True}
def boom(k, greedy):
    fn = orig(k, greedy)
    def w(*a, **kw):
        if state["armed"]:
            state["armed"] = False
            raise RuntimeError("injected")
        return fn(*a, **kw)
    return w
eng._runner._segment_fn = boom
r1 = post({"prompt_ids": [9], "max_tokens": 8})
r2 = post({"prompt_ids": [9], "max_tokens": 8})
check("failure fails one request, next serves",
      "error" in r1 and len(r2.get("token_ids", [])) == 8,
      f"r1={r1.get('error','?')[:30]}")
with urllib.request.urlopen(f"http://127.0.0.1:{port}/v1/stats", timeout=10) as r:
    st2 = json.loads(r.read())
check("error accounted + pipeline counters reset",
      st2["pipeline"]["errors"] == 1 and st2["pipeline"]["inflight"] == 0)

srv.shutdown(); srv.server_close(); eng.close()
print(f"\n{sum(ok)}/{len(ok)} checks passed")
raise SystemExit(0 if all(ok) else 1)
