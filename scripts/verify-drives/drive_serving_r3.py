"""Drive: round-3 serving — batched prefill TTFT, configurable bind host,
timeout slot release — end to end through the operator + real HTTP."""
import json, os, sys, tempfile, time, urllib.request

os.environ["JAX_PLATFORMS"] = "cpu"
import pathlib; sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from kubedl_tpu.lineage.types import ModelVersion, ModelVersionPhase
from kubedl_tpu.operator import Operator, OperatorOptions
from kubedl_tpu.runtime.executor import ThreadRuntime
from kubedl_tpu.serving.types import Framework, Inference, Predictor
from kubedl_tpu.utils.invariants import check_invariants

checks = []
def check(name, ok, detail=""):
    checks.append((name, ok))
    print(("PASS " if ok else "FAIL ") + name + (f" — {detail}" if detail else ""))

tmp = tempfile.mkdtemp(prefix="kdl-serve-r3-")
opts = OperatorOptions(
    local_addresses=True, artifact_registry_root=os.path.join(tmp, "reg"),
    compile_cache_dir=os.path.join(tmp, "cc"),
)
port = 18091
with Operator(opts, runtime=ThreadRuntime()) as op:
    mv = ModelVersion(model_name="m1", storage_root=os.path.join(tmp, "model"),
                      phase=ModelVersionPhase.PENDING)
    mv.metadata.name = "mv1"
    op.store.create(mv)
    pred = Predictor(name="main", model_version="mv1")
    # explicit non-loopback-capable host config (0.0.0.0 binds all ifaces)
    pred.template.spec.main_container().set_env(
        "KUBEDL_SERVE_CONFIG",
        json.dumps({"port": port, "preset": "tiny", "host": "0.0.0.0",
                    "max_batch": 2}),
    )
    inf = Inference(framework=Framework.JAX, predictors=[pred])
    inf.metadata.name = "inf1"
    os.makedirs(os.path.join(tmp, "model"), exist_ok=True)
    op.store.create(inf)

    def post(prompt, n, timeout=30):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=json.dumps({"prompt_ids": prompt, "max_tokens": n}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    deadline = time.time() + 90
    result = None
    while time.time() < deadline and result is None:
        try:
            result = post([1, 2, 3], 4)
        except Exception:
            time.sleep(0.5)
    check("server answered (0.0.0.0 bind)", result is not None)
    check("short prompt generates", result and len(result["token_ids"]) == 4)

    # long prompt: prefill makes this 1 forward + n decode steps
    long_prompt = list(range(1, 60))
    t0 = time.perf_counter()
    r2 = post(long_prompt, 3)
    dt = (time.perf_counter() - t0) * 1e3
    check("59-token prompt served", len(r2["token_ids"]) == 3, f"{dt:.0f}ms")
    check("prompt_len recorded", r2["prompt_len"] == 59)

    # prefill path: compare latency vs per-token feeding expectation: a
    # 59-token prompt must NOT cost ~59x a decode step. Engine decode step
    # on CPU tiny ~ a few ms; allow generous bound.
    r3 = post([5], 3)
    t0 = time.perf_counter()
    r4 = post(long_prompt, 1)
    dt_long = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    r5 = post([7], 1)
    dt_short = (time.perf_counter() - t0) * 1e3
    check("long-prompt TTFT not ~O(prompt_len) decode steps",
          dt_long < dt_short * 8 + 200, f"long {dt_long:.0f}ms short {dt_short:.0f}ms")
    stats = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/v1/stats", timeout=5).read())
    check("stats served", stats["requests"] >= 5, str(stats.get("requests")))
    bad = check_invariants(op)
    check("invariants green", not bad, str(bad))

failed = [n for n, ok in checks if not ok]
print(f"\n{len(checks) - len(failed)}/{len(checks)} checks passed")
sys.exit(1 if failed else 0)
