#!/usr/bin/env python3
"""MNIST-class convergence workload (BASELINE target 1 analogue;
reference: example/tf/mnist). Runs as a pod command under any workload
kind:

    python examples/mnist_convnet.py [--steps 150] [--batch 128]

Trains the convnet family on MNIST-shaped synthetic digits (fixed class
templates + noise — learnable structure without a dataset download) and
exits 0 only if the loss dropped AND held-out accuracy clears 90%.
Prints one worker_summary JSON line like the LM entrypoint does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubedl_tpu.utils.compile_cache import enable_compilation_cache

enable_compilation_cache()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--min-accuracy", type=float, default=0.9)
    ap.add_argument(
        "--require-tf-config", action="store_true",
        help="fail unless a valid TF_CONFIG is injected (TFJob pods: "
        "proves the operator's cluster-spec wiring feeds a real consumer, "
        "reference scripts/run_tf_test_job.sh)",
    )
    args = ap.parse_args()

    task = {}
    tf_config = os.environ.get("TF_CONFIG", "")
    if tf_config:
        parsed = json.loads(tf_config)  # malformed wiring must crash
        task = parsed.get("task", {})
        assert parsed.get("cluster", {}).get("worker"), "TF_CONFIG has no workers"
        print(json.dumps({"tf_config_task": task}), flush=True)
    elif args.require_tf_config:
        print("TF_CONFIG missing", file=sys.stderr)
        return 1

    from kubedl_tpu.models import convnet

    cfg = convnet.ConvNetConfig()
    data = convnet.SyntheticDigits(cfg, args.batch)
    params, summary = convnet.fit(cfg, iter(data), steps=args.steps)

    test_images, test_labels = next(iter(
        convnet.SyntheticDigits(cfg, 512, seed=99)
    ))[:2]
    acc = convnet.accuracy(params, test_images, test_labels, cfg)
    summary["accuracy"] = round(acc, 4)
    print(json.dumps({"worker_summary": summary}), flush=True)
    ok = summary["final_loss"] < summary["first_loss"] and acc >= args.min_accuracy
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
