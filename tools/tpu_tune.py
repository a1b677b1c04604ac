"""Batch-size sweep for the bench workloads on the real chip.

Finds the throughput-optimal per-chip batch for each bench.py workload by
re-running bench.py's own workload builders (same model, loss, timing
discipline) with a batch override — short runs sized to finish well
inside any driver timeout.

Usage:
    python tools/tpu_tune.py --workload gpt2 --batches 8,16,24,32
    python tools/tpu_tune.py --workload resnet50 --batches 64,128,256

Prints one JSON line per batch plus a `best` line; feed the winner back
into bench.py's on_tpu config.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench as _bench

_TABLE = {"gpt2": _bench.bench_gpt2, "gpt2_long": _bench.bench_gpt2_long,
          "resnet50": _bench.bench_resnet50,
          "resnet50_io": _bench.bench_resnet50_io,
          "bert": _bench.bench_bert, "nmt": _bench.bench_nmt}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gpt2", choices=sorted(_TABLE))
    ap.add_argument("--batches", default="8,16,24,32")
    args = ap.parse_args()

    from mxnet_tpu.utils.platform import enable_compile_cache, require_tpu
    require_tpu()
    enable_compile_cache()

    from mxnet_tpu import amp
    amp.init("bfloat16")

    best = None
    for b in [int(x) for x in args.batches.split(",")]:
        try:
            rec = _TABLE[args.workload](True, batch_override=b)
        except Exception as e:  # OOM etc. — report and keep sweeping
            print(json.dumps({"batch": b, "error": str(e)[:200]}),
                  flush=True)
            continue
        actual = rec.get("batch", b)
        print(json.dumps({"batch": actual, "requested": b,
                          "value": rec["value"], "unit": rec["unit"],
                          "vs_baseline": rec["vs_baseline"]}), flush=True)
        if best is None or rec["value"] > best[1]:
            best = (actual, rec["value"])
    if best:
        print(json.dumps({"best": best[0], "value": best[1]}), flush=True)


if __name__ == "__main__":
    main()
