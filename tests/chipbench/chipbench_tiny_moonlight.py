"""The latent-attention expert cell's tiny twin: ``chipbench_tiny``'s
temporary checkout with a tiny configuration, its traffic mix and one more
cell."""
import json
import os
import shutil

import chipbench_tiny

HERE = os.path.dirname(os.path.abspath(__file__))
REAL_CELL = "train_moonlight_seq8192"
CELL = "tiny_moonlight_train"


def make_root(tmp: str) -> str:
    chipbench_tiny.make_root(tmp)
    data = os.path.join(HERE, "data")
    shutil.copy(os.path.join(data, "tiny_moonlight.json"),
                os.path.join(tmp, "chipbench", "configs",
                             "tiny_moonlight.json"))
    shutil.copy(os.path.join(data, "tiny_moonlight_train.json"),
                os.path.join(tmp, "chipbench", "traffic", CELL + ".json"))
    with open(os.path.join(chipbench_tiny.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny_moonlight", "source": "tests",
                             "reduced": [], "why": "CPU test size",
                             "file": "chipbench/configs/tiny_moonlight.json"})
    bench["workloads"].append({"name": CELL, "config": "tiny_moonlight",
                               "traffic": CELL, "chips": 1, "why": "train"})
    # the twin reports exactly what BENCHMARK.json gives the real cell
    for group in ("end_to_end", "per_layer"):
        for m, r in zip(bench[group], real[group]):
            assert m["name"] == r["name"]
            if REAL_CELL in r.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return tmp
