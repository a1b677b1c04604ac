"""A temporary checkout for the CPU tests: a copy of ``chipbench/`` with a
tiny configuration, tiny traffic mixes and a ``BENCHMARK.json`` of tiny
cells dropped in beside the real files, none of which is edited."""
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


# the real training cell stands behind its tiny twin, which reports
# exactly the metrics that BENCHMARK.json gives the real one; the serving
# cells' metrics are in data/tiny_serving_metrics.json, as the PR that
# adds a serving cell will enter them in BENCHMARK.json
TWINS = {"train_124m_seq1024": ["tiny_train"]}


def tiny_bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "tests", "reduced": [],
                         "file": "chipbench/configs/tiny.json",
                         "why": "CPU test size"}]
    bench["workloads"] = [
        {"name": "tiny_chat", "config": "tiny", "traffic": "tiny_chat",
         "chips": 1, "why": "open loop"},
        {"name": "tiny_offline", "config": "tiny",
         "traffic": "tiny_offline", "chips": 1, "why": "closed loop"},
        {"name": "tiny_long", "config": "tiny", "traffic": "tiny_long",
         "chips": 1, "why": "open loop, answers of 30 to 50 tokens"},
        {"name": "tiny_train", "config": "tiny", "traffic": "tiny_train",
         "chips": 1, "why": "train"},
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [t for w in m["workloads"]
                              for t in TWINS.get(w, [])]
    with open(os.path.join(HERE, "data",
                           "tiny_serving_metrics.json")) as f:
        serving = json.load(f)
    bench["end_to_end"] += serving["end_to_end"]
    bench["per_layer"] += serving["per_layer"]
    return bench


def end_to_end_of(bench: dict, cell: str) -> set:
    return {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}


def make_root(tmp: str) -> str:
    """``tmp`` becomes a checkout: chipbench/ copied, tiny files added."""
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(tmp, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    data = os.path.join(HERE, "data")
    shutil.copy(os.path.join(data, "tiny.json"),
                os.path.join(tmp, "chipbench", "configs", "tiny.json"))
    for name in ("tiny_chat", "tiny_offline", "tiny_long", "tiny_train"):
        shutil.copy(os.path.join(data, name + ".json"),
                    os.path.join(tmp, "chipbench", "traffic",
                                 name + ".json"))
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(tiny_bench(), f)
    return tmp
