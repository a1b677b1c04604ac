"""The three ``trainer.*`` metrics that read the program's stall log: the
readers on hand-made logs (none, one inside the window, one straddling
its end, one on the feeder's thread, one before the window), nothing read
from a program that keeps no log or has lost records, the entries as
``run.py`` finds them, and the tiny training cell driven on the CPU with
a sleep planted in its feed."""
import json
import os
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny  # noqa: E402
from chipbench import run as R  # noqa: E402
from chipbench.harness import stalls as harness  # noqa: E402

NAMES = ["trainer.stalled_steps", "trainer.stall_pct",
         "trainer.host_late_ms"]
UNITS = {"trainer.stalled_steps": "steps", "trainer.stall_pct": "%",
         "trainer.host_late_ms": "ms"}
RUN = {"window": (130.0, 180.0)}


def rec(phase, start, seconds, expected=0.5, thread="MainThread"):
    return {"phase": phase, "thread": thread, "step": 7, "start": start,
            "seconds": seconds, "expected_s": expected,
            "verdict": "device_or_runtime"}


INSIDE = rec("ndarray.readback", 150.0, 3.0)            # 2.5 s over
STRADDLING = rec("input.next", 179.0, 4.0, 0.0)         # 1.0 s of it inside
FEEDER = rec("input.pull", 150.0, 3.0, 0.0, "mxtpu-data-feeder")
WARM_UP = rec("ndarray.readback", 120.0, 12.0)          # began before it
DISPATCH = rec("trainer.dispatch", 140.0, 1.0, 0.002)   # 0.998 s over
LATE = [(100.0, 0.9), (131.0, 0.0004), (150.5, 0.0031), (179.5, 0.0007),
        (190.0, 2.5)]
# the log, then stalled_steps, stall_pct, host_late_ms
CASES = {
    "none": ([], 0.0, 0.0, 3.1),
    "one_inside": ([INSIDE], 1.0, 100 * 2.5 / 50, 3.1),
    "one_straddling_the_end": ([STRADDLING], 1.0, 100 * 1.0 / 50, 3.1),
    "one_on_the_feeder": ([FEEDER], 0.0, 0.0, 3.1),
    "one_before_the_window": ([WARM_UP], 0.0, 0.0, 3.1),
    "all_of_them": ([WARM_UP, DISPATCH, INSIDE, FEEDER, STRADDLING], 3.0,
                    100 * (0.998 + 2.5 + 1.0) / 50, 3.1),
}


def _read(name, run=RUN):
    reader = R.load_module(REPO, "layer_metrics", name)
    assert reader.NAME == name
    return reader.read(run)


@pytest.mark.parametrize("case", sorted(CASES))
def test_readers_on_a_hand_made_log(case, monkeypatch):
    records, *want = CASES[case]
    monkeypatch.setattr(harness, "program_log",
                        lambda: (list(records), list(LATE)))
    got = [_read(name) for name in NAMES]
    assert got == pytest.approx(want)
    assert all(isinstance(x, float) for x in got)   # 0.0, never absent


def test_no_witness_in_the_window_leaves_its_metric_out(monkeypatch):
    monkeypatch.setattr(harness, "program_log",
                        lambda: ([INSIDE], [(100.0, 0.9)]))
    assert _read("trainer.host_late_ms") is None
    assert _read("trainer.stalled_steps") == 1.0


@pytest.mark.parametrize("program", ["parent", "lost"])
@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_without_a_whole_log(name, program,
                                                  monkeypatch):
    """The parent's program (no such module, or one with no ``log``) and
    one whose bounded log has lost records: the line leaves the metric
    out and nothing raises."""
    from mxnet_tpu.observability import stalls

    if program == "parent":
        monkeypatch.delattr(stalls, "log")
    else:
        monkeypatch.setattr(stalls, "log", lambda: [INSIDE])
        monkeypatch.setattr(stalls, "dropped", lambda: 2)
    assert _read(name) is None


def test_entries_as_the_harness_finds_them():
    """Found BY NAME, wherever later entries put them.  No ``workloads``
    key: the three are due in every cell that reports
    ``train_tokens_per_s``, the eight there are and those to come (a
    list naming the cells would also break the cells' own tests, which
    hold every listed metric but their own and the shared ones of their
    day away from their cell)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = bench["end_to_end"][0]["workloads"]
    assert bench["end_to_end"][0]["name"] == "train_tokens_per_s"
    assert len(cells) == 8
    for name in NAMES:
        assert by_name[name] == {
            "name": name, "unit": UNITS[name], "better": "lower",
            "source": "program_counter", "layer": "trainer",
            "moves": "train_tokens_per_s"}
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py"))
    for cell in cells:
        listed = {m["name"] for m in R.metrics_for(
            bench, "per_layer", cell, {"train_tokens_per_s": 1.0})}
        assert set(NAMES) <= listed
    # and in no cell that trains nothing
    assert not set(NAMES) & {m["name"] for m in R.metrics_for(
        bench, "per_layer", cells[0], {"serve_tokens_per_s": 1.0})}


def test_tiny_training_cell_counts_a_sleep_planted_in_its_feed(tmp_path,
                                                               capsys):
    """The training driver on the CPU WITHOUT a trace (a traced CPU run
    refuses), its records handed to ``run.per_layer`` with the bench cut
    to the three entries: one stalled step, and the share of the window
    that was planted."""
    import jax

    from mxnet_tpu.observability import stalls

    planted_s, at_batch = 0.6, 40
    root = chipbench_tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = [m for m in bench["per_layer"] if m["name"] in NAMES]
    assert [m["name"] for m in entries] == NAMES
    bench = dict(bench, per_layer=entries)
    cell, cfg = R.find_cell(bench, "tiny_train")
    traffic = R.load_json(os.path.join(root, "chipbench", "traffic",
                                       cell["traffic"] + ".json"))
    real = R.load_module(root, "generators", traffic["generator"])
    feeds = []

    def generate(*args):
        """The program's feed (the first one made) sleeps once; the
        reference's batches come as they are."""
        feeds.append(None)
        sleeps = len(feeds) == 1
        for i, batch in enumerate(real.generate(*args)):
            if sleeps and i == at_batch:
                time.sleep(planted_s)
            yield batch

    stalls._reset()
    res = R.load_module(root, "drivers", traffic["driver"]).run({
        "cell": cell, "config": R.load_json(os.path.join(root, cfg["file"])),
        "traffic": traffic, "seed": 2 ** 31 + 41, "seconds": 2.0,
        "trace": False, "devices": jax.devices()[:1],
        "t_start": time.monotonic(), "options": {},
        "trace_dir": str(tmp_path / "trace"),
        "generator": types.SimpleNamespace(generate=generate)})
    assert res["correct"], capsys.readouterr().out
    run = dict(res["records"], e2e=res["metrics"])
    got = {k: v["value"] for k, v in
           R.per_layer(bench, "tiny_train", run, root).items()}
    assert set(got) == set(NAMES)
    w0, w1 = run["window"]
    waits = [r for r in stalls.log() if r["phase"] == "input.next"]
    assert len(waits) == 1 and w0 <= waits[0]["start"] < w1, stalls.log()
    assert waits[0]["verdict"] == "thread:input.pull"
    assert got["trainer.stalled_steps"] == 1.0, stalls.log()
    planted_pct = 100.0 * planted_s / (w1 - w0)
    assert abs(got["trainer.stall_pct"] - planted_pct) < planted_pct / 5
    assert 0.0 <= got["trainer.host_late_ms"] < 1e3 * planted_s
    stalls._reset()
