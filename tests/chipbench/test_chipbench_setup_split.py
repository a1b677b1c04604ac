"""The seven ``setup.*`` metrics that split ``setup_s``: the readers on a
hand-made run and log (to the millisecond: overlapping intervals, one
that began before the process's first clock reading, one that straddles
the window's start, records after it), the identity they keep, nothing
read from a program that keeps no log, the entries as ``run.py`` finds
them, and the tiny training cell driven on the CPU with its own log."""
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny  # noqa: E402
from chipbench import run as R  # noqa: E402
from chipbench.harness import startup  # noqa: E402

CELLS = ["train_124m_seq1024", "train_nemotron_tt_seq8192",
         "train_qwen3_next_seq8192", "train_granite_4h_p10"]
# process start 100.0, the window's start 130.0
RUN = {"window": (130.0, 181.0), "e2e": {"setup_s": 30.0}}
MAIN, OTHER = 1, 2


def _iv(kind, start, end, thread=MAIN):
    return (kind, end, end - start, thread, "jit(f)")


RECORDS = [
    _iv("trace", 99.0, 100.5),           # began before the clock was read
    _iv("trace", 101.0, 103.0),
    _iv("lower", 102.5, 104.0, OTHER),   # overlaps the trace
    ("cache_hit", 105.0, 0.0, MAIN, ""),
    _iv("cache_load", 104.5, 106.0),     # inside its compile
    _iv("compile", 104.0, 110.0),
    ("cache_miss", 110.0, 0.0, MAIN, ""),
    _iv("compile", 108.0, 112.0, OTHER),  # beside it, on another thread
    _iv("trace", 113.0, 114.0),          # inside build
    _iv("trace", 119.0, 121.0),
    _iv("compile", 121.0, 125.0),
    _iv("compile", 128.0, 133.0),        # straddles the cut
    ("cache_miss", 141.0, 0.0, MAIN, ""),    # the reference's, after the cut
    _iv("compile", 140.0, 150.0),
]
BUILDS = [(112.0, 118.0, 1.0, 3.0, 1.5)]
WANT = {"setup.before_build_s": 12.0, "setup.build_s": 6.0,
        "setup.trace_s": 0.5 + 3.0 + 1.0 + 2.0,
        "setup.compile_s": 8.0 + 4.0 + 2.0,
        "setup.programs": 3.0, "setup.cache_misses": 1.0,
        "setup.unnamed_s": 30.0 - (12.0 + 6.0 + 2.0 + 4.0 + 2.0)}


@pytest.fixture
def hand_made(monkeypatch):
    monkeypatch.setattr(startup, "program_log",
                        lambda: (list(RECORDS), list(BUILDS)))


def _read(name, run=RUN):
    reader = R.load_module(REPO, "layer_metrics", name)
    assert reader.NAME == name
    return reader.read(run)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_hand_made_log(name, hand_made):
    assert abs(_read(name) - WANT[name]) < 1e-3


def test_the_parts_add_up_to_setup_s(hand_made):
    """before_build + build + (trace and compile outside both) + unnamed
    is ``setup_s``: nothing is counted twice and nothing is lost."""
    got = {name: _read(name) for name in WANT}
    cut = RUN["window"][0]
    after_build = startup.covered(
        [(end - s, end) for kind, end, s, *_ in RECORDS
         if kind in ("trace", "lower", "compile", "cache_load")],
        BUILDS[0][1], cut)
    assert after_build == pytest.approx(8.0)
    total = (got["setup.before_build_s"] + got["setup.build_s"]
             + after_build + got["setup.unnamed_s"])
    assert abs(total - RUN["e2e"]["setup_s"]) < 1e-3


def test_covered_counts_an_overlap_once():
    assert startup.covered([(1, 3), (2, 4), (2.5, 3.5), (6, 7)], 0, 10) == 4
    assert startup.covered([(1, 3), (2, 4)], 2.5, 3.25) == 0.75
    assert startup.covered([], 0, 10) == 0


def test_no_trainer_built_leaves_the_build_metrics_out(monkeypatch):
    monkeypatch.setattr(startup, "program_log",
                        lambda: (list(RECORDS), []))
    assert _read("setup.build_s") is None
    assert _read("setup.before_build_s") is None
    assert _read("setup.trace_s") == pytest.approx(6.5)
    # [99, 100.5] clipped, [101, 112], [113, 114], [119, 125], [128, 130]
    assert _read("setup.unnamed_s") == pytest.approx(30 - 20.5)


@pytest.mark.parametrize("program", ["empty", "parent", "lost"])
@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_without_a_whole_log(name, program,
                                                  monkeypatch):
    """A program that has logged nothing, the parent's (its module has no
    ``log``), and one whose bounded log has lost records: the line leaves
    the metric out and nothing raises."""
    from mxnet_tpu.observability import compiles

    if program == "parent":
        monkeypatch.delattr(compiles, "log")
    else:
        monkeypatch.setattr(compiles, "log", lambda: [])
        monkeypatch.setattr(compiles, "builds", lambda: [])
        monkeypatch.setattr(compiles, "dropped",
                            lambda: 3 if program == "lost" else 0)
    if program == "lost":
        monkeypatch.setattr(compiles, "log", lambda: list(RECORDS))
    assert _read(name) is None


def test_entries_as_the_harness_finds_them():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = bench["per_layer"][-len(WANT):]        # appended, in this order
    assert [m["name"] for m in new] == [
        "setup.before_build_s", "setup.build_s", "setup.trace_s",
        "setup.compile_s", "setup.programs", "setup.cache_misses",
        "setup.unnamed_s"]
    for m in new:
        assert m["moves"] == "setup_s" and m["layer"] == "startup"
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert m["workloads"] == CELLS
        assert m["unit"] == ("count" if m["name"] in (
            "setup.programs", "setup.cache_misses") else "s")
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "layer_metrics", m["name"] + ".py"))
    # nothing else of the benchmark moves set-up, and no cell but these
    # four is asked for the seven
    assert [m["name"] for m in bench["per_layer"]
            if m["moves"] == "setup_s"] == [m["name"] for m in new]
    for cell in CELLS:
        listed = {m["name"] for m in R.metrics_for(
            bench, "per_layer", cell, {"setup_s": 1.0})}
        assert set(WANT) <= listed


def test_tiny_training_cell_splits_its_own_setup(tmp_path, capsys):
    """The training driver on the CPU WITHOUT a trace (a traced CPU run
    refuses), its records handed to ``run.per_layer`` with the bench cut
    to the seven entries: all seven numbers from the program's own log,
    and the identity holding."""
    import jax

    from mxnet_tpu.observability import compiles

    root = chipbench_tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = [m for m in bench["per_layer"] if m["name"] in WANT]
    assert all(m["workloads"] == ["tiny_train"] for m in entries)
    bench = dict(bench, per_layer=entries)
    cell, cfg = R.find_cell(bench, "tiny_train")
    traffic = R.load_json(os.path.join(root, "chipbench", "traffic",
                                       cell["traffic"] + ".json"))
    t_start = time.monotonic()
    res = R.load_module(root, "drivers", traffic["driver"]).run({
        "cell": cell, "config": R.load_json(os.path.join(root, cfg["file"])),
        "traffic": traffic, "seed": 2 ** 31 + 37, "seconds": 0.3,
        "trace": False, "devices": jax.devices()[:1], "t_start": t_start,
        "options": {}, "trace_dir": str(tmp_path / "trace"),
        "generator": R.load_module(root, "generators",
                                   traffic["generator"])})
    assert res["correct"], capsys.readouterr().out
    run = dict(res["records"], e2e=res["metrics"])
    got = {k: v["value"] for k, v in
           R.per_layer(bench, "tiny_train", run, root).items()}
    assert set(got) == set(WANT)
    setup_s, cut = res["metrics"]["setup_s"], run["window"][0]
    assert all(0 < got[k] < setup_s for k in WANT if k.endswith("_s"))
    start, end = compiles.builds()[-1][:2]
    assert got["setup.before_build_s"] == pytest.approx(start - t_start)
    assert got["setup.build_s"] == pytest.approx(end - start)
    # the step compiles in the first step, after build: programs got
    # during set-up, and none of them taken by a cache that asks half a
    # second of compiling
    in_setup = [r for r in compiles.log(until=cut) if r[1] > t_start]
    assert got["setup.programs"] == sum(
        1 for r in in_setup if r[0] == "compile") > 0
    assert got["setup.cache_misses"] == sum(
        1 for r in in_setup if r[0] == "cache_miss")
    assert any(r[0] == "compile" and r[1] > end for r in in_setup)
    outside = startup.covered(
        [(e - s, e) for kind, e, s, *_ in in_setup
         if not kind.startswith("cache_") or kind == "cache_load"],
        end, cut)
    total = (got["setup.before_build_s"] + got["setup.build_s"] + outside
             + got["setup.unnamed_s"])
    assert abs(total - setup_s) < 1e-3
    # the reference compiled after the cut and is in none of it
    assert any(r[0] == "compile" and r[1] > cut for r in compiles.log())
