"""The command end to end on the CPU at a tiny size: each driver (open
loop, closed loop, train), the result line's keys, no result without a chip, the timed path broken underneath
(``correct`` must come out false), and discovery: a new configuration,
traffic mix, generator and per-layer metric dropped into a temporary copy
run without editing any file that was there."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny  # noqa: E402

SEED = 2 ** 31 + 11        # more than 32 signed bits hold
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(root, cell, seconds=1.5, trace=False, bench=None, options=None):
    import jax
    from chipbench import run as R

    if bench is None:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
    return R.run_cell(bench, cell, SEED, seconds, trace, jax.devices()[:1],
                      options, root=root)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return chipbench_tiny.make_root(str(tmp_path_factory.mktemp("ckout")))


@pytest.mark.parametrize("cell", ["tiny_chat", "tiny_offline",
                                  "tiny_long", "tiny_train"])
def test_cell_end_to_end(root, cell, capsys):
    e2e = chipbench_tiny.end_to_end_of(chipbench_tiny.tiny_bench(), cell)
    assert "setup_s" in e2e and len(e2e) >= 2
    line = _run(root, cell)
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True, capsys.readouterr().out
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == e2e
    assert all(m["value"] > 0 and m["unit"] for m in
               line["metrics"].values())
    assert line["device"]["platform"] == "cpu"      # named, never hidden
    assert line["device"]["count"] == 1
    # every number compared is printed beside its limit
    checks = _checks(capsys)
    assert checks and all({"what", "value", "limit", "ok"} <= set(c)
                          for c in checks)
    json.dumps(line)


def test_cpu_run_prints_no_device_metrics(root, tmp_path):
    """A traced run needs the device's published peaks and the TPU's
    planes of the profiler's trace; the CPU has neither and it refuses."""
    with pytest.raises(KeyError, match="no published peaks"):
        import jax
        from chipbench import run as R
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        R.run_cell(bench, "tiny_train", SEED, 0.5, True, jax.devices()[:1],
                   root=root, trace_dir=str(tmp_path / "trace"))


def test_no_chip_no_result():
    """The command itself, here on the CPU: exit code 2, no result line."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", "train_124m_seq1024", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bare_directory_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: the program is
    missing, so the command fails and prints no result."""
    import shutil
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "train_124m_seq1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300, env=dict(env, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert not any(l.startswith('{"correct"')
                   for l in p.stdout.splitlines())


def test_altered_token_is_not_correct(root, monkeypatch, capsys):
    """A token altered where it is produced: the engine's sampler."""
    import mxnet_tpu.serving.engine as eng

    real = eng.sample_tokens

    def off_by_one(logits, *a, **kw):
        return (real(logits, *a, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(eng, "sample_tokens", off_by_one)
    line = _run(root, "tiny_chat")
    assert line["correct"] is False
    assert {c["what"] for c in _checks(capsys) if not c["ok"]} == {
        "served_mean_logit_gap_share",
        "worst_request_mean_logit_gap_share"}


def test_engine_in_lower_precision_is_not_correct(root, capsys):
    """The control on the timed path: the engine itself with its int8 KV
    pages switched on, under a cell's own traffic (some hundreds of served
    tokens: greedy tokens of a random model mostly have wide margins)."""
    line = _run(root, "tiny_long", seconds=2.0,
                options={"control": "kv_int8"})
    assert line["correct"] is False and line["failed"] == 0
    assert "served_mean_logit_gap_share" in {
        c["what"] for c in _checks(capsys) if not c["ok"]}


def test_one_wrong_token_fails_its_request():
    """One served token a little below the reference's best in one short
    answer among many long sound ones: the mean over all tokens stays
    inside its limit, that request's own mean does not."""
    from chipbench.drivers import serve

    gaps = [{"widest": 0.0, "sum": 0.0, "below_best": 0, "tokens": 200}
            for _ in range(20)]
    gaps.append({"widest": 0.04, "sum": 0.04, "below_best": 1,
                 "tokens": 16})
    limits = {"mean_gap": 6e-5, "worst_request_mean_gap": 6e-4}
    mean, worst = serve.judge_gaps(serve._gap_summary(gaps), limits, True)
    assert mean["ok"] and mean["value"] < 1e-5
    assert not worst["ok"] and worst["value"] == 0.04 / 16


def test_refused_request_is_not_correct(root, monkeypatch, capsys):
    """A request the engine refuses is a failed request, and a run with
    a failed request is not correct."""
    from mxnet_tpu.serving import InferenceEngine

    real = InferenceEngine.submit
    calls = []

    def refuse_one(self, tokens, **kw):
        calls.append(1)
        if len(calls) == 12:
            raise RuntimeError("refused")
        return real(self, tokens, **kw)

    monkeypatch.setattr(InferenceEngine, "submit", refuse_one)
    line = _run(root, "tiny_chat")
    assert line["failed"] == 1 and line["correct"] is False
    assert [c["what"] for c in _checks(capsys) if not c["ok"]] == [
        "requests_failed"]


def test_step_that_keeps_its_state_is_not_correct(root, monkeypatch):
    """An optimizer step that returns its state unchanged."""
    from mxnet_tpu import optimizer as opt

    monkeypatch.setattr(opt.Adam, "update",
                        lambda self, index, weight, grad, state: None)
    line = _run(root, "tiny_train")
    assert line["correct"] is False


def _checks(capsys) -> list:
    return [json.loads(l)["check"] for l in
            capsys.readouterr().out.splitlines()
            if l.startswith('{"check"')]


GEN = '''"""A generator added by a later PR: every prompt the same length."""
import numpy as np


def generate(traffic, seed, horizon_s, vocab, rate=None, lead_in_s=0.0):
    rng = np.random.default_rng(seed)
    n = int(traffic["n"])
    return [{"t": i * horizon_s / n,
             "tokens": rng.integers(0, vocab, 12).astype("int32"),
             "new_tokens": 3} for i in range(n)]
'''
READER = '''"""A per-layer metric added by a later PR."""
NAME = "scheduler.admitted_per_s"


def read(run):
    w0, w1 = run["window"]
    return run["counters"]["admitted"] / (w1 - w0)
'''


def test_discovery_new_files_only(tmp_path):
    """Adding a cell, configuration, traffic mix, generator and per-layer
    metric is adding files and BENCHMARK.json entries: no file that was
    there is edited."""
    import hashlib

    root = chipbench_tiny.make_root(str(tmp_path))
    cb = os.path.join(root, "chipbench")

    def digest():
        out = {}
        for d, _dirs, files in os.walk(cb):
            for f in files:
                with open(os.path.join(d, f), "rb") as fh:
                    out[os.path.join(d, f)] = hashlib.sha1(
                        fh.read()).hexdigest()
        return out

    before = digest()
    with open(os.path.join(cb, "configs", "tiny.json")) as f:
        cfg = json.load(f)
    cfg["name"], cfg["n_layer"] = "tiny3", 3
    with open(os.path.join(cb, "configs", "tiny3.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(cb, "traffic", "even.json"), "w") as f:
        json.dump({"kind": "open_loop", "driver": "serve",
                   "generator": "even_spacing", "n": 8, "lead_in_s": 0.2,
                   "drain_s": 20, "check_requests": 3,
                   "trace_offset_s": 0.1, "trace_s": 0.3}, f)
    with open(os.path.join(cb, "generators", "even_spacing.py"), "w") as f:
        f.write(GEN)
    with open(os.path.join(cb, "layer_metrics",
                           "scheduler.admitted_per_s.py"), "w") as f:
        f.write(READER)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny3", "source": "tests",
                             "file": "chipbench/configs/tiny3.json",
                             "reduced": [], "why": "three layers"})
    bench["workloads"].append({"name": "tiny3_even", "config": "tiny3",
                               "traffic": "even", "chips": 1,
                               "why": "evenly spaced"})
    for m in bench["end_to_end"]:
        if m["name"] == "ms_per_token_p50":
            m["workloads"].append("tiny3_even")   # an entry's list grows
    bench["per_layer"].append(
        {"name": "scheduler.admitted_per_s", "unit": "1/s",
         "better": "higher", "source": "program_counter",
         "layer": "scheduler", "moves": "ms_per_token_p50",
         "workloads": ["tiny3_even"]})
    line = _run(root, "tiny3_even", bench=bench)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ms_per_token_p50", "setup_s"}
    after = digest()
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    assert len(after) == len(before) + 4
    # the new reader is found by its name, with no registry to edit
    from chipbench import run as R
    assert R.load_module(root, "layer_metrics",
                         "scheduler.admitted_per_s").NAME == \
        "scheduler.admitted_per_s"
