"""The looped decoder training cell's tiny twin end to end through
``run_cell`` on the CPU: ``correct`` true; false with each of the six
faults planted under the timed path; the fp8 control fails; every file
``BENCHMARK.json``'s new entries name exists, found by name; the
configuration against the catalog's row and the parameter count from the
leaves; the counts against the issue's arithmetic; the four readers on a
counted record, on other cells' records and on empty ones."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny_ouro as twin  # noqa: E402

SEED = 2 ** 31 + 19
CELL = twin.CELL
REAL = twin.REAL_CELL
CONFIG = os.path.join(REPO, "chipbench", "configs", "ouro_2p6b_ut4.json")
NEW_METRICS = ["ouro.mfu_pct", "flash_mha128_roofline",
               "ouro.layer_applications_per_step", "ouro.expected_passes"]
SHARED_METRICS = ["trainer.device_step_ms", "trainer.step_gap_ms",
                  "trainer.launches_per_step"]
COMPARED = {"objective_rel_gap_first_steps", "pass_loss_rel_gap_worst_pass",
            "exit_mass_gap_worst_pass", "first_grad_norm_worst_leaf_gap",
            "param_change_norm_worst_leaf_gap"}
FAULTS = ["three_passes", "norm_between_out", "post_mlp_norm_out",
          "uniform_exit", "entropy_out", "last_pass_only"]


def _run(root, seconds=0.6, options=None):
    import jax
    from chipbench import run as R

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return R.run_cell(bench, CELL, SEED, seconds, False, jax.devices()[:1],
                      options, root=root)


def _records(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return twin.make_root(str(tmp_path_factory.mktemp("ckout")))


def test_twin_end_to_end_is_correct(root, capsys):
    from mxnet_tpu import observability as obs

    line = _run(root)
    recs = _records(capsys)
    assert line["correct"] is True, recs
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    checks = [r["check"] for r in recs if "check" in r]
    assert {c["what"] for c in checks} == COMPARED | {
        "window_losses_finite", "xla_compiles_in_window",
        "batches_fell_back_to_host", "steps_the_program_counted"}
    assert all({"what", "value", "limit", "ok"} <= set(c) for c in checks)
    change = [c for c in checks
              if c["what"] == "param_change_norm_worst_leaf_gap"][0]
    assert "gate_b[0]" in change["unread"]
    window = [r for r in recs if r.get("phase") == "window"][0]
    plan = window["loop_plan"]
    assert (plan["passes"], plan["layers"], plan["applications"]) == (4, 2, 8)
    assert plan["runs_as"] == "scan"
    last = window["loop_last_step"]
    assert abs(sum(last["loop.exit_mass"]) - 1.0) < 1e-5
    assert last["steps"] == line["attempted"] + 3 + 1   # checked, warm
    assert plan["blocks"] == 4              # two recomputed halves a layer
    # the readers get the mean over the WINDOW's steps, from the
    # program's running sum: of all the steps it cannot exceed that sum
    mass = window["loop_window_exit_mass"]
    assert len(mass) == 4 and abs(sum(mass) - 1.0) < 1e-5
    assert all(m * line["attempted"] <= total + 1e-6 for m, total in
               zip(mass, last["loop.exit_mass_sum"]))
    assert obs.active_tracer() is None      # off again before the window


def test_every_planted_fault_is_one_the_file_names():
    from chipbench.drivers import ouro_faults as faults

    assert list(faults.FAULTS) == FAULTS
    with pytest.raises(ValueError):
        with faults.planted("nothing"):
            pass


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_underneath_is_not_correct(root, capsys, fault):
    from chipbench.drivers import ouro_faults as faults

    with faults.planted(fault):
        line = _run(root)
    failed = {r["check"]["what"] for r in _records(capsys)
              if "check" in r and not r["check"]["ok"]}
    assert line["correct"] is False
    assert failed & COMPARED, failed


def test_the_fp8_control_fails(root, capsys):
    _run(root, options={"control": "fp8"})
    ctl = [r for r in _records(capsys) if "control" in r]
    assert ctl and ctl[0]["control"] == "fp8"
    assert ctl[0]["control_fails"] is True, ctl[0]["control_checks"]


def test_every_file_the_new_entries_name_exists():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # by name, never by position: a later cell may be appended
    cell = [w for w in bench["workloads"] if w["name"] == REAL][0]
    cfg = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert cell["chips"] == 1
    assert cell["traffic"] == "one_seq_slice_ouro"
    assert cfg["name"] == "ouro_2p6b_ut4"
    assert [w["config"] for w in bench["workloads"]].count(cfg["name"]) == 1
    assert len(cell["why"]) <= 200 and len(cfg["why"]) <= 200
    assert os.path.isfile(os.path.join(REPO, cfg["file"]))
    with open(os.path.join(REPO, cfg["file"])) as f:
        config = json.load(f)
    assert config["source"] == cfg["source"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    with open(os.path.join(REPO, "chipbench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["batches"] == {"batch": 1, "seq": 8192}
    assert traffic["driver"] == "train_ouro"
    assert (traffic["warm_steps"], traffic["trace_s"]) == (2, 6.0)
    for kind, name in (("drivers", traffic["driver"]),
                       ("generators", traffic["generator"]),
                       ("harness", "counts_ouro"),
                       ("harness", "weights_ouro"),
                       ("drivers", "ouro_program"),
                       ("drivers", "ouro_faults"),
                       ("reference", "ouro_ref")):
        assert os.path.isfile(os.path.join(REPO, "chipbench", kind,
                                           name + ".py")), (kind, name)
    assert os.path.isfile(os.path.join(REPO, "chipbench",
                                       "rehearse_ouro.py"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [REAL]
        assert by_name[name]["moves"] == "train_tokens_per_s"
        assert os.path.isfile(os.path.join(REPO, "chipbench",
                                           "layer_metrics", name + ".py"))
    assert by_name["ouro.mfu_pct"]["layer"] == "trainer"
    assert by_name["ouro.mfu_pct"]["source"] == "host_clock"
    assert by_name["flash_mha128_roofline"]["source"] == "device_trace"
    assert by_name["flash_mha128_roofline"]["layer"] == "kernels"
    for name in NEW_METRICS[2:]:
        assert by_name[name]["source"] == "program_counter"
        assert by_name[name]["layer"] == "loop"
        assert (by_name[name]["unit"], by_name[name]["better"]) == \
            ("count", "lower")
    for name in SHARED_METRICS:
        assert REAL in by_name[name]["workloads"]
    # the other cells' own readers stay theirs
    for name, m in by_name.items():
        if name not in NEW_METRICS + SHARED_METRICS and "workloads" in m:
            assert REAL not in m["workloads"], name
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert REAL in e2e["train_tokens_per_s"]["workloads"]
    module, _, factory = config["program"]["factory"].rpartition(".")
    assert module == "mxnet_tpu.models" and factory == "get_ouro"


def test_configuration_keeps_every_published_width():
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(cat):
        pytest.skip("no catalog here")
    with open(cat) as f:
        entry = [json.loads(l) for l in f if '"Ouro-2.6B"' in l][0]
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert config["published"] == {k: entry["config"][k] for k in differ}
    # whole layers of one pipeline stage, every pass, a quarter of the rows
    assert config["layer_types"] == \
        entry["config"]["layer_types"][:config["num_hidden_layers"]]
    assert 48 % config["num_hidden_layers"] == 0
    assert config["num_hidden_layers"] >= 4
    assert config["total_ut_steps"] == 4
    assert config["vocab_size"] * 4 == config["vocab_size_published"] == 49152
    assert set(config["how_reduced"]) == set(config["reduced"])
    assert {"norms", "final_norm_between_passes", "exit_gate", "no_bias",
            "objective", "exit_beta", "initializer_range", "weights",
            "optimizer", "layout", "rotary", "early_exit_threshold",
            "max_window_layers"} <= set(config["assumed"])
    assert "pipeline stages" in config["deployment"]
    assert set(config["training"]["limits"]) == {
        "objective_rel_gap", "pass_loss_rel_gap", "exit_mass_gap",
        "grad_norm_gap", "delta_norm_gap"}
    assert config["training"]["exit_beta"] == 0.05


def test_parameter_count_from_the_leaves_without_allocating_them():
    import math

    from chipbench.drivers import ouro_program as prog
    from chipbench.harness.weights_ouro import (leaves, parameter_count,
                                                sizes_of)

    with open(CONFIG) as f:
        config = json.load(f)
    s = sizes_of(config)
    assert (s["layers"], s["passes"]) == (config["num_hidden_layers"], 4)
    by_leaf = {name: math.prod(shape) for name, shape, _law in leaves(s)}
    layer = sum(n for k, n in by_leaf.items()
                if k[:2] in ("a_", "m_")) // s["layers"]
    assert layer == 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416
    assert by_leaf["embed"] + by_leaf["lm_head"] == 50_331_648
    assert by_leaf["norm_f"] == 2048
    assert by_leaf["gate_w"] + by_leaf["gate_b"] == 2049
    assert parameter_count(s) == config["parameters"] \
        == s["layers"] * 51_388_416 + 50_331_648 + 2048 + 2049
    # eight layers would be the issue's 461,443,073
    assert parameter_count(dict(s, layers=8)) == 461_443_073
    # the program's own count of its parameters is the file's (shapes
    # only: nothing is initialised)
    net = prog.build_net(config)
    assert sum(math.prod(p.shape) for n, p in
               net._collect_params_with_prefix().items()
               if not n.endswith(prog._OWN)) == config["parameters"]


def test_counts_reproduce_the_issues_arithmetic():
    from chipbench.harness import counts_ouro as co
    from chipbench.harness.weights_ouro import sizes_of

    with open(CONFIG) as f:
        s = sizes_of(json.load(f))
    # the issue's numbers are of eight layers: 13.7 GFLOP a token by parts
    s8 = dict(s, layers=8)
    macs = co.forward_macs_per_token(s8)
    assert co.applications(s8) == 32
    assert macs["attention_proj"] == 32 * 4 * 2048 * 2048
    assert macs["feed_forward"] == 32 * 3 * 2048 * 5632
    assert macs["head"] == 4 * 2048 * 12288
    assert macs["exit_gate"] == 4 * 2048
    assert sum(macs.values()) / 1e6 == pytest.approx(1744.8, abs=0.05)
    assert co.seen_pairs(8192) == 33_558_528
    one = co.score_flops(1, 8192, s8)
    assert one == 4.0 * 128 * 16 * 33_558_528
    flops = co.train_flops_per_token(s8, 8192)
    assert flops == 6.0 * sum(macs.values()) + 3.0 * 32 * one / 8192
    assert flops / 1e9 == pytest.approx(13.7, abs=0.05)
    share = lambda x: round(100.0 * x / flops, 1)             # noqa: E731
    assert share(6.0 * macs["feed_forward"]) == 48.5
    assert share(6.0 * macs["attention_proj"]) == 23.5
    assert share(6.0 * macs["head"]) == 4.4
    assert share(3.0 * 32 * one / 8192) == 23.5
    assert flops * 8192 / 1e12 == pytest.approx(112, abs=0.5)
    # the layers held: 24 applications
    assert co.applications(s) == 4 * s["layers"]
    held = co.train_flops_per_token(s, 8192)
    assert held == pytest.approx(
        flops - 2 * 4 * (6.0 * 51_380_224 + 3.0 * one / 8192))
    f, b = co.flash_mha_flops_bytes(1, 8192, s)
    assert (f, b) == (one, 8192 * 128 * 2 * (32 + 32))
    fb, bb = co.flash_mha_flops_bytes(1, 8192, s, backward=True)
    assert (fb, bb) == (2.5 * one, 8192 * 128 * 2 * (64 + 64))
    assert co.flash_output_shapes(1, 8192, s) == [(16, 8192, 128)] * 2


def _counted_run(config):
    return {"e2e": {"train_tokens_per_s": 7000.0}, "tokens": 81920,
            "tokens_per_step": 8192, "n_devices": 1, "config": config,
            "traffic": {"batches": {"batch": 1, "seq": 8192}},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "traced": (5, (0, 1)),
            "loop": {"plan": {"passes": 4, "layers": 6, "applications": 24,
                              "runs_as": "scan"},
                     "exit_mass": [0.5, 0.25, 0.125, 0.125],
                     "pass_loss": [9.4, 9.4, 9.4, 9.4]},
            "trace": {"op_seconds": {
                # flash forward (writes the logsumexp too), dq, dkv
                "custom-call:tpu_custom_call (bf16[16,8192,128], "
                "f32[16,1,8192])": 0.6,
                "custom-call:tpu_custom_call bf16[16,8192,128]": 0.8,
                "custom-call:tpu_custom_call (bf16[16,8192,128], "
                "bf16[16,8192,128])": 1.0,
                "fusion bf16[16,8192,128]": 1.0}}}


def test_readers_on_a_counted_run():
    from chipbench import run as R
    from chipbench.harness import counts_ouro as co
    from chipbench.harness.weights_ouro import sizes_of

    with open(CONFIG) as f:
        run = _counted_run(json.load(f))
    s = sizes_of(run["config"])
    read = lambda n: R.load_module(REPO, "layer_metrics", n).read(run)  # noqa: E731,E501
    assert read("ouro.mfu_pct") == pytest.approx(
        100 * 7000 * co.train_flops_per_token(s, 8192) / 197e12)
    assert 0 < read("ouro.mfu_pct") < 100
    assert read("ouro.layer_applications_per_step") == 24
    assert read("ouro.expected_passes") == pytest.approx(1.875)
    # a pass that was skipped shows
    short = dict(run, loop=dict(run["loop"], exit_mass=[0.5, 0.25, 0.25]))
    assert R.load_module(
        REPO, "layer_metrics",
        "ouro.layer_applications_per_step").read(short) == 18
    # 5 steps x 24 applications x (forward + backward) against the three
    # kernel rows' 2.4 s; the fusion of the same shape is not a kernel
    least = sum(co.roofline_seconds(
        *co.flash_mha_flops_bytes(1, 8192, s, backward=bw),
        run["peaks"])[0] for bw in (False, True))
    assert read("flash_mha128_roofline") == pytest.approx(
        100 * 5 * 24 * least / 2.4)
    assert 10 < read("flash_mha128_roofline") < 100


def test_new_readers_return_nothing_elsewhere():
    """In a cell of another configuration (a parent's, too: its program
    has no such counters), on a record with nothing in it and on a trace
    with no such kernel, each reader returns None and does not raise."""
    from chipbench import run as R

    with open(os.path.join(REPO, "chipbench", "configs",
                           "mellum2_12b_a2p5b_ep4.json")) as f:
        other = json.load(f)
    with open(CONFIG) as f:
        mine = json.load(f)
    bare = {"e2e": {"train_tokens_per_s": 1.0}, "traced": (3, (0, 1)),
            "trace": {"op_seconds": {}}, "config": {}, "traffic": {},
            "tokens": 10}
    elsewhere = dict(_counted_run(other), loop=None)
    no_kernels = dict(_counted_run(mine), trace={"op_seconds": {}})
    not_traced = dict(_counted_run(mine), traced=None)
    no_counters = dict(_counted_run(mine), loop=None)
    for name in NEW_METRICS:
        read = R.load_module(REPO, "layer_metrics", name).read
        assert read(bare) is None
        assert read(elsewhere) is None
        if name.endswith("_roofline"):
            assert read(no_kernels) is None
            assert read(not_traced) is None
        if name.startswith("ouro.") and name != "ouro.mfu_pct":
            assert read(no_counters) is None
