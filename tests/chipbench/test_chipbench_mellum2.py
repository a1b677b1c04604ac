"""The sliding-window expert training cell's tiny twin end to end through
``run_cell`` on the CPU: ``correct`` true; false with each of the five
faults planted under the timed path; the fp8 control fails; every file
``BENCHMARK.json``'s new entries name exists, found by name; the
configuration against the catalog's row and the parameter count from the
leaves; the counts against the issue's shares; the five readers on a
counted record, on other cells' records and on empty ones."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny_mellum2 as twin  # noqa: E402

SEED = 2 ** 31 + 19
CELL = twin.CELL
REAL = twin.REAL_CELL
CONFIG = os.path.join(REPO, "chipbench", "configs",
                      "mellum2_12b_a2p5b_ep4.json")
NEW_METRICS = ["mellum2.mfu_pct", "flash_swa_roofline",
               "moe_gmm_m2_roofline", "mellum2.pairs_local_per_token",
               "mellum2.load_max_over_mean"]
SHARED_METRICS = ["trainer.device_step_ms", "trainer.step_gap_ms",
                  "trainer.launches_per_step"]
COMPARED = {"loss_rel_gap_first_steps", "first_grad_norm_worst_leaf_gap",
            "param_change_norm_worst_leaf_gap", "routing_mismatch_share"}


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
    line = _run(root)
    recs = _records(capsys)
    assert line["correct"] is True, recs
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    checks = [r["check"] for r in recs if "check" in r]
    assert {c["what"] for c in checks} == COMPARED | {
        "window_losses_finite", "xla_compiles_in_window",
        "batches_fell_back_to_host"}
    assert all({"what", "value", "limit", "ok"} <= set(c) for c in checks)
    routed = [r for r in recs if r.get("phase") == "window"][0]["routed"]
    assert routed["layers"] == 4 and routed["experts_held"] == 4
    assert routed["pairs_total"] == routed["steps"] * 4 * 64 * 3
    assert 0 < routed["pairs_local"] < routed["pairs_total"]


def test_every_planted_fault_is_one_the_file_names():
    from chipbench.drivers import mellum2_faults as faults

    assert len(faults.FAULTS) == 5 and faults.READ_ONLY == ("window_1023",)
    with pytest.raises(ValueError):
        with faults.planted("nothing"):
            pass


@pytest.mark.parametrize("fault", ["window_ignored", "plain_table_on_full",
                                   "attention_factor_out",
                                   "weights_not_renormalised",
                                   "gate_product_out"])
def test_a_fault_underneath_is_not_correct(root, capsys, fault):
    from chipbench.drivers import mellum2_faults as faults

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
    assert cell["traffic"] == "one_seq_slice_mellum2"
    assert cfg["name"] == "mellum2_12b_a2p5b_ep4"
    assert [w["config"] for w in bench["workloads"]].count(cfg["name"]) == 1
    assert os.path.isfile(os.path.join(REPO, cfg["file"]))
    with open(os.path.join(REPO, cfg["file"])) as f:
        config = json.load(f)
    assert config["source"] == cfg["source"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    with open(os.path.join(REPO, "chipbench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["batches"] == {"batch": 1, "seq": 8192}
    assert traffic["driver"] == "train_mellum2"
    assert (traffic["warm_steps"], traffic["trace_s"]) == (2, 3.0)
    for kind, name in (("drivers", traffic["driver"]),
                       ("generators", traffic["generator"]),
                       ("harness", "counts_mellum2"),
                       ("harness", "weights_mellum2"),
                       ("drivers", "mellum2_program"),
                       ("drivers", "mellum2_faults"),
                       ("reference", "mellum2_ref")):
        assert os.path.isfile(os.path.join(REPO, "chipbench", kind,
                                           name + ".py")), (kind, name)
    assert os.path.isfile(os.path.join(REPO, "chipbench",
                                       "rehearse_mellum2.py"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"][0] == REAL
        assert by_name[name]["moves"] == "train_tokens_per_s"
        assert os.path.isfile(os.path.join(REPO, "chipbench",
                                           "layer_metrics", name + ".py"))
    assert by_name["mellum2.mfu_pct"]["layer"] == "trainer"
    assert by_name["flash_swa_roofline"]["source"] == "device_trace"
    assert by_name["moe_gmm_m2_roofline"]["layer"] == "kernels"
    assert by_name["mellum2.pairs_local_per_token"]["layer"] == "experts"
    assert by_name["mellum2.load_max_over_mean"]["better"] == "lower"
    for name in SHARED_METRICS:
        assert REAL in by_name[name]["workloads"]
    # the other cells' own readers stay theirs
    for name, m in by_name.items():
        if name not in NEW_METRICS + SHARED_METRICS and "workloads" in m:
            assert REAL not in m["workloads"], name
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert REAL in e2e["train_tokens_per_s"]["workloads"]
    module, _, factory = config["program"]["factory"].rpartition(".")
    assert module == "mxnet_tpu.models" and factory == "get_mellum"


def test_configuration_keeps_every_published_width():
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(cat):
        pytest.skip("no catalog here")
    with open(cat) as f:
        entry = [json.loads(l) for l in f
                 if '"Mellum2-12B-A2.5B-Instruct"' in l][0]
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types"}
    assert config["published"] == {k: entry["config"][k] for k in differ}
    assert config["rope_parameters"] == entry["config"]["rope_parameters"]
    # one whole period, in its published ratio; a quarter of the experts
    # and of the vocabulary
    assert config["layer_types"] == entry["config"]["layer_types"][:4] == \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert config["num_experts"] * 4 == config["num_experts_published"] == 64
    assert config["vocab_size"] * 4 == config["vocab_size_published"]
    assert {"qk_norm", "weights", "initializer_range", "layout", "rotary",
            "multi_token_prediction", "optimizer", "intermediate_size",
            "max_window_layers"} <= set(config["assumed"])
    assert "4 chips share each layer" in config["deployment"]
    assert set(config["training"]["limits"]) == {
        "loss_rel_gap", "grad_norm_gap", "delta_norm_gap",
        "routing_mismatch_share"}


def test_parameter_count_from_the_leaves_without_allocating_them():
    import math

    from chipbench.drivers import mellum2_program as prog
    from chipbench.harness.weights_mellum2 import (leaves, parameter_count,
                                                   sizes_of)

    with open(CONFIG) as f:
        config = json.load(f)
    s = sizes_of(config)
    assert s["pattern"] == "SSSF" and s["window"] == 1024
    assert parameter_count(s) == config["parameters"] == 595_154_176
    by_leaf = {name: math.prod(shape) for name, shape, _law in leaves(s)}
    layer = sum(n for k, n in by_leaf.items() if k[:2] in ("a_", "e_")) // 4
    assert layer == 21_233_664 + 256 + 4_608 + 147_456 + 16 * 6_193_152 \
        == 120_476_416
    assert by_leaf["embed"] + by_leaf["lm_head"] == 113_246_208
    # the program's own count of its parameters is the file's (shapes
    # only: nothing is initialised)
    net = prog.build_net(config)
    assert sum(math.prod(p.shape) for n, p in
               net._collect_params_with_prefix().items()
               if not n.endswith(prog._OWN)) == config["parameters"]


def test_counts_reproduce_the_issues_shares():
    from chipbench.harness import counts_mellum2 as cm
    from chipbench.harness.weights_mellum2 import sizes_of

    with open(CONFIG) as f:
        s = sizes_of(json.load(f))
    macs = cm.forward_macs_per_token(s, 2.0)
    assert macs["attention_proj"] == 4 * (2 * 2304 * 4096 + 2 * 2304 * 512)
    assert macs["router"] == 4 * 2304 * 64
    assert macs["routed_experts"] == 4 * 2.0 * 3 * 2304 * 896
    assert macs["head"] == 2304 * 24576
    assert cm.layer_windows(s) == [1024, 1024, 1024, None]
    assert cm.seen_pairs(8192, 1024) == 7_864_832
    assert cm.seen_pairs(8192) == 33_558_528
    scores = [cm.score_flops(1, 8192, s, w) / 8192
              for w in cm.layer_windows(s)]
    assert scores[0] == 4.0 * 128 * 32 * 7_864_832 / 8192
    assert scores[0] / 1e6 == pytest.approx(15.73, abs=0.005)
    assert scores[3] / 1e6 == pytest.approx(67.1, abs=0.05)
    forward = 2.0 * sum(macs.values()) + sum(scores)
    assert forward / 1e6 == pytest.approx(497.7, abs=0.05)
    share = lambda x: round(100.0 * x / forward, 1)           # noqa: E731
    assert share(2.0 * macs["attention_proj"]) == 34.1
    assert share(sum(scores)) == 23.0
    assert share(2.0 * macs["routed_experts"]) == 19.9
    assert share(2.0 * macs["router"]) == 0.2
    assert share(2.0 * macs["head"]) == 22.8        # 22.75: the issue's 22.7
    flops = cm.train_flops_per_token(s, 8192, 2.0)
    assert flops == 6.0 * sum(macs.values()) + 3.0 * sum(scores)
    assert flops / 1e9 == pytest.approx(1.493, abs=0.001)
    assert cm.train_flops_per_token(s, 8192, 2.5) > flops
    assert cm.forward_macs_per_token(s, 0.0)["routed_experts"] == 0
    f, b = cm.flash_swa_flops_bytes(1, 8192, s, 1024)
    assert f == 4.0 * 128 * 32 * 7_864_832
    assert b == 8192 * 128 * 2 * (64 + 8)
    assert cm.flash_forward_shapes(1, 8192, s) == [(32, 1, 8192)]
    assert cm.moe_gmm_output_shapes(65536, s) == [
        (65536, 896), (65536, 2304), (16, 2304, 896), (16, 896, 2304)]
    assert cm.GMM_CALLS_A_LAYER == 9


def _counted_run(config):
    routed = {"pairs_local": 4 * 16384.0 * 10, "pairs_total": 4 * 65536.0 * 10,
              "load_max": 4 * 1500.0 * 10, "steps": 10.0, "layers": 4,
              "experts_held": 16}
    return {"e2e": {"train_tokens_per_s": 25000.0}, "tokens": 81920,
            "tokens_per_step": 8192, "n_devices": 1, "config": config,
            "traffic": {"batches": {"batch": 1, "seq": 8192}},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "routed": routed, "routed_traced": routed, "traced": (10, (0, 1)),
            "trace": {"op_seconds": {
                # flash forward (writes the logsumexp), dq, dkv
                "custom-call:tpu_custom_call (bf16[32,8192,128], "
                "f32[32,1,8192])": 0.25,
                "custom-call:tpu_custom_call bf16[32,8192,128]": 0.3,
                "custom-call:tpu_custom_call (f32[32,8192,128], "
                "f32[32,8192,128])": 0.3,
                # grouped products
                "custom-call:tpu_custom_call bf16[65536,896]": 0.2,
                "custom-call:tpu_custom_call bf16[65536,2304]": 0.1,
                "custom-call:tpu_custom_call bf16[16,2304,896]": 0.1,
                "custom-call:tpu_custom_call bf16[16,896,2304]": 0.1,
                "fusion bf16[65536,2304]": 1.0}}}


def test_readers_on_a_counted_run():
    from chipbench import run as R
    from chipbench.harness import counts_mellum2 as cm
    from chipbench.harness.weights_mellum2 import sizes_of

    with open(CONFIG) as f:
        run = _counted_run(json.load(f))
    s = sizes_of(run["config"])
    read = lambda n: R.load_module(REPO, "layer_metrics", n).read(run)  # noqa: E731,E501
    assert read("mellum2.pairs_local_per_token") == pytest.approx(2.0)
    assert read("mellum2.load_max_over_mean") == pytest.approx(1500 / 1024)
    # 25,000 tokens/s x 1.493 GFLOP over 197 TFLOP/s
    assert read("mellum2.mfu_pct") == pytest.approx(18.95, abs=0.05)
    # 10 steps x (three windowed + one full) forward calls against the
    # 0.25 s of the ONE row that writes the logsumexp
    least = sum(cm.roofline_seconds(
        *cm.flash_swa_flops_bytes(1, 8192, s, w), run["peaks"])[0]
        for w in (1024, 1024, 1024, None))
    assert read("flash_swa_roofline") == pytest.approx(
        100 * 10 * least / 0.25)
    assert 10 < read("flash_swa_roofline") < 100
    # 10 x 4 x 9 products of 16,384 rows against the four rows' 0.5 s
    flops, nbytes = cm.moe_gmm_flops_bytes(16384.0, 2304, 896, 16)
    one = max(flops / 197e12, nbytes / 819e9)
    assert read("moe_gmm_m2_roofline") == pytest.approx(
        100 * 360 * one / 0.5)
    assert 0 < read("moe_gmm_m2_roofline") < 100


def test_new_readers_return_nothing_elsewhere():
    """In a cell of another configuration (a parent's, too: its program
    has no such counters under this family's name), on a record with
    nothing in it and on a trace with no such kernel, each reader returns
    None and does not raise."""
    from chipbench import run as R

    with open(os.path.join(REPO, "chipbench", "configs",
                           "qwen3_next_80b_a3b_share.json")) as f:
        other = json.load(f)
    with open(CONFIG) as f:
        mine = json.load(f)
    bare = {"e2e": {"train_tokens_per_s": 1.0}, "traced": (3, (0, 1)),
            "trace": {"op_seconds": {}}, "config": {}, "traffic": {},
            "tokens": 10}
    elsewhere = _counted_run(other)
    no_kernels = dict(_counted_run(mine), trace={"op_seconds": {}})
    not_traced = dict(_counted_run(mine), traced=None, routed_traced=None)
    not_routed = dict(_counted_run(mine), routed=None, routed_traced=None)
    for name in NEW_METRICS:
        read = R.load_module(REPO, "layer_metrics", name).read
        assert read(bare) is None
        assert read(elsewhere) is None
        if name.endswith("_roofline"):
            assert read(no_kernels) is None
            assert read(not_traced) is None
        if name != "flash_swa_roofline":
            assert read(not_routed) is None
