"""The latent-attention expert training cell's tiny twin end to end through
``run_cell`` on the CPU: ``correct`` true; false with each of the seven
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

import chipbench_tiny_moonlight as twin  # noqa: E402

SEED = 2 ** 31 + 23
CELL = twin.CELL
REAL = twin.REAL_CELL
CONFIG = os.path.join(REPO, "chipbench", "configs",
                      "moonlight_16b_a3b_ep8.json")
NEW_METRICS = ["moonlight.mfu_pct", "flash_mla_roofline",
               "moe_gmm_ml_roofline", "moonlight.pairs_local_per_token",
               "moonlight.load_max_over_mean"]
SHARED_METRICS = ["trainer.device_step_ms", "trainer.step_gap_ms",
                  "trainer.launches_per_step"]
COMPARED = {"loss_rel_gap_first_steps", "first_grad_norm_worst_leaf_gap",
            "param_change_norm_worst_leaf_gap", "routing_mismatch_share"}
FAULTS = ["scale_of_128", "rope_on_nope", "latent_norm_out",
          "rope_key_per_head", "scaling_out", "bias_in_weights",
          "shared_half"]


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
        "batches_fell_back_to_host", "routing_buffer_moved"}
    assert all({"what", "value", "limit", "ok"} <= set(c) for c in checks)
    routed = [r for r in recs if r.get("phase") == "window"][0]["routed"]
    assert routed["layers"] == 2 and routed["experts_held"] == 4
    assert routed["pairs_total"] == routed["steps"] * 2 * 64 * 3
    assert 0 < routed["pairs_local"] < routed["pairs_total"]


def test_every_planted_fault_is_one_the_file_names():
    from chipbench.drivers import moonlight_faults as faults

    assert list(faults.FAULTS) == FAULTS
    with pytest.raises(ValueError):
        with faults.planted("nothing"):
            pass


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_underneath_is_not_correct(root, capsys, fault):
    from chipbench.drivers import moonlight_faults as faults

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
    assert cell["traffic"] == "one_seq_slice_moonlight"
    assert cfg["name"] == "moonlight_16b_a3b_ep8"
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
    assert traffic["driver"] == "train_moonlight"
    assert (traffic["warm_steps"], traffic["trace_s"]) == (2, 3.0)
    for kind, name in (("drivers", traffic["driver"]),
                       ("generators", traffic["generator"]),
                       ("harness", "counts_moonlight"),
                       ("harness", "weights_moonlight"),
                       ("drivers", "moonlight_program"),
                       ("drivers", "moonlight_faults"),
                       ("reference", "moonlight_ref")):
        assert os.path.isfile(os.path.join(REPO, "chipbench", kind,
                                           name + ".py")), (kind, name)
    assert os.path.isfile(os.path.join(REPO, "chipbench",
                                       "rehearse_moonlight.py"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [REAL]
        assert by_name[name]["moves"] == "train_tokens_per_s"
        assert os.path.isfile(os.path.join(REPO, "chipbench",
                                           "layer_metrics", name + ".py"))
    assert by_name["moonlight.mfu_pct"]["layer"] == "trainer"
    assert by_name["flash_mla_roofline"]["source"] == "device_trace"
    assert by_name["moe_gmm_ml_roofline"]["layer"] == "kernels"
    assert by_name["moonlight.pairs_local_per_token"]["layer"] == "experts"
    assert by_name["moonlight.load_max_over_mean"]["better"] == "lower"
    for name in SHARED_METRICS:
        assert REAL in by_name[name]["workloads"]
    # the other cells' own readers stay theirs
    for name, m in by_name.items():
        if name not in NEW_METRICS + SHARED_METRICS and "workloads" in m:
            assert REAL not in m["workloads"], name
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert REAL in e2e["train_tokens_per_s"]["workloads"]
    module, _, factory = config["program"]["factory"].rpartition(".")
    assert module == "mxnet_tpu.models" and factory == "get_deepseek_v3"


def test_configuration_keeps_every_published_width():
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(cat):
        pytest.skip("no catalog here")
    with open(cat) as f:
        entry = [json.loads(l) for l in f
                 if '"Moonlight-16B-A3B"' in l][0]
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items()
              if k not in config or config[k] != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert config["published"] == {k: entry["config"][k] for k in differ}
    # the leading dense layer once and five (the floor is four) of the
    # expert layers; an eighth of the experts and of the vocabulary
    assert config["first_k_dense_replace"] == 1
    assert config["num_hidden_layers"] == 6
    assert config["n_routed_experts"] * 8 \
        == config["n_routed_experts_published"] == 64
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert {"kv_a_layernorm_eps", "rotary", "objective", "correction_bias",
            "initializer_range", "weights", "shared_experts", "layout",
            "optimizer", "unused"} <= set(config["assumed"])
    assert set(config["how_reduced"]) == set(config["reduced"])
    assert "8 chips share each layer" in config["deployment"]
    assert set(config["training"]["limits"]) == {
        "loss_rel_gap", "grad_norm_gap", "delta_norm_gap",
        "routing_mismatch_share"}


def test_parameter_count_from_the_leaves_without_allocating_them():
    import math

    from chipbench.drivers import moonlight_program as prog
    from chipbench.harness.weights_moonlight import (leaves, parameter_count,
                                                     sizes_of)

    with open(CONFIG) as f:
        config = json.load(f)
    s = sizes_of(config)
    assert s["pattern"] == "DEEEEE" and s["shared_width"] == 2816
    assert (s["latent_eps"], s["eps"]) == (1e-6, 1e-5)
    assert parameter_count(s) == config["parameters"] == 668_890_112
    by_leaf = {name: math.prod(shape) for name, shape, _law in leaves(s)}
    assert sum(n for k, n in by_leaf.items() if k[:2] == "a_") \
        == 6 * (13_763_072 + 2_048)
    assert sum(n for k, n in by_leaf.items() if k[:2] == "d_") \
        == 69_206_016 + 2_048
    assert sum(n for k, n in by_leaf.items()
               if k[:2] == "e_" and k != "e_bias") == 5 * (
        131_072 + 17_301_504 + 8 * 8_650_752 + 2_048)
    assert by_leaf["e_bias"] == 320
    assert by_leaf["embed"] + by_leaf["lm_head"] == 83_886_080
    # the program's own count of its parameters is the file's (shapes
    # only: nothing is initialised)
    net = prog.build_net(config)
    assert sum(math.prod(p.shape) for n, p in
               net._collect_params_with_prefix().items()
               if not n.endswith(prog._OWN + ("e_score_correction_bias",))) \
        == config["parameters"]


def test_counts_reproduce_the_issues_shares():
    from chipbench.harness import counts_moonlight as cm
    from chipbench.harness.weights_moonlight import sizes_of

    with open(CONFIG) as f:
        s = sizes_of(json.load(f))
    macs = cm.forward_macs_per_token(s, 0.75)
    assert macs["latent_proj"] == 6 * 13_762_560
    assert macs["dense_mlp"] == 3 * 2048 * 11264
    assert macs["router"] == 5 * 2048 * 64
    assert macs["shared_expert"] == 5 * 3 * 2048 * 2816
    assert macs["routed_experts"] == 5 * 0.75 * 3 * 2048 * 1408
    assert macs["head"] == 2048 * 20480
    scores = 6 * cm.score_flops(1, 8192, s) / 8192
    assert cm.score_flops(1, 8192, s) == 2.0 * 320 * 16 * 33_558_528
    forward = 2.0 * sum(macs.values()) + scores
    # the issue's MFLOP a token: 165, 252, 138, 173, 65, 84; 877 in all
    # (878.5 with the router's 1.3, which it leaves out)
    mflop = lambda x: round(x / 1e6)                          # noqa: E731
    assert mflop(2.0 * macs["latent_proj"]) == 165
    assert mflop(scores) == 252
    assert mflop(2.0 * macs["dense_mlp"]) == 138
    assert mflop(2.0 * macs["shared_expert"]) == 173
    assert mflop(2.0 * macs["routed_experts"]) == 65
    assert mflop(2.0 * macs["head"]) == 84
    assert forward / 1e6 == pytest.approx(878.5, abs=0.5)
    share = lambda x: round(100.0 * x / forward)              # noqa: E731
    assert share(2.0 * macs["latent_proj"] + scores) == 47    # the 48%
    assert share(2.0 * (macs["shared_expert"] + macs["routed_experts"])) == 27
    flops = cm.train_flops_per_token(s, 8192, 0.75)
    assert flops == 6.0 * sum(macs.values()) + 3.0 * scores
    assert flops / 1e9 == pytest.approx(2.635, abs=0.002)
    assert cm.train_flops_per_token(s, 8192, 1.0) > flops
    assert cm.forward_macs_per_token(s, 0.0)["routed_experts"] == 0
    f, b = cm.flash_mla_flops_bytes(1, 8192, s)
    assert f == cm.score_flops(1, 8192, s)
    assert b == 8192 * 2 * (16 * (192 + 128 + 128 + 128) + 64)
    assert cm.flash_forward_shapes(1, 8192, s) == [(16, 1, 8192)]
    assert cm.moe_gmm_output_shapes(49152, s) == [
        (49152, 1408), (49152, 2048), (8, 2048, 1408), (8, 1408, 2048)]
    assert cm.GMM_CALLS_A_LAYER == 9


def _counted_run(config):
    routed = {"pairs_local": 5 * 6144.0 * 10, "pairs_total": 5 * 49152.0 * 10,
              "load_max": 5 * 1000.0 * 10, "steps": 10.0, "layers": 5,
              "experts_held": 8}
    return {"e2e": {"train_tokens_per_s": 15000.0}, "tokens": 81920,
            "tokens_per_step": 8192, "n_devices": 1, "config": config,
            "traffic": {"batches": {"batch": 1, "seq": 8192}},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "routed": routed, "routed_traced": routed, "traced": (10, (0, 1)),
            "trace": {"op_seconds": {
                # flash forward (writes the logsumexp), dq + dq2, dkv + dk2
                "custom-call:tpu_custom_call (bf16[16,8192,128], "
                "f32[16,1,8192])": 0.25,
                "custom-call:tpu_custom_call (bf16[16,8192,128], "
                "bf16[16,8192,64])": 0.3,
                "custom-call:tpu_custom_call (bf16[16,8192,128], "
                "bf16[16,8192,128], f32[16,8192,64])": 0.3,
                # grouped products
                "custom-call:tpu_custom_call bf16[49152,1408]": 0.06,
                "custom-call:tpu_custom_call bf16[49152,2048]": 0.03,
                "custom-call:tpu_custom_call bf16[8,2048,1408]": 0.03,
                "custom-call:tpu_custom_call bf16[8,1408,2048]": 0.03,
                "fusion bf16[49152,2048]": 1.0}}}


def test_readers_on_a_counted_run():
    from chipbench import run as R
    from chipbench.harness import counts_moonlight as cm
    from chipbench.harness.weights_moonlight import sizes_of

    with open(CONFIG) as f:
        run = _counted_run(json.load(f))
    s = sizes_of(run["config"])
    read = lambda n: R.load_module(REPO, "layer_metrics", n).read(run)  # noqa: E731,E501
    assert read("moonlight.pairs_local_per_token") == pytest.approx(0.75)
    assert read("moonlight.load_max_over_mean") == pytest.approx(1000 / 768)
    # 15,000 tokens/s x 2.635 GFLOP over 197 TFLOP/s
    assert read("moonlight.mfu_pct") == pytest.approx(20.06, abs=0.05)
    # 10 steps x 6 forward calls against the 0.25 s of the ONE row that
    # writes the logsumexp
    least = cm.roofline_seconds(*cm.flash_mla_flops_bytes(1, 8192, s),
                                run["peaks"])[0]
    assert read("flash_mla_roofline") == pytest.approx(
        100 * 60 * least / 0.25)
    assert 10 < read("flash_mla_roofline") < 100
    # 10 x 5 x 9 products of 6,144 rows against the four rows' 0.15 s
    flops, nbytes = cm.moe_gmm_flops_bytes(6144.0, 2048, 1408, 8)
    one = max(flops / 197e12, nbytes / 819e9)
    assert read("moe_gmm_ml_roofline") == pytest.approx(
        100 * 450 * one / 0.15)
    assert 0 < read("moe_gmm_ml_roofline") < 100


def test_new_readers_return_nothing_elsewhere():
    """In a cell of another configuration (a parent's, too: its program
    has no such counters under this family's name), on a record with
    nothing in it and on a trace with no such kernel, each reader returns
    None and does not raise."""
    from chipbench import run as R

    with open(os.path.join(REPO, "chipbench", "configs",
                           "mellum2_12b_a2p5b_ep4.json")) as f:
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
        if name != "flash_mla_roofline":
            assert read(not_routed) is None
