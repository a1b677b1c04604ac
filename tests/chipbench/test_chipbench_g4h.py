"""The Granite hybrid training cell's tiny twin end to end through
``run_cell`` on the CPU: ``correct`` true; false with a multiplier dropped
underneath; both controls fail; every file ``BENCHMARK.json``'s new
entries name exists; the configuration against the catalog's row and the
parameter count from the leaves; the counts against hand counts; the
three readers on a counted record and on other cells' records."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny_g4h as twin  # noqa: E402

SEED = 2 ** 31 + 17
CELL = twin.CELL
REAL = twin.REAL_CELL
CONFIG = os.path.join(REPO, "chipbench", "configs",
                      "granite_4h_micro_p10.json")
NEW_METRICS = ["g4h.mfu_pct", "ssd_g1_roofline", "flash_gqa64_roofline"]
SHARED_METRICS = ["trainer.device_step_ms", "trainer.step_gap_ms",
                  "trainer.launches_per_step"]
COMPARED = {"loss_rel_gap_first_steps", "first_grad_norm_worst_leaf_gap",
            "param_change_norm_worst_leaf_gap"}


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


@pytest.mark.parametrize("dropped", ["residual_multiplier",
                                     "logits_scaling"])
def test_multiplier_dropped_underneath_is_not_correct(root, monkeypatch,
                                                      capsys, dropped):
    from chipbench.drivers import g4h_program as prog

    real = prog.build_net

    def build(config, **kw):
        net = real(config, **kw)
        if dropped == "logits_scaling":
            net._logits = 1.0
        for blk in net.blocks:
            if dropped == "residual_multiplier":
                blk._r = 1.0
        return net

    monkeypatch.setattr(prog, "build_net", build)
    line = _run(root)
    failed = {r["check"]["what"] for r in _records(capsys)
              if "check" in r and not r["check"]["ok"]}
    assert line["correct"] is False
    assert failed & COMPARED, failed


@pytest.mark.parametrize("control", ["bf16", "fp8"])
def test_control_fails(root, capsys, control):
    _run(root, options={"control": control})
    ctl = [r for r in _records(capsys) if "control" in r]
    assert ctl and ctl[0]["control"] == control
    assert ctl[0]["control_fails"] is True, ctl[0]["control_checks"]


def test_every_file_the_new_entries_name_exists():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == REAL][0]
    cfg = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert cell["chips"] == 1 and cell["traffic"] == "one_seq_slice_g4h"
    assert not any(w["chips"] == 4 for w in bench["workloads"])
    assert os.path.isfile(os.path.join(REPO, cfg["file"]))
    with open(os.path.join(REPO, cfg["file"])) as f:
        config = json.load(f)
    assert config["source"] == cfg["source"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    with open(os.path.join(REPO, "chipbench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["batches"] == {"batch": 1, "seq": 8192}
    assert traffic["driver"] == "train_g4h"
    for kind, name in (("drivers", traffic["driver"]),
                       ("generators", traffic["generator"]),
                       ("harness", "counts_granite_hybrid"),
                       ("harness", "weights_granite_hybrid"),
                       ("drivers", "g4h_program"),
                       ("reference", "granite_hybrid_ref")):
        assert os.path.isfile(os.path.join(REPO, "chipbench", kind,
                                           name + ".py")), (kind, name)
    assert os.path.isfile(os.path.join(REPO, "chipbench", "rehearse_g4h.py"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        # membership, not position: a later cell may be appended
        assert by_name[name]["workloads"][0] == REAL
        assert by_name[name]["moves"] == "train_tokens_per_s"
        assert by_name[name]["unit"] == "%"
        assert os.path.isfile(os.path.join(REPO, "chipbench",
                                           "layer_metrics", name + ".py"))
    for name in SHARED_METRICS:
        assert REAL in by_name[name]["workloads"]
    # the other cells' own readers stay theirs
    for name in ("trainer.mfu_pct", "flash_roofline", "hybrid.mfu_pct",
                 "ssd_roofline", "moe_gmm_roofline", "flash_gqa_roofline",
                 "moe.pairs_local_per_token", "moe.load_max_over_mean",
                 "gdn_roofline", "gdn_moe.mfu_pct", "moe_gmm_glu_roofline",
                 "flash_gated_roofline"):
        assert REAL not in by_name[name]["workloads"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert REAL in e2e["train_tokens_per_s"]["workloads"]
    module, _, factory = config["program"]["factory"].rpartition(".")
    assert module == "mxnet_tpu.models" and factory == "get_granite_hybrid"


def test_configuration_keeps_every_published_width():
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(cat):
        pytest.skip("no catalog here")
    with open(cat) as f:
        entry = [json.loads(l) for l in f
                 if '"granite-4.0-h-micro"' in l][0]
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert config["published"] == {k: entry["config"][k] for k in differ}
    # one whole period, in the published ratio
    period = entry["config"]["layer_types"].index("attention", 6) - 5
    assert config["num_hidden_layers"] == period == 10
    assert config["layer_types"] == entry["config"]["layer_types"][:10]
    assert config["layer_types"].count("mamba") == 9
    assert config["vocab_size"] * 8 == entry["config"]["vocab_size"]
    assert {"head_dim", "initializer_range", "weights", "scan_chunk",
            "optimizer"} <= set(config["assumed"])
    assert config["head_dim"] * config["num_attention_heads"] == \
        config["hidden_size"]
    from chipbench.harness.weights_granite_hybrid import (leaves,
                                                          parameter_count,
                                                          sizes_of)
    s = sizes_of(config)
    assert parameter_count(s) == config["parameters"] == 772_160_448
    assert "lm_head" not in {name for name, _s, _l in leaves(s)}   # tied


def test_counts_against_hand_counts():
    from chipbench.harness import counts_granite_hybrid as cg
    from chipbench.harness.weights_granite_hybrid import sizes_of

    with open(CONFIG) as f:
        config = json.load(f)
    s = sizes_of(config)
    assert s["pattern"] == "MMMMMAMMMM" and s["groups"] == 1
    assert s["chunk"] == config["program"].get(
        "scan_chunk", config["mamba_chunk_size"])
    q = s["chunk"]
    macs = cg.forward_macs_per_token(s)
    assert macs["feed_forward"] == 10 * 3 * 2048 * 8192
    assert macs["mamba_proj"] == 9 * (2048 * 8512 + 4096 * 2048)
    assert macs["scan"] == 9 * (q * 128 + 64 * (q * 64 + 2 * 128 * 64))
    assert macs["attention_proj"] == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert macs["head"] == 2048 * 12544
    total = sum(macs.values())
    assert 0.60 < macs["feed_forward"] / total < 0.65
    flops = cg.train_flops_per_token(s, 8192)
    assert flops == 6.0 * total + 12.0 * 32 * 64 * 8192
    assert 4.8e9 < flops < 5.0e9
    f, b = cg.ssd_chunk_flops_bytes(1, 8192, s)
    nc = 8192 // q
    assert f == nc * (2.0 * q * q * 128 + 64 * (2.0 * q * q * 64
                                                + 2.0 * q * 128 * 64))
    assert b == 8192 * ((4096 + 256) * 2 + 2 * 64 * 4 + 4096 * 4) \
        + nc * 64 * 128 * 64 * 4
    assert cg.ssd_output_shapes(1, 8192, s) == [(1, 8192, 4096)]
    assert cg.flash_output_shapes(1, 8192, s) == [(32, 8192, 64)]
    ff, fb = cg.flash_gqa_flops_bytes(1, 32, 8, 8192, 64)
    assert ff == 2.0 * 32 * 8192 * 8192 * 64
    assert fb == 2.0 * 32 * 8192 * 64 * 2 + 2.0 * 8 * 8192 * 64 * 2


def _counted_run(config):
    return {"e2e": {"train_tokens_per_s": 8000.0}, "tokens": 81920,
            "tokens_per_step": 8192, "n_devices": 1, "config": config,
            "traffic": {"batches": {"batch": 1, "seq": 8192}},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "traced": (3, (0, 1)),
            "trace": {"op_seconds": {
                "custom-call:tpu_custom_call (f32[1,8192,4096], "
                "f32[1,64,64,128,64])": 0.04,
                "custom-call:tpu_custom_call (bf16[32,8192,64], "
                "f32[32,1,8192])": 0.01,
                "custom-call:tpu_custom_call bf16[32,8192,64]": 0.03,
                "fusion f32[1,8192,4096]": 1.0}}}


def test_readers_on_a_counted_run():
    from chipbench import run as R

    with open(CONFIG) as f:
        run = _counted_run(json.load(f))
    read = lambda n: R.load_module(REPO, "layer_metrics", n).read(run)  # noqa: E731,E501
    # 8,000 tokens/s x 4.92 GFLOP over 197 TFLOP/s
    assert read("g4h.mfu_pct") == pytest.approx(19.97, abs=0.15)
    # 3 steps x 9 layers of needed calls, memory-bound, against the 0.04 s
    # of the one kernel row with that output (the fusion's is not one)
    from chipbench.harness import counts_granite_hybrid as cg
    from chipbench.harness.weights_granite_hybrid import sizes_of
    flops, nbytes = cg.ssd_chunk_flops_bytes(1, 8192, sizes_of(run["config"]))
    assert nbytes / 819e9 > flops / 197e12
    assert read("ssd_g1_roofline") == pytest.approx(
        100.0 * 27 * (nbytes / 819e9) / 0.04)
    assert 10 < read("ssd_g1_roofline") < 50
    assert 0 < read("flash_gqa64_roofline") < 100


def test_new_readers_return_nothing_elsewhere():
    """In a cell of another configuration, on a record with nothing in it
    and on a trace with no such kernel, each reader returns None and does
    not raise."""
    from chipbench import run as R

    with open(os.path.join(REPO, "chipbench", "configs",
                           "nemotron_tt_30b_a3b_ep16.json")) as f:
        other = json.load(f)
    with open(CONFIG) as f:
        mine = json.load(f)
    bare = {"e2e": {"train_tokens_per_s": 1.0}, "traced": (3, (0, 1)),
            "trace": {"op_seconds": {}}, "config": {}, "traffic": {},
            "tokens": 10}
    elsewhere = dict(_counted_run(other))
    no_kernels = dict(_counted_run(mine), trace={"op_seconds": {}})
    not_traced = dict(_counted_run(mine), traced=None)
    for name in NEW_METRICS:
        read = R.load_module(REPO, "layer_metrics", name).read
        assert read(bare) is None
        assert read(elsewhere) is None
        if name != "g4h.mfu_pct":
            assert read(no_kernels) is None
            assert read(not_traced) is None


def test_reference_layers_against_the_programs_own():
    """The reference's mixers and feed-forward (written apart, the scan by
    its quadratic definition) against the program's blocks on the same
    seeded weights, one sublayer at a time."""
    import jax.numpy as jnp
    import numpy as onp

    from chipbench.harness.weights_granite_hybrid import (make_weights,
                                                          sizes_of)
    from chipbench.reference import granite_hybrid_ref as ref
    from mxnet_tpu.models.granite_hybrid import gated_mlp
    from mxnet_tpu.models.nemotron_h import (GroupedQueryAttention,
                                             Mamba2Mixer)

    with open(os.path.join(HERE, "data", "tiny_g4h.json")) as f:
        s = sizes_of(json.load(f))
    w = make_weights(s, SEED)
    hn = jnp.asarray(onp.random.default_rng(0).standard_normal(
        (2, 32, s["units"])), jnp.float32)
    f32 = jnp.float32
    m = Mamba2Mixer(s["units"], s["m_heads"], s["m_head_dim"], s["groups"],
                    s["state"], conv_kernel=s["conv"], chunk_size=s["chunk"],
                    eps=s["eps"])
    wm = {k: v[0] for k, v in w.items() if k.startswith("m_")}
    got = m.mix(hn, wm["m_in_proj"], wm["m_conv_w"], wm["m_conv_b"],
                wm["m_dt_bias"], wm["m_A_log"], wm["m_D"], wm["m_norm_w"],
                wm["m_out_proj"], f32)
    onp.testing.assert_allclose(got, ref.mamba(hn, wm, s), rtol=2e-4,
                                atol=2e-4)
    a = GroupedQueryAttention(s["units"], s["heads"], s["kv_heads"],
                              s["head_dim"], scale=s["attn_mult"])
    wa = {k: v[0] for k, v in w.items() if k.startswith("a_")}
    got = a.mix(hn, wa["a_q"], wa["a_k"], wa["a_v"], wa["a_o"], f32)
    onp.testing.assert_allclose(got, ref.attention(hn, wa, s, rows=16),
                                rtol=2e-4, atol=2e-4)
    wf = {k: v[0] for k, v in w.items() if k.startswith("f_")}
    onp.testing.assert_allclose(
        gated_mlp(hn, wf["f_in"], wf["f_out"], f32), ref.mlp(hn, wf),
        rtol=2e-4, atol=2e-4)
