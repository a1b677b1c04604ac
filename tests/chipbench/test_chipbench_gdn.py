"""The Gated DeltaNet training cell's tiny twin end to end through
``run_cell`` on the CPU: ``correct`` true; false with the rule's decay
dropped, its write strength ignored, the top-k weights not renormalised,
one expert's gate product left out or rotary positions on all of a head's
dimensions underneath; both controls fail; the counts against hand
counts; every file ``BENCHMARK.json``'s new entries name exists; the
reference's recurrence equals the program's by-definition form."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny_gdn as twin  # noqa: E402

SEED = 2 ** 31 + 13
CELL = twin.CELL
REAL = twin.REAL_CELL
CONFIG = os.path.join(REPO, "chipbench", "configs",
                      "qwen3_next_80b_a3b_share.json")
NEW_METRICS = ["gdn_roofline", "gdn_moe.mfu_pct", "moe_gmm_glu_roofline",
               "flash_gated_roofline"]
SHARED_METRICS = ["trainer.device_step_ms", "trainer.step_gap_ms",
                  "trainer.launches_per_step"]


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


def _failed(capsys):
    return {r["check"]["what"] for r in _records(capsys)
            if "check" in r and not r["check"]["ok"]}


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
    assert {c["what"] for c in checks} == {
        "loss_rel_gap_first_steps", "first_grad_norm_worst_leaf_gap",
        "param_change_norm_worst_leaf_gap", "routing_mismatch_share",
        "window_losses_finite", "xla_compiles_in_window",
        "batches_fell_back_to_host"}
    assert all({"what", "value", "limit", "ok"} <= set(c) for c in checks)
    routed = [r for r in recs if r.get("phase") == "window"][0]["routed"]
    assert routed["layers"] == 4 and routed["experts_held"] == 4
    assert routed["pairs_total"] == routed["steps"] * 4 * 64 * 3
    assert 0 < routed["pairs_local"] < routed["pairs_total"]


COMPARED = {"loss_rel_gap_first_steps", "first_grad_norm_worst_leaf_gap",
            "param_change_norm_worst_leaf_gap", "routing_mismatch_share"}


@pytest.mark.parametrize("broken", ["decay_dropped", "beta_ignored",
                                    "weights_not_renormalised",
                                    "one_gate_product_left_out",
                                    "rotary_on_every_dimension"])
def test_broken_underneath_is_not_correct(root, monkeypatch, capsys, broken):
    import jax.numpy as jnp

    if broken in ("decay_dropped", "beta_ignored"):
        from mxnet_tpu.ops import gdn
        real = gdn.gdn_scan

        def rule(q, k, v, g, beta, **kw):
            if broken == "decay_dropped":
                return real(q, k, v, jnp.zeros_like(g), beta, **kw)
            return real(q, k, v, g, jnp.ones_like(beta), **kw)
        monkeypatch.setattr(gdn, "gdn_scan", rule)
    elif broken == "weights_not_renormalised":
        from mxnet_tpu.models import moe
        real = moe.route_softmax_topk

        def plain(*a, norm_topk=True, **kw):
            return real(*a, norm_topk=False, **kw)
        monkeypatch.setattr(moe, "route_softmax_topk", plain)
    elif broken == "one_gate_product_left_out":
        # the layer's products come in threes: up, gate, down.  The first
        # held expert's rows of the gate product become the value whose
        # silu is 1: that expert computes (x W_u) W_d
        from mxnet_tpu.ops import gmm
        real, calls = gmm.grouped_matmul, []

        def ungated(lhs, rhs, sizes, **kw):
            calls.append(1)
            out = real(lhs, rhs, sizes, **kw)
            if len(calls) % 3 != 2:
                return out
            first = jnp.arange(out.shape[0])[:, None] < sizes[0]
            return jnp.where(first, jnp.asarray(1.2784645, out.dtype), out)
        monkeypatch.setattr(gmm, "grouped_matmul", ungated)
    else:
        from mxnet_tpu.ops import attention
        real = attention.rotary_embedding

        def everywhere(x, positions=None, *, theta=10000.0, rotary_dim=None):
            return real(x, positions, theta=theta, rotary_dim=None)
        monkeypatch.setattr(attention, "rotary_embedding", everywhere)
    line = _run(root)
    failed = _failed(capsys)
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
    assert cell["chips"] == 1 and bench["workloads"][-1] is cell
    assert os.path.isfile(os.path.join(REPO, cfg["file"]))
    with open(os.path.join(REPO, cfg["file"])) as f:
        config = json.load(f)
    assert config["source"] == cfg["source"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    with open(os.path.join(REPO, "chipbench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["batches"] == {"batch": 1, "seq": 8192}
    for kind, name in (("drivers", traffic["driver"]),
                       ("generators", traffic["generator"]),
                       ("harness", "counts_qwen3_next"),
                       ("harness", "weights_qwen3_next"),
                       ("drivers", "qwen3_next_program"),
                       ("reference", "qwen3_next_ref")):
        assert os.path.isfile(os.path.join(REPO, "chipbench", kind,
                                           name + ".py")), (kind, name)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        # the first cell, not the only one: a later cell may be appended
        assert by_name[name]["workloads"][0] == REAL
        assert by_name[name]["moves"] == "train_tokens_per_s"
        assert by_name[name]["unit"] == "%"
        assert os.path.isfile(os.path.join(REPO, "chipbench",
                                           "layer_metrics", name + ".py"))
    for name in SHARED_METRICS:
        assert by_name[name]["workloads"][-1] == REAL
    # the two routing metrics stay the other cell's alone: its test file
    # (tests/chipbench/test_chipbench_hybrid.py, not this PR's to edit)
    # holds their lists to exactly that cell; their readers are driven on
    # this cell's records below all the same
    for name in ("trainer.mfu_pct", "flash_roofline", "hybrid.mfu_pct",
                 "ssd_roofline", "moe_gmm_roofline", "flash_gqa_roofline",
                 "moe.pairs_local_per_token", "moe.load_max_over_mean"):
        assert REAL not in by_name[name]["workloads"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["train_tokens_per_s"]["workloads"][-1] == REAL
    module, _, factory = config["program"]["factory"].rpartition(".")
    assert module == "mxnet_tpu.models" and factory == "get_qwen3_next"


def test_configuration_keeps_every_published_width():
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(cat):
        pytest.skip("no catalog here")
    with open(cat) as f:
        entry = [json.loads(l) for l in f
                 if '"Qwen3-Next-80B-A3B-Instruct"' in l][0]
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert config["published"] == {k: entry["config"][k] for k in differ}
    assert config["num_experts_published"] == 512
    assert config["num_hidden_layers"] % config["full_attention_interval"] \
        == 0                                   # whole periods
    assert "multi_token_prediction" in config["assumed"]
    # parameters, from the leaves
    from chipbench.harness.weights_qwen3_next import (parameter_count,
                                                      sizes_of)
    assert parameter_count(sizes_of(config)) == config["parameters"] \
        == 625_667_136


def test_counts_against_hand_counts():
    from chipbench.harness import counts_qwen3_next as cq
    from chipbench.harness.weights_qwen3_next import sizes_of

    with open(CONFIG) as f:
        s = sizes_of(json.load(f))
    assert s["pattern"] == "LLLF" and s["rotary_dim"] == 64
    macs = cq.forward_macs_per_token(s, 0.625)
    assert macs["deltanet_proj"] == 3 * (2048 * (12288 + 64) + 4096 * 2048)
    assert macs["attention_proj"] == 2048 * 8192 + 2 * 2048 * 512 \
        + 4096 * 2048
    assert macs["router"] == 4 * 2048 * 512
    assert macs["shared_expert"] == 4 * (3 * 2048 * 512 + 2048)
    assert macs["routed_experts"] == 4 * 0.625 * 3 * 2048 * 512
    assert macs["head"] == 2048 * 18992
    rule = 16 * 2 * 64 * 128 + 32 * (64 * 128 + 64 * 128 + 3 * 128 * 128
                                     + 64 * 128 + 64 * 64 / 6)
    assert macs["delta_rule"] == pytest.approx(3 * rule)
    total = sum(macs.values())
    assert 1.95e8 < total < 2.05e8
    flops = cq.train_flops_per_token(s, 8192, 0.625)
    assert flops == 6.0 * total + 12.0 * 16 * 256 * 8192
    assert 1.55e9 < flops < 1.65e9
    assert cq.train_flops_per_token(s, 8192, 1.0) > flops
    assert cq.forward_macs_per_token(s, 0.0)["routed_experts"] == 0
    f, b = cq.gdn_chunk_flops_bytes(1, 8192, s)
    assert f == pytest.approx(2 * 8192 * rule)
    assert b == 8192 * ((2048 + 2048 + 4096) * 2 + 2 * 32 * 4 + 4096 * 4)
    assert cq.gdn_output_shapes(1, 8192, s) == [(1, 8192, 4096)]
    assert cq.moe_gmm_output_shapes(81920, s) == [
        (81920, 512), (81920, 2048), (32, 2048, 512), (32, 512, 2048)]
    assert cq.flash_output_shapes(1, 8192, s) == [(16, 8192, 256)]
    assert cq.GMM_CALLS_A_LAYER == 9


def test_new_readers_return_nothing_where_the_program_says_nothing():
    """On a parent whose program has no such kernel or counters, and in a
    cell of another configuration, each reader returns None and does not
    raise."""
    from chipbench import run as R

    with open(os.path.join(REPO, "chipbench", "configs",
                           "nemotron_tt_30b_a3b_ep16.json")) as f:
        other = json.load(f)
    bare = {"e2e": {"train_tokens_per_s": 1.0}, "traced": (3, (0, 1)),
            "trace": {"op_seconds": {}}, "config": {}, "traffic": {},
            "tokens": 10}
    routed = {"pairs_local": 10.0, "steps": 3.0, "layers": 4,
              "experts_held": 8}
    elsewhere = dict(bare, config=other, routed=routed, routed_traced=routed,
                     tokens_per_step=8192, n_devices=1,
                     traffic={"batches": {"batch": 1, "seq": 8192}},
                     peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    with open(CONFIG) as f:
        no_kernels = dict(elsewhere, config=json.load(f))
    for name in NEW_METRICS:
        read = R.load_module(REPO, "layer_metrics", name).read
        assert read(bare) is None
        assert read(elsewhere) is None
        if name != "gdn_moe.mfu_pct":
            assert read(no_kernels) is None      # nothing found in the trace


def test_readers_on_a_counted_run():
    from chipbench import run as R

    with open(CONFIG) as f:
        config = json.load(f)
    routed = {"pairs_local": 4 * 5120.0 * 10, "pairs_total": 4 * 81920.0 * 10,
              "load_max": 4 * 240.0 * 10, "steps": 10.0, "layers": 4,
              "experts_held": 32}
    run = {"e2e": {"train_tokens_per_s": 25000.0}, "tokens": 81920,
           "tokens_per_step": 8192, "n_devices": 1, "config": config,
           "traffic": {"batches": {"batch": 1, "seq": 8192}},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "routed": routed, "routed_traced": routed, "traced": (10, (0, 1)),
           "trace": {"op_seconds": {
               "custom-call:tpu_custom_call f32[1,8192,4096]": 0.2,
               "custom-call:tpu_custom_call bf16[81920,512]": 0.1,
               "custom-call:tpu_custom_call bf16[81920,2048]": 0.1,
               "custom-call:tpu_custom_call bf16[32,2048,512]": 0.1,
               "custom-call:tpu_custom_call (bf16[16,8192,256], "
               "f32[16,1,8192])": 0.05,
               "custom-call:tpu_custom_call bf16[16,8192,256]": 0.15,
               "fusion bf16[81920,2048]": 1.0}}}
    read = lambda n: R.load_module(REPO, "layer_metrics", n).read(run)  # noqa: E731,E501
    assert read("moe.pairs_local_per_token") == pytest.approx(0.625)
    assert read("moe.load_max_over_mean") == pytest.approx(240 / 160)
    assert 15 < read("gdn_moe.mfu_pct") < 25
    # 30 needed calls of 0.33 ms (memory-bound) against 0.2 s spent
    assert read("gdn_roofline") == pytest.approx(4.95, abs=0.1)
    assert 0 < read("moe_gmm_glu_roofline") < 100
    assert 0 < read("flash_gated_roofline") < 100


def test_reference_recurrence_equals_the_programs_definition():
    """Two recurrences written apart (the reference's segments of 64 with
    elementwise sums; the program's test oracle) give the same rule."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    from chipbench.reference import qwen3_next_ref as ref
    from mxnet_tpu.ops.gdn import gdn_recurrence

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    b, t, h, dk, dv = 2, 128, 3, 16, 8
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731,E501
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    for decay in (0.1, 3.0, 90.0):         # the last underflows exp(sum g)
        g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
        got = ref.delta_rule(q, k, v, g, beta)
        want = gdn_recurrence(q, k, v, g, beta)
        assert bool(jnp.all(jnp.isfinite(got)))
        onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                    rtol=1e-4, atol=1e-5)
