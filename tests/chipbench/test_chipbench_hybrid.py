"""The hybrid training cell's tiny twin end to end through ``run_cell`` on
the CPU: ``correct`` true; false with the scan's decay, the router's
scaling, one expert's output or the router's correction bias broken
underneath; both controls fail; the
counts against hand counts; every file ``BENCHMARK.json``'s new entries
name exists; the reference's quadratic form equals the recurrence."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny_hybrid as twin  # noqa: E402

SEED = 2 ** 31 + 11
CELL = twin.CELL
REAL = twin.REAL_CELL
NEW_METRICS = ["hybrid.mfu_pct", "ssd_roofline", "moe_gmm_roofline",
               "moe.pairs_local_per_token", "moe.load_max_over_mean",
               "flash_gqa_roofline"]


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
    assert {c["what"] for c in checks} >= {
        "loss_rel_gap_first_steps", "first_grad_norm_worst_leaf_gap",
        "param_change_norm_worst_leaf_gap", "routing_mismatch_share",
        "xla_compiles_in_window", "routing_buffer_moved"}
    assert all({"what", "value", "limit", "ok"} <= set(c) for c in checks)
    routed = [r for r in recs if r.get("phase") == "window"][0]["routed"]
    assert routed["pairs_total"] == routed["steps"] * 2 * 64 * 3
    assert 0 < routed["pairs_local"] < routed["pairs_total"]


@pytest.mark.parametrize("broken,expect", [
    ("scan_decay", "loss_rel_gap_first_steps"),
    ("router_scaling", "loss_rel_gap_first_steps"),
    ("one_expert", "first_grad_norm_worst_leaf_gap"),
    ("correction_bias", "routing_mismatch_share"),
])
def test_broken_underneath_is_not_correct(root, monkeypatch, capsys, broken,
                                          expect):
    import jax.numpy as jnp

    if broken == "scan_decay":
        from mxnet_tpu.ops import ssd
        real = ssd._prep

        def slower_decay(x, dt, a, chunk):
            return real(x, dt, 0.9 * a, chunk)
        monkeypatch.setattr(ssd, "_prep", slower_decay)
    elif broken == "router_scaling":
        from mxnet_tpu.models import moe
        real = moe.route_sigmoid_topk

        def unscaled(*a, scaling=1.0, **kw):
            return real(*a, scaling=1.0, **kw)
        monkeypatch.setattr(moe, "route_sigmoid_topk", unscaled)
    elif broken == "correction_bias":
        # a router that chooses by its scores alone: the reference follows
        # the program's indices, so only the share of tokens whose own
        # top-k set differs can say so
        from mxnet_tpu.models import moe
        real = moe.route_sigmoid_topk

        def unbiased(x, w_router, choice_bias, **kw):
            return real(x, w_router, jnp.zeros_like(choice_bias), **kw)
        monkeypatch.setattr(moe, "route_sigmoid_topk", unbiased)
    else:
        from mxnet_tpu.ops import gmm
        real = gmm.grouped_matmul

        def first_expert_off(lhs, rhs, sizes, **kw):
            return real(lhs, rhs.at[0].multiply(0.9), sizes, **kw)
        monkeypatch.setattr(gmm, "grouped_matmul", first_expert_off)
    line = _run(root)
    failed = _failed(capsys)
    assert line["correct"] is False
    assert expect in failed, failed
    del jnp


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
    assert cell["chips"] == 1
    assert os.path.isfile(os.path.join(REPO, cfg["file"]))
    with open(os.path.join(REPO, cfg["file"])) as f:
        config = json.load(f)
    assert config["source"] == cfg["source"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    with open(os.path.join(REPO, "chipbench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    for kind, name in (("drivers", traffic["driver"]),
                       ("generators", traffic["generator"]),
                       ("harness", "counts_hybrid"),
                       ("harness", "weights_hybrid"),
                       ("drivers", "hybrid_program"),
                       ("reference", "nemotron_h_ref")):
        assert os.path.isfile(os.path.join(REPO, "chipbench", kind,
                                           name + ".py")), (kind, name)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [REAL]
        assert by_name[name]["moves"] == "train_tokens_per_s"
        assert os.path.isfile(os.path.join(REPO, "chipbench",
                                           "layer_metrics", name + ".py"))
    assert by_name["trainer.mfu_pct"]["workloads"] == ["train_124m_seq1024"]
    for name in ("trainer.device_step_ms", "trainer.step_gap_ms",
                 "trainer.launches_per_step"):
        assert REAL in by_name[name]["workloads"]
    assert REAL not in by_name["flash_roofline"]["workloads"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert REAL in e2e["train_tokens_per_s"]["workloads"]


def test_configuration_keeps_every_published_width():
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(cat):
        pytest.skip("no catalog here")
    with open(cat) as f:
        entry = [json.loads(l) for l in f
                 if "TwoTower-30B-A3B" in l][0]
    with open(os.path.join(REPO, "chipbench", "configs",
                           "nemotron_tt_30b_a3b_ep16.json")) as f:
        config = json.load(f)
    assert config["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if config.get(k) != v}
    assert differ == set(config["reduced"])
    assert config["hybrid_override_pattern"] == \
        entry["config"]["hybrid_override_pattern"][:9]
    assert config["published"]["n_routed_experts"] == 128
    assert "second_tower" in config["assumed"]


def test_counts_against_hand_counts():
    from chipbench.harness import counts_hybrid as ch
    from chipbench.harness.weights_hybrid import sizes_of

    with open(os.path.join(REPO, "chipbench", "configs",
                           "nemotron_tt_30b_a3b_ep16.json")) as f:
        s = sizes_of(json.load(f))
    macs = ch.forward_macs_per_token(s, 0.375)
    assert macs["mamba_proj"] == 4 * (2688 * 10304 + 4096 * 2688)
    assert macs["attention_proj"] == 2 * 2688 * 4096 + 2 * 2688 * 256
    assert macs["router"] == 4 * 2688 * 128
    assert macs["shared_expert"] == 4 * 2 * 2688 * 3712
    assert macs["routed_experts"] == 4 * 0.375 * 2 * 2688 * 1856
    assert macs["head"] == 2688 * 16384
    assert macs["scan"] == 4 * (8 * 128 * 128 + 64 * (128 * 64
                                                      + 2 * 128 * 64))
    total = sum(macs.values())
    assert 3.15e8 < total < 3.3e8                  # the issue's 318.6M
    flops = ch.train_flops_per_token(s, 8192, 0.375)
    assert flops == 6.0 * total + 12.0 * 32 * 128 * 8192
    assert 2.2e9 < flops < 2.4e9                   # 2.3 GFLOP a token
    # more pairs routed here, more work counted; none routed, none counted
    assert ch.train_flops_per_token(s, 8192, 0.5) > flops
    assert ch.forward_macs_per_token(s, 0.0)["routed_experts"] == 0
    f, b = ch.ssd_chunk_flops_bytes(1, 8192, s)
    assert f == 64 * (8 * 2 * 128 * 128 * 128
                      + 64 * (2 * 128 * 128 * 64 + 2 * 128 * 128 * 64))
    assert b == 8192 * ((4096 + 2048) * 2 + 2 * 64 * 4 + 4096 * 4) \
        + 64 * 64 * 128 * 64 * 4
    f, b = ch.moe_gmm_flops_bytes(3072, 2688, 1856, 8)
    assert f == 2 * 3072 * 2688 * 1856
    assert b == (3072 * (2688 + 1856) + 8 * 2688 * 1856) * 2
    assert ch.moe_gmm_flops_bytes(0, 2688, 1856, 8)[0] == 0
    # 32 query heads over 2 key/value heads of 128 at T 8,192
    f, b = ch.flash_gqa_flops_bytes(1, 32, 2, 8192, 128)
    assert f == 2 * 32 * 8192 * 8192 * 128
    assert b == (2 * 32 + 2 * 2) * 8192 * 128 * 2
    fb, bb = ch.flash_gqa_flops_bytes(1, 32, 2, 8192, 128, backward=True)
    assert fb == 2.5 * f and bb == (4 * 32 + 4 * 2) * 8192 * 128 * 2
    # equal heads: the count the GPT-2 cell's reader uses
    from chipbench.harness import counts
    for backward in (False, True):
        assert ch.flash_gqa_flops_bytes(8, 12, 12, 1024, 64,
                                        backward=backward) == \
            counts.flash_flops_bytes(8, 12, 1024, 64, backward=backward)


def test_kernel_seconds_finds_calls_by_output_shape():
    from chipbench.harness import counts_hybrid as ch

    ops = {
        "custom-call:tpu_custom_call (f32[1,8192,4096], f32[1,64,64,128,64])":
            1.0,
        "custom-call:tpu_custom_call bf16[49152,1856]": 2.0,
        "custom-call:tpu_custom_call bf16[8,2688,1856]": 4.0,
        "custom-call:tpu_custom_call bf16[32,8192,128]": 8.0,   # flash dq
        "custom-call:tpu_custom_call (bf16[32,8192,128], f32[32,1,8192])":
            32.0,                                               # flash fwd
        "fusion bf16[49152,2688]": 16.0,                        # not a kernel
    }
    s = {"m_heads": 64, "m_head_dim": 64, "units": 2688,
         "expert_width": 1856, "experts_held": 8, "heads": 32,
         "head_dim": 128}
    assert ch.kernel_seconds(ops, ch.flash_output_shapes(1, 8192, s)) == 40.0
    assert ch.kernel_seconds(ops, ch.ssd_output_shapes(1, 8192, s)) == 1.0
    assert ch.kernel_seconds(
        ops, ch.moe_gmm_output_shapes(49152, s)) == 6.0


def test_new_readers_return_nothing_where_the_program_says_nothing():
    """On a parent whose program has no such counters the records lack
    them: each reader returns None and does not raise."""
    from chipbench import run as R

    run = {"e2e": {"train_tokens_per_s": 1.0}, "traced": (3, (0, 1)),
           "trace": {"op_seconds": {}}, "config": {}, "traffic": {},
           "tokens": 10}
    for name in NEW_METRICS:
        assert R.load_module(REPO, "layer_metrics", name).read(run) is None


def test_readers_on_a_counted_run():
    from chipbench import run as R

    with open(os.path.join(REPO, "chipbench", "configs",
                           "nemotron_tt_30b_a3b_ep16.json")) as f:
        config = json.load(f)
    routed = {"pairs_local": 4 * 3072.0 * 10, "pairs_total": 4 * 49152.0 * 10,
              "load_max": 4 * 480.0 * 10, "steps": 10.0, "layers": 4,
              "experts_held": 8}
    run = {"e2e": {"train_tokens_per_s": 16000.0}, "tokens": 81920,
           "tokens_per_step": 8192, "n_devices": 1, "config": config,
           "traffic": {"batches": {"batch": 1, "seq": 8192}},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "routed": routed, "routed_traced": routed, "traced": (10, (0, 1)),
           "trace": {"op_seconds": {
               "custom-call:tpu_custom_call (f32[1,8192,4096], "
               "f32[1,64,64,128,64])": 0.2,
               "custom-call:tpu_custom_call bf16[49152,1856]": 0.1,
               "custom-call:tpu_custom_call bf16[8,1856,2688]": 0.1,
               "custom-call:tpu_custom_call (bf16[32,8192,128], "
               "f32[32,1,8192])": 0.098,
               "custom-call:tpu_custom_call (f32[32,8192,128], "
               "f32[32,8192,128])": 0.081,
               "custom-call:tpu_custom_call bf16[32,8192,128]": 0.062}}}
    read = lambda n: R.load_module(REPO, "layer_metrics", n).read(run)
    assert read("moe.pairs_local_per_token") == pytest.approx(0.375)
    assert read("moe.load_max_over_mean") == pytest.approx(480 / 384)
    assert 15 < read("hybrid.mfu_pct") < 25
    assert 0 < read("ssd_roofline") < 100
    assert 0 < read("moe_gmm_roofline") < 100
    # fwd 2.79 ms + bwd 6.98 ms needed a step against 24.1 ms spent
    assert read("flash_gqa_roofline") == pytest.approx(40.5, abs=0.5)


def test_reference_quadratic_form_equals_the_recurrence():
    import jax
    import jax.numpy as jnp
    import numpy as onp

    from chipbench.reference import nemotron_h_ref as ref

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    b, t, p, n = 2, 48, 8, 16
    x = jax.random.normal(ks[0], (b, t, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t)))
    bm = jax.random.normal(ks[2], (b, t, n))
    cm = jax.random.normal(ks[3], (b, t, n))
    for a in (-0.3, -4.0, -40.0):          # the last underflows exp(cum)
        got = ref.scan_quadratic(x, dt, jnp.float32(a), bm, cm)
        want = ref.scan_by_recurrence(x, dt, jnp.float32(a), bm, cm)
        onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                    rtol=1e-4, atol=1e-4)
