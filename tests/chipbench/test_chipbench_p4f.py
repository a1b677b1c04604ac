"""The SambaY training cell's tiny twin end to end through ``run_cell`` on
the CPU: ``correct`` true; the lower-precision controls and each of the
five faults planted under the timed path not ``correct``; every file
``BENCHMARK.json``'s new entries name exists; the configuration against
the catalog's row and the parameter count from the leaves; the counts
against hand counts; the three readers on a counted record, on other
cells' records and on empty ones."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny_p4f as twin  # noqa: E402

SEED = 2 ** 31 + 17
CELL = twin.CELL
REAL = twin.REAL_CELL
CONFIG = os.path.join(REPO, "chipbench", "configs",
                      "phi4_mini_flash_l14_19.json")
NEW_METRICS = ["p4f.mfu_pct", "sscan_roofline", "flash_diff_roofline"]
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


def test_what_the_comparison_reads_of_the_small_leaves(root):
    """Every leaf in both numbers, the bias vector in its three parts;
    the parameter change leaves out a part by the reference's gradient
    on a checked step and by nothing else."""
    import jax
    from chipbench import run as R
    from chipbench.drivers import train_p4f as drv
    from chipbench.harness.weights_phi4_flash import (compared_apart,
                                                      leaves, sizes_of)

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cell, cfg = R.find_cell(json.load(f), CELL)
    config = R.load_json(os.path.join(root, cfg["file"]))
    traffic = R.load_json(os.path.join(root, "chipbench", "traffic",
                                       cell["traffic"] + ".json"))
    gen = R.load_module(root, "generators", traffic["generator"])
    sizes = sizes_of(config)
    assert [n for n, _, _ in compared_apart(sizes)["a_qkv_b"]] == [
        "a_q_b", "a_k_b", "a_v_b"]
    want = drv.reference_steps(gen, config, traffic, SEED)
    job = drv.Job(gen, config, traffic, SEED, jax.devices()[:1])
    try:
        job.step()
        got = {"grad_norms": job.first_grad_norms(),
               "delta_norms": job.delta_norms(SEED)}
    finally:
        job.close()
    read = {n for n, _, _ in leaves(sizes)} - {"a_qkv_b"} | {
        "a_q_b", "a_k_b", "a_v_b"}
    for side in (want, got):
        assert set(side["grad_norms"]) == read
        assert set(side["delta_norms"]) == read
    assert set(want["off_line"]) == read
    # no softmax sees the key bias: noise by its gradient, as in GPT-2's
    from chipbench.drivers.train import noise_leaves
    assert noise_leaves(want["grad_norms"]) == {("a_k_b", 0), ("a_k_b", 1)}


def test_the_reference_knows_a_part_adam_moves_as_one_number(root):
    """At the cell's rate the lambda vectors, whose 256 numbers enter the
    loss through one scalar, read nought to rounding and no other part
    comes near; the parameter change leaves them out and the first
    gradient reads them."""
    from chipbench import run as R
    from chipbench.drivers import train_p4f as drv

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, cfg = R.find_cell(bench, CELL)
    config = R.load_json(os.path.join(root, cfg["file"]))
    with open(CONFIG) as f:
        config["training"]["learning_rate"] = json.load(f)[
            "training"]["learning_rate"]
    traffic = R.load_json(os.path.join(root, "chipbench", "traffic",
                                       cell["traffic"] + ".json"))
    gen = R.load_module(root, "generators", traffic["generator"])
    ref = drv.reference_steps(gen, config, traffic, SEED)
    lambdas = {("a_lambdas", 0), ("a_lambdas", 1), ("c_lambdas", 0)}
    assert ref["one_number"] == lambdas
    line = ref["off_line"]
    assert max(line[k][i] for k, i in lambdas) < drv.ONE_NUMBER / 10
    assert min(x for k in line for i, x in enumerate(line[k])
               if (k, i) not in lambdas) > 100 * drv.ONE_NUMBER
    limits = config["training"]["limits"]
    got = {k: ref[k] for k in ("losses", "grad_norms", "delta_norms")}
    got["delta_norms"] = dict(got["delta_norms"], a_lambdas=[
        0.3 * x for x in ref["delta_norms"]["a_lambdas"]])
    checks = {c["what"]: c for c in drv.compare(got, ref, limits)}
    change = checks["param_change_norm_worst_leaf_gap"]
    assert change["ok"] and change["value"] == 0.0
    assert change["unread"] == ["a_k_b[0]", "a_k_b[1]", "a_lambdas[0]",
                                "a_lambdas[1]", "c_lambdas[0]"]
    got["delta_norms"] = dict(ref["delta_norms"], a_subln=[
        0.3 * x for x in ref["delta_norms"]["a_subln"]])   # 128 numbers
    checks = {c["what"]: c for c in drv.compare(got, ref, limits)}
    assert not checks["param_change_norm_worst_leaf_gap"]["ok"]
    got["delta_norms"] = ref["delta_norms"]
    got["grad_norms"] = dict(ref["grad_norms"], a_lambdas=[
        3.0 * x for x in ref["grad_norms"]["a_lambdas"]])
    checks = {c["what"]: c for c in drv.compare(got, ref, limits)}
    assert checks["first_grad_norm_worst_leaf_gap"]["leaf"].startswith(
        "a_lambdas")


def test_the_lambda_vectors_update_is_the_references(root):
    """What the parameter-change number does not read on the chip is held
    here, element for element: after the three checked steps the
    program's lambda vectors are the reference's (float32 both)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from chipbench import run as R
    from chipbench.drivers import p4f_program as prog
    from chipbench.drivers import train_p4f as drv
    from chipbench.harness.weights_phi4_flash import make_weights, sizes_of
    from chipbench.reference import phi4_flash_ref as ref

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cell, cfg = R.find_cell(json.load(f), CELL)
    config = R.load_json(os.path.join(root, cfg["file"]))
    traffic = R.load_json(os.path.join(root, "chipbench", "traffic",
                                       cell["traffic"] + ".json"))
    gen = R.load_module(root, "generators", traffic["generator"])
    sizes, tr = sizes_of(config), config["training"]
    w0 = make_weights(sizes, SEED, "float32")
    start = {k: onp.asarray(w0[k]) for k in ("a_lambdas", "c_lambdas")}
    w, state = w0, ref.adam_init(w0)
    batches = gen.generate(traffic, SEED, sizes["vocab"])
    for t in range(1, drv.CHECK_STEPS + 1):
        tokens, labels = next(batches)
        _loss, grads = ref.loss_and_grads(
            w, jnp.asarray(tokens), jnp.asarray(labels), sizes,
            rows=int(tr["reference_attention_rows_per_block"]))
        w, state = ref.adam_step(w, grads, state, t=t,
                                 lr=float(tr["learning_rate"]))
    job = drv.Job(gen, config, traffic, SEED, jax.devices()[:1])
    try:
        for _ in range(drv.CHECK_STEPS):
            job.step()
        params = prog.param_map(job.net, sizes["pattern"])
        for leaf, before in start.items():
            for i in range(before.shape[0]):
                want = onp.asarray(w[leaf][i]) - before[i]
                got = onp.asarray(params[(leaf, i)].data().jax) - before[i]
                assert onp.abs(want).min() > 0.5 * tr["learning_rate"]
                onp.testing.assert_allclose(got, want, rtol=0.02,
                                            atol=0.02 * tr["learning_rate"])
    finally:
        job.close()


def test_the_faults_are_the_five_the_cell_was_held_to():
    from chipbench.drivers import p4f_faults

    assert p4f_faults.FAULTS == ("window_ignored", "lambda_dropped",
                                 "memory_gated", "own_keys", "linear_decay")
    with pytest.raises(ValueError):
        with p4f_faults.planted("nothing"):
            pass


@pytest.mark.parametrize("fault", ["window_ignored", "lambda_dropped",
                                   "memory_gated", "own_keys",
                                   "linear_decay"])
def test_fault_planted_underneath_is_not_correct(root, capsys, fault):
    from chipbench.drivers import p4f_faults

    with p4f_faults.planted(fault):
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
    assert cell["chips"] == 1 and cell["traffic"] == "one_seq_slice_p4f"
    assert cfg["name"] == "phi4_mini_flash_l14_19"
    assert os.path.isfile(os.path.join(REPO, cfg["file"]))
    with open(os.path.join(REPO, cfg["file"])) as f:
        config = json.load(f)
    assert config["source"] == cfg["source"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    with open(os.path.join(REPO, "chipbench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["batches"] == {"batch": 1, "seq": 8192}
    assert traffic["driver"] == "train_p4f"
    assert (traffic["warm_steps"], traffic["trace_s"]) == (2, 3.0)
    for kind, name in (("drivers", traffic["driver"]),
                       ("generators", traffic["generator"]),
                       ("harness", "counts_phi4_flash"),
                       ("harness", "weights_phi4_flash"),
                       ("drivers", "p4f_program"),
                       ("drivers", "p4f_faults"),
                       ("reference", "phi4_flash_ref")):
        assert os.path.isfile(os.path.join(REPO, "chipbench", kind,
                                           name + ".py")), (kind, name)
    assert os.path.isfile(os.path.join(REPO, "chipbench", "rehearse_p4f.py"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        # membership, not position: a later cell may be appended
        assert by_name[name]["workloads"][0] == REAL
        assert by_name[name]["moves"] == "train_tokens_per_s"
        assert by_name[name]["unit"] == "%"
        assert os.path.isfile(os.path.join(REPO, "chipbench",
                                           "layer_metrics", name + ".py"))
    assert by_name["p4f.mfu_pct"]["layer"] == "trainer"
    assert by_name["sscan_roofline"]["layer"] == "kernels"
    assert by_name["flash_diff_roofline"]["source"] == "device_trace"
    for name in SHARED_METRICS:
        assert REAL in by_name[name]["workloads"]
    # the other cells' own readers stay theirs (the split of set-up may
    # be widened to this cell by a benchmark issue)
    for name, m in by_name.items():
        if (name not in NEW_METRICS + SHARED_METRICS and "workloads" in m
                and m["layer"] != "startup"):
            assert REAL not in m["workloads"], name
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert REAL in e2e["train_tokens_per_s"]["workloads"]
    module, _, factory = config["program"]["factory"].rpartition(".")
    assert module == "mxnet_tpu.models" and factory == "get_phi4_flash"


def test_configuration_keeps_every_published_width():
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(cat):
        pytest.skip("no catalog here")
    with open(cat) as f:
        entry = [json.loads(l) for l in f
                 if '"Phi-4-mini-flash-reasoning"' in l][0]
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {"num_hidden_layers",
                                                "vocab_size"}
    assert config["published"] == {k: entry["config"][k] for k in differ}
    # a contiguous slice with one of every kind of layer, an eighth of
    # the tied table
    assert config["layers_held"] == list(range(14, 20))
    assert config["vocab_size"] * 8 == entry["config"]["vocab_size"]
    assert {"mamba", "head_dim", "biases", "differential_heads", "subnorm",
            "lambda_init", "memory", "position", "normalization", "weights",
            "not_built", "optimizer"} <= set(config["assumed"])
    assert config["head_dim"] * config["num_attention_heads"] == \
        config["hidden_size"]
    assert config["mamba_dt_rank"] * 16 == config["hidden_size"]
    from chipbench.harness.weights_phi4_flash import (kinds_of, leaves,
                                                      parameter_count,
                                                      sizes_of)
    s = sizes_of(config)
    assert s["pattern"] == "MSWFGC" and s["d_inner"] == 5120
    assert kinds_of(32) == "MS" * 8 + "WF" + "GC" * 7
    assert parameter_count(s) == config["parameters"] == 697_094_272
    assert sum(config["parameters_by_part"].values()) == config["parameters"]
    assert "lm_head" not in {name for name, _s, _l in leaves(s)}   # tied
    limits = config["training"]["limits"]
    assert set(limits) == {"loss_rel_gap", "grad_norm_gap", "delta_norm_gap"}
    # the program's own count of its parameters is the file's
    from chipbench.drivers import p4f_program as prog
    import math
    net = prog.build_net(config)
    assert sum(math.prod(p.shape) for p in
               net._collect_params_with_prefix().values()) == \
        config["parameters"]


def test_counts_against_hand_counts():
    from chipbench.harness import counts_phi4_flash as cp
    from chipbench.harness.weights_phi4_flash import sizes_of

    with open(CONFIG) as f:
        config = json.load(f)
    s = sizes_of(config)
    macs = cp.forward_macs_per_token(s)
    assert macs["feed_forward"] == 6 * 3 * 2560 * 10240
    assert macs["mamba_proj"] == 2 * (2560 * 10240 + 5120 * 192
                                      + 160 * 5120 + 5120 * 2560)
    assert macs["attention_proj"] == (2 * (2560 * 5120 + 2560 * 2560)
                                      + 2 * 2560 * 2560)
    assert macs["gmu"] == 2 * 2560 * 5120
    assert macs["head"] == 2560 * 25008
    assert cp.seen_pairs(8192) == 8192 * 8193 // 2
    assert cp.seen_pairs(8192, 512) == 512 * 513 // 2 + 7680 * 512
    assert cp.seen_pairs(100, 512) == 100 * 101 // 2
    assert cp.layer_windows(s) == [512, None, None]
    full = cp.score_flops(1, 8192, s)
    assert full == 6.0 * 64 * 40 * (8192 * 8193 // 2)
    assert cp.score_flops(1, 8192, s, 512) < full / 8
    total = sum(macs.values())
    flops = cp.train_flops_per_token(s, 8192)
    scores = (2 * full + cp.score_flops(1, 8192, s, 512)) / 8192
    assert flops == 6.0 * total + 3.0 * scores
    assert 4.5e9 < flops < 4.7e9
    # by work: the dense SwiGLU about five eighths, the scores under a tenth
    work = 2.0 * total + scores
    assert 0.60 < 2.0 * macs["feed_forward"] / work < 0.64
    assert 0.08 < scores / work < 0.10
    f, b = cp.flash_diff_flops_bytes(1, 8192, s)
    assert f == full and b == 8192 * 64 * 2 * (40 + 40 + 80)
    assert cp.flash_diff_forward_shapes(1, 8192, s) == [(40, 1, 8192)]
    assert cp.sscan_bytes(1, 8192, s) == \
        8192 * (3 * 5120 + 32) * 2 + 5120 * 16 * 4
    assert cp.sscan_forward_shapes(1, 8192, s) == [(1, 64, 16, 5120)]
    from mxnet_tpu.ops.sscan import DEFAULT_CHUNK
    assert cp.SCAN_CHUNK == DEFAULT_CHUNK


def _counted_run(config):
    return {"e2e": {"train_tokens_per_s": 12000.0}, "tokens": 81920,
            "tokens_per_step": 8192, "n_devices": 1, "config": config,
            "traffic": {"batches": {"batch": 1, "seq": 8192}},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "traced": (3, (0, 1)),
            "trace": {"op_seconds": {
                # sscan_fwd, sscan_bwd
                "custom-call:tpu_custom_call (f32[1,8192,5120], "
                "f32[1,64,16,5120])": 0.06,
                "custom-call:tpu_custom_call (f32[1,8192,5120], "
                "f32[1,8192,5120], f32[1,10,512,16,16], "
                "f32[1,10,512,16,16], f32[1,16,5120])": 0.2,
                # flash forward, dq, dkv
                "custom-call:tpu_custom_call (bf16[40,8192,128], "
                "f32[40,1,8192])": 0.12,
                "custom-call:tpu_custom_call bf16[40,8192,64]": 0.3,
                "custom-call:tpu_custom_call (f32[40,8192,64], "
                "f32[40,8192,128])": 0.3,
                "fusion f32[1,8192,5120]": 1.0}}}


def test_readers_on_a_counted_run():
    from chipbench import run as R
    from chipbench.harness import counts_phi4_flash as cp
    from chipbench.harness.weights_phi4_flash import sizes_of

    with open(CONFIG) as f:
        run = _counted_run(json.load(f))
    s = sizes_of(run["config"])
    read = lambda n: R.load_module(REPO, "layer_metrics", n).read(run)  # noqa: E731,E501
    # 12,000 tokens/s x 4.58 GFLOP over 197 TFLOP/s
    assert read("p4f.mfu_pct") == pytest.approx(27.9, abs=0.15)
    # 3 steps x 2 layers of needed forward calls against the 0.06 s of the
    # ONE kernel row that writes the kept states (the backward's row and
    # the fusion's are not it)
    least = cp.sscan_bytes(1, 8192, s) / 819e9
    assert read("sscan_roofline") == pytest.approx(100 * 6 * least / 0.06)
    assert 1 < read("sscan_roofline") < 50
    # 3 steps x (one windowed + two full) forward calls, compute-bound,
    # against the 0.12 s of the row that writes the logsumexp
    f_full, b_full = cp.flash_diff_flops_bytes(1, 8192, s)
    f_win, b_win = cp.flash_diff_flops_bytes(1, 8192, s, 512)
    assert f_full / 197e12 > b_full / 819e9
    want = 3 * (2 * f_full / 197e12
                + max(f_win / 197e12, b_win / 819e9)) / 0.12
    assert read("flash_diff_roofline") == pytest.approx(100 * want)
    assert 10 < read("flash_diff_roofline") < 100


def test_new_readers_return_nothing_elsewhere():
    """In a cell of another configuration, on a record with nothing in it
    and on a trace with no such kernel, each reader returns None and does
    not raise."""
    from chipbench import run as R

    with open(os.path.join(REPO, "chipbench", "configs",
                           "granite_4h_micro_p10.json")) as f:
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
        if name != "p4f.mfu_pct":
            assert read(no_kernels) is None
            assert read(not_traced) is None
