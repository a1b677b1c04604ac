"""The sliding-window / full attention expert decoder against the
benchmark's plain reference in float32 on seeded weights (logits, loss,
every gradient leaf, three Adam steps through ``ShardedTrainer``), the
window and the rotary table by layer kind, the expert shares adding up to
the uncut layer with attention and router counted once, and the plans and
counters a traced run carries."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import parallel as par  # noqa: E402
from mxnet_tpu.models import get_mellum  # noqa: E402
from mxnet_tpu.models.mellum import lm_loss  # noqa: E402
from mxnet_tpu.models.moe import read_routing_counters  # noqa: E402

B, T = 2, 32


@pytest.fixture(scope="module")
def tiny():
    from chipbench.drivers import mellum2_program as prog
    from chipbench.harness.weights_mellum2 import make_weights, sizes_of

    with open(os.path.join(REPO, "tests", "chipbench", "data",
                           "tiny_mellum2.json")) as f:
        cfg = json.load(f)
    sizes = sizes_of(cfg)
    weights = make_weights(sizes, 5)
    rng = onp.random.default_rng(0)
    tok = rng.integers(0, sizes["vocab"], (B, T)).astype("int32")
    lab = rng.integers(0, sizes["vocab"], (B, T)).astype("int32")
    return prog, cfg, sizes, weights, tok, lab


def _net(tiny, **kw):
    prog, cfg, _sizes, weights, _tok, _lab = tiny
    net = prog.build_net(cfg, record_choice_rows=B * T, **kw)
    prog.load_weights(net, weights)
    return net


def _steps(net, tok, lab, steps=1):
    mesh = par.make_mesh(devices=jax.devices()[:1])
    data, labels = (mx.nd.array(a, dtype="int32") for a in (tok, lab))
    with par.use_mesh(mesh):
        tr = par.ShardedTrainer(net, "adam", loss=lm_loss,
                                optimizer_params={"learning_rate": 1e-3},
                                mesh=mesh)
        tr.build(data, labels)
        losses = [float(tr.step(data, labels).asnumpy())
                  for _ in range(steps)]
    return tr, losses


def _first_grads(tr, prog, net):
    """After one Adam step the first moment is (1 - beta1) g."""
    sd = tr.state_dict()
    index = {id(sd[k]): int(k.split(":")[1]) for k in sd
             if k.startswith("param:")}
    return {key: onp.asarray(sd[f"state:{2 * index[id(p.data())]}"].jax) / 0.1
            for key, p in prog.param_map(net).items()}


def test_factory_is_public_and_holds_what_it_is_told():
    net = get_mellum(num_layers=8, vocab_size=512, vocab_held=64, units=32,
                     num_heads=8, num_kv_heads=1, head_dim=16,
                     sliding_window=8, num_experts=16, top_k=3,
                     expert_hidden=24, experts_held=(8, 4))
    net.initialize()
    assert net.kinds == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert len(net.blocks) == 16          # an attention and an expert block
    assert [b.mixer._window for b in net.blocks[::2]] == [8, 8, 8, None] * 2
    assert net.blocks[0].mixer._rope["rope_type"] == "default"
    assert net.blocks[6].mixer._rope["factor"] == 16
    assert net.embed.weight.shape == (64, 32)
    assert net.lm_head.shape == (64, 32)       # untied, the rows held
    layer = net.blocks[1].moe
    assert layer.gate.shape == (16, 32) and layer.w_gate.shape == (4, 32, 24)
    assert layer.shared_up is None             # no shared expert
    assert not hasattr(layer, "e_score_correction_bias")
    with pytest.raises(ValueError):
        get_mellum(num_layers=1, layer_types=("linear_attention",))
    # the published sizes: a layer's halves, counted from the issue
    full = get_mellum(num_layers=4, vocab_held=8, experts_held=(0, 16))
    count = lambda b: sum(int(onp.prod(p.shape)) for n, p in  # noqa: E731
                          b._collect_params_with_prefix().items()
                          if not n.endswith(("routing_stats", "last_choice")))
    assert count(full.blocks[0]) == count(full.blocks[6]) \
        == 21_233_664 + 256 + 2_304
    assert count(full.blocks[1]) == 147_456 + 16 * 6_193_152 + 2_304
    assert full.blocks[4].mixer._window == 1024
    assert full.blocks[6].mixer._window is None


def test_logits_loss_and_every_gradient_leaf_match_the_reference(tiny):
    from chipbench.reference import mellum2_ref as ref

    prog, _cfg, sizes, weights, tok, lab = tiny
    net = _net(tiny, remat=False)
    logits = net(mx.nd.array(tok, dtype="int32")).asnumpy()
    want, _used, differ = ref.forward(weights, jnp.asarray(tok), sizes,
                                      rows=16)
    assert [int(d) for d in differ] == [0, 0, 0, 0]
    onp.testing.assert_allclose(logits, onp.asarray(want), rtol=1e-4,
                                atol=2e-5)
    tr, (loss,) = _steps(net, tok, lab)
    ref_loss, grads, _, _ = ref.loss_and_grads(
        weights, jnp.asarray(tok), jnp.asarray(lab), sizes, rows=16)
    assert abs(loss - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    got = _first_grads(tr, prog, net)
    assert {leaf for leaf, _i in got} == set(weights)
    for (leaf, i), g in got.items():
        r = onp.asarray(grads[leaf] if i is None else grads[leaf][i])
        assert onp.abs(r).max() > 0, (leaf, i)
        assert onp.abs(g - r).max() <= 1e-4 * onp.abs(r).max() + 1e-7, \
            (leaf, i)


def test_the_reference_feels_the_window_and_the_table(tiny):
    """What the planted faults change in the program moves the reference
    too: its masks and its tables are not decoration at this size."""
    from chipbench.reference import mellum2_ref as ref

    _prog, _cfg, sizes, weights, tok, _lab = tiny
    base, _, _ = ref.forward(weights, jnp.asarray(tok), sizes, rows=16)
    yarn = dict(sizes["rope_F"])
    for other in (dict(sizes, window=7), dict(sizes, window=T),
                  dict(sizes, rope_F=sizes["rope_S"]),
                  dict(sizes, rope_F=tuple(sorted(
                      dict(yarn, attention_factor=1.0).items())))):
        out, _, _ = ref.forward(weights, jnp.asarray(tok), other, rows=16)
        assert float(jnp.max(jnp.abs(out - base))) > 1e-3
    mask = onp.asarray(ref.attention_mask(jnp.arange(4, 8), 8, window=3))
    assert mask.tolist() == [[0, 0, 1, 1, 1, 0, 0, 0], [0, 0, 0, 1, 1, 1, 0, 0],
                             [0, 0, 0, 0, 1, 1, 1, 0], [0, 0, 0, 0, 0, 1, 1, 1]]
    assert onp.asarray(ref.attention_mask(jnp.arange(2), 3)).tolist() == \
        [[1, 0, 0], [1, 1, 0]]


def test_three_adam_steps_match_the_reference_under_recomputation(tiny):
    """Per-block recomputation, one launch a step, the reference following
    the indices the program chose: the driver's own comparison."""
    from chipbench.drivers import train_mellum2
    from chipbench.generators import token_batches

    _prog, cfg, _sizes, _weights, _tok, _lab = tiny
    traffic = {"batches": {"batch": B, "seq": T}}
    seed = 2 ** 31 + 7
    job = train_mellum2.Job(token_batches, cfg, traffic, seed,
                            jax.devices()[:1])
    try:
        program = {"losses": [], "chosen": []}
        for t in range(3):
            program["losses"].append(job.step())
            program["chosen"].append(job.choices())
            if t == 0:
                program["grad_norms"] = job.first_grad_norms()
        program["delta_norms"] = job.delta_norms(seed)
        assert job.trainer.stats()["batch_puts"] == 0
        counters = job.counters()
    finally:
        job.close()
    reference = train_mellum2.reference_steps(token_batches, cfg, traffic,
                                              seed, chosen=program["chosen"])
    checks = train_mellum2.compare_hybrid(program, reference,
                                          cfg["training"]["limits"])
    assert all(c["ok"] for c in checks), checks
    assert [c["what"] for c in checks] == [
        "loss_rel_gap_first_steps", "first_grad_norm_worst_leaf_gap",
        "param_change_norm_worst_leaf_gap", "routing_mismatch_share"]
    assert counters["layers"] == 4 and counters["experts_held"] == 4
    assert counters["moe.pairs_total"] == 4 * B * T * 3
    assert 0 < counters["moe.pairs_local"] < counters["moe.pairs_total"]


def test_recomputing_each_block_changes_nothing(tiny):
    prog, _cfg, _sizes, _weights, tok, lab = tiny
    plain, remat = _net(tiny, remat=False), _net(tiny, remat=True)
    tr0, l0 = _steps(plain, tok, lab)
    tr1, l1 = _steps(remat, tok, lab)
    assert abs(l0[0] - l1[0]) <= 1e-6 * abs(l0[0])
    g0, g1 = _first_grads(tr0, prog, plain), _first_grads(tr1, prog, remat)
    for key in g0:
        onp.testing.assert_allclose(g1[key], g0[key], rtol=1e-5, atol=1e-7)
    assert read_routing_counters(remat)["moe.pairs_total"] == 4 * B * T * 3


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """16 experts in 4 shares of 4, one decoder layer: the four chips'
    streams after the layer, the attention half and the router (which
    every chip computes alike) counted once, add up to the uncut 16-expert
    REFERENCE layer's output."""
    from chipbench.harness.weights_mellum2 import make_weights
    from chipbench.reference import mellum2_ref as ref

    prog, cfg, sizes, _weights, tok, _lab = tiny
    one = dict(cfg, num_hidden_layers=1, layer_types=["sliding_attention"],
               mlp_layer_types=["sparse"], num_experts=16)
    whole = make_weights(prog.sizes_of(one), 11)
    x = whole["embed"][jnp.asarray(tok)]
    s1 = prog.sizes_of(one)
    u = ref._attention(x, ref._layer_weights(whole, "a_", 0), s1, "f32", 16,
                       "S")
    want, chosen, _ = ref._experts(u, ref._layer_weights(whole, "e_", 0), s1,
                                   "f32", None)
    parts = []
    for first in (0, 4, 8, 12):
        share = dict(one, num_experts=4, first_expert_held=first)
        net = prog.build_net(share, remat=False, record_choice_rows=B * T)
        held = dict(whole, **{k: whole[k][:, first:first + 4]
                              for k in ("e_gate", "e_up", "e_down")})
        prog.load_weights(net, held)
        attended = net.blocks[0](mx.nd.array(onp.asarray(x)))
        onp.testing.assert_allclose(attended.asnumpy(), onp.asarray(u),
                                    rtol=1e-4, atol=1e-5)
        with mx.autograd.record(train_mode=True):      # payloads move
            out = net.blocks[1](attended)
        picked = net.blocks[1].moe.last_choice.data().asnumpy()
        assert onp.array_equal(picked, onp.asarray(chosen))
        parts.append(out.asnumpy() - onp.asarray(u))
        # each share left out exactly what the others hold
        alone, _, _ = ref._experts(
            u, {k: v[0] for k, v in held.items() if k.startswith("e_")},
            prog.sizes_of(share), "f32", None)
        onp.testing.assert_allclose(out.asnumpy(), onp.asarray(alone),
                                    rtol=1e-4, atol=1e-5)
    assert all(onp.abs(p).max() > 1e-3 for p in parts)
    onp.testing.assert_allclose(onp.asarray(u) + sum(parts),
                                onp.asarray(want), rtol=1e-4, atol=2e-5)


def test_three_amp_steps_counters_and_plans(tiny):
    from mxnet_tpu import amp
    from mxnet_tpu import observability as obs

    _prog, _cfg, sizes, _weights, tok, lab = tiny
    amp.init("bfloat16")
    tr = obs.enable_tracing()
    try:
        net = _net(tiny, remat=True)
        _tr, losses = _steps(net, tok, lab, steps=3)
        tables = [e.attrs for e in tr.spans(name="rope.plan")]
        experts = tr.spans(name="moe.plan")
    finally:
        obs.disable_tracing()
        amp.reset()
    assert all(onp.isfinite(losses)) and losses[2] < losses[0]
    # one event a distinct table, however many layers and passes use it
    assert sorted(t["kind"] for t in tables) == ["default", "yarn"]
    yarn = [t for t in tables if t["kind"] == "yarn"][0]
    assert (yarn["low"], yarn["high"], yarn["dim"]) == (1, 5, 16)
    assert yarn["factor"] == 4.0 and yarn["theta"] == 100.0
    assert yarn["amplitude"] == pytest.approx(0.1 * onp.log(4) + 1)
    layer = [e.attrs for e in experts if "form" in e.attrs]
    assert layer == [{"form": "swiglu", "scoring": "softmax",
                      "top_k": sizes["top_k"],
                      "buffer_rows": B * T * sizes["top_k"],
                      "experts_held": sizes["experts_held"],
                      "gather_chunk_rows": B * T * sizes["top_k"]}]
    got = read_routing_counters(net)
    assert got["layers"] == 4 and got["steps"] == 3
    assert got["moe.pairs_total"] == 4 * B * T * sizes["top_k"]
    chosen = net.blocks[1].moe.last_choice.data().asnumpy()
    assert chosen.shape == (B * T, 3) and chosen.dtype == onp.int32
    assert chosen.min() >= 0 and chosen.max() < 16
