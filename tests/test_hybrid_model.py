"""The hybrid Mamba-2 / expert / attention decoder against the benchmark's
plain reference in float32 on seeded weights, per-layer recomputation, and
three AMP steps through ``ShardedTrainer``."""
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
from mxnet_tpu.models import get_nemotron_h  # noqa: E402
from mxnet_tpu.models.moe import read_routing_counters  # noqa: E402
from mxnet_tpu.models.nemotron_h import lm_loss  # noqa: E402

B, T = 2, 32


@pytest.fixture(scope="module")
def tiny():
    from chipbench.drivers import hybrid_program as prog
    from chipbench.harness.weights_hybrid import make_weights, sizes_of

    with open(os.path.join(REPO, "tests", "chipbench", "data",
                           "tiny_hybrid.json")) as f:
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
    net.initialize()
    prog.load_weights(net, weights)
    return net


def _one_step(net, tok, lab, steps=1):
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
            for key, p in prog.param_map(net).items()
            if key[0] not in prog.BUFFERS}


def test_factory_is_public_and_holds_what_it_is_told():
    net = get_nemotron_h(pattern="ME*", vocab_size=512, vocab_held=64,
                         units=32, num_heads=4, num_kv_heads=2, head_dim=8,
                         mamba_heads=4, mamba_head_dim=8, mamba_groups=2,
                         state_size=16, chunk_size=16, num_experts=16,
                         top_k=3, expert_hidden=24, shared_hidden=48,
                         experts_held=(8, 4))
    net.initialize()
    assert [b.kind for b in net.blocks] == ["M", "E", "*"]
    assert net.embed.weight.shape == (64, 32)
    assert net.lm_head.shape == (64, 32)          # untied, the rows held
    moe = net.blocks[1].mixer
    assert moe.gate.shape == (16, 32) and moe.w1.shape == (4, 32, 24)
    with pytest.raises(ValueError):
        get_nemotron_h(pattern="MX", vocab_size=8, units=8)


def test_logits_loss_and_every_gradient_leaf_match_the_reference(tiny):
    from chipbench.reference import nemotron_h_ref as ref

    prog, _cfg, sizes, weights, tok, lab = tiny
    net = _net(tiny, remat=False)
    logits = net(mx.nd.array(tok, dtype="int32")).asnumpy()
    want, _used, differ = ref.forward(weights, jnp.asarray(tok), sizes,
                                      rows=16)
    assert [int(d) for d in differ] == [0, 0]
    onp.testing.assert_allclose(logits, onp.asarray(want), rtol=1e-4,
                                atol=2e-5)
    tr, (loss,) = _one_step(net, tok, lab)
    ref_loss, grads, _, _ = ref.loss_and_grads(
        weights, jnp.asarray(tok), jnp.asarray(lab), sizes, rows=16)
    assert abs(loss - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    for (leaf, i), g in _first_grads(tr, prog, net).items():
        r = onp.asarray(grads[leaf] if i is None else grads[leaf][i])
        assert onp.abs(g - r).max() <= 1e-4 * onp.abs(r).max() + 1e-7, \
            (leaf, i)


def test_recomputing_each_layer_changes_nothing(tiny):
    prog, _cfg, _sizes, _weights, tok, lab = tiny
    plain, remat = _net(tiny, remat=False), _net(tiny, remat=True)
    tr0, l0 = _one_step(plain, tok, lab)
    tr1, l1 = _one_step(remat, tok, lab)
    assert abs(l0[0] - l1[0]) <= 1e-6 * abs(l0[0])
    g0, g1 = _first_grads(tr0, prog, plain), _first_grads(tr1, prog, remat)
    for key in g0:
        onp.testing.assert_allclose(g1[key], g0[key], rtol=1e-5, atol=1e-7)
    # the payloads a layer rebinds leave the checkpointed layer as outputs
    assert read_routing_counters(remat)["moe.pairs_total"] == \
        2 * B * T * 3


def test_pallas_kernels_interpreted_agree_with_the_xla_forms(tiny,
                                                            monkeypatch):
    """The model has no option for it: the ops choose by platform.  Here
    each op is told its form underneath the same model."""
    from mxnet_tpu.ops import gmm, ssd

    _prog, _cfg, _sizes, _weights, tok, _lab = tiny
    net, x = _net(tiny, remat=False), mx.nd.array(tok, dtype="int32")
    scan, product, out, ran = ssd.ssd_scan, gmm.grouped_matmul, {}, []
    for impl in ("xla", "pallas"):
        def told(f, name, impl=impl):
            def call(*a, **kw):
                ran.append((name, impl))
                return f(*a, **dict(kw, impl=impl))
            return call
        monkeypatch.setattr(ssd, "ssd_scan", told(scan, "scan"))
        monkeypatch.setattr(gmm, "grouped_matmul", told(product, "product"))
        out[impl] = net(x).asnumpy()
    assert {("scan", "pallas"), ("product", "pallas"), ("scan", "xla"),
            ("product", "xla")} <= set(ran)
    onp.testing.assert_allclose(out["pallas"], out["xla"], rtol=1e-4,
                                atol=2e-5)


def test_three_amp_steps_buffer_unchanged_counters_read(tiny):
    from mxnet_tpu import amp

    _prog, _cfg, sizes, _weights, tok, lab = tiny
    amp.init("bfloat16")
    try:
        net = _net(tiny, remat=True)
        layers = [b.mixer for b in net.blocks if b.kind == "E"]
        before = [m.e_score_correction_bias.data().asnumpy().copy()
                  for m in layers]
        _tr, losses = _one_step(net, tok, lab, steps=3)
    finally:
        amp.reset()
    assert all(onp.isfinite(losses)) and losses[2] < losses[0]
    for m, b in zip(layers, before):       # routes, is not trained
        assert onp.array_equal(m.e_score_correction_bias.data().asnumpy(), b)
    got = read_routing_counters(net)
    assert got["layers"] == 2 and got["experts_held"] == 4
    assert got["moe.pairs_total"] == 2 * B * T * sizes["top_k"]
    assert 0 < got["moe.pairs_local"] < got["moe.pairs_total"]
    assert got["steps"] == 3               # training steps only
    chosen = layers[0].last_choice.data().asnumpy()
    assert chosen.shape == (B * T, 3) and chosen.dtype == onp.int32
    assert chosen.min() >= 0 and chosen.max() < 16
