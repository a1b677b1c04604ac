"""The hybrid Gated DeltaNet / gated attention / sparse expert decoder
against the benchmark's plain reference in float32 on seeded weights
(logits, loss, every gradient leaf, three Adam steps through
``ShardedTrainer``), partial rotary positions, the (1 + w) norm, the
softmax-routed gated experts' shares adding up to the uncut layer, and the
sigmoid / relu2 path computing bit for bit what it computed before it
learned the second form."""
import functools
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
from mxnet_tpu.models import get_qwen3_next, moe  # noqa: E402
from mxnet_tpu.models.moe import read_routing_counters  # noqa: E402
from mxnet_tpu.models.qwen3_next import lm_loss  # noqa: E402
from mxnet_tpu.ops.attention import rotary_embedding  # noqa: E402

B, T = 2, 32
HI = jax.lax.Precision.HIGHEST


@pytest.fixture(scope="module")
def tiny():
    from chipbench.drivers import qwen3_next_program as prog
    from chipbench.harness.weights_qwen3_next import make_weights, sizes_of

    with open(os.path.join(REPO, "tests", "chipbench", "data",
                           "tiny_gdn.json")) as f:
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
    net = get_qwen3_next(num_layers=8, vocab_size=512, vocab_held=64,
                         units=32, num_heads=4, num_kv_heads=2, head_dim=16,
                         linear_key_heads=2, linear_value_heads=4,
                         linear_key_dim=8, linear_value_dim=8, chunk_size=16,
                         num_experts=16, top_k=3, expert_hidden=24,
                         shared_hidden=24, experts_held=(8, 4))
    net.initialize()
    assert net.kinds == ["linear"] * 3 + ["full"] + ["linear"] * 3 + ["full"]
    assert len(net.blocks) == 16               # a mixer and an expert block
    assert net.embed.weight.shape == (64, 32)
    assert net.lm_head.shape == (64, 32)       # untied, the rows held
    layer = net.blocks[1].moe
    assert layer.gate.shape == (16, 32) and layer.w_gate.shape == (4, 32, 24)
    assert not hasattr(layer, "e_score_correction_bias")
    # the published sizes: a layer of each kind, counted from the issue
    full = get_qwen3_next(num_layers=4, vocab_held=8, experts_held=(0, 32))
    count = lambda b: sum(int(onp.prod(p.shape)) for n, p in  # noqa: E731
                          b._collect_params_with_prefix().items()
                          if not n.endswith(("routing_stats", "last_choice")))
    assert count(full.blocks[0]) == 33_718_464 + 2_048
    assert count(full.blocks[6]) == 27_263_488 + 2_048
    assert count(full.blocks[1]) == 104_859_648 + 2_048


def test_logits_loss_and_every_gradient_leaf_match_the_reference(tiny):
    from chipbench.reference import qwen3_next_ref as ref

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


def test_three_adam_steps_match_the_reference_under_recomputation(tiny):
    """Per-block recomputation, one launch a step, the reference following
    the indices the program chose: the driver's own comparison."""
    from chipbench.drivers import train_gdn
    from chipbench.generators import token_batches

    _prog, cfg, _sizes, _weights, _tok, _lab = tiny
    traffic = {"batches": {"batch": B, "seq": T}}
    seed = 2 ** 31 + 7
    job = train_gdn.Job(token_batches, cfg, traffic, seed, jax.devices()[:1])
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
    reference = train_gdn.reference_steps(token_batches, cfg, traffic, seed,
                                          chosen=program["chosen"])
    checks = train_gdn.compare_hybrid(program, reference,
                                      cfg["training"]["limits"])
    assert all(c["ok"] for c in checks), checks
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


def test_pallas_kernels_interpreted_agree_with_the_xla_forms(tiny,
                                                            monkeypatch):
    """The model has no option for it: the ops choose by platform.  Here
    each op is told its form underneath the same model."""
    from mxnet_tpu.ops import gdn, gmm

    _prog, _cfg, _sizes, _weights, tok, _lab = tiny
    net, x = _net(tiny, remat=False), mx.nd.array(tok, dtype="int32")
    rule, product, out, ran = gdn.gdn_scan, gmm.grouped_matmul, {}, []
    for impl in ("xla", "pallas"):
        def told(f, name, impl=impl):
            def call(*a, **kw):
                ran.append((name, impl))
                return f(*a, **dict(kw, impl=impl))
            return call
        monkeypatch.setattr(gdn, "gdn_scan", told(rule, "rule"))
        monkeypatch.setattr(gmm, "grouped_matmul", told(product, "product"))
        out[impl] = net(x).asnumpy()
    assert {("rule", "pallas"), ("product", "pallas"), ("rule", "xla"),
            ("product", "xla")} <= set(ran)
    onp.testing.assert_allclose(out["pallas"], out["xla"], rtol=1e-4,
                                atol=2e-5)


def test_three_amp_steps_counters_and_plans(tiny):
    from mxnet_tpu import amp
    from mxnet_tpu import observability as obs

    _prog, _cfg, sizes, _weights, tok, lab = tiny
    amp.init("bfloat16")
    tr = obs.enable_tracing()
    try:
        net = _net(tiny, remat=True)
        _tr, losses = _steps(net, tok, lab, steps=3)
        rule = tr.spans(name="gdn.plan")
        experts = tr.spans(name="moe.plan")
    finally:
        obs.disable_tracing()
        amp.reset()
    assert all(onp.isfinite(losses)) and losses[2] < losses[0]
    assert len(rule) == 1 and rule[0].attrs["dtype"] == "bfloat16"
    assert rule[0].attrs["chunk"] == 16 and rule[0].attrs["seq"] == T
    # the expert layer's own event beside the grouped products' tile plans
    layer = [e.attrs for e in experts if "form" in e.attrs]
    assert layer == [{"form": "swiglu", "scoring": "softmax",
                      "top_k": sizes["top_k"],
                      "buffer_rows": B * T * sizes["top_k"],
                      "experts_held": sizes["experts_held"],
                      "gather_chunk_rows": B * T * sizes["top_k"]}]
    assert any("tm" in e.attrs for e in experts)
    got = read_routing_counters(net)
    assert got["layers"] == 4 and got["steps"] == 3
    assert got["moe.pairs_total"] == 4 * B * T * sizes["top_k"]
    chosen = net.blocks[1].moe.last_choice.data().asnumpy()
    assert chosen.shape == (B * T, 3) and chosen.dtype == onp.int32
    assert chosen.min() >= 0 and chosen.max() < 16


# ------------------------------------------------------- rotary positions

def test_partial_rotary_turns_the_first_dimensions_and_leaves_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 256))
    y = rotary_embedding(x, theta=1e7, rotary_dim=64)
    assert y.shape == x.shape and y.dtype == x.dtype
    assert onp.array_equal(onp.asarray(y[..., 64:]), onp.asarray(x[..., 64:]))
    # position 0 is the identity; later positions are not
    assert onp.allclose(onp.asarray(y[:, 0]), onp.asarray(x[:, 0]))
    assert not onp.allclose(onp.asarray(y[:, 5, :, :64]),
                            onp.asarray(x[:, 5, :, :64]))
    # a rotation of each pair (i, i + 32) by t theta^(-2 i / 64)
    pair = lambda a, i: onp.asarray(a[..., [i, i + 32]])  # noqa: E731
    for i in (0, 7, 31):
        onp.testing.assert_allclose(
            onp.linalg.norm(pair(y, i), axis=-1),
            onp.linalg.norm(pair(x, i), axis=-1), rtol=1e-5)
        ang = 5 * 1e7 ** (-2.0 * i / 64)
        want = (onp.asarray(x[:, 5, :, i]) * onp.cos(ang)
                - onp.asarray(x[:, 5, :, i + 32]) * onp.sin(ang))
        onp.testing.assert_allclose(onp.asarray(y[:, 5, :, i]), want,
                                    rtol=1e-4, atol=1e-5)
    # scores depend on the distance between positions alone
    q = rotary_embedding(x, jnp.arange(12) + 100, theta=1e4, rotary_dim=64)
    p = rotary_embedding(x, theta=1e4, rotary_dim=64)
    dots = lambda a: jnp.einsum("bqhd,bkhd->bhqk", a, a, precision=HI)  # noqa: E731,E501
    onp.testing.assert_allclose(onp.asarray(dots(q)), onp.asarray(dots(p)),
                                rtol=1e-3, atol=1e-3)
    # all of a head's dimensions by default; the benchmark's reference
    # computes the same
    from chipbench.reference.qwen3_next_ref import rotary
    onp.testing.assert_allclose(
        onp.asarray(rotary_embedding(x, theta=1e7, rotary_dim=64)),
        onp.asarray(rotary(x, 1e7, 64)), rtol=1e-5, atol=1e-5)
    full = rotary_embedding(x[..., :8], theta=100.0)
    assert not onp.allclose(onp.asarray(full[:, 3]),
                            onp.asarray(x[:, 3, :, :8]))
    with pytest.raises(ValueError):
        rotary_embedding(x, rotary_dim=63)


def test_rms_norm_with_a_unit_offset_starts_as_the_plain_norm():
    from mxnet_tpu.gluon import nn

    x = mx.nd.array(onp.random.default_rng(0).normal(size=(3, 5, 8)))
    plain, offset = nn.RMSNorm(in_channels=8), nn.RMSNorm(in_channels=8,
                                                          unit_offset=True)
    plain.initialize()
    offset.initialize()
    assert float(offset.gamma.data().asnumpy().max()) == 0.0
    onp.testing.assert_allclose(offset(x).asnumpy(), plain(x).asnumpy(),
                                rtol=1e-6)
    offset.gamma.set_data(mx.nd.array(onp.full((8,), 0.5)))
    onp.testing.assert_allclose(offset(x).asnumpy(),
                                1.5 * plain(x).asnumpy(), rtol=1e-6)


# ---------------------------------------------------------------- experts

N, D, F, E, K = 96, 32, 24, 16, 3


def _expert_weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    n = lambda i, shape, s: s * jax.random.normal(ks[i], shape)  # noqa: E731
    return {"x": n(0, (N, D), 1.0), "e_router": n(1, (E, D), 0.3),
            "e_gate": n(2, (E, D, F), 0.2), "e_up": n(3, (E, D, F), 0.2),
            "e_down": n(4, (E, F, D), 0.2), "e_sh_gate": n(5, (F, D), 0.2),
            "e_sh_up": n(6, (F, D), 0.2), "e_sh_down": n(7, (D, F), 0.2),
            "e_sh_sig": n(8, (D,), 0.3)}


def test_the_shares_add_up_to_the_uncut_reference_layer():
    """16 experts in 4 shares of 4: the four partial results, the shared
    expert and its gate counted once, equal the uncut REFERENCE layer."""
    from chipbench.reference import qwen3_next_ref as ref

    w = _expert_weights(seed=2)
    x = w["x"]
    sizes = {"top_k": K, "first_expert": 0, "norm_topk": True}
    whole, chosen, _ = ref.expert_layer(x, w, sizes)
    shared = moe.swiglu_mlp(x, w["e_sh_gate"], w["e_sh_up"], w["e_sh_down"]) \
        * jax.nn.sigmoid(jnp.dot(x, w["e_sh_sig"], precision=HI))[:, None]
    parts = []
    for f in (0, 4, 8, 12):
        y, picked, sizes_held = moe.dropless_ffn(
            x, w["e_router"], None, w["e_up"][f:f + 4], w["e_down"][f:f + 4],
            top_k=K, first=f, impl="xla", scoring="softmax",
            w_gate=w["e_gate"][f:f + 4])
        assert onp.array_equal(onp.asarray(picked), onp.asarray(chosen))
        assert int(jnp.sum(sizes_held)) == int(jnp.sum(
            (chosen >= f) & (chosen < f + 4)))
        parts.append(y)
        # each share left out exactly what the others hold
        held = dict(w, **{k: w[k][f:f + 4]
                          for k in ("e_gate", "e_up", "e_down")})
        want, _, _ = ref.expert_layer(x, held, dict(sizes, first_expert=f),
                                      shared=False)
        onp.testing.assert_allclose(onp.asarray(y), onp.asarray(want),
                                    rtol=1e-4, atol=1e-5)
    onp.testing.assert_allclose(onp.asarray(sum(parts) + shared),
                                onp.asarray(whole), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_softmax_gated_layer_equals_the_reference_values_and_gradients(impl):
    from chipbench.reference import qwen3_next_ref as ref

    w = _expert_weights(seed=3)
    x = w.pop("x")
    sizes = {"top_k": K, "first_expert": 4, "norm_topk": True}
    ct = jax.random.normal(jax.random.PRNGKey(5), (N, D))

    def program(x, w):
        return jnp.sum(ct * moe.dropless_ffn(
            x, w["e_router"], None, w["e_up"], w["e_down"], top_k=K,
            first=4, impl=impl, scoring="softmax", w_gate=w["e_gate"])[0])

    def reference(x, w):
        return jnp.sum(ct * ref.expert_layer(x, w, sizes, shared=False)[0])

    held = dict(w, **{k: w[k][4:10] for k in ("e_gate", "e_up", "e_down")})
    got = jax.value_and_grad(program, argnums=(0, 1))(x, held)
    want = jax.value_and_grad(reference, argnums=(0, 1))(x, held)
    assert abs(float(got[0]) - float(want[0])) <= 1e-4 * abs(float(want[0]))
    onp.testing.assert_allclose(onp.asarray(got[1][0]),
                                onp.asarray(want[1][0]), rtol=1e-3, atol=1e-4)
    for k in ("e_router", "e_gate", "e_up", "e_down"):
        onp.testing.assert_allclose(onp.asarray(got[1][1][k]),
                                    onp.asarray(want[1][1][k]), rtol=1e-3,
                                    atol=1e-4, err_msg=k)


# what ``dropless_ffn`` and ``route_sigmoid_topk`` were before the layer
# learned softmax scores and gated experts (commit 7ebb423), kept verbatim
# as the oracle for "the other model's computation did not change"
def _old_route_sigmoid_topk(x, w_router, choice_bias, *, top_k,
                            norm_topk=True, scaling=1.0):
    logits = jnp.einsum("nd,ed->ne", x.astype(jnp.float32),
                        w_router.astype(jnp.float32), precision=HI)
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(
        jax.lax.stop_gradient(s) + choice_bias.astype(jnp.float32)[None, :],
        top_k)
    chosen = chosen.astype(jnp.int32)
    w = jnp.take_along_axis(s, chosen, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scaling, chosen


# its two gathers as they were, written out here so that nothing the program
# does to its own can move the oracle: pairs numbered TOKEN-major
# (``token * top_k + slot``), backward by gathers, the rows past the routed
# ones masked by ``valid`` before anything reads them
def _zero_int(x):
    return onp.zeros(x.shape, jax.dtypes.float0)


@jax.custom_vjp
def _old_permute(x, idx, inv):
    return x[idx]


_old_permute.defvjp(
    lambda x, idx, inv: (x[idx], (idx, inv)),
    lambda res, g: (g[res[1]], _zero_int(res[0]), _zero_int(res[1])))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _old_rows_of_pairs(x, order, inv, valid, top_k):
    return x[order // top_k]


def _old_rows_bwd(top_k, res, g):
    order, inv, valid, shape = res
    g = jnp.where(valid[:, None], g, jnp.zeros_like(g))[inv]
    dx = g.reshape(shape[0], top_k, shape[1]).astype(jnp.float32).sum(axis=1)
    return (dx.astype(g.dtype), _zero_int(order), _zero_int(inv),
            _zero_int(valid))


_old_rows_of_pairs.defvjp(
    lambda x, order, inv, valid, top_k: (x[order // top_k],
                                         (order, inv, valid, x.shape)),
    _old_rows_bwd)


def _old_dropless_ffn(x, w_router, choice_bias, w_up, w_down, *, top_k,
                      first, scaling, compute_dtype, impl):
    from mxnet_tpu.ops.gmm import grouped_matmul
    n, d = x.shape
    held = w_up.shape[0]
    cd = jnp.dtype(compute_dtype or x.dtype)
    w, chosen = _old_route_sigmoid_topk(x, w_router, choice_bias,
                                        top_k=top_k, scaling=scaling)
    local = jnp.logical_and(chosen >= first, chosen < first + held)
    key = jnp.where(local, chosen - first, held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    valid = jnp.arange(n * top_k) < jnp.sum(sizes)
    rows = _old_rows_of_pairs(x.astype(cd), order, inv, valid, top_k)
    u = grouped_matmul(rows, w_up.astype(cd), sizes, impl=impl)
    u = jnp.where(valid[:, None], u, jnp.zeros_like(u)).astype(jnp.float32)
    h = jnp.square(jax.nn.relu(u)).astype(cd)
    y = grouped_matmul(h, w_down.astype(cd), sizes, impl=impl)
    y = jnp.where(valid[:, None], y, jnp.zeros_like(y))
    y = _old_permute(y, inv, order).reshape(n, top_k, d)
    return jnp.sum(w[:, :, None] * y.astype(jnp.float32), axis=1)


@pytest.mark.parametrize("impl,cd", [("xla", None), ("pallas", None),
                                     ("xla", "bfloat16")])
def test_the_sigmoid_relu2_path_is_bit_for_bit_what_it_was(impl, cd):
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    x = jax.random.normal(ks[0], (N, D))
    wr = 0.3 * jax.random.normal(ks[1], (E, D))
    bias = 0.05 * jax.random.normal(ks[2], (E,))
    w_up = 0.2 * jax.random.normal(ks[3], (4, D, F))
    w_down = 0.2 * jax.random.normal(ks[4], (4, F, D))
    ct = jax.random.normal(ks[5], (N, D))
    kw = dict(top_k=K, first=4, scaling=2.5, compute_dtype=cd, impl=impl)
    new = lambda *a: moe.dropless_ffn(*a, **kw)[0]  # noqa: E731
    old = functools.partial(_old_dropless_ffn, **kw)
    args = (x, wr, bias, w_up, w_down)
    assert onp.array_equal(onp.asarray(new(*args)), onp.asarray(old(*args)))
    g_new = jax.grad(lambda *a: jnp.sum(ct * new(*a)), (0, 1, 3, 4))(*args)
    g_old = jax.grad(lambda *a: jnp.sum(ct * old(*a)), (0, 1, 3, 4))(*args)
    # x and the router: the same float32 terms in the same order, to the
    # bit.  The experts' weights: a group's rows now lie slot by slot, so
    # the sum over them adds the same terms in another order (float32
    # rounding against the leaf's largest entry; none in bf16 operands)
    for a, b in zip(g_new[:2], g_old[:2]):
        assert onp.array_equal(onp.asarray(a), onp.asarray(b))
    for a, b in zip(g_new[2:], g_old[2:]):
        a, b = onp.asarray(a), onp.asarray(b)
        onp.testing.assert_allclose(a, b, rtol=1e-6,
                                    atol=1e-6 * onp.abs(b).max())


def test_the_other_models_layer_is_built_as_it_was():
    """``MoELayer(routing="dropless")`` with no word on scoring or form is
    the sigmoid / relu2 layer with its buffer and no gate."""
    layer = moe.MoELayer(D, F, E, top_k=K, routing="dropless",
                         experts_held=(4, 4), shared_hidden=2 * F,
                         routed_scaling=2.5)
    layer.initialize()
    names = set(layer._collect_params_with_prefix())
    assert names == {"gate", "e_score_correction_bias", "w1", "w2",
                     "shared_up", "shared_down", "routing_stats"}
    x = mx.nd.array(onp.random.default_rng(1).normal(size=(2, 8, D)))
    y = layer(x).asnumpy()
    want = _old_dropless_ffn(
        jnp.asarray(x.asnumpy().reshape(-1, D)), layer.gate.data().jax,
        layer.e_score_correction_bias.data().jax, layer.w1.data().jax,
        layer.w2.data().jax, top_k=K, first=4, scaling=2.5,
        compute_dtype=None, impl="xla") + moe.relu2_mlp(
            jnp.asarray(x.asnumpy().reshape(-1, D)),
            layer.shared_up.data().jax, layer.shared_down.data().jax)
    assert onp.array_equal(y.reshape(-1, D), onp.asarray(want))
    with pytest.raises(ValueError, match="scoring"):
        moe.MoELayer(D, F, E, routing="dropless", scoring="tanh")
    with pytest.raises(ValueError, match="expert_form"):
        moe.MoELayer(D, F, E, routing="dropless", expert_form="gelu")
