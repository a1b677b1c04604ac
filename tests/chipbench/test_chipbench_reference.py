"""The plain reference against the program's GPT2Model in float32 at a
tiny size (logits, loss, one gradient, an Adam step), and the control:
both comparisons must FAIL when the system side is computed from
fp8-rounded operands."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CFG = dict(n_layer=2, n_embd=64, n_head=4, vocab_size=128, n_positions=96,
           layer_norm_epsilon=1e-5, initializer_range=0.3,
           program={"name": "gpt2_124m"})
SEED = 2 ** 31 + 7        # more than 32 signed bits hold


@pytest.fixture(scope="module")
def world():
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from chipbench.drivers import gpt2_program as G
    from chipbench.harness.weights import make_weights

    w = make_weights(G.sizes_of(CFG), SEED, "float32")
    net = G.build_net(CFG)
    G.load_weights(net, w, dtype="float32", trainable=True)
    rs = np.random.RandomState(0)
    toks = rs.randint(0, 128, (4, 33)).astype("int32")
    return {"w": w, "net": net, "mx": mx, "jnp": jnp,
            "tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_weights_are_the_seeds():
    from chipbench.drivers import gpt2_program as G
    from chipbench.harness.weights import make_weights

    a = make_weights(G.sizes_of(CFG), SEED, "float32")
    b = make_weights(G.sizes_of(CFG), SEED, "float32")
    c = make_weights(G.sizes_of(CFG), SEED - 2 ** 31, "float32")
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wte"], c["wte"])   # high bits count
    assert a["q_w"].shape == (2, 64, 64) and a["fc1_w"].shape == (2, 256, 64)


def test_logits_match_program(world):
    from chipbench.reference import gpt2_ref as R

    mx, jnp = world["mx"], world["jnp"]
    got = world["net"](mx.nd.array(world["tokens"], dtype="int32")).asnumpy()
    want = np.asarray(R.forward(world["w"], jnp.asarray(world["tokens"]),
                                n_head=4))
    assert np.abs(got - want).max() < 2e-6 * np.abs(want).max() + 2e-6


def test_loss_and_gradient_match_program(world):
    from mxnet_tpu import autograd
    from mxnet_tpu.models import gpt2_lm_loss
    from chipbench.drivers import gpt2_program as G
    from chipbench.reference import gpt2_ref as R

    mx, jnp = world["mx"], world["jnp"]
    net = world["net"]
    with autograd.record():
        loss = gpt2_lm_loss(net(mx.nd.array(world["tokens"], dtype="int32")),
                            mx.nd.array(world["labels"], dtype="int32"))
    loss.backward()
    want, grads = R.loss_and_grads(
        world["w"], jnp.asarray(world["tokens"]),
        jnp.asarray(world["labels"]), rows_per_block=2, n_head=4)
    assert abs(float(loss.asnumpy()) - float(want)) < 1e-5
    p = G.param_map(net)[("fc1_w", 1)]
    got = p.grad().asnumpy()
    ref = np.asarray(grads["fc1_w"][1])
    assert np.abs(got - ref).max() < 1e-5 * np.abs(ref).max() + 1e-8


def test_adam_step_is_mxnets(world):
    """w -= lr sqrt(1-b2^t)/(1-b1^t) m/(sqrt(v)+eps), by hand at t=1."""
    from chipbench.reference import gpt2_ref as R
    jnp = world["jnp"]

    w = {"a": jnp.asarray([1.0, -2.0])}
    g = {"a": jnp.asarray([0.5, -0.25])}
    new, st = R.adam_step(w, g, R.adam_init(w), t=1, lr=0.1)
    m, v = 0.1 * np.array([0.5, -0.25]), 0.001 * np.array([0.25, 0.0625])
    step = 0.1 * (1 - 0.999) ** 0.5 / (1 - 0.9)
    want = np.array([1.0, -2.0]) - step * m / (np.sqrt(v) + 1e-8)
    assert np.allclose(np.asarray(new["a"]), want, rtol=1e-6)
    assert np.allclose(np.asarray(st["m"]["a"]), m, rtol=1e-6)


def test_serving_comparison_fails_on_fp8(world):
    """Greedy tokens of the float32 pass sit at gap 0; the token an
    fp8-operand pass puts first sits measurably below the best."""
    from chipbench.reference import gpt2_ref as R
    jnp = world["jnp"]

    import jax

    fwd = jax.jit(lambda w, t: R.forward(w, t, n_head=4))
    seqs = []
    for row in world["tokens"]:
        buf = np.zeros((1, 32), "int32")       # right padding: causal
        buf[0, :8] = row[:8]
        for n in range(8, 32):
            buf[0, n] = int(np.asarray(fwd(world["w"], jnp.asarray(buf)))
                            [0, n - 1].argmax())
        seqs.append(buf[0].tolist())
    kw = dict(n_head=4, pad_to=32)
    sound = R.teacher_forced_gaps(world["w"], seqs, [8] * 4, **kw)
    control = R.teacher_forced_gaps(world["w"], seqs, [8] * 4,
                                    control="fp8", **kw)
    widest = lambda gaps: max(g["widest"] for g in gaps)
    assert widest(sound) == 0.0 and all(g["tokens"] == 24 for g in sound)
    assert widest(control) > 0.01       # any limit near the sound runs fails
    # a token altered where it is produced is far outside
    seqs[0][20] = (seqs[0][20] + 1) % 128
    assert widest(R.teacher_forced_gaps(world["w"], seqs, [8] * 4,
                                        **kw)) > 0.01


def test_training_comparison_fails_on_fp8():
    from chipbench.drivers import train as T
    from chipbench.generators import token_batches

    cfg = dict(CFG, training={"learning_rate": 1e-3,
                              "reference_rows_per_block": 2})
    traffic = {"batches": {"batch": 4, "seq": 32}}
    ref = T.reference_steps(token_batches, cfg, traffic, SEED)
    low = T.reference_steps(token_batches, cfg, traffic, SEED,
                            precision="fp8")
    limits = {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
              "delta_norm_gap": 1e-3}
    same = T.compare(ref, ref, limits)
    assert all(c["ok"] for c in same)
    failed = [c["what"] for c in T.compare(low, ref, limits) if not c["ok"]]
    assert "first_grad_norm_worst_leaf_gap" in failed
    # a step that leaves its state unchanged: no change of any parameter
    frozen = dict(ref, delta_norms={k: [0.0] * len(v) for k, v in
                                    ref["delta_norms"].items()})
    assert not T.compare(frozen, ref, limits)[2]["ok"]
    # part of the batch left out: the loss moves
    part = dict(ref, losses=[x * 1.01 for x in ref["losses"]])
    assert not T.compare(part, ref, limits)[0]["ok"]
