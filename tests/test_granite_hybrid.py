"""The Granite hybrid stack (``models/granite_hybrid.py``) through
``ShardedTrainer`` against the plain reference
(``chipbench/reference/granite_hybrid_ref.py``) on seeded weights at a tiny
size: loss, gradients leaf by leaf, three Adam steps.  Then six twins, each
with one piece of the mathematics dropped underneath, every one of which
has to come out different."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.drivers import g4h_program as prog  # noqa: E402
from chipbench.drivers import train_g4h  # noqa: E402
from chipbench.drivers.train import compare  # noqa: E402
from chipbench.generators import token_batches  # noqa: E402
from chipbench.harness.weights_granite_hybrid import make_weights  # noqa: E402
from chipbench.reference import granite_hybrid_ref as ref  # noqa: E402

SEED = 2 ** 31 + 19
STEPS = 3
LIMITS = {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
          "delta_norm_gap": 2e-2}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "tests", "chipbench", "data",
                           "tiny_g4h.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    return {"batches": {"batch": 2, "seq": 32}}


@pytest.fixture(scope="module")
def reference(config, traffic):
    return train_g4h.reference_steps(token_batches, config, traffic, SEED)


def _program(config, traffic):
    job = train_g4h.Job(token_batches, config, traffic, SEED,
                        jax.devices()[:1])
    try:
        out = {"losses": []}
        for t in range(STEPS):
            out["losses"].append(job.step())
            if t == 0:
                out["grad_norms"] = job.first_grad_norms()
                sd = job.trainer.state_dict()
                index = {id(sd[k]): int(k.split(":")[1]) for k in sd
                         if k.startswith("param:")}
                out["grads"] = {
                    key: onp.asarray(
                        sd[f"state:{2 * index[id(p.data())]}"].jax) / 0.1
                    for key, p in prog.param_map(job.net).items()}
        out["delta_norms"] = job.delta_norms(SEED)
        out["weights"] = {key: onp.asarray(p.data().jax)
                          for key, p in prog.param_map(job.net).items()}
        out["layer_types"] = job.net.layer_types
    finally:
        job.close()
    return out


def test_program_agrees_with_the_reference(config, traffic, reference):
    got = _program(config, traffic)
    checks = compare(got, reference, LIMITS)
    assert all(c["ok"] for c in checks), checks
    assert got["layer_types"] == ("mamba", "mamba", "attention", "mamba")
    # gradients and the weights after three Adam steps, element by element
    sizes = prog.sizes_of(config)
    w = make_weights(sizes, SEED)
    batches = token_batches.generate(traffic, SEED, sizes["vocab"])
    state = ref.adam_init(w)
    for t in range(1, STEPS + 1):
        tokens, labels = next(batches)
        _loss, grads = ref.loss_and_grads(w, jnp.asarray(tokens),
                                          jnp.asarray(labels), sizes, rows=16)
        if t == 1:
            for (leaf, i), g in got["grads"].items():
                want = onp.asarray(grads[leaf] if i is None
                                   else grads[leaf][i])
                onp.testing.assert_allclose(
                    g, want, rtol=2e-3, atol=2e-4 * onp.abs(want).max(),
                    err_msg=f"{leaf}[{i}]")
        w, state = ref.adam_step(w, grads, state, t=t, lr=1e-3)
    for (leaf, i), a in got["weights"].items():
        want = onp.asarray(w[leaf] if i is None else w[leaf][i])
        onp.testing.assert_allclose(a, want, rtol=0, atol=2e-5,
                                    err_msg=f"{leaf}[{i}]")


def _gate_after_norm(self, hn, in_w, conv_w, conv_b, dt_b, a_log, d_skip,
                     norm_w, out_w, cd):
    """``Mamba2Mixer.mix`` with ``silu(z)`` applied AFTER the norm."""
    from mxnet_tpu.models.hybrid_common import dense
    from mxnet_tpu.ops.ssd import causal_conv1d, ssd_scan
    b, t, _u = hn.shape
    h, p, g, n = self._h, self._p, self._g, self._n
    f32 = jnp.float32
    proj = dense(hn, in_w, cd)
    z = proj[..., :self._d_inner]
    xbc = proj[..., self._d_inner:self._d_inner + self._conv_dim]
    dt = proj[..., self._d_inner + self._conv_dim:]
    xbc = jax.nn.silu(causal_conv1d(xbc, conv_w.astype(f32),
                                    conv_b.astype(f32)))
    x = xbc[..., :self._d_inner].reshape(b, t, h, p)
    bm = xbc[..., self._d_inner:self._d_inner + g * n]
    cm = xbc[..., self._d_inner + g * n:]
    dt = jax.nn.softplus(dt + dt_b.astype(f32))
    a = -jnp.exp(a_log.astype(f32))
    y = ssd_scan(x.astype(cd), dt, a, bm.reshape(b, t, g, n).astype(cd),
                 cm.reshape(b, t, g, n).astype(cd), chunk=min(self._chunk, t))
    y = (y + d_skip.astype(f32)[:, None] * x).reshape(b, t, self._d_inner)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + self._eps) * norm_w.astype(f32)
    return dense(y * jax.nn.silu(z), out_w, cd)


@pytest.mark.parametrize("dropped", [
    "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling", "gate_before_norm", "conv_bias"])
def test_twin_with_one_piece_dropped_disagrees(config, traffic, reference,
                                               monkeypatch, dropped):
    real = prog.build_net

    def build(cfg, **kw):
        net = real(cfg, **kw)
        if dropped == "embedding_multiplier":
            net._emb = 1.0
        elif dropped == "logits_scaling":
            net._logits = 1.0
        for blk in net.blocks:
            if dropped == "residual_multiplier":
                blk._r = 1.0
            elif dropped == "attention_multiplier" \
                    and blk.kind == "attention":
                blk.mixer._scale = None         # head_dim ** -0.5
        return net

    monkeypatch.setattr(prog, "build_net", build)
    if dropped == "gate_before_norm":
        from mxnet_tpu.models.nemotron_h import Mamba2Mixer
        monkeypatch.setattr(Mamba2Mixer, "mix", _gate_after_norm)
    elif dropped == "conv_bias":
        from mxnet_tpu.ops import ssd
        conv = ssd.causal_conv1d
        monkeypatch.setattr(ssd, "causal_conv1d",
                            lambda x, w, bias: conv(x, w, None))
    checks = compare(_program(config, traffic), reference, LIMITS)
    assert not all(c["ok"] for c in checks), checks


def test_gate_after_norm_twin_is_the_programs_mix_otherwise(config):
    """The twin above differs from ``Mamba2Mixer.mix`` by the gate's place
    alone: where the gate is one (z = 1.2785 everywhere, whose silu is 1)
    the two agree."""
    from mxnet_tpu.models.nemotron_h import Mamba2Mixer

    s = prog.sizes_of(config)
    m = Mamba2Mixer(s["units"], s["m_heads"], s["m_head_dim"], s["groups"],
                    s["state"], conv_kernel=s["conv"], chunk_size=s["chunk"],
                    eps=s["eps"])
    w = {k: v[0] for k, v in make_weights(s, SEED).items()
         if k.startswith("m_")}
    d_inner = s["m_heads"] * s["m_head_dim"]
    hn = jnp.ones((1, 16, s["units"]), jnp.float32)
    in_w = w["m_in_proj"].at[:d_inner].set(1.2784645 / s["units"])
    args = [in_w, w["m_conv_w"], w["m_conv_b"], w["m_dt_bias"],
            w["m_A_log"], w["m_D"], w["m_norm_w"], w["m_out_proj"],
            jnp.float32]
    want = m.mix(hn, *args)
    assert float(jnp.abs(want).max()) > 1e-3
    onp.testing.assert_allclose(onp.asarray(_gate_after_norm(m, hn, *args)),
                                onp.asarray(want), rtol=1e-4, atol=1e-5)


def test_attention_scale_default_is_unchanged():
    """``GroupedQueryAttention`` without ``scale`` computes what it did:
    the scores times ``head_dim ** -0.5``."""
    from mxnet_tpu.models.nemotron_h import GroupedQueryAttention

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    u, h, hk, d = 32, 4, 2, 8
    hn = jax.random.normal(ks[0], (2, 16, u))
    ws = [0.3 * jax.random.normal(k, shape) for k, shape in zip(
        ks[1:], [(h * d, u), (hk * d, u), (hk * d, u), (u, h * d)])]
    plain = GroupedQueryAttention(u, h, hk, d)
    stated = GroupedQueryAttention(u, h, hk, d, scale=d ** -0.5)
    other = GroupedQueryAttention(u, h, hk, d, scale=1 / 64)
    a = plain.mix(hn, *ws, jnp.float32)
    onp.testing.assert_array_equal(onp.asarray(a),
                                   onp.asarray(stated.mix(hn, *ws,
                                                          jnp.float32)))
    assert float(jnp.abs(a - other.mix(hn, *ws, jnp.float32)).max()) > 1e-3


def test_granite_plan_event():
    from mxnet_tpu import observability as obs
    from mxnet_tpu.models import get_granite_hybrid

    kw = dict(layer_types=("mamba", "attention"), vocab_size=256,
              vocab_held=32, units=32, num_heads=4, num_kv_heads=2,
              head_dim=8, mamba_heads=4, mamba_head_dim=16, state_size=8,
              chunk_size=16, mlp_hidden=48)
    obs.disable_tracing()
    get_granite_hybrid(**kw)                 # says nothing while off
    tr = obs.enable_tracing()
    try:
        assert not tr.spans(name="granite.plan")
        get_granite_hybrid(**kw)
        get_granite_hybrid(**kw)
        events = tr.spans(name="granite.plan")
    finally:
        obs.disable_tracing()
    assert len(events) == 1
    assert events[0].attrs == {
        "mamba_layers": 1, "attention_layers": 1,
        "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
        "attention_multiplier": 0.015625, "logits_scaling": 8.0,
        "vocab_held": 32, "tied": True}


def test_named_scopes_in_the_compiled_step(config, traffic):
    """``mixer`` and ``mlp`` show in the step's text (off the TPU the
    scan is its XLA form, which has no ``ssd_chunk_bwd`` of its own)."""
    job = train_g4h.Job(token_batches, config, traffic, SEED,
                        jax.devices()[:1])
    try:
        data, labels = next(job.feed)
        text = job.trainer.lower_step(data, labels).as_text(
            debug_info=True)
    finally:
        job.close()
    for scope in ("mixer", "mlp"):
        assert scope in text, scope
