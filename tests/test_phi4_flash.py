"""The SambaY decoder-hybrid-decoder stack (``models/phi4_flash.py``)
against the benchmark's plain reference (``chipbench/reference/
phi4_flash_ref.py``, written apart: the scan by its recurrence, two
explicit softmaxes a differential head) on seeded weights at a tiny size:
loss and every leaf's gradient, with and without per-layer recomputation;
the memory and the keys and values carried beside the stream receive
their readers' cotangents; every layer index of the published 32 gets
the right kind; the mixers one at a time."""
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
from mxnet_tpu import autograd  # noqa: E402
from mxnet_tpu.models import get_phi4_flash  # noqa: E402
from mxnet_tpu.models import phi4_flash as model  # noqa: E402

SEED = 2 ** 31 + 5
TINY = os.path.join(REPO, "tests", "chipbench", "data", "tiny_p4f.json")


@pytest.fixture(scope="module")
def tiny():
    from chipbench.harness.weights_phi4_flash import make_weights, sizes_of

    with open(TINY) as f:
        config = json.load(f)
    s = sizes_of(config)
    return config, s, make_weights(s, SEED)


def _batch(s, seed=0, shape=(2, 32)):
    rng = onp.random.default_rng(seed)
    return (rng.integers(0, s["vocab"], shape).astype("int32"),
            rng.integers(0, s["vocab"], shape).astype("int32"))


def _program_grads(config, s, w, tok, lab, remat):
    from chipbench.drivers import p4f_program as prog

    net = prog.build_net(config, remat=remat)
    prog.load_weights(net, w, s["pattern"])
    for p in net.collect_params().values():
        p.grad_req = "write"
    with autograd.record():
        loss = model.lm_loss(net(mx.nd.array(tok, dtype="int32")),
                             mx.nd.array(lab, dtype="int32"))
    loss.backward()
    grads = {}
    for (leaf, i), p in prog.param_map(net, s["pattern"]).items():
        grads[(leaf, i)] = onp.asarray(p.grad().asnumpy())
    return float(loss.asnumpy()), grads


def _reference_grads(s, w, tok, lab):
    from chipbench.reference import phi4_flash_ref as ref

    loss, g = ref.loss_and_grads(w, jnp.asarray(tok), jnp.asarray(lab), s,
                                 rows=16)
    return float(loss), {k: onp.asarray(v) for k, v in g.items()}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_every_leaf_gradient_against_the_reference(tiny, remat):
    config, s, w = tiny
    assert s["pattern"] == "MSWFGC"
    tok, lab = _batch(s)
    loss, grads = _program_grads(config, s, w, tok, lab, remat)
    want_loss, want = _reference_grads(s, w, tok, lab)
    assert loss == pytest.approx(want_loss, rel=2e-6)
    assert {leaf for leaf, _i in grads} == set(want)
    for (leaf, i), g in grads.items():
        r = want[leaf] if i is None else want[leaf][i]
        assert g.shape == r.shape, leaf
        onp.testing.assert_allclose(g, r, rtol=2e-3,
                                    atol=2e-4 * float(onp.abs(r).max()),
                                    err_msg=f"{leaf}[{i}]")


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_carried_values_receive_their_readers_cotangents(tiny, remat):
    """With the memory layer's ``out_proj`` zero its scan reaches the loss
    ONLY as the memory the Gated Memory Unit reads, and with the full
    layer's ``o_proj`` and bias zero its keys and values reach it ONLY
    through the cross-attention layer: the gradients of what makes them
    are then the cotangents that came back beside the stream, and they
    are the reference's."""
    config, s, w = tiny
    w = dict(w)
    w["m_out_proj"] = w["m_out_proj"].at[1].set(0.0)      # layer W
    w["a_o"] = w["a_o"].at[1].set(0.0)                    # layer F
    w["a_o_b"] = w["a_o_b"].at[1].set(0.0)
    tok, lab = _batch(s, 3)
    _loss, grads = _program_grads(config, s, w, tok, lab, remat)
    _want_loss, want = _reference_grads(s, w, tok, lab)
    for leaf in ("m_x_proj", "m_A_log", "m_dt_bias", "m_D", "m_conv_w"):
        g, r = grads[(leaf, 1)], want[leaf][1]
        assert onp.abs(r).max() > 0, leaf
        onp.testing.assert_allclose(g, r, rtol=2e-3,
                                    atol=2e-4 * float(onp.abs(r).max()))
    hq = s["heads"] * s["head_dim"]
    g, r = grads[("a_qkv", 1)], want["a_qkv"][1]
    assert onp.abs(g[:hq]).max() == 0.0          # its own queries: cut off
    assert onp.abs(g[hq:]).max() > 0.0           # its keys and values: not
    onp.testing.assert_allclose(g, r, rtol=2e-3,
                                atol=2e-4 * float(onp.abs(r).max()))


def test_every_layer_of_the_published_stack_gets_its_kind():
    kinds = model.layer_kinds(32)
    assert len(kinds) == 32
    for i, kind in enumerate(kinds):
        want = (("mamba" if i <= 14 else "mamba_mem" if i == 16 else "gmu")
                if i % 2 == 0 else
                ("swa" if i <= 15 else "full" if i == 17 else "cross"))
        assert kind == want, i
    assert [kinds.count(k) for k in ("mamba", "swa", "mamba_mem", "full",
                                     "gmu", "cross")] == [8, 8, 1, 1, 7, 7]
    with pytest.raises(ValueError):
        model.layer_kinds(30)
    net = get_phi4_flash(
        num_layers=32, vocab_size=64, units=32, num_heads=8, num_kv_heads=4,
        head_dim=4, window=8, mlp_hidden=48, d_inner=64, state_size=4,
        conv_kernel=4, dt_rank=2)
    assert net.kinds == kinds and net.layers == tuple(range(32))
    for i, (blk, kind) in enumerate(zip(net.blocks, kinds)):
        assert (blk.kind, blk.layer) == (kind, i)
        if kind in ("mamba", "mamba_mem"):
            assert isinstance(blk.mixer, model.Mamba1Mixer)
        elif kind == "gmu":
            assert isinstance(blk.mixer, model.GatedMemoryUnit)
        else:
            assert isinstance(blk.mixer, model.DifferentialAttention)
            assert blk.mixer._window == (8 if kind == "swa" else None)
            assert blk.mixer._cross == (kind == "cross")
            assert blk.mixer._lambda_init == pytest.approx(
                0.8 - 0.6 * onp.exp(-0.3 * i))
        assert blk.side_out == {"mamba_mem": ("memory",),
                                "full": ("keys", "values")}.get(kind, ())
        assert blk.side_in == {"gmu": ("memory",),
                               "cross": ("keys", "values")}.get(kind, ())
    net.initialize(mx.init.Xavier())
    tok = mx.nd.array(_batch({"vocab": 64}, shape=(1, 16))[0], dtype="int32")
    assert net(tok).shape == (1, 16, 64)


def test_published_sizes_and_a_stage_that_lacks_its_emitter():
    cfg = model._CONFIGS["phi4_mini_flash_reasoning"]
    assert cfg["num_heads"] * cfg["head_dim"] == cfg["units"] == 2560
    assert cfg["d_inner"] == 2 * cfg["units"] and cfg["dt_rank"] == 160
    net = get_phi4_flash(layers=(14, 15, 16, 17, 18, 19), vocab_held=25008)
    assert net.kinds == ("mamba", "swa", "mamba_mem", "full", "gmu", "cross")
    shapes = {k: p.shape for k, p in
              net._collect_params_with_prefix().items()}
    assert shapes["embed.weight"] == (25008, 2560)
    assert shapes["l14.mixer.A_log"] == (5120, 16)
    assert shapes["l15.mixer.qkv_proj"] == (5120, 2560)
    assert shapes["l19.mixer.qkv_proj"] == (2560, 2560)
    assert shapes["l18.mixer.in_proj"] == (5120, 2560)
    assert sum(int(onp.prod(s)) for s in shapes.values()) == 697_094_272
    with pytest.raises(ValueError, match="memory"):
        get_phi4_flash(layers=(17, 18, 19))
    with pytest.raises(ValueError, match="keys"):
        get_phi4_flash(layers=(16, 18, 19))


def test_mixers_against_the_reference_one_at_a_time(tiny):
    from chipbench.reference import phi4_flash_ref as ref

    _config, s, w = tiny
    f32 = jnp.float32
    hn = jnp.asarray(onp.random.default_rng(0).standard_normal(
        (2, 32, s["units"])), f32)
    mamba = model.Mamba1Mixer(s["units"], s["d_inner"], s["state"],
                              s["conv"], s["dt_rank"])
    wm = {k: v[0] for k, v in w.items() if k.startswith("m_")}
    got = mamba.mix(hn, *(wm[k] for k in (
        "m_in_proj", "m_conv_w", "m_conv_b", "m_x_proj", "m_dt_proj",
        "m_dt_bias", "m_A_log", "m_D", "m_out_proj")), f32)
    for g, r in zip(got, ref.mamba(hn, wm, s)):
        onp.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-4)
    names = ("qkv", "qkv_b", "o", "o_b", "lambdas", "subln")
    wa = {k: v[0] for k, v in w.items() if k.startswith("a_")}
    for layer, window in ((3, s["window"]), (5, None)):
        attn = model.DifferentialAttention(
            s["units"], s["heads"], s["kv_heads"], s["head_dim"], layer,
            window=window)
        got = attn.mix(hn, *(wa[f"a_{k}"] for k in names), f32)
        want = ref.attention(hn, wa, s, layer, window, rows=16)
        for g, r in zip(got, want):
            onp.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-4)
    keys, values = want[1:]
    wc = {k: v[0] for k, v in w.items() if k.startswith("c_")}
    cross = model.DifferentialAttention(
        s["units"], s["heads"], s["kv_heads"], s["head_dim"], 7, cross=True)
    got = cross.mix(hn, *(wc[f"c_{k}"] for k in names), f32,
                    kv=(keys, values))[0]
    onp.testing.assert_allclose(
        got, ref.attention(hn, wc, s, 7, None, rows=16, kv=(keys, values),
                           pre="c")[0], rtol=2e-4, atol=2e-4)
    memory = jnp.asarray(onp.random.default_rng(1).standard_normal(
        (2, 32, s["d_inner"])), f32)
    wg = {k: v[0] for k, v in w.items() if k.startswith("g_")}
    unit = model.GatedMemoryUnit(s["units"], s["d_inner"])
    onp.testing.assert_allclose(
        unit.mix(hn, wg["g_in"], wg["g_out"], f32, memory=memory),
        ref.gmu(hn, memory, wg), rtol=2e-4, atol=2e-4)


def test_reference_scan_is_the_programs_recurrence():
    """Two recurrences written apart (the reference's in segments under
    ``jax.checkpoint``, the program's in one ``lax.scan``) agree, values
    and gradients."""
    from chipbench.reference import phi4_flash_ref as ref
    from mxnet_tpu.ops.sscan import sscan_recurrence

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    args = (jax.random.normal(ks[0], (2, 48, 24)),
            jax.nn.softplus(jax.random.normal(ks[1], (2, 48, 24)) - 2.0),
            -jnp.exp(jax.random.normal(ks[2], (24, 4))),
            jax.random.normal(ks[3], (2, 48, 4)),
            jax.random.normal(ks[4], (2, 48, 4)))
    scan = lambda *o: ref.scan(*o, segment=16)  # noqa: E731
    onp.testing.assert_allclose(scan(*args), sscan_recurrence(*args),
                                rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *o: jnp.sum(jnp.square(scan(*o))),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *o: jnp.sum(jnp.square(sscan_recurrence(*o))),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for g, r in zip(got, want):
        onp.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)


def test_plan_event_and_named_scopes(tiny):
    """``phi4flash.plan`` with a ``Tracer`` on; the lowered mixers' op
    names carry ``diff_attn`` and ``gmu``."""
    from mxnet_tpu import observability as obs

    _config, s, _w = tiny
    tr = obs.enable_tracing()
    try:
        get_phi4_flash(layers=(14, 15, 16, 17, 18, 19), vocab_held=25008)
        ev, = tr.spans(name="phi4flash.plan")
    finally:
        obs.disable_tracing()
    assert ev.attrs["layers"] == (14, 15, 16, 17, 18, 19)
    assert ev.attrs["window"] == 512 and ev.attrs["tied"] is True
    f32 = jnp.float32
    hn = jnp.zeros((1, 16, s["units"]), f32)
    attn = model.DifferentialAttention(
        s["units"], s["heads"], s["kv_heads"], s["head_dim"], 3, window=8)
    shapes = [p.shape for p in attn.params_in_order()]
    text = jax.jit(lambda x, *ws: attn.mix(x, *ws, f32)[0]).lower(
        hn, *(jnp.zeros(sh, f32) for sh in shapes)).as_text(debug_info=True)
    assert "diff_attn" in text
    unit = model.GatedMemoryUnit(s["units"], s["d_inner"])
    text = jax.jit(lambda x, m, a, b: unit.mix(x, a, b, f32, memory=m)).lower(
        hn, jnp.zeros((1, 16, s["d_inner"]), f32),
        jnp.zeros((s["d_inner"], s["units"]), f32),
        jnp.zeros((s["units"], s["d_inner"]), f32)).as_text(debug_info=True)
    assert "gmu" in text
