"""The latent-attention expert decoder against the benchmark's plain
reference in float32 on seeded weights (logits, loss, every gradient leaf,
whole and one chip's share; three Adam steps through ``ShardedTrainer``),
the published shapes without allocating them, the expert shares adding up
to the uncut layer, the two-operand flash call against the plain 192-wide
score, the plans of the calls the other cells make, and the events and
scopes a traced run carries."""
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
from mxnet_tpu.models import get_deepseek_v3  # noqa: E402
from mxnet_tpu.models.deepseek_v3 import lm_loss  # noqa: E402
from mxnet_tpu.models.moe import read_routing_counters  # noqa: E402
from mxnet_tpu.ops import flash  # noqa: E402
from mxnet_tpu.ops.attention import _attention_ref, _use_flash  # noqa: E402

B, T = 2, 32
_OWN = ("routing_stats", "last_choice", "e_score_correction_bias")


def _config(**over):
    with open(os.path.join(REPO, "tests", "chipbench", "data",
                           "tiny_moonlight.json")) as f:
        return dict(json.load(f), **over)


def _case(cfg, seed=5):
    from chipbench.drivers import moonlight_program as prog
    from chipbench.harness.weights_moonlight import make_weights, sizes_of

    sizes = sizes_of(cfg)
    rng = onp.random.default_rng(0)
    tok = rng.integers(0, sizes["vocab"], (B, T)).astype("int32")
    lab = rng.integers(0, sizes["vocab"], (B, T)).astype("int32")
    return prog, cfg, sizes, make_weights(sizes, seed), tok, lab


@pytest.fixture(scope="module")
def tiny():
    """One chip's share: 4 of 16 experts, 64 of 512 rows."""
    return _case(_config())


def _net(case, **kw):
    prog, cfg, _sizes, weights, _tok, _lab = case
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
            for key, p in prog.param_map(net).items()
            if key[0] not in prog.BUFFERS}


def test_the_published_model_by_its_shapes_without_allocating_it():
    net = get_deepseek_v3("moonlight_16b_a3b")
    ps = net._collect_params_with_prefix()
    assert len(net.blocks) == 2 * 27 and net.first_k_dense == 1
    for i in (0, 26):
        at = f"l{i}_mixer.mixer."
        assert ps[at + "q_proj"].shape == (3072, 2048)
        assert ps[at + "kv_a_proj_with_mqa"].shape == (576, 2048)
        assert ps[at + "kv_a_layernorm"].shape == (512,)
        assert ps[at + "kv_b_proj"].shape == (4096, 512)
        assert ps[at + "o_proj"].shape == (2048, 2048)
    assert ps["l0_mlp.mixer.gate_up"].shape == (2 * 11264, 2048)
    assert ps["l0_mlp.mixer.down"].shape == (2048, 11264)
    assert "l0_experts.moe.gate" not in ps and "l1_mlp.mixer.down" not in ps
    for i in (1, 26):
        at = f"l{i}_experts.moe."
        assert ps[at + "gate"].shape == (64, 2048)
        assert ps[at + "e_score_correction_bias"].shape == (64,)
        assert ps[at + "w1"].shape == ps[at + "w_gate"].shape \
            == (64, 2048, 1408)
        assert ps[at + "w2"].shape == (64, 1408, 2048)
        assert ps[at + "shared_up"].shape == (2816, 2048)
        assert ps[at + "shared_gate_proj"].shape == (2816, 2048)
        assert ps[at + "shared_down"].shape == (2048, 2816)
        assert at + "shared_expert_gate" not in ps          # no gate
    assert ps["embed.weight"].shape == ps["lm_head"].shape == (163840, 2048)
    # the cell's share, counted as the issue counts it
    share = get_deepseek_v3(num_layers=6, experts_held=(0, 8),
                            vocab_held=20480)
    count = lambda names: sum(                                # noqa: E731
        int(onp.prod(p.shape))
        for n, p in share._collect_params_with_prefix().items()
        if n.startswith(names) and not n.endswith(_OWN))
    assert count(("l0_mixer",)) == 13_763_072 + 2_048
    assert count(("l0_",)) == 82_973_184
    assert count(("l3_",)) == 100_405_760
    assert count(("embed", "lm_head", "norm_f")) == 83_886_080 + 2_048
    assert count(("",)) == 668_890_112
    with pytest.raises(ValueError):
        get_deepseek_v3(num_layers=2, experts_held=(60, 8))


@pytest.mark.parametrize("held", [4, 16], ids=["one_share", "all_held"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(held):
    from chipbench.reference import moonlight_ref as ref

    case = _case(_config(n_routed_experts=held))
    prog, _cfg, sizes, weights, tok, lab = case
    net = _net(case, remat=False)
    assert [type(b).__name__ for b in net.blocks[1::2]] == [
        "HalfLayer", "ExpertBlock", "ExpertBlock"]          # dense first
    logits = net(mx.nd.array(tok, dtype="int32")).asnumpy()
    want, _used, differ = ref.forward(weights, jnp.asarray(tok), sizes,
                                      rows=16)
    assert [int(d) for d in differ] == [0, 0]
    onp.testing.assert_allclose(logits, onp.asarray(want), rtol=1e-4,
                                atol=2e-5)
    tr, (loss,) = _steps(net, tok, lab)
    ref_loss, grads, _, _ = ref.loss_and_grads(
        weights, jnp.asarray(tok), jnp.asarray(lab), sizes, rows=16)
    assert abs(loss - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    got = _first_grads(tr, prog, net)
    assert {leaf for leaf, _i in got} == set(weights) - set(prog.BUFFERS)
    for (leaf, i), g in got.items():
        r = onp.asarray(grads[leaf] if i is None else grads[leaf][i])
        assert onp.abs(r).max() > 0, (leaf, i)
        assert onp.abs(g - r).max() <= 1e-4 * onp.abs(r).max() + 1e-7, \
            (leaf, i)
    assert not onp.asarray(grads["e_bias"]).any()   # a buffer: no gradient


def test_the_reference_feels_what_the_faults_change(tiny):
    """The parts a planted fault removes from the program move the
    reference too: none is decoration at this size."""
    from chipbench.reference import moonlight_ref as ref

    _prog, _cfg, sizes, weights, tok, _lab = tiny
    base, _, _ = ref.forward(weights, jnp.asarray(tok), sizes, rows=16)
    flat = dict(weights, a_cnorm=jnp.ones_like(weights["a_cnorm"]) * 3.0)
    for w, s in ((weights, dict(sizes, theta=3.0)),
                 (weights, dict(sizes, scaling=1.0)),
                 (weights, dict(sizes, latent_eps=10.0)),
                 (flat, sizes),
                 (dict(weights, e_bias=-weights["e_bias"]), sizes)):
        out, _, _ = ref.forward(w, jnp.asarray(tok), s, rows=16)
        assert float(jnp.max(jnp.abs(out - base))) > 1e-3
    # the score is ONE sum over both widths, the rotary key shared
    hn = jax.random.normal(jax.random.PRNGKey(1), (1, 8, sizes["units"]))
    w0 = {k: v[0] for k, v in weights.items() if k.startswith("a_")}
    a = ref.attention(hn, w0, sizes, rows=4)
    assert a.shape == (1, 8, sizes["units"])
    onp.testing.assert_allclose(a, ref.attention(hn, w0, sizes, rows=8),
                                rtol=1e-5, atol=1e-6)


def test_three_adam_steps_match_the_reference_under_recomputation(tiny):
    """Per-block recomputation, one launch a step, the reference following
    the indices the program chose: the driver's own comparison."""
    from chipbench.drivers import train_moonlight
    from chipbench.generators import token_batches

    _prog, cfg, _sizes, _weights, _tok, _lab = tiny
    traffic = {"batches": {"batch": B, "seq": T}}
    seed = 2 ** 31 + 7
    job = train_moonlight.Job(token_batches, cfg, traffic, seed,
                              jax.devices()[:1])
    try:
        program = {"losses": [], "chosen": []}
        bias0 = job.buffers()
        for t in range(3):
            program["losses"].append(job.step())
            program["chosen"].append(job.choices())
            if t == 0:
                program["grad_norms"] = job.first_grad_norms()
        program["delta_norms"] = job.delta_norms(seed)
        assert job.trainer.stats()["batch_puts"] == 0
        counters = job.counters()
        assert all(onp.array_equal(a, b)
                   for a, b in zip(bias0, job.buffers()))
    finally:
        job.close()
    assert len(bias0) == 2 and bias0[0].shape == (16,) and bias0[0].any()
    reference = train_moonlight.reference_steps(
        token_batches, cfg, traffic, seed, chosen=program["chosen"])
    checks = train_moonlight.compare_hybrid(program, reference,
                                            cfg["training"]["limits"])
    assert all(c["ok"] for c in checks), checks
    assert [c["what"] for c in checks] == [
        "loss_rel_gap_first_steps", "first_grad_norm_worst_leaf_gap",
        "param_change_norm_worst_leaf_gap", "routing_mismatch_share"]
    assert "e_bias" not in program["grad_norms"]
    assert counters["layers"] == 2 and counters["experts_held"] == 4
    assert counters["moe.pairs_total"] == 2 * B * T * 3
    assert 0 < counters["moe.pairs_local"] < counters["moe.pairs_total"]


def test_recomputing_each_half_changes_nothing(tiny):
    prog, _cfg, _sizes, _weights, tok, lab = tiny
    plain, remat = _net(tiny, remat=False), _net(tiny, remat=True)
    tr0, l0 = _steps(plain, tok, lab)
    tr1, l1 = _steps(remat, tok, lab)
    assert abs(l0[0] - l1[0]) <= 1e-6 * abs(l0[0])
    g0, g1 = _first_grads(tr0, prog, plain), _first_grads(tr1, prog, remat)
    for key in g0:
        onp.testing.assert_allclose(g1[key], g0[key], rtol=1e-5, atol=1e-7)
    assert read_routing_counters(remat)["moe.pairs_total"] == 2 * B * T * 3


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """16 experts in 4 shares of 4, one expert layer: the routed parts of
    the four chips' results, with the shared expert and the residual
    (which every chip computes alike) counted once, add up to the uncut
    16-expert REFERENCE layer's output."""
    from chipbench.harness.weights_moonlight import make_weights
    from chipbench.reference import moonlight_ref as ref

    prog, cfg, _sizes, _weights, tok, _lab = tiny
    one = dict(cfg, num_hidden_layers=1, first_k_dense_replace=0,
               n_routed_experts=16)
    s1 = prog.sizes_of(one)
    assert s1["pattern"] == "E"
    whole = make_weights(s1, 11)
    x = whole["embed"][jnp.asarray(tok)]
    u = ref._attention_half(x, ref._layer_weights(whole, "a_", 0), s1, "f32",
                            16)
    we = ref._layer_weights(whole, "e_", 0)
    want, chosen, _ = ref._expert_half(u, we, s1, "f32", None)
    hn = ref._rms(u, we["e_norm"], s1["eps"]).reshape(B * T, -1)
    shared = onp.asarray(ref._swiglu(hn, we["e_sh_gate"], we["e_sh_up"],
                                     we["e_sh_down"], "f32")).reshape(u.shape)
    routed = []
    for first in (0, 4, 8, 12):
        share = dict(one, n_routed_experts=4, first_expert_held=first)
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
        routed.append(out.asnumpy() - onp.asarray(u) - shared)
        # each share left out exactly what the others hold
        alone, _, _ = ref._expert_half(
            u, {k: v[0] for k, v in held.items() if k.startswith("e_")},
            prog.sizes_of(share), "f32", None)
        onp.testing.assert_allclose(out.asnumpy(), onp.asarray(alone),
                                    rtol=1e-4, atol=1e-5)
    assert all(onp.abs(p).max() > 1e-3 for p in routed)
    assert onp.abs(shared).max() > 1e-3
    onp.testing.assert_allclose(onp.asarray(u) + shared + sum(routed),
                                onp.asarray(want), rtol=1e-4, atol=2e-5)


# ------------------------------------------- the two-operand flash call

def _operands(t=256, h=4, d=128, d2=64, dv=128, k2_heads=1, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    shapes = ((h, d), (h, d), (h, dv), (h, d2), (k2_heads, d2), (h, dv))
    return [jax.random.normal(k, (1, t) + s, dtype)
            for k, s in zip(ks, shapes)]


def _plain_192(q, k, v, q2, k2):
    """The concatenated score computed plainly, no second operand."""
    wide = jnp.broadcast_to(k2, q2.shape) if k2.shape[2] == 1 else \
        jnp.repeat(k2, q2.shape[2] // k2.shape[2], axis=2)
    return _attention_ref(jnp.concatenate([q, q2], -1),
                          jnp.concatenate([k, wide], -1), v, causal=True)


@pytest.mark.parametrize("kept", [False, True], ids=["plain", "kept"])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128)])
def test_two_operand_flash_matches_the_plain_192_wide_score(
        block_q, block_k, kept):
    """Forward and all five cotangents, interpreted, at two block sizes;
    ``kept``: inside a checkpoint that keeps the kernel's two residuals,
    as ``run_blocks`` recomputes a half-layer."""
    *ops, ct = _operands()

    def kernel(q, k, v, q2, k2):
        return flash.flash_attention(q, k, v, q2=q2, k2=k2, causal=True,
                                     interpret=True, block_q=block_q,
                                     block_k=block_k)

    if kept:
        kernel = jax.checkpoint(
            kernel, policy=jax.checkpoint_policies.save_only_these_names(
                *flash.KEPT_NAMES))
    out, vjp = jax.vjp(kernel, *ops)
    want, vjp_plain = jax.vjp(_plain_192, *ops)
    by_ref, vjp_ref = jax.vjp(
        lambda q, k, v, q2, k2: _attention_ref(q, k, v, causal=True, q2=q2,
                                               k2=k2), *ops)
    onp.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(by_ref, want, rtol=1e-5, atol=1e-5)
    names = ("dq", "dk", "dv", "dq2", "dk2")
    for name, g, w, r in zip(names, vjp(ct), vjp_plain(ct), vjp_ref(ct)):
        assert g.shape == w.shape == r.shape, name
        scale = float(jnp.abs(w).max())
        assert float(jnp.abs(g - w).max()) <= 2e-5 * scale, name
        assert float(jnp.abs(r - w).max()) <= 2e-5 * scale, name
    if kept:
        # the forward kernel is not run again in the backward pass
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: kernel(*a).sum(), argnums=(0, 1, 2, 3, 4)))(*ops))
        assert text.count("flash_fwd") == 1 and "flash_bwd" in text


def test_two_operand_flash_with_as_many_rotary_keys_as_heads_and_bf16():
    *ops, _ct = _operands(k2_heads=4, dtype=jnp.bfloat16)
    out = flash.flash_attention(*ops[:3], q2=ops[3], k2=ops[4], causal=True,
                                interpret=True)
    want = _plain_192(*ops)
    assert out.dtype == jnp.bfloat16
    onp.testing.assert_allclose(out.astype(jnp.float32),
                                want.astype(jnp.float32), rtol=0, atol=3e-2)


def test_what_the_two_operand_call_refuses_and_the_gate_admits():
    q, k, v, q2, k2, _ = _operands()
    with pytest.raises(ValueError):
        flash.flash_attention(q, k, v, q2=q2, causal=True, interpret=True)
    with pytest.raises(ValueError):
        flash.flash_attention(q, k, v, q2=q2, k2=k2, causal=True, window=64,
                              interpret=True)
    with pytest.raises(ValueError):
        flash.flash_attention(q, k, v, q2=q2, k2=k2[:, :128], causal=True,
                              interpret=True)
    big = (1, 8192, 16, 128)
    two = dict(q2_shape=(1, 8192, 16, 64), k2_shape=(1, 8192, 1, 64))
    assert _use_flash(big, True, None, 0.0, big, platform="tpu", **two)
    assert not _use_flash(big, True, None, 0.0, big, platform="cpu", **two)
    assert not _use_flash(big, True, None, 0.0, big, platform="tpu",
                          q2_shape=(1, 8192, 16, 32),
                          k2_shape=(1, 8192, 1, 32))
    assert not _use_flash(big, True, None, 0.0, big, platform="tpu",
                          q2_shape=two["q2_shape"],
                          k2_shape=(1, 8192, 3, 64))
    assert not _use_flash((1, 8192, 16, 192), True, None, 0.0,
                          platform="tpu")          # 192 in ONE operand


# what tile_plan gave the seven cells' attention calls at the parent
# commit (PR 48): this PR's new operands must not move them.  (PR 50's
# one backward call holds the walked Q and dO whole: ``major_q`` 8,192
# where it was the forward's stretch, ``tiles_run_bwd`` 2,080 where the
# two stretches ran 2,304 / 2,176.)
_FULL_128 = (1024, 256, 512, 128, 4096, 8192, 1, 144, 256, 32, 2080, 4096)
_CELL_PLANS = {
    "gpt2_124m": (dict(tq=1024, d=64, heads=12, kv_heads=12),
                  (1024, 256, 512, 128, 1024, 1024, 1, 3, 4, 2, 36, 64)),
    "nemotron_tt": (dict(tq=8192, d=128, heads=32, kv_heads=2), _FULL_128),
    "qwen3_next": (dict(tq=8192, d=256, heads=16, kv_heads=2),
                   (512, 256, 512, 128, 2048, 8192, 1, 136, 256, 16, 2080,
                    4096)),
    "granite_4h": (dict(tq=8192, d=64, heads=32, kv_heads=8), _FULL_128),
    "phi4_flash_window": (dict(tq=8192, d=64, heads=40, kv_heads=20, dv=128,
                               v_heads=10, window=512),
                          (512, 256, 512, 128, 4096, 8192, 1, 31, 256, 31,
                           310, 4096)),
    "phi4_flash_full": (dict(tq=8192, d=64, heads=40, kv_heads=20, dv=128,
                             v_heads=10), _FULL_128),
    "mellum2_window": (dict(tq=8192, d=128, heads=32, kv_heads=4,
                            window=1024),
                       (1024, 256, 512, 128, 4096, 8192, 1, 45, 256, 30, 540,
                        4096)),
    "mellum2_full": (dict(tq=8192, d=128, heads=32, kv_heads=4), _FULL_128),
    "ouro": (dict(tq=8192, d=128, heads=16, kv_heads=16), _FULL_128),
}


@pytest.mark.parametrize("cell", sorted(_CELL_PLANS))
def test_the_other_cells_plans_and_events_are_what_they_were(cell):
    from mxnet_tpu import observability as obs

    call, want = _CELL_PLANS[cell]
    call = dict(call)
    tq, d = call.pop("tq"), call.pop("d")
    plan = flash.tile_plan(tq, tq, d, jnp.bfloat16, True, **call)
    assert plan[:12] == want and plan.backward == "fused"
    # no second operand: the plan does not know the new words, and a
    # second operand of width 0 is no second operand
    assert plan == flash.tile_plan(tq, tq, d, jnp.bfloat16, True, d2=0,
                                   k2_heads=None, **call)
    tr = obs.enable_tracing()
    try:
        flash._report_plan(plan, tq, tq, d, jnp.bfloat16, True, False,
                           call.get("window"), call.get("dv"))
        (event,) = tr.spans(name="flash.plan")
    finally:
        obs.disable_tracing()
    assert "d2" not in event.attrs and "k2_heads" not in event.attrs
    assert set(event.attrs) - set(plan._asdict()) == {
        "tq", "tk", "d", "dtype", "causal", "has_seg"} | (
        {"window"} if "window" in call else set()) | (
        {"dv"} if call.get("dv", d) != d else set())


def test_a_call_without_a_second_operand_lowers_to_the_kernels_it_had():
    """Two kernels with 3 and 6 operands: nothing of the second pair
    reaches a call that has none."""
    q, k, v, *_ = _operands()
    text = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash.flash_attention(
            q, k, v, causal=True, interpret=True).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    calls = {}

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] = (len(eqn.invars),
                                             len(eqn.outvars))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                visit(sub)

    visit(text.jaxpr)
    assert calls == {"flash_fwd": (3, 2), "flash_bwd": (6, 3)}


def test_the_latent_plan_at_the_published_sizes():
    """16 heads, 128 + 64 over ONE rotary key head, values of 128, T
    8,192: blocks of 1,024 as the plain head of 128 has them, one head a
    grid step, and the 64 more lanes of q and k halve the stretch held."""
    plain = flash.tile_plan(8192, 8192, 128, jnp.bfloat16, True, heads=16,
                            kv_heads=16, dv=128, v_heads=16)
    plan = flash.tile_plan(8192, 8192, 128, jnp.bfloat16, True, heads=16,
                           kv_heads=16, dv=128, v_heads=16, d2=64, k2_heads=1)
    assert (plan.block_q, plan.chunk, plan.slab, plan.slab_bwd, plan.group) \
        == (1024, 256, 512, 128, 1)
    assert (plain.major, plan.major, plan.major_q) == (4096, 2048, 8192)
    assert (plan.backward, plan.dq_bytes) == ("fused", 4 * 8192 * 256)
    assert plan.tiles_run == plain.tiles_run == 144
    assert flash._vmem_bytes(1024, 512, 2048, 128, 2, d2=64) > \
        flash._vmem_bytes(1024, 512, 2048, 128, 2)
    # as many rotary keys as heads at a head count of four: still four
    # heads a step where VMEM allows
    assert flash.tile_plan(1024, 1024, 64, jnp.bfloat16, True, heads=12,
                           kv_heads=12, d2=64, k2_heads=12).group >= 1
    with pytest.raises(ValueError):
        flash.tile_plan(1024, 1024, 128, jnp.bfloat16, True, heads=16,
                        kv_heads=16, d2=64, k2_heads=3)


def test_three_amp_steps_counters_plans_and_scopes(tiny):
    from mxnet_tpu import amp
    from mxnet_tpu import observability as obs

    _prog, _cfg, sizes, _weights, tok, lab = tiny
    amp.init("bfloat16")
    tr = obs.enable_tracing()
    try:
        net = _net(tiny, remat=True)
        _trainer, losses = _steps(net, tok, lab, steps=3)
        latent = [e.attrs for e in tr.spans(name="mla.plan")]
        model = [e.attrs for e in tr.spans(name="deepseek.plan")]
        experts = [e.attrs for e in tr.spans(name="moe.plan")
                   if "form" in e.attrs]
        dense = [e.attrs for e in tr.spans(name="mlp.plan")]
    finally:
        obs.disable_tracing()
        amp.reset()
    assert all(onp.isfinite(losses)) and losses[2] < losses[0]
    # one event a distinct set of attributes, however many layers
    assert latent == [dict(
        heads=4, dn=16, dr=8, dv=16, r=24, score_form="two_products",
        compute_dtype="bfloat16", latent_bytes=B * T * 32 * 2,
        expanded_bytes=B * T * 4 * 32 * 2)]
    assert model == [dict(layers=3, dense_first=1, experts=16,
                          first_expert=0, experts_held=4, vocab_rows=512,
                          vocab_held=64)]
    assert experts == [{"form": "swiglu", "scoring": "sigmoid",
                        "top_k": sizes["top_k"],
                        "buffer_rows": B * T * sizes["top_k"],
                        "experts_held": 4,
                        "gather_chunk_rows": B * T * sizes["top_k"]}]
    assert [d["half"] for d in dense] == [sizes["dense_width"]]
    got = read_routing_counters(net)
    assert got["layers"] == 2 and got["steps"] == 3
    chosen = net.blocks[3].moe.last_choice.data().asnumpy()
    assert chosen.shape == (B * T, 3) and chosen.max() < 16
    # the scopes reach the lowered text's locations (a compiled step's
    # op_name)
    mixer = net.blocks[0].mixer
    hn = jnp.zeros((B, T, sizes["units"]), jnp.bfloat16)
    text = jax.jit(lambda *a: mixer.mix(*a, jnp.bfloat16)).lower(
        hn, *[p.data().jax for p in mixer.params_in_order()]).as_text(
        debug_info=True)
    for scope in ("mla_latent", "mla_expand", "mla_scores"):
        assert scope in text, scope


def test_the_flash_plan_event_of_the_latent_call_carries_the_second_pair():
    from mxnet_tpu import observability as obs

    q, k, v, q2, k2, _ = _operands()
    tr = obs.enable_tracing()
    try:
        flash.flash_attention(q, k, v, q2=q2, k2=k2, causal=True,
                              interpret=True)
        (event,) = tr.spans(name="flash.plan")
    finally:
        obs.disable_tracing()
    assert event.attrs["d2"] == 64 and event.attrs["k2_heads"] == 1
    assert event.attrs["d"] == 128 and "dv" not in event.attrs
