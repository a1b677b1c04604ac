"""The looped decoder (one stack run several times on ONE set of weights,
a head and an exit gate a pass, the exit-weighted objective) against the
benchmark's plain reference in float32 on seeded weights; the shared
weights' gradient as the sum over their uses; the exit distribution; the
gate's bias moved as the reference moves it; the plans, scopes and
counters a traced step carries; and the shell's one-pass path lowering to
the text it lowered to before it learnt passes."""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import observability as obs  # noqa: E402
from mxnet_tpu import parallel as par  # noqa: E402
from mxnet_tpu.models import get_ouro  # noqa: E402
from mxnet_tpu.models import hybrid_common as hc  # noqa: E402
from mxnet_tpu.models.ouro import read_loop_counters  # noqa: E402

B, T = 2, 32
DATA = os.path.join(REPO, "tests", "chipbench", "data")


def _tiny(passes):
    from chipbench.drivers import ouro_program as prog
    from chipbench.harness.weights_ouro import make_weights, sizes_of

    with open(os.path.join(DATA, "tiny_ouro.json")) as f:
        cfg = json.load(f)
    cfg["total_ut_steps"] = passes
    sizes = sizes_of(cfg)
    weights = make_weights(sizes, 5)
    rng = onp.random.default_rng(0)
    tok = rng.integers(0, sizes["vocab"], (B, T)).astype("int32")
    lab = rng.integers(0, sizes["vocab"], (B, T)).astype("int32")
    return prog, cfg, sizes, weights, tok, lab


def _net(tiny, **kw):
    prog, cfg, _sizes, weights, _tok, _lab = tiny
    net = prog.build_net(cfg, **kw)
    prog.load_weights(net, weights)
    return net


def _steps(net, tok, lab, steps=1, lr=1e-3):
    mesh = par.make_mesh(devices=jax.devices()[:1])
    batch = tuple(mx.nd.array(a, dtype="int32") for a in (tok, lab))
    with par.use_mesh(mesh):
        tr = par.ShardedTrainer(net, "adam", loss=None,
                                optimizer_params={"learning_rate": lr},
                                mesh=mesh)
        tr.build(batch)
        losses = [float(tr.step(batch).asnumpy()) for _ in range(steps)]
    return tr, losses


def _first_grads(tr, prog, net):
    """After one Adam step the first moment is (1 - beta1) g."""
    sd = tr.state_dict()
    index = {id(sd[k]): int(k.split(":")[1]) for k in sd
             if k.startswith("param:")}
    return {key: onp.asarray(sd[f"state:{2 * index[id(p.data())]}"].jax) / 0.1
            for key, p in prog.param_map(net).items()}


def _close(got, want, what):
    want = onp.asarray(want)
    assert onp.abs(onp.asarray(got) - want).max() \
        <= 2e-5 * onp.abs(want).max() + 1e-7, what


def test_factory_is_public_and_is_the_shell_with_passes():
    net = get_ouro(num_layers=2, vocab_size=512, vocab_held=128, units=64,
                   num_heads=2, num_kv_heads=2, head_dim=32, mlp_hidden=176)
    assert isinstance(net, hc.HybridDecoder)
    assert type(net).forward is hc.HybridDecoder.forward
    # a layer is two blocks, each sandwich half-layer recomputed alone
    assert net.passes == 4 and len(net.blocks) == 4 and net._layers == 2
    assert all(isinstance(b, hc.HalfLayer) for b in net.blocks)
    names = list(net._collect_params_with_prefix())
    assert names[:4] == ["lm_head", "exit_gate", "exit_bias", "loop_stats"]
    assert [n for n in names if n.startswith("l0_")] == [
        "l0_mixer.norm.gamma", "l0_mixer.mixer.q_proj",
        "l0_mixer.mixer.k_proj", "l0_mixer.mixer.v_proj",
        "l0_mixer.mixer.o_proj", "l0_mixer.post_norm.gamma",
        "l0_mlp.norm.gamma", "l0_mlp.mixer.gate_up", "l0_mlp.mixer.down",
        "l0_mlp.post_norm.gamma"]
    assert net.lm_head.shape == net.embed.weight.shape == (128, 64)
    # the published sizes whole: a layer's parameters, counted from the issue
    full = get_ouro(num_layers=1, vocab_held=8)
    assert sum(int(onp.prod(p.shape)) for b in full.blocks for p in
               b.collect_params().values()) == 51_388_416
    assert full.passes == 4 and full.exit_gate.shape == (2048,)
    # what cannot be run several times says so when it is built
    with pytest.raises(ValueError, match="OwnHead"):
        hc.HybridDecoder([], mx.gluon.nn.RMSNorm,
                         hc.TiedHead("t", lambda *a: None), 8, 4, 1e-6,
                         passes=2, exit_beta=0.05)
    with pytest.raises(ValueError, match="exit_beta"):
        hc.HybridDecoder([], mx.gluon.nn.RMSNorm, hc.OwnHead(), 8, 4, 1e-6,
                         passes=2)


@pytest.mark.parametrize("passes", [1, 3, 4])
def test_every_pass_and_every_gradient_leaf_match_the_reference(passes):
    from chipbench.reference import ouro_ref as ref

    tiny = _tiny(passes)
    prog, _cfg, sizes, weights, tok, lab = tiny
    net = _net(tiny, remat=False)
    want_logits, want_gates = ref.forward(weights, jnp.asarray(tok), sizes,
                                          rows=16)
    out = net(mx.nd.array(tok, dtype="int32"))
    if passes == 1:         # the plain decoder: logits alone, no gate
        _close(out.asnumpy(), want_logits[0], "logits")
    else:
        logits, gates = (a.asnumpy() for a in out)
        assert logits.shape == (passes, B, T, sizes["vocab"])
        _close(logits, want_logits, "logits")
        _close(gates, want_gates, "gates")
        p = onp.asarray(hc.exit_distribution(jnp.asarray(gates)))
        _close(p, ref.exit_distribution(want_gates), "p")
        onp.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    (ref_loss, mass, pass_loss), grads = ref.loss_and_grads(
        weights, jnp.asarray(tok), jnp.asarray(lab), sizes, rows=16)
    eager = net(mx.nd.array(tok, dtype="int32"),
                mx.nd.array(lab, dtype="int32"))
    assert abs(float(eager.asnumpy()) - float(ref_loss)) \
        <= 1e-6 * abs(float(ref_loss))
    for remat in (False, True):         # the scan, and the scan recomputed
        net = _net(tiny, remat=remat)
        tr, (loss,) = _steps(net, tok, lab)
        assert abs(loss - float(ref_loss)) <= 1e-6 * abs(float(ref_loss))
        got = _first_grads(tr, prog, net)
        gate = {"gate_w", "gate_b"} if passes == 1 else set()
        assert {leaf for leaf, _i in got} == set(weights) - gate
        for (leaf, i), g in got.items():
            r = onp.asarray(grads[leaf] if i is None else grads[leaf][i])
            assert onp.abs(r).max() > 0, (leaf, i)
            _close(g, r, (leaf, i, remat))
        if passes > 1:
            read = read_loop_counters(net)
            _close(read["loop.exit_mass"], mass, "exit mass")
            _close(read["loop.pass_loss"], pass_loss, "pass loss")
            assert read["steps"] == 1 and len(read["loop.exit_mass"]) == passes
    if passes == 1:
        for leaf in ("gate_w", "gate_b"):       # one pass has no use for it
            assert float(jnp.abs(grads[leaf]).max()) == 0.0


def test_shared_gradient_is_the_sum_over_untied_copies():
    """The program's gradient of a weight used in four passes is the SUM
    of the four gradients of a model whose passes hold untied copies."""
    from chipbench.reference import ouro_ref as ref

    tiny = _tiny(4)
    prog, _cfg, sizes, weights, tok, lab = tiny
    layer_leaves = [k for k in weights if k not in ref.TOP]

    def untied(copies):
        h = weights["embed"][jnp.asarray(tok)]
        losses, gates = [], []
        for w in copies:
            w = dict(weights, **w)
            h, logits, gate = ref._leave(
                ref._stack(h, w, sizes, "f32", 16, False), w, sizes, "f32")
            losses.append(ref._token_loss(logits, jnp.asarray(lab)))
            gates.append(gate)
        return ref.objective(jnp.stack(losses), jnp.stack(gates),
                             sizes["beta"])[0]

    one = {k: weights[k] for k in layer_leaves}
    per_copy = jax.grad(untied)([one] * 4)
    assert len(per_copy) == 4
    net = _net(tiny, remat=True)
    tr, _ = _steps(net, tok, lab)
    for (leaf, i), g in _first_grads(tr, prog, net).items():
        if leaf not in layer_leaves:
            continue
        uses = [onp.asarray(c[leaf][i]) for c in per_copy]
        assert all(onp.abs(u).max() > 0 for u in uses), (leaf, i)
        _close(g, sum(uses), (leaf, i))
        # and no single use is the whole of it
        assert onp.abs(g - uses[-1]).max() > 1e-3 * onp.abs(g).max()


def test_exit_distribution_sums_to_one_and_one_pass_is_the_plain_loss():
    rng = onp.random.default_rng(1)
    for passes in (1, 2, 4, 7):
        gates = jnp.asarray(rng.uniform(0, 1, (passes, 3, 5)), jnp.float32)
        p = hc.exit_distribution(gates)
        assert p.shape == gates.shape
        onp.testing.assert_allclose(onp.asarray(p.sum(0)), 1.0, atol=1e-6)
        assert float(p.min()) >= 0.0
    onp.testing.assert_allclose(
        onp.asarray(hc.exit_distribution(jnp.full((4, 1), 0.5))[:, 0]),
        [0.5, 0.25, 0.125, 0.125])
    # one pass: p is 1 whatever its gate, the entropy nought, the
    # objective the cross entropy
    ce = jnp.asarray(rng.uniform(1, 5, (1, 3, 5)), jnp.float32)
    obj, p = hc.exit_objective(ce, jnp.full((1, 3, 5), 0.3), 0.05)
    assert onp.array_equal(onp.asarray(obj), onp.asarray(ce[0]))
    assert onp.array_equal(onp.asarray(p), onp.ones((1, 3, 5), "f"))
    # ... and the shell with one pass returns lm_loss of its own logits
    tiny = _tiny(1)
    net = _net(tiny, remat=False)
    tok, lab = (mx.nd.array(a, dtype="int32") for a in tiny[4:])
    assert float(net(tok, lab).asnumpy()) == \
        float(hc.lm_loss(net(tok), lab).asnumpy())
    # per token, the loss the head hands out is lm_loss before its mean
    logits = net(tok)
    assert abs(float(jnp.mean(hc.token_loss(logits.jax, lab.jax)))
               - float(hc.lm_loss(logits, lab).asnumpy())) < 1e-6


def test_gate_bias_moves_as_the_reference_moves_it():
    """The gate's bias is ONE number that Adam moves by its sign: the
    benchmark leaves it out of the change-norm comparison, and this holds
    its update element for element."""
    from chipbench.reference import ouro_ref as ref

    tiny = _tiny(4)
    prog, _cfg, sizes, weights, tok, lab = tiny
    net = _net(tiny, remat=True)
    lr = 1e-3
    _steps(net, tok, lab, steps=3, lr=lr)
    w, state = dict(weights), ref.adam_init(weights)
    for t in (1, 2, 3):
        _, grads = ref.loss_and_grads(w, jnp.asarray(tok), jnp.asarray(lab),
                                      sizes, rows=16)
        w, state = ref.adam_step(w, grads, state, t=t, lr=lr)
    moved = float(net.exit_bias.data().asnumpy()[0])
    want = float(w["gate_b"][0])
    assert abs(want) > 0.5 * lr          # it did move, by about the rate
    assert abs(moved - want) <= 1e-3 * abs(want)


def test_traced_step_carries_plans_scopes_and_counters(monkeypatch):
    from mxnet_tpu import base
    from mxnet_tpu.ops import flash

    # the dispatcher's TPU branch, its kernels interpreted
    monkeypatch.setattr(base, "resolve_exec_platform", lambda x=None: "tpu")
    monkeypatch.setattr(flash, "_default_interpret", lambda x: True)
    tracer = obs.enable_tracing()
    try:
        net = get_ouro(num_layers=2, vocab_size=64, units=128, num_heads=2,
                       num_kv_heads=2, head_dim=64, mlp_hidden=96,
                       total_ut_steps=3, remat=True)
        net.initialize()
        tok = onp.zeros((1, 256), "int32")
        tr, (loss,) = _steps(net, tok, tok)
        plans = [s.attrs for s in tracer.spans(name="loop.plan")]
    finally:
        obs.disable_tracing()
    assert onp.isfinite(loss)
    last = plans[-1]
    assert (last["passes"], last["layers"], last["applications"]) == (3, 2, 6)
    assert last["blocks"] == 4          # two recomputed blocks a layer
    assert last["runs_as"] == "scan" and last["remat"] is True
    # a pass keeps, for each of its two layers, flash's output and
    # logsumexp (float32 here): the scan stacks three of these
    assert last["kept_bytes_a_pass"] == 2 * (2 * 256 * 64 * 4 + 2 * 256 * 4)
    batch = tuple(mx.nd.array(tok, dtype="int32") for _ in range(2))
    text = tr.lower_step(batch).as_text(debug_info=True)
    for scope in ("fwd", "pass", "exit_loss", "optimizer"):
        assert re.search(rf'"[^"]*\b{scope}\b[^"]*"', text), scope
    # ONE scan over the passes: the stack is in the program once
    assert text.count("stablehlo.while") >= 2           # forward, backward
    read = read_loop_counters(net)
    assert read["steps"] == 1 and len(read["loop.exit_mass"]) == 3
    assert abs(sum(read["loop.exit_mass"]) - 1.0) < 1e-5
    # the running sum, of one step so far
    assert read["loop.exit_mass_sum"] == read["loop.exit_mass"]


# ---- the one-pass path is the program it was ------------------------------
def _old_half_forward(self, x, mask=None):
    """``HalfLayer.forward`` as PR 45 left it."""
    mixer, eps, unit_offset = self.mixer, self._eps, self._unit_offset

    def body(xv, gain, *ws, cd):
        return xv + mixer.mix(hc.rms(xv, gain, eps, unit_offset), *ws,
                              cd).astype(xv.dtype)

    return hc.fused(self._op, body, x,
                    [self.norm.gamma] + mixer.params_in_order())


def _old_shell_forward(self, tokens):
    """``HybridDecoder.forward`` as PR 45 left it."""
    x = self.embed(tokens)
    if self._emb is not None:
        x = x * self._emb
    x = hc._par.with_sharding_constraint(x, "batch", None, None)
    x = hc.run_blocks(self.blocks, x, scan=False, remat=self._remat)
    return self._head(self, x)


def _old_own_head(self, net, x):
    """``OwnHead.__call__`` as PR 45 left it."""
    logits = hc.F.FullyConnected(net.norm_f(x), net.lm_head.data(), None,
                                 num_hidden=net.vocab_held, no_bias=True,
                                 flatten=False)
    return hc._par.with_sharding_constraint(logits, "batch", None, "vocab")


FAMILIES = {
    "hybrid": ("hybrid_program", "weights_hybrid", "nemotron_h"),
    "gdn": ("qwen3_next_program", "weights_qwen3_next", "qwen3_next"),
    "g4h": ("g4h_program", "weights_granite_hybrid", "granite_hybrid"),
    "p4f": ("p4f_program", "weights_phi4_flash", "phi4_flash"),
    "mellum2": ("mellum2_program", "weights_mellum2", "mellum"),
}


def _lowered_step(family):
    """The tiny twin's training step, lowered, without locations."""
    import importlib

    program, weights, model = FAMILIES[family]
    prog = importlib.import_module("chipbench.drivers." + program)
    wmod = importlib.import_module("chipbench.harness." + weights)
    lm_loss = importlib.import_module("mxnet_tpu.models." + model).lm_loss
    with open(os.path.join(DATA, f"tiny_{family}.json")) as f:
        cfg = json.load(f)
    sizes = wmod.sizes_of(cfg)
    net = prog.build_net(cfg, remat=True)
    w = wmod.make_weights(sizes, 3, "float32")
    if family == "p4f":
        prog.load_weights(net, w, sizes["pattern"])
    else:
        prog.load_weights(net, w)
    tok = mx.nd.array(onp.zeros((2, 32), "int32"), dtype="int32")
    mesh = par.make_mesh(devices=jax.devices()[:1])
    with par.use_mesh(mesh):
        tr = par.ShardedTrainer(net, "adam", loss=lm_loss,
                                optimizer_params={"learning_rate": 1e-3},
                                mesh=mesh)
        text = tr.lower_step(tok, tok).as_text(debug_info=False)
    return re.sub(r"loc\([^)]*\)", "", text)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_one_pass_and_no_post_norm_lower_to_the_text_they_did(family,
                                                              monkeypatch):
    """PR 45's method: the five families' tiny twins' lowered step text on
    the CPU, here against the same tree with the shell's ``forward``, the
    half-layer's and the own head's as they stood before they learnt
    ``passes`` and ``post_norm``."""
    now = _lowered_step(family)
    monkeypatch.setattr(hc.HalfLayer, "forward", _old_half_forward)
    monkeypatch.setattr(hc.HybridDecoder, "forward", _old_shell_forward)
    monkeypatch.setattr(hc.OwnHead, "__call__", _old_own_head)
    before = _lowered_step(family)
    assert len(now) > 10_000
    assert now == before
