"""The seam PR 45 cut: the five hybrid families are ONE decoder shell
(``models.hybrid_common.HybridDecoder``) handed their blocks, their norm
and their head, and a sixth family costs its mixer and its sizes."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import models, parallel as par  # noqa: E402
from mxnet_tpu.gluon.block import HybridBlock  # noqa: E402
from mxnet_tpu.gluon.nn import RMSNorm  # noqa: E402
from mxnet_tpu.models import hybrid_common as hc  # noqa: E402

# each factory at the tiny sizes its own test file builds it at
TINY = {
    "get_nemotron_h": dict(
        pattern="ME*", vocab_size=512, vocab_held=64, units=32, num_heads=4,
        num_kv_heads=2, head_dim=8, mamba_heads=4, mamba_head_dim=8,
        mamba_groups=2, state_size=16, chunk_size=16, num_experts=16,
        top_k=3, expert_hidden=24, shared_hidden=48, experts_held=(8, 4)),
    "get_qwen3_next": dict(
        num_layers=8, vocab_size=512, vocab_held=64, units=32, num_heads=4,
        num_kv_heads=2, head_dim=16, linear_key_heads=2,
        linear_value_heads=4, linear_key_dim=8, linear_value_dim=8,
        chunk_size=16, num_experts=16, top_k=3, expert_hidden=24,
        shared_hidden=24, experts_held=(8, 4)),
    "get_granite_hybrid": dict(
        layer_types=("mamba", "attention"), vocab_size=256, vocab_held=32,
        units=32, num_heads=4, num_kv_heads=2, head_dim=8, mamba_heads=4,
        mamba_head_dim=16, state_size=8, chunk_size=16, mlp_hidden=48),
    "get_phi4_flash": dict(
        num_layers=32, vocab_size=64, units=32, num_heads=8, num_kv_heads=4,
        head_dim=4, window=8, mlp_hidden=48, d_inner=64, state_size=4,
        conv_kernel=4, dt_rank=2),
    "get_mellum": dict(
        num_layers=8, vocab_size=512, vocab_held=64, units=32, num_heads=8,
        num_kv_heads=1, head_dim=16, sliding_window=8, num_experts=16,
        top_k=3, expert_hidden=24, experts_held=(8, 4)),
    "get_deepseek_v3": dict(
        num_layers=3, vocab_size=512, vocab_held=64, units=32, num_heads=4,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, kv_lora_rank=24,
        mlp_hidden=48, num_experts=16, top_k=3, expert_hidden=24,
        shared_hidden=48, experts_held=(8, 4)),
}


@pytest.mark.parametrize("factory", sorted(TINY))
def test_family_is_the_one_shell_and_has_no_forward_of_its_own(factory):
    net = getattr(models, factory)(**TINY[factory])
    assert isinstance(net, hc.HybridDecoder)
    assert type(net).forward is hc.HybridDecoder.forward
    assert net.vocab_held == TINY[factory].get("vocab_held",
                                                TINY[factory]["vocab_size"])
    assert net.embed.weight.shape == (net.vocab_held, 32)
    names = list(net._children)
    assert names[0] == "embed" and names[-1] == "norm_f"
    assert len(names) == len(net.blocks) + 2


def test_one_expert_half_serves_the_three_expert_families():
    from mxnet_tpu.models import mellum, nemotron_h, qwen3_next

    assert qwen3_next.ExpertBlock is mellum.ExpertBlock is hc.ExpertBlock
    net = models.get_nemotron_h(**TINY["get_nemotron_h"])
    assert isinstance(net.blocks[1], hc.ExpertBlock)
    assert isinstance(net.blocks[0], nemotron_h.HybridLayer)


def test_a_second_half_is_made_by_layer():
    """``two_halves`` hands the second half's factory the layer's index:
    one stack may hold a dense half in some layers and an expert half in
    the others, each under its own name; the callers that give every
    layer the same half take the index and ignore it."""
    cfg = dict(units=32, eps=1e-5, expert_hidden=24, num_experts=8, top_k=2,
               norm_topk=True)
    made = list(hc.two_halves(
        range(3), lambda _: hc.HalfLayer("toy_layer", cfg, RunningMean(32)),
        lambda i: hc.HalfLayer("toy_mlp", cfg, hc.GatedMLP(32, 48))
        if i == 0 else hc.ExpertBlock(cfg, scoring="softmax",
                                      expert_form="swiglu"),
        second=lambda i: "mlp" if i == 0 else "experts"))
    assert [n for n, _ in made] == ["l0_mixer", "l0_mlp", "l1_mixer",
                                    "l1_experts", "l2_mixer", "l2_experts"]
    assert [type(b).__name__ for _, b in made[1::2]] == [
        "HalfLayer", "ExpertBlock", "ExpertBlock"]
    same = list(hc.two_halves("ab", str.upper, lambda i: i * 10,
                              second="mlp"))
    assert same == [("l0_mixer", "A"), ("l0_mlp", 0), ("l1_mixer", "B"),
                    ("l1_mlp", 10)]
    net = models.get_deepseek_v3(**TINY["get_deepseek_v3"])
    assert list(net._children)[1:7] == ["l0_mixer", "l0_mlp", "l1_mixer",
                                        "l1_experts", "l2_mixer",
                                        "l2_experts"]
    from mxnet_tpu.models import ouro
    assert ouro.GatedMLP is hc.GatedMLP          # two families' one block


# ---- a sixth family, whole: a mixer, its half-layer, its sizes ----------
class RunningMean(HybridBlock):
    """``(mean of the tokens so far) W^T``: causal, one weight."""

    def __init__(self, units):
        super().__init__()
        self.w = self.params.get("w", shape=(units, units), init="xavier")

    def mix(self, hn, w, cd):
        steps = jnp.arange(1, hn.shape[1] + 1, dtype=hn.dtype)[:, None]
        return hc.dense(jnp.cumsum(hn, 1) / steps, w, cd)

    def params_in_order(self):
        return [self.w]


class ToyModel(hc.HybridDecoder):
    def __init__(self, num_layers, vocab_size, units, eps, remat=False,
                 post_norm=False, passes=1):
        cfg = dict(units=units, eps=eps)
        super().__init__(
            ((f"l{i}", hc.HalfLayer("toy_layer", cfg, RunningMean(units),
                                    post_norm=post_norm))
             for i in range(num_layers)),
            RMSNorm, hc.OwnHead(), vocab_size, units, eps, remat=remat,
            passes=passes, exit_beta=0.05)


TOY = dict(num_layers=3, vocab_size=32, units=16, eps=1e-5)


def _toy_batch():
    rng = onp.random.default_rng(0)
    tok = rng.integers(0, TOY["vocab_size"], (2, 16)).astype("int32")
    return tuple(mx.nd.array(a, dtype="int32")
                 for a in (tok, onp.roll(tok, -1, 1)))


def test_a_sixth_family_is_its_mixer_and_its_sizes_and_trains():
    net = ToyModel(remat=True, **TOY)
    net.initialize(mx.init.Xavier())
    assert list(net._collect_params_with_prefix()) == [
        "lm_head", "embed.weight", "l0.norm.gamma", "l0.mixer.w",
        "l1.norm.gamma", "l1.mixer.w", "l2.norm.gamma", "l2.mixer.w",
        "norm_f.gamma"]
    data, labels = _toy_batch()
    mesh = par.make_mesh(devices=jax.devices()[:1])
    with par.use_mesh(mesh):
        tr = par.ShardedTrainer(net, "adam", loss=hc.lm_loss,
                                optimizer_params={"learning_rate": 1e-2},
                                mesh=mesh)
        losses = [float(tr.step(data, labels).asnumpy()) for _ in range(2)]
    assert all(onp.isfinite(losses)) and losses[1] < losses[0], losses


def test_the_sixth_family_sandwiched_and_run_twice_trains():
    """The shell's two newer words: a norm on the mixer's OUTPUT, and the
    same blocks run ``passes`` times with a gate a pass; the net then
    takes the labels and returns its objective."""
    net = ToyModel(remat=True, post_norm=True, passes=2, **TOY)
    net.initialize(mx.init.Xavier())
    assert list(net._collect_params_with_prefix()) == [
        "lm_head", "exit_gate", "exit_bias", "loop_stats", "embed.weight",
        "l0.norm.gamma", "l0.mixer.w", "l0.post_norm.gamma",
        "l1.norm.gamma", "l1.mixer.w", "l1.post_norm.gamma",
        "l2.norm.gamma", "l2.mixer.w", "l2.post_norm.gamma",
        "norm_f.gamma"]
    data, labels = _toy_batch()
    logits, gates = net(data)
    assert logits.shape == (2, 2, 16, TOY["vocab_size"])
    assert gates.shape == (2, 2, 16)
    assert onp.allclose(gates.asnumpy(), 0.5)       # a gate starts at zero
    mesh = par.make_mesh(devices=jax.devices()[:1])
    with par.use_mesh(mesh):
        tr = par.ShardedTrainer(net, "adam", loss=None,
                                optimizer_params={"learning_rate": 1e-2},
                                mesh=mesh)
        losses = [float(tr.step((data, labels)).asnumpy())
                  for _ in range(2)]
    assert all(onp.isfinite(losses)) and losses[1] < losses[0], losses
    read = hc.read_loop_counters(net)
    assert read["steps"] == 2 and len(read["loop.exit_mass"]) == 2
    assert net.exit_gate.data().asnumpy().any()     # the gate learns
