"""The system under test, as the looped decoder's training driver reaches
it: builds the program's Ouro stack through its public factory and hands
it the benchmark's weights (``harness/weights_ouro.py``)."""
from __future__ import annotations

from chipbench.harness.weights_ouro import sizes_of  # noqa: F401

# benchmark leaf -> the program's structural parameter name in a block
# (``l{i}_mixer`` the attention half of layer i, ``l{i}_mlp`` the other)
_ATTN = {"a_norm": "norm.gamma", "a_q": "mixer.q_proj",
         "a_k": "mixer.k_proj", "a_v": "mixer.v_proj",
         "a_o": "mixer.o_proj", "a_post": "post_norm.gamma"}
_MLP = {"m_norm": "norm.gamma", "m_in": "mixer.gate_up",
        "m_out": "mixer.down", "m_post": "post_norm.gamma"}
_TOP = {"embed": "embed.weight", "norm_f": "norm_f.gamma",
        "lm_head": "lm_head"}
# what only a stack run more than once has: the gate, and the payload the
# program rewrites itself (zeros at start; the benchmark hands it nothing)
_GATE = {"gate_w": "exit_gate", "gate_b": "exit_bias"}
_OWN = ("loop_stats",)


def build_net(config: dict, *, remat=True):
    """The configuration's factory at its sizes and this chip's share; no
    parameters allocated yet."""
    import importlib

    s = sizes_of(config)
    module, _, factory = config["program"]["factory"].rpartition(".")
    make = getattr(importlib.import_module(module), factory)
    return make(
        config["program"]["name"], num_layers=s["layers"],
        vocab_size=config.get("vocab_size_published", s["vocab"]),
        vocab_held=s["vocab"], units=s["units"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
        mlp_hidden=s["mlp_width"], rope_theta=s["rope_theta"],
        total_ut_steps=s["passes"], eps=s["eps"], exit_beta=s["beta"],
        remat=remat)


def param_map(net) -> dict:
    """(benchmark leaf, layer) -> program Parameter; the top leaves' layer
    is None."""
    ps = net._collect_params_with_prefix()
    out = {}
    looped = net.passes > 1
    for leaf, name in {**_TOP, **(_GATE if looped else {})}.items():
        out[(leaf, None)] = ps.pop(name)
    # a layer is two blocks of the program, each recomputed on its own
    for i in range(len(net.blocks) // 2):
        for leaf, name in _ATTN.items():
            out[(leaf, i)] = ps.pop(f"l{i}_mixer.{name}")
        for leaf, name in _MLP.items():
            out[(leaf, i)] = ps.pop(f"l{i}_mlp.{name}")
    for name in _OWN if looped else ():
        ps.pop(name)
    if ps:
        raise RuntimeError(f"program parameters the benchmark does not "
                           f"know: {sorted(ps)}")
    return out


def load_weights(net, weights: dict):
    """Hand the benchmark's stacked float32 weights to the program's
    parameters, and start the payload the program rewrites itself at
    zero.  The net is NOT initialised first (``hybrid_program``)."""
    from mxnet_tpu.ndarray import NDArray

    ps = net._collect_params_with_prefix()
    for (leaf, i), p in param_map(net).items():
        a = weights[leaf] if i is None else weights[leaf][i]
        p.set_data(NDArray(a.astype("float32")))
    for name in _OWN if net.passes > 1 else ():
        ps[name].initialize()


def read_loop(net) -> dict:
    """The program's own counters of the last step (no launch)."""
    from mxnet_tpu.models.ouro import read_loop_counters
    return read_loop_counters(net)
