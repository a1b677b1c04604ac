"""The system under test, as the drivers reach it: builds the program's
GPT-2 through its public factory and hands it the benchmark's weights.
Shared by the serve and train drivers."""
from __future__ import annotations

# benchmark leaf -> the program's structural parameter name
_PER_LAYER = {
    "ln1_g": "ln1.gamma", "ln1_b": "ln1.beta",
    "q_w": "attn.q_proj.weight", "q_b": "attn.q_proj.bias",
    "k_w": "attn.k_proj.weight", "k_b": "attn.k_proj.bias",
    "v_w": "attn.v_proj.weight", "v_b": "attn.v_proj.bias",
    "o_w": "attn.out_proj.weight", "o_b": "attn.out_proj.bias",
    "ln2_g": "ln2.gamma", "ln2_b": "ln2.beta",
    "fc1_w": "ffn.fc1.weight", "fc1_b": "ffn.fc1.bias",
    "fc2_w": "ffn.fc2.weight", "fc2_b": "ffn.fc2.bias",
}
_TOP = {"wte": "wte.weight", "wpe": "wpe.weight",
        "lnf_g": "ln_f.gamma", "lnf_b": "ln_f.beta"}


def sizes_of(config: dict) -> dict:
    """The published sizes the benchmark's arithmetic needs."""
    keys = ("n_layer", "n_embd", "n_head", "vocab_size", "n_positions",
            "layer_norm_epsilon", "initializer_range")
    return {k: config[k] for k in keys}


def build_net(config: dict, *, remat=False):
    """``get_gpt2`` at the configuration's sizes, dropout off, no
    parameters allocated yet."""
    from mxnet_tpu.models import get_gpt2

    prog = config["program"]
    net = get_gpt2(prog["name"], dropout=0.0, remat=remat,
                   vocab_size=config["vocab_size"],
                   units=config["n_embd"], num_layers=config["n_layer"],
                   num_heads=config["n_head"],
                   max_length=config["n_positions"],
                   layer_norm_eps=config["layer_norm_epsilon"])
    return net


def param_map(net) -> dict:
    """(benchmark leaf, layer index or None) -> program Parameter."""
    ps = net._collect_params_with_prefix()
    out = {}
    for leaf, name in _TOP.items():
        out[(leaf, None)] = ps.pop(name)
    n_layer = len(net.blocks)
    for i in range(n_layer):
        for leaf, name in _PER_LAYER.items():
            out[(leaf, i)] = ps.pop(f"h{i}.{name}")
    if ps:
        raise RuntimeError(f"program parameters the benchmark does not "
                           f"know: {sorted(ps)}")
    return out


def load_weights(net, weights: dict, *, dtype: str, trainable: bool):
    """Hand the benchmark's stacked weights to the program's parameters,
    as ``dtype``.  Serving parameters carry no gradient buffers."""
    from mxnet_tpu.ndarray import NDArray

    if not trainable:
        net.collect_params().setattr("grad_req", "null")
    net.cast(dtype)
    for (leaf, i), p in param_map(net).items():
        a = weights[leaf] if i is None else weights[leaf][i]
        p.set_data(NDArray(a.astype(dtype)))


def read_params(net) -> dict:
    """The program's parameters back in the benchmark's stacked layout
    (float32), for comparing a trained state with the reference's."""
    import jax.numpy as jnp

    pm = param_map(net)
    out = {}
    for leaf in _TOP:
        out[leaf] = pm[(leaf, None)].data().jax.astype(jnp.float32)
    n_layer = len(net.blocks)
    for leaf in _PER_LAYER:
        out[leaf] = jnp.stack([pm[(leaf, i)].data().jax.astype(jnp.float32)
                               for i in range(n_layer)])
    return out
