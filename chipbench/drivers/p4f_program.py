"""The system under test, as the SambaY training driver reaches it: builds
the program's stage through the configuration's factory and hands it the
benchmark's weights (``harness/weights_phi4_flash.py``)."""
from __future__ import annotations

from chipbench.harness.weights_phi4_flash import (CROSS, GMU, MAMBA, SELF,
                                                  sizes_of)

_ATTENTION = {"qkv": "mixer.qkv_proj", "qkv_b": "mixer.qkv_bias",
              "o": "mixer.o_proj", "o_b": "mixer.o_bias",
              "lambdas": "mixer.lambdas", "subln": "mixer.subln"}
# kinds of layer -> benchmark leaf -> the program's structural parameter
# name in a layer
_MIXER = {
    MAMBA: {"m_in_proj": "mixer.in_proj", "m_conv_w": "mixer.conv_weight",
            "m_conv_b": "mixer.conv_bias", "m_x_proj": "mixer.x_proj",
            "m_dt_proj": "mixer.dt_proj", "m_dt_bias": "mixer.dt_bias",
            "m_A_log": "mixer.A_log", "m_D": "mixer.D",
            "m_out_proj": "mixer.out_proj"},
    SELF: {f"a_{k}": v for k, v in _ATTENTION.items()},
    CROSS: {f"c_{k}": v for k, v in _ATTENTION.items()},
    GMU: {"g_in": "mixer.in_proj", "g_out": "mixer.out_proj"},
}
_EVERY = {"n1_g": "norm1.gamma", "n1_b": "norm1.beta", "n2_g": "norm2.gamma",
          "n2_b": "norm2.beta", "f_fc1": "fc1", "f_fc2": "fc2"}
_TOP = {"embed": "embed.weight", "norm_f_g": "norm_f.gamma",
        "norm_f_b": "norm_f.beta"}


def build_net(config: dict, *, remat=True):
    """The configuration's factory at its sizes, the published layers held
    and this chip's rows of the tied table; no parameters allocated yet."""
    import importlib

    s = sizes_of(config)
    module, _, factory = config["program"]["factory"].rpartition(".")
    make = getattr(importlib.import_module(module), factory)
    return make(
        config["program"]["name"], num_layers=s["layers_published"],
        layers=s["layers"], vocab_size=s["vocab_published"],
        vocab_held=s["vocab"], units=s["units"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
        window=s["window"], mlp_hidden=s["mlp_width"], d_inner=s["d_inner"],
        state_size=s["state"], conv_kernel=s["conv"], dt_rank=s["dt_rank"],
        eps=s["eps"], remat=remat)


def param_map(net, pattern: str) -> dict:
    """(benchmark leaf, index among the layers of its kind or None) ->
    program Parameter; ``pattern`` is the configuration's letter a layer
    held (``sizes_of``)."""
    ps = net._collect_params_with_prefix()
    out = {}
    for leaf, name in _TOP.items():
        out[(leaf, None)] = ps.pop(name)
    seen = dict.fromkeys(_MIXER, 0)
    for j, (i, letter) in enumerate(zip(net.layers, pattern)):
        kinds = next(k for k in _MIXER if letter in k)
        for leaf, name in _MIXER[kinds].items():
            out[(leaf, seen[kinds])] = ps.pop(f"l{i}.{name}")
        seen[kinds] += 1
        for leaf, name in _EVERY.items():
            out[(leaf, j)] = ps.pop(f"l{i}.{name}")
    if ps:
        raise RuntimeError(f"program parameters the benchmark does not "
                           f"know: {sorted(ps)}")
    return out


def load_weights(net, weights: dict, pattern: str):
    """Hand the benchmark's stacked float32 weights to the program's
    parameters.  The net is NOT initialised first (``hybrid_program``)."""
    from mxnet_tpu.ndarray import NDArray

    for (leaf, i), p in param_map(net, pattern).items():
        a = weights[leaf] if i is None else weights[leaf][i]
        p.set_data(NDArray(a.astype("float32")))
