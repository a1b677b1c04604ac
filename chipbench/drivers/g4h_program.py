"""The system under test, as the Granite hybrid training driver reaches
it: builds the program's stack through the configuration's factory and
hands it the benchmark's weights (``harness/weights_granite_hybrid.py``)."""
from __future__ import annotations

from chipbench.harness.weights_granite_hybrid import sizes_of  # noqa: F401

# benchmark leaf -> the program's structural parameter name in a layer
_MIXER = {
    "M": {"m_norm": "norm1.gamma", "m_in_proj": "mixer.in_proj",
          "m_conv_w": "mixer.conv_weight", "m_conv_b": "mixer.conv_bias",
          "m_dt_bias": "mixer.dt_bias", "m_A_log": "mixer.A_log",
          "m_D": "mixer.D", "m_norm_w": "mixer.norm_weight",
          "m_out_proj": "mixer.out_proj"},
    "A": {"a_norm": "norm1.gamma", "a_q": "mixer.q_proj",
          "a_k": "mixer.k_proj", "a_v": "mixer.v_proj",
          "a_o": "mixer.o_proj"},
}
_MLP = {"f_norm": "norm2.gamma", "f_in": "mlp_in", "f_out": "mlp_out"}
_TOP = {"embed": "embed.weight", "norm_f": "norm_f.gamma"}


def build_net(config: dict, *, remat=True):
    """The configuration's factory at its sizes and this chip's rows of
    the tied table; no parameters allocated yet."""
    import importlib

    s = sizes_of(config)
    module, _, factory = config["program"]["factory"].rpartition(".")
    make = getattr(importlib.import_module(module), factory)
    return make(
        config["program"]["name"], layer_types=tuple(config["layer_types"]),
        vocab_size=config["published"]["vocab_size"], vocab_held=s["vocab"],
        units=s["units"], num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], mamba_heads=s["m_heads"],
        mamba_head_dim=s["m_head_dim"], mamba_groups=s["groups"],
        state_size=s["state"], conv_kernel=s["conv"], chunk_size=s["chunk"],
        mlp_hidden=s["mlp_width"], embedding_multiplier=s["emb_mult"],
        residual_multiplier=s["res_mult"],
        attention_multiplier=s["attn_mult"],
        logits_scaling=s["logits_scaling"], eps=s["eps"], remat=remat)


def param_map(net) -> dict:
    """(benchmark leaf, index among the layers of its kind or None) ->
    program Parameter."""
    ps = net._collect_params_with_prefix()
    out = {}
    for leaf, name in _TOP.items():
        out[(leaf, None)] = ps.pop(name)
    seen = {"M": 0, "A": 0}
    for i, kind in enumerate(net.layer_types):
        kind = "A" if kind == "attention" else "M"
        for leaf, name in _MIXER[kind].items():
            out[(leaf, seen[kind])] = ps.pop(f"l{i}.{name}")
        seen[kind] += 1
        for leaf, name in _MLP.items():
            out[(leaf, i)] = ps.pop(f"l{i}.{name}")
    if ps:
        raise RuntimeError(f"program parameters the benchmark does not "
                           f"know: {sorted(ps)}")
    return out


def load_weights(net, weights: dict):
    """Hand the benchmark's stacked float32 weights to the program's
    parameters.  The net is NOT initialised first (``hybrid_program``)."""
    from mxnet_tpu.ndarray import NDArray

    for (leaf, i), p in param_map(net).items():
        a = weights[leaf] if i is None else weights[leaf][i]
        p.set_data(NDArray(a.astype("float32")))
