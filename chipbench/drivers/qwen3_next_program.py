"""The system under test, as the Gated DeltaNet training driver reaches
it: builds the program's Qwen3-Next stack through its public factory and
hands it the benchmark's weights (``harness/weights_qwen3_next.py``)."""
from __future__ import annotations

from chipbench.harness.weights_qwen3_next import BUFFERS, sizes_of  # noqa: F401

# benchmark leaf -> the program's structural parameter name in a block
_MIXER = {
    "L": {"l_norm": "norm.gamma", "l_qkvz": "mixer.in_proj_qkvz",
          "l_ba": "mixer.in_proj_ba", "l_conv": "mixer.conv_weight",
          "l_dt_bias": "mixer.dt_bias", "l_A_log": "mixer.A_log",
          "l_gnorm": "mixer.norm_weight", "l_out": "mixer.out_proj"},
    "F": {"f_norm": "norm.gamma", "f_q": "mixer.q_proj",
          "f_k": "mixer.k_proj", "f_v": "mixer.v_proj",
          "f_qnorm": "mixer.q_norm", "f_knorm": "mixer.k_norm",
          "f_o": "mixer.o_proj"},
}
_EXPERTS = {"e_norm": "norm.gamma", "e_router": "moe.gate",
            "e_gate": "moe.w_gate", "e_up": "moe.w1", "e_down": "moe.w2",
            "e_sh_gate": "moe.shared_gate_proj", "e_sh_up": "moe.shared_up",
            "e_sh_down": "moe.shared_down",
            "e_sh_sig": "moe.shared_expert_gate"}
_TOP = {"embed": "embed.weight", "norm_f": "norm_f.gamma",
        "lm_head": "lm_head"}
# payloads the program rewrites itself; the benchmark hands them nothing
_OWN = ("moe.routing_stats", "moe.last_choice")       # zeros at start


def build_net(config: dict, *, remat=True, record_choice_rows=0):
    """The configuration's factory at its sizes and this chip's share; no
    parameters allocated yet."""
    import importlib

    s = sizes_of(config)
    module, _, factory = config["program"]["factory"].rpartition(".")
    make = getattr(importlib.import_module(module), factory)
    return make(
        config["program"]["name"], num_layers=len(s["pattern"]),
        full_attention_interval=int(config["full_attention_interval"]),
        vocab_size=config.get("vocab_size_published", s["vocab"]),
        vocab_held=s["vocab"], units=s["units"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
        partial_rotary_factor=config["partial_rotary_factor"],
        rope_theta=s["theta"], linear_key_heads=s["k_heads"],
        linear_value_heads=s["v_heads"], linear_key_dim=s["k_dim"],
        linear_value_dim=s["v_dim"], conv_kernel=s["conv"],
        chunk_size=s["chunk"], num_experts=s["experts"], top_k=s["top_k"],
        expert_hidden=s["expert_width"], shared_hidden=s["shared_width"],
        norm_topk=s["norm_topk"], eps=s["eps"],
        experts_held=(s["first_expert"], s["experts_held"]),
        record_choice_rows=record_choice_rows, remat=remat)


def param_map(net) -> dict:
    """(benchmark leaf, index among the blocks of its kind or None) ->
    program Parameter."""
    ps = net._collect_params_with_prefix()
    out = {}
    for leaf, name in _TOP.items():
        out[(leaf, None)] = ps.pop(name)
    seen = {"L": 0, "F": 0}
    for i, kind in enumerate(net.kinds):
        kind = "F" if kind == "full" else "L"
        for leaf, name in _MIXER[kind].items():
            out[(leaf, seen[kind])] = ps.pop(f"l{i}_mixer.{name}")
        seen[kind] += 1
        for leaf, name in _EXPERTS.items():
            out[(leaf, i)] = ps.pop(f"l{i}_experts.{name}")
        for name in _OWN:
            ps.pop(f"l{i}_experts.{name}", None)
    if ps:
        raise RuntimeError(f"program parameters the benchmark does not "
                           f"know: {sorted(ps)}")
    return out


def load_weights(net, weights: dict):
    """Hand the benchmark's stacked float32 weights to the program's
    parameters, and start the payloads the program rewrites itself at
    zero.  The net is NOT initialised first (``hybrid_program``)."""
    from mxnet_tpu.ndarray import NDArray

    ps = net._collect_params_with_prefix()
    for (leaf, i), p in param_map(net).items():
        a = weights[leaf] if i is None else weights[leaf][i]
        p.set_data(NDArray(a.astype("float32")))
    for name, p in ps.items():
        if name.endswith(_OWN):
            p.initialize()


def expert_layers(net) -> list:
    """The program's expert blocks' layers, in layer order."""
    return [b.moe for b in net.blocks if hasattr(b, "moe")]


def read_choices(net) -> list:
    """The chosen expert indices (tokens, k) the last step left in each
    expert layer, as device arrays."""
    import jax.numpy as jnp

    return [jnp.asarray(m.last_choice.data().jax, jnp.int32)
            for m in expert_layers(net)]
