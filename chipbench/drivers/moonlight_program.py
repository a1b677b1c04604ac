"""The system under test, as the latent-attention expert decoder's
training driver reaches it: builds the program's DeepSeek-V3-shaped stack
through its public factory and hands it the benchmark's weights
(``harness/weights_moonlight.py``)."""
from __future__ import annotations

from chipbench.drivers.qwen3_next_program import (  # noqa: F401
    expert_layers, read_choices)
from chipbench.harness.weights_moonlight import (BUFFERS,  # noqa: F401
                                                 sizes_of)

# benchmark leaf -> the program's structural parameter name in a block
_MIXER = {"a_norm": "norm.gamma", "a_q": "mixer.q_proj",
          "a_kva": "mixer.kv_a_proj_with_mqa",
          "a_cnorm": "mixer.kv_a_layernorm", "a_kvb": "mixer.kv_b_proj",
          "a_o": "mixer.o_proj"}
_DENSE = {"d_norm": "norm.gamma", "d_gate_up": "mixer.gate_up",
          "d_down": "mixer.down"}
_EXPERTS = {"e_norm": "norm.gamma", "e_router": "moe.gate",
            "e_bias": "moe.e_score_correction_bias",
            "e_gate": "moe.w_gate", "e_up": "moe.w1", "e_down": "moe.w2",
            "e_sh_gate": "moe.shared_gate_proj", "e_sh_up": "moe.shared_up",
            "e_sh_down": "moe.shared_down"}
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
        first_k_dense=s["pattern"].count("D"),
        vocab_size=config.get("vocab_size_published", s["vocab"]),
        vocab_held=s["vocab"], units=s["units"], num_heads=s["heads"],
        qk_nope_dim=s["nope"], qk_rope_dim=s["rope"], v_head_dim=s["v_dim"],
        kv_lora_rank=s["rank"], rope_theta=s["theta"],
        mlp_hidden=s["dense_width"], num_experts=s["experts"],
        top_k=s["top_k"], expert_hidden=s["expert_width"],
        shared_hidden=s["shared_width"], routed_scaling=s["scaling"],
        norm_topk=s["norm_topk"], eps=s["eps"], latent_eps=s["latent_eps"],
        experts_held=(s["first_expert"], s["experts_held"]),
        record_choice_rows=record_choice_rows, remat=remat)


def param_map(net) -> dict:
    """(benchmark leaf, index among the half-layers of its kind or None) ->
    program Parameter."""
    ps = net._collect_params_with_prefix()
    out = {}
    for leaf, name in _TOP.items():
        out[(leaf, None)] = ps.pop(name)
    layers = len(net.blocks) // 2
    for i in range(layers):
        for leaf, name in _MIXER.items():
            out[(leaf, i)] = ps.pop(f"l{i}_mixer.{name}")
        if i < net.first_k_dense:
            for leaf, name in _DENSE.items():
                out[(leaf, i)] = ps.pop(f"l{i}_mlp.{name}")
            continue
        for leaf, name in _EXPERTS.items():
            out[(leaf, i - net.first_k_dense)] = ps.pop(
                f"l{i}_experts.{name}")
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
