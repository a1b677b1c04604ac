"""The system under test, as the hybrid training driver reaches it: builds
the program's Nemotron-H stack through its public factory and hands it the
benchmark's weights (``harness/weights_hybrid.py``)."""
from __future__ import annotations

from chipbench.harness.weights_hybrid import BUFFERS, sizes_of  # noqa: F401

# benchmark leaf -> the program's structural parameter name in a layer
_BY_KIND = {
    "M": {"m_norm": "norm.gamma", "m_in_proj": "mixer.in_proj",
          "m_conv_w": "mixer.conv_weight", "m_conv_b": "mixer.conv_bias",
          "m_dt_bias": "mixer.dt_bias", "m_A_log": "mixer.A_log",
          "m_D": "mixer.D", "m_norm_w": "mixer.norm_weight",
          "m_out_proj": "mixer.out_proj"},
    "E": {"e_norm": "norm.gamma", "e_router": "mixer.gate",
          "e_bias": "mixer.e_score_correction_bias", "e_up": "mixer.w1",
          "e_down": "mixer.w2", "e_shared_up": "mixer.shared_up",
          "e_shared_down": "mixer.shared_down"},
    "*": {"a_norm": "norm.gamma", "a_q": "mixer.q_proj",
          "a_k": "mixer.k_proj", "a_v": "mixer.v_proj",
          "a_o": "mixer.o_proj"},
}
_TOP = {"embed": "embed.weight", "norm_f": "norm_f.gamma",
        "lm_head": "lm_head"}
# payloads the program rewrites itself; the benchmark hands them nothing
_OWN = ("mixer.routing_stats", "mixer.last_choice")   # zeros at start


def build_net(config: dict, *, remat=True, record_choice_rows=0):
    """``get_nemotron_h`` at the configuration's sizes and this chip's
    share; no parameters allocated yet."""
    from mxnet_tpu.models import get_nemotron_h

    s = sizes_of(config)
    return get_nemotron_h(
        config["program"]["name"], pattern=s["pattern"],
        vocab_size=config.get("vocab_size_published", s["vocab"]),
        vocab_held=s["vocab"], units=s["units"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
        mamba_heads=s["m_heads"], mamba_head_dim=s["m_head_dim"],
        mamba_groups=s["groups"], state_size=s["state"],
        conv_kernel=s["conv"], chunk_size=s["chunk"],
        num_experts=s["experts"], top_k=s["top_k"],
        expert_hidden=s["expert_width"], shared_hidden=s["shared_width"],
        routed_scaling=s["scaling"], norm_topk=s["norm_topk"], eps=s["eps"],
        experts_held=(s["first_expert"], s["experts_held"]),
        record_choice_rows=record_choice_rows, remat=remat)


def param_map(net) -> dict:
    """(benchmark leaf, index among the layers of its kind or None) ->
    program Parameter."""
    ps = net._collect_params_with_prefix()
    out = {}
    for leaf, name in _TOP.items():
        out[(leaf, None)] = ps.pop(name)
    seen = {"M": 0, "E": 0, "*": 0}
    for i, kind in enumerate(net.pattern):
        for leaf, name in _BY_KIND[kind].items():
            out[(leaf, seen[kind])] = ps.pop(f"l{i}.{name}")
        for name in _OWN:
            ps.pop(f"l{i}.{name}", None)
        seen[kind] += 1
    if ps:
        raise RuntimeError(f"program parameters the benchmark does not "
                           f"know: {sorted(ps)}")
    return out


def load_weights(net, weights: dict):
    """Hand the benchmark's stacked float32 weights to the program's
    parameters, and start the payloads the program rewrites itself at
    zero.  The net is NOT initialised first: drawing 667M default weights
    only to replace them cost set-up some 200 s (my chip runs, PR 26)."""
    from mxnet_tpu.ndarray import NDArray

    ps = net._collect_params_with_prefix()
    for (leaf, i), p in param_map(net).items():
        a = weights[leaf] if i is None else weights[leaf][i]
        p.set_data(NDArray(a.astype("float32")))
    for name, p in ps.items():
        if name.endswith(_OWN):
            p.initialize()


def expert_layers(net) -> list:
    """The program's E layers' expert blocks, in pattern order."""
    return [b.mixer for b in net.blocks if b.kind == "E"]


def read_choices(net) -> list:
    """The chosen expert indices (tokens, k) the last step left in each E
    layer, as device arrays."""
    import jax.numpy as jnp

    return [jnp.asarray(m.last_choice.data().jax, jnp.int32)
            for m in expert_layers(net)]
