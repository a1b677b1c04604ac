"""The system under test, as the sliding-window expert decoder's training
driver reaches it: builds the program's Mellum stack through its public
factory and hands it the benchmark's weights
(``harness/weights_mellum2.py``)."""
from __future__ import annotations

from chipbench.drivers.qwen3_next_program import (  # noqa: F401
    expert_layers, read_choices)
from chipbench.harness.weights_mellum2 import KINDS, sizes_of  # noqa: F401

# benchmark leaf -> the program's structural parameter name in a block
_MIXER = {"a_norm": "norm.gamma", "a_q": "mixer.q_proj",
          "a_k": "mixer.k_proj", "a_v": "mixer.v_proj",
          "a_qnorm": "mixer.q_norm", "a_knorm": "mixer.k_norm",
          "a_o": "mixer.o_proj"}
_EXPERTS = {"e_norm": "norm.gamma", "e_router": "moe.gate",
            "e_gate": "moe.w_gate", "e_up": "moe.w1", "e_down": "moe.w2"}
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
        layer_types=tuple(config["layer_types"]),
        vocab_size=config.get("vocab_size_published", s["vocab"]),
        vocab_held=s["vocab"], units=s["units"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
        sliding_window=s["window"],
        rope_parameters=config["rope_parameters"],
        num_experts=s["experts"], top_k=s["top_k"],
        expert_hidden=s["expert_width"], norm_topk=s["norm_topk"],
        eps=s["eps"], experts_held=(s["first_expert"], s["experts_held"]),
        record_choice_rows=record_choice_rows, remat=remat)


def param_map(net) -> dict:
    """(benchmark leaf, layer) -> program Parameter; the top leaves' layer
    is None."""
    ps = net._collect_params_with_prefix()
    out = {}
    for leaf, name in _TOP.items():
        out[(leaf, None)] = ps.pop(name)
    for i, kind in enumerate(net.kinds):
        if kind not in KINDS:
            raise RuntimeError(f"layer {i} is of a kind the benchmark does "
                               f"not know: {kind!r}")
        for leaf, name in _MIXER.items():
            out[(leaf, i)] = ps.pop(f"l{i}_mixer.{name}")
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
