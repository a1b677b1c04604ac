"""Operations and bytes a latent-attention expert decoder needs, computed
from the configuration's PUBLISHED sizes (a score over 128 + 64 = 192
dimensions, values of 128, whatever form the kernels give the sum) and
FROM THE ROWS THE RUN'S OWN COUNTER SAYS WERE ROUTED (as ``counts_hybrid``,
whose roofline, kernel search and grouped-product counts it uses), so that
no share of a peak can pass 100%: every score matrix counted only over the
pairs the causal mask lets through.  Recomputation, padding, the rows of
the expert buffer past the routed ones and upcasts do not count.
"""
from __future__ import annotations

from chipbench.harness.counts_hybrid import (  # noqa: F401
    kernel_seconds, moe_gmm_flops_bytes, moe_gmm_output_shapes,
    roofline_seconds)
from chipbench.harness.counts_phi4_flash import seen_pairs

# grouped products a layer and step: up, gate and down forward; in the
# backward pass the three products for the rows and the three for the
# weights
GMM_CALLS_A_LAYER = 9


def sizes_for(run: dict):
    """The run's sizes, or None where its configuration is not of this
    family (a reader then has nothing to read)."""
    from chipbench.harness.weights_moonlight import sizes_of

    config = run.get("config", {})
    if config.get("model_type") != "deepseek_v3":
        return None
    return sizes_of(config)


def forward_macs_per_token(s: dict, pairs_local_per_token: float) -> dict:
    """Multiply-adds in matrix products of one token's forward pass, by
    part; the attention scores are apart (:func:`score_flops`).
    ``pairs_local_per_token``: token-expert pairs computed on this chip per
    token and expert layer (the run's counter)."""
    u, h, r = s["units"], s["heads"], s["rank"]
    n = len(s["pattern"])
    nd, ne = (s["pattern"].count(k) for k in "DE")
    return {
        # q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj
        "latent_proj": n * (u * h * (s["nope"] + s["rope"])
                            + u * (r + s["rope"])
                            + r * h * (s["nope"] + s["v_dim"])
                            + h * s["v_dim"] * u),
        "dense_mlp": nd * 3 * u * s["dense_width"],
        "router": ne * u * s["experts"],
        "shared_expert": ne * 3 * u * s["shared_width"],
        "routed_experts": ne * pairs_local_per_token * 3 * u
        * s["expert_width"],
        "head": u * s["vocab"],
    }


def score_flops(batch: int, seq: int, s: dict) -> float:
    """FLOPs of one attention layer's forward score and value products as
    the algorithm needs them: every head a score over nope + rope
    dimensions and a product with values of v_dim (2 FLOP a dimension and
    pair), over the pairs the causal mask lets through."""
    return (2.0 * (s["nope"] + s["rope"] + s["v_dim"]) * batch * s["heads"]
            * seen_pairs(seq))


def train_flops_per_token(s: dict, seq: int,
                          pairs_local_per_token: float) -> float:
    """Forward + backward FLOPs a trained token: 6 x the matmul
    multiply-adds + 3 x the forward score and value products (the
    backward's are twice the forward's).  Recomputation not counted."""
    macs = sum(forward_macs_per_token(s, pairs_local_per_token).values())
    return 6.0 * macs + 3.0 * len(s["pattern"]) * score_flops(1, seq, s) / seq


def flash_mla_flops_bytes(batch: int, seq: int, s: dict,
                          itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one latent-attention flash FORWARD call: reads
    every head's query (nope + rope wide), its nope-wide keys and its
    values, the ONE rope-wide rotary key all heads share, and writes the
    output."""
    per_head = s["nope"] + s["rope"] + s["nope"] + 2 * s["v_dim"]
    nbytes = batch * seq * itemsize * (s["heads"] * per_head + s["rope"])
    return score_flops(batch, seq, s), nbytes


def flash_forward_shapes(batch: int, seq: int, s: dict) -> list:
    """The per-row logsumexp only the forward kernel writes."""
    return [(batch * s["heads"], 1, seq)]
