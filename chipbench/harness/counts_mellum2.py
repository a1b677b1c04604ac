"""Operations and bytes a sliding-window / full attention expert decoder
needs, computed from the configuration's sizes and FROM THE ROWS THE RUN'S
OWN COUNTER SAYS WERE ROUTED (as ``counts_hybrid``, whose roofline, kernel
search and grouped-product counts it uses), so that no share of a peak can
pass 100%: every score matrix counted only over the keys the layer's mask
lets a query see (``counts_phi4_flash.seen_pairs``).  Recomputation,
padding, the rows of the expert buffer past the routed ones and upcasts do
not count.
"""
from __future__ import annotations

from chipbench.harness.counts_hybrid import (  # noqa: F401
    kernel_seconds, moe_gmm_flops_bytes, moe_gmm_output_shapes,
    roofline_seconds)
from chipbench.harness.counts_phi4_flash import seen_pairs  # noqa: F401

# grouped products a layer and step: up, gate and down forward; in the
# backward pass the three products for the rows and the three for the
# weights
GMM_CALLS_A_LAYER = 9


def sizes_for(run: dict):
    """The run's sizes, or None where its configuration is not of this
    family (a reader then has nothing to read)."""
    from chipbench.harness.weights_mellum2 import sizes_of

    config = run.get("config", {})
    if config.get("model_type") != "mellum":
        return None
    return sizes_of(config)


def layer_windows(s: dict) -> list:
    """The window (None: none) of every layer held."""
    return [s["window"] if k == "S" else None for k in s["pattern"]]


def forward_macs_per_token(s: dict, pairs_local_per_token: float) -> dict:
    """Multiply-adds in matrix products of one token's forward pass, by
    part; the attention scores are apart (:func:`score_flops`).
    ``pairs_local_per_token``: token-expert pairs computed on this chip per
    token and layer (the run's counter)."""
    u, n = s["units"], len(s["pattern"])
    hq, hk = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    return {
        "attention_proj": n * (2 * u * hq + 2 * u * hk),
        "router": n * u * s["experts"],
        "routed_experts": n * pairs_local_per_token * 3 * u
        * s["expert_width"],
        "head": u * s["vocab"],
    }


def score_flops(batch: int, seq: int, s: dict, window=None) -> float:
    """FLOPs of one attention layer's forward score and value products as
    the algorithm needs them: every query head one score and one value
    product (2 D FLOP a pair each), over the pairs the mask lets
    through."""
    return (4.0 * s["head_dim"] * batch * s["heads"]
            * seen_pairs(seq, window))


def train_flops_per_token(s: dict, seq: int,
                          pairs_local_per_token: float) -> float:
    """Forward + backward FLOPs a trained token: 6 x the matmul
    multiply-adds + 3 x the forward score and value products (the
    backward's are twice the forward's), those over the pairs the masks
    let through.  Recomputation not counted."""
    macs = sum(forward_macs_per_token(s, pairs_local_per_token).values())
    scores = sum(score_flops(1, seq, s, w) for w in layer_windows(s))
    return 6.0 * macs + 3.0 * scores / seq


def flash_swa_flops_bytes(batch: int, seq: int, s: dict, window=None,
                          itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one grouped-query flash FORWARD call: reads q and
    writes o over the query heads, reads k and v over the key/value
    heads."""
    nbytes = batch * seq * s["head_dim"] * itemsize * (
        2 * s["heads"] + 2 * s["kv_heads"])
    return score_flops(batch, seq, s, window), nbytes


def flash_forward_shapes(batch: int, seq: int, s: dict) -> list:
    """The per-row logsumexp only the forward kernel writes."""
    return [(batch * s["heads"], 1, seq)]
