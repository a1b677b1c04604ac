"""Operations and bytes a hybrid Gated DeltaNet / gated attention / sparse
expert stack needs, computed from the configuration's sizes and FROM THE
ROWS THE RUN'S OWN COUNTER SAYS WERE ROUTED (as ``counts_hybrid``, whose
roofline, kernel search, grouped-product and grouped-query counts it
uses), so that no share of a peak can pass 100%.  Recomputation, padding,
the rows of the expert buffer past the routed ones and upcasts do not
count.
"""
from __future__ import annotations

from chipbench.harness.counts_hybrid import (  # noqa: F401
    flash_gqa_flops_bytes, flash_output_shapes, kernel_seconds,
    moe_gmm_flops_bytes, moe_gmm_output_shapes, roofline_seconds)

# grouped products a layer and step: up, gate and down forward; in the
# backward pass the three products for the rows and the three for the
# weights
GMM_CALLS_A_LAYER = 9


def rule_macs_per_token(s: dict) -> float:
    """Multiply-adds of the chunked delta rule for one token of one layer
    (all value heads).  A key head, shared by the value heads it serves:
    its rows of ``k k^T`` and ``q k^T`` (2 c d_k).  A value head: ``T (beta
    v)`` (c d_v), ``T (beta exp(G) k)`` (c d_k), ``W S``, ``q S`` and the
    state's update (3 d_k d_v), ``tril(q k^T) U`` (c d_v), and the unit
    triangular inverse by substitution (c^3 / 6 a chunk: c^2 / 6 a
    token; the program's product form does more and is not counted)."""
    c, dk, dv = s["chunk"], s["k_dim"], s["v_dim"]
    return (s["k_heads"] * 2.0 * c * dk
            + s["v_heads"] * (c * dv + c * dk + 3.0 * dk * dv + c * dv
                              + c * c / 6.0))


def forward_macs_per_token(s: dict, pairs_local_per_token: float) -> dict:
    """Multiply-adds in matrix products of one token's forward pass, by
    part.  ``pairs_local_per_token``: token-expert pairs computed on this
    chip per token and expert layer (the run's counter)."""
    u = s["units"]
    nl, nf = s["pattern"].count("L"), s["pattern"].count("F")
    kd, vd = s["k_heads"] * s["k_dim"], s["v_heads"] * s["v_dim"]
    hq, hk = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    return {
        "deltanet_proj": nl * (u * (2 * kd + 2 * vd + 2 * s["v_heads"])
                               + vd * u),
        "delta_rule": nl * rule_macs_per_token(s),
        "attention_proj": nf * (u * 2 * hq + 2 * u * hk + hq * u),
        "router": (nl + nf) * u * s["experts"],
        "shared_expert": (nl + nf) * (3 * u * s["shared_width"] + u),
        "routed_experts": (nl + nf) * pairs_local_per_token * 3 * u
        * s["expert_width"],
        "head": u * s["vocab"],
    }


def train_flops_per_token(s: dict, seq: int,
                          pairs_local_per_token: float) -> float:
    """Forward + backward FLOPs a trained token: 6 x the matmul
    multiply-adds + the causal-agnostic attention score/value products
    12 x layers x heads x head_dim x T (the PaLM count, as
    ``counts_hybrid.train_flops_per_token``).  Recomputation not
    counted."""
    macs = sum(forward_macs_per_token(s, pairs_local_per_token).values())
    nf = s["pattern"].count("F")
    return 6.0 * macs + 12.0 * nf * s["heads"] * s["head_dim"] * seq


def gdn_chunk_flops_bytes(batch: int, seq: int, s: dict,
                          itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one ``gdn_chunk_fwd`` call: the rule's
    multiply-adds twice over; reads q, k, v once and the running sums and
    write strengths in float32, writes o in float32."""
    kd, vd = s["k_heads"] * s["k_dim"], s["v_heads"] * s["v_dim"]
    flops = 2.0 * batch * seq * rule_macs_per_token(s)
    nbytes = batch * seq * ((2 * kd + vd) * itemsize
                            + 2 * s["v_heads"] * 4 + vd * 4)
    return flops, nbytes


def gdn_output_shapes(batch: int, seq: int, s: dict) -> list:
    return [(batch, seq, s["v_heads"] * s["v_dim"])]
