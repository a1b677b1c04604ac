"""Operations and bytes a looped decoder needs, computed from the
configuration's sizes: ONE stack's matrix products and attention scores
times the passes it is run, a head and a gate after every pass, and every
score matrix counted only over the keys the causal mask lets a query see
(``counts_phi4_flash.seen_pairs``), so that no share of a peak can pass
100%.  Recomputation, padding and upcasts do not count.
"""
from __future__ import annotations

from chipbench.harness.counts_hybrid import (  # noqa: F401
    kernel_seconds, roofline_seconds)
from chipbench.harness.counts_phi4_flash import seen_pairs  # noqa: F401

# the backward pass's two kernels together over the forward's products:
# the scores again, dv, dp, dq and dk, five products for the forward's two
BACKWARD_OVER_FORWARD = 2.5


def sizes_for(run: dict):
    """The run's sizes, or None where its configuration is not of this
    family (a reader then has nothing to read)."""
    from chipbench.harness.weights_ouro import sizes_of

    config = run.get("config", {})
    if config.get("model_type") != "ouro":
        return None
    return sizes_of(config)


def applications(s: dict) -> int:
    """Layer applications a step: every layer held, every pass."""
    return s["passes"] * s["layers"]


def forward_macs_per_token(s: dict) -> dict:
    """Multiply-adds in matrix products of one token's forward pass
    through ALL the passes, by part; the attention scores are apart
    (:func:`score_flops`)."""
    u = s["units"]
    hq, hk = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    return {
        "attention_proj": applications(s) * (2 * u * hq + 2 * u * hk),
        # gate and up, then down
        "feed_forward": applications(s) * 3 * u * s["mlp_width"],
        "head": s["passes"] * u * s["vocab"],
        "exit_gate": s["passes"] * u,
    }


def score_flops(batch: int, seq: int, s: dict) -> float:
    """FLOPs of ONE layer application's forward score and value products
    as the algorithm needs them: every head one score and one value
    product (2 D FLOP a pair each), over the pairs the causal mask lets
    through."""
    return 4.0 * s["head_dim"] * batch * s["heads"] * seen_pairs(seq)


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward FLOPs a trained token, all passes and all heads
    counted: 6 x the matmul multiply-adds + 3 x the forward score and
    value products (the backward's are twice the forward's) of every
    layer application.  Recomputation not counted."""
    macs = sum(forward_macs_per_token(s).values())
    return 6.0 * macs + 3.0 * applications(s) * score_flops(1, seq, s) / seq


def flash_mha_flops_bytes(batch: int, seq: int, s: dict,
                          backward: bool = False,
                          itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one layer application's flash call(s) as the
    algorithm needs them.  Forward: reads q and writes o over the query
    heads, reads k and v over the key/value heads.  Backward (dq and dkv
    together): ``BACKWARD_OVER_FORWARD`` x the forward's FLOPs; reads q,
    o, do, k, v and writes dq, dk, dv."""
    row = batch * seq * s["head_dim"] * itemsize
    flops = score_flops(batch, seq, s)
    if not backward:
        return flops, row * (2 * s["heads"] + 2 * s["kv_heads"])
    return (BACKWARD_OVER_FORWARD * flops,
            row * (4 * s["heads"] + 4 * s["kv_heads"]))


def flash_output_shapes(batch: int, seq: int, s: dict) -> list:
    """o, dq, dk and dv of the flash kernels (as many key/value as query
    heads here, so all four have one shape)."""
    return [(batch * s["heads"], seq, s["head_dim"]),
            (batch * s["kv_heads"], seq, s["head_dim"])]
