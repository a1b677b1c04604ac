"""Operations and bytes a dense hybrid Mamba-2 / attention stack under the
Granite family's multipliers needs, computed from the configuration's
sizes (as ``counts_hybrid``, whose roofline, kernel search, scan and
grouped-query counts it uses at this stack's sizes: the scan at ONE B/C
group, flash at 32 query heads of 64 over 8), so that no share of a peak
can pass 100%.  Recomputation, padding and upcasts do not count; the
multipliers are elementwise and count nothing.
"""
from __future__ import annotations

from chipbench.harness.counts_hybrid import (  # noqa: F401
    flash_gqa_flops_bytes, flash_output_shapes, kernel_seconds,
    roofline_seconds, ssd_chunk_flops_bytes, ssd_output_shapes)


def sizes_for(run: dict):
    """The run's sizes, or None where its configuration is not of this
    family (a reader then has nothing to read)."""
    from chipbench.harness.weights_granite_hybrid import sizes_of

    config = run.get("config", {})
    if config.get("model_type") != "granitemoehybrid":
        return None
    return sizes_of(config)


def forward_macs_per_token(s: dict) -> dict:
    """Multiply-adds in matrix products of one token's forward pass, by
    part."""
    u = s["units"]
    nm, na = s["pattern"].count("M"), s["pattern"].count("A")
    d_inner = s["m_heads"] * s["m_head_dim"]
    conv_dim = d_inner + 2 * s["groups"] * s["state"]
    hq, hk = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    q, n, p = s["chunk"], s["state"], s["m_head_dim"]
    return {
        # input_linear to gate and up, output_linear back
        "feed_forward": (nm + na) * 3 * u * s["mlp_width"],
        "mamba_proj": nm * (u * (d_inner + conv_dim + s["m_heads"])
                            + d_inner * u),
        # the chunked form (``counts_hybrid``): C B^T a group, (C B^T o L) x,
        # the chunk's state and the read of the carried state a head
        "scan": nm * (s["groups"] * q * n + s["m_heads"] * (q * p
                                                            + 2 * n * p)),
        "attention_proj": na * (2 * u * hq + 2 * u * hk),
        "head": u * s["vocab"],
    }


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward FLOPs a trained token: 6 x the matmul
    multiply-adds + the causal-agnostic attention score/value products
    12 x layers x heads x head_dim x T (the PaLM count, as
    ``counts_hybrid``).  Recomputation not counted."""
    macs = sum(forward_macs_per_token(s).values())
    na = s["pattern"].count("A")
    return 6.0 * macs + 12.0 * na * s["heads"] * s["head_dim"] * seq
