"""Operations and bytes a hybrid Mamba-2 / expert / attention stack needs,
computed from the configuration's sizes and FROM THE ROWS THE RUN'S OWN
COUNTER SAYS WERE ROUTED (never from the expectation), so that no share
of a peak can pass 100%.  Recomputation, padding, the rows of the expert
buffer past the routed ones and upcasts do not count.
"""
from __future__ import annotations

import re

from chipbench.harness.counts import roofline_seconds  # noqa: F401


def forward_macs_per_token(s: dict, pairs_local_per_token: float) -> dict:
    """Multiply-adds in matrix products of one token's forward pass, by
    part.  ``pairs_local_per_token``: token-expert pairs computed on this
    chip per token and E layer (the run's counter)."""
    u = s["units"]
    nm, ne, na = (s["pattern"].count(k) for k in "ME*")
    d_inner = s["m_heads"] * s["m_head_dim"]
    conv_dim = d_inner + 2 * s["groups"] * s["state"]
    hq, hk = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    q, n, p = s["chunk"], s["state"], s["m_head_dim"]
    return {
        "mamba_proj": nm * (u * (d_inner + conv_dim + s["m_heads"])
                            + d_inner * u),
        # the chunked form: C B^T a group, (C B^T o L) x, the chunk's
        # state and the read of the carried state a head
        "scan": nm * (s["groups"] * q * n + s["m_heads"] * (q * p
                                                            + 2 * n * p)),
        "attention_proj": na * (2 * u * hq + 2 * u * hk),
        "router": ne * u * s["experts"],
        "shared_expert": ne * 2 * u * s["shared_width"],
        "routed_experts": ne * pairs_local_per_token * 2 * u
        * s["expert_width"],
        "head": u * s["vocab"],
    }


def train_flops_per_token(s: dict, seq: int,
                          pairs_local_per_token: float) -> float:
    """Forward + backward FLOPs a trained token: 6 x the matmul
    multiply-adds + the causal-agnostic attention score/value products
    12 x layers x heads x head_dim x T (the PaLM count, as
    ``counts.gpt2_train_flops_per_token``).  Recomputation not counted."""
    macs = sum(forward_macs_per_token(s, pairs_local_per_token).values())
    na = s["pattern"].count("*")
    return 6.0 * macs + 12.0 * na * s["heads"] * s["head_dim"] * seq


def ssd_chunk_flops_bytes(batch: int, seq: int, s: dict,
                          itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one ``ssd_chunk_fwd`` call: per chunk and group
    ``C B^T`` (2 Q^2 N), per head ``(C B^T o L) x`` (2 Q^2 P) and the
    chunk's state (2 Q N P); reads x, B, C once and the running sums twice
    (float32), writes y and the states in float32."""
    q, n, p = s["chunk"], s["state"], s["m_head_dim"]
    h, g = s["m_heads"], s["groups"]
    nc = seq // q
    flops = batch * nc * (g * 2.0 * q * q * n
                          + h * (2.0 * q * q * p + 2.0 * q * n * p))
    nbytes = batch * seq * ((h * p + 2 * g * n) * itemsize + 2 * h * 4
                            + h * p * 4) + batch * nc * h * n * p * 4
    return flops, nbytes


def moe_gmm_flops_bytes(rows: float, k: int, n: int, experts: int,
                        itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one grouped product over ``rows`` routed rows:
    2 rows k n; reads the rows and writes the results once, moves each
    held expert's (k, n) weights (or their gradient) once."""
    return (2.0 * rows * k * n,
            (rows * (k + n) + experts * k * n) * itemsize)


def flash_gqa_flops_bytes(batch: int, heads: int, kv_heads: int, seq: int,
                          head_dim: int, itemsize: int = 2,
                          backward: bool = False) -> tuple:
    """(FLOPs, bytes) of one causal attention call with ``heads`` query
    heads over ``kv_heads`` key/value heads, as the algorithm needs them.
    FLOPs as ``counts.flash_flops_bytes`` (every query head does its own
    products): forward 2 B H T^2 D, backward (dq + dkv together) 2.5 x
    that.  Bytes: the forward reads q and writes o over H heads and reads
    k, v over the heads that exist; the backward reads q, o, do, k, v and
    writes dq, dk, dv: the key/value tensors at their own size, whatever
    the kernel writes for a query head."""
    fwd = 2.0 * batch * heads * seq * seq * head_dim
    over_q = batch * heads * seq * head_dim * itemsize
    over_kv = batch * kv_heads * seq * head_dim * itemsize
    if not backward:
        return fwd, 2.0 * over_q + 2.0 * over_kv
    return 2.5 * fwd, 4.0 * over_q + 4.0 * over_kv


# grouped products a layer and step: up and down forward; in the backward
# pass the two products for the rows and the two for the weights
GMM_CALLS_A_LAYER = 6

_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def kernel_seconds(op_seconds: dict, shapes: list) -> float:
    """Summed device time of the Pallas calls whose output holds one of
    ``shapes`` (dims tuples): ``xtrace.short_name`` drops a kernel's name
    and keeps ``custom-call:tpu_custom_call <output shape>``."""
    want = {tuple(int(x) for x in sh) for sh in shapes}
    total = 0.0
    for name, sec in op_seconds.items():
        if not name.startswith("custom-call:tpu_custom_call "):
            continue
        dims = {tuple(int(x) for x in d.split(",") if x)
                for _t, d in _SHAPE.findall(name)}
        if dims & want:
            total += sec
    return total


def ssd_output_shapes(batch: int, seq: int, s: dict) -> list:
    return [(batch, seq, s["m_heads"] * s["m_head_dim"])]


def flash_output_shapes(batch: int, seq: int, s: dict) -> list:
    """o, dq and the per-query-head dk, dv of the flash kernels."""
    return [(batch * s["heads"], seq, s["head_dim"])]


def moe_gmm_output_shapes(buffer_rows: int, s: dict) -> list:
    u, f, held = s["units"], s["expert_width"], s["experts_held"]
    return [(buffer_rows, f), (buffer_rows, u), (held, u, f), (held, f, u)]
