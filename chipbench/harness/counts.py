"""Operations and bytes the algorithm needs, computed from shapes.  These
are the yardstick for MFU and roofline shares: recomputation, padding and
upcasts do not count, so a share cannot be raised by doing more work."""
from __future__ import annotations


def gpt2_params(sizes: dict) -> int:
    """Parameter count of the GPT-2 stack (tied head counted once)."""
    L, U, V, T = (sizes["n_layer"], sizes["n_embd"], sizes["vocab_size"],
                  sizes["n_positions"])
    per_layer = 12 * U * U + 13 * U      # qkv+o (4U^2+4U), mlp (8U^2+5U), 2 LN (4U)
    return V * U + T * U + L * per_layer + 2 * U


def gpt2_train_flops_per_token(sizes: dict, seq: int) -> float:
    """Forward + backward FLOPs per trained token: 6 x (matmul parameters:
    12 L U^2 in the blocks and U V in the tied head) + causal-agnostic
    attention score/value matmuls 12 L U T (the PaLM/Chinchilla count,
    as in bench.py ``_bench_gpt2_config``).  Recomputation is not counted."""
    L, U, V = sizes["n_layer"], sizes["n_embd"], sizes["vocab_size"]
    return 6.0 * (12 * L * U * U + U * V) + 12.0 * L * U * seq


def gpt2_forward_flops_per_token(sizes: dict, context: int) -> float:
    """Forward FLOPs for one token attending ``context`` keys."""
    L, U, V = sizes["n_layer"], sizes["n_embd"], sizes["vocab_size"]
    return 2.0 * (12 * L * U * U + U * V) + 4.0 * L * U * context


def kv_bytes_per_token(sizes: dict, itemsize: int = 2) -> int:
    """K and V of one position over all layers."""
    return sizes["n_layer"] * 2 * sizes["n_embd"] * itemsize


def flash_flops_bytes(batch: int, heads: int, seq: int, head_dim: int,
                      itemsize: int = 2, causal: bool = True,
                      backward: bool = False) -> tuple:
    """(FLOPs, bytes) of one attention call as the algorithm needs them.
    Forward: QK^T and PV, 4 B H T^2 D, halved under a causal mask; reads
    q, k, v and writes o once.  Backward (dq + dkv together): 2.5 x the
    forward matmuls (dP, dV, dQ, dK plus the recomputed scores), reads
    q, k, v, o, do and writes dq, dk, dv."""
    fwd = 4.0 * batch * heads * seq * seq * head_dim
    if causal:
        fwd /= 2.0
    tensor = batch * heads * seq * head_dim * itemsize
    if not backward:
        return fwd, 4.0 * tensor
    return 2.5 * fwd, 8.0 * tensor


def paged_flops_bytes(context_lens, q_len: int, heads: int, head_dim: int,
                      page_size: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one paged-attention call over rows whose live
    contexts are ``context_lens`` (tokens), each with ``q_len`` queries.
    Reads every live page of K and V once (whole pages: the tail of the
    last page moves too), q once, writes o once; 4 H D FLOPs per
    (query, key) pair."""
    flops = 0.0
    nbytes = 0.0
    for ctx in context_lens:
        pages = -(-int(ctx) // page_size)
        flops += 4.0 * q_len * ctx * heads * head_dim
        nbytes += 2.0 * pages * page_size * heads * head_dim * itemsize
        nbytes += 2.0 * q_len * heads * head_dim * itemsize
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """Least time the chip could take and which bound sets it."""
    tc = flops / peak["bf16_flops"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
