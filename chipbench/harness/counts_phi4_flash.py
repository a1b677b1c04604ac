"""Operations and bytes one contiguous stage of a SambaY
decoder-hybrid-decoder stack needs, computed from the configuration's
sizes, so that no share of a peak can pass 100%: every score matrix of a
differential head counted ONCE and only over the keys the mask lets a
query see, the selective scan as the bytes it has to move (it is no
matrix product, and ``peaks.py`` publishes no peak for the vector unit).
Recomputation, padding and upcasts do not count.
"""
from __future__ import annotations

from chipbench.harness.counts_hybrid import (  # noqa: F401
    kernel_seconds, roofline_seconds)
from chipbench.harness.weights_phi4_flash import CROSS, GMU, MAMBA, SELF

# steps of the sequence a grid step of the scan kernel takes: the kept
# states' shape, by which the trace's forward calls are found
SCAN_CHUNK = 128


def sizes_for(run: dict):
    """The run's sizes, or None where its configuration is not of this
    family (a reader then has nothing to read)."""
    from chipbench.harness.weights_phi4_flash import sizes_of

    config = run.get("config", {})
    if config.get("model_type") != "phi4flash":
        return None
    return sizes_of(config)


def layers_of(s: dict, kinds: str) -> int:
    """Layers held whose letter is one of ``kinds``."""
    return sum(s["pattern"].count(k) for k in kinds)


def seen_pairs(seq: int, window=None) -> int:
    """(query, key) pairs a causal mask lets through, under a window of
    ``window`` keys where given."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def forward_macs_per_token(s: dict) -> dict:
    """Multiply-adds in matrix products of one token's forward pass, by
    part; the attention scores are apart (:func:`score_flops`)."""
    u, di = s["units"], s["d_inner"]
    hq, hk = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    low = s["dt_rank"] + 2 * s["state"]
    return {
        # fc1 to gate and up, fc2 back
        "feed_forward": len(s["pattern"]) * 3 * u * s["mlp_width"],
        "mamba_proj": layers_of(s, MAMBA) * (u * 2 * di + di * low
                                          + s["dt_rank"] * di + di * u),
        "attention_proj": (layers_of(s, SELF) * (u * (hq + 2 * hk) + hq * u)
                           + layers_of(s, CROSS) * 2 * u * hq),
        "gmu": layers_of(s, GMU) * 2 * u * di,
        "head": u * s["vocab"],
    }


def score_flops(batch: int, seq: int, s: dict, window=None) -> float:
    """FLOPs of one differential-attention layer's forward score and
    value products as the algorithm needs them: every query head one
    score matrix against a key head (2 D a pair) and one product with the
    value, two key heads wide (4 D a pair), over the pairs the mask lets
    through."""
    return (6.0 * s["head_dim"] * batch * s["heads"]
            * seen_pairs(seq, window))


def layer_windows(s: dict) -> list:
    """The window (None: none) of every attention layer held."""
    return [s["window"] if k == "S" else None
            for k in s["pattern"] if k in SELF + CROSS]


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward + backward FLOPs a trained token: 6 x the matmul
    multiply-adds + 3 x the forward score and value products (the
    backward's are twice the forward's), those over the pairs the masks
    let through.  Recomputation not counted."""
    macs = sum(forward_macs_per_token(s).values())
    scores = sum(score_flops(1, seq, s, w) for w in layer_windows(s))
    return 6.0 * macs + 3.0 * scores / seq


def flash_diff_flops_bytes(batch: int, seq: int, s: dict, window=None,
                           itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one differential flash FORWARD call: reads q
    over the query heads, k over the key heads, the values (half as many
    heads, twice as wide) and writes o, two key heads wide a query
    head."""
    d = s["head_dim"]
    nbytes = batch * seq * d * itemsize * (s["heads"] + 2 * s["kv_heads"]
                                           + 2 * s["heads"])
    return score_flops(batch, seq, s, window), nbytes


def flash_diff_forward_shapes(batch: int, seq: int, s: dict) -> list:
    """The per-row logsumexp only the forward kernel writes."""
    return [(batch * s["heads"], 1, seq)]


def sscan_bytes(batch: int, seq: int, s: dict, itemsize: int = 2) -> float:
    """Bytes one forward scan has to move, in the configuration's compute
    type: x and dt in, y out over the channels, B and C over the states,
    the decay rates once."""
    return (batch * seq * (3 * s["d_inner"] + 2 * s["state"]) * itemsize
            + s["d_inner"] * s["state"] * 4)


def sscan_forward_shapes(batch: int, seq: int, s: dict) -> list:
    """The states a chunk starts from, which only the forward kernel
    writes."""
    return [(batch, -(-seq // SCAN_CHUNK), s["state"], s["d_inner"])]
