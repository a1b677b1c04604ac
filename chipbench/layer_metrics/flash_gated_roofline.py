"""The flash kernels' share of their roofline in a training step whose
full-attention layers have 16 query heads over 2 key/value heads at head
size 256: the least time the chip could take for the attention the traced
steps NEED (forward, dq and dkv of every full-attention layer; the forward
recomputed in the backward pass is not counted as needed, so per-layer
recomputation alone holds the share under about 80; FLOPs and bytes from
``counts_hybrid.flash_gqa_flops_bytes`` at this configuration's heads,
key/value heads, head size and the cell's sequence) over the summed device
time of the calls found in the trace by their output shape (batch x heads,
sequence, head size).  Rotary positions, the q/k norms and the output gate
are XLA around the kernel and in neither term."""
NAME = "flash_gated_roofline"


def read(run):
    from chipbench.harness import counts_qwen3_next as cq
    from chipbench.harness.weights_qwen3_next import sizes_of

    traced = run.get("traced")
    if not traced or "routed" not in run or \
            "linear_num_value_heads" not in run.get("config", {}):
        return None
    s, b = sizes_of(run["config"]), run["traffic"]["batches"]
    batch, seq = int(b["batch"]), int(b["seq"])
    spent = cq.kernel_seconds(run["trace"]["op_seconds"],
                              cq.flash_output_shapes(batch, seq, s))
    if not spent:
        return None
    least = 0.0
    for backward in (False, True):
        flops, nbytes = cq.flash_gqa_flops_bytes(
            batch, s["heads"], s["kv_heads"], seq, s["head_dim"],
            backward=backward)
        least += cq.roofline_seconds(flops, nbytes, run["peaks"])[0]
    return 100.0 * traced[0] * s["pattern"].count("F") * least / spent
