"""The flash kernels' share of their roofline at head size 64 with 32
query heads over 8 key/value heads and a softmax scale of the
configuration's own: the least time the chip could take for the attention
the traced steps NEED (forward, dq and dkv of every attention layer; the
forward recomputed in the backward pass is not counted as needed; FLOPs
and bytes from ``chipbench.harness.counts_granite_hybrid``) over the
summed device time of the calls found in the trace by their output shape
(batch x heads, sequence, head size)."""
NAME = "flash_gqa64_roofline"


def read(run):
    from chipbench.harness import counts_granite_hybrid as cg

    traced, s = run.get("traced"), cg.sizes_for(run)
    if not traced or s is None:
        return None
    b = run["traffic"]["batches"]
    batch, seq = int(b["batch"]), int(b["seq"])
    spent = cg.kernel_seconds(run["trace"]["op_seconds"],
                              cg.flash_output_shapes(batch, seq, s))
    if not spent:
        return None
    least = 0.0
    for backward in (False, True):
        flops, nbytes = cg.flash_gqa_flops_bytes(
            batch, s["heads"], s["kv_heads"], seq, s["head_dim"],
            backward=backward)
        least += cg.roofline_seconds(flops, nbytes, run["peaks"])[0]
    return 100.0 * traced[0] * s["pattern"].count("A") * least / spent
