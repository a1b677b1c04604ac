"""The flash kernels' share of their roofline in a training step whose
attention has fewer key/value than query heads: the least time the chip
could take for the attention the traced steps NEED (forward, dq and dkv
of every attention layer; the forward recomputed in the backward pass is
not counted as needed, so full per-layer recomputation alone holds the
share under about 80%; FLOPs and bytes from
``chipbench.harness.counts_hybrid`` at the configuration's heads,
key/value heads, head size and the cell's sequence) over the summed
device time of the calls found in the trace by their output shape
(batch x heads, sequence, head size)."""
NAME = "flash_gqa_roofline"


def read(run):
    from chipbench.harness import counts_hybrid as ch
    from chipbench.harness.weights_hybrid import sizes_of

    traced = run.get("traced")
    if not traced or "routed" not in run:
        return None
    s, b = sizes_of(run["config"]), run["traffic"]["batches"]
    batch, seq = int(b["batch"]), int(b["seq"])
    spent = ch.kernel_seconds(run["trace"]["op_seconds"],
                              ch.flash_output_shapes(batch, seq, s))
    if not spent:
        return None
    least = 0.0
    for backward in (False, True):
        flops, nbytes = ch.flash_gqa_flops_bytes(
            batch, s["heads"], s["kv_heads"], seq, s["head_dim"],
            backward=backward)
        least += ch.roofline_seconds(flops, nbytes, run["peaks"])[0]
    return 100.0 * traced[0] * s["pattern"].count("*") * least / spent
