"""The differential flash FORWARD kernels' share of their roofline: the
least time the chip could take for the score and value products the
traced steps need (one forward call an attention layer and step: every
query head's one score matrix against a key head 64 wide and its product
with a value 128 wide, over the (query, key) pairs the mask lets through,
which under the 512 window are 512 keys a query; FLOPs and bytes from
``chipbench.harness.counts_phi4_flash``) over the summed device time of
the forward calls, found in the trace by the output only they have (the
per-row logsumexp: batch x heads, 1, sequence).

Under full per-layer recomputation every needed call runs twice, so the
recomputed call HALVES this share; and a 64-deep contraction fills half
the MXU, which the published peak does not know."""
NAME = "flash_diff_roofline"


def read(run):
    from chipbench.harness import counts_phi4_flash as cp

    traced, s = run.get("traced"), cp.sizes_for(run)
    if not traced or s is None:
        return None
    b = run["traffic"]["batches"]
    batch, seq = int(b["batch"]), int(b["seq"])
    spent = cp.kernel_seconds(run["trace"]["op_seconds"],
                              cp.flash_diff_forward_shapes(batch, seq, s))
    if not spent:
        return None
    least = sum(cp.roofline_seconds(
        *cp.flash_diff_flops_bytes(batch, seq, s, window), run["peaks"])[0]
        for window in cp.layer_windows(s))
    return 100.0 * traced[0] * least / spent
