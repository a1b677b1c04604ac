"""The latent-attention flash FORWARD kernels' share of their roofline: the
least time the chip could take for the score and value products the traced
steps need (one forward call a layer and step, the recomputed half-layer
keeps the kernel's output and logsumexp: every head's score over 128 + 64
dimensions and its product with values of 128, over the pairs the causal
mask lets through; bytes of q, the 128-wide keys, the ONE 64-wide rotary
key, the values and the output; ``chipbench.harness.counts_moonlight``)
over the summed device time of the forward calls, found in the trace by
the output only they have (the per-row logsumexp: batch x heads, 1,
sequence).  The published widths are counted whatever form the kernels
give the sum (two products in the tile, or one product 256 wide)."""
NAME = "flash_mla_roofline"


def read(run):
    from chipbench.harness import counts_moonlight as cm

    traced, s = run.get("traced"), cm.sizes_for(run)
    if not traced or s is None:
        return None
    b = run["traffic"]["batches"]
    batch, seq = int(b["batch"]), int(b["seq"])
    spent = cm.kernel_seconds(run["trace"]["op_seconds"],
                              cm.flash_forward_shapes(batch, seq, s))
    if not spent:
        return None
    least = cm.roofline_seconds(*cm.flash_mla_flops_bytes(batch, seq, s),
                                run["peaks"])[0]
    return 100.0 * traced[0] * len(s["pattern"]) * least / spent
