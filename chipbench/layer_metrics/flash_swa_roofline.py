"""The grouped-query flash FORWARD kernels' share of their roofline in a
stack whose layers attend under a window or in full: the least time the
chip could take for the score and value products the traced steps need
(one forward call a layer and step: every query head's score matrix and
its product with the values, over the (query, key) pairs the layer's mask
lets through, which under the 1,024 window are 1,024 keys a query; FLOPs
and bytes from ``chipbench.harness.counts_mellum2``) over the summed
device time of the forward calls, found in the trace by the output only
they have (the per-row logsumexp: batch x heads, 1, sequence).

Windowed and full calls share that shape, so the share is of all four
layers together and cannot be split by kind until the trace names its
kernels.  Under full per-layer recomputation every needed call runs
twice, so the recomputed call HALVES this share."""
NAME = "flash_swa_roofline"


def read(run):
    from chipbench.harness import counts_mellum2 as cm

    traced, s = run.get("traced"), cm.sizes_for(run)
    if not traced or s is None:
        return None
    b = run["traffic"]["batches"]
    batch, seq = int(b["batch"]), int(b["seq"])
    spent = cm.kernel_seconds(run["trace"]["op_seconds"],
                              cm.flash_forward_shapes(batch, seq, s))
    if not spent:
        return None
    least = sum(cm.roofline_seconds(
        *cm.flash_swa_flops_bytes(batch, seq, s, window), run["peaks"])[0]
        for window in cm.layer_windows(s))
    return 100.0 * traced[0] * least / spent
