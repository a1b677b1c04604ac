"""The selective-scan kernel's share of its roofline, a BYTES roofline:
the least time the chip could take for the ``sscan_fwd`` calls the traced
steps need (one a Mamba-1 layer and step) is the time its memory takes to
move x, dt, B, C in and y out once in the configuration's compute type
(``chipbench.harness.counts_phi4_flash.sscan_bytes``); the elementwise
work is counted against no peak, because ``harness/peaks.py`` publishes
none for the vector unit.  Over the summed device time of the forward
calls, found in the trace by the output only they have (the state every
chunk starts from: batch, chunks, states, channels).

Under full per-layer recomputation every needed call runs twice (4 calls
a step for two layers), so the recomputed call HALVES this share: a
kernel at its roofline would read 50."""
NAME = "sscan_roofline"


def read(run):
    from chipbench.harness import counts_phi4_flash as cp

    traced, s = run.get("traced"), cp.sizes_for(run)
    if not traced or s is None:
        return None
    b = run["traffic"]["batches"]
    batch, seq = int(b["batch"]), int(b["seq"])
    spent = cp.kernel_seconds(run["trace"]["op_seconds"],
                              cp.sscan_forward_shapes(batch, seq, s))
    if not spent:
        return None
    least = cp.sscan_bytes(batch, seq, s) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * traced[0] * cp.layers_of(s, cp.MAMBA) * least / spent
