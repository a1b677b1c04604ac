"""The scan kernel's share of its roofline in the training step: the least
time the chip could take for the ``ssd_chunk_fwd`` calls the traced steps
need (one a Mamba layer and step; FLOPs and bytes from
``chipbench.harness.counts_hybrid``; the recomputed call of the backward
pass is not counted as needed) over the summed device time of the calls
found in the trace by their output shape.

Under full per-layer recomputation every needed call runs twice, so the
recomputed call HALVES this share: a kernel at its roofline would read
50, and the gap from the reading to 50, not to 100, is the kernel's own
headroom."""
NAME = "ssd_roofline"


def read(run):
    from chipbench.harness import counts_hybrid as ch
    from chipbench.harness.weights_hybrid import sizes_of

    traced = run.get("traced")
    if not traced or "routed" not in run:
        return None
    s, b = sizes_of(run["config"]), run["traffic"]["batches"]
    batch, seq = int(b["batch"]), int(b["seq"])
    spent = ch.kernel_seconds(run["trace"]["op_seconds"],
                              ch.ssd_output_shapes(batch, seq, s))
    if not spent:
        return None
    flops, nbytes = ch.ssd_chunk_flops_bytes(batch, seq, s)
    least = ch.roofline_seconds(flops, nbytes, run["peaks"])[0]
    return 100.0 * traced[0] * s["pattern"].count("M") * least / spent
