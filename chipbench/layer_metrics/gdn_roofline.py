"""The delta-rule kernel's share of its roofline in the training step: the
least time the chip could take for the ``gdn_chunk_fwd`` calls the traced
steps need (one a Gated DeltaNet layer and step; FLOPs and bytes from
``chipbench.harness.counts_qwen3_next``; the recomputed call of the
backward pass is not counted as needed) over the summed device time of the
calls found in the trace by their output shape (batch, sequence, value
heads x value size, float32).  It reads the form that is on the timed
path: the kernel.  The rule's backward is XLA (scope ``gdn_chunk_bwd``),
no call of this kind, and is in neither term.

Under full per-layer recomputation every needed call runs twice, so the
recomputed call HALVES this share (as ``ssd_roofline``): a kernel at its
roofline would read 50.  On a program with no such kernel nothing is
found and nothing is returned."""
NAME = "gdn_roofline"


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
                              cq.gdn_output_shapes(batch, seq, s))
    if not spent:
        return None
    flops, nbytes = cq.gdn_chunk_flops_bytes(batch, seq, s)
    least = cq.roofline_seconds(flops, nbytes, run["peaks"])[0]
    return 100.0 * traced[0] * s["pattern"].count("L") * least / spent
