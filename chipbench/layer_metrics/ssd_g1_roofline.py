"""The scan kernel's share of its roofline at ONE B/C group (a group cut
into head blocks, ``ops/ssd.py ssd_plan``): the least time the chip could
take for the ``ssd_chunk_fwd`` calls the traced steps need (one a Mamba
layer and step; FLOPs and bytes from
``chipbench.harness.counts_granite_hybrid``: ``C B^T`` once a group
however many head blocks compute it; the recomputed call of the backward
pass is not counted as needed) over the summed device time of the calls
found in the trace by their output shape (batch, sequence, heads x head
size, float32).

Under full per-layer recomputation every needed call runs twice (18 calls
a step for nine layers), so the recomputed call HALVES this share, as
``ssd_roofline``: a kernel at its roofline would read 50."""
NAME = "ssd_g1_roofline"


def read(run):
    from chipbench.harness import counts_granite_hybrid as cg

    traced, s = run.get("traced"), cg.sizes_for(run)
    if not traced or s is None:
        return None
    b = run["traffic"]["batches"]
    batch, seq = int(b["batch"]), int(b["seq"])
    spent = cg.kernel_seconds(run["trace"]["op_seconds"],
                              cg.ssd_output_shapes(batch, seq, s))
    if not spent:
        return None
    flops, nbytes = cg.ssd_chunk_flops_bytes(batch, seq, s)
    least = cg.roofline_seconds(flops, nbytes, run["peaks"])[0]
    return 100.0 * traced[0] * s["pattern"].count("M") * least / spent
