"""The grouped products' share of their roofline in the training step: the
least time the chip could take for the ``moe_gmm`` calls the traced steps
need over the rows THE RUN'S OWN COUNTER says were routed in those steps
(six products a layer and step: up and down forward, and in the backward
pass two for the rows and two for the weights; the recomputed forward is
not counted as needed) over the summed device time of the calls found in
the trace by their output shape."""
NAME = "moe_gmm_roofline"


def read(run):
    from chipbench.harness import counts_hybrid as ch
    from chipbench.harness.weights_hybrid import sizes_of

    routed = run.get("routed_traced")
    if not run.get("traced") or not routed or not routed["steps"]:
        return None
    s = sizes_of(run["config"])
    buffer_rows = run["tokens_per_step"] * s["top_k"]
    spent = ch.kernel_seconds(run["trace"]["op_seconds"],
                              ch.moe_gmm_output_shapes(buffer_rows, s))
    if not spent:
        return None
    layer_steps = routed["steps"] * routed["layers"]
    rows = routed["pairs_local"] / layer_steps
    flops, nbytes = ch.moe_gmm_flops_bytes(
        rows, s["units"], s["expert_width"], s["experts_held"])
    least = ch.roofline_seconds(flops, nbytes, run["peaks"])[0]
    return 100.0 * layer_steps * ch.GMM_CALLS_A_LAYER * least / spent
