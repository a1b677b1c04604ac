"""The grouped products' share of their roofline in a training step whose
experts are gated (three matrices): the least time the chip could take
for the ``moe_gmm`` calls the traced steps need over the rows THE RUN'S
OWN COUNTER says were routed in those steps (nine products a layer and
step: up, gate and down forward, and in the backward pass three for the
rows and three for the weights; the recomputed forward's three are not
counted as needed, so per-layer recomputation alone holds the share under
75) over the summed device time of the calls found in the trace by their
output shape (as ``moe_gmm_roofline``, at this configuration's sizes)."""
NAME = "moe_gmm_glu_roofline"


def read(run):
    from chipbench.harness import counts_qwen3_next as cq
    from chipbench.harness.weights_qwen3_next import sizes_of

    routed = run.get("routed_traced")
    if not run.get("traced") or not routed or not routed["steps"] or \
            "linear_num_value_heads" not in run.get("config", {}):
        return None
    s = sizes_of(run["config"])
    buffer_rows = run["tokens_per_step"] * s["top_k"]
    spent = cq.kernel_seconds(run["trace"]["op_seconds"],
                              cq.moe_gmm_output_shapes(buffer_rows, s))
    if not spent:
        return None
    layer_steps = routed["steps"] * routed["layers"]
    rows = routed["pairs_local"] / layer_steps
    flops, nbytes = cq.moe_gmm_flops_bytes(
        rows, s["units"], s["expert_width"], s["experts_held"])
    least = cq.roofline_seconds(flops, nbytes, run["peaks"])[0]
    return 100.0 * layer_steps * cq.GMM_CALLS_A_LAYER * least / spent
