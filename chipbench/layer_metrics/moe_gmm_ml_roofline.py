"""The grouped products' share of their roofline in a training step whose
gated experts (three matrices, 2,048 x 1,408) see 0.75 local pairs a
token: as ``moe_gmm_m2_roofline`` at this configuration's sizes
(``chipbench.harness.counts_moonlight``): the least time the chip could
take for the nine products a layer and step over the rows THE RUN'S OWN
COUNTER says were routed in the traced steps (the recomputed forward's
three are not counted as needed) over the summed device time of the calls
found in the trace by their output shape."""
NAME = "moe_gmm_ml_roofline"


def read(run):
    from chipbench.harness import counts_moonlight as cm

    routed, s = run.get("routed_traced"), cm.sizes_for(run)
    if not run.get("traced") or not routed or not routed["steps"] \
            or s is None:
        return None
    buffer_rows = run["tokens_per_step"] * s["top_k"]
    spent = cm.kernel_seconds(run["trace"]["op_seconds"],
                              cm.moe_gmm_output_shapes(buffer_rows, s))
    if not spent:
        return None
    layer_steps = routed["steps"] * routed["layers"]
    rows = routed["pairs_local"] / layer_steps
    flops, nbytes = cm.moe_gmm_flops_bytes(
        rows, s["units"], s["expert_width"], s["experts_held"])
    least = cm.roofline_seconds(flops, nbytes, run["peaks"])[0]
    return 100.0 * layer_steps * cm.GMM_CALLS_A_LAYER * least / spent
