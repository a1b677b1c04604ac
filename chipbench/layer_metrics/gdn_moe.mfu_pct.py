"""Model FLOP/s utilization of a Gated DeltaNet / gated attention / sparse
expert stack: tokens per second times the FLOPs a trained token needs
(``chipbench.harness.counts_qwen3_next``: forward and backward,
recomputation not counted, the held experts' share from the pairs the
run's own counter says were computed here) over chips times the published
bf16 peak."""
NAME = "gdn_moe.mfu_pct"


def read(run):
    from chipbench.harness.counts_qwen3_next import train_flops_per_token
    from chipbench.harness.weights_qwen3_next import sizes_of

    routed = run.get("routed")
    if "train_tokens_per_s" not in run["e2e"] or not routed \
            or not run.get("tokens") \
            or "linear_num_value_heads" not in run.get("config", {}):
        return None
    per_token = routed["pairs_local"] / (run["tokens"] * routed["layers"])
    flops = train_flops_per_token(
        sizes_of(run["config"]), int(run["traffic"]["batches"]["seq"]),
        per_token)
    return (100.0 * run["e2e"]["train_tokens_per_s"] * flops
            / (run["n_devices"] * run["peaks"]["bf16_flops"]))
