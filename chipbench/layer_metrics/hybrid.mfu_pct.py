"""Model FLOP/s utilization of a hybrid stack: tokens per second times the
FLOPs a trained token needs (``chipbench.harness.counts_hybrid``: forward
and backward, recomputation not counted, the routed experts' share from
the pairs the run's own counter says were computed here) over chips times
the published bf16 peak."""
NAME = "hybrid.mfu_pct"


def read(run):
    from chipbench.harness.counts_hybrid import train_flops_per_token
    from chipbench.harness.weights_hybrid import sizes_of

    routed = run.get("routed")
    if "train_tokens_per_s" not in run["e2e"] or not routed \
            or not run.get("tokens"):
        return None
    per_token = routed["pairs_local"] / (run["tokens"] * routed["layers"])
    flops = train_flops_per_token(
        sizes_of(run["config"]), int(run["traffic"]["batches"]["seq"]),
        per_token)
    return (100.0 * run["e2e"]["train_tokens_per_s"] * flops
            / (run["n_devices"] * run["peaks"]["bf16_flops"]))
