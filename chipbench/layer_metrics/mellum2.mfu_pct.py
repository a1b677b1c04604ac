"""Model FLOP/s utilization of a sliding-window / full attention expert
decoder: tokens per second times the FLOPs a trained token needs
(``chipbench.harness.counts_mellum2``: 6 x the matmul multiply-adds, the
held experts' share from the pairs the run's own counter says were
computed here, plus the score and value products over the pairs the masks
let through, forward and backward, recomputation not counted) over chips
times the published bf16 peak."""
NAME = "mellum2.mfu_pct"


def read(run):
    from chipbench.harness import counts_mellum2 as cm

    s, routed = cm.sizes_for(run), run.get("routed")
    if s is None or not routed or not run.get("tokens") \
            or "train_tokens_per_s" not in run["e2e"]:
        return None
    per_token = routed["pairs_local"] / (run["tokens"] * routed["layers"])
    flops = cm.train_flops_per_token(
        s, int(run["traffic"]["batches"]["seq"]), per_token)
    return (100.0 * run["e2e"]["train_tokens_per_s"] * flops
            / (run["n_devices"] * run["peaks"]["bf16_flops"]))
