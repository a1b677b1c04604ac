"""Model FLOP/s utilization of a latent-attention expert decoder's WHOLE
step: tokens per second times the FLOPs a trained token needs
(``chipbench.harness.counts_moonlight``: 6 x the matmul multiply-adds, the
held experts' share from the pairs the run's own counter says were
computed here, plus the score products over 192 dimensions and the value
products over 128 for the pairs the causal mask lets through, forward and
backward, recomputation not counted) over chips times the published bf16
peak."""
NAME = "moonlight.mfu_pct"


def read(run):
    from chipbench.harness import counts_moonlight as cm

    s, routed = cm.sizes_for(run), run.get("routed")
    if s is None or not routed or not run.get("tokens") \
            or "train_tokens_per_s" not in run["e2e"]:
        return None
    per_token = routed["pairs_local"] / (run["tokens"] * routed["layers"])
    flops = cm.train_flops_per_token(
        s, int(run["traffic"]["batches"]["seq"]), per_token)
    return (100.0 * run["e2e"]["train_tokens_per_s"] * flops
            / (run["n_devices"] * run["peaks"]["bf16_flops"]))
