"""Model FLOP/s utilization: tokens per second times the FLOPs a token
needs (forward and backward, recomputation not counted; the count is
``chipbench.harness.counts``) over chips times the published bf16 peak."""
NAME = "trainer.mfu_pct"


def read(run):
    from chipbench.harness.counts import gpt2_train_flops_per_token

    if "train_tokens_per_s" not in run["e2e"]:
        return None
    cfg = run["config"]
    seq = int(run["traffic"]["batches"]["seq"])
    flops = gpt2_train_flops_per_token(cfg, seq)
    return (100.0 * run["e2e"]["train_tokens_per_s"] * flops
            / (run["n_devices"] * run["peaks"]["bf16_flops"]))
