"""Model FLOP/s utilization of a SambaY stage: tokens per second times the
FLOPs a trained token needs (``chipbench.harness.counts_phi4_flash``:
6 x the matmul multiply-adds plus the score and value products over the
pairs the masks let through, forward and backward, recomputation not
counted; the selective scan is no matrix product and counts nothing) over
chips times the published bf16 peak."""
NAME = "p4f.mfu_pct"


def read(run):
    from chipbench.harness import counts_phi4_flash as cp

    s = cp.sizes_for(run)
    if s is None or "train_tokens_per_s" not in run["e2e"]:
        return None
    flops = cp.train_flops_per_token(
        s, int(run["traffic"]["batches"]["seq"]))
    return (100.0 * run["e2e"]["train_tokens_per_s"] * flops
            / (run["n_devices"] * run["peaks"]["bf16_flops"]))
