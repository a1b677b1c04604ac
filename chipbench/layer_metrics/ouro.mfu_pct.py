"""Model FLOP/s utilization of a looped decoder's WHOLE step: tokens per
second times the FLOPs a trained token needs
(``chipbench.harness.counts_ouro``: 6 x the matmul multiply-adds of every
pass over the one stack, a head and a gate a pass, plus the score and
value products over the pairs the causal mask lets through, forward and
backward, recomputation not counted) over chips times the published bf16
peak."""
NAME = "ouro.mfu_pct"


def read(run):
    from chipbench.harness import counts_ouro as co

    s = co.sizes_for(run)
    if s is None or "train_tokens_per_s" not in run["e2e"]:
        return None
    flops = co.train_flops_per_token(
        s, int(run["traffic"]["batches"]["seq"]))
    return (100.0 * run["e2e"]["train_tokens_per_s"] * flops
            / (run["n_devices"] * run["peaks"]["bf16_flops"]))
