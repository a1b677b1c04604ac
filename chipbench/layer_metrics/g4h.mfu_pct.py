"""Model FLOP/s utilization of a dense Granite hybrid stack: tokens per
second times the FLOPs a trained token needs
(``chipbench.harness.counts_granite_hybrid``: forward and backward,
recomputation not counted) over chips times the published bf16 peak."""
NAME = "g4h.mfu_pct"


def read(run):
    from chipbench.harness import counts_granite_hybrid as cg

    s = cg.sizes_for(run)
    if s is None or "train_tokens_per_s" not in run["e2e"]:
        return None
    flops = cg.train_flops_per_token(
        s, int(run["traffic"]["batches"]["seq"]))
    return (100.0 * run["e2e"]["train_tokens_per_s"] * flops
            / (run["n_devices"] * run["peaks"]["bf16_flops"]))
