"""The flash kernels' share of their roofline in the training step: the
least time the chip could take for the attention the traced steps need
(forward, dq and dkv of every layer; FLOPs and bytes from
``chipbench.harness.counts`` at the cell's batch, heads and sequence)
over the summed device time of the step's Pallas kernels.  The training
step's only Pallas kernels are the flash kernels."""
NAME = "flash_roofline"


def read(run):
    from chipbench.harness import counts

    traced = run.get("traced")
    kernel_s = run["trace"].get("kernel_seconds")
    if not traced or not kernel_s:
        return None
    steps = traced[0]
    cfg, b = run["config"], run["traffic"]["batches"]
    shape = (int(b["batch"]), cfg["n_head"], int(b["seq"]),
             cfg["n_embd"] // cfg["n_head"])
    least = 0.0
    for backward in (False, True):
        flops, nbytes = counts.flash_flops_bytes(*shape, itemsize=2,
                                                 causal=True,
                                                 backward=backward)
        least += counts.roofline_seconds(flops, nbytes, run["peaks"])[0]
    return 100.0 * steps * cfg["n_layer"] * least / kernel_s
