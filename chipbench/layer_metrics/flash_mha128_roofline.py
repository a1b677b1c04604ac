"""The flash kernels' share of their roofline in a looped decoder's step,
plain multi-head attention (as many key/value as query heads) at head size
128: the least time the chip could take for the attention the traced steps
NEED (one forward call and one backward, dq and dkv, of every layer
application: passes x layers of each a step; the forward is NOT run again
in the backward pass, whose recomputed layer keeps the kernel's output and
logsumexp; FLOPs over the pairs the causal mask lets through and bytes
from ``chipbench.harness.counts_ouro``) over the summed device time of the
calls found in the trace by their output shape (batch x heads, sequence,
head size)."""
NAME = "flash_mha128_roofline"


def read(run):
    from chipbench.harness import counts_ouro as co

    traced, s = run.get("traced"), co.sizes_for(run)
    if not traced or s is None:
        return None
    b = run["traffic"]["batches"]
    batch, seq = int(b["batch"]), int(b["seq"])
    spent = co.kernel_seconds(run["trace"]["op_seconds"],
                              co.flash_output_shapes(batch, seq, s))
    if not spent:
        return None
    least = sum(co.roofline_seconds(
        *co.flash_mha_flops_bytes(batch, seq, s, backward=backward),
        run["peaks"])[0] for backward in (False, True))
    return 100.0 * traced[0] * co.applications(s) * least / spent
