"""Token-expert pairs computed on the 8 experts this chip holds, per token
and expert layer, over the window: the program's ``moe.pairs_local``
counter as ``moe.pairs_local_per_token`` reads it, under this
configuration's own name.  Even routing of 6 of 64 gives 0.75."""
NAME = "moonlight.pairs_local_per_token"


def read(run):
    from chipbench.harness.counts_moonlight import sizes_for

    routed = run.get("routed")
    if sizes_for(run) is None or not routed or not run.get("tokens"):
        return None
    return routed["pairs_local"] / (run["tokens"] * routed["layers"])
