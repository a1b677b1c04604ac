"""Token-expert pairs computed on the 16 experts this chip holds, per
token and layer, over the window: the program's ``moe.pairs_local``
counter as ``moe.pairs_local_per_token`` reads it, under this
configuration's own name (a test holds that metric's list to its cell).
Even routing of 8 of 64 gives 2.0."""
NAME = "mellum2.pairs_local_per_token"


def read(run):
    from chipbench.harness.counts_mellum2 import sizes_for

    routed = run.get("routed")
    if sizes_for(run) is None or not routed or not run.get("tokens"):
        return None
    return routed["pairs_local"] / (run["tokens"] * routed["layers"])
