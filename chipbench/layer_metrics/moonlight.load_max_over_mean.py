"""Imbalance over the 8 experts held: the largest count on one held expert
(the program's ``moe.load_max``) over the mean count a held expert got,
both summed over the window's steps and expert layers, as
``moe.load_max_over_mean`` reads it, under this configuration's own name.
1.0 is even."""
NAME = "moonlight.load_max_over_mean"


def read(run):
    from chipbench.harness.counts_moonlight import sizes_for

    routed = run.get("routed")
    if sizes_for(run) is None or not routed or not routed["pairs_local"]:
        return None
    return routed["load_max"] * routed["experts_held"] / routed["pairs_local"]
