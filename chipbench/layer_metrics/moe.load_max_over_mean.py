"""Imbalance over the experts held: the largest count on one held expert
(the program's ``moe.load_max``) over the mean count a held expert got,
both summed over the window's steps and expert layers.  1.0 is even."""
NAME = "moe.load_max_over_mean"


def read(run):
    routed = run.get("routed")
    if not routed or not routed["pairs_local"]:
        return None
    return routed["load_max"] * routed["experts_held"] / routed["pairs_local"]
