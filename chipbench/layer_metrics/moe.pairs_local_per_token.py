"""Token-expert pairs computed on the experts this chip holds, per token
and expert layer, over the window (the program's ``moe.pairs_local``
counter, carried in the step's payload).  Even routing over E experts of
which H are held gives top_k x H / E."""
NAME = "moe.pairs_local_per_token"


def read(run):
    routed = run.get("routed")
    if not routed or not run.get("tokens"):
        return None
    return routed["pairs_local"] / (run["tokens"] * routed["layers"])
