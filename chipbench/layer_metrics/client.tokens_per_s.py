"""New tokens of requests completed inside the window, over the window,
on the client's clock.  Below the knee this is the offered load plus the
window's edges, so in an open-loop cell it is recorded and not judged."""
NAME = "client.tokens_per_s"


def read(run):
    return run.get("client", {}).get("serve_tokens_per_s")
