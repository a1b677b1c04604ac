"""How late the load generator ran: actual submit minus due instant, 95th
percentile over requests due in the window.  A starved generator must not
be read as a fast server."""
NAME = "loadgen.lag_p95_ms"


def read(run):
    return run.get("client", {}).get("lag_p95_ms")
