"""Share of the window that its stalled steps took over what such a
phase takes by its running median: the share by which the run's
``train_tokens_per_s`` reads low.  From the program's own stall log."""
from chipbench.harness import stalls

NAME = "trainer.stall_pct"


def read(run):
    got = stalls.in_window(run)
    if got is None:
        return None
    w0, w1 = run["window"]
    return 100.0 * sum(over for _rec, over in got) / (w1 - w0)
