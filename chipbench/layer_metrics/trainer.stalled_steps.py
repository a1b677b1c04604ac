"""Steps of the window that stalled: records of the program's own stall
log (``observability.stalls.log()``) that started inside the window on a
phase its thread waits in.  0.0 in a run with none."""
from chipbench.harness import stalls

NAME = "trainer.stalled_steps"


def read(run):
    got = stalls.in_window(run)
    return None if got is None else float(len(got))
