"""The latest that the program's witness thread, which asks to be woken
every 20 ms, was woken inside the window: whether this process was run
when it asked to be, in every run, stalled or not."""
from chipbench.harness import stalls

NAME = "trainer.host_late_ms"


def read(run):
    late = stalls.late_in_window(run)
    return None if late is None else 1e3 * late
