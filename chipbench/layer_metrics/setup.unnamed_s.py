"""``setup_s`` less everything that has a name: the union of the stretch
before ``build``, ``build``, and every trace, lowering, compile and cache
load up to the window's start.  What is left is the checked and warm
steps' device time, the checks' read-backs and the benchmark's own work:
the root span's self time."""
from chipbench.harness import startup

NAME = "setup.unnamed_s"


def read(run):
    return startup.read(run, "unnamed_s")
