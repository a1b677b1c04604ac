"""Python re-tracing and lowering during set-up: the length of the union
of the program's ``trace`` and ``lower`` intervals up to the window's
start (a jit traced inside another once)."""
from chipbench.harness import startup

NAME = "setup.trace_s"


def read(run):
    return startup.read(run, "trace_s")
