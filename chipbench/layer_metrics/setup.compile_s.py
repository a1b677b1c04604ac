"""Waiting for executables during set-up: the length of the union of the
program's ``compile`` intervals (every request to the backend, compiled
or loaded) and ``cache_load`` intervals up to the window's start."""
from chipbench.harness import startup

NAME = "setup.compile_s"


def read(run):
    return startup.read(run, "compile_s")
