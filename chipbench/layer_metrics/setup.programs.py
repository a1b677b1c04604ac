"""Requests to the backend during set-up: each one a program that was
compiled or fetched from the persistent cache."""
from chipbench.harness import startup

NAME = "setup.programs"


def read(run):
    return startup.read(run, "programs")
