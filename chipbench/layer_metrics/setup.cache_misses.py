"""Of the programs got during set-up, those compiled AND written to the
persistent cache (jax's ``cache_misses`` event): every program over the
cache's minimum compile time on a machine's first run, none on a warm
one.  What tells ``first_setup_s`` from ``setup_s``, and a noisy pair from
a slower program."""
from chipbench.harness import startup

NAME = "setup.cache_misses"


def read(run):
    return startup.read(run, "cache_misses")
