"""From where ``run.py`` first read the clock to the instant the cell's
``ShardedTrainer`` entered ``build``: jax and libtpu imported, the backend
found, the package's modules, the model built, the weights drawn and
loaded."""
from chipbench.harness import startup

NAME = "setup.before_build_s"


def read(run):
    return startup.read(run, "before_build_s")
