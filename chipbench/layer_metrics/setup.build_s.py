"""``ShardedTrainer.build`` from start to end, by the one record the
trainer leaves of itself: shapes settled, the optimizer's state made,
parameters and state placed on the device.  The step is not compiled
there (the first ``step`` does that), so its trace and compile fall
outside."""
from chipbench.harness import startup

NAME = "setup.build_s"


def read(run):
    return startup.read(run, "build_s")
