"""Programs the device runs per training step: every program executed on
device 0 from the start of the first ``jit_trainer_step`` in the traced
window to the start of the last, over the steps in that stretch (the
executions of ``jit_trainer_step`` less one).  1.0 is the step alone;
each program more is a launch the host makes between steps."""
NAME = "trainer.launches_per_step"
PROGRAM = "jit_trainer_step"


def read(run):
    modules = run["trace"]["modules"]
    steps = sorted(start for name, _launcher, start, _dur in modules
                   if name.split("(")[0] == PROGRAM)
    if len(steps) < 2:
        return None
    launched = sum(1 for _name, _launcher, start, _dur in modules
                   if steps[0] <= start < steps[-1])
    return launched / (len(steps) - 1)
