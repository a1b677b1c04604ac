"""Device time of one execution of the trainer's compiled step, median
over the traced window.  The program is told by its name in the trace,
``jit_trainer_step``, never by the host range that launched it: a launch
is asynchronous, and the next range may open before the program starts."""
NAME = "trainer.device_step_ms"
PROGRAM = "jit_trainer_step"


def read(run):
    from chipbench.harness.stats import median

    durs = [1e3 * dur for name, _launcher, _start, dur
            in run["trace"]["modules"] if name.split("(")[0] == PROGRAM]
    return median(durs) if durs else None
