"""From the end of one execution of the trainer's compiled step
(``jit_trainer_step``, told by its name in the trace) to the start of the
next, median over the traced window: what the device waits for the host
between steps, small programs launched meanwhile included.  With
``trainer.device_step_ms`` it adds up to the step."""
NAME = "trainer.step_gap_ms"
PROGRAM = "jit_trainer_step"


def read(run):
    from chipbench.harness.stats import median

    steps = sorted((start, start + dur) for name, _launcher, start, dur
                   in run["trace"]["modules"]
                   if name.split("(")[0] == PROGRAM)
    gaps = [1e3 * (nxt[0] - this[1]) for this, nxt in zip(steps, steps[1:])]
    return median(gaps) if gaps else None
