"""Host clock around one ``step`` ending in the loss read back, median
over the window's steps."""
NAME = "trainer.step_ms"


def read(run):
    from chipbench.harness.stats import median

    return 1e3 * median(run["step_s"]) if run.get("step_s") else None
