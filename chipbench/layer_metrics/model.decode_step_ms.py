"""Device time of one decode program execution, median over the traced
window.  Every serving program of the engine is a jit named ``pure``, so
the program is told by the host range that launched it: the last
``marker:<engine>:decode`` opened before it started."""
NAME = "model.decode_step_ms"


def read(run):
    from chipbench.harness.stats import median

    durs = [1e3 * d for _n, launcher, _s, d in run["trace"]["modules"]
            if launcher and launcher.endswith(":decode")]
    return median(durs) if durs else None
