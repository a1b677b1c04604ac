"""Serving cells: the program's ``InferenceEngine`` under open- or
closed-loop traffic.

set-up (weights from the seed on the device, engine, warm-up of exactly
the cell's lattice, a lead-in that fills the engine) -> the measured
window on the client's clock -> drain -> the engine is stopped and freed
-> the plain reference runs over a seeded sample of what was served.
"""
from __future__ import annotations

import gc
import math
import threading
import time

import numpy as np

from chipbench.drivers import gpt2_program as prog
from chipbench.harness import env, loadgen, stats
from chipbench.harness.weights import make_weights


def _engine_kwargs(config: dict) -> dict:
    kw = dict(config["engine"])
    for k in ("seq_buckets", "batch_buckets"):
        if k in kw:
            kw[k] = tuple(kw[k])
    return kw


def build(config: dict, seed: int, control: str | None = None):
    """The engine as deployed: bf16 weights from the seed, warmed.  With
    ``control="kv_int8"`` the program's own lower-precision path is
    switched on (int8 KV pages): the control that a run's comparison has
    to fail."""
    from mxnet_tpu.serving import InferenceEngine

    dtype = config["serving"]["dtype"]
    t0 = time.monotonic()
    weights = make_weights(prog.sizes_of(config), seed, dtype)
    net = prog.build_net(config)
    prog.load_weights(net, weights, dtype=dtype, trainable=False)
    del weights
    t1 = time.monotonic()
    kw = _engine_kwargs(config)
    if control == "kv_int8":
        kw["kv_quant"] = "int8"
    eng = InferenceEngine(net, **kw)
    env.say(phase="build", weights_s=round(t1 - t0, 3),
            engine_s=round(time.monotonic() - t1, 3))
    return net, eng


def warm_traffic(eng, config: dict):
    """A few real requests through the started engine, covering every
    point of the lattice on the live path (one short prompt, one longer
    than a prefill chunk, then a burst of each for every batch bucket
    above one), and waited for.  ``warmup()`` alone is not enough: it runs
    the decode program on freshly made caches, and the first live step,
    on caches that a prefill has returned, compiled that program again
    with the engine's own counter unmoved (PERF.md, Findings)."""
    e = config["engine"]
    vocab, chunk = config["vocab_size"], int(e["prefill_chunk"])
    rng = np.random.default_rng(0)
    waves = [[8], [chunk + 8]]
    for bb in e["batch_buckets"]:
        if bb > 1:
            waves += [[8] * bb, [chunk + 8] * bb]
    for wave in waves:
        futs = [eng.submit(rng.integers(0, vocab, n).astype("int32"),
                           max_new_tokens=4) for n in wave]
        for f in futs:
            f.result(timeout=1200)


def _counter_snapshot(eng) -> dict:
    """The engine's own counts, read through ``eng.stats()``."""
    s = eng.stats()
    flat = {}
    for section in ("requests", "batches", "tokens", "prefix_cache",
                    "compile_cache"):
        for k, v in s[section].items():
            if isinstance(v, int) and not isinstance(v, bool):
                flat[k] = v
    flat["preemptions"] = s["overload"]["preemptions"]
    flat["nonfinite_outputs"] = s["resilience"]["nonfinite_outputs"]
    return flat


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b if k in a}


class _Tracing:
    """The traced sub-window of a ``--trace 1`` run, driven from its own
    thread: the program's tracer for request spans, ``jax.profiler`` for
    the device, counter snapshots at both ends."""

    def __init__(self, eng, offset_s: float, length_s: float, out_dir: str):
        self.eng, self.offset_s, self.length_s = eng, offset_s, length_s
        self.out_dir = out_dir
        self.start_at = None
        self.counters = None
        self.error = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="chipbench-trace")

    def start(self, window_opens: float):
        self.start_at = window_opens + self.offset_s
        self._thread.start()

    def _run(self):
        import jax

        try:
            while time.monotonic() < self.start_at:
                time.sleep(min(0.05, max(0.0, self.start_at
                                         - time.monotonic())))
            before = _counter_snapshot(self.eng)
            jax.profiler.start_trace(self.out_dir)
            with jax.profiler.TraceAnnotation("chipbench:window"):
                time.sleep(self.length_s)
            after = _counter_snapshot(self.eng)
            jax.profiler.stop_trace()
            self.counters = _delta(before, after)
        except Exception as e:              # reported, fails the run
            self.error = f"{type(e).__name__}: {e}"

    def join(self):
        self._thread.join(timeout=300)
        if self._thread.is_alive():
            self.error = "trace thread did not end"


def _traffic_once(gen, eng, traffic, config, seed, seconds, lead_s,
                  drain_s, rate=None, tracing=None):
    """One lead-in + window + drain of the cell's traffic.  Returns the
    client's records and the window's bounds."""
    vocab = config["vocab_size"]
    horizon = lead_s + seconds
    reqs = gen.generate(traffic, seed, horizon, vocab, rate=rate,
                        lead_in_s=lead_s)

    def submit(tokens, new_tokens):
        return eng.submit(tokens, max_new_tokens=new_tokens)

    t0 = time.monotonic() + 0.05
    w0, w1 = t0 + lead_s, t0 + lead_s + seconds
    if tracing is not None:
        tracing.start(w0)
    if traffic["kind"] == "open_loop":
        recs = loadgen.open_loop(submit, reqs, t0, drain_s)
    elif traffic["kind"] == "closed_loop":
        while time.monotonic() < t0:
            time.sleep(0.001)
        recs = loadgen.closed_loop(submit, reqs, int(traffic["clients"]),
                                   lambda: w1, drain_s)
    else:
        raise ValueError(f"serve driver cannot run kind "
                         f"{traffic['kind']!r}")
    return recs, (w0, w1)


def _window_metrics(recs, window, open_loop: bool) -> dict:
    w0, w1 = window
    seconds = w1 - w0
    done_in = [r for r in recs if r.ok and w0 <= r.t_done <= w1]
    tokens = sum(r.new_tokens for r in done_in)
    if open_loop:
        # every request that was due in the window: a failed, refused or
        # unfinished one counts as worse than any
        due_in = [r for r in recs if w0 <= r.t_due < w1]
    else:
        # a closed loop's clients are always waiting on something: what
        # the window saw end, and what was refused or failed in it; a
        # request still in flight at the end is no failure
        due_in = done_in + [r for r in recs if not r.ok
                            and r.error != loadgen.UNFINISHED
                            and w0 <= r.t_due < w1]
    per_tok = [1e3 * (r.t_done - r.t_due) / r.new_tokens if r.ok
               else math.inf for r in due_in]
    out = {
        "serve_tokens_per_s": tokens / seconds,
        "attempted": len(due_in),
        "failed": sum(1 for r in due_in if not r.ok),
        "completed_in_window": len(done_in),
        "errors": sorted({r.error for r in recs if r.error})[:5],
    }
    if per_tok:
        out["ms_per_token_p50"] = stats.percentile(per_tok, 50)
        out["ms_per_token_p95"] = stats.percentile(per_tok, 95)
    if open_loop:
        lag = [1e3 * (r.t_submit - r.t_due) for r in due_in]
        if lag:
            out["lag_p95_ms"] = stats.percentile(lag, 95)
            out["lag_max_ms"] = max(lag)
    return out


def _check_sample(recs, window, seed: int, k: int):
    """A seeded sample of the requests finished inside the window, the
    longest among them."""
    w0, w1 = window
    done = [r for r in recs if r.ok and w0 <= r.t_done <= w1]
    if not done:
        return []
    done.sort(key=lambda r: r.idx)
    longest = max(done, key=lambda r: (r.prompt_len + r.new_tokens, r.idx))
    rng = np.random.default_rng(int(seed) + 1)
    rest = [r for r in done if r is not longest]
    pick = list(rng.permutation(len(rest))[:max(0, k - 1)])
    return [longest] + [rest[i] for i in sorted(pick)]


def _echo_faults(recs) -> int:
    """Requests whose result is not their prompt followed by exactly the
    new tokens asked for."""
    bad = 0
    for r in recs:
        if not r.ok:
            continue
        out = np.asarray(r.result)
        if out.ndim != 1 or len(out) != r.prompt_len + r.new_tokens:
            bad += 1
    return bad


def reference_gaps(config, seed, sample, control=None):
    """The reference pass over the sampled requests' served sequences."""
    from chipbench.reference import gpt2_ref as ref

    sizes = prog.sizes_of(config)
    weights = make_weights(sizes, seed, config["serving"]["dtype"])
    seqs = [np.asarray(r.result, "int32") for r in sample]
    gaps = ref.teacher_forced_gaps(
        weights, seqs, [r.prompt_len for r in sample],
        n_head=sizes["n_head"], eps=sizes["layer_norm_epsilon"],
        pad_to=sizes["n_positions"], control=control)
    return gaps


def _gap_summary(gaps) -> dict:
    n = sum(g["tokens"] for g in gaps) or 1
    return {"widest": max((g["widest"] for g in gaps), default=None),
            "mean": sum(g["sum"] for g in gaps) / n,
            "worst_request_mean": max(
                (g["sum"] / g["tokens"] for g in gaps), default=None),
            "below_best_share": sum(g["below_best"] for g in gaps) / n,
            "tokens": n, "requests": len(gaps)}


def judge_gaps(summary: dict, limits: dict, any_sample: bool) -> list:
    """The logit-gap numbers held to the configuration's limits: the mean
    over all sampled served tokens, and the worst request's own mean (one
    wrong token in a short answer moves the second and hardly the
    first).  Nothing sampled fails both."""
    checks = []
    for what, key in (("served_mean_logit_gap_share", "mean"),
                      ("worst_request_mean_logit_gap_share",
                       "worst_request_mean")):
        value = summary[key] if any_sample else math.inf
        limit = float(limits[key + "_gap"])
        checks.append({"what": what, "value": value, "limit": limit,
                       "ok": value <= limit})
    return checks


def sweep(ctx):
    """Find the knee once: every rate in one process, a short window
    each.  Prints one line per rate; no result line."""
    config, traffic = ctx["config"], ctx["traffic"]
    net, eng = build(config, ctx["seed"])
    eng.warmup()
    eng.start()
    warm_traffic(eng, config)
    try:
        for i, rate in enumerate(ctx["options"]["sweep"]):
            # fewest free pages seen: whether the pool is what fills
            free, done = [], threading.Event()

            def watch():
                while not done.wait(0.5):
                    free.append(eng.stats()["slots"]["pages_free"])

            watcher = threading.Thread(target=watch, daemon=True)
            watcher.start()
            try:
                recs, window = _traffic_once(
                    ctx["generator"], eng, traffic, config, ctx["seed"] + i,
                    ctx["seconds"], float(traffic["lead_in_s"]),
                    float(traffic["drain_s"]), rate=rate)
            finally:
                done.set()
                watcher.join()
            m = _window_metrics(recs, window, True)
            # backlog when the window closed: requests due before its end
            # and not finished by then
            w1 = window[1]
            backlog = sum(1 for r in recs if r.t_due < w1
                          and (not r.ok or r.t_done > w1))
            half = window[0] + 0.5 * (w1 - window[0])
            backlog_half = sum(1 for r in recs if r.t_due < half
                               and (not r.ok or r.t_done > half))
            s = eng.stats()
            env.say(sweep_rate=rate, backlog_mid=backlog_half,
                    backlog_end=backlog,
                    active_highwater=s["slots"]["active_highwater"],
                    pages_free_min=min(free, default=None),
                    preemptions=s["overload"]["preemptions"],
                    longest_request_s=max(
                        (r.t_done - r.t_due for r in recs if r.ok),
                        default=None),
                    **{k: v for k, v in m.items() if k != "errors"},
                    errors=m["errors"])
        env.device_record(ctx["devices"])
    finally:
        eng.stop()
    return None


def run(ctx) -> dict:
    config, traffic = ctx["config"], ctx["traffic"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    if ctx["options"].get("sweep"):
        return sweep(ctx)
    compiles = env.CompileCounter()
    control = ctx["options"].get("control")
    net, eng = build(config, seed, control)
    warmed = eng.warmup()
    env.say(phase="warm", programs=warmed,
            seconds=round(time.monotonic() - ctx["t_start"], 3))
    tracer = None
    tracing = None
    if ctx["trace"]:
        from mxnet_tpu.observability import trace as obs_trace

        tracer = obs_trace.enable(capacity=1 << 20)
        tracing = _Tracing(eng, traffic.get("trace_offset_s", 1.0),
                           min(traffic.get("trace_s", 3.0),
                               max(0.5, seconds - 1.5)),
                           ctx["trace_dir"])
    eng.start()
    warm_traffic(eng, config)
    env.say(phase="warm_traffic",
            seconds=round(time.monotonic() - ctx["t_start"], 3))
    xla0 = compiles.count()
    before = _counter_snapshot(eng)
    # the lead-in belongs to set-up: the window opens on a full engine
    lead = float(traffic["lead_in_s"])
    try:
        recs, window = _traffic_once(
            ctx["generator"], eng, traffic, config, seed, seconds, lead,
            float(traffic["drain_s"]), tracing=tracing)
        if tracing is not None:
            tracing.join()
        after = _counter_snapshot(eng)
        xla_in_traffic = compiles.count() - xla0
        engine_compiles = after["compiles"] - before["compiles"]
        spans = tracer.spans() if tracer is not None else []
        dropped = tracer.dropped if tracer is not None else 0
    finally:
        eng.stop()
        if tracer is not None:
            from mxnet_tpu.observability import trace as obs_trace
            obs_trace.disable()
    open_loop = traffic["kind"] == "open_loop"
    m = _window_metrics(recs, window, open_loop)
    setup_s = window[0] - ctx["t_start"]
    device = env.device_record(ctx["devices"])
    sample = _check_sample(recs, window, seed,
                           int(traffic["check_requests"]))
    echo_bad = _echo_faults(recs)
    # free the program's state before the reference takes the device
    del eng, net
    gc.collect()
    t_ref = time.monotonic()
    limits = config["serving"]["limits"]
    gaps = reference_gaps(config, seed, sample) if sample else []
    sound = _gap_summary(gaps)
    checks = judge_gaps(sound, limits, bool(gaps))
    checks[0].update(requests=len(sample),
                     served_tokens=sum(r.new_tokens for r in sample))
    checks += [
        {"what": "requests_failed", "value": m["failed"], "limit": 0,
         "ok": m["failed"] == 0},
        {"what": "results_not_prompt_plus_new", "value": echo_bad,
         "limit": 0, "ok": echo_bad == 0},
        {"what": "xla_compiles_in_traffic", "value": xla_in_traffic,
         "limit": 0, "ok": xla_in_traffic == 0},
        {"what": "engine_compiles_in_traffic", "value": engine_compiles,
         "limit": 0, "ok": engine_compiles == 0},
        {"what": "completed_in_window", "value": m["completed_in_window"],
         "limit": ">0", "ok": m["completed_in_window"] > 0},
    ]
    if control in ("fp8", "bf16"):
        # the reference in the program's place, one precision lower
        low = _gap_summary(reference_gaps(config, seed, sample,
                                          control=control))
        env.say(precision=control, control=low, sound=sound,
                control_fails=not all(
                    c["ok"] for c in judge_gaps(low, limits, bool(gaps))))
    env.say(phase="reference", seconds=round(time.monotonic() - t_ref, 3),
            **sound)
    for c in checks:
        env.say(check=c)
    env.say(phase="window", **{k: v for k, v in m.items()},
            counters=_delta(before, after), window_s=window[1] - window[0])
    metrics = {
        "serve_tokens_per_s": m["serve_tokens_per_s"],
        "setup_s": setup_s,
    }
    for k in ("ms_per_token_p50", "ms_per_token_p95"):
        if k in m:
            metrics[k] = m[k]
    records = {
        "recs": recs, "window": window, "client": m, "spans": spans,
        "spans_dropped": dropped, "counters": _delta(before, after),
        "trace_counters": tracing.counters if tracing else None,
        "trace_error": tracing.error if tracing else None,
        "config": config, "traffic": traffic,
    }
    return {"correct": all(c["ok"] for c in checks),
            "attempted": m["attempted"], "failed": m["failed"],
            "metrics": metrics, "device": device, "records": records}
