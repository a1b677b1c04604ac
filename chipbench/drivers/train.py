"""Training cells: the program's ``ShardedTrainer`` fed by its
``DevicePrefetcher``.

Set-up builds the one compiled step with its state, drives it from the
seed through its first three batches by the window's own call and feed,
and hands that same object to the window.  When the window has closed and
the program's state is freed, the plain reference follows the same three
steps (Adam from the seed, float32, in blocks of rows) and the two are
compared.
"""
from __future__ import annotations

import gc
import math
import statistics
import time

from chipbench.drivers import gpt2_program as prog
from chipbench.harness import env
from chipbench.harness.weights import make_weights

CHECK_STEPS = 3


def reference_steps(token_batches, config, traffic, seed,
                    precision="f32"):
    """Losses of the first ``CHECK_STEPS`` steps, per-leaf norms of the
    first gradient and of the parameters' change after the steps, by the
    plain reference.  Frees everything it made."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import gpt2_ref as ref

    sizes = prog.sizes_of(config)
    tr = config["training"]
    w0 = make_weights(sizes, seed, "float32")
    w = w0
    state = ref.adam_init(w)
    batches = token_batches.generate(traffic, seed, sizes["vocab_size"])
    losses, grad_norms = [], None
    for t in range(1, CHECK_STEPS + 1):
        tokens, labels = next(batches)
        loss, grads = ref.loss_and_grads(
            w, jnp.asarray(tokens), jnp.asarray(labels),
            rows_per_block=int(tr["reference_rows_per_block"]),
            n_head=sizes["n_head"], eps=sizes["layer_norm_epsilon"],
            precision=precision)
        losses.append(float(loss))
        if t == 1:
            grad_norms = ref.leaf_norms(grads)
        w, state = ref.adam_step(w, grads, state, t=t,
                                 lr=float(tr["learning_rate"]))
        del grads
    delta = ref.leaf_norms(jax.tree_util.tree_map(jnp.subtract, w, w0))
    del w, w0, state
    gc.collect()
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}


def worst_leaf_gap(got: dict, want: dict, skip=()) -> tuple:
    """The largest gap between a leaf's norm and the reference's, measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    flat_want = [x for k in want for x in want[k]]
    floor = statistics.median(flat_want)
    worst, where = 0.0, None
    for k in want:
        for i, (g, w) in enumerate(zip(got[k], want[k])):
            if (k, i) in skip:
                continue
            gap = abs(g - w) / max(w, floor)
            if not gap <= worst:          # also catches NaN
                worst, where = gap, f"{k}[{i}]"
    return worst, where


def noise_leaves(grad_norms: dict, share: float = 1e-3) -> set:
    """Leaves whose reference gradient is all but zero (the key bias:
    softmax does not see it).  Adam turns such a gradient's rounding
    noise into a full-size update, so the CHANGE of these parameters is
    noise in any precision and is left out of that one comparison."""
    flat = [x for k in grad_norms for x in grad_norms[k]]
    floor = share * statistics.median(flat)
    return {(k, i) for k in grad_norms
            for i, x in enumerate(grad_norms[k]) if x < floor}


def compare(program: dict, reference: dict, limits: dict) -> list:
    checks = []
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(program["losses"], reference["losses"]))
    checks.append({"what": "loss_rel_gap_first_steps", "value": loss_gap,
                   "limit": limits["loss_rel_gap"],
                   "ok": loss_gap <= limits["loss_rel_gap"],
                   "program": program["losses"],
                   "reference": reference["losses"]})
    g, where = worst_leaf_gap(program["grad_norms"],
                              reference["grad_norms"])
    checks.append({"what": "first_grad_norm_worst_leaf_gap", "value": g,
                   "limit": limits["grad_norm_gap"], "leaf": where,
                   "ok": g <= limits["grad_norm_gap"]})
    d, where = worst_leaf_gap(program["delta_norms"],
                              reference["delta_norms"],
                              skip=noise_leaves(reference["grad_norms"]))
    checks.append({"what": "param_change_norm_worst_leaf_gap", "value": d,
                   "limit": limits["delta_norm_gap"], "leaf": where,
                   "ok": d <= limits["delta_norm_gap"]})
    return checks


class Job:
    """The one compiled step with its state and its feed: what set-up
    builds and the window drives."""

    def __init__(self, token_batches, config, traffic, seed, devices):
        from mxnet_tpu import amp
        from mxnet_tpu import parallel as par
        from mxnet_tpu.data import DevicePrefetcher
        from mxnet_tpu.models import gpt2_lm_loss

        import mxnet_tpu as mx

        tr = config["training"]
        sizes = prog.sizes_of(config)
        self.tokens_per_step = (int(traffic["batches"]["batch"])
                                * int(traffic["batches"]["seq"]))
        self._amp = amp if tr.get("amp") else None
        if self._amp is not None:
            self._amp.init(tr["amp"])
        self.net = prog.build_net(config, remat=tr.get("remat", False))
        weights = make_weights(sizes, seed, "float32")
        prog.load_weights(self.net, weights, dtype="float32",
                          trainable=True)
        del weights
        if len(devices) != 1:
            raise ValueError("this driver trains on one chip; a cell across "
                             "chips brings its layout in a driver of its own")
        self.mesh = par.make_mesh(devices=list(devices))
        batches = token_batches.generate(traffic, seed, sizes["vocab_size"])
        first = next(batches)
        sample = tuple(mx.nd.array(a, dtype="int32") for a in first)

        def source():
            yield first
            yield from batches

        self._ctx = par.use_mesh(self.mesh)
        self._ctx.__enter__()
        self.trainer = par.ShardedTrainer(
            self.net, tr["optimizer"], loss=gpt2_lm_loss,
            optimizer_params={"learning_rate": float(tr["learning_rate"])},
            mesh=self.mesh)
        self.trainer.build(*sample)
        self.feed = DevicePrefetcher(
            source(), shardings=self.trainer.batch_shardings)
        self.trainer.attach_data_source(self.feed)

    def step(self) -> float:
        """The window's own call: next batch from the feed, one step,
        the loss read back (which waits for the device).  ``parts`` keeps
        where the call's time went: waiting for the batch, dispatching
        the step, waiting for the loss."""
        t0 = time.monotonic()
        data, labels = next(self.feed)
        t1 = time.monotonic()
        loss = self.trainer.step(data, labels)
        t2 = time.monotonic()
        out = float(loss.asnumpy())
        self.parts = (t1 - t0, t2 - t1, time.monotonic() - t2)
        return out

    def first_grad_norms(self) -> dict:
        """After exactly one Adam step the first moment is
        (1 - beta1) g: the gradient as the optimizer got it."""
        from chipbench.reference import gpt2_ref as ref
        import jax.numpy as jnp

        sd = self.trainer.state_dict()
        index = {id(sd[k]): int(k.split(":")[1]) for k in sd
                 if k.startswith("param:")}
        pm = prog.param_map(self.net)
        beta1 = 0.9
        means = {}
        for (leaf, i), p in pm.items():
            j = index[id(p.data())]
            m = sd[f"state:{2 * j}"].jax.astype(jnp.float32) / (1 - beta1)
            means.setdefault(leaf, {})[i] = m
        tree = {leaf: (d[None] if None in d else
                       jnp.stack([d[i] for i in sorted(d)]))
                for leaf, d in means.items()}
        return ref.leaf_norms(tree)

    def delta_norms(self, seed, config) -> dict:
        import jax
        import jax.numpy as jnp

        from chipbench.reference import gpt2_ref as ref

        w0 = make_weights(prog.sizes_of(config), seed, "float32")
        now = prog.read_params(self.net)
        return ref.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, now, w0))

    def close(self):
        try:
            self.feed.close()
        finally:
            self._ctx.__exit__(None, None, None)
            if self._amp is not None:
                self._amp.reset()


def _trace_window(job, seconds, trace_dir):
    """A few steps under ``jax.profiler``, after the timed window."""
    import jax

    jax.profiler.start_trace(trace_dir)
    t0 = time.monotonic()
    n = 0
    with jax.profiler.TraceAnnotation("chipbench:window"):
        while time.monotonic() - t0 < seconds:
            with jax.profiler.TraceAnnotation("chipbench:step"):
                job.step()
            n += 1
    t1 = time.monotonic()
    jax.profiler.stop_trace()
    return n, (t0, t1)


def run(ctx) -> dict:
    config, traffic = ctx["config"], ctx["traffic"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    limits = config["training"]["limits"]
    compiles = env.CompileCounter()
    gen = ctx["generator"]
    job = Job(gen, config, traffic, seed, ctx["devices"])
    try:
        program = {"losses": []}
        for t in range(CHECK_STEPS):
            program["losses"].append(job.step())
            if t == 0:
                program["grad_norms"] = job.first_grad_norms()
        program["delta_norms"] = job.delta_norms(seed, config)
        for _ in range(int(traffic.get("warm_steps", 2))):
            job.step()
        fed0 = job.feed.stats()
        xla0 = compiles.count()
        pauses = env.GcPauses()
        # ---- the window
        w0 = time.monotonic()
        losses, step_s = [], []
        slowest = {"seconds": 0.0}
        while True:
            t0, cpu0 = time.monotonic(), time.process_time()
            losses.append(job.step())
            now = time.monotonic()
            step_s.append(now - t0)
            if now - t0 > slowest["seconds"]:
                # what the slowest step waited on, and whether this
                # process ran meanwhile (CPU seconds of all its threads)
                slowest = {"seconds": now - t0, "step": len(step_s) - 1,
                           "at_s": t0 - w0, "input_wait_s": job.parts[0],
                           "dispatch_s": job.parts[1],
                           "readback_s": job.parts[2],
                           "process_cpu_s": time.process_time() - cpu0}
            if now - w0 >= seconds:
                break
        w1 = time.monotonic()
        gc_pauses = pauses.close()
        xla_in_window = compiles.count() - xla0
        fed1 = job.feed.stats()
        device = env.device_record(ctx["devices"])
        traced = None
        if ctx["trace"]:
            traced = _trace_window(job, float(traffic.get("trace_s", 2.0)),
                                   ctx["trace_dir"])
        if ctx["options"].get("sample_trace"):
            # two steps, small enough to keep with the benchmark
            _trace_window(job, 0.15, ctx["options"]["sample_trace"])
    finally:
        job.close()
    tokens_per_step = job.tokens_per_step
    # the program's state is freed before the reference takes the device
    del job
    gc.collect()
    t_ref = time.monotonic()
    reference = reference_steps(gen, config, traffic, seed)
    control = None
    if ctx["options"].get("control"):
        control = reference_steps(gen, config, traffic, seed,
                                  precision=ctx["options"]["control"])
    env.say(phase="reference", seconds=round(time.monotonic() - t_ref, 3),
            losses=reference["losses"])
    window_s = w1 - w0
    tokens = len(losses) * tokens_per_step
    checks = compare(program, reference, limits)
    finite = all(math.isfinite(x) for x in losses)
    checks += [
        {"what": "window_losses_finite", "value": int(finite), "limit": 1,
         "ok": finite},
        {"what": "xla_compiles_in_window", "value": xla_in_window,
         "limit": 0, "ok": xla_in_window == 0},
        {"what": "batches_fell_back_to_host",
         "value": fed1["batches_fallback"], "limit": 0,
         "ok": fed1["batches_fallback"] == 0},
    ]
    if control is not None:
        cchecks = compare(control, reference, limits)
        env.say(control=ctx["options"]["control"],
                control_fails=not all(c["ok"] for c in cchecks),
                control_checks=cchecks)
    for c in checks:
        env.say(check=c)
    env.say(phase="window", steps=len(losses), window_s=window_s,
            first_loss=losses[0], last_loss=losses[-1],
            step_s_median=statistics.median(step_s), step_s_max=max(step_s),
            steps_over_twice_median=sum(
                1 for x in step_s if x > 2 * statistics.median(step_s)),
            slowest_step=slowest, gc_pauses_over_50ms=gc_pauses)
    setup_s = w0 - ctx["t_start"]
    records = {
        "step_s": step_s, "window": (w0, w1), "tokens": tokens,
        "tokens_per_step": tokens_per_step,
        "input_wait_s": (fed1["input_wait_seconds_total"]
                         - fed0["input_wait_seconds_total"]),
        "traced": traced, "config": config, "traffic": traffic,
        "n_devices": len(ctx["devices"]),
    }
    return {"correct": all(c["ok"] for c in checks),
            "attempted": len(losses), "failed": 0 if finite else 1,
            "metrics": {"train_tokens_per_s": tokens / window_s,
                        "setup_s": setup_s},
            "device": device, "records": records}
