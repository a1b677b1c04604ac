"""Training cells of a looped decoder (one stack of sandwich-normed
full-attention layers run several times on ONE set of weights, a head and
an exit gate after every pass, the loss weighted token by token by the
gates' exit distribution): the program's ``ShardedTrainer`` fed by its
``DevicePrefetcher``, one pipeline stage of whole layers on one chip.

``train_hybrid.py``'s procedure (imported: its ``Job``'s first-gradient
norms and close, three checked steps by the window's own call and feed,
the window, then the plain reference) with this stack's program, weights
and reference (``ouro_program``, ``weights_ouro``, ``ouro_ref``).  The net
takes the labels and returns the objective (``ShardedTrainer(loss=None)``),
so a batch is handed over whole.  Nothing is chosen, so the reference
follows nothing.  Five numbers are compared: the objective, the WORST
pass's mean cross entropy and the largest gap of a pass's mean exit
probability (both read from the program's own counters after each checked
step), the first gradient and the parameters' change; the last leaves
out, beside ``train.noise_leaves``, the gate's bias, ONE number that Adam
moves by its sign (:data:`ONE_NUMBER`).  A control (``--control
bf16|fp8``) is the reference in that precision in the program's place,
judged the same way.
"""
from __future__ import annotations

import gc
import math
import statistics
import time

from chipbench.drivers import ouro_program as prog
from chipbench.drivers import train_hybrid as hybrid
from chipbench.drivers.train import (_trace_window, noise_leaves,
                                     worst_leaf_gap)
from chipbench.drivers.train_hybrid import CHECK_STEPS
from chipbench.harness import env
from chipbench.harness.weights_ouro import leaves, make_leaf, make_weights

# The gate's bias is one number: Adam moves it by the rate times its
# gradient's sign, so the norm of its change counts signs, and rounding
# decides a sign wherever that gradient comes out near nought on a batch
# (as ``train_p4f.ONE_NUMBER``'s parts; here the part is known by name).
# A CPU test holds its update element for element.
ONE_NUMBER = frozenset({("gate_b", 0)})


def reference_steps(token_batches, config, traffic, seed, precision="f32"):
    """Objectives of the first ``CHECK_STEPS`` steps with every pass's
    mean exit probability and mean cross entropy, per-leaf norms of the
    first gradient and of the parameters' change after the steps, by
    ``ouro_ref``.  Frees everything it made."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import ouro_ref as ref

    sizes = prog.sizes_of(config)
    tr = config["training"]
    w = make_weights(sizes, seed, "float32")
    state = None
    batches = token_batches.generate(traffic, seed, sizes["vocab"])
    out = {"losses": [], "exit_mass": [], "pass_loss": []}
    for t in range(1, CHECK_STEPS + 1):
        tokens, labels = next(batches)
        (loss, mass, pass_loss), grads = ref.loss_and_grads(
            w, jnp.asarray(tokens), jnp.asarray(labels), sizes,
            precision=precision,
            rows=int(tr["reference_attention_rows_per_block"]))
        out["losses"].append(float(loss))
        out["exit_mass"].append([float(x) for x in mass])
        out["pass_loss"].append([float(x) for x in pass_loss])
        if t == 1:
            out["grad_norms"] = ref.leaf_norms(grads)
        # Adam's moments wait on the host while the gradients are computed
        state = ref.adam_init(w) if state is None else jax.device_put(state)
        w, state = ref.adam_step(w, grads, state, t=t,
                                 lr=float(tr["learning_rate"]))
        del grads
        if t < CHECK_STEPS:
            state = jax.device_get(state)
    del state
    delta = {}
    for name, _shape, _law in leaves(sizes):
        delta.update(ref.leaf_norms(
            {name: w.pop(name) - make_leaf(sizes, seed, name)}))
    del w
    gc.collect()
    out["delta_norms"] = delta
    return out


def _worst(got, want, relative):
    """The largest gap between two lists of per-pass lists, step by step;
    a list of another length (a pass that was not run) is infinitely
    far."""
    worst = 0.0
    for a, b in zip(got, want):
        if len(a) != len(b):
            return math.inf
        for x, y in zip(a, b):
            gap = abs(x - y) / (abs(y) if relative else 1.0)
            if not gap <= worst:                  # also catches NaN
                worst = gap
    return worst


def compare(program: dict, reference: dict, limits: dict) -> list:
    """Five numbers of the first steps (the module's docstring)."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(program["losses"], reference["losses"]))
    ce_gap = _worst(program["pass_loss"], reference["pass_loss"], True)
    p_gap = _worst(program["exit_mass"], reference["exit_mass"], False)
    g, g_where = worst_leaf_gap(program["grad_norms"],
                                reference["grad_norms"])
    skip = noise_leaves(reference["grad_norms"]) | ONE_NUMBER
    d, d_where = worst_leaf_gap(program["delta_norms"],
                                reference["delta_norms"], skip=skip)
    return [
        {"what": "objective_rel_gap_first_steps", "value": loss_gap,
         "limit": limits["objective_rel_gap"],
         "ok": loss_gap <= limits["objective_rel_gap"],
         "program": program["losses"], "reference": reference["losses"]},
        {"what": "pass_loss_rel_gap_worst_pass", "value": ce_gap,
         "limit": limits["pass_loss_rel_gap"],
         "ok": ce_gap <= limits["pass_loss_rel_gap"],
         "program": program["pass_loss"],
         "reference": reference["pass_loss"]},
        {"what": "exit_mass_gap_worst_pass", "value": p_gap,
         "limit": limits["exit_mass_gap"],
         "ok": p_gap <= limits["exit_mass_gap"],
         "program": program["exit_mass"],
         "reference": reference["exit_mass"]},
        {"what": "first_grad_norm_worst_leaf_gap", "value": g,
         "limit": limits["grad_norm_gap"], "leaf": g_where,
         "ok": g <= limits["grad_norm_gap"]},
        {"what": "param_change_norm_worst_leaf_gap", "value": d,
         "limit": limits["delta_norm_gap"], "leaf": d_where,
         "ok": d <= limits["delta_norm_gap"],
         "unread": sorted(f"{k}[{i}]" for k, i in skip)},
    ]


class Job(hybrid.Job):
    """``train_hybrid.Job`` (its first-gradient norms and close) over this
    stack's program and weights; a step hands the net tokens AND labels."""

    def __init__(self, token_batches, config, traffic, seed, devices):
        from mxnet_tpu import amp
        from mxnet_tpu import parallel as par
        from mxnet_tpu.data import DevicePrefetcher

        import mxnet_tpu as mx

        tr = config["training"]
        self.sizes = prog.sizes_of(config)
        b = traffic["batches"]
        self.tokens_per_step = int(b["batch"]) * int(b["seq"])
        self._amp = amp if tr.get("amp") else None
        if self._amp is not None:
            self._amp.init(tr["amp"])
        self.net = prog.build_net(config, remat=tr.get("remat", True))
        weights = make_weights(self.sizes, seed, "float32")
        prog.load_weights(self.net, weights)
        del weights
        if len(devices) != 1:
            raise ValueError("this driver trains one pipeline stage on one "
                             "chip; a cell across chips brings its "
                             "exchange in a driver of its own")
        self.mesh = par.make_mesh(devices=list(devices))
        batches = token_batches.generate(traffic, seed, self.sizes["vocab"])
        first = next(batches)
        sample = tuple(mx.nd.array(a, dtype="int32") for a in first)

        def source():
            yield first
            yield from batches

        self._ctx = par.use_mesh(self.mesh)
        self._ctx.__enter__()
        self.trainer = par.ShardedTrainer(
            self.net, tr["optimizer"], loss=None,
            optimizer_params={"learning_rate": float(tr["learning_rate"])},
            mesh=self.mesh)
        self.trainer.build(sample)
        self.feed = DevicePrefetcher(
            source(), shardings=self.trainer.batch_shardings)
        self.trainer.attach_data_source(self.feed)

    def step(self) -> float:
        """The window's own call: next batch from the feed, one step, the
        objective read back (which waits for the device)."""
        t0 = time.monotonic()
        batch = tuple(next(self.feed))
        t1 = time.monotonic()
        loss = self.trainer.step(batch)
        t2 = time.monotonic()
        out = float(loss.asnumpy())
        self.parts = (t1 - t0, t2 - t1, time.monotonic() - t2)
        return out

    def _leaf_norms(self, value_of) -> dict:
        import jax.numpy as jnp

        out = {}
        for (leaf, i), p in sorted(prog.param_map(self.net).items(),
                                   key=lambda kv: (kv[0][0], kv[0][1] or 0)):
            a = value_of(leaf, i, p).astype(jnp.float32)
            out.setdefault(leaf, []).append(
                float(jnp.sqrt(jnp.sum(jnp.square(a)))))
        return out

    def delta_norms(self, seed) -> dict:
        made = {}

        def change(leaf, i, p):
            if leaf not in made:
                made.clear()
                made[leaf] = make_leaf(self.sizes, seed, leaf)
            w0 = made[leaf] if i is None else made[leaf][i]
            return p.data().jax - w0

        return self._leaf_norms(change)

    def counters(self) -> dict:
        """``loop.exit_mass`` and ``loop.pass_loss`` of the last step,
        ``steps`` and ``loop.exit_mass_sum`` since the build, from the
        payload the last step left (no launch)."""
        return prog.read_loop(self.net)


def _plan(tracer) -> dict:
    """The last ``loop.plan`` event a differentiated trace left."""
    plans = [s.attrs for s in tracer.spans(name="loop.plan")]
    return dict(plans[-1]) if plans else {}


def run(ctx) -> dict:
    from mxnet_tpu import observability as obs

    config, traffic = ctx["config"], ctx["traffic"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    limits = config["training"]["limits"]
    compiles = env.CompileCounter()
    gen = ctx["generator"]
    # the program's tracer is on while the step is built and traced, for
    # its plan events, and off again before anything is timed
    tracer = obs.enable_tracing()
    try:
        job = Job(gen, config, traffic, seed, ctx["devices"])
    except BaseException:
        obs.disable_tracing()
        raise
    try:
        program = {"losses": [], "exit_mass": [], "pass_loss": []}
        for t in range(CHECK_STEPS):
            program["losses"].append(job.step())
            read = job.counters()
            program["exit_mass"].append(read["loop.exit_mass"])
            program["pass_loss"].append(read["loop.pass_loss"])
            if t == 0:
                plan = _plan(tracer)
                obs.disable_tracing()
                program["grad_norms"] = job.first_grad_norms()
        program["delta_norms"] = job.delta_norms(seed)
        for _ in range(int(traffic.get("warm_steps", 2))):
            job.step()
        fed0 = job.feed.stats()
        xla0 = compiles.count()
        before = job.counters()
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
        last = job.counters()
        device = env.device_record(ctx["devices"])
        traced = None
        if ctx["trace"]:
            traced = _trace_window(job, float(traffic.get("trace_s", 3.0)),
                                   ctx["trace_dir"])
        if ctx["options"].get("sample_trace"):
            _trace_window(job, 0.5, ctx["options"]["sample_trace"])
    finally:
        obs.disable_tracing()
        job.close()
    tokens_per_step = job.tokens_per_step
    # the program's state is freed before the reference takes the device
    del job
    gc.collect()
    t_ref = time.monotonic()
    reference = reference_steps(gen, config, traffic, seed)
    env.say(phase="reference", seconds=round(time.monotonic() - t_ref, 3),
            losses=reference["losses"], exit_mass=reference["exit_mass"],
            pass_loss=reference["pass_loss"])
    checks = compare(program, reference, limits)
    if ctx["options"].get("control"):
        control = reference_steps(gen, config, traffic, seed,
                                  precision=ctx["options"]["control"])
        cchecks = compare(control, reference, limits)
        env.say(control=ctx["options"]["control"],
                control_fails=not all(c["ok"] for c in cchecks),
                control_checks=cchecks)
    window_s = w1 - w0
    tokens = len(losses) * tokens_per_step
    finite = all(math.isfinite(x) for x in losses)
    counted = last["steps"] - before["steps"]
    # the window's mean exit mass, from the program's running sum: one
    # step's reading swings with its batch (PERF.md section 6, PR 46)
    window_mass = [(b - a) / max(counted, 1) for a, b in zip(
        before["loop.exit_mass_sum"], last["loop.exit_mass_sum"])]
    checks += [
        {"what": "window_losses_finite", "value": int(finite), "limit": 1,
         "ok": finite},
        {"what": "xla_compiles_in_window", "value": xla_in_window,
         "limit": 0, "ok": xla_in_window == 0},
        {"what": "batches_fell_back_to_host",
         "value": fed1["batches_fallback"], "limit": 0,
         "ok": fed1["batches_fallback"] == 0},
        {"what": "steps_the_program_counted", "value": counted,
         "limit": len(losses), "ok": counted == len(losses)},
    ]
    for c in checks:
        env.say(check=c)
    env.say(phase="window", steps=len(losses), window_s=window_s,
            first_loss=losses[0], last_loss=losses[-1],
            step_s_median=statistics.median(step_s), step_s_max=max(step_s),
            steps_over_twice_median=sum(
                1 for x in step_s if x > 2 * statistics.median(step_s)),
            slowest_step=slowest, gc_pauses_over_50ms=gc_pauses,
            loop_plan=plan, loop_last_step=last,
            loop_window_exit_mass=window_mass)
    setup_s = w0 - ctx["t_start"]
    records = {
        "step_s": step_s, "window": (w0, w1), "tokens": tokens,
        "tokens_per_step": tokens_per_step,
        "input_wait_s": (fed1["input_wait_seconds_total"]
                         - fed0["input_wait_seconds_total"]),
        "traced": traced, "config": config, "traffic": traffic,
        "n_devices": len(ctx["devices"]),
        "loop": {"plan": plan, "exit_mass": window_mass,
                 "pass_loss": last["loop.pass_loss"]},
    }
    return {"correct": all(c["ok"] for c in checks),
            "attempted": len(losses), "failed": 0 if finite else 1,
            "metrics": {"train_tokens_per_s": tokens / window_s,
                        "setup_s": setup_s},
            "device": device, "records": records}
