"""Training cells of one contiguous stage of a SambaY decoder-hybrid-decoder
stack (Mamba-1, differential attention under a window and in full, Gated
Memory Units, differential cross-attention): the program's
``ShardedTrainer`` fed by its ``DevicePrefetcher``, one pipeline stage of
whole layers on one chip.

``train_hybrid.py``'s procedure (imported: its ``Job``'s step and
first-gradient norms, three checked steps by the window's own call and
feed, the window, then the plain reference) with this stack's program,
weights and reference (``p4f_program``, ``weights_phi4_flash``,
``phi4_flash_ref``), as ``train_g4h.py``.  There are no routers: nothing
is chosen, so the reference follows nothing and the comparison is
``train.compare``'s three numbers; the third also leaves out the parts
the reference finds Adam moved as one number (:data:`ONE_NUMBER`).  A
control (``--control bf16|fp8``) is the reference in that precision in
the program's place, judged the same way.
"""
from __future__ import annotations

import gc
import math
import statistics
import time

from chipbench.drivers import p4f_program as prog
from chipbench.drivers import train_hybrid as hybrid
from chipbench.drivers.train import (_trace_window, noise_leaves,
                                     worst_leaf_gap)
from chipbench.drivers.train_hybrid import CHECK_STEPS
from chipbench.harness import env
from chipbench.harness.weights_phi4_flash import (compared_apart, leaves,
                                                  make_leaf, make_weights)

# A part whose gradient is ONE number times fixed vectors (a differential
# layer's four lambda vectors: all 256 enter the loss through the scalar
# lambda) is moved by Adam as one number: every element by the rate, all
# by that number's sign.  The norm of its change then counts signs, and
# rounding decides a sign wherever the number comes out near nought on a
# batch (PERF.md section 6, PR 39: dL/dlambda -1.33e-5 in float32 and
# +3.76e-5 in bf16 on one batch, and the whole part's change read 0.25
# off).  The reference knows such a part with no name given: its last
# gradient has, element for element, the sizes of all its gradients,
# whose squares Adam's second moment averages (``off_line`` near 1e-7,
# which is rounding), where a part of many numbers reads 1e-2 and more.
ONE_NUMBER = 1e-5


def reference_steps(token_batches, config, traffic, seed, precision="f32"):
    """Losses of the first ``CHECK_STEPS`` steps, per-leaf norms of the
    first gradient and of the parameters' change after the steps, and
    the parts Adam moved as one number (``one_number``, with every
    part's reading in ``off_line``), by ``phi4_flash_ref``.  Frees
    everything it made."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import phi4_flash_ref as ref

    sizes = prog.sizes_of(config)
    apart = compared_apart(sizes)
    tr = config["training"]
    w = make_weights(sizes, seed, "float32")
    state = None
    batches = token_batches.generate(traffic, seed, sizes["vocab"])
    losses, grad_norms = [], None
    for t in range(1, CHECK_STEPS + 1):
        tokens, labels = next(batches)
        loss, grads = ref.loss_and_grads(
            w, jnp.asarray(tokens), jnp.asarray(labels), sizes,
            precision=precision,
            rows=int(tr["reference_attention_rows_per_block"]))
        losses.append(float(loss))
        if t == 1:
            grad_norms = ref.leaf_norms(grads, apart)
        # Adam's moments wait on the host while the gradients are computed
        state = ref.adam_init(w) if state is None else jax.device_put(state)
        w, state = ref.adam_step(w, grads, state, t=t,
                                 lr=float(tr["learning_rate"]))
        if t < CHECK_STEPS:
            state = jax.device_get(state)
        else:
            line = ref.off_line(grads, state["v"], apart)
        del grads
    del state
    delta = {}
    for name, _shape, _law in leaves(sizes):
        delta.update(ref.leaf_norms(
            {name: w.pop(name) - make_leaf(sizes, seed, name)}, apart))
    del w
    gc.collect()
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta, "off_line": line,
            "one_number": {(k, i) for k in line
                           for i, x in enumerate(line[k]) if x < ONE_NUMBER}}


def compare(program: dict, reference: dict, limits: dict) -> list:
    """``train.compare``'s three numbers of the first steps; the first
    gradient reads every part.  The parameter change leaves out, beside
    ``train.noise_leaves``, the parts Adam moved as ONE number
    (``reference["one_number"]``, named in the check's ``unread``)."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(program["losses"], reference["losses"]))
    g, g_where = worst_leaf_gap(program["grad_norms"],
                                reference["grad_norms"])
    skip = noise_leaves(reference["grad_norms"]) | reference["one_number"]
    d, d_where = worst_leaf_gap(program["delta_norms"],
                                reference["delta_norms"], skip=skip)
    return [
        {"what": "loss_rel_gap_first_steps", "value": loss_gap,
         "limit": limits["loss_rel_gap"],
         "ok": loss_gap <= limits["loss_rel_gap"],
         "program": program["losses"], "reference": reference["losses"]},
        {"what": "first_grad_norm_worst_leaf_gap", "value": g,
         "limit": limits["grad_norm_gap"], "leaf": g_where,
         "ok": g <= limits["grad_norm_gap"]},
        {"what": "param_change_norm_worst_leaf_gap", "value": d,
         "limit": limits["delta_norm_gap"], "leaf": d_where,
         "ok": d <= limits["delta_norm_gap"],
         "unread": sorted(f"{k}[{i}]" for k, i in skip)},
    ]


class Job(hybrid.Job):
    """``train_hybrid.Job`` (its step, first-gradient norms and close)
    over this stack's program and weights."""

    def __init__(self, token_batches, config, traffic, seed, devices):
        from mxnet_tpu import amp
        from mxnet_tpu import parallel as par
        from mxnet_tpu.data import DevicePrefetcher
        from mxnet_tpu.models.phi4_flash import lm_loss

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
        prog.load_weights(self.net, weights, self.sizes["pattern"])
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
            self.net, tr["optimizer"], loss=lm_loss,
            optimizer_params={"learning_rate": float(tr["learning_rate"])},
            mesh=self.mesh)
        self.trainer.build(*sample)
        self.feed = DevicePrefetcher(
            source(), shardings=self.trainer.batch_shardings)
        self.trainer.attach_data_source(self.feed)

    def _leaf_norms(self, value_of) -> dict:
        import jax.numpy as jnp

        out = {}
        params = prog.param_map(self.net, self.sizes["pattern"])
        apart = compared_apart(self.sizes)
        for (leaf, i), p in sorted(params.items(),
                                   key=lambda kv: (kv[0][0], kv[0][1] or 0)):
            a = value_of(leaf, i, p).astype(jnp.float32)
            for name, lo, hi in apart.get(leaf, [(leaf, 0, a.shape[0])]):
                out.setdefault(name, []).append(
                    float(jnp.sqrt(jnp.sum(jnp.square(a[lo:hi])))))
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
        program["delta_norms"] = job.delta_norms(seed)
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
            traced = _trace_window(job, float(traffic.get("trace_s", 3.0)),
                                   ctx["trace_dir"])
        if ctx["options"].get("sample_trace"):
            _trace_window(job, 0.5, ctx["options"]["sample_trace"])
    finally:
        job.close()
    tokens_per_step = job.tokens_per_step
    # the program's state is freed before the reference takes the device
    del job
    gc.collect()
    t_ref = time.monotonic()
    reference = reference_steps(gen, config, traffic, seed)
    line = reference["off_line"]
    env.say(phase="reference", seconds=round(time.monotonic() - t_ref, 3),
            losses=reference["losses"],
            one_number={f"{k}[{i}]": line[k][i]
                        for k, i in sorted(reference["one_number"])},
            nearest_other=min((x, f"{k}[{i}]") for k in line
                              for i, x in enumerate(line[k])
                              if (k, i) not in reference["one_number"]))
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
    checks += [
        {"what": "window_losses_finite", "value": int(finite), "limit": 1,
         "ok": finite},
        {"what": "xla_compiles_in_window", "value": xla_in_window,
         "limit": 0, "ok": xla_in_window == 0},
        {"what": "batches_fell_back_to_host",
         "value": fed1["batches_fallback"], "limit": 0,
         "ok": fed1["batches_fallback"] == 0},
    ]
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
