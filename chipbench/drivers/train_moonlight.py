"""Training cells of a latent-attention expert decoder: the program's
``ShardedTrainer`` fed by its ``DevicePrefetcher``, one chip's share of an
expert-parallel deployment.

``train_hybrid.py``'s procedure and comparison (both imported, as
``train_mellum2.py`` imports them: three checked steps by the window's own
call and feed, the window, then the plain reference following the indices
the program chose, the share of (token, layer) pairs whose own top-k set
differs under a limit of its own, and the routers' correction buffers
found where they were) with this stack's program, weights and reference
(``moonlight_program``, ``weights_moonlight``, ``moonlight_ref``).
"""
from __future__ import annotations

import gc
import math
import statistics
import time

from chipbench.drivers import moonlight_program as prog
from chipbench.drivers import train_hybrid as hybrid
from chipbench.drivers.train import _trace_window
from chipbench.drivers.train_hybrid import CHECK_STEPS, compare_hybrid
from chipbench.harness import env
from chipbench.harness.weights_moonlight import (BUFFERS, leaves, make_leaf,
                                                 make_weights)


def reference_steps(token_batches, config, traffic, seed, precision="f32",
                    chosen=None):
    """As ``train_hybrid.reference_steps``, by ``moonlight_ref``."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import moonlight_ref as ref

    sizes = prog.sizes_of(config)
    tr = config["training"]
    w = make_weights(sizes, seed, "float32")
    state = None
    batches = token_batches.generate(traffic, seed, sizes["vocab"])
    losses, grad_norms, used_all, differ, pairs = [], None, [], 0, 0
    for t in range(1, CHECK_STEPS + 1):
        tokens, labels = next(batches)
        given = None if chosen is None else \
            [jnp.asarray(c, jnp.int32) for c in chosen[t - 1]]
        loss, grads, used, diff = ref.loss_and_grads(
            w, jnp.asarray(tokens), jnp.asarray(labels), sizes,
            precision=precision, chosen=given,
            rows=int(tr["reference_attention_rows_per_block"]))
        losses.append(float(loss))
        used_all.append([jnp.asarray(u).astype("int32") for u in used])
        differ += sum(int(d) for d in diff)
        pairs += tokens.size * len(used)
        if t == 1:
            grad_norms = ref.leaf_norms(grads, skip=BUFFERS)
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
        if name in BUFFERS:
            continue
        d = {name: w.pop(name) - make_leaf(sizes, seed, name)}
        delta.update(ref.leaf_norms(d))
    del w
    gc.collect()
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta, "chosen": used_all,
            "routing_mismatch_share": differ / max(pairs, 1)}


class Job(hybrid.Job):
    """``train_hybrid.Job`` (its step, first-gradient norms, choices,
    buffers, counters and close) over this stack's program and weights."""

    def __init__(self, token_batches, config, traffic, seed, devices):
        from mxnet_tpu import amp
        from mxnet_tpu import parallel as par
        from mxnet_tpu.data import DevicePrefetcher
        from mxnet_tpu.models.deepseek_v3 import lm_loss

        import mxnet_tpu as mx

        tr = config["training"]
        self.sizes = prog.sizes_of(config)
        b = traffic["batches"]
        self.tokens_per_step = int(b["batch"]) * int(b["seq"])
        self._amp = amp if tr.get("amp") else None
        if self._amp is not None:
            self._amp.init(tr["amp"])
        self.net = prog.build_net(config, remat=tr.get("remat", True),
                                  record_choice_rows=self.tokens_per_step)
        weights = make_weights(self.sizes, seed, "float32")
        prog.load_weights(self.net, weights)
        del weights
        if len(devices) != 1:
            raise ValueError("this driver trains one chip's share; a cell "
                             "across chips brings its exchange in a driver "
                             "of its own")
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
        for (leaf, i), p in sorted(prog.param_map(self.net).items(),
                                   key=lambda kv: (kv[0][0], kv[0][1] or 0)):
            if leaf in BUFFERS:
                continue
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

    def choices(self) -> list:
        import numpy as np
        return [np.asarray(c) for c in prog.read_choices(self.net)]

    def buffers(self) -> list:
        import numpy as np
        return [np.asarray(m.e_score_correction_bias.data().asnumpy())
                for m in prog.expert_layers(self.net)]


def run(ctx) -> dict:
    import numpy as np

    config, traffic = ctx["config"], ctx["traffic"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    limits = config["training"]["limits"]
    compiles = env.CompileCounter()
    gen = ctx["generator"]
    job = Job(gen, config, traffic, seed, ctx["devices"])
    try:
        program = {"losses": [], "chosen": []}
        bias0 = job.buffers()
        for t in range(CHECK_STEPS):
            program["losses"].append(job.step())
            program["chosen"].append(job.choices())
            if t == 0:
                program["grad_norms"] = job.first_grad_norms()
        program["delta_norms"] = job.delta_norms(seed)
        for _ in range(int(traffic.get("warm_steps", 2))):
            job.step()
        fed0 = job.feed.stats()
        xla0 = compiles.count()
        routed0 = job.counters()
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
        routed1 = job.counters()
        device = env.device_record(ctx["devices"])
        traced, routed_traced = None, None
        if ctx["trace"]:
            traced = _trace_window(job, float(traffic.get("trace_s", 3.0)),
                                   ctx["trace_dir"])
            routed_traced = hybrid._routed(routed1, job.counters())
        if ctx["options"].get("sample_trace"):
            _trace_window(job, 0.5, ctx["options"]["sample_trace"])
        bias_moved = max(float(np.max(np.abs(a - b)))
                         for a, b in zip(bias0, job.buffers()))
    finally:
        job.close()
    tokens_per_step = job.tokens_per_step
    # the program's state is freed before the reference takes the device
    del job
    gc.collect()
    t_ref = time.monotonic()
    reference = reference_steps(gen, config, traffic, seed,
                                chosen=program["chosen"])
    env.say(phase="reference", seconds=round(time.monotonic() - t_ref, 3),
            losses=reference["losses"])
    checks = compare_hybrid(program, reference, limits)
    if ctx["options"].get("control"):
        control = reference_steps(gen, config, traffic, seed,
                                  precision=ctx["options"]["control"])
        followed = reference_steps(gen, config, traffic, seed,
                                   chosen=control["chosen"])
        cchecks = compare_hybrid(control, followed, limits)
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
        {"what": "routing_buffer_moved", "value": bias_moved, "limit": 0.0,
         "ok": bias_moved == 0.0},
    ]
    for c in checks:
        env.say(check=c)
    routed = hybrid._routed(routed0, routed1)
    env.say(phase="window", steps=len(losses), window_s=window_s,
            first_loss=losses[0], last_loss=losses[-1],
            step_s_median=statistics.median(step_s), step_s_max=max(step_s),
            steps_over_twice_median=sum(
                1 for x in step_s if x > 2 * statistics.median(step_s)),
            slowest_step=slowest, gc_pauses_over_50ms=gc_pauses,
            routed=routed)
    setup_s = w0 - ctx["t_start"]
    records = {
        "step_s": step_s, "window": (w0, w1), "tokens": tokens,
        "tokens_per_step": tokens_per_step,
        "input_wait_s": (fed1["input_wait_seconds_total"]
                         - fed0["input_wait_seconds_total"]),
        "traced": traced, "config": config, "traffic": traffic,
        "n_devices": len(ctx["devices"]),
        "routed": routed, "routed_traced": routed_traced,
    }
    return {"correct": all(c["ok"] for c in checks),
            "attempted": len(losses), "failed": 0 if finite else 1,
            "metrics": {"train_tokens_per_s": tokens / window_s,
                        "setup_s": setup_s},
            "device": device, "records": records}
