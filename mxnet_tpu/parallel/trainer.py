"""ShardedTrainer: the whole training step as ONE jitted, sharded XLA
computation over the device mesh.

Parity note: this subsumes three MXNet mechanisms at once (SURVEY.md §3.2/3.3)
— CachedOp forward/backward (src/imperative/cached_op.cc), KVStore gradient
allreduce (src/kvstore/comm.h: Comm::Reduce → here a psum XLA inserts from
the dp-sharded batch), and the fused optimizer update ops
(src/operator/optimizer_op.cc: here the *same* mxnet_tpu.optimizer.Optimizer
instance runs inside the trace, so every MXNet optimizer works sharded,
unmodified).  Gluon's ``Trainer`` keeps the imperative API for single-device
flows; ShardedTrainer is the pjit path that scales it to a pod.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import base as _base
from .. import optimizer as opt_mod
from .. import random as _random
from ..ndarray import NDArray
from ..observability import compiles as _compile_log
from ..observability import stalls as _stall_log
from ..observability.compiles import on_this_thread as _xla_compiles
from ..observability.flightrecorder import active as _fr_active
from ..observability.trace import active as _trace_active
from ..observability.trace import host_range as _host_range
from ..resilience.faults import inject as _inject, poison as _poison
from ..ndarray.ndarray import swap_values
from .mesh import current_mesh, use_mesh
from .sharding import (ShardingRules, batch_spec,
                       mesh_device_put as _mesh_device_put, param_sharding,
                       shard_params)


def _flatten_state(state) -> Tuple[List[NDArray], Any]:
    """Flatten an optimizer state pytree (None / NDArray / nested tuples)."""
    leaves: List[NDArray] = []

    def walk(s):
        if s is None:
            return ("none",)
        if isinstance(s, NDArray):
            leaves.append(s)
            return ("leaf",)
        if isinstance(s, (tuple, list)):
            return ("seq", type(s) is list, [walk(x) for x in s])
        raise _base.MXNetError(f"unsupported optimizer state {type(s)}")

    tree = walk(state)
    return leaves, tree


def _wrap_state(tree, it) -> Any:
    """Rebuild a state pytree with fresh NDArrays around traced leaves."""
    kind = tree[0]
    if kind == "none":
        return None
    if kind == "leaf":
        return NDArray(next(it))
    _, is_list, subs = tree
    seq = [_wrap_state(s, it) for s in subs]
    return seq if is_list else tuple(seq)


def _state_leaves(state_nd) -> List[NDArray]:
    leaves, _ = _flatten_state(state_nd)
    return leaves


class ShardedTrainer:
    """Train a Gluon block SPMD over a mesh (parity role: gluon.Trainer +
    KVStore ``dist_sync_device``, re-expressed as pjit).

    Parameters
    ----------
    net : Block — initialized (or initializable via one forward) model.
    optimizer : str or Optimizer — any registered MXNet optimizer.
    loss : callable(out, *labels) -> NDArray, reduced to scalar mean.
    mesh : jax.sharding.Mesh (default: ambient/current mesh).
    rules : ShardingRules mapping logical param axes → mesh axes.
    data_specs/label_specs : optional explicit PartitionSpecs per input;
        default shards dim0 over ``dp`` (and ``seq_axis`` over ``sp``).
    donate : donate param/state buffers to the step (XLA in-place update,
        the static_alloc analogue).
    grad_accum : microbatch count — the batch splits into ``grad_accum``
        microbatches run through ``lax.scan`` INSIDE the one jitted step,
        gradients accumulated in f32 and averaged before the single
        optimizer update.  Activation memory is O(batch/grad_accum)
        while the optimizer sees the full effective batch (the
        grad_req='add' accumulation idiom, compiled).  Batch dim must be
        divisible by grad_accum (and the microbatch by dp).
    guard_nonfinite : compile the training-health guardrails into the
        step (docs/guardrails.md): an ``all_finite`` flag over loss +
        gradients is computed IN-GRAPH and the optimizer update is
        applied through ``jnp.where`` selects, so a non-finite step
        leaves params/aux/optimizer state bit-identical — no
        ``lax.cond`` divergence, no recompile, no extra host sync.
        ``step()`` then returns ``(loss, all_finite)`` (both lazy
        NDArrays) instead of the bare loss.
    clip_global_norm : optional in-graph global-norm gradient clipping
        (the unscaled gradient's global L2 norm is capped at this value
        before the update).  Implies the guarded step.
    loss_scaler : an :class:`mxnet_tpu.amp.LossScaler` whose dynamic
        schedule (init_scale / scale_factor / scale_window) is compiled
        into the step: the loss is scaled in-graph, gradients unscaled
        before clipping/update, and the scale shrinks on a non-finite
        step / grows after ``scale_window`` consecutive finite ones —
        all as traced scalars, so the scale changing never recompiles.
        ``amp.init_trainer(trainer)`` attaches one for you.  Implies the
        guarded step.
    """

    def __init__(self, net, optimizer, loss=None, optimizer_params=None,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[ShardingRules] = None,
                 data_specs=None, label_specs=None, seq_axis: Optional[int] = None,
                 donate: bool = True, donate_batch: bool = False,
                 grad_accum: int = 1,
                 guard_nonfinite: bool = False,
                 clip_global_norm: Optional[float] = None,
                 loss_scaler=None):
        self.net = net
        self.loss = loss
        if grad_accum != int(grad_accum) or int(grad_accum) < 1:
            raise _base.MXNetError(
                f"grad_accum must be a positive integer, got {grad_accum}")
        self._grad_accum = int(grad_accum)
        self.mesh = mesh or current_mesh()
        if self.mesh is None:
            raise _base.MXNetError(
                "ShardedTrainer needs a mesh — parallel.make_mesh() first")
        self.rules = rules or ShardingRules()
        if isinstance(optimizer, opt_mod.Optimizer):
            self.optimizer = optimizer
        else:
            self.optimizer = opt_mod.create(optimizer,
                                            **(optimizer_params or {}))
        self._data_specs = data_specs
        self._label_specs = label_specs
        self._seq_axis = seq_axis
        self._donate = donate
        # batch-buffer donation: safe ONLY when every step's batch is a
        # single-use array (the DevicePrefetcher contract) — callers
        # that re-feed the same NDArray each step must leave this off,
        # so it is opt-in unlike param/state donation
        self._donate_batch = bool(donate_batch)
        self._guard_nonfinite = bool(guard_nonfinite)
        self._data_source = None   # attach_data_source: stats()/span stamp
        self._batch_puts = 0       # batch arrays this trainer placed itself
        self._scalar_slots = {}    # position -> (host value, its device copy)
        self._replicated = NamedSharding(self.mesh, P())
        if clip_global_norm is not None and clip_global_norm <= 0:
            raise _base.MXNetError(
                f"clip_global_norm must be > 0, got {clip_global_norm}")
        self._clip_global_norm = clip_global_norm
        self._loss_scaler = loss_scaler
        self._amp_loss_scaler = loss_scaler   # amp duck-type parity
        self._scale_arr = None     # traced loss-scale state (device)
        self._good_arr = None      # consecutive-finite-step counter
        self._built = False
        self._build_stats = None   # seconds of the build and its phases
        self._step_fn = None
        self._trainable: List[Tuple[str, Any]] = []
        self._aux: List[Tuple[str, Any]] = []
        self._states: List[Any] = []       # NDArray pytrees, per trainable
        self._state_flat: List[NDArray] = []
        self._state_shardings: List[NamedSharding] = []
        self._pending_states: Optional[dict] = None
        self._ckpt_managers: Dict[str, Any] = {}
        # fleet counters (docs/observability.md): process-wide step and
        # guarded-bad-step counts, shared across trainer instances
        from ..observability.registry import default_registry
        self._obs_steps = default_registry().counter(
            "mxtpu_trainer_steps_total",
            help="ShardedTrainer.step calls, all trainers")

    # ----------------------------------------------------------- guardrails
    @property
    def _guarded(self) -> bool:
        return (self._guard_nonfinite or self._loss_scaler is not None
                or self._clip_global_norm is not None)

    def attach_loss_scaler(self, scaler=None):
        """Enable in-graph dynamic loss scaling (the guarded step) with
        the given :class:`~mxnet_tpu.amp.LossScaler`'s schedule — what
        ``amp.init_trainer`` calls.  Must run before the first
        ``build()``/``step()``: the schedule compiles into the step."""
        if self._built:
            raise _base.MXNetError(
                "attach_loss_scaler after the trainer is built: the "
                "scale schedule compiles into the jitted step — attach "
                "before the first build()/step()")
        if scaler is None:
            from .. import amp as _amp
            scaler = _amp.LossScaler()
        self._loss_scaler = scaler
        self._amp_loss_scaler = scaler
        return scaler

    @property
    def loss_scale(self) -> float:
        """Current dynamic loss scale (syncs the device scalar; 1.0
        when no scaler is attached)."""
        if self._scale_arr is not None:
            return float(self._scale_arr)
        if self._loss_scaler is not None:
            return float(self._loss_scaler.loss_scale)
        return 1.0

    def _set_carried(self, scale=None, good=None):
        """The guarded step's carried loss scale and run of finite steps,
        placed as the step itself returns them: replicated on the mesh.
        To jit an array that sits on no mesh is of another type than the
        step's own output, and the second step would trace and compile
        again."""
        repl = self._replicated
        if scale is not None:
            self._scale_arr = _mesh_device_put(onp.float32(scale), repl)
        if good is not None:
            self._good_arr = _mesh_device_put(onp.int32(good), repl)

    # ------------------------------------------------------------------
    def _build(self, data, labels):
        """Everything before the first step, in three host phases, each a
        ``host_range`` under ``trainer.build``: ``settle`` (deferred
        shapes, by an abstract forward), ``state`` (the optimizer's
        leaves made, gradient buffers given back), ``place`` (parameters
        and state put on the mesh).  The step is only WRAPPED in
        ``jax.jit`` here: it is traced and compiled by the first
        ``step``.  With no tracer on, the phases' seconds still reach
        ``stats()["build"]`` and ``observability.compiles.builds()``.
        A process that trains is one whose stalled steps are worth a
        record: the first build turns ``observability.stalls`` on."""
        _stall_log.start()
        start, phases = time.monotonic(), {}
        with _host_range("trainer", "build", launches=True) as build:
            for phase, run in (("settle", lambda: self._settle(data)),
                               ("state", self._make_state),
                               ("place", self._place)):
                began = time.monotonic()
                with _host_range("trainer", "build." + phase,
                                 launches=True, parent=build.span):
                    run()
                phases[phase + "_s"] = time.monotonic() - began
            self._compile(data, labels)
            self._built = True
            if self._pending_states is not None:
                self._apply_loaded_states(self._pending_states)
                self._pending_states = None
        end = time.monotonic()
        self._build_stats = {"seconds": end - start, **phases}
        _compile_log.note_build(start, end, **phases)

    def _settle(self, data):
        net = self.net
        # settle deferred shapes with one forward — in inference mode so
        # BatchNorm running stats / dropout are untouched by shape
        # settling.  Preferred path is ABSTRACT (jax.eval_shape): shape
        # propagation without a single FLOP or per-op XLA compile — on a
        # real chip an eager full-batch settle of a large model costs
        # minutes of tiny-op compiles.  Fallbacks: eager on a batch-1
        # slice (dim0 = batch by the data_specs contract; param shapes
        # never depend on it), then eager on the real batch (models with
        # batch-shape contracts, e.g. GPipe microbatching).
        def _settle_slice(x):
            if isinstance(x, NDArray) and x.ndim >= 1 and x.shape[0] > 1:
                return x[0:1]
            return x

        def _abstract_settle():
            import jax

            def run(*jv):
                net(*[NDArray(v) for v in jv])
                return jnp.zeros(())

            jax.eval_shape(run, *[d.jax for d in data])

        with _base.training_mode(False):
            rec = _base.set_recording(False)
            # settling runs MoE layers inside eval_shape traces: open a
            # collection scope so the router doesn't warn about a foreign
            # trace, and drain whatever gets recorded (shape settling
            # computes no loss)
            aux_prev = _base.set_aux_collection(True)
            try:
                import jax
                before = {id(p): p._data.jax
                          for p in net.collect_params().values()
                          if p._data is not None}
                try:
                    _abstract_settle()
                    leaked = any(
                        p._data is not None
                        and isinstance(p._data.jax, jax.core.Tracer)
                        for p in net.collect_params().values())
                except Exception:
                    leaked = True
                if leaked:
                    # abstract settle failed or silently bound tracers (a
                    # forward that rebinds state inside the trace).  Restore
                    # pre-existing params, re-init any freshly allocated
                    # ones concretely, and settle eagerly.
                    for p in net.collect_params().values():
                        d = p._data
                        if d is None or not isinstance(d.jax,
                                                       jax.core.Tracer):
                            continue
                        if id(p) in before:
                            d._rebind(before[id(p)])
                        else:
                            p._data = None
                            p.initialize(force_reinit=True)
                    try:
                        net(*[_settle_slice(d) for d in data])
                    except Exception:
                        net(*data)
            finally:
                _base.set_recording(rec)
                _base.set_aux_collection(aux_prev)
                _base.pop_aux_losses()

    def _make_state(self):
        seen = set()
        for name, p in self.net.collect_params().items():
            if id(p) in seen:
                continue
            seen.add(id(p))
            if p._data is None:
                continue
            if p.grad_req != "null":
                self._trainable.append((name, p))
            else:
                self._aux.append((name, p))
        self._release_grad_buffers()
        # optimizer states (NDArray pytrees, kept for save/load parity)
        self.optimizer.param_dict = {
            i: p for i, (_, p) in enumerate(self._trainable)}
        for i, (_, p) in enumerate(self._trainable):
            st = self.optimizer.create_state_multi_precision(i, p.data())
            self._states.append(st)
            self._state_flat.extend(_state_leaves(st))

    def _place(self):
        # place params on the mesh
        shard_params(self.net, self.mesh, self.rules)
        # a state leaf shards like its parameter when shapes match
        self._state_shardings = []
        for (name, p), st in zip(self._trainable, self._states):
            psh = param_sharding(p, self.mesh, self.rules)
            repl = NamedSharding(self.mesh, P())
            for l in _state_leaves(st):
                self._state_shardings.append(
                    psh if tuple(l.shape) == tuple(p.shape) else repl)
        for st, sh in zip(self._state_flat, self._state_shardings):
            st._rebind(_mesh_device_put(st.jax, sh))
        self._state_trees = [_flatten_state(st)[1] for st in self._states]
        self._state_counts = [len(_state_leaves(st)) for st in self._states]
        if self._guarded:
            init_scale = (self._loss_scaler.loss_scale
                          if self._loss_scaler is not None else 1.0)
            self._set_carried(scale=init_scale, good=0)

    def _release_grad_buffers(self):
        """The step computes and consumes its gradients inside its own
        program, so the buffer ``initialize`` attached to each trainable
        (4 B a float32 parameter) is never read or written here: give it
        back.  ``Parameter.grad()`` and an eager ``backward`` attach a
        zero one again on demand."""
        released = sum(p._release_grad() for _, p in self._trainable)
        from ..observability.registry import default_registry
        from ..observability.trace import active
        default_registry().gauge(
            "mxtpu_trainer_grad_buffer_bytes_released",
            help="gradient buffers ShardedTrainer released at build, "
                 "last trainer built").set(released)
        tr = active()
        if tr is not None:
            tr.event("trainer.grad_buffers", released_bytes=released,
                     parameters=len(self._trainable))

    # ------------------------------------------------------------------
    def _make_pure(self, n_data):
        net, loss_fn, optimizer = self.net, self.loss, self.optimizer
        trainable, aux = self._trainable, self._aux
        state_trees, state_counts = self._state_trees, self._state_counts

        mesh = self.mesh

        accum = self._grad_accum

        def forward_loss(pvals, aux_now, data_vals, label_vals, k):
            """Loss + updated aux payloads for ONE (micro)batch — a pure
            function of its arguments, re-enterable per scan iteration."""
            _random.push_trace_key(k)
            aux_nds = [p._data for _, p in aux]
            swap_ctx = swap_values(aux_nds, aux_now)
            swap_ctx.__enter__()
            try:
                data = [NDArray(v) for v in data_vals]
                labels = [NDArray(v) for v in label_vals]
                _base.pop_aux_losses()   # discard stale entries (e.g.
                # from the eager shape-settling forward) so the loss
                # only sums aux losses of THIS trace
                # loss runs inside this same trace → tracers may be
                # collected (MoE router aux losses)
                aux_prev = _base.set_aux_collection(True)
                try:
                    # "fwd" names the forward and the loss in the
                    # compiled program; the backward then reads
                    # transpose(jvp(fwd)) by itself
                    with jax.named_scope("fwd"), \
                            swap_values([p._data for _, p in trainable],
                                        pvals):
                        with _base.training_mode(True):
                            rec = _base.set_recording(False)
                            try:
                                out = net.forward(*data)
                            finally:
                                _base.set_recording(rec)
                        if loss_fn is not None:
                            l = loss_fn(out, *labels)
                        else:
                            l = out
                        lval = l.jax if isinstance(l, NDArray) else l
                        lval = jnp.mean(lval)
                        new_aux = tuple(
                            p._data._data for _, p in aux)
                        return lval, new_aux
                finally:
                    _base.set_aux_collection(aux_prev)
                    _base.pop_aux_losses()  # nothing may outlive the
                    # trace, drained or not
            finally:
                swap_ctx.__exit__(None, None, None)
                _random.pop_trace_key()

        # NOTE: pure() and pure_guarded() below are deliberate near-twins.
        # They are NOT folded into one function driven by constant guard
        # inputs because the unguarded jaxpr must stay byte-identical
        # across this change: it keys the persistent XLA compile cache for
        # every existing unguarded run, and relying on XLA to fold away
        # constant-predicate selects is a bet, not a guarantee.  A fix to
        # the shared step logic (microbatch scan, optimizer state
        # wrapping) must be applied to BOTH.
        def pure(param_vals, aux_vals, state_vals, batch_vals, key, lr, t):
            _random.push_trace_key(key)
            ctx = use_mesh(mesh)
            ctx.__enter__()
            try:
                data_vals = tuple(batch_vals[:n_data])
                label_vals = tuple(batch_vals[n_data:])
                if accum == 1:
                    (loss_val, new_aux), grads = jax.value_and_grad(
                        lambda pv: forward_loss(pv, aux_vals, data_vals,
                                                label_vals, key),
                        has_aux=True)(tuple(param_vals))
                else:
                    # gradient accumulation: scan over microbatches —
                    # activations live for ONE microbatch; grads
                    # accumulate in f32; BN/aux state threads through
                    # the carry like sequential small steps would
                    def split_mb(v):
                        return v.reshape(
                            (accum, v.shape[0] // accum) + v.shape[1:])

                    mb_data = tuple(split_mb(v) for v in data_vals)
                    mb_labels = tuple(split_mb(v) for v in label_vals)
                    keys = jax.random.split(key, accum)

                    def body(carry, xs):
                        aux_c, gacc, lacc = carry
                        k_i, d_i, l_i = xs
                        (lv, aux_n), g = jax.value_and_grad(
                            lambda pv: forward_loss(pv, aux_c, d_i, l_i,
                                                    k_i),
                            has_aux=True)(tuple(param_vals))
                        gacc = tuple(
                            a + b.astype(jnp.float32)
                            for a, b in zip(gacc, g))
                        return (aux_n, gacc,
                                lacc + lv.astype(jnp.float32)), None

                    g0 = tuple(jnp.zeros(v.shape, jnp.float32)
                               for v in param_vals)
                    carry0 = (tuple(aux_vals), g0,
                              jnp.zeros((), jnp.float32))
                    (new_aux, gsum, lsum), _ = jax.lax.scan(
                        body, carry0, (keys, mb_data, mb_labels))
                    grads = tuple(
                        (g / accum).astype(v.dtype)
                        for g, v in zip(gsum, param_vals))
                    loss_val = lsum / accum

                new_params, new_states = [], []
                with jax.named_scope("optimizer"), optimizer.traced(lr, t):
                    off = 0
                    for i, ((name, p), g) in enumerate(zip(trainable, grads)):
                        w_nd = NDArray(param_vals[i])
                        n = state_counts[i]
                        it = iter(state_vals[off:off + n])
                        st = _wrap_state(state_trees[i], it)
                        off += n
                        optimizer.update_multi_precision(
                            i, w_nd, NDArray(g), st)
                        new_params.append(w_nd._data)
                        new_states.extend(
                            l._data for l in _state_leaves(st))
                return (loss_val, tuple(new_params), tuple(new_aux),
                        tuple(new_states))
            finally:
                ctx.__exit__()
                _random.pop_trace_key()

        if not self._guarded:
            return pure

        has_scaler = self._loss_scaler is not None
        scaler = self._loss_scaler
        clip_norm = self._clip_global_norm

        def pure_guarded(param_vals, aux_vals, state_vals, batch_vals, key,
                         lr, t, scale, good, lpoison, gpoison):
            """The guarded step: loss scaling, NaN/Inf injection splice
            points, global-norm clipping, the in-graph ``all_finite``
            flag, and a ``jnp.where``-masked optimizer update — one
            straight-line XLA program (no ``lax.cond``: both arms of a
            skip are trivially cheap selects, and a single program keeps
            compile count and step time identical to the happy path).
            Mirrors ``pure()`` above — keep shared step logic in sync
            (see the NOTE there for why they are not merged)."""
            _random.push_trace_key(key)
            ctx = use_mesh(mesh)
            ctx.__enter__()
            try:
                data_vals = tuple(batch_vals[:n_data])
                label_vals = tuple(batch_vals[n_data:])

                def scaled_loss(pv, aux_now, d, l, k):
                    lval, aux_n = forward_loss(pv, aux_now, d, l, k)
                    # loss poison splice: lpoison is 0.0 (finite → keep
                    # the real loss) or NaN/Inf from the fault plan
                    lval = jnp.where(jnp.isfinite(lpoison), lval,
                                     lpoison.astype(lval.dtype))
                    out = lval * scale.astype(lval.dtype) \
                        if has_scaler else lval
                    return out, (lval, aux_n)

                if accum == 1:
                    (_slval, (loss_val, new_aux)), grads = \
                        jax.value_and_grad(
                            lambda pv: scaled_loss(pv, aux_vals, data_vals,
                                                   label_vals, key),
                            has_aux=True)(tuple(param_vals))
                else:
                    def split_mb(v):
                        return v.reshape(
                            (accum, v.shape[0] // accum) + v.shape[1:])

                    mb_data = tuple(split_mb(v) for v in data_vals)
                    mb_labels = tuple(split_mb(v) for v in label_vals)
                    keys = jax.random.split(key, accum)

                    def body(carry, xs):
                        aux_c, gacc, lacc = carry
                        k_i, d_i, l_i = xs
                        (_slv, (lv, aux_n)), g = jax.value_and_grad(
                            lambda pv: scaled_loss(pv, aux_c, d_i, l_i,
                                                   k_i),
                            has_aux=True)(tuple(param_vals))
                        gacc = tuple(
                            a + b.astype(jnp.float32)
                            for a, b in zip(gacc, g))
                        return (aux_n, gacc,
                                lacc + lv.astype(jnp.float32)), None

                    g0 = tuple(jnp.zeros(v.shape, jnp.float32)
                               for v in param_vals)
                    carry0 = (tuple(aux_vals), g0,
                              jnp.zeros((), jnp.float32))
                    (new_aux, gsum, lsum), _ = jax.lax.scan(
                        body, carry0, (keys, mb_data, mb_labels))
                    grads = tuple(
                        (g / accum).astype(v.dtype)
                        for g, v in zip(gsum, param_vals))
                    loss_val = lsum / accum

                if has_scaler:       # unscale BEFORE clip/flag/update
                    inv = 1.0 / scale
                    grads = tuple(g * inv.astype(g.dtype) for g in grads)
                # grad poison splice (same contract as lpoison)
                grads = tuple(
                    jnp.where(jnp.isfinite(gpoison), g,
                              gpoison.astype(g.dtype))
                    for g in grads)

                all_finite = jnp.isfinite(loss_val)
                for g in grads:
                    all_finite = all_finite & jnp.all(jnp.isfinite(g))

                if clip_norm is not None:
                    gnorm = jnp.sqrt(sum(
                        jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in grads))
                    coef = jnp.minimum(1.0, clip_norm / (gnorm + 1e-6))
                    grads = tuple(g * coef.astype(g.dtype) for g in grads)

                # zero the grads on a bad step so Inf*0 inside the
                # optimizer can't mint fresh NaNs; the where-select on
                # params/aux/state below is what makes the skip
                # bit-identical
                grads = tuple(
                    jnp.where(all_finite, g, jnp.zeros_like(g))
                    for g in grads)

                new_params, new_states = [], []
                with jax.named_scope("optimizer"), optimizer.traced(lr, t):
                    off = 0
                    for i, ((name, p), g) in enumerate(zip(trainable,
                                                           grads)):
                        w_nd = NDArray(param_vals[i])
                        n = state_counts[i]
                        old_states = state_vals[off:off + n]
                        it = iter(old_states)
                        st = _wrap_state(state_trees[i], it)
                        off += n
                        optimizer.update_multi_precision(
                            i, w_nd, NDArray(g), st)
                        new_params.append(
                            jnp.where(all_finite, w_nd._data,
                                      param_vals[i]))
                        new_states.extend(
                            jnp.where(all_finite, l._data, old)
                            for l, old in zip(_state_leaves(st),
                                              old_states))
                new_aux = tuple(
                    jnp.where(all_finite, a, old)
                    for a, old in zip(new_aux, aux_vals))

                if has_scaler:
                    factor = jnp.float32(scaler._scale_factor)
                    window = jnp.int32(scaler._scale_window)
                    shrunk = jnp.maximum(scale / factor, 1.0)
                    good_ok = good + 1
                    grow = good_ok >= window
                    grown = jnp.where(grow, scale * factor, scale)
                    good_ok = jnp.where(grow, jnp.int32(0), good_ok)
                    new_scale = jnp.where(all_finite, grown, shrunk)
                    new_good = jnp.where(all_finite, good_ok,
                                         jnp.int32(0))
                else:
                    new_scale = scale
                    new_good = jnp.where(all_finite, good + 1,
                                         jnp.int32(0))

                return (loss_val, all_finite, new_scale, new_good,
                        tuple(new_params), tuple(new_aux),
                        tuple(new_states))
            finally:
                ctx.__exit__()
                _random.pop_trace_key()

        return pure_guarded

    # ------------------------------------------------------------------
    def _compile(self, data, labels):
        mesh, rules = self.mesh, self.rules
        pure = self._make_pure(len(data))
        # jit names the program after the function: guarded or not, the
        # device trace shows the trainer's step as jit_trainer_step, and
        # no other program of the package shares that name
        pure.__name__ = pure.__qualname__ = "trainer_step"

        def ns(spec):
            return NamedSharding(mesh, spec)

        param_sh = tuple(param_sharding(p, mesh, rules)
                         for _, p in self._trainable)
        aux_sh = tuple(param_sharding(p, mesh, rules)
                       for _, p in self._aux)
        state_sh = tuple(self._state_shardings)

        def default_spec(v):
            return batch_spec(v.ndim, 0, self._seq_axis)

        data_sh = tuple(ns(s) for s in (
            self._data_specs or [default_spec(d) for d in data]))
        label_sh = tuple(ns(s) for s in (
            self._label_specs or [default_spec(l) for l in labels]))
        self._batch_shardings = data_sh + label_sh
        scalar = ns(P())

        # donate on accelerators only: on CPU-XLA donation buys nothing
        # (host memory, no in-place MXU update) and combined with the
        # persistent compilation cache it corrupts the heap on cache
        # HITS — deserialized executables mis-handle the aliased
        # buffers (observed: NaN params, GC-time segfaults).  Same
        # gating the serving engine applies to its KV cache donation.
        donate = self._donate and jax.default_backend() != "cpu"
        dargs = (0, 1, 2) if donate else ()
        if self._donate_batch and jax.default_backend() != "cpu":
            # batch buffers are argument 3; donating them lets XLA
            # recycle the prefetcher's freshly-shipped arrays in place
            dargs += (3,)
        if self._guarded:
            # extra traced scalars: loss scale, consecutive-finite
            # counter, and the two poison splice values — runtime
            # inputs, so scale updates and fault injection never
            # recompile (still exactly ONE compiled step function)
            self._step_fn = jax.jit(
                pure,
                in_shardings=(param_sh, aux_sh, state_sh,
                              data_sh + label_sh, scalar, scalar, scalar,
                              scalar, scalar, scalar, scalar),
                out_shardings=(scalar, scalar, scalar, scalar,
                               param_sh, aux_sh, state_sh),
                donate_argnums=dargs)
        else:
            self._step_fn = jax.jit(
                pure,
                in_shardings=(param_sh, aux_sh, state_sh,
                              data_sh + label_sh, scalar, scalar, scalar),
                out_shardings=(scalar, param_sh, aux_sh, state_sh),
                donate_argnums=dargs)

    # ------------------------------------------------------------------
    def build(self, data, labels=()):
        """Settle shapes, make the optimizer's state and place both on
        the mesh WITHOUT stepping — params are untouched, so a resume can
        restore a checkpoint into a freshly built trainer before any
        optimizer update runs (ResilientLoop's resume path).  Nothing is
        compiled here: the step is wrapped in ``jax.jit`` and the first
        ``step`` traces and compiles it (``trainer.compile`` says for how
        long)."""
        if not isinstance(data, (tuple, list)):
            data = (data,)
        if not isinstance(labels, (tuple, list)):
            labels = (labels,)
        if not self._built:
            self._build(data, labels)
        return self

    def step(self, data, labels=()):
        """Run one full training step.

        Returns the (replicated, lazy) loss NDArray — or, when the
        guardrails are compiled in (``guard_nonfinite`` /
        ``clip_global_norm`` / an attached loss scaler), the pair
        ``(loss, all_finite)``: ``all_finite`` is a lazy boolean
        NDArray that is False iff this step's loss or gradients were
        non-finite, in which case params/aux/optimizer state were left
        bit-identical (the update was a no-op select) and the loss
        scale was shrunk.  Neither return forces a device→host sync;
        callers that don't read the flag pay nothing for it.
        """
        tr = _trace_active()
        if tr is None:              # zero-cost: one global + None check
            return self._step(data, labels)
        src = self._data_source
        if src is None:
            with tr.span("trainer.step",
                         step=self.optimizer.num_update + 1,
                         guarded=self._guarded) as sp:
                return self._step(data, labels, sp)
        # per-step input-wait stamp: how long the caller's last batch
        # acquisition blocked on the prefetch ring (0 = fully hidden)
        with tr.span("trainer.step", step=self.optimizer.num_update + 1,
                     guarded=self._guarded,
                     input_wait=round(
                         getattr(src, "last_wait_seconds", 0.0), 6)) as sp:
            return self._step(data, labels, sp)

    def _step(self, data, labels=(), span=None):
        """One step in four host phases, each a ``host_range`` (and a
        child of ``span``, the live ``trainer.step``, when tracing is
        on).  Between the caller's read of the last loss and the launch
        of the compiled step the device stands idle, so nothing is
        launched there but the step, and nothing moved that could move
        earlier: ``scalars`` makes the learning rate, the step count,
        the poisons and the key as HOST values (numpy scalars, two key
        words) and takes their copies on the device, which the last step
        shipped ahead (a transfer happens here only for a value nobody
        could foretell: a new rate, a reseed, a poison); ``place``
        gathers the arrays as they sit on the mesh and places only a
        batch array that is not already where the step wants it
        (``stats()["batch_puts"]``); ``dispatch`` is the compiled step's
        call alone, the one launch of a step; ``rebind`` is host
        bookkeeping beside the running step, and ships the next step's
        count and key.  The device's work and the wait for it are in
        none of them: the step is asynchronous, the wait happens where
        the caller reads the loss (``span:ndarray.readback``)."""
        _inject("trainer.step")
        self._obs_steps.inc()
        compiled0 = _xla_compiles()
        if not isinstance(data, (tuple, list)):
            data = (data,)
        if not isinstance(labels, (tuple, list)):
            labels = (labels,)
        if self._grad_accum > 1 and data and \
                data[0].shape[0] % self._grad_accum:
            raise _base.MXNetError(
                f"batch dim {data[0].shape[0]} not divisible by "
                f"grad_accum={self._grad_accum}")
        if not self._built:
            self._build(data, labels)
        opt = self.optimizer
        opt.num_update += 1
        with _host_range("trainer", "scalars", launches=True, parent=span):
            poisons = (_poison("trainer.loss_nonfinite"),
                       _poison("trainer.grad_nonfinite")) \
                if self._guarded else ()
            scalars = self._on_device(self._host_scalars(
                _random.next_key_words(), opt.num_update, *poisons))

        with _host_range("trainer", "place", launches=True, parent=span):
            param_vals, aux_vals, state_vals, batch_vals = \
                self._device_args(data, labels)

        with _host_range("trainer", "dispatch", launches=True,
                         parent=span):
            if self._guarded:
                (loss, flag, new_scale, new_good, new_params, new_aux,
                 new_states) = self._step_fn(
                    param_vals, aux_vals, state_vals, batch_vals, *scalars)
            else:
                loss, new_params, new_aux, new_states = self._step_fn(
                    param_vals, aux_vals, state_vals, batch_vals, *scalars)

        with _host_range("trainer", "rebind", launches=False,
                         parent=span):
            if self._guarded:
                self._scale_arr, self._good_arr = new_scale, new_good
            for (_, p), v in zip(self._trainable, new_params):
                p._data._rebind(v)
            for (_, p), v in zip(self._aux, new_aux):
                p._data._rebind(v)
            for l, v in zip(self._state_flat, new_states):
                l._rebind(v)
            # the device is busy with the step: ship now what the next
            # step's scalars will be if nobody reseeds, draws or changes
            # the rate in between (what they then are is compared again)
            self._on_device(self._host_scalars(
                _random.next_key_words(advance=False), opt.num_update + 1))
        compiled = _xla_compiles() - compiled0
        if compiled:
            self._note_compile(compiled, data, labels, span)
        # what this thread will wait for next, should the wait run long
        _stall_log.awaiting(opt.num_update, loss)
        if self._guarded:
            return NDArray(loss), NDArray(flag)
        return NDArray(loss)

    def _note_compile(self, compiled, data, labels, span):
        """This step sent ``compiled`` programs to XLA: say which step
        and with what batch, to whoever is listening (the tracer's ring,
        the flight recorder's), and what they cost this thread: seconds
        of tracing, lowering and waiting for the backend, and how many
        the persistent cache served or took.  Past the first step that
        is a new batch shape or dtype, or an argument that changed how
        it is placed."""
        attrs = {"step": int(self.optimizer.num_update),
                 "compiles": int(compiled),
                 "shapes": [f"{x.dtype}{list(x.shape)}"
                            for x in tuple(data) + tuple(labels)]}
        attrs.update(_compile_log.of_last(compiled))
        tr = _trace_active()
        if tr is not None:
            tr.event("trainer.compile", parent=span, **attrs)
        fr = _fr_active()
        if fr is not None:
            fr.record("trainer.compile", **attrs)

    def _host_scalars(self, key, t, loss_poison=None, grad_poison=None):
        """What the step takes after its arrays, as host values of fixed
        type (never a weak one: a type that changed between calls would
        compile again): the key, ``lr`` float32, ``t`` int32 and, guarded,
        the carried scale and counter with the two poisons float32."""
        out = (key, onp.float32(self.optimizer.learning_rate), onp.int32(t))
        if self._guarded:
            out += (self._scale_arr, self._good_arr,
                    onp.float32(0.0 if loss_poison is None else loss_poison),
                    onp.float32(0.0 if grad_poison is None else grad_poison))
        return out

    def _on_device(self, scalars):
        """``scalars`` with each host value replaced by a copy of it on
        the mesh (a transfer, never a program).  A copy is kept per
        position and serves again while the value is the same: the rate
        between its changes, the poisons' zeros, and the count and key
        that the last step shipped ahead, behind its own launch, so that
        a step in the usual run transfers nothing before its launch.  (A
        host value in the call itself costs that call a third of a
        millisecond on the chip's host, each.)"""
        repl = self._replicated
        out = []
        for i, v in enumerate(scalars):
            if v is None or isinstance(v, jax.Array):
                out.append(v)
                continue
            slot = self._scalar_slots.get(i)
            if slot is None or slot[0].tobytes() != v.tobytes():
                slot = self._scalar_slots[i] = (v, _mesh_device_put(v, repl))
            out.append(slot[1])
        return tuple(out)

    def _device_args(self, data, labels):
        """The step's array arguments as they sit on the mesh: params,
        aux, optimizer state, and the batch.  A batch array that is
        already committed with the step's sharding (what a
        ``DevicePrefetcher`` built with ``batch_shardings`` delivers)
        passes through untouched; any other is placed here and counted
        in ``stats()["batch_puts"]``."""
        batch = []
        for x, sh in zip(tuple(data) + tuple(labels), self._batch_shardings):
            v = x.jax if isinstance(x, NDArray) else x
            if not (isinstance(v, jax.Array) and v.committed
                    and v.sharding == sh):
                v = _mesh_device_put(
                    v if isinstance(x, NDArray) else jnp.asarray(v), sh)
                self._batch_puts += 1
            batch.append(v)
        return (tuple(p._data.jax for _, p in self._trainable),
                tuple(p._data.jax for _, p in self._aux),
                tuple(l.jax for l in self._state_flat),
                tuple(batch))

    def lower_step(self, data, labels=()):
        """The jitted step lowered for this batch (``jax.stages.Lowered``):
        ``.compile()`` gives the program ``step()`` runs, for
        ``as_text()`` / ``memory_analysis()``.  Builds the trainer if
        needed; advances neither the optimizer nor the RNG, and consumes
        no donated buffer."""
        self.build(data, labels)
        if not isinstance(data, (tuple, list)):
            data = (data,)
        if not isinstance(labels, (tuple, list)):
            labels = (labels,)
        args = self._device_args(data, labels) + self._on_device(
            self._host_scalars(onp.zeros(2, onp.uint32),
                               self.optimizer.num_update))
        return self._step_fn.lower(*args)

    # ------------------------------------------------------------------
    @property
    def batch_shardings(self):
        """Target ``NamedSharding`` per flattened ``data + labels``
        array (None before the first ``build()``/``step()``) — what a
        :class:`mxnet_tpu.data.DevicePrefetcher` ships against so the
        hot-path ``device_put`` is a no-op."""
        return getattr(self, "_batch_shardings", None)

    def attach_data_source(self, source):
        """Associate the input pipeline (a ``DevicePrefetcher`` or
        anything with ``stats()``/``last_wait_seconds``) so
        ``stats()['data']`` and the per-step ``trainer.step`` span
        carry the input-wait facts.  Returns ``source`` for chaining."""
        self._data_source = source
        return source

    def stats(self) -> dict:
        """Point-in-time trainer facts (the engine-``stats()`` shape):
        step counter, ``batch_puts`` (batch arrays the trainer had to
        place itself since it was built: 0 behind a ``DevicePrefetcher``
        that ships against ``batch_shardings``), once built ``build``
        (``seconds`` of the build and of its phases ``settle_s``,
        ``state_s``, ``place_s``), plus a ``data`` section from the
        attached input pipeline when one is present."""
        out = {"num_update": int(self.optimizer.num_update),
               "built": self._built,
               "guarded": self._guarded,
               "batch_puts": self._batch_puts,
               "stalls": _stall_log.summary()}
        if self._build_stats is not None:
            out["build"] = dict(self._build_stats)
        src = self._data_source
        if src is not None and hasattr(src, "stats"):
            out["data"] = src.stats()
        return out

    @property
    def learning_rate(self):
        return self.optimizer.learning_rate

    def set_learning_rate(self, lr):
        self.optimizer.set_learning_rate(lr)

    def save_states(self, fname):
        from ..ndarray import array as _nd_array
        from ..utils.serialization import save
        if not self._built:
            raise _base.MXNetError(
                "save_states before the first step(): optimizer states do "
                "not exist yet (nothing to save)")
        data = {"num_update": _nd_array([self.optimizer.num_update],
                                        dtype="int64")}
        if self._guarded:
            data["loss_scale"] = _nd_array([self.loss_scale],
                                           dtype="float32")
            data["good_steps"] = _nd_array([int(self._good_arr)],
                                           dtype="int64")
        for i, st in enumerate(self._states):
            for j, l in enumerate(_state_leaves(st)):
                data[f"state_{i}_{j}"] = l
        save(fname, data)

    def load_states(self, fname):
        from ..utils.serialization import load
        loaded = load(fname)
        if not self._built:
            # states don't exist until the first step; apply after _build
            self._pending_states = loaded
            return
        self._apply_loaded_states(loaded)

    # ------------------------------------------------------- flat state dict
    def state_dict(self) -> Dict[str, NDArray]:
        """The trainer's whole restorable state as a FLAT ``{key:
        NDArray}`` dict (params, aux, optimizer-state leaves, step
        counter) — the unit :class:`~mxnet_tpu.resilience.ResilientLoop`
        commits through its atomic checkpointer and the portable
        counterpart of the orbax tree in :meth:`save_checkpoint`.

        Keys are POSITIONAL (``param:0``, ``aux:0``, ``state:0``):
        parameter *names* carry a process-global counter, so a resumed
        process (whose fresh net may count from a different base) could
        never match them; collection order is deterministic for a given
        model, which is exactly the resume contract.  Shapes are
        verified on load."""
        from ..ndarray import array as _nd_array
        if not self._built:
            raise _base.MXNetError(
                "state_dict before build: run build()/step() first so "
                "params and optimizer states exist")
        out: Dict[str, NDArray] = {
            "meta:num_update": _nd_array([self.optimizer.num_update],
                                         dtype="int64")}
        if self._guarded:
            # guard state rides the checkpoint so a resume/rewind also
            # restores the dynamic loss scale and its grow counter
            out["meta:loss_scale"] = _nd_array([self.loss_scale],
                                               dtype="float32")
            out["meta:good_steps"] = _nd_array([int(self._good_arr)],
                                               dtype="int64")
        for i, (_n, p) in enumerate(self._trainable):
            out[f"param:{i}"] = p._data
        for i, (_n, p) in enumerate(self._aux):
            out[f"aux:{i}"] = p._data
        for i, l in enumerate(self._state_flat):
            out[f"state:{i}"] = l
        return out

    def load_state_dict(self, d: Dict[str, NDArray]):
        """Inverse of :meth:`state_dict`: rebind every leaf onto its live
        mesh sharding.  Missing keys or mismatched shapes are an error
        (a foreign/corrupt checkpoint — refuse, don't half-load)."""
        if not self._built:
            raise _base.MXNetError(
                "load_state_dict needs the trainer built — call "
                "build() on example data first (shapes/shardings "
                "must exist)")
        want = ([f"param:{i}" for i in range(len(self._trainable))]
                + [f"aux:{i}" for i in range(len(self._aux))]
                + [f"state:{i}" for i in range(len(self._state_flat))]
                + ["meta:num_update"])
        missing = [k for k in want if k not in d]
        if missing:
            raise _base.MXNetError(
                f"state dict is missing {len(missing)} keys "
                f"(e.g. {missing[:3]}) — not a checkpoint of this "
                "trainer/model")

        def _check(key, have, want_shape, name):
            if tuple(have.shape) != tuple(want_shape):
                raise _base.MXNetError(
                    f"state dict {key} ({name}) has shape "
                    f"{tuple(have.shape)}, expected {tuple(want_shape)} "
                    "— checkpoint of a different model")

        for i, (n, p) in enumerate(self._trainable):
            _check(f"param:{i}", d[f"param:{i}"], p.shape, n)
        for i, (n, p) in enumerate(self._aux):
            _check(f"aux:{i}", d[f"aux:{i}"], p.shape, n)
        for i, l in enumerate(self._state_flat):
            _check(f"state:{i}", d[f"state:{i}"], l.shape, "opt state")
        for i, (_n, p) in enumerate(self._trainable):
            sh = param_sharding(p, self.mesh, self.rules)
            p._data._rebind(_mesh_device_put(d[f"param:{i}"].jax, sh))
        for i, (_n, p) in enumerate(self._aux):
            sh = param_sharding(p, self.mesh, self.rules)
            p._data._rebind(_mesh_device_put(d[f"aux:{i}"].jax, sh))
        for i, l in enumerate(self._state_flat):
            l._rebind(_mesh_device_put(d[f"state:{i}"].jax,
                                       self._state_shardings[i]))
        self.optimizer.num_update = int(
            d["meta:num_update"].asnumpy()[0])
        if self._guarded:
            # optional (a checkpoint from an unguarded run lacks them)
            if "meta:loss_scale" in d:
                self._set_carried(scale=d["meta:loss_scale"].asnumpy()[0])
            if "meta:good_steps" in d:
                self._set_carried(good=d["meta:good_steps"].asnumpy()[0])

    # -------------------------------------------------- sharded checkpoints
    def _checkpoint_tree(self):
        return {
            "params": {n: p._data for n, p in self._trainable},
            "aux": {n: p._data for n, p in self._aux},
            "states": {f"s{i}": l for i, l in enumerate(self._state_flat)},
        }

    def save_checkpoint(self, directory, step: int, async_save=True,
                        max_to_keep=5):
        """Async sharded checkpoint (orbax): params + aux + optimizer states
        + step counter; each host writes only its shards.  One manager is
        cached per directory (so periodic saves share async machinery and
        max_to_keep GC never races an in-flight write); returns it so
        callers can `wait_until_finished` before exit."""
        import os
        from ..utils.checkpoint import CheckpointManager
        if not self._built:
            raise _base.MXNetError("save_checkpoint before first step()")
        key = os.path.abspath(str(directory))
        cached = self._ckpt_managers.get(key)
        if cached is not None and cached[1] != (max_to_keep, async_save):
            cached[0].wait_until_finished()
            cached[0].close()
            cached = None
        if cached is None:
            m = CheckpointManager(directory, max_to_keep=max_to_keep,
                                  async_save=async_save)
            self._ckpt_managers[key] = (m, (max_to_keep, async_save))
        else:
            m = cached[0]
        tree = self._checkpoint_tree()
        tree["num_update"] = jnp.asarray(self.optimizer.num_update, jnp.int32)
        if self._guarded:
            # the guard schedule is restorable state on EVERY checkpoint
            # surface (state_dict carries meta:loss_scale/good_steps):
            # resuming with a reset scale would overflow-skip until it
            # re-shrinks
            tree["loss_scale"] = jnp.asarray(self._scale_arr, jnp.float32)
            tree["good_steps"] = jnp.asarray(self._good_arr, jnp.int32)
        m.save(step, tree)
        return m

    def load_checkpoint(self, directory, step=None):
        """Restore a sharded checkpoint with the live NamedShardings."""
        import os
        from ..utils.checkpoint import CheckpointManager
        if not self._built:
            raise _base.MXNetError(
                "load_checkpoint needs the trainer built — run one step() "
                "on example data first (shapes/shardings must exist)")
        # drain any in-flight async save to this directory first, else the
        # restore silently lands on the previous step
        cached = self._ckpt_managers.get(os.path.abspath(str(directory)))
        if cached is not None:
            cached[0].wait_until_finished()
        like = self._checkpoint_tree()
        like["num_update"] = jnp.asarray(0, jnp.int32)
        if self._guarded:
            like["loss_scale"] = jnp.asarray(0.0, jnp.float32)
            like["good_steps"] = jnp.asarray(0, jnp.int32)
        m = CheckpointManager(directory, async_save=False)
        try:
            restored = m.restore(step, like=like)
        finally:
            m.close()
        for n, p in self._trainable:
            p._data._rebind(restored["params"][n])
        for n, p in self._aux:
            p._data._rebind(restored["aux"][n])
        for i, l in enumerate(self._state_flat):
            l._rebind(restored["states"][f"s{i}"])
        self.optimizer.num_update = int(restored["num_update"])
        if self._guarded:
            self._set_carried(scale=restored["loss_scale"],
                              good=restored["good_steps"])

    def _apply_loaded_states(self, loaded):
        if "num_update" in loaded:
            self.optimizer.num_update = int(loaded["num_update"].asnumpy()[0])
        if self._guarded:
            if "loss_scale" in loaded:
                self._set_carried(scale=loaded["loss_scale"].asnumpy()[0])
            if "good_steps" in loaded:
                self._set_carried(good=loaded["good_steps"].asnumpy()[0])
        flat_idx = 0
        for i, st in enumerate(self._states):
            for j, l in enumerate(_state_leaves(st)):
                l._rebind(_mesh_device_put(loaded[f"state_{i}_{j}"].jax,
                                         self._state_shardings[flat_idx]))
                flat_idx += 1
