"""CachedOp: hybridized execution as ONE jitted XLA computation.

Parity target: ``src/imperative/cached_op.cc`` (SURVEY.md §2.2) — the
executor behind ``HybridBlock.hybridize()``.  TPU-first realization:

- The block's imperative forward is traced once per (shapes, dtypes,
  train-mode) signature into a pure function of (param values, inputs, rng
  key) and compiled with ``jax.jit`` — MXNet's graph caching/bulking/static
  alloc machinery collapses into XLA's compilation cache + buffer donation.
- Stochastic ops (Dropout) draw keys from a traced key *argument* (see
  mxnet_tpu.random), so randomness is fresh per call with zero retraces.
- Mutable aux state (BatchNorm moving stats) is functionalized: any parameter
  whose payload was rebound during the trace becomes an extra output, written
  back after execution — the imperative mutation API survives unchanged.
- Under ``autograd.record()`` the whole cached computation registers as a
  single tape node via ``jax.vjp`` over the jitted function, so backward is
  one more compiled XLA computation (MXNet: CachedOp::Backward).
"""
from __future__ import annotations

import functools
import threading
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from .. import base as _base
from ..analysis.lockwitness import named_rlock as _named_rlock
from .. import random as _random
from ..autograd.tape import OpNode, OutRef, node_of
from ..ndarray import NDArray
from ..ndarray.ndarray import swap_values


_WARNED_FOREIGN_TRACE = False

#: Serializes every window in which a trace SWAPS tracer values into
#: shared parameter payloads (``swap_values`` below) against every
#: reader that snapshots those payloads (``param_snapshot``).  Needed
#: the moment two engines share one block across threads — the fleet's
#: rebuild-and-rewarm path traces a fresh engine's functions while
#: sibling replicas keep serving, and without this lock a sibling's
#: ``p._data`` read lands inside the swap window and captures a
#: DynamicJaxprTracer (UnexpectedTracerError at its next dispatch).
#: RLock: a trace that re-enters (nested pure fns) must not self-deadlock.
_PARAM_SWAP_LOCK = _named_rlock("gluon.param_swap",
                                "trace-time parameter payload swaps")


def param_snapshot(items):
    """Read the live jax payloads of ``items`` (Parameter objects)
    atomically w.r.t. any in-flight trace's parameter swap — the
    reader-side half of ``_PARAM_SWAP_LOCK``."""
    with _PARAM_SWAP_LOCK:
        return tuple(p._data.jax for p in items)


def collect_block_params(block):
    """Stable, deduped list of a block's INITIALIZED parameters — the
    same collect_params()-ordered convention GPT2.generate uses (CachedOp
    keeps its own _iter_params-based collection for trace signatures)."""
    items, seen = [], set()
    for _, p in block.collect_params().items():
        if id(p) in seen or p._data is None:
            continue
        seen.add(id(p))
        items.append(p)
    return items


def make_pure_fn(block, fn, name):
    """Reuse CachedOp's functionalization for arbitrary INFERENCE entries
    (the serving engine's prefill/decode/forward steps): returns
    ``(params, pure)`` where ``pure(param_vals, *args)`` evaluates
    ``fn(*args)`` with every parameter payload swapped for the
    corresponding entry of ``param_vals`` — inference mode, no autograd
    tape, live payloads re-captured at trace time (so reset_ctx/astype
    between traces can never bake stale weights in as constants).  The
    caller jits ``pure``; jax caches one executable per shape bucket.
    ``name`` is what the program is called wherever jax names it (the
    device trace shows ``jit_<name>``): say what the entry is, so that
    no two hot-path programs share a name."""
    items = collect_block_params(block)
    if not items:
        raise _base.MXNetError(
            f"make_pure_fn: {type(block).__name__} has no initialized "
            "parameters — call block.initialize() first")

    def pure(param_vals, *args):
        # `pure` runs at TRACE time only (jit executes the compiled
        # binary on cache hits), so holding the swap lock here costs one
        # uncontended acquire per compile — and makes the tracer-valued
        # payload swap invisible to every concurrent param_snapshot
        # reader (two engines sharing one net, e.g. fleet replicas)
        with _PARAM_SWAP_LOCK:
            live = [p._data for p in items]
            with swap_values(live, param_vals):
                with _base.training_mode(False):
                    rec = _base.set_recording(False)
                    try:
                        return fn(*args)
                    finally:
                        _base.set_recording(rec)

    pure.__name__ = pure.__qualname__ = name
    return items, pure


class CachedOp:
    def __init__(self, block, flags=None):
        self.block = block
        self.flags = flags or {}
        self._jit_cache: Dict = {}
        # stable parameter ordering for the life of this cached op
        self._param_items: List[Tuple[str, object]] = None

    # ------------------------------------------------------------------
    def _collect_param_items(self):
        items = []
        seen = set()
        for name, p in self.block._iter_params():
            if id(p) in seen:
                continue
            seen.add(id(p))
            items.append((name, p))
        return items

    def _make_pure(self, structure, train: bool, n_params: int, n_inputs: int,
                   param_objs, mutated_slots, collect_aux: bool = True):
        """Build the pure traced function.  `mutated_slots` is discovered on
        the first trace (param indices rebound during forward).
        `collect_aux=False` (export) still opens the aux-collection scope —
        so layers record without warning — but discards the losses instead
        of emitting extra outputs, keeping the exported graph's signature
        exactly the declared out_tree."""
        block = self.block
        unflatten = structure

        def pure(flat_args, key):
            param_vals = flat_args[:n_params]
            input_vals = flat_args[n_params:]
            _random.push_trace_key(key)
            try:
                nds = [p._data for _, p in param_objs]
                with swap_values(nds, param_vals) as saved:
                    args = unflatten(input_vals)
                    # functionalized ambient aux losses (MoE router load
                    # balancing): open a collection scope for the duration
                    # of the trace and emit whatever was recorded as extra
                    # outputs — the same pattern ShardedTrainer uses, so
                    # hybridize() no longer drops router losses.
                    aux_prev = _base.set_aux_collection(True)
                    # the caller may already hold recorded aux losses in its
                    # own record scope (imperative MoE layer earlier in the
                    # same user step) — set them aside and restore after the
                    # trace instead of destroying them
                    outer_aux = _base.pop_aux_losses()
                    try:
                        with _base.training_mode(train):
                            rec = _base.set_recording(False)
                            try:
                                out = block.forward(*args)
                            finally:
                                _base.set_recording(rec)
                        drained = _base.pop_aux_losses()
                        auxloss_vals = ([a.jax for a in drained]
                                        if collect_aux else [])
                    finally:
                        _base.set_aux_collection(aux_prev)
                        _base.pop_aux_losses()  # no tracer outlives the trace
                        for a in outer_aux:
                            _base.record_aux_loss(a)
                    outs, out_tree = _flatten_out(out)
                    out_vals = [o.jax for o in outs]
                    # functionalized aux-state updates: a param whose payload
                    # no longer is the tracer we swapped in was mutated in
                    # forward
                    aux_vals = []
                    aux_idx = []
                    for i, (v, (d, old, _)) in enumerate(
                            zip(param_vals, saved)):
                        if d._data is not v:
                            aux_vals.append(d._data)
                            aux_idx.append(i)
                    pure._out_tree = out_tree
                    pure._aux_idx = aux_idx
                    pure._n_auxloss = len(auxloss_vals)
                    return (tuple(out_vals) + tuple(auxloss_vals)
                            + tuple(aux_vals))
            finally:
                _random.pop_trace_key()

        return pure

    # ------------------------------------------------------------------
    def __call__(self, *args):
        block = self.block
        if self._param_items is None:
            self._param_items = self._collect_param_items()
        param_objs = self._param_items
        # ensure initialized (raises DeferredInitializationError for retry)
        param_vals = [p.data().jax for _, p in param_objs]

        flat_inputs, unflatten, static_sig = _flatten_in(args,
                                                         with_static=True)
        input_vals = [x.jax for x in flat_inputs]
        train = _base.is_training()
        sig = (train, static_sig,
               tuple((tuple(v.shape), str(v.dtype)) for v in param_vals),
               tuple((tuple(v.shape), str(v.dtype)) for v in input_vals))

        entry = self._jit_cache.get(sig)
        if entry is None:
            pure = self._make_pure(unflatten, train, len(param_vals),
                                   len(input_vals), param_objs, None)
            jitted = jax.jit(pure)
            # prime: trace once to discover out_tree/aux_idx
            key = _random.next_key()
            _ = jax.eval_shape(pure, tuple(param_vals + input_vals), key)
            entry = (jitted, pure._out_tree, pure._aux_idx,
                     pure._n_auxloss, pure)
            self._jit_cache[sig] = entry
        jitted, out_tree, aux_idx, n_auxloss, pure = entry

        key = _random.next_key()
        flat_args = tuple(param_vals + input_vals)

        recording = _base.is_recording()
        diff_nodes = [node_of(p.data()) for _, p in param_objs] + \
                     [node_of(x) for x in flat_inputs]
        needs_grad = recording and any(n is not None for n in diff_nodes)

        if needs_grad:
            f = lambda *fa: jitted(fa, key)
            out_all, vjp_fn = jax.vjp(f, *flat_args)
        else:
            out_all = jitted(flat_args, key)

        n_main = len(out_all) - n_auxloss - len(aux_idx)
        n_out = n_main + n_auxloss   # tape-visible outputs
        out_vals = out_all[:n_main]
        auxloss_vals = out_all[n_main:n_out]
        aux_vals = out_all[n_out:]

        ctx = (flat_inputs[0].context if flat_inputs
               else param_objs[0][1].data().context)
        outs = [NDArray(v, ctx=ctx) for v in out_vals]
        auxloss_nds = [NDArray(v, ctx=ctx) for v in auxloss_vals]

        if needs_grad:
            def _vjp_wrapper(cots, _vjp=vjp_fn, _aux=aux_vals, _n=n_out):
                if _n == 1 and not isinstance(cots, (tuple, list)):
                    cots = (cots,)
                return _vjp(tuple(cots) + tuple(
                    jnp.zeros(v.shape, v.dtype) for v in _aux))
            node = OpNode(
                _vjp_wrapper,
                diff_nodes, n_out, name=f"CachedOp({type(block).__name__})",
                out_avals=[jax.ShapeDtypeStruct(v.shape, v.dtype)
                           for v in list(out_vals) + list(auxloss_vals)])
            for i, o in enumerate(outs + auxloss_nds):
                o._node = OutRef(node, i)

        # re-record the materialized aux losses into the ambient collector
        # so loss functions drain them exactly as in the imperative path
        global _WARNED_FOREIGN_TRACE
        for a_nd, v in zip(auxloss_nds, auxloss_vals):
            if isinstance(v, jax.core.Tracer):
                if _base.aux_collection_active():
                    _base.record_aux_loss(a_nd)
                elif not _WARNED_FOREIGN_TRACE:
                    import logging
                    logging.warning(
                        "aux loss from hybridized %s is dropped inside a "
                        "foreign trace with no aux-collection scope",
                        type(block).__name__)
                    _WARNED_FOREIGN_TRACE = True
            elif _base.is_recording() or _base.aux_collection_active():
                _base.record_aux_loss(a_nd)

        # write back functionalized aux updates (moving stats)
        for i, v in zip(aux_idx, aux_vals):
            param_objs[i][1].data()._rebind(v)

        return _unflatten_out(outs, out_tree)


# ---------------------------------------------------------------- flattening

def _flatten_in(args, with_static=False):
    """Flatten (NDArray | list/tuple of NDArray) args; non-array args are
    closed over statically and contribute to the jit-cache signature."""
    flat: List[NDArray] = []
    spec = []
    static_parts = []
    for a in args:
        if isinstance(a, NDArray):
            spec.append(("nd", None))
            flat.append(a)
        elif isinstance(a, (list, tuple)) and all(
                isinstance(x, NDArray) for x in a):
            spec.append(("seq", (type(a), len(a))))
            flat.extend(a)
        else:
            spec.append(("static", a))
            try:
                hash(a)
                static_parts.append(a)
            except TypeError:
                static_parts.append(repr(a))

    def unflatten(vals):
        out = []
        it = iter(vals)
        for kind, meta in spec:
            if kind == "nd":
                out.append(NDArray(next(it)))
            elif kind == "seq":
                typ, n = meta
                seq = [NDArray(next(it)) for _ in range(n)]
                out.append(list(seq) if typ is list else tuple(seq))
            else:
                out.append(meta)
        return tuple(out)

    if with_static:
        return flat, unflatten, tuple(static_parts)
    return flat, unflatten


def _flatten_out(out):
    if isinstance(out, NDArray):
        return [out], ("nd", None)
    if isinstance(out, (list, tuple)):
        flats, trees = [], []
        for o in out:
            f, t = _flatten_out(o)
            flats.extend(f)
            trees.append((len(f), t))
        return flats, ("seq", (type(out).__name__, trees))
    raise _base.MXNetError(f"unsupported hybrid_forward output {type(out)}")


def _unflatten_out(flat, tree):
    kind, meta = tree
    if kind == "nd":
        return flat[0]
    name, subtrees = meta
    out, i = [], 0
    for n, t in subtrees:
        out.append(_unflatten_out(flat[i:i + n], t))
        i += n
    return tuple(out) if name == "tuple" else out
