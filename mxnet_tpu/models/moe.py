"""Mixture-of-Experts layers with expert parallelism over the ``ep`` mesh
axis.

Capability add over the reference (SURVEY.md §2.4: "EP/MoE: none" in
MXNet).  TPU-first design: experts live as stacked (E, ...) parameters
annotated with the "expert" logical axis (sharded over ``ep`` by the
default rules), and routing is the dense GShard/Switch dispatch — one-hot
dispatch/combine einsums with a fixed per-expert capacity so every shape
is static and every FLOP lands on the MXU.  XLA turns the expert einsums
into per-shard grouped matmuls with an all-to-all across ``ep``.

Router aux losses (load-balancing) are recorded into an ambient collector
during forward; loss functions drain it via :func:`pop_aux_losses`.

A second routing, ``routing="dropless"``, is one chip's share of an
expert-parallel deployment: the layer is told ``num_experts`` and which
of them it holds (``experts_held=(first, count)``).  The router scores all
``num_experts`` by the scoring it was built with (``"sigmoid"``: sigmoid
scores, a choice biased by a buffer that is not trained, top-k weights
renormalised and scaled, as DeepSeek-V3 and Nemotron-H route;
``"softmax"``: a softmax over all experts, top-k, renormalised, no
buffer and no scale, as Qwen3-Next routes); the token-expert pairs that
fall on the experts held are sorted by expert into a buffer of the
worst-case size and run as grouped matrix products
(:mod:`mxnet_tpu.ops.gmm`) whose time follows the rows routed, so no
token is dropped under any imbalance.  An expert is ``relu(x W_up)^2
W_down`` (``"relu2"``, two matrices) or ``(silu(x W_gate) * x W_up)
W_down`` (``"swiglu"``, three: one more grouped product a pass).  What the
experts NOT held would add is left out: nothing stands in for the absent
chips, and the partial result goes on.  Summed over the chips of the
deployment (the shared expert counted once) the shares equal the whole
layer.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .. import parallel as _par
from ..gluon.block import HybridBlock
from ..ndarray.ops import invoke
from ..ops.flash import matmul_precision as _prec
from ..parallel.sharding import annotate

__all__ = ["MoELayer", "MoETransformerBlock", "pop_aux_losses",
           "aux_loss_scope", "dropless_ffn", "route_sigmoid_topk",
           "route_softmax_topk", "read_routing_counters"]

from .. import base as _base

_WARNED_CACHED = False


def pop_aux_losses():
    """Drain and return the aux losses recorded since the last pop
    (scalar NDArrays; empty list if no MoE layer ran)."""
    return _base.pop_aux_losses()


class aux_loss_scope:
    """Context manager guaranteeing a clean aux-loss slate (used by
    training loops that may abandon traces)."""

    def __enter__(self):
        _base.pop_aux_losses()
        return self

    def __exit__(self, *a):
        _base.pop_aux_losses()


def _moe_ffn(x, wg, w1, b1, w2, b2, *, num_experts, top_k, capacity,
             activation):
    """Pure-jax GShard dispatch; x (B, T, D) → (y (B, T, D), aux scalar)."""
    b, t, d = x.shape
    e, c = num_experts, capacity
    n = b * t
    xf = x.reshape(n, d)
    logits = jnp.einsum("nd,ed->ne", xf.astype(jnp.float32),
                        wg.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)              # (N, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)    # (N, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    combine = jnp.zeros((n, e, c), jnp.float32)
    counts = jnp.zeros((e,), jnp.float32)
    for j in range(top_k):
        m = jax.nn.one_hot(gate_idx[:, j], e, dtype=jnp.float32)  # (N, E)
        pos = jnp.cumsum(m, axis=0) - 1.0 + counts[None, :]
        keep = (pos < c) * m                              # (N, E)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), c,
                              dtype=jnp.float32)          # (N, E, C)
        combine = combine + gate_vals[:, j, None, None] * \
            keep[:, :, None] * slot
        counts = counts + jnp.sum(m, axis=0)
    dispatch = (combine > 0).astype(xf.dtype)             # (N, E, C)

    x_e = jnp.einsum("nec,nd->ecd", dispatch, xf)
    x_e = _par.with_sharding_constraint(x_e, "expert", None, None)
    h = jnp.einsum("ecd,edh->ech", x_e, w1,
                   preferred_element_type=jnp.float32) + b1[:, None, :]
    h = activation(h).astype(xf.dtype)
    h = _par.with_sharding_constraint(h, "expert", None, "mlp")
    y_e = jnp.einsum("ech,ehd->ecd", h, w2,
                     preferred_element_type=jnp.float32) + b2[:, None, :]
    y_e = _par.with_sharding_constraint(y_e, "expert", None, None)
    y = jnp.einsum("nec,ecd->nd", combine, y_e.astype(jnp.float32))

    # GShard load-balance loss: E * Σ_e (token fraction)·(mean router prob)
    top1 = jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32)
    frac = jnp.mean(top1, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_prob)
    return y.reshape(b, t, d).astype(x.dtype), aux


# ------------------------------------------------------- dropless routing

def _zero_int(x):
    import numpy as np
    return np.zeros(x.shape, jax.dtypes.float0)


# rows a turn of the walk below gathers.  On the chip the walk's time does
# not depend on it between 256 and 8,192 rows (tools/hybrid_kernels_bench.py
# gather; PERF.md section 6, PR 47): 1,024 rounds the routed rows up by
# little and keeps a turn's gather (4-5 MB) in fast memory
_GATHER_CHUNK_ROWS = 1024


# gathers INTO sorted order a layer runs in a step: the rows in, forward
# and recomputed, and ``dy`` (the three that walk; those into pair order
# read the whole buffer)
_SORTED_GATHERS = 3


def gather_chunk_rows(rows: int) -> int:
    """Rows one turn of :func:`_gather_routed` reads into a buffer of
    ``rows`` rows: one size for every family, from the shape alone."""
    return min(_GATHER_CHUNK_ROWS, rows)


def _gather_routed(src, idx, count):
    """``src[idx]`` on the rows ``r < count`` of a sorted buffer, zeros
    (or more gathered rows, up to a whole turn) past them: a walk over
    ``ceil(count / chunk)`` chunks of ``idx``, each turn one gather of
    ``chunk`` rows written into the buffer in place.  The rows READ follow
    the count the step observes, from none to all of ``idx``, as
    ``ops.gmm``'s grid follows the tiles that hold a routed row; no row is
    left out at any count.  A last chunk that would overhang the buffer
    starts earlier (both slices clamp alike) and re-reads rows already
    there.  Never differentiated: it stands inside ``custom_vjp`` rules."""
    rows = idx.shape[0]
    chunk = gather_chunk_rows(rows)

    def turn(i, buf):
        ids = jax.lax.dynamic_slice_in_dim(idx, i * chunk, chunk)
        return jax.lax.dynamic_update_slice_in_dim(buf, src[ids], i * chunk,
                                                   axis=0)

    turns = jax.lax.div(count + (chunk - 1), jnp.asarray(chunk, count.dtype))
    return jax.lax.fori_loop(
        0, turns, turn, jnp.zeros((rows,) + src.shape[1:], src.dtype))


@jax.custom_vjp
def _permute(x, idx, inv, count):
    """``x[idx]`` for a permutation ``idx`` whose inverse is ``inv``: the
    backward is a gather too, never a scatter, and it is a gather INTO
    sorted order, of which only the first ``count`` rows are read by
    anything: it walks those (:func:`_gather_routed`)."""
    return x[idx]


def _permute_fwd(x, idx, inv, count):
    return x[idx], (idx, inv, count)


def _permute_bwd(res, g):
    idx, inv, count = res
    return (_gather_routed(g, inv, count), _zero_int(idx), _zero_int(inv),
            _zero_int(count))


_permute.defvjp(_permute_fwd, _permute_bwd)


def _row_major(x):
    """``x`` (N, D) with D minor, on the TPU.  The buffer is row-major
    because its rows are gathered; where the chip's compiler lays the
    stream around the layer out the other way (it does, tokens minor), the
    re-laying is asked for HERE, on (N, D), and not left to fall on the
    ``top_k`` times larger buffer.  Elsewhere nothing is re-laid, and the
    constraint would only stand between XLA:CPU's fusions (a recomputed
    pass then rounds its float32 sums otherwise than the plain one)."""
    from ..base import resolve_exec_platform
    if resolve_exec_platform(x) != "tpu":
        return x
    from jax.experimental.layout import Layout, with_layout_constraint
    return with_layout_constraint(x, Layout(tuple(range(x.ndim))))


def _slots(buf, top_k):
    """The ``top_k`` slots of a buffer in pair order, each (N, D): static
    slices of its rows, which a fusion reads in place (a reshape to
    (top_k, N, D) stands between the fusions and keeps them apart)."""
    n = buf.shape[0] // top_k
    return [buf[j * n:(j + 1) * n] for j in range(top_k)]


def _sum_held(terms, held):
    """``sum_j terms[j]`` in float32 over the slots ``j`` whose pair is on
    a held expert (``held`` (top_k, N)): the others are selected away."""
    return functools.reduce(jnp.add, [jnp.where(held[j][:, None], t, 0.0)
                                      for j, t in enumerate(terms)])


@jax.custom_vjp
def _rows_of_pairs(x, order, inv, held, count):
    """The buffer of token rows in sorted-pair order: row r is the token
    of pair ``order[r]``, and pair ``p`` is ``slot * N + token``.  Only
    the ``count`` rows routed to a held expert (the sort puts them first)
    are read from ``x``, a chunk at a time (:func:`_gather_routed`); the
    rows past the last chunk are zeros, and no product's tile reads them.
    Backward: the rows return to pair order, where slot ``j`` is rows
    ``j N .. (j + 1) N``, and a token's pairs ON A HELD EXPERT (``held``
    (top_k, N)) are summed in float32; the others hold whatever the kernel
    left and are selected away inside that sum."""
    return _gather_routed(x, order % x.shape[0], count)


def _rows_fwd(x, order, inv, held, count):
    return (_rows_of_pairs(x, order, inv, held, count),
            (order, inv, held, count))


def _rows_bwd(res, g):
    order, inv, held, count = res
    dx = _sum_held([s.astype(jnp.float32)
                    for s in _slots(g[inv], held.shape[0])], held)
    return (_row_major(dx.astype(g.dtype)), _zero_int(order), _zero_int(inv),
            _zero_int(held), _zero_int(count))


_rows_of_pairs.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def _combine(w, y, held):
    """``sum_j w[j] * y[j N:(j + 1) N]`` in float32 over the pairs on a
    held expert: ``w`` and ``held`` (top_k, N), ``y`` (top_k N, D) in pair
    order and the compute type, the result (N, D) float32.  One pass over
    ``y``; the backward makes ``dy`` slot by slot straight in ``y``'s
    type, zero where the pair is not held, so no float32 array of the
    buffer's size exists."""
    return _row_major(_sum_held(
        [w[j][:, None] * s.astype(jnp.float32)
         for j, s in enumerate(_slots(y, w.shape[0]))], held))


def _combine_fwd(w, y, held):
    return _combine(w, y, held), (w, y, held)


def _combine_bwd(res, ct):
    w, y, held = res
    ct = _row_major(ct)
    dy = jnp.concatenate(
        [jnp.where(held[j][:, None], w[j][:, None] * ct, 0.0).astype(y.dtype)
         for j in range(w.shape[0])], axis=0)
    dw = jnp.stack(
        [jnp.sum(jnp.where(held[j][:, None], s.astype(jnp.float32) * ct,
                           0.0), axis=-1)
         for j, s in enumerate(_slots(y, w.shape[0]))])
    return dw, dy, _zero_int(held)


_combine.defvjp(_combine_fwd, _combine_bwd)


def route_sigmoid_topk(x, w_router, choice_bias, *, top_k, norm_topk=True,
                       scaling=1.0, chosen=None):
    """Scores of all experts in float32 and the top-k choice: logits
    ``x W_r^T``, ``s = sigmoid(logits)``, experts chosen by ``s +
    choice_bias`` (no gradient reaches the bias), weights ``s`` at the
    chosen, divided by their sum and scaled.  ``chosen`` (N, k) replaces
    the choice (a reference following a program's discrete choice).
    Returns (weights (N, k) float32, indices (N, k) int32)."""
    logits = jnp.einsum("nd,ed->ne", x.astype(jnp.float32),
                        w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    if chosen is None:
        _, chosen = jax.lax.top_k(
            jax.lax.stop_gradient(s)
            + choice_bias.astype(jnp.float32)[None, :], top_k)
    chosen = chosen.astype(jnp.int32)
    w = jnp.take_along_axis(s, chosen, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scaling, chosen


def route_softmax_topk(x, w_router, *, top_k, norm_topk=True, chosen=None):
    """Scores of all experts in float32 and the top-k choice: ``p =
    softmax(x W_r^T)`` over ALL experts, the ``top_k`` largest, their
    weights ``p`` at the chosen, divided by their sum.  No buffer, no
    scale.  ``chosen`` as in :func:`route_sigmoid_topk`."""
    logits = jnp.einsum("nd,ed->ne", x.astype(jnp.float32),
                        w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    if chosen is None:
        _, chosen = jax.lax.top_k(jax.lax.stop_gradient(p), top_k)
    chosen = chosen.astype(jnp.int32)
    w = jnp.take_along_axis(p, chosen, axis=1)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, chosen


def dropless_ffn(x, w_router, choice_bias, w_up, w_down, *, top_k, first,
                 norm_topk=True, scaling=1.0, compute_dtype=None,
                 impl="auto", chosen=None, scoring="sigmoid", w_gate=None):
    """One chip's share of a routed expert layer.

    ``x`` (N, D); ``w_router`` (E, D) over ALL experts; ``w_up`` (H, D, F)
    and ``w_down`` (H, F, D) of the H experts held, which are experts
    ``first .. first + H - 1``.  ``scoring``: "sigmoid"
    (:func:`route_sigmoid_topk`, with ``choice_bias`` and ``scaling``) or
    "softmax" (:func:`route_softmax_topk`, which has neither).  An expert
    is ``relu(x W_up)^2 W_down``, or with ``w_gate`` (H, D, F)
    ``(silu(x W_gate) * x W_up) W_down``.  Returns ``(y (N, D) float32,
    chosen (N, k), sizes (H,) int32)``: the sum over a token's chosen
    experts THAT ARE HELD of weight x expert output, and how many pairs
    each held expert got.  Every pair on a held expert is computed: the
    buffer holds ``N * top_k`` rows, an expert's rows slot by slot.  What
    the layer pays follows ``sum(sizes)`` and not the buffer: the grouped
    products' grid (``ops.gmm``) and the gathers that write the sorted
    buffer (:func:`_gather_routed`) both stop at the rows routed; rows
    past them hold zeros, other tokens' rows up to a whole chunk, or what
    a kernel left, and reach no sum."""
    from ..ops.flash import plan_event
    from ..ops.gmm import grouped_matmul
    n = x.shape[0]
    held = w_up.shape[0]
    cd = jnp.dtype(compute_dtype or x.dtype)
    plan_event("moe.plan", form="relu2" if w_gate is None else "swiglu",
               scoring=scoring, top_k=top_k, buffer_rows=n * top_k,
               experts_held=held,
               gather_chunk_rows=gather_chunk_rows(n * top_k))
    if scoring == "sigmoid":
        w, chosen = route_sigmoid_topk(x, w_router, choice_bias, top_k=top_k,
                                       norm_topk=norm_topk, scaling=scaling,
                                       chosen=chosen)
    elif scoring == "softmax":
        w, chosen = route_softmax_topk(x, w_router, top_k=top_k,
                                       norm_topk=norm_topk, chosen=chosen)
    else:
        raise ValueError(f"scoring must be sigmoid or softmax, got "
                         f"{scoring!r}")
    # pair p = slot * N + token: the buffer in pair order is its top_k
    # slots of (N, D) one after another, read in place; token-major pairs
    # made it (N, top_k, D), top_k in the tiled place: a padded copy
    local = jnp.logical_and(chosen >= first, chosen < first + held).T
    key = jnp.where(local, chosen.T - first, held).reshape(-1)   # (k N,)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    routed = jnp.sum(sizes)
    valid = jnp.arange(n * top_k) < routed
    # Rows past the routed ones hold whatever the kernel left (NaN, too),
    # or what the gathers into sorted order left (zeros; rows of other
    # tokens up to a whole chunk).
    # They may be gathered and may pass row-wise elementwise work; they are
    # SELECTED away (never multiplied) before any sum that crosses rows or
    # leaves the buffer.  The narrow (rows, F) outputs are masked here,
    # where the select fuses with the activation; the wide (rows, D) ones
    # where they are consumed: after the gather back to pair order, by
    # ``local``, inside the combine's sum and inside ``_rows_of_pairs``'s.
    def product(lhs, rhs):
        out = grouped_matmul(lhs, rhs.astype(cd), sizes, impl=impl)
        return jnp.where(valid[:, None], out, jnp.zeros_like(out))

    rows = _rows_of_pairs(x.astype(cd), order, inv, local, routed)
    u = product(rows, w_up).astype(jnp.float32)
    if w_gate is None:
        h = jnp.square(jax.nn.relu(u)).astype(cd)
    else:
        h = (jax.nn.silu(product(rows, w_gate).astype(jnp.float32))
             * u).astype(cd)
    y = grouped_matmul(h, w_down.astype(cd), sizes, impl=impl)
    y = _permute(y, inv, order, routed)
    return _combine(w.T, y, local), chosen, sizes


def amp_compute_dtype(x):
    """bf16 (or fp16) under ``amp.init``, else the input's own type: what
    a layer that does its own casting computes its matmuls in."""
    from .. import amp as _amp
    pol = _amp.current_policy()
    return pol.target_dtype if pol is not None else x.dtype


def relu2_mlp(x, w_up, w_down, compute_dtype=None):
    """``relu(x W_up^T)^2 W_down^T`` with (out, in) weights: the shared
    expert, and the form of every routed one."""
    cd = jnp.dtype(compute_dtype or x.dtype)
    u = jnp.einsum("nd,fd->nf", x.astype(cd), w_up.astype(cd),
                   precision=_prec(cd), preferred_element_type=jnp.float32)
    h = jnp.square(jax.nn.relu(u)).astype(cd)
    return jnp.einsum("nf,df->nd", h, w_down.astype(cd),
                      precision=_prec(cd), preferred_element_type=jnp.float32)


def swiglu_mlp(x, w_gate, w_up, w_down, compute_dtype=None):
    """``(silu(x W_gate^T) * x W_up^T) W_down^T`` with (out, in) weights:
    the gated form of a shared expert."""
    cd = jnp.dtype(compute_dtype or x.dtype)

    def dense(a, w, spec):
        return jnp.einsum(spec, a.astype(cd), w.astype(cd),
                          precision=_prec(cd),
                          preferred_element_type=jnp.float32)

    h = (jax.nn.silu(dense(x, w_gate, "nd,fd->nf"))
         * dense(x, w_up, "nd,fd->nf")).astype(cd)
    return dense(h, w_down, "nf,df->nd")


def read_routing_counters(net) -> dict:
    """Routing counters of every dropless ``MoELayer`` under ``net``, read
    from the payloads the last step left (no program is launched): summed
    over the layers, ``moe.pairs_local`` (token-expert pairs computed
    here in the last step), ``moe.pairs_total`` (top_k a token) and
    ``moe.load_max`` (the largest count on one held expert, largest
    layer), and the running sums since the layers were built
    (``sum_*``, with ``steps``); ``moe.gather_rows_walked`` (the rows the
    three gathers into sorted order read in the last step: the routed
    rows rounded up to whole chunks, a layer's three alike) and
    ``moe.gather_rows_buffer`` (the rows they would read whole), worked
    out here from each layer's counts.  The registry counter
    ``mxtpu_moe_pairs_local_total`` is brought up to the layers' running
    sums: a step is counted once however often it is read, and a step no
    read fell on is counted by the next."""
    import numpy as np

    out = {"moe.pairs_local": 0.0, "moe.pairs_total": 0.0,
           "moe.load_max": 0.0, "moe.gather_rows_walked": 0.0,
           "moe.gather_rows_buffer": 0.0, "sum_pairs_local": 0.0,
           "sum_pairs_total": 0.0, "sum_load_max": 0.0, "steps": 0.0,
           "layers": 0, "experts_held": 0, "per_layer_sum_pairs_local": []}
    fresh = 0.0
    for blk in _dropless_layers(net):
        v = np.asarray(blk.routing_stats.data().asnumpy(), np.float64)
        out["per_layer_sum_pairs_local"].append(float(v[3]))
        # a sum below what was reported is a payload started afresh
        fresh += v[3] - (blk._pairs_reported
                         if v[3] >= blk._pairs_reported else 0.0)
        blk._pairs_reported = float(v[3])
        out["moe.pairs_local"] += v[0]
        out["moe.pairs_total"] += v[1]
        out["moe.load_max"] = max(out["moe.load_max"], v[2])
        if v[1]:
            chunk = gather_chunk_rows(int(v[1]))
            out["moe.gather_rows_walked"] += \
                _SORTED_GATHERS * math.ceil(v[0] / chunk) * chunk
            out["moe.gather_rows_buffer"] += _SORTED_GATHERS * v[1]
        out["sum_pairs_local"] += v[3]
        out["sum_pairs_total"] += v[4]
        out["sum_load_max"] += v[5]
        out["steps"] = max(out["steps"], v[6])
        out["layers"] += 1
        out["experts_held"] = blk._held[1]
    if out["layers"]:
        from ..observability.registry import default_registry
        default_registry().counter(
            "mxtpu_moe_pairs_local_total",
            help="token-expert pairs computed on the experts this chip "
                 "holds, up to the last read_routing_counters").inc(fresh)
    return out


def _dropless_layers(net):
    found = []

    def visit(b):
        if isinstance(b, MoELayer) and b._routing == "dropless":
            found.append(b)
        for c in getattr(b, "_children", {}).values():
            visit(c)
    visit(net)
    return found


class MoELayer(HybridBlock):
    """Top-k routed expert FFN (drop-in for PositionwiseFFN).

    Parameters are stacked over the expert dim and annotated "expert" so
    the default sharding rules place them over the ``ep`` mesh axis.
    """

    def __init__(self, units, hidden_size, num_experts, top_k=2,
                 capacity_factor=1.25, activation="gelu", dropout=0.0,
                 dtype="float32", routing="capacity", experts_held=None,
                 shared_hidden=0, routed_scaling=1.0, norm_topk=True,
                 record_choice_rows=0, scoring="sigmoid",
                 expert_form="relu2", shared_gate=False, **kwargs):
        """``routing="capacity"``: softmax top-k with a capacity per
        expert over the ``ep`` axis (tokens over capacity are dropped).
        ``routing="dropless"``: one chip's share (module docstring):
        ``experts_held=(first, count)`` of the ``num_experts`` the router
        scores (default: all), by ``scoring`` ("sigmoid": with the
        correction buffer and ``routed_scaling``; "softmax": neither);
        ``expert_form`` "relu2" (two matrices) or "swiglu" (three), for
        the routed and the shared expert alike; ``shared_hidden`` > 0
        adds a shared expert of that width to every token, times
        ``sigmoid(x . w)`` of a trained vector with ``shared_gate``;
        ``record_choice_rows=N`` keeps the last step's chosen expert
        indices of N tokens as a payload (``last_choice``).  The grouped products choose their form by
        platform (``ops.gmm``: Pallas on the TPU, ``ragged_dot``
        elsewhere)."""
        super().__init__(**kwargs)
        from ..gluon.nn import Dropout
        self.dropout = Dropout(dropout) if dropout else None
        self._units = units
        self._hidden = hidden_size
        self._num_experts = num_experts
        self._top_k = min(top_k, num_experts)
        self._capacity_factor = capacity_factor
        self._act_name = activation
        if routing not in ("capacity", "dropless"):
            raise ValueError(f"routing must be capacity or dropless, got "
                             f"{routing!r}")
        self._routing = routing
        self.gate = self.params.get(
            "gate", shape=(num_experts, units), dtype=dtype,
            init="xavier", allow_deferred_init=True)
        annotate(self.gate, None, "embed")
        if routing == "dropless":
            first, count = experts_held or (0, num_experts)
            if first < 0 or count < 1 or first + count > num_experts:
                raise ValueError(f"experts_held {(first, count)} is not a "
                                 f"range of {num_experts} experts")
            if scoring not in ("sigmoid", "softmax"):
                raise ValueError(f"scoring must be sigmoid or softmax, got "
                                 f"{scoring!r}")
            if expert_form not in ("relu2", "swiglu"):
                raise ValueError(f"expert_form must be relu2 or swiglu, "
                                 f"got {expert_form!r}")
            self._held = (int(first), int(count))
            self._scaling = float(routed_scaling)
            self._norm_topk = bool(norm_topk)
            self._scoring = scoring
            glu = expert_form == "swiglu"
            g = self.params.get
            # what the layer computes with, in the order its step takes it
            ins = {"gate": self.gate}
            if scoring == "sigmoid":
                # chooses, is not trained, and no step changes it
                self.e_score_correction_bias = ins["bias"] = g(
                    "e_score_correction_bias", shape=(num_experts,),
                    dtype="float32", init="zeros", differentiable=False)
            self.w1 = ins["w1"] = g(
                "w1", shape=(count, units, hidden_size), dtype=dtype,
                init="xavier")
            self.w2 = ins["w2"] = g(
                "w2", shape=(count, hidden_size, units), dtype=dtype,
                init="xavier")
            if glu:
                self.w_gate = ins["w_gate"] = g(
                    "w_gate", shape=(count, units, hidden_size),
                    dtype=dtype, init="xavier")
            self.shared_up = self.shared_down = None
            if shared_hidden:
                self.shared_up = ins["shared_up"] = g(
                    "shared_up", shape=(shared_hidden, units), dtype=dtype,
                    init="xavier")
                self.shared_down = ins["shared_down"] = g(
                    "shared_down", shape=(units, shared_hidden),
                    dtype=dtype, init="xavier")
                if glu:
                    self.shared_gate_proj = ins["shared_gate_proj"] = g(
                        "shared_gate_proj", shape=(shared_hidden, units),
                        dtype=dtype, init="xavier")
                if shared_gate:
                    self.shared_expert_gate = ins["shared_expert_gate"] = g(
                        "shared_expert_gate", shape=(units,), dtype=dtype,
                        init="zeros")
            # [pairs_local, pairs_total, load_max] of the last step, their
            # running sums, steps: rewritten by every forward, read
            # without a launch (read_routing_counters)
            self.routing_stats = ins["stats"] = g(
                "routing_stats", shape=(7,), dtype="float32", init="zeros",
                differentiable=False)
            self._dropless_ins = ins
            self._pairs_reported = 0.0   # of the running sum, in the registry
            self.last_choice = None
            if record_choice_rows:
                self.last_choice = g(
                    "last_choice", shape=(record_choice_rows, self._top_k),
                    dtype="int32", init="zeros", differentiable=False)
            return
        self.w1 = self.params.get(
            "w1", shape=(num_experts, units, hidden_size), dtype=dtype,
            init="xavier", allow_deferred_init=True)
        annotate(self.w1, "expert", "embed", "mlp")
        self.b1 = self.params.get(
            "b1", shape=(num_experts, hidden_size), dtype=dtype,
            init="zeros", allow_deferred_init=True)
        annotate(self.b1, "expert", "mlp")
        self.w2 = self.params.get(
            "w2", shape=(num_experts, hidden_size, units), dtype=dtype,
            init="xavier", allow_deferred_init=True)
        annotate(self.w2, "expert", "mlp", "embed")
        self.b2 = self.params.get(
            "b2", shape=(num_experts, units), dtype=dtype,
            init="zeros", allow_deferred_init=True)
        annotate(self.b2, "expert", "embed")

    def _forward_dropless(self, x):
        lead = x.shape[:-1]
        first, _count = self._held
        names = list(self._dropless_ins)
        ins = [x] + [p.data() for p in self._dropless_ins.values()]

        def f(xv, *values):
            p = dict(zip(names, values))
            cd = amp_compute_dtype(xv)
            xf = xv.reshape(-1, xv.shape[-1])
            y, chosen, sizes = dropless_ffn(
                xf, p["gate"], p.get("bias"), p["w1"], p["w2"],
                top_k=self._top_k, first=first, norm_topk=self._norm_topk,
                scaling=self._scaling, compute_dtype=cd,
                scoring=self._scoring, w_gate=p.get("w_gate"))
            if "shared_up" in p:
                if "shared_gate_proj" in p:
                    shared = swiglu_mlp(xf, p["shared_gate_proj"],
                                        p["shared_up"], p["shared_down"], cd)
                else:
                    shared = relu2_mlp(xf, p["shared_up"], p["shared_down"],
                                       cd)
                if "shared_expert_gate" in p:
                    shared = shared * jax.nn.sigmoid(jnp.einsum(
                        "nd,d->n", xf.astype(cd),
                        p["shared_expert_gate"].astype(cd),
                        precision=_prec(cd),
                        preferred_element_type=jnp.float32))[:, None]
                y = y + shared
            stats = p["stats"]
            now = jnp.stack([jnp.sum(sizes), xf.shape[0] * self._top_k,
                             jnp.max(sizes)]).astype(jnp.float32)
            stats = jnp.concatenate(
                [now, stats[3:6] + now, stats[6:] + 1.0])
            return (y.reshape(lead + (y.shape[-1],)).astype(cd),
                    jax.lax.stop_gradient(stats),
                    chosen.astype(jnp.float32))   # ids are exact in f32

        y, stats, chosen = invoke("moe_dropless", f, ins, nout=3)
        if not _base.is_training():
            return y        # as BatchNorm: payloads move in training only
        self.routing_stats.data()._rebind(stats.jax)
        if self.last_choice is not None and \
                tuple(chosen.shape) == tuple(self.last_choice.shape):
            self.last_choice.data()._rebind(chosen.jax.astype(jnp.int32))
        return y

    def capacity(self, n_tokens: int) -> int:
        cap = int(math.ceil(self._top_k * n_tokens / self._num_experts
                            * self._capacity_factor))
        return max(cap, self._top_k)

    def forward(self, x):
        if self._routing == "dropless":
            y = self._forward_dropless(x)
            return self.dropout(y) if self.dropout is not None else y
        b, t = x.shape[0], x.shape[1]
        act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
               "silu": jax.nn.silu}[self._act_name]

        def f(xv, wg, w1, b1, w2, b2):
            return _moe_ffn(
                xv, wg, w1, b1, w2, b2, num_experts=self._num_experts,
                top_k=self._top_k, capacity=self.capacity(b * t),
                activation=act)

        y, aux = invoke("moe_ffn", f,
                        [x, self.gate.data(), self.w1.data(),
                         self.b1.data(), self.w2.data(), self.b2.data()])
        # record only when a loss will drain it within the same tape/trace:
        # eager autograd recording, or a trace whose owner opened an
        # aux-collection scope (ShardedTrainer, CachedOp — the latter
        # functionalizes the losses as extra traced outputs).  Tracers
        # outside such a scope must NOT be recorded — they would leak out
        # of their trace.
        traced = isinstance(aux.jax, jax.core.Tracer)
        if traced and _base.aux_collection_active():
            _base.record_aux_loss(aux)
        elif not traced and _base.is_recording():
            _base.record_aux_loss(aux)   # NDArray, autograd node intact
        elif traced:
            global _WARNED_CACHED
            if not _WARNED_CACHED:
                import logging
                logging.warning(
                    "MoE router aux loss is dropped inside a foreign "
                    "trace with no aux-collection scope; run the layer "
                    "imperatively, via hybridize()/CachedOp, or under "
                    "parallel.ShardedTrainer to include it")
                _WARNED_CACHED = True
        if self.dropout is not None:
            y = self.dropout(y)
        return y


class MoETransformerBlock(HybridBlock):
    """Pre-LN transformer layer whose FFN is a routed MoE."""

    def __init__(self, units, hidden_size, num_heads, num_experts,
                 top_k=2, capacity_factor=1.25, dropout=0.0,
                 attention_dropout=0.0, causal=True, layer_norm_eps=1e-5,
                 **kwargs):
        super().__init__(**kwargs)
        from ..gluon.nn import LayerNorm
        from .transformer import MultiHeadAttention
        self.ln1 = LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.attn = MultiHeadAttention(
            units, num_heads, dropout=dropout,
            attention_dropout=attention_dropout, causal=causal)
        self.ln2 = LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.moe = MoELayer(units, hidden_size, num_experts, top_k=top_k,
                            capacity_factor=capacity_factor,
                            dropout=dropout)

    def forward(self, x, mask=None):
        x = x + self.attn(self.ln1(x), mask)
        x = _par.with_sharding_constraint(x, "batch", "seq", None)
        x = x + self.moe(self.ln2(x))
        return _par.with_sharding_constraint(x, "batch", "seq", None)
