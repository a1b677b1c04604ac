"""Faults planted UNDER the sliding-window expert cell's timed path, for
the builder's chip runs and the CPU tests: each is a context manager that
changes the program (never the reference) the way a wrong implementation
would, so that a run inside it has to come out not ``correct``.  Nothing
here is on any measured path.

    window_ignored        the first sliding layer attends over everything
    plain_table_on_full   the full layer is turned by the plain table
                          (YaRN's blend dropped, its amplitude kept)
    attention_factor_out  YaRN's amplitude on cos and sin left out
    weights_not_renormalised  the top-k weights keep the softmax's scale
    gate_product_out      the first held expert computes (x W_u) W_d

``window_1023`` is no fault with a limit on it: every sliding layer's
window one key short, read for the record.
"""
from __future__ import annotations

import contextlib
from unittest import mock

FAULTS = ("window_ignored", "plain_table_on_full", "attention_factor_out",
          "weights_not_renormalised", "gate_product_out")
READ_ONLY = ("window_1023",)


@contextlib.contextmanager
def planted(fault: str):
    import jax.numpy as jnp

    from mxnet_tpu.models import mellum as model
    from mxnet_tpu.models import moe
    from mxnet_tpu.ops import attention, gmm

    if fault in ("window_ignored", "window_1023"):
        init, sliding = model.SlidingGQAttention.__init__, []

        def other_window(self, *a, window=None, **kw):
            if window is not None:
                sliding.append(self)
                if fault == "window_1023":
                    window -= 1
                elif len(sliding) == 1:
                    window = None
            init(self, *a, window=window, **kw)

        patch = mock.patch.object(model.SlidingGQAttention, "__init__",
                                  other_window)
    elif fault in ("plain_table_on_full", "attention_factor_out"):
        real = attention.rope_frequencies

        def table(rope, head_dim):
            if fault == "plain_table_on_full":
                return real(dict(rope, factor=1.0), head_dim)
            return real(rope, head_dim)[0], 1.0

        patch = mock.patch.object(attention, "rope_frequencies", table)
    elif fault == "weights_not_renormalised":
        real = moe.route_softmax_topk

        def plain(*a, norm_topk=True, **kw):
            return real(*a, norm_topk=False, **kw)

        patch = mock.patch.object(moe, "route_softmax_topk", plain)
    elif fault == "gate_product_out":
        # the layer's products come in threes: up, gate, down.  The first
        # held expert's rows of the gate product become the value whose
        # silu is 1
        real, calls = gmm.grouped_matmul, []

        def ungated(lhs, rhs, sizes, **kw):
            calls.append(1)
            out = real(lhs, rhs, sizes, **kw)
            if len(calls) % 3 != 2:
                return out
            first = jnp.arange(out.shape[0])[:, None] < sizes[0]
            return jnp.where(first, jnp.asarray(1.2784645, out.dtype), out)

        patch = mock.patch.object(gmm, "grouped_matmul", ungated)
    else:
        raise ValueError(f"fault {fault!r} is not one of "
                         f"{FAULTS + READ_ONLY}")
    with patch:
        yield
