"""Faults planted UNDER the SambaY cell's timed path, for the builder's
chip runs and the CPU tests: each is a context manager that changes the
program (never the reference) the way a wrong implementation would, so
that a run inside it has to come out not ``correct``.  Nothing here is on
any measured path.

    window_ignored   the windowed layer attends over everything
    lambda_dropped   a differential head keeps o1 alone (lambda o2 gone)
    memory_gated     the memory is taken AFTER the gate, m = y * silu(z)
    own_keys         the cross-attention layer reads keys and values made
                     from its own projection, not the full layer's
    linear_decay     the scan's decay exp(dt A) replaced by 1 + dt A
"""
from __future__ import annotations

import contextlib
from unittest import mock

FAULTS = ("window_ignored", "lambda_dropped", "memory_gated", "own_keys",
          "linear_decay")


@contextlib.contextmanager
def planted(fault: str):
    import jax

    from mxnet_tpu.models import phi4_flash as model
    from mxnet_tpu.models.hybrid_common import dense
    from mxnet_tpu.ops import sscan

    if fault == "window_ignored":
        init = model.DifferentialAttention.__init__

        def no_window(self, *a, window=None, **kw):
            init(self, *a, window=None, **kw)

        patch = mock.patch.object(model.DifferentialAttention, "__init__",
                                  no_window)
    elif fault == "lambda_dropped":
        patch = mock.patch.object(model, "_differential",
                                  lambda o1, o2, lam: o1)
    elif fault == "memory_gated":
        mix = model.Mamba1Mixer.mix

        def gated(self, hn, in_w, *rest):
            out, y = mix(self, hn, in_w, *rest)
            z = dense(hn, in_w, rest[-1])[..., self._di:]
            return out, y * jax.nn.silu(z)

        patch = mock.patch.object(model.Mamba1Mixer, "mix", gated)
    elif fault == "own_keys":
        mix = model.DifferentialAttention.mix

        def own(self, hn, w_qkv, b_qkv, *rest, kv=None):
            if kv is not None:
                b, t, _u = hn.shape
                cd = rest[-1]
                q = (dense(hn, w_qkv, cd) + b_qkv).astype(cd)
                q = q.reshape(b, t, self._h, self._d)
                kv = (q[:, :, :self._hk], q[:, :, self._hk:2 * self._hk])
            return mix(self, hn, w_qkv, b_qkv, *rest, kv=kv)

        patch = mock.patch.object(model.DifferentialAttention, "mix", own)
    elif fault == "linear_decay":
        patch = mock.patch.object(sscan, "_decay", lambda x: 1.0 + x)
    else:
        raise ValueError(f"fault {fault!r} is not one of {FAULTS}")
    with patch:
        yield
