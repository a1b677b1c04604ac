"""Faults planted UNDER the looped decoder cell's timed path, for the
builder's chip runs and the CPU tests: each is a context manager that
changes the program (never the reference) the way a wrong implementation
would, so that a run inside it has to come out not ``correct``.  Nothing
here is on any measured path.

    three_passes          one pass fewer than the configuration says
    norm_between_out      the final norm between passes left out: the
                          head and the gate read the normed stream, the
                          next pass is handed the one before the norm
    post_mlp_norm_out     the first layer's post-feed-forward norm left
                          out, in every pass
    uniform_exit          p replaced by 1/P each
    entropy_out           the entropy term dropped
    last_pass_only        the gradient stopped through every pass but the
                          last: the shared weights learn from one use
"""
from __future__ import annotations

import contextlib
from unittest import mock

FAULTS = ("three_passes", "norm_between_out", "post_mlp_norm_out",
          "uniform_exit", "entropy_out", "last_pass_only")


@contextlib.contextmanager
def planted(fault: str):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import hybrid_common as hc
    from mxnet_tpu.ndarray import NDArray

    shell = hc.HybridDecoder
    if fault == "three_passes":
        init = shell.__init__

        def fewer(self, *a, passes=1, **kw):
            init(self, *a, passes=passes - 1, **kw)

        patch = mock.patch.object(shell, "__init__", fewer)
    elif fault == "norm_between_out":
        real = shell._leave

        def unnormed_on(self, y, labels):
            return (y,) + tuple(real(self, y, labels)[1:])

        patch = mock.patch.object(shell, "_leave", unnormed_on)
    elif fault == "post_mlp_norm_out":
        real, first = hc.HalfLayer.forward, []

        def bare(self, x, mask=None):
            if self._op != "ouro_mlp_layer" or (first and first[0] is not self):
                return real(self, x, mask)
            first[:] = [self]
            kept, self.post_norm = self.post_norm, None
            try:
                return real(self, x, mask)
            finally:
                self.post_norm = kept

        patch = mock.patch.object(hc.HalfLayer, "forward", bare)
    elif fault == "uniform_exit":
        patch = mock.patch.object(
            hc, "exit_distribution",
            lambda gates: jnp.full_like(gates, 1.0 / gates.shape[0]))
    elif fault == "entropy_out":
        real = hc.exit_objective
        patch = mock.patch.object(
            hc, "exit_objective",
            lambda losses, gates, beta: real(losses, gates, 0.0))
    elif fault == "last_pass_only":
        one_pass = shell._one_pass

        def passes(self, x, labels):
            # the program's own loop over the passes, pass by pass (a
            # scan has one body), with all but the last cut off
            run = []

            def cut(h, labels):
                got = one_pass(self, h, labels)
                run.append(None)
                if len(run) < self.passes:
                    got = [NDArray(jax.lax.stop_gradient(a.jax))
                           for a in got]
                return got

            with mock.patch.object(self, "_one_pass", cut, create=True):
                return self._loop_passes(x, labels)

        patch = mock.patch.object(shell, "_run_passes", passes)
    else:
        raise ValueError(f"fault {fault!r} is not one of {FAULTS}")
    with patch:
        yield
