#!/usr/bin/env python
"""Cross-backend op consistency battery: the same op runs on the host CPU
XLA backend and the TPU backend in ONE process and outputs/gradients are
cross-compared (parity role: mx.test_utils.check_consistency + the
tests/python/gpu/test_operator_gpu.py re-run pattern, SURVEY.md §4).

Run where the chip is attached, with both backends enabled
(``JAX_PLATFORMS=tpu,cpu`` or unset):

    python tools/tpu_consistency.py            # battery below, cpu vs tpu

As a tool it fails without a TPU.  ``main()`` imported on a CPU-only host
(tests/test_consistency.py) runs the battery as a f32-vs-bf16 dtype check.
"""
from __future__ import annotations

import sys

import numpy as onp

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _flash_case(q, k, v):
    """Pallas flash kernel as an NDArray op (interpret off-TPU, compiled
    on-chip) so check_consistency covers the kernel across backends."""
    from mxnet_tpu.ndarray.ops import invoke
    from mxnet_tpu.ops.flash import flash_attention

    def f(qj, kj, vj):
        return flash_attention(qj, kj, vj, causal=True)

    return invoke("flash_attention", f, [q, k, v])


def _flash_seg_case(q, k, v):
    """Segment-packed flash through the default dispatch: the unit tests
    pin interpret=True with 128-blocks, so this is the only place the
    compiled TPU segment path (1-D seg-id loads, min/max skip reductions,
    mask temporary in VMEM) is exercised at the production tile sizes the
    has_seg-aware VMEM clamp actually selects (1024x512 for d=64 —
    the seg mask temporary pushes full 1024x1024 over budget)."""
    import numpy as _np

    from mxnet_tpu.ndarray.ops import invoke
    from mxnet_tpu.ops.flash import flash_attention

    t = q.shape[1]
    seg = _np.repeat(_np.arange(4, dtype=_np.int32), t // 4)[None, :]

    def f(qj, kj, vj):
        return flash_attention(qj, kj, vj, causal=True, segment_ids=seg)

    return invoke("flash_attention_seg", f, [q, k, v])


def battery():
    from mxnet_tpu.ndarray import ops as F
    from mxnet_tpu.ops import dot_product_attention

    rs = onp.random.RandomState(0)

    def r(*shape):
        return rs.uniform(-1, 1, shape).astype(onp.float32)

    # name: (fn, inputs) or (fn, inputs, opts); opts {"grad_dtypes": False}
    # keeps the gradient compare to same-dtype configs only (BatchNorm's
    # mean/var cancellation makes bf16 grads legitimately loose — exactly
    # why AMP pins BN to f32)
    cases = {
        "dense": (lambda x, w, b: F.FullyConnected(
            x, w, b, num_hidden=32), [r(8, 64), r(32, 64), r(32)]),
        "conv3x3": (lambda x, w: F.Convolution(
            x, w, kernel=(3, 3), num_filter=8, pad=(1, 1), no_bias=True),
            [r(2, 4, 16, 16), r(8, 4, 3, 3)]),
        "batchnorm": (lambda x, g, b, m, v: F.BatchNorm(
            x, g, b, m, v, fix_gamma=False), [r(4, 8, 6, 6), r(8),
                                              r(8), r(8), abs(r(8)) + 1],
            {"grad_dtypes": False}),
        "softmax": (lambda x: F.softmax(x, axis=-1), [r(6, 50)]),
        "log_softmax": (lambda x: F.log_softmax(x, axis=-1), [r(6, 50)]),
        "layernorm": (lambda x, g, b: F.LayerNorm(x, g, b, axis=-1),
                      [r(6, 32), r(32), r(32)]),
        "pool_max": (lambda x: F.Pooling(
            x, kernel=(2, 2), stride=(2, 2), pool_type="max"),
            [r(2, 4, 8, 8)]),
        "pool_avg": (lambda x: F.Pooling(
            x, kernel=(2, 2), stride=(2, 2), pool_type="avg"),
            [r(2, 4, 8, 8)]),
        "reduce_sum": (lambda x: F.sum(x, axis=1), [r(5, 7, 3)]),
        "broadcast_mul": (lambda a, b: F.broadcast_mul(a, b),
                          [r(4, 1, 6), r(1, 5, 6)]),
        "dot": (lambda a, b: F.dot(a, b), [r(16, 24), r(24, 8)]),
        "batch_dot": (lambda a, b: F.batch_dot(a, b),
                      [r(4, 8, 12), r(4, 12, 6)]),
        "take": (lambda w, i: F.take(w, i),
                 [r(50, 16), onp.array([[1, 4], [7, 2]], onp.int32)]),
        "attention": (lambda q, k, v: dot_product_attention(
            q, k, v, causal=True), [r(2, 128, 2, 64), r(2, 128, 2, 64),
                                    r(2, 128, 2, 64)]),
        # flash path across supported head dims — the VMEM-aware block
        # clamp (ops/flash.py) must be safe at d=128/256 on the real chip
        "flash_d64": (_flash_case, [r(1, 256, 2, 64), r(1, 256, 2, 64),
                                    r(1, 256, 2, 64)]),
        "flash_d128": (_flash_case, [r(1, 256, 2, 128), r(1, 256, 2, 128),
                                     r(1, 256, 2, 128)]),
        "flash_d256": (_flash_case, [r(1, 256, 2, 256), r(1, 256, 2, 256),
                                     r(1, 256, 2, 256)]),
        "flash_seg_1024": (_flash_seg_case,
                           [r(1, 1024, 1, 64), r(1, 1024, 1, 64),
                            r(1, 1024, 1, 64)]),
        "gelu": (lambda x: F.Activation(x, act_type="gelu"), [r(8, 32)]),
        "logsumexp": (lambda x: F.logsumexp(x, axis=-1), [r(6, 40)]),
    }
    return cases


def main():
    import mxnet_tpu as mx
    from mxnet_tpu.test_utils import check_consistency

    on_tpu = mx.context.num_tpus() > 0
    if on_tpu:
        ctx_list = [mx.cpu(), mx.tpu(0)]
        dtypes = ["float32"]
        mode = "cpu-vs-tpu f32"
    else:
        ctx_list = [mx.cpu()]
        dtypes = ["float32", "bfloat16"]
        mode = "cpu f32-vs-bf16"
    print(f"consistency battery ({mode})")
    failed = []
    for name, case in battery().items():
        fn, inputs = case[0], case[1]
        opts = case[2] if len(case) > 2 else {}
        grad = True
        if not opts.get("grad_dtypes", True) and len(dtypes) > 1:
            grad = False   # dtype axis active: fwd-only for this case
        try:
            check_consistency(fn, inputs, ctx_list=ctx_list, dtypes=dtypes,
                              grad=grad,
                              rtol=3e-2 if not on_tpu else None,
                              atol=3e-2 if not on_tpu else None)
            print(f"  {name:16s} OK")
        except AssertionError as e:
            failed.append(name)
            print(f"  {name:16s} MISMATCH: {str(e)[:200]}")
        except Exception as e:
            failed.append(name)
            print(f"  {name:16s} ERROR: {type(e).__name__}: {str(e)[:200]}")
    if failed:
        print(f"FAILED: {failed}")
        return 1
    print("all consistent")
    return 0


if __name__ == "__main__":
    # as a tool this is the cpu-vs-tpu battery; the f32-vs-bf16 mode above
    # is what the CPU test suite imports
    from mxnet_tpu.utils.platform import require_tpu
    require_tpu()
    sys.exit(main())
