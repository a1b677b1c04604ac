"""check_consistency harness (parity: mx.test_utils.check_consistency +
the cross-backend suite pattern of SURVEY.md §4).  On this CPU-only test
env it exercises the dtype axis; on a TPU host the same utility can
compare cpu-vs-tpu backends in one process (``ctx_list=``)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ndarray import ops as F
from mxnet_tpu.test_utils import check_consistency


def test_dtype_consistency_elemwise():
    x = onp.random.RandomState(0).uniform(-1, 1, (4, 6)).astype(onp.float32)
    res = check_consistency(lambda a: (a * 2 + 1).tanh(), [x],
                            dtypes=["float32", "bfloat16"],
                            rtol=3e-2, atol=3e-2)
    # two configs ran on the single cpu ctx
    assert len(res) == 2
    assert res[0][1] == "float32" and res[1][1] == "bfloat16"


def test_dtype_consistency_dense_grads():
    rs = onp.random.RandomState(1)
    x, w = rs.uniform(-1, 1, (6, 16)).astype("f"), \
        rs.uniform(-1, 1, (8, 16)).astype("f")
    res = check_consistency(
        lambda a, b: F.FullyConnected(a, b, None, num_hidden=8,
                                      no_bias=True),
        [x, w], dtypes=["float32", "float16"], rtol=2e-2, atol=2e-2)
    # gradients exist for every input in every config
    for _, _, _, grads in res:
        assert all(g is not None for g in grads)


def test_consistency_catches_divergence():
    """A function whose result depends on dtype must FAIL the check."""
    x = onp.full((4,), 3.0, onp.float32)

    def bad(a):
        # 1e-3 is representable in f32 but rounds to a different value in
        # bf16 amplified far past tolerance
        return (a + 1e-3) * 1e6 - a * 1e6

    with pytest.raises(AssertionError):
        check_consistency(bad, [x], dtypes=["float32", "bfloat16"],
                          rtol=1e-3, atol=1e-3)


def test_consistency_int_inputs_pass_through():
    rs = onp.random.RandomState(2)
    w = rs.uniform(-1, 1, (20, 8)).astype("f")
    idx = onp.array([1, 5, 7], onp.int32)
    check_consistency(lambda a, i: F.take(a, i), [w, idx],
                      dtypes=["float32", "float16"], rtol=2e-2, atol=2e-2)
