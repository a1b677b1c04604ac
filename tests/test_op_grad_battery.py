"""Table-driven gradient + consistency battery over the registered op
surface (VERDICT r2 #6; parity model: upstream test_operator.py's
finite-difference check of every op backward, SURVEY.md §4).

Every differentiable public ``mx.nd`` op gets a spec (inputs with the
right domain, closed-over static args) and runs through
``check_numeric_gradient`` (finite differences vs the autograd tape —
catches dispatcher-level mistakes like wrong ``differentiable=`` flags or
amp-cast interactions that trusting jax.vjp cannot) and
``check_consistency`` (cross-(ctx, dtype) execution).  A module-level
assertion enforces >80% coverage of the differentiable surface, so new
ops must either get a spec or an explicit skip reason.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ndarray import ops as OPS
from mxnet_tpu.test_utils import check_consistency, check_numeric_gradient

pytestmark = pytest.mark.slow

_rs = onp.random.RandomState(7)


def R(*s):
    """Smooth-domain input in (-0.9, 0.9)."""
    return _rs.uniform(-0.9, 0.9, s).astype("float32")


def NZ(*s):
    """Bounded away from 0 (kinks/singularities at the origin)."""
    return (_rs.uniform(0.4, 0.9, s) * _rs.choice([-1.0, 1.0], s)) \
        .astype("float32")


def POS(*s):
    """Strictly positive."""
    return _rs.uniform(0.3, 1.8, s).astype("float32")


def GT1(*s):
    return _rs.uniform(1.2, 2.2, s).astype("float32")


def SML(*s):
    """Small values, away from ±1 kinks (smooth_l1, arctanh, erfinv)."""
    return (_rs.uniform(0.05, 0.55, s) * _rs.choice([-1.0, 1.0], s)) \
        .astype("float32")


_I23 = onp.array([[1, 0, 2], [2, 1, 0]], "int32")


def _spd(n):
    a = _rs.uniform(-1, 1, (n, n)).astype("float32")
    return a @ a.T + n * onp.eye(n, dtype="float32")


# ---------------------------------------------------------------------------
# spec table: op name -> (fn taking float NDArrays, [float inputs], tol kw)
# Static/int arguments are closed over so every tabled input is a float
# tensor the checker may perturb.
# ---------------------------------------------------------------------------

def _unary(name, builder=R, **tol):
    return (lambda x, _f=getattr(OPS, name): _f(x), [builder(2, 3)], tol)


def _binary(name, lb=R, rb=R, **tol):
    return (lambda a, b, _f=getattr(OPS, name): _f(a, b),
            [lb(2, 3), rb(2, 3)], tol)


SPECS = {}

for _n in ["arctan", "arcsinh", "cos", "cosh", "degrees", "erf", "exp",
           "expm1", "gelu", "hard_sigmoid", "identity", "log1p",
           "negative", "radians", "sigmoid", "sin", "sinh", "softplus",
           "softsign", "square", "tan", "tanh"]:
    SPECS[_n] = _unary(_n)
for _n in ["abs", "cbrt", "reciprocal", "relu", "relu6", "selu"]:
    SPECS[_n] = _unary(_n, NZ)
for _n in ["sqrt", "rsqrt", "rcbrt", "log", "log10", "log2", "gamma",
           "gammaln"]:
    SPECS[_n] = _unary(_n, POS)
SPECS["erfinv"] = _unary("erfinv", SML)
SPECS["arcsin"] = _unary("arcsin", SML)
SPECS["arccos"] = _unary("arccos", SML)
SPECS["arctanh"] = _unary("arctanh", SML)
SPECS["arccosh"] = _unary("arccosh", GT1)
SPECS["smooth_l1"] = _unary("smooth_l1", SML)
SPECS["prelu"] = (lambda x, a: OPS.prelu(x, a), [NZ(2, 3), R(3)], {})
SPECS["LeakyReLU"] = (lambda x: OPS.LeakyReLU(x, slope=0.1), [NZ(2, 3)], {})
SPECS["Activation"] = (lambda x: OPS.Activation(x, act_type="tanh"),
                       [R(2, 3)], {})
SPECS["clip"] = (lambda x: OPS.clip(x, -2.0, 2.0), [R(2, 3)], {})

for _n in ["add", "subtract", "multiply", "maximum", "minimum",
           "elemwise_add", "elemwise_sub", "elemwise_mul",
           "broadcast_add", "broadcast_sub", "broadcast_mul",
           "broadcast_maximum", "broadcast_minimum"]:
    SPECS[_n] = _binary(_n)
for _n in ["divide", "elemwise_div", "broadcast_div"]:
    SPECS[_n] = _binary(_n, R, NZ)
for _n in ["power", "broadcast_power"]:
    SPECS[_n] = _binary(_n, POS, R)
SPECS["hypot"] = _binary("hypot", NZ, NZ)
SPECS["arctan2"] = _binary("arctan2", NZ, NZ)
SPECS["add_n"] = (lambda a, b, c: OPS.add_n(a, b, c),
                  [R(2, 3), R(2, 3), R(2, 3)], {})

for _n, _kw in [("sum", {}), ("mean", {}), ("nansum", {}),
                ("logsumexp", {"axis": 1}), ("sum_axis", {"axis": 1})]:
    SPECS[_n] = (lambda x, _f=getattr(OPS, _n), _kw=_kw: _f(x, **_kw),
                 [R(2, 3)], {})
SPECS["prod"] = (lambda x: OPS.prod(x), [NZ(2, 3)], {})
SPECS["nanprod"] = (lambda x: OPS.nanprod(x), [NZ(2, 3)], {})
SPECS["max"] = (lambda x: OPS.max(x), [R(2, 3)], {})
SPECS["min"] = (lambda x: OPS.min(x), [R(2, 3)], {})
SPECS["norm"] = (lambda x: OPS.norm(x), [NZ(2, 3)], {})
SPECS["L2Normalization"] = (lambda x: OPS.L2Normalization(x),
                            [NZ(2, 3)], {})
SPECS["div_sqrt_dim"] = _unary("div_sqrt_dim")

SPECS["reshape"] = (lambda x: OPS.reshape(x, shape=(3, 2)), [R(2, 3)], {})
SPECS["reshape_like"] = (lambda x, y: OPS.reshape_like(x, y),
                         [R(2, 3), R(3, 2)], {})
SPECS["Flatten"] = (lambda x: OPS.Flatten(x), [R(2, 3, 2)], {})
SPECS["flatten"] = (lambda x: OPS.flatten(x), [R(2, 3, 2)], {})
SPECS["expand_dims"] = (lambda x: OPS.expand_dims(x, axis=1), [R(2, 3)], {})
SPECS["squeeze"] = (lambda x: OPS.squeeze(x), [R(2, 1, 3)], {})
SPECS["transpose"] = (lambda x: OPS.transpose(x), [R(2, 3)], {})
SPECS["swapaxes"] = (lambda x: OPS.swapaxes(x, 0, 1), [R(2, 3)], {})
SPECS["SwapAxis"] = (lambda x: OPS.SwapAxis(x, dim1=0, dim2=1),
                     [R(2, 3)], {})
SPECS["tile"] = (lambda x: OPS.tile(x, reps=(2, 1)), [R(2, 3)], {})
SPECS["repeat"] = (lambda x: OPS.repeat(x, repeats=2, axis=0),
                   [R(2, 3)], {})
SPECS["flip"] = (lambda x: OPS.flip(x, axis=0), [R(2, 3)], {})
SPECS["reverse"] = (lambda x: OPS.reverse(x, axis=0), [R(2, 3)], {})
SPECS["slice"] = (lambda x: OPS.slice(x, begin=(0, 1), end=(2, 3)),
                  [R(2, 3)], {})
SPECS["slice_axis"] = (lambda x: OPS.slice_axis(x, axis=1, begin=0, end=2),
                       [R(2, 3)], {})
SPECS["slice_like"] = (lambda x, y: OPS.slice_like(x, y),
                       [R(3, 4), R(2, 3)], {})
SPECS["broadcast_to"] = (lambda x: OPS.broadcast_to(x, shape=(2, 3)),
                         [R(1, 3)], {})
SPECS["broadcast_axis"] = (
    lambda x: OPS.broadcast_axis(x, axis=0, size=2), [R(1, 3)], {})
SPECS["broadcast_like"] = (lambda x, y: OPS.broadcast_like(x, y),
                           [R(1, 3), R(2, 3)], {})
SPECS["Pad"] = (
    lambda x: OPS.Pad(x, mode="constant",
                      pad_width=(0, 0, 0, 0, 1, 1, 1, 1)),
    [R(1, 1, 2, 3)], {})
SPECS["pad"] = (
    lambda x: OPS.pad(x, mode="constant",
                      pad_width=(0, 0, 0, 0, 1, 1, 1, 1)),
    [R(1, 1, 2, 3)], {})
SPECS["Concat"] = (lambda a, b: OPS.Concat(a, b, dim=1),
                   [R(2, 2), R(2, 3)], {})
SPECS["concat"] = (lambda a, b: OPS.concat(a, b, dim=1),
                   [R(2, 2), R(2, 3)], {})
SPECS["stack"] = (lambda a, b: OPS.stack(a, b, axis=0),
                  [R(2, 3), R(2, 3)], {})
SPECS["split"] = (lambda x: OPS.split(x, num_outputs=2, axis=1)[0],
                  [R(2, 4)], {})
SPECS["SliceChannel"] = (
    lambda x: OPS.SliceChannel(x, num_outputs=2, axis=1)[0], [R(2, 4)], {})
SPECS["Crop"] = (
    lambda x: OPS.Crop(x, offset=(1, 1), h_w=(2, 2)), [R(1, 1, 4, 4)], {})
SPECS["diag"] = (lambda x: OPS.diag(x), [R(3, 3)], {})
SPECS["where"] = (
    lambda x, y: OPS.where(nd.array(_I23 % 2, dtype="int32"), x, y),
    [R(2, 3), R(2, 3)], {})
SPECS["take"] = (
    lambda w: OPS.take(w, nd.array(_I23, dtype="int32")), [R(4, 2)], {})
SPECS["pick"] = (
    lambda x: OPS.pick(x, nd.array([1, 0], dtype="int32"), axis=1),
    [R(2, 3)], {})
SPECS["gather_nd"] = (
    lambda x: OPS.gather_nd(x, nd.array([[0, 1], [1, 2]], dtype="int32")),
    [R(2, 3)], {})
SPECS["choose_element_0index"] = (
    lambda x: OPS.choose_element_0index(x, nd.array([1, 0],
                                                    dtype="int32")),
    [R(2, 3)], {})
SPECS["Embedding"] = (
    lambda w: OPS.Embedding(nd.array([1, 3], dtype="int32"), w,
                            input_dim=4, output_dim=2),
    [R(4, 2)], {})
SPECS["SequenceReverse"] = (lambda x: OPS.SequenceReverse(x),
                            [R(3, 2, 2)], {})
SPECS["SequenceLast"] = (lambda x: OPS.SequenceLast(x), [R(3, 2, 2)], {})
SPECS["SequenceMask"] = (
    lambda x: OPS.SequenceMask(
        x, sequence_length=nd.array([1, 2], dtype="int32"),
        use_sequence_length=True),
    [R(3, 2, 2)], {})
# long-tail sweep ops
SPECS["LRN"] = (lambda x: OPS.LRN(x, nsize=3), [R(1, 4, 3, 3)], {})
SPECS["SoftmaxActivation"] = (lambda x: OPS.SoftmaxActivation(x),
                              [R(2, 3, 2)], {})
SPECS["depth_to_space"] = (lambda x: OPS.depth_to_space(x, 2),
                           [R(1, 4, 2, 2)], {})
SPECS["space_to_depth"] = (lambda x: OPS.space_to_depth(x, 2),
                           [R(1, 1, 4, 4)], {})
SPECS["batch_take"] = (
    lambda x: OPS.batch_take(x, nd.array([1, 0], dtype="int32")),
    [R(2, 3)], {})
SPECS["cumsum"] = (lambda x: OPS.cumsum(x, axis=1), [R(2, 3)], {})
SPECS["cumprod"] = (lambda x: OPS.cumprod(x, axis=1), [NZ(2, 3)], {})
SPECS["moments"] = (lambda x: OPS.moments(x, axes=(0,))[0] +
                    OPS.moments(x, axes=(0,))[1], [R(3, 4)], {})
SPECS["linalg_det"] = (lambda a: OPS.linalg_det(a), [_spd(3)],
                       {"rtol": 0.05, "atol": 0.05})
SPECS["linalg_inverse"] = (lambda a: OPS.linalg_inverse(a), [_spd(3)],
                           {"rtol": 0.05, "atol": 0.02})
SPECS["linalg_slogdet"] = (lambda a: OPS.linalg_slogdet(a)[1], [_spd(3)],
                           {"rtol": 0.05, "atol": 0.01})
SPECS["linalg_extractdiag"] = (lambda a: OPS.linalg_extractdiag(a),
                               [R(3, 3)], {})
SPECS["linalg_makediag"] = (lambda a: OPS.linalg_makediag(a), [R(3)], {})
SPECS["box_iou"] = (
    lambda a, b: OPS.box_iou(a, b),
    [onp.array([[0.1, 0.1, 0.9, 0.8]], "f"),
     onp.array([[0.2, 0.0, 0.8, 0.7]], "f")], {"rtol": 0.05, "atol": 0.01})
_GRID = onp.stack(onp.meshgrid(onp.linspace(-0.9, 0.9, 4),
                               onp.linspace(-0.9, 0.9, 4)),
                  axis=0)[None].astype("f")
SPECS["BilinearSampler"] = (
    lambda x: OPS.BilinearSampler(x, nd.array(_GRID)),
    [R(1, 2, 4, 4)], {"rtol": 0.05, "atol": 0.01})
SPECS["GridGenerator"] = (
    lambda t: OPS.GridGenerator(t, target_shape=(3, 3)),
    [onp.array([[1.1, 0.1, 0.0, -0.1, 0.9, 0.1]], "f")], {})
SPECS["SpatialTransformer"] = (
    lambda x, t: OPS.SpatialTransformer(x, t, target_shape=(4, 4)),
    [R(1, 2, 4, 4),
     onp.array([[0.9, 0.05, 0.0, 0.05, 0.9, 0.0]], "f")],
    {"rtol": 0.05, "atol": 0.02})
SPECS["ROIAlign"] = (
    lambda x: OPS.ROIAlign(x, nd.array([[0, 1.0, 1.0, 6.0, 6.0]]),
                           pooled_size=(2, 2)),
    [R(1, 2, 8, 8)], {"rtol": 0.05, "atol": 0.02})

SPECS["dot"] = (lambda a, b: OPS.dot(a, b), [R(2, 3), R(3, 2)], {})
SPECS["batch_dot"] = (lambda a, b: OPS.batch_dot(a, b),
                      [R(2, 2, 3), R(2, 3, 2)], {})
SPECS["matmul"] = (lambda a, b: OPS.matmul(a, b), [R(2, 3), R(3, 2)], {})
SPECS["linalg_gemm2"] = (lambda a, b: OPS.linalg_gemm2(a, b),
                         [R(2, 3), R(3, 2)], {})
SPECS["linalg_syrk"] = (lambda a: OPS.linalg_syrk(a), [R(2, 3)], {})
SPECS["linalg_potrf"] = (lambda a: OPS.linalg_potrf(a), [_spd(3)],
                         {"rtol": 0.05, "atol": 0.01})
SPECS["linalg_trsm"] = (
    lambda a, b: OPS.linalg_trsm(a, b),
    [onp.linalg.cholesky(_spd(3)).astype("float32"), R(3, 2)],
    {"rtol": 0.05, "atol": 0.01})
SPECS["interleaved_matmul_selfatt_qk"] = (
    lambda x: OPS.interleaved_matmul_selfatt_qk(x, heads=2),
    [R(3, 1, 2 * 3 * 4)], {})
SPECS["interleaved_matmul_selfatt_valatt"] = (
    lambda kqv, att: OPS.interleaved_matmul_selfatt_valatt(
        kqv, att, heads=2),
    [R(3, 1, 2 * 3 * 4), POS(2, 3, 3)], {})

SPECS["FullyConnected"] = (
    lambda x, w, b: OPS.FullyConnected(x, w, b, num_hidden=3),
    [R(2, 4), R(3, 4), R(3)], {})
SPECS["Convolution"] = (
    lambda x, w, b: OPS.Convolution(x, w, b, kernel=(3, 3), num_filter=2,
                                    pad=(1, 1)),
    [R(1, 2, 4, 4), R(2, 2, 3, 3), R(2)], {"rtol": 0.05, "atol": 0.01})
SPECS["Deconvolution"] = (
    lambda x, w: OPS.Deconvolution(x, w, kernel=(2, 2), num_filter=2,
                                   no_bias=True),
    [R(1, 2, 3, 3), R(2, 2, 2, 2)], {"rtol": 0.05, "atol": 0.01})
SPECS["Pooling"] = (
    lambda x: OPS.Pooling(x, kernel=(2, 2), pool_type="avg",
                          stride=(2, 2)),
    [R(1, 1, 4, 4)], {})
SPECS["UpSampling"] = (
    lambda x: OPS.UpSampling(x, scale=2, sample_type="nearest"),
    [R(1, 1, 2, 2)], {})
SPECS["BatchNorm"] = (
    lambda x, g, b: OPS.BatchNorm(
        x, g, b, nd.zeros((2,)), nd.ones((2,)), fix_gamma=False,
        use_global_stats=True),
    [R(3, 2), POS(2), R(2)], {"rtol": 0.05, "atol": 0.01})
SPECS["LayerNorm"] = (
    lambda x, g, b: OPS.LayerNorm(x, g, b),
    [R(2, 3), POS(3), R(3)], {"rtol": 0.05, "atol": 0.01})
SPECS["GroupNorm"] = (
    lambda x, g, b: OPS.GroupNorm(x, g, b, num_groups=2),
    [R(1, 4, 3), POS(4), R(4)], {"rtol": 0.05, "atol": 0.01})
SPECS["InstanceNorm"] = (
    lambda x, g, b: OPS.InstanceNorm(x, g, b),
    [R(2, 2, 3), POS(2), R(2)], {"rtol": 0.05, "atol": 0.01})
SPECS["softmax"] = (lambda x: OPS.softmax(x, axis=-1), [R(2, 3)], {})
SPECS["log_softmax"] = (lambda x: OPS.log_softmax(x, axis=-1),
                        [R(2, 3)], {})
SPECS["softmax_cross_entropy"] = (
    lambda x: OPS.softmax_cross_entropy(x, nd.array([1, 0],
                                                    dtype="int32")),
    [R(2, 3)], {})
SPECS["MakeLoss"] = (lambda x: OPS.MakeLoss(x ** 2), [R(2, 3)], {})
SPECS["make_loss"] = (lambda x: OPS.make_loss(x ** 2), [R(2, 3)], {})

# ---------------------------------------------------------------------------
# Explicitly NOT gradient-checked, with the reason (forward-only or n/a).
# ---------------------------------------------------------------------------
NONDIFF = {
    # integer / boolean outputs
    "argmax", "argmin", "argsort", "topk", "one_hot", "shape_array",
    "size_array", "ravel_multi_index", "unravel_index",
    "equal", "not_equal", "greater", "greater_equal", "lesser",
    "lesser_equal", "logical_and", "logical_or", "logical_xor",
    "logical_not", "isfinite", "isinf", "isnan",
    "broadcast_equal", "broadcast_not_equal", "broadcast_greater",
    "broadcast_greater_equal", "broadcast_lesser",
    "broadcast_lesser_equal", "broadcast_logical_and",
    "broadcast_logical_or", "broadcast_logical_xor",
    # piecewise-constant (analytic grad 0; finite differences see jumps)
    # and sign (registered differentiable=False in the dispatcher)
    "ceil", "floor", "fix", "rint", "round", "trunc", "sort", "sign",
    # modulo family: grad w.r.t. divisor undefined at jumps
    "mod", "broadcast_mod", "floor_divide",
    # randomness (non-deterministic between evals)
    "normal", "uniform", "shuffle", "random_bernoulli",
    "random_exponential", "random_gamma",
    "random_generalized_negative_binomial", "random_negative_binomial",
    "random_normal", "random_poisson", "random_randint",
    "random_uniform", "sample_exponential", "sample_gamma",
    "sample_multinomial", "sample_normal", "sample_poisson",
    "sample_uniform", "Dropout",
    # gradient-stopping / custom-backward semantics by design
    "BlockGrad", "stop_gradient", "SoftmaxOutput",
    "LinearRegressionOutput",
    # dtype / constant factories (zero or no gradient)
    "Cast", "cast", "zeros_like", "ones_like", "arange_like",
    # index scatter (int index input drives the op)
    "scatter_nd",
    # NMS: output is a keep/-1 row masking (piecewise-constant selection)
    "box_nms",
    # stateful recurrent wrapper (covered by dedicated RNN tests)
    "RNN",
    # max-pool over generated ROIs (kink-dominated; dedicated exact test
    # in test_amp_profiler_image.py)
    "ROIPooling",
}


def test_battery_covers_differentiable_surface():
    all_ops = set(OPS.__all__)
    diff_ops = all_ops - NONDIFF
    covered = set(SPECS) & all_ops
    missing = sorted(diff_ops - covered)
    ratio = len(covered) / len(diff_ops)
    assert ratio > 0.80, (
        f"op-gradient battery covers {ratio:.0%} of the differentiable "
        f"surface ({len(covered)}/{len(diff_ops)}); missing: {missing}")


@pytest.mark.parametrize("name", sorted(n for n in SPECS
                                        if hasattr(OPS, n)))
def test_numeric_gradient(name):
    fn, inputs, tol = SPECS[name]

    def scalarized(*xs):
        out = fn(*xs)
        if isinstance(out, (tuple, list)):
            out = out[0]
        return (out * out).sum()

    check_numeric_gradient(scalarized, [onp.array(a) for a in inputs],
                           **tol)


@pytest.mark.parametrize("name", sorted(n for n in SPECS
                                        if hasattr(OPS, n)))
def test_consistency(name):
    """On a TPU host: cpu-vs-tpu f32 with gradients.  On a CPU-only host
    the default single config compares nothing, so force an f32-vs-bf16
    dtype axis (forward-only; bf16 grads of norm-style ops are
    legitimately loose)."""
    fn, inputs, _ = SPECS[name]

    def first(*xs):
        out = fn(*xs)
        return out[0] if isinstance(out, (tuple, list)) else out

    from mxnet_tpu import context as ctx_mod
    if ctx_mod.num_tpus():
        check_consistency(first, [onp.array(a) for a in inputs])
    else:
        if name in _NO_BF16:
            pytest.skip("no bf16 kernel on the CPU backend")
        check_consistency(first, [onp.array(a) for a in inputs],
                          dtypes=["float32", "bfloat16"], grad=False,
                          rtol=4e-2, atol=4e-2)


# ops whose CPU backend has no bf16 kernel (LAPACK-backed)
_NO_BF16 = {"linalg_potrf", "linalg_inverse", "linalg_slogdet",
            "linalg_det"}
