"""What a recomputed block keeps (PR 43): ``run_blocks(remat=...)`` hands
``jax.checkpoint`` a policy that saves the two residuals ``ops/flash.py``
names (the kernel's output and logsumexp) and nothing else, so the
gradient program runs ONE flash forward kernel a block, not two, and
every value is what it was.

The kernels run interpreted here; the counts are taken on the gradient's
jaxpr (what XLA is handed, after JAX's own dead-code pass over the
recomputation), where a kernel is a ``pallas_call`` with its name.  Every
count is taken beside the same count under the old policy (``None``, or
``checkpoint_dots`` alone), so that it means something.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import base
from mxnet_tpu import observability as obs
from mxnet_tpu.gluon.block import HybridBlock
from mxnet_tpu.gluon.nn import LayerNorm
from mxnet_tpu.models import phi4_flash, transformer as tr
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ndarray.ndarray import swap_values
from mxnet_tpu.ops import flash

T, UNITS, HEADS = 256, 128, 2           # head size 64: the kernel's smallest
OUT_BYTES = HEADS * T * 64 * 4          # float32 here
LSE_BYTES = HEADS * T * 4


@pytest.fixture
def flash_on_cpu(monkeypatch):
    """The dispatcher's TPU branch, its kernels interpreted."""
    monkeypatch.setattr(base, "resolve_exec_platform", lambda x=None: "tpu")
    monkeypatch.setattr(flash, "_default_interpret", lambda x: True)


@pytest.fixture
def old_policy(monkeypatch):
    """Enter it to recompute as the parent did: nothing named is kept."""
    def enter():
        monkeypatch.setattr(
            tr, "_remat_policy",
            lambda remat: (jax.checkpoint_policies.checkpoint_dots
                           if remat == "dots" else None))
    return enter


class FeedForward(HybridBlock):
    """A block with no attention: norm, two products, residual."""

    def __init__(self, units, hidden, **kwargs):
        super().__init__(**kwargs)
        self.ln = LayerNorm(in_channels=units)
        self.ffn = tr.PositionwiseFFN(units, hidden)

    def forward(self, x, mask=None):
        return x + self.ffn(self.ln(x))


def _attention_blocks(n):
    return [tr.TransformerBlock(UNITS, 2 * UNITS, HEADS, causal=True)
            for _ in range(n)]


def _cross_decoder_pair():
    """The SambaY shape: a full-attention layer hands its keys and values
    to a layer that brings only queries (``side_out`` / ``side_in``)."""
    cfg = dict(units=UNITS, mlp_hidden=UNITS, eps=1e-5, num_heads=4,
               num_kv_heads=2, head_dim=64, window=None)
    return [phi4_flash.Phi4FlashLayer("full", 3, cfg),
            phi4_flash.Phi4FlashLayer("cross", 4, cfg)]


def _settled(blocks, seed=0):
    rng = onp.random.default_rng(seed)
    x = mx.nd.array(rng.standard_normal((1, T, UNITS)).astype("f"))
    for b in blocks:
        b.initialize()
    tr.run_blocks(blocks, x, scan=False)        # allocates every parameter
    params = [p for b in blocks for p in b.collect_params().values()]
    # no parameter at an initial zero (a differential head's lambdas),
    # so that no gradient leaf is zero by construction
    vals = [jnp.asarray(0.1 * rng.standard_normal(p.shape), jnp.float32)
            for p in params]
    return x.jax, params, vals


def _value_and_grad(blocks, params, remat, scan=False):
    def loss(vals, v):
        with swap_values([p._data for p in params], list(vals)):
            out = tr.run_blocks(blocks, NDArray(v), scan=scan, remat=remat)
        return jnp.sum(jnp.square(out.jax.astype(jnp.float32)))
    return jax.value_and_grad(loss, argnums=(0, 1))


def _count(jaxpr, found=None):
    """Kernels by name, and the ``dot_general`` outside them, through
    every sub-jaxpr."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            found[eqn.params["name"]] = found.get(eqn.params["name"], 0) + 1
            continue                    # an interpreted kernel's own body
        if name == "dot_general":
            found[name] = found.get(name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count(sub, found)
    return found


def _leaf_pairs(a, b):
    leaves_a, leaves_b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(leaves_a) == len(leaves_b)
    return [(onp.asarray(u), onp.asarray(v))
            for u, v in zip(leaves_a, leaves_b)]


def _same(a, b):
    for u, v in _leaf_pairs(a, b):
        onp.testing.assert_array_equal(u, v)


STACKS = {
    # name: (blocks, scan, flash forward kernels the gradient needs)
    "loop": (lambda: _attention_blocks(2), False, 2),
    "scan": (lambda: _attention_blocks(8), True, 1),    # one body
    "sides": (_cross_decoder_pair, False, 2),
}
CASES = [(stack, remat) for stack in STACKS for remat in (True, "dots")]


@pytest.mark.parametrize("stack,remat", CASES)
def test_one_flash_forward_a_block(flash_on_cpu, old_policy, stack, remat):
    """(a), (c): the forward kernel once a block (once a scan body) where
    the old policy ran it twice; dq and dkv once, as before."""
    make, scan, needed = STACKS[stack]
    blocks = make()
    x, params, vals = _settled(blocks)
    engaged = tr._scan_engaged_count
    got = _count(jax.make_jaxpr(
        _value_and_grad(blocks, params, remat, scan))(vals, x).jaxpr)
    assert (tr._scan_engaged_count > engaged) == scan
    old_policy()
    old = _count(jax.make_jaxpr(
        _value_and_grad(blocks, params, remat, scan))(vals, x).jaxpr)
    assert old["flash_fwd"] == 2 * needed
    assert got["flash_fwd"] == needed
    assert got["flash_bwd"] == old["flash_bwd"] == needed
    if remat is True:       # the projections are still recomputed
        assert got["dot_general"] == old["dot_general"]


@pytest.mark.parametrize("stack,remat", CASES)
def test_values_are_what_they_were(flash_on_cpu, old_policy, stack, remat):
    """(b), (c), (e): loss and every gradient leaf bit-equal to no
    recomputation, and to the old policy wherever that policy is itself
    bit-equal to no recomputation on this backend."""
    make, scan, _needed = STACKS[stack]
    blocks = make()
    x, params, vals = _settled(blocks)
    plain = jax.jit(_value_and_grad(blocks, params, False, scan))(vals, x)
    kept = jax.jit(_value_and_grad(blocks, params, remat, scan))(vals, x)
    old_policy()
    old = jax.jit(_value_and_grad(blocks, params, remat, scan))(vals, x)
    assert all(bool(jnp.any(g != 0)) for g in jax.tree.leaves(kept[1]))
    _same(kept, plain)
    if not all(onp.array_equal(u, v) for u, v in _leaf_pairs(old, plain)):
        # XLA's CPU backend compiles the old policy's second run of an
        # interpreted kernel into other fusions than the first, so that
        # policy is itself not bit-equal to no recomputation in every
        # case here; what is kept is the first run's own arrays
        for u, v in _leaf_pairs(kept, old):
            onp.testing.assert_allclose(u, v, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("remat", [True, "dots"])
def test_a_block_with_no_flash_call_keeps_nothing(old_policy, remat):
    """(d): as many products in the gradient as under the old policy,
    bit-equal gradients, and ``kept_bytes`` 0."""
    blocks = [FeedForward(UNITS, 2 * UNITS) for _ in range(2)]
    x, params, vals = _settled(blocks)
    fn = _value_and_grad(blocks, params, remat)
    tracer = obs.enable_tracing()
    try:
        got = _count(jax.make_jaxpr(fn)(vals, x).jaxpr)
        events = [s.attrs for s in tracer.spans(name="remat.plan")]
    finally:
        obs.disable_tracing()
    kept = jax.jit(fn)(vals, x)
    old_policy()
    fn = _value_and_grad(blocks, params, remat)
    assert got == _count(jax.make_jaxpr(fn)(vals, x).jaxpr)
    assert set(got) == {"dot_general"}
    _same(kept, jax.jit(fn)(vals, x))
    assert events == [dict(layer=i, kept=(), kept_bytes=0) for i in (0, 1)]


@pytest.mark.parametrize("stack", ["loop", "scan"])
def test_remat_plan_says_what_each_block_kept(flash_on_cpu, stack):
    """(f): one event a block, the names and their bytes; nothing kept by
    a trace that is not differentiated."""
    make, scan, _needed = STACKS[stack]
    blocks = make()
    x, params, vals = _settled(blocks)
    tracer = obs.enable_tracing()
    try:
        jax.make_jaxpr(_value_and_grad(blocks, params, True, scan))(vals, x)
        events = [s.attrs for s in tracer.spans(name="remat.plan")]
    finally:
        obs.disable_tracing()
    assert events == [dict(layer=i, kept=("flash_lse", "flash_out"),
                           kept_bytes=OUT_BYTES + LSE_BYTES)
                      for i in range(len(blocks))]
    assert set(events[0]["kept"]) == set(flash.KEPT_NAMES)
    tracer = obs.enable_tracing()
    try:
        jax.make_jaxpr(lambda v: tr.run_blocks(
            blocks, NDArray(v), scan=scan, remat=True).jax)(x)
        forward_only = [s.attrs for s in tracer.spans(name="remat.plan")]
    finally:
        obs.disable_tracing()
    assert [e["kept_bytes"] for e in forward_only] == [0] * len(blocks)


def test_a_name_outside_a_checkpoint_is_the_identity(flash_on_cpu,
                                                     monkeypatch):
    """Serving's prefill, and a model with ``remat`` off: the same
    kernels and the same values with the names as without them."""
    q = jnp.asarray(onp.random.default_rng(1).standard_normal(
        (1, T, HEADS, 64)), jnp.float32)

    def run():
        fn = jax.value_and_grad(lambda q: jnp.sum(
            flash.flash_attention(q, q, q, causal=True)))
        return jax.jit(fn)(q), _count(jax.make_jaxpr(fn)(q).jaxpr)

    named, kernels = run()
    monkeypatch.setattr(flash, "_name", lambda x, name: x)
    bare, bare_kernels = run()
    _same(named, bare)
    assert kernels == bare_kernels == dict(flash_fwd=1, flash_bwd=1)
