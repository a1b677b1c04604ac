"""Stateful RNG facade over JAX's functional PRNG.

Parity target: ``python/mxnet/random.py`` + per-device parallel RNG resources
(``src/resource.cc`` kParallelRandom).  MXNet exposes a *stateful* per-context
RNG (``mx.random.seed(n)``); JAX is functional (explicit keys).  Design:

- A process-global :class:`RandomState` holds one root key per Context plus a
  monotonically increasing counter; every stochastic op calls
  :func:`next_key` which folds the counter in — stateful semantics, functional
  core.
- Under ``hybridize``/``jit`` tracing, a concrete key baked into the trace
  would freeze randomness across calls (wrong dropout).  The CachedOp
  machinery installs a *trace key provider* (`push_trace_key`): while tracing,
  ``next_key()`` derives keys from a key that is an *argument* of the jitted
  function, so each invocation gets fresh randomness with zero retraces.
- A caller that only hands its key to a compiled program as an argument
  (``ShardedTrainer``'s step) takes it from :func:`next_key_words`: the same
  root, the same counter, the same bits, folded on the host in numpy, so
  that drawing the key launches nothing on the device.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

import jax
import numpy as onp

from .analysis.lockwitness import named_lock as _named_lock
from .context import Context, current_context

__all__ = ["seed", "next_key", "next_key_words", "RandomState",
           "push_trace_key", "pop_trace_key", "get_state", "host_rng"]

_tls = threading.local()

_M32 = 0xFFFFFFFF
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _threefry2x32(k0: int, k1: int, x0: int, x1: int):
    """Threefry-2x32, 20 rounds, on two words: what
    ``jax._src.prng.threefry_2x32`` computes for one pair of counts, in
    Python integers (a few microseconds, and no program)."""
    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    x0 = (x0 + ks[0]) & _M32
    x1 = (x1 + ks[1]) & _M32
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = (x0 + x1) & _M32
            x1 = ((x1 << r) | (x1 >> (32 - r))) & _M32
            x1 ^= x0
        x0 = (x0 + ks[(i + 1) % 3]) & _M32
        x1 = (x1 + ks[(i + 2) % 3] + i + 1) & _M32
    return x0, x1


def _seed_words(seed_: int):
    """The two words of ``jax.random.PRNGKey(seed_)`` under threefry: the
    seed as a 64-bit integer split in two, or, without x64, its low 32
    bits behind a zero word."""
    s = int(onp.int64(seed_)) + int(jax.config.jax_random_seed_offset)
    return ((s >> 32) & _M32 if jax.config.jax_enable_x64 else 0), s & _M32


def _fold_in_words(root, data: int) -> onp.ndarray:
    """``jax.random.fold_in(root, data)`` for a raw threefry key given as
    its two words, on the host: the root hashes the pair (0, data)."""
    return onp.array(_threefry2x32(root[0], root[1], 0, int(data) & _M32),
                     dtype=onp.uint32)


class RandomState:
    def __init__(self, seed_: int = 0):
        self._lock = _named_lock("random.generator",
                                 "seeded generator state")
        self.seed(seed_)

    def seed(self, seed_: int, ctx: Optional[Context] = None):
        with self._lock:
            if ctx is None or not hasattr(self, "_keys"):
                self._keys: Dict[Context, jax.Array] = {}
                self._seeds: Dict[Context, int] = {}
                self._counters: Dict[Context, int] = {}
                self._base_seed = int(seed_)
            if ctx is not None:
                self._seeds[ctx] = int(seed_) + hash(ctx) % 2**16
                self._keys.pop(ctx, None)
                self._counters[ctx] = 0
            if ctx is None:
                # host-side RNG for data-pipeline shuffling (samplers);
                # reseeded together with the device keys so mx.random.seed
                # controls epoch orders too
                self._host_rng = onp.random.RandomState(
                    int(seed_) & 0x7FFFFFFF)

    def _seed_of(self, ctx: Context) -> int:  # guarded-by: _lock
        """The integer a context's root key is made from; a context seen
        for the first time starts its counter."""
        if ctx not in self._seeds:
            self._seeds[ctx] = (
                self._base_seed + (Context.devtype2id[ctx.device_type] << 8)
                + ctx.device_id)
            self._counters[ctx] = 0
        return self._seeds[ctx]

    def _root(self, ctx: Context) -> jax.Array:  # guarded-by: _lock
        if ctx not in self._keys:
            self._keys[ctx] = jax.random.PRNGKey(self._seed_of(ctx))
        return self._keys[ctx]

    def next_key(self, ctx: Optional[Context] = None) -> jax.Array:
        ctx = ctx or current_context()
        provider = _trace_providers()
        if provider:
            return provider[-1].next()
        with self._lock:
            root = self._root(ctx)
            c = self._counters[ctx]
            self._counters[ctx] = c + 1
        return jax.random.fold_in(root, c)

    def next_key_words(self, ctx: Optional[Context] = None, *,
                       advance: bool = True):
        """:meth:`next_key` for a caller that passes the key on as an
        argument of a compiled program: the counter advances once under
        the lock and the key is ``fold_in(root, c)`` bit for bit, but as
        two host words (numpy ``uint32[2]``) made with no program
        launched and nothing read from a device.  ``advance=False`` only
        looks: the words the next draw would get, the counter left where
        it is (``None`` where it cannot be told without drawing).  Only
        threefry is folded here; under another ``jax_default_prng_impl``
        this is :meth:`next_key`."""
        if jax.config.jax_default_prng_impl != "threefry2x32" \
                or _trace_providers():
            return self.next_key(ctx) if advance else None
        ctx = ctx or current_context()
        with self._lock:
            root = _seed_words(self._seed_of(ctx))
            c = self._counters[ctx]
            if advance:
                self._counters[ctx] = c + 1
        return _fold_in_words(root, c)


class _TraceKeyProvider:
    """Derives per-op keys from a traced key argument during jit tracing."""

    def __init__(self, key):
        self.key = key
        self.count = 0
        self.used = False

    def next(self):
        self.used = True
        k = jax.random.fold_in(self.key, self.count)
        self.count += 1
        return k


def _trace_providers() -> List[_TraceKeyProvider]:
    if not hasattr(_tls, "providers"):
        _tls.providers = []
    return _tls.providers


def push_trace_key(key) -> _TraceKeyProvider:
    p = _TraceKeyProvider(key)
    _trace_providers().append(p)
    return p


def pop_trace_key() -> _TraceKeyProvider:
    return _trace_providers().pop()


_STATE = RandomState(onp.random.randint(0, 2**31 - 1))


def get_state() -> RandomState:
    return _STATE


def seed(seed_state: int, ctx: Optional[Context] = None):
    """mx.random.seed parity: reseed all contexts, or one."""
    _STATE.seed(seed_state, ctx=ctx)


def next_key(ctx: Optional[Context] = None) -> jax.Array:
    return _STATE.next_key(ctx)


def next_key_words(ctx: Optional[Context] = None, *, advance: bool = True):
    return _STATE.next_key_words(ctx, advance=advance)


def host_rng() -> onp.random.RandomState:
    """The process-global host-side RandomState (follows mx.random.seed);
    used by data samplers for shuffle order."""
    return _STATE._host_rng
