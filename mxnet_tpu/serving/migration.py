"""KV migration transport for disaggregated prefill/decode serving
(docs/serving.md "Disaggregated serving").

A prefill-role :class:`~.engine.InferenceEngine` finishes a request's
prefill and, instead of entering decode, exports the request's KV state
as a :class:`MigrationBundle` — a self-describing, layer-major host
copy of exactly the rows/pages the prompt wrote, plus everything the
decode side needs to resume the request *token-identically*: the
prompt, the first token (already sampled from the prefill logits), the
remaining budget, and the per-request sampling state.  Because every
sampling draw folds the request's seeded key with its ABSOLUTE position
(:mod:`.sampling`), the decode-role engine reproduces the exact token
stream the prefill engine would have produced colocated — migration
moves *where* decode runs, never *what* it produces.

Integrity: the bundle carries a BLAKE2b-128 tree digest
(:class:`~mxnet_tpu.resilience.integrity.TreeHasher` — the same hasher
that guards checkpoints) over a canonical header plus every array's
bytes.  :func:`verify_bundle` recomputes it on the receiving side
BEFORE any slot or page is claimed, so a torn or tampered transfer is
a typed :class:`~.errors.MigrationDigestError` and the decode pool
stays pristine — a corrupt bundle is never adopted.

The transport is host-side by design: bundles are plain numpy, so the
same bytes work in-process (the tests and the chaos scenarios), over
shared memory, or pickled across an RPC boundary.  Device placement is
the *importing* engine's job (it installs pages under its own mesh
sharding), which is what lets a prefill replica and a decode replica
run different mesh shapes.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as onp

from ..resilience.integrity import TreeHasher
from .errors import MigrationDigestError, MigrationError

__all__ = ["MigrationBundle", "MIGRATION_SCHEMA_VERSION",
           "export_bundle", "bundle_digest", "verify_bundle",
           "PrefixSeed", "PREFIX_SEED_SCHEMA_VERSION",
           "seed_digest", "verify_seed"]

#: bump when the bundle field layout changes — adopt() refuses bundles
#: from a different schema instead of misinterpreting them
#: (v2: quantized KV — bundles declare ``kv_quant`` so an int8 page
#: gather can never be reinterpreted as fp32 payload, or vice versa)
MIGRATION_SCHEMA_VERSION = 2


class MigrationBundle:
    """One request's migratable state.  ``arrays`` holds one host numpy
    array per KV-cache pytree leaf, in ``jax.tree_util.tree_leaves``
    order of the exporting engine's cache — ``(n_pages, page_size, …)``
    page gathers for the paged layout, ``(prompt_len, …)`` row slices
    for dense.  Everything else is plain scalars/lists, so the bundle
    pickles cleanly across process boundaries."""

    __slots__ = ("schema", "source", "layout", "page_size", "kv_quant",
                 "prompt", "prompt_len", "first_token", "max_new_tokens",
                 "eos_id", "deadline", "priority", "temperature", "top_k",
                 "top_p", "seed", "n_pages", "arrays", "trace_id",
                 "route_hint", "digest")

    def __init__(self, *, source: str, layout: str, page_size: int,
                 prompt, first_token: int, max_new_tokens: int,
                 eos_id: Optional[int], deadline: Optional[float],
                 priority: int, temperature: float, top_k: int,
                 top_p: float, seed: int, n_pages: int,
                 arrays: List[onp.ndarray],
                 trace_id: Optional[str] = None,
                 route_hint: Optional[bytes] = None,
                 kv_quant: Optional[str] = None):
        self.schema = MIGRATION_SCHEMA_VERSION
        self.source = source
        self.layout = layout
        self.page_size = int(page_size)
        # KV storage dtype contract (None = fp-native, 'int8' = int8
        # pages + fp32 scale sidecars interleaved in leaf order).  A
        # header field, not an inference from dtypes: digest-pinned so
        # the importing engine refuses a mismatched arm instead of
        # scattering scales into payload pages.
        self.kv_quant = kv_quant
        self.prompt = onp.asarray(prompt, "int32")
        self.prompt_len = int(self.prompt.shape[0])
        self.first_token = int(first_token)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.deadline = deadline
        self.priority = int(priority)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed)
        self.n_pages = int(n_pages)
        self.arrays = arrays
        self.trace_id = trace_id
        # opaque routing cookie from submit(route_hint=): lets the
        # fleet router place the decode half by the SAME affinity key
        # it routed the prefill by (it must not re-derive the key — the
        # prompt now self-matches in the radix tracker and would key
        # differently; docs/fleet.md "Disaggregated serving")
        self.route_hint = None if route_hint is None else bytes(route_hint)
        self.digest: Optional[str] = None

    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in self.arrays)
                   + self.prompt.nbytes)

    def __repr__(self):
        return (f"MigrationBundle(source={self.source!r}, "
                f"layout={self.layout!r}, prompt_len={self.prompt_len}, "
                f"n_pages={self.n_pages}, leaves={len(self.arrays)}, "
                f"{self.nbytes()} bytes)")


def _header_bytes(b: MigrationBundle) -> bytes:
    """Canonical byte encoding of everything about the bundle that is
    NOT array payload — scalar fields plus each array's shape/dtype —
    so the digest pins metadata and data together: a bundle whose
    arrays were swapped or whose position/seed was edited mismatches
    just like flipped payload bits."""
    head = (b.schema, b.layout, b.page_size, b.kv_quant, b.prompt_len,
            b.first_token, b.max_new_tokens, b.eos_id, b.priority,
            b.temperature, b.top_k, b.top_p, b.seed, b.n_pages,
            b.route_hint,
            tuple((tuple(a.shape), str(a.dtype)) for a in b.arrays))
    return repr(head).encode()


def bundle_digest(b: MigrationBundle) -> str:
    """BLAKE2b-128 tree digest over the canonical header and every
    array's contiguous bytes, in leaf order."""
    h = TreeHasher()
    h.update(_header_bytes(b))
    h.update(onp.ascontiguousarray(b.prompt).tobytes())
    for a in b.arrays:
        h.update(onp.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def verify_bundle(b: MigrationBundle) -> None:
    """Receiving-side gate: schema must match and the recomputed digest
    must equal the one stamped at export.  Raises typed — callers run
    this BEFORE claiming any slot/page so rejection has nothing to
    undo."""
    if getattr(b, "schema", None) != MIGRATION_SCHEMA_VERSION:
        raise MigrationError(
            f"migration bundle schema {getattr(b, 'schema', None)!r} != "
            f"{MIGRATION_SCHEMA_VERSION} — refusing to reinterpret a "
            f"foreign layout")
    if not b.digest:
        raise MigrationDigestError(
            "migration bundle carries no digest — refusing an "
            "unverifiable transfer")
    got = bundle_digest(b)
    if got != b.digest:
        raise MigrationDigestError(
            f"migration bundle digest mismatch (want {b.digest}, got "
            f"{got}): torn or corrupted transfer — bundle NOT adopted, "
            f"decode pool untouched")


def export_bundle(eng, slot: int, st, first_token: int) -> MigrationBundle:
    """Host-copy one slot's KV state off ``eng`` right after its
    prefill completed (``st.filled == st.prompt_len``, nothing
    generated yet).  Runs on the scheduler thread under ``_step_lock``
    — the slot cannot move while we read it.  Paged layout gathers
    exactly ``st.pages`` (shared prefix pages export fine: the copy
    takes their *content*, refcounts stay with the exporter); dense
    slices the slot's first ``prompt_len`` rows.  The returned bundle
    is fully self-describing and digest-stamped."""
    import jax
    import jax.numpy as jnp

    req = st.request
    leaves = jax.tree_util.tree_leaves(eng._caches)
    if eng._paged:
        pids = jnp.asarray(onp.asarray(st.pages, "int32"))
        arrays = [onp.asarray(leaf[pids]) for leaf in leaves]
        n_pages = len(st.pages)
    else:
        arrays = [onp.asarray(leaf[slot, :st.prompt_len])
                  for leaf in leaves]
        n_pages = 0
    b = MigrationBundle(
        source=eng.name, layout=eng.kv_layout,
        page_size=eng.page_size if eng._paged else 0,
        prompt=req.payload, first_token=first_token,
        max_new_tokens=st.max_new_tokens, eos_id=req.eos_id,
        deadline=req.deadline, priority=req.priority,
        temperature=req.temperature, top_k=req.top_k, top_p=req.top_p,
        seed=req.seed, n_pages=n_pages, arrays=arrays,
        trace_id=req.trace_id, route_hint=req.route_hint,
        kv_quant=eng.kv_quant)
    b.digest = bundle_digest(b)
    return b


# --------------------------------------------------- prefix-seed transport

#: bump when the seed field layout changes — seed_prefix() refuses
#: seeds from a different schema instead of misinterpreting them
#: (v2: quantized KV — seeds declare ``kv_quant``; the scale sidecars
#: ride ``arrays`` like any other leaf and are digest-sealed with it)
PREFIX_SEED_SCHEMA_VERSION = 2


class PrefixSeed:
    """One cached prefix entry's migratable state (docs/fleet.md
    "Elastic fleet"): the token sequence a prefix-cache entry spells
    plus a host copy of its K/V — dense: the pool row's first
    ``length`` positions per cache leaf; paged: a gather of the entry's
    whole pages.  A replica leaving the fleet exports its hot entries
    as seeds and the router re-plants them on survivors via the
    ordinary prefix-insert path, so warm prompt families survive
    scale-down instead of going cold.

    Same digest discipline as :class:`MigrationBundle`: a BLAKE2b-128
    tree digest over a canonical header + every array's bytes, checked
    by :func:`verify_seed` on the importing side BEFORE any row or
    page is claimed."""

    __slots__ = ("schema", "source", "layout", "page_size", "kv_quant",
                 "tokens", "length", "arrays", "digest")

    def __init__(self, *, source: str, layout: str, page_size: int,
                 tokens, length: int, arrays: List[onp.ndarray],
                 kv_quant: Optional[str] = None):
        self.schema = PREFIX_SEED_SCHEMA_VERSION
        self.source = source
        self.layout = layout
        self.page_size = int(page_size)
        self.kv_quant = kv_quant
        self.tokens = onp.asarray(tokens, "int32")
        self.length = int(length)
        self.arrays = arrays
        self.digest: Optional[str] = None

    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in self.arrays)
                   + self.tokens.nbytes)

    def __repr__(self):
        return (f"PrefixSeed(source={self.source!r}, "
                f"layout={self.layout!r}, length={self.length}, "
                f"leaves={len(self.arrays)}, {self.nbytes()} bytes)")


def _seed_header_bytes(s: PrefixSeed) -> bytes:
    head = (s.schema, s.layout, s.page_size, s.kv_quant, s.length,
            tuple((tuple(a.shape), str(a.dtype)) for a in s.arrays))
    return repr(head).encode()


def seed_digest(s: PrefixSeed) -> str:
    """BLAKE2b-128 tree digest over the canonical header, the token
    sequence, and every array's contiguous bytes, in leaf order."""
    h = TreeHasher()
    h.update(_seed_header_bytes(s))
    # zero-copy buffer views: tobytes() would duplicate every leaf on
    # the hash path, and seal/verify sit on the tier's demote and
    # promote critical paths
    h.update(memoryview(onp.ascontiguousarray(s.tokens)).cast("B"))
    for a in s.arrays:
        h.update(memoryview(onp.ascontiguousarray(a)).cast("B"))
    return h.hexdigest()


def verify_seed(s: PrefixSeed) -> None:
    """Importing-side gate, mirroring :func:`verify_bundle`: schema
    must match and the recomputed digest must equal the one stamped at
    export — checked BEFORE any row/page claim, so a rotten seed can
    never poison a survivor's pool."""
    if getattr(s, "schema", None) != PREFIX_SEED_SCHEMA_VERSION:
        raise MigrationError(
            f"prefix seed schema {getattr(s, 'schema', None)!r} != "
            f"{PREFIX_SEED_SCHEMA_VERSION} — refusing to reinterpret "
            f"a foreign layout")
    if not s.digest:
        raise MigrationDigestError(
            "prefix seed carries no digest — refusing an unverifiable "
            "transfer")
    got = seed_digest(s)
    if got != s.digest:
        raise MigrationDigestError(
            f"prefix seed digest mismatch (want {s.digest}, got {got}):"
            f" torn or corrupted transfer — seed NOT planted, prefix "
            f"pool untouched")
