"""KV-cache slot management for continuous batching.

The engine owns ONE persistent batched KV cache per layer, shaped
(S+1+P, Tmax, H, D): rows 0..S-1 are SLOTS a generation request leases
for its lifetime, row S is SCRATCH (the write target for padding rows of
a bucketed prefill, and for free slots during a decode step — XLA wants
a fixed shape, so every row computes every step), and rows S+1..S+P are
the PREFIX POOL (prefix_cache.py) holding the K/V of cached prompt
prefixes — decode and prefill only ever index rows < S+1, so pool rows
are never written except by the engine's explicit row-to-row copies.
This is the fixed-shape, XLA-friendly version of vLLM's paged KV blocks:
instead of paging, a request leases a whole row, and "continuous
batching" (Orca) falls out of rows being at independent positions —
admission drops a new request into any free row mid-flight without
disturbing the others.

:class:`SlotAllocator` tracks the lease lifecycle (admit → [prefix copy
→ chunked prefill…] → decode… → free) plus per-slot decode state; it is
scheduler-thread-only (no locks) — the engine serializes all access.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from .errors import ServingError

__all__ = ["SlotState", "SlotAllocator"]


class SlotState:
    """Decode-time state of one leased slot.

    A slot is PREFILLING from lease until its first token: ``filled``
    counts cache positions already populated (a prefix-cache copy plus
    any completed prefill chunks); once the final chunk's logits yield
    the first token, ``last_token`` is set and the slot joins the decode
    batch.  ``pinned`` holds the prefix-cache entry this slot copied
    from, refcounted for the whole prefill so LRU eviction can never
    reassign a row a retried copy might still read."""

    __slots__ = ("request", "prompt_len", "pos", "last_token", "generated",
                 "max_new_tokens", "tokens", "filled", "pinned", "t_first",
                 "pages", "pages_shared", "waiting", "tier_promo")

    def __init__(self, request, prompt_len: int, max_new_tokens: int,
                 tokens=None):
        self.request = request
        self.prompt_len = prompt_len
        self.max_new_tokens = max_new_tokens
        # pos == position of last_token == where the NEXT decode step
        # writes its K/V (the step consumes last_token at pos, emits the
        # token for pos+1)
        self.pos = prompt_len
        self.last_token: Optional[int] = None
        self.generated: List[int] = []
        self.tokens = tokens          # full prompt (decode mode)
        self.filled = 0               # populated K/V positions [0, filled)
        self.pinned = None            # PrefixEntry read-pinned while prefilling
        self.t_first: Optional[float] = None   # first-token wall time (TTFT)
        # paged KV layout only (docs/serving.md "Paged KV"): the slot's
        # claimed physical pages in logical order (page i covers
        # positions [i*page_size, (i+1)*page_size)); the first
        # ``pages_shared`` of them were shared-in whole from a prefix
        # entry and are READ-ONLY to this slot (scrub-on-NaN must know
        # which pages the slot could have written); and a transient
        # flag set when a page allocation was deferred this cycle (the
        # slot sits out prefill/decode until pages arrive)
        self.pages: List[int] = []
        self.pages_shared = 0
        self.waiting = False
        # tiered prefix cache (docs/serving.md "Tiered prefix cache"):
        # (entry, handle, t0) while this slot waits on an async
        # host→device promotion of a tier-2 claim — the slot sits out
        # prefill until the upload resolves (or times out → recompute)
        self.tier_promo = None

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens

    @property
    def prefilling(self) -> bool:
        return self.last_token is None

    @property
    def remaining(self) -> int:
        """Decode budget left — what speculation may accept at most."""
        return self.max_new_tokens - len(self.generated)

    def advance(self, token: int):
        """Record one generated token; generated[i] sits at position
        prompt_len + i, so pos tracks the LAST token's position."""
        self.generated.append(token)
        self.last_token = token
        self.pos = self.prompt_len + len(self.generated) - 1

    def advance_many(self, tokens):
        """Record a speculation cycle's ACCEPTED tokens in order.  The
        invariant is unchanged — ``pos`` ends at the LAST accepted
        token's position, so K/V the verify forward wrote BEYOND the
        accepted prefix sit past ``pos`` and are rewritten before they
        can be attended (the same argument chunk padding relies on):
        rejected speculation rewinds by simply not advancing, which is
        also why preemption always parks at the last accepted position,
        never mid-draft."""
        for t in tokens:
            self.advance(t)


class SlotAllocator:
    """Free-list allocator over the S cache rows (scratch excluded)."""

    def __init__(self, num_slots: int):
        if num_slots < 1:
            raise ServingError(f"num_slots must be >= 1, got {num_slots}")
        self.num_slots = num_slots
        self.scratch = num_slots           # row S of the (S+1, ...) cache
        self._free: List[int] = list(range(num_slots - 1, -1, -1))
        self._active: Dict[int, SlotState] = {}
        # deepest concurrency ever reached (max sustainable concurrency
        # at fixed KV memory: what the paged layout is for)
        self.active_highwater = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def active_count(self) -> int:
        return len(self._active)

    def alloc(self, state: SlotState) -> int:
        """Lease a free row for ``state``; raises if none free (the
        engine admits at most ``free_count`` requests per cycle)."""
        if not self._free:
            raise ServingError("no free KV slots (admission bug: engine "
                               "must admit <= free_count)")
        slot = self._free.pop()
        self._active[slot] = state
        if len(self._active) > self.active_highwater:
            self.active_highwater = len(self._active)
        return slot

    def free(self, slot: int) -> SlotState:
        """End a lease.  The row's stale K/V needs no scrubbing: the next
        prefill overwrites [0, Tb) and decode rewrites each later
        position before ever attending to it."""
        state = self._active.pop(slot)
        self._free.append(slot)
        return state

    def items(self):
        """(slot, state) pairs of active leases, slot-ordered (stable
        iteration while the engine mutates per-slot state)."""
        return sorted(self._active.items())

    def __contains__(self, slot: int) -> bool:
        return slot in self._active
