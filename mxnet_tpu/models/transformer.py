"""Transformer building blocks with first-class tensor/sequence parallelism.

Capability parity: MXNet's transformer support was GluonNLP-side Python over
the fused contrib matmuls (src/operator/contrib/transformer.cc); there was
no TP/SP (SURVEY.md §2.4 row "Parallelism strategies").  Here every layer
carries logical sharding axes (Megatron-style: attention heads and FFN hidden
over ``tp``, sequence over ``sp``) so the same Block runs single-chip or
SPMD over a mesh without code changes.
"""
from __future__ import annotations

import math
from typing import Optional

from ..gluon.block import HybridBlock
from ..gluon.nn import Dense, Dropout, GELU, LayerNorm
from ..ops import dot_product_attention
from ..ops.flash import KEPT_NAMES, named_residuals, plan_event
from ..parallel.sharding import annotate
from .. import parallel as _par

_WARNED_ULYSSES_FALLBACK = False


class MultiHeadAttention(HybridBlock):
    """Self-attention with per-head tensor parallelism.

    q/k/v/out projections are separate Dense layers so the ``tp`` sharding
    of the ``units`` dim splits along head boundaries (Megatron column/row
    parallel); attention math runs through ops.dot_product_attention
    (Pallas flash kernel on TPU for long sequences).
    """

    def __init__(self, units, num_heads, dropout=0.0, attention_dropout=0.0,
                 use_bias=True, causal=False, seq_parallel=None, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise ValueError(f"units {units} not divisible by heads "
                             f"{num_heads}")
        if seq_parallel is None:
            import os
            seq_parallel = os.environ.get("MXNET_TPU_SEQ_PARALLEL", "ring")
        if seq_parallel not in ("ring", "ulysses"):
            raise ValueError(
                f"seq_parallel must be 'ring' or 'ulysses', "
                f"got {seq_parallel!r}")
        self._seq_parallel = seq_parallel
        self._units = units
        self._num_heads = num_heads
        self._head_dim = units // num_heads
        self._causal = causal
        self._att_dropout = attention_dropout
        for name in ("q_proj", "k_proj", "v_proj"):
            d = Dense(units, use_bias=use_bias, flatten=False,
                      in_units=units)
            annotate(d.weight, "heads", "embed")
            if d.bias is not None:
                annotate(d.bias, "heads")
            setattr(self, name, d)
        self.out_proj = Dense(units, use_bias=use_bias, flatten=False,
                              in_units=units)
        annotate(self.out_proj.weight, "embed", "heads")
        if self.out_proj.bias is not None:
            annotate(self.out_proj.bias, "norm")
        self.dropout = Dropout(dropout) if dropout else None

    def forward(self, x, mask=None, memory=None):
        """Self-attention over ``x``; cross-attention when ``memory`` is
        given (queries from ``x``, keys/values from ``memory`` — the
        encoder-decoder attention of Sockeye-style NMT)."""
        b, t = x.shape[0], x.shape[1]
        h, d = self._num_heads, self._head_dim
        kv = x if memory is None else memory
        tk = kv.shape[1]
        q = self.q_proj(x).reshape((b, t, h, d))
        k = self.k_proj(kv).reshape((b, tk, h, d))
        v = self.v_proj(kv).reshape((b, tk, h, d))
        mesh = _par.current_mesh()
        sp = _par.axis_size(mesh, "sp") if mesh is not None else 1
        # shard_map needs every sharded dim to divide its mesh axis —
        # uneven shapes (e.g. a last odd-sized batch) keep the GSPMD path
        divisible = (sp > 1 and isinstance(t, int) and t % sp == 0
                     and b % _par.axis_size(mesh, "dp") == 0
                     and h % _par.axis_size(mesh, "tp") == 0)
        if divisible and mask is None and memory is None \
                and self._att_dropout == 0.0:
            # sequence parallel: either K/V chunks ride the ICI ring, or
            # (Ulysses) two all-to-alls re-shard seq<->heads so each
            # device runs FULL-sequence flash attention on its head group
            if self._seq_parallel == "ulysses":
                if (h // _par.axis_size(mesh, "tp")) % sp == 0:
                    from ..ops import nd_ulysses_attention
                    out = nd_ulysses_attention(q, k, v,
                                               causal=self._causal,
                                               mesh=mesh)
                else:
                    global _WARNED_ULYSSES_FALLBACK
                    if not _WARNED_ULYSSES_FALLBACK:
                        import logging
                        logging.warning(
                            "seq_parallel='ulysses' needs local heads "
                            "(%d/|tp|) divisible by |sp|=%d; falling "
                            "back to ring attention", h, sp)
                        _WARNED_ULYSSES_FALLBACK = True
                    from ..ops import nd_ring_attention
                    out = nd_ring_attention(q, k, v, causal=self._causal,
                                            mesh=mesh)
            else:
                from ..ops import nd_ring_attention
                out = nd_ring_attention(q, k, v, causal=self._causal,
                                        mesh=mesh)
        else:
            out = dot_product_attention(
                q, k, v, causal=self._causal, mask=mask,
                dropout=self._att_dropout)
        out = _par.with_sharding_constraint(out, "batch", "seq", "heads",
                                            None)
        out = self.out_proj(out.reshape((b, t, h * d)))
        if self.dropout is not None:
            out = self.dropout(out)
        return out

    def forward_step(self, x, cache, idx):
        """Incremental decode: x (B,1,U) at position ``idx`` against the
        KV cache {'k','v': (B,Tmax,H,D) jax arrays}.  Returns
        (out (B,1,U), new cache).  Inference only (no dropout)."""
        import jax

        from ..ndarray import NDArray

        b = x.shape[0]
        h, d = self._num_heads, self._head_dim
        q = self.q_proj(x).reshape((b, 1, h, d))
        k_new = self.k_proj(x).reshape((b, 1, h, d))
        v_new = self.v_proj(x).reshape((b, 1, h, d))
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k_new.jax.astype(cache["k"].dtype), (0, idx, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v_new.jax.astype(cache["v"].dtype), (0, idx, 0, 0))
        out = _attention_step(q.jax, kc, vc, idx, 1.0 / (d ** 0.5))
        out = self.out_proj(NDArray(out.reshape(b, 1, h * d)))
        return out, {"k": kc, "v": vc}

    def forward_prefill(self, x, cache):
        """Batched cache fill: full causal attention over the prompt
        (B,T,U) in ONE pass, writing K/V for positions [0, T) into the
        cache.  Inference only."""
        import jax

        from ..ndarray import NDArray
        from ..ops import dot_product_attention

        b, t = x.shape[0], x.shape[1]
        h, d = self._num_heads, self._head_dim
        q = self.q_proj(x).reshape((b, t, h, d))
        k = self.k_proj(x).reshape((b, t, h, d))
        v = self.v_proj(x).reshape((b, t, h, d))
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.jax.astype(cache["k"].dtype), (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.jax.astype(cache["v"].dtype), (0, 0, 0, 0))
        out = dot_product_attention(q, k, v, causal=True)
        out = self.out_proj(out.reshape((b, t, h * d)))
        return out, {"k": kc, "v": vc}

    def forward_step_slots(self, x, cache, pos, page_table=None,
                           paged_kernel=False):
        """Continuous-batching decode: x (S,1,U) where row s is an
        independent request parked in SLOT s of the persistent cache
        {'k','v': (R,Tmax,H,D)}, at its OWN position ``pos`` (S,) int32.
        Writes K/V at [s, pos[s]] and attends row-wise to keys
        <= pos[s].  The cache may carry MORE rows than the decode batch
        (R >= S: the scratch and prefix-pool rows live past the slots) —
        only rows [0, S) are written or attended; an out-of-range
        ``pos`` (the engine parks idle rows at Tmax) makes the write an
        out-of-bounds scatter, which jax DROPS, so idle rows never
        clobber cache state.  Inference only.

        PAGED variant (``page_table`` (S, P) int32 given — docs/
        serving.md "Paged KV"): the cache is {'k','v': (N+1, ps, H, D)}
        pages instead of rows; row s's write routes through its table
        entry ``page_table[s, pos[s]//ps]`` (parked rows and writes
        into unassigned table entries route OUT OF BOUNDS, which jax
        drops — page N is the never-written ZERO page that unassigned
        entries READ).  With ``paged_kernel=True`` attention reads the
        pages IN PLACE through the table (:func:`mxnet_tpu.ops.paged.
        paged_attention`); otherwise the row's pages are gathered back
        into a contiguous (S, P*ps, H, D) view so the masked attention
        below is shared verbatim with the dense layout — identical
        shapes, identical masked values, bit-identical tokens (the
        kernel arm matches token-for-token; its online softmax
        reassociates the reduction, so bits may differ).

        QUANTIZED variant (the cache carries ``k_scale``/``v_scale``
        leaves — docs/serving.md "Quantized KV"): new K/V quantize to
        int8 on the scatter write with per-position-per-head fp32
        scales landing beside them (same routing, so targetless scale
        writes drop identically), and dequantize at attention time —
        fused into the kernel's tile loads, or broadcast-multiplied
        after the gather on the reference arm."""
        import jax.numpy as jnp

        from ..ndarray import NDArray
        from ..ops.paged import kv_quantize, paged_attention

        s = x.shape[0]
        h, d = self._num_heads, self._head_dim
        q = self.q_proj(x).reshape((s, 1, h, d))
        k_new = self.k_proj(x).reshape((s, h, d))
        v_new = self.v_proj(x).reshape((s, h, d))
        if page_table is None:
            rows = jnp.arange(s)
            kc = cache["k"].at[rows, pos].set(
                k_new.jax.astype(cache["k"].dtype))
            vc = cache["v"].at[rows, pos].set(
                v_new.jax.astype(cache["v"].dtype))
            krow, vrow = kc[:s], vc[:s]
        else:
            ps = cache["k"].shape[1]
            tmax = page_table.shape[1] * ps
            zero_page = cache["k"].shape[0] - 1
            lp = jnp.minimum(pos // ps, page_table.shape[1] - 1)
            mapped = page_table[jnp.arange(s), lp]
            # a write with no real target — a parked row (pos >= Tmax)
            # or an unassigned table entry (zero page) — routes OUT OF
            # BOUNDS so jax DROPS it.  Nothing may ever write the zero
            # page: unassigned logical pages of every live slot read
            # it, so one row's NaN landing there would poison every
            # other row through the 0·NaN=NaN value einsum (the dense
            # layout isolates rows; paging must too)
            phys = jnp.where((pos < tmax) & (mapped != zero_page),
                             mapped, zero_page + 1)
            off = pos % ps
            quant = "k_scale" in cache
            if quant:
                kq, ksc = kv_quantize(k_new.jax)
                vq, vsc = kv_quantize(v_new.jax)
                kc = cache["k"].at[phys, off].set(kq)
                vc = cache["v"].at[phys, off].set(vq)
                ks_c = cache["k_scale"].at[phys, off].set(ksc)
                vs_c = cache["v_scale"].at[phys, off].set(vsc)
            else:
                kc = cache["k"].at[phys, off].set(
                    k_new.jax.astype(cache["k"].dtype))
                vc = cache["v"].at[phys, off].set(
                    v_new.jax.astype(cache["v"].dtype))
            newc = {"k": kc, "v": vc}
            if quant:
                newc["k_scale"] = ks_c
                newc["v_scale"] = vs_c
            if paged_kernel:
                out = paged_attention(
                    q.jax, kc, vc, page_table, pos[:, None],
                    k_scale=ks_c if quant else None,
                    v_scale=vs_c if quant else None,
                    scale=1.0 / (d ** 0.5))
                out = self.out_proj(NDArray(out.reshape(s, 1, h * d)))
                return out, newc
            krow = _paged_rows(kc, page_table)
            vrow = _paged_rows(vc, page_table)
            if quant:
                krow = krow.astype(jnp.float32) * \
                    _paged_rows(ks_c, page_table)
                vrow = vrow.astype(jnp.float32) * \
                    _paged_rows(vs_c, page_table)
            out = _attention_step_slots(q.jax, krow, vrow, pos,
                                        1.0 / (d ** 0.5))
            out = self.out_proj(NDArray(out.reshape(s, 1, h * d)))
            return out, newc
        out = _attention_step_slots(q.jax, krow, vrow, pos,
                                    1.0 / (d ** 0.5))
        out = self.out_proj(NDArray(out.reshape(s, 1, h * d)))
        return out, {"k": kc, "v": vc}

    def forward_step_window(self, x, cache, pos, win_k, win_v, i,
                            page_table=None):
        """READ-ONLY draft decode step (docs/serving.md "Speculative
        decode"): like :meth:`forward_step_slots`, but the new K/V land
        in per-layer WINDOW buffers ``win_k``/``win_v`` (S, W, H, D) at
        column ``i`` instead of the shared cache — the cache is never
        written, so a drafter that is aborted (verify fault, rejected
        proposals, NaN-poisoned draft head) leaves NO trace in shared
        state and degrading to a plain decode step is always safe.

        Row s is drafting token ``i`` of its window: it consumes a
        token at absolute position ``pos[s] + i``, where the cache row
        holds valid K/V for positions ``< pos[s]`` (strictly — the
        consumed token's own K/V lives in window column 0) and window
        columns ``0..i`` hold the speculated positions
        ``pos[s]..pos[s]+i``.  Attention runs over the concatenation
        [cache row (keys < pos), window (cols <= i)].  Returns
        ``(out, new win_k, new win_v)``.  Inference only."""
        import jax.numpy as jnp

        from ..ndarray import NDArray

        s = x.shape[0]
        h, d = self._num_heads, self._head_dim
        q = self.q_proj(x).reshape((s, 1, h, d))
        k_new = self.k_proj(x).reshape((s, h, d))
        v_new = self.v_proj(x).reshape((s, h, d))
        wk = win_k.at[:, i].set(k_new.jax.astype(win_k.dtype))
        wv = win_v.at[:, i].set(v_new.jax.astype(win_v.dtype))
        if page_table is None:
            krow, vrow = cache["k"][:s], cache["v"][:s]
        else:
            krow = _paged_rows(cache["k"], page_table)
            vrow = _paged_rows(cache["v"], page_table)
            if "k_scale" in cache:
                # quantized pages: dequantize the gathered view — the
                # draft stays on the gather arm (it is read-only and
                # off the throughput-critical path), but the window
                # buffers themselves are always fp32 (gpt2.draft_slots)
                krow = krow.astype(jnp.float32) * \
                    _paged_rows(cache["k_scale"], page_table)
                vrow = vrow.astype(jnp.float32) * \
                    _paged_rows(cache["v_scale"], page_table)
        out = _attention_step_window(q.jax, krow, vrow, wk, wv, pos, i,
                                     1.0 / (d ** 0.5))
        out = self.out_proj(NDArray(out.reshape(s, 1, h * d)))
        return out, wk, wv

    def forward_prefill_slots(self, x, cache, slot_idx, offset=None,
                              page_table=None, paged_kernel=False):
        """Bucketed admission prefill: x (B,Tb,U) is a batch of PADDED
        prompts; row i's K/V for positions [0, Tb) land in cache row
        ``slot_idx[i]`` of the persistent (R,Tmax,H,D) cache.  Causal
        attention keeps real tokens blind to the right-padding; padded
        positions write garbage K/V beyond each prompt's true length,
        which decode overwrites (position p is rewritten before it is
        ever attended).  Duplicate slot_idx rows (scratch padding) are
        allowed — last-writer-wins is fine for rows nobody reads.

        CHUNKED/OFFSET variant (``offset`` (B,) int32 given): row i's
        tokens are the chunk at absolute positions ``[offset[i],
        offset[i]+Tb)`` of a prompt whose K/V for ``[0, offset[i])`` is
        ALREADY in cache row ``slot_idx[i]`` (earlier chunks, or a
        prefix-cache copy) — so each chunk query at absolute position p
        attends to the row's cached keys ``<= p``, not just the chunk.
        The chunk K/V are written first, then each row's full cache row
        is gathered back for the attention (the data dependency through
        the scatter keeps XLA honest about ordering).  Writes landing at
        positions >= Tmax (padding columns of a final chunk) are
        out-of-bounds scatters, which jax drops.

        PAGED variant (``page_table`` (S+1, P) int32 given): the cache
        is {'k','v': (N+1, ps, H, D)} pages; row i's K/V scatter through
        ITS table row ``page_table[slot_idx[i]]`` — position p lands in
        page ``table[p//ps]`` at in-page offset ``p%ps``; writes with
        no real target (positions past Tmax, columns spilling into an
        unassigned logical page, the scratch slot-row's padding rows)
        route OUT OF BOUNDS and are dropped — page N is the
        never-written ZERO page unassigned entries read.  The offset
        path gathers each row's pages back into a contiguous
        (B, Tmax, H, D) view so :func:`_attention_chunk` is shared
        verbatim with the dense layout — or, with ``paged_kernel=True``,
        attention reads the pages in place through the table.  A cache
        carrying ``k_scale``/``v_scale`` leaves quantizes the scatter
        write to int8 (scales ride the same routing) and dequantizes at
        attention time, exactly as in :meth:`forward_step_slots`."""
        import jax.numpy as jnp

        from ..ndarray import NDArray
        from ..ops import dot_product_attention
        from ..ops.paged import kv_quantize, paged_attention

        b, t = x.shape[0], x.shape[1]
        h, d = self._num_heads, self._head_dim
        q = self.q_proj(x).reshape((b, t, h, d))
        k = self.k_proj(x).reshape((b, t, h, d))
        v = self.v_proj(x).reshape((b, t, h, d))
        cidx = jnp.arange(t)[None, :] if offset is None \
            else offset[:, None] + jnp.arange(t)[None, :]
        quant = page_table is not None and "k_scale" in cache
        # slot_idx=None means "row i IS slot i" (the speculative verify
        # window, whose batch dim spans every slot): the row read below
        # becomes a SLICE instead of a gather — an identity-permutation
        # gather copies the whole (B, Tmax, H, D) cut per layer, which
        # XLA cannot see through and which would dominate a small
        # verify window's cost
        if page_table is None:
            ridx = jnp.arange(b)[:, None] if slot_idx is None \
                else slot_idx[:, None]
            kc = cache["k"].at[ridx, cidx].set(
                k.jax.astype(cache["k"].dtype))
            vc = cache["v"].at[ridx, cidx].set(
                v.jax.astype(cache["v"].dtype))
        else:
            ps = cache["k"].shape[1]
            tmax = page_table.shape[1] * ps
            zero_page = cache["k"].shape[0] - 1
            trows = page_table[:b] if slot_idx is None \
                else page_table[slot_idx]                    # (B, P)
            lp = jnp.minimum(cidx // ps, page_table.shape[1] - 1)
            mapped = jnp.take_along_axis(trows, lp, axis=1)  # (B, Tb)
            # padding columns past Tmax, columns spilling into a
            # logical page the row never claimed (mixed-offset chunk
            # batches pad every row to the LONGEST take), and the
            # scratch slot-row's padding rows all route OUT OF BOUNDS
            # (dropped) — the zero page must never be written, every
            # live slot reads it through its unassigned table entries
            phys = jnp.where((cidx < tmax) & (mapped != zero_page),
                             mapped, zero_page + 1)
            off = cidx % ps
            if quant:
                kq, ksc = kv_quantize(k.jax)
                vq, vsc = kv_quantize(v.jax)
                kc = cache["k"].at[phys, off].set(kq)
                vc = cache["v"].at[phys, off].set(vq)
                ks_c = cache["k_scale"].at[phys, off].set(ksc)
                vs_c = cache["v_scale"].at[phys, off].set(vsc)
            else:
                kc = cache["k"].at[phys, off].set(
                    k.jax.astype(cache["k"].dtype))
                vc = cache["v"].at[phys, off].set(
                    v.jax.astype(cache["v"].dtype))
        if offset is None:
            # full-prompt prefill attends the chunk's OWN fresh fp32
            # K/V (no cache read) — shared by every layout and dtype
            out = dot_product_attention(q, k, v, causal=True)
        elif page_table is None:
            if slot_idx is None:
                krow, vrow = kc[:b], vc[:b]      # slice, not gather
            else:
                krow = kc[slot_idx]          # (B, Tmax, H, D)
                vrow = vc[slot_idx]
            out = NDArray(_attention_chunk(q.jax, krow, vrow, cidx,
                                           1.0 / (d ** 0.5)))
        else:
            trows = page_table[:b] if slot_idx is None \
                else page_table[slot_idx]
            if paged_kernel:
                out = NDArray(paged_attention(
                    q.jax, kc, vc, trows, cidx,
                    k_scale=ks_c if quant else None,
                    v_scale=vs_c if quant else None,
                    scale=1.0 / (d ** 0.5)))
            else:
                krow = _paged_rows(kc, trows)
                vrow = _paged_rows(vc, trows)
                if quant:
                    krow = krow.astype(jnp.float32) * \
                        _paged_rows(ks_c, trows)
                    vrow = vrow.astype(jnp.float32) * \
                        _paged_rows(vs_c, trows)
                out = NDArray(_attention_chunk(q.jax, krow, vrow, cidx,
                                               1.0 / (d ** 0.5)))
        out = self.out_proj(out.reshape((b, t, h * d)))
        newc = {"k": kc, "v": vc}
        if quant:
            newc["k_scale"] = ks_c
            newc["v_scale"] = vs_c
        return out, newc


def _attention_step(q, k_cache, v_cache, idx, scale):
    """Single-position attention against a KV cache: q (B,1,H,D),
    caches (B,Tmax,H,D), idx = current position (traced int32).  Masked
    to positions <= idx; returns (B,1,H,D)."""
    import jax.numpy as jnp

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(k_cache.shape[1])
    logits = jnp.where(pos[None, None, None, :] <= idx, logits, -1e30)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v_cache.dtype),
                      v_cache)


def _paged_rows(pages, table_rows):
    """Gather per-slot pages back into contiguous rows: ``pages``
    (N+1, ps, H, D) physical KV pages, ``table_rows`` (B, P) int32 page
    tables → (B, P*ps, H, D), i.e. exactly the dense (B, Tmax, H, D)
    row view, so the masked attentions are shared verbatim between the
    two layouts (token parity by construction: every attended position
    holds identical values, every masked position is selected out
    BEFORE the softmax).  Unassigned logical pages point at the ZERO
    page — pristine zeros, NEVER written (targetless writes route out
    of bounds and drop): that matters because a masked-out lane is
    only harmless if its VALUE is finite — probs underflow to exactly
    0.0 but 0·NaN = NaN in the value einsum, so scratch-page NaN from
    one poisoned row would otherwise fail every live request at once
    (the dense layout isolates rows; paging must too).  The gather
    materializes a (B, Tmax) working set transiently — the HBM win of
    paging is in the PERSISTENT allocation (live tokens, not
    Tmax*slots); :func:`mxnet_tpu.ops.paged.paged_attention` skips the
    materialization entirely (the default ``paged_attention='kernel'``
    arm), keeping this gather as the opt-out reference arm."""
    b, p = table_rows.shape
    g = pages[table_rows]                    # (B, P, ps, H, D)
    return g.reshape(b, p * g.shape[2], g.shape[3], g.shape[4])


def _attention_chunk(q, k_rows, v_rows, qpos, scale):
    """Chunked-prefill attention against populated cache rows: q
    (B,Tq,H,D) are chunk queries at ABSOLUTE positions ``qpos`` (B,Tq);
    k_rows/v_rows (B,Tmax,H,D) are each request's full (gathered) cache
    row, already containing this chunk's K/V plus everything before it.
    Query (b, i) attends keys at positions <= qpos[b, i] — causal over
    the whole prompt, not just the chunk.  This is the decode-step mask
    generalized to Tq queries; O(Tq·Tmax) scores per row, the price of
    offset prefill without a custom kernel (a flash variant with a
    kv-length stop is the TPU follow-up)."""
    import jax.numpy as jnp

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_rows,
                        preferred_element_type=jnp.float32) * scale
    keys = jnp.arange(k_rows.shape[1])
    keep = keys[None, None, None, :] <= qpos[:, None, :, None]
    logits = jnp.where(keep, logits, -1e30)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v_rows.dtype),
                      v_rows)


def _attention_step_window(q, k_cache, v_cache, k_win, v_win, pos, i,
                           scale):
    """Draft-step attention over [cache row, speculation window]: row s
    attends cache keys at positions ``< pos[s]`` (strictly — unlike
    :func:`_attention_step_slots`'s ``<= pos``, because the draft never
    writes the cache: the consumed token's K/V sits in window column 0)
    plus window columns ``<= i`` (absolute positions
    ``pos[s]..pos[s]+i``).  Same masked-select-before-softmax math as
    every other attention here, so masked lanes only need FINITE
    values, which both sources guarantee."""
    import jax.numpy as jnp

    lc = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache,
                    preferred_element_type=jnp.float32) * scale
    keys = jnp.arange(k_cache.shape[1])
    lc = jnp.where(keys[None, None, None, :] < pos[:, None, None, None],
                   lc, -1e30)
    lw = jnp.einsum("bqhd,bkhd->bhqk", q, k_win,
                    preferred_element_type=jnp.float32) * scale
    cols = jnp.arange(k_win.shape[1])
    lw = jnp.where(cols[None, None, None, :] <= i, lw, -1e30)
    logits = jnp.concatenate([lc, lw], axis=-1)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    vals = jnp.concatenate([v_cache, v_win], axis=1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vals.dtype), vals)


def _attention_step_slots(q, k_cache, v_cache, pos, scale):
    """Per-row-position variant of :func:`_attention_step` for continuous
    batching: row s attends keys <= pos[s] (pos (S,) int32).  Attention
    reads only its own cache row, so slots never contaminate each other."""
    import jax.numpy as jnp

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache,
                        preferred_element_type=jnp.float32) * scale
    keys = jnp.arange(k_cache.shape[1])
    keep = keys[None, None, None, :] <= pos[:, None, None, None]
    logits = jnp.where(keep, logits, -1e30)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v_cache.dtype),
                      v_cache)


class PositionwiseFFN(HybridBlock):
    """Transformer FFN: Dense(hidden) → GELU → Dense(units), hidden sharded
    over ``tp`` (Megatron column then row parallel)."""

    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu",
                 use_bias=True, **kwargs):
        super().__init__(**kwargs)
        self.fc1 = Dense(hidden_size, use_bias=use_bias, flatten=False,
                         in_units=units)
        annotate(self.fc1.weight, "mlp", "embed")
        if self.fc1.bias is not None:
            annotate(self.fc1.bias, "mlp")
        self.act = GELU() if activation == "gelu" else None
        self._activation = activation
        self.fc2 = Dense(units, use_bias=use_bias, flatten=False,
                         in_units=hidden_size)
        annotate(self.fc2.weight, "embed", "mlp")
        self.dropout = Dropout(dropout) if dropout else None

    def forward(self, x):
        from ..ndarray import ops as F
        h = self.fc1(x)
        h = self.act(h) if self.act is not None else \
            F.Activation(h, act_type=self._activation)
        h = self.fc2(h)
        if self.dropout is not None:
            h = self.dropout(h)
        return h


def _block_param_items(block):
    """(structural_name, Parameter) pairs in REGISTRATION order — the
    alignment key for stacking layers.  Structural names ('attn.q_proj.weight')
    are identical across identically-constructed blocks, unlike the global
    per-class name counters ('dense10_weight' sorts before 'dense6_weight')."""
    return list(block._collect_params_with_prefix().items())


def _block_config_key(b):
    """Hyperparameters that change the layer FUNCTION without changing its
    param tree — blocks must agree on all of them to share one scan body."""
    return (
        b.attn._num_heads, b.attn._head_dim, b.attn._causal,
        b.attn._att_dropout,
        b.attn.dropout._rate if b.attn.dropout is not None else 0.0,
        b.ffn._activation,
        b.ffn.dropout._rate if b.ffn.dropout is not None else 0.0,
        b.ln1._axis, b.ln1._eps, b.ln2._axis, b.ln2._eps,
    )


def _scan_eligible(blocks, x) -> bool:
    """True iff the stack can run as ONE lax.scan body: homogeneous layer
    class AND config, params allocated, identical structural param trees
    (names, shapes, dtypes), and we are inside a jit trace (eager mode
    keeps the python loop so the imperative autograd tape sees every op)."""
    import jax

    from ..ndarray import NDArray

    if len(blocks) < 2:
        return False
    cls = type(blocks[0])
    if cls not in (TransformerBlock, TransformerEncoderLayer):
        return False
    if any(type(b) is not cls for b in blocks):
        return False
    try:
        if any(_block_config_key(b) != _block_config_key(blocks[0])
               for b in blocks):
            return False
    except AttributeError:   # subclass with a different structure
        return False
    if not isinstance(x, NDArray) or not isinstance(x.jax, jax.core.Tracer):
        return False
    trees = []
    for b in blocks:
        ps = _block_param_items(b)
        if any(p._data is None for _, p in ps):
            return False
        trees.append(tuple((n, tuple(p.shape), str(p._data.jax.dtype))
                           for n, p in ps))
    return all(t == trees[0] for t in trees)


def _scan_blocks(blocks, x, mask, remat):
    """Run identical transformer layers as ``lax.scan`` over stacked params.

    TPU-first compile economics (SURVEY.md §7.3 hard part 3): a 24-layer
    stack unrolled is 24 copies of the same HLO — XLA compiles the scan
    body ONCE instead.  Gradients flow through the jnp.stack to each
    layer's own Parameter, so checkpoint format / Trainer integration are
    unchanged.  Per-layer RNG (dropout) folds the layer index into the
    ambient trace key so layers decorrelate exactly like the python loop.
    """
    import jax
    import jax.numpy as jnp

    from .. import random as _random
    from ..ndarray import NDArray

    global _scan_engaged_count
    _scan_engaged_count += 1
    b0 = blocks[0]
    b0_params = [p._data for _, p in _block_param_items(b0)]
    per_block = [[p._data.jax for _, p in _block_param_items(blk)]
                 for blk in blocks]
    stacked = [jnp.stack([vals[j] for vals in per_block])
               for j in range(len(b0_params))]
    providers = _random._trace_providers()
    base_key = providers[-1].key if providers else None

    from ..ndarray.ndarray import swap_values

    def body(carry, xs):
        idx, layer_vals = xs[0], xs[1:]
        if base_key is not None:
            _random.push_trace_key(jax.random.fold_in(base_key, idx))
        try:
            with swap_values(b0_params, list(layer_vals)):
                out = b0(NDArray(carry), mask)
            return out.jax, None
        finally:
            if base_key is not None:
                _random.pop_trace_key()

    if remat:
        # remat="dots" keeps matmul outputs resident (cheap: O(layers *
        # tokens * units)) and recomputes only elementwise/softmax in the
        # backward — near-zero extra MXU FLOPs, while full remat (True)
        # recomputes the whole layer.  Without remat a deep scanned stack
        # saves every intermediate per layer and OOMs HBM (BERT-large
        # batch 8 seq 512 wants >16GB of scan-saved activations).
        body = jax.checkpoint(body, policy=_remat_policy(remat))
    idxs = jnp.arange(len(blocks), dtype=jnp.int32)
    with named_residuals() as kept:
        h, _ = jax.lax.scan(body, x.jax, (idxs, *stacked))
    if remat:
        for i in range(len(blocks)):    # one body: every layer keeps alike
            _report_kept(i, kept)
    return NDArray(h)


def _remat_policy(remat):
    """What a recomputed block keeps beside its inputs (``remat`` is True
    or "dots"; see :func:`run_blocks`): the residuals ``ops.flash`` names,
    and under "dots" the matrix products' outputs as well."""
    import jax

    keep = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)
    if remat == "dots":
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.checkpoint_dots, keep)
    return keep


def _report_kept(layer, kept):
    """Event ``remat.plan`` for one checkpointed block: the named
    residuals its differentiation met (``ops.flash.named_residuals``) and
    their bytes; ``kept_bytes`` 0 for a block with no flash call, and for
    a trace that is not differentiated, which keeps nothing."""
    plan_event("remat.plan", layer=layer,
               kept=tuple(sorted({name for name, _ in kept})),
               kept_bytes=sum(size for _, size in kept))


# diagnostic: how many times the scan fast path actually compiled in
# (tests assert it engages — a silently ineligible stack would otherwise
# make loop-vs-scan comparisons vacuous)
_scan_engaged_count = 0


def run_blocks(blocks, x, mask=None, scan=None, remat=False):
    """Apply a stack of transformer layers: ``lax.scan`` fast path for deep
    homogeneous stacks under jit (one compiled body), python loop otherwise.

    ``scan=None`` auto-enables scanning at >=8 layers; pass True/False to
    force.  ``remat`` wraps the scan body, or on the loop path each layer,
    in jax.checkpoint (activation rematerialization for long sequences /
    deep stacks); ``remat="dots"`` also saves matmul outputs and recomputes
    only elementwise — the usual best memory/FLOP point on TPU.

    ``remat=True`` means: recompute the block but for what only a kernel
    can remake and costs under a few MB a ms, which is the flash kernels'
    output and logsumexp (``ops.flash.KEPT_NAMES``; :func:`_remat_policy`).
    At 8,192 tokens that is 35-85 MB a layer for the 2.2-6.1 ms a second
    run of the forward kernel takes (12.0 of 294 ms a step with four
    attention layers, 14.5 of 503 with three, 3.8-4.9 of 363-680 with
    one: PERF.md section 6, PR 43); a scan's or a delta rule's states
    cost ten times the bytes a ms and are recomputed.  There is no
    argument for it: the rule depends only on whether a block holds a
    flash call.  With a tracer on, each checkpointed block says what it
    kept in one ``remat.plan`` event (``layer``, ``kept``, ``kept_bytes``).

    A layer may hand later layers more than the stream (one layer's keys
    and values for a whole cross-decoder, a scan's output as a memory):
    a block with ``side_out`` (names) returns ``(stream, *those)``, and a
    block with ``side_in`` is called with the named values after the
    mask.  They travel the loop paths beside the stream, differentiable:
    under ``remat`` they leave the emitting layer's checkpoint as outputs
    and enter each reader's as arguments, so their cotangents flow back
    and no recomputed layer's internals are kept for them.
    """
    use_scan = scan if scan is not None else len(blocks) >= 8
    if use_scan and _scan_eligible(blocks, x):
        return _scan_blocks(blocks, x, mask, remat)
    if remat:
        import jax

        from ..ndarray import NDArray

        if isinstance(x, NDArray) and isinstance(x.jax, jax.core.Tracer):
            # honor remat on the loop path too (short/heterogeneous
            # stacks): checkpoint each layer, folding the layer index
            # into the trace key so fwd and rematerialized traces draw
            # IDENTICAL dropout masks (scan-body key semantics)
            from .. import random as _random
            providers = _random._trace_providers()
            base_key = providers[-1].key if providers else None

            side = {}
            policy = _remat_policy(remat)
            for i, blk in enumerate(blocks):
                reads = tuple(getattr(blk, "side_in", ()))
                emits = tuple(getattr(blk, "side_out", ()))
                # payloads the layer rebinds in its forward (a buffer
                # that is not trained: running statistics, routing
                # counters) leave the checkpointed function as outputs
                # and are rebound outside it, so no tracer of the inner
                # trace outlives it
                aux = [p._data for p in blk.collect_params().values()
                       if p.grad_req == "null" and p._data is not None]

                moved = []      # which of them this layer rebound

                def f(h, *given, _blk=blk, _i=i, _aux=aux, _moved=moved,
                      _emits=emits):
                    if base_key is not None:
                        _random.push_trace_key(
                            jax.random.fold_in(base_key, _i))
                    saved = [(a._data, a._node) for a in _aux]
                    try:
                        out = _blk(NDArray(h), mask,
                                   *(NDArray(v) for v in given))
                        out, *emitted = out if _emits else (out,)
                        _moved[:] = [j for j, a in enumerate(_aux)
                                     if a._data is not saved[j][0]]
                        return (out.jax, tuple(e.jax for e in emitted),
                                tuple(_aux[j]._data for j in _moved))
                    finally:
                        for a, (d, n) in zip(_aux, saved):
                            a._data, a._node = d, n
                        if base_key is not None:
                            _random.pop_trace_key()
                with named_residuals() as kept:
                    out, emitted, new_aux = jax.checkpoint(f, policy=policy)(
                        x.jax, *(side[name].jax for name in reads))
                _report_kept(i, kept)
                for j, v in zip(moved, new_aux):
                    aux[j]._rebind(v)
                side.update(zip(emits, map(NDArray, emitted)))
                x = NDArray(out)
            return x
    side = {}
    for blk in blocks:
        x = blk(x, mask, *(side[name] for name in getattr(blk, "side_in", ())))
        if getattr(blk, "side_out", ()):
            x, *emitted = x
            side.update(zip(blk.side_out, emitted))
    return x


class TransformerBlock(HybridBlock):
    """Pre-LN transformer layer (GPT-2 style)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 attention_dropout=0.0, causal=True, layer_norm_eps=1e-5,
                 **kwargs):
        super().__init__(**kwargs)
        self.ln1 = LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.attn = MultiHeadAttention(
            units, num_heads, dropout=dropout,
            attention_dropout=attention_dropout, causal=causal)
        self.ln2 = LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout=dropout)

    def forward(self, x, mask=None):
        x = x + self.attn(self.ln1(x), mask)
        x = _par.with_sharding_constraint(x, "batch", "seq", None)
        x = x + self.ffn(self.ln2(x))
        return _par.with_sharding_constraint(x, "batch", "seq", None)

    def forward_step(self, x, cache, idx):
        """Incremental decode through the block (see
        MultiHeadAttention.forward_step)."""
        a, cache = self.attn.forward_step(self.ln1(x), cache, idx)
        x = x + a
        x = x + self.ffn(self.ln2(x))
        return x, cache

    def forward_prefill(self, x, cache):
        """Batched cache fill through the block (see
        MultiHeadAttention.forward_prefill)."""
        a, cache = self.attn.forward_prefill(self.ln1(x), cache)
        x = x + a
        x = x + self.ffn(self.ln2(x))
        return x, cache

    def forward_step_slots(self, x, cache, pos, page_table=None,
                           paged_kernel=False):
        """Continuous-batching decode through the block (see
        MultiHeadAttention.forward_step_slots; ``page_table`` selects
        the paged-KV layout, ``paged_kernel`` the in-place Pallas read
        arm)."""
        a, cache = self.attn.forward_step_slots(self.ln1(x), cache, pos,
                                                page_table, paged_kernel)
        x = x + a
        x = x + self.ffn(self.ln2(x))
        return x, cache

    def forward_prefill_slots(self, x, cache, slot_idx, offset=None,
                              page_table=None, paged_kernel=False):
        """Bucketed admission prefill through the block (see
        MultiHeadAttention.forward_prefill_slots; ``offset`` selects the
        chunked/offset variant, ``page_table`` the paged-KV layout,
        ``paged_kernel`` the in-place Pallas read arm)."""
        a, cache = self.attn.forward_prefill_slots(self.ln1(x), cache,
                                                   slot_idx, offset,
                                                   page_table,
                                                   paged_kernel)
        x = x + a
        x = x + self.ffn(self.ln2(x))
        return x, cache

    def forward_step_window(self, x, cache, pos, win_k, win_v, i,
                            page_table=None):
        """Read-only draft decode through the block (see
        MultiHeadAttention.forward_step_window; the cache is never
        written — new K/V ride the window buffers)."""
        a, wk, wv = self.attn.forward_step_window(self.ln1(x), cache,
                                                  pos, win_k, win_v, i,
                                                  page_table)
        x = x + a
        x = x + self.ffn(self.ln2(x))
        return x, wk, wv


class TransformerEncoderLayer(TransformerBlock):
    """Bidirectional (BERT-style) layer: post-LN off, no causal mask."""

    def __init__(self, units, hidden_size, num_heads, **kwargs):
        super().__init__(units, hidden_size, num_heads, causal=False,
                         **kwargs)
