"""Pallas TPU flash attention: O(T) memory, MXU-tiled, fwd + custom bwd.

Capability add over the reference (SURVEY.md §5.7: MXNet ships NO
flash/ring attention; its fused BERT matmuls in
src/operator/contrib/transformer.cc materialize the full (T, T) score
matrix).  This kernel never materializes scores: the softmax is computed
online per tile held in VMEM, accumulating into an f32 VMEM scratch, so
long sequences are bounded by HBM for Q/K/V only.

Layout: public entry takes (B, T, H, D) and flattens to (B*H, T, D).
The grid is (groups of heads, blocks, major stretches).  One grid step
owns one ``block_q``-row block of q (fwd) or of k/v (bwd) and is handed
the operand it walks — K and V forward; Q, dO, lse and delta backward —
as one *major* stretch of the sequence: all of it whenever
that fits VMEM (at T = 1,024, D = 64, bf16 one head's K is 128 KB), so
the third grid axis has one step and Pallas fetches the stretch once per
head, not once per block.  Only a sequence too long for that walks the
third ("arbitrary") axis, the accumulators carried in scratch across it.

Inside the step the walk over that operand is code, not grid
(:func:`_walk`, :func:`_schedule`).  Under ``causal`` the block's own
square on the diagonal is cut into slabs, each run straight-line on the
trapezoid of rows that can see it, and only a slab's diagonal tile builds
the position mask (iota, compare, select); what the block sees whole is a
``fori_loop`` over chunks whose trip count is the causal bound and whose
body holds no mask code at all; what the mask hides is never visited.
So the upper triangle is skipped at slab granularity at EVERY sequence
length — at T = 1,024 a head runs 3 of 4 512-wide tiles forward and 36
of 64 128-wide tiles backward — where a grid of one 1,024 x 1,024 tile a
head skipped nothing.  With segment ids every slice masks by segment,
and a slice whose id range cannot meet the block's is skipped.

A sliding ``window`` gives the band a block sees a second edge,
``window - 1`` behind the diagonal, and that edge is the diagonal
mirrored (:func:`_band`): the columns older than the block's own square
are cut into the same slabs, each run on the rows that still see any of
it, and only the rows that see a slab in part compare ``q - k <
window``; between the two edges (a window wider than the block) runs the
same plain loop; what is older than the window is never visited.  At
T = 8,192 under a 1,024-key window a head runs 45 of 256 forward tiles
(its mask lets 30 tiles' worth of pairs through) and 540 of 4,096
backward, where walking the band's bounding box in masked chunks of
every row ran 60 and 960.

The backward pass is ONE kernel, ``flash_bwd`` (:func:`_bwd_kernel`): a
grid step owns a block of k/v rows and walks Q and dO, makes each visited
tile's probabilities again from the saved per-row logsumexp, ONCE, and
takes all three gradients from it: five products a tile (``S``, ``dP``,
``dV``, ``dK``, ``dQ``), one exp, one mask.  It holds its tiles
transposed, (kv rows, q columns), so that dV = P^T dO and dK = dS^T Q are
plain row-by-column products and the per-row lse/delta broadcast along
sublanes as they are stored; dQ = dS K is the one product that contracts
the tile's rows.  ``dk`` and ``dv`` are the block's own, in block-sized
scratch; ``dq`` belongs to the rows walked, and is summed in float32 VMEM
over the heads' WHOLE query sequence (4 MB a head at T = 8,192, D = 128)
across the block axis of the grid and written once, after the head's last
k/v block: no partial ``dq`` goes through HBM.  Only where a sequence's
``dq`` cannot be held (:data:`_VMEM_FUSED`) does the plan keep the
flash-attention-2 split: a ``dq`` kernel (q blocks, walking kv) beside
the same body without its ``dq``, each making the tiles for itself —
seven products, two exps.

Sizes (``block_q``, ``chunk``, slab widths, ``major``, heads a step) are
computed in ONE place, :func:`tile_plan`, from what the call can see:
sequence lengths, head dim, dtype, masks, head count.

Of the five residuals the backward reads (q, k, v, out, lse), ``out`` and
``lse`` are the two only the forward KERNEL can remake, and the smallest
arrays of an attention layer.  The differentiation rule's forward
(:func:`_flash_fwd`) therefore passes them through
``jax.ad_checkpoint.checkpoint_name`` as :data:`KEPT_NAMES`, so that a
``jax.checkpoint`` whose policy saves those names
(``models.transformer.run_blocks`` under ``remat``) recomputes a layer's
projections but not its attention.  Outside such a checkpoint a name is
the identity and nothing in plain use shows it: the names are not
decoration, and a step without them runs every forward kernel twice.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured on TPU v5e, B8 T1024 H12 D64 bf16 causal, fwd / dq / dkv in ms a
# call (PERF.md §6, PR 25): the one-tile-a-head grid this replaces 0.500 /
# 0.439 / 0.616.  At D = 64 its dq and dkv were already bound by the MXU
# (a 64-deep contraction or a 64-wide result fills half the array), so
# the time is in the tiles run, and what a finer cut gains it must not
# lose to work that runs beside no matmul.  Blocks of 256 rows on the
# grid with a loop of 256-wide chunks: 0.369 / 0.330 / 0.479 with four
# heads a step, 0.567 / 0.490 / 0.726 with one (a loop body of one small
# tile leaves the matmul → exp → matmul chain's latencies bare).  One
# 1,024-row block a head, its square cut into trapezoid slabs, all
# straight-line: slabs of 512 / 256 / 128 give fwd 0.264 / 0.294 / 0.348
# (each slab updates the per-row softmax state), dq 0.335 / 0.294 /
# 0.280, dkv 0.459 / 0.412 / 0.356 (no such state; finer skips more).
# Off the diagonal there is nothing to skip and the loop's chunk is
# independent of the slabs: at T = 4,096 chunks of 256 run the three in
# 6.4 ms, chunks of 512 in 7.0 (the parent: 8.5).  Packed documents of
# 128-512 tokens want block and chunk both at 256 (1.16 ms; 1.35 at block
# 512, 1.65 at 1,024).
# Under a window (PERF.md §6, PR 44: T 8,192 bf16, one call of fwd / dq /
# dkv in ms, dq with its delta and dkv with the sum over a share of query
# heads).  32 heads of 128 over 4, window 1,024, blocks of 1,024: the
# band's bounding box in masked 256-chunks of every row 2.46 / 3.09 /
# 4.31; by the two edges with slabs of 512 fwd and 128 bwd 1.52 / 1.95 /
# 2.69; fwd slabs of 256 / 128 give 1.46 / 1.51, bwd slabs of 256 2.02 /
# 2.85.  40 heads of 64 over 20, values 10 heads of 128, window 512,
# blocks of 512: 2.48 / 3.29 / 4.36 before; 1.81 / 2.71 / 3.44 with 512
# and 128; fwd slabs of 256 / 128 give 2.05 / 2.00 (fewer tiles, and
# slower: at D = 64 the forward's time is in the per-row state each slab
# updates), bwd slabs of 256 2.73 / 3.59.  So a window keeps the widths
# the diagonal alone chose: no reading moved by more than 4% for them.
DEFAULT_BLOCK_Q = 1024
DEFAULT_CHUNK = 256
DEFAULT_SLAB = 512
DEFAULT_SLAB_BWD = 128
DEFAULT_SEG = 256
DEFAULT_GROUP = 4
_MASK = -1e30
_LANES = 128
# which comparisons a visit's masked rows make, (diagonal, trailing edge):
# without a window only ever the first
_DIAGONAL = (True, False)

# ~16 MiB of VMEM is what Mosaic gives a call unasked on v4/v5e; the
# budget leaves headroom for compiler temporaries/semaphores so the clamp
# errs safe rather than tight.
_VMEM_BUDGET = 12 * 2 ** 20
# The fused backward holds a head's whole float32 dq beside a step's
# working set and asks for the scoped limit it needs (a v5e core has 128
# MiB; ``ops/sscan.py`` asks for 48): up to this much, past which the
# plan keeps the two calls.  T = 32,768 at D = 128 still fits.
_VMEM_FUSED = 48 * 2 ** 20


class TilePlan(NamedTuple):
    """Sizes of one flash call and how much of the score square they run.

    ``block_q``: rows of the block a grid step owns (q rows in fwd/dq, kv
    rows in the backward call).  ``chunk``: rows of one slice of the walked operand in
    the loop over what the block sees whole.  ``slab`` / ``slab_bwd``:
    width of the slices the diagonal's own square is cut into, in fwd and
    backward.  ``major`` / ``major_q``: the stretch of kv (fwd, dq) / of
    q (the backward call) a grid step holds in VMEM; the fused backward's
    is cut by the limit it asks for and not by the budget.  ``group``:
    heads of one batch row a grid step works on side by side.  ``tiles_*`` count (slab, slab)
    tiles of one head's forward: in the whole square, visited, and
    visited with a mask; ``tiles_*_bwd`` the same in (slab_bwd, slab_bwd)
    tiles for the backward.  ``backward``: "fused", one call that takes
    dq, dk and dv from each score tile, its ``dq`` summed in ``dq_bytes``
    of float32 VMEM over the group's whole query sequence, or "split",
    a dq call and a dkv call, where that does not fit (``dq_bytes`` 0).
    ``bwd_vmem``: the scoped VMEM the fused call asks Mosaic for, 0
    where the default will do."""
    block_q: int
    chunk: int
    slab: int
    slab_bwd: int
    major: int
    major_q: int
    group: int
    tiles_run: int
    tiles_full: int
    tiles_masked: int
    tiles_run_bwd: int
    tiles_full_bwd: int
    backward: str
    dq_bytes: int
    bwd_vmem: int


def _vmem_bytes(block: int, chunk: int, major: int, d: int, itemsize: int,
                has_seg: bool = False, group: int = 1, d2: int = 0) -> int:
    """Working-set model of one grid step, sized for the WORST of the
    three kernels, per head of the step's ``group``: two live
    (block, chunk) f32 score-tile temporaries (s→p and dp→ds are reused
    in place) and one more for a mask; the walked operand resident and
    double-buffered (dkv walks the most: q, do and the f32 lse/delta
    rows, which pad to 8 sublanes); the block's own double-buffered input
    and output tiles (dq reads q, do; dkv writes dk, dv); and the largest
    f32 scratch set (fwd and dq: an accumulator and two lane-replicated
    row tiles).  The minor dim pads to 128 lanes.  Segments add their
    resident int32 row.  A second pair of score operands ``d2`` wide adds
    one more walked operand, one more own tile, one more output tile (in
    float32: dkv's, of a shared head) and its accumulator."""
    dl = max(d, _LANES)
    score = 3 * 4 * block * chunk
    resident = 2 * (2 * major * dl * itemsize + 2 * 8 * major * 4)
    tiles = 2 * (2 * block * dl * itemsize + 2 * 8 * block * 4)
    outs = 2 * 2 * block * dl * itemsize
    scratch = 4 * max(block * dl + 2 * block * _LANES,   # fwd, dq
                      2 * block * dl)                    # dkv: dk + dv
    seg = 2 * 8 * 4 * (major + block) if has_seg else 0
    second = 0
    if d2:
        d2l = max(d2, _LANES)
        second = (2 * (major + block) * d2l * itemsize
                  + (2 + 1) * 4 * block * d2l)
    return group * (score + resident + tiles + outs + scratch + second) + seg


def _dq_vmem(group: int, tq: int, d: int, d2: int, itemsize: int):
    """What the fused backward holds of a group's WHOLE query sequence,
    in bytes: (the float32 accumulators of dq and, with a second pair of
    score operands ``d2`` wide, of dq2; the block they are written out
    through, which Pallas buffers twice).  The minor dim pads to 128
    lanes."""
    lanes = max(d, _LANES) + (max(d2, _LANES) if d2 else 0)
    return 4 * group * tq * lanes, 2 * group * tq * lanes * itemsize


def _largest_stretch(t: int, chunk: int, fits) -> int:
    """The longest stretch of a ``t``-row operand that divides it, is a
    whole number of chunks, and ``fits``; one chunk at the least."""
    for n in range(1, t // chunk + 1):
        if t % n == 0 and (t // n) % chunk == 0 and fits(t // n):
            return t // n
    return chunk


def _clamp_blocks(blocks, chunk: int, tq: int, tk: int, d: int,
                  itemsize: int, has_seg: bool = False, group: int = 1,
                  wide: Optional[int] = None, d2: int = 0):
    """(block, major, major_q, group) that fit the VMEM budget, from the
    candidate ``blocks`` (largest first).  What is kept longest is what
    the chip showed to matter most: the largest block (fewest grid steps,
    the longest regions to schedule, the biggest tiles in the loop) that
    leaves room for a stretch of the walked operand at least two blocks
    long, or the whole sequence if that is shorter; then the longest
    stretch; then heads side by side — head-dim and dtype aware, so d=64
    bf16 at T=1024 keeps a 1024-row block with the head resident while
    d=256 f32 at T=8192 lands on one head, a 512-row block and a
    1024-row stretch.  ``wide`` is the widest slice a step holds scores
    of, where that is not the chunk."""
    def fits(block, major, group=1):
        return _vmem_bytes(block, wide or chunk, major, d, itemsize,
                           has_seg, group, d2) <= _VMEM_BUDGET

    longest = max(tq, tk)
    block = next((b for b in blocks if fits(b, min(longest, 2 * b))),
                 blocks[-1])
    major = _largest_stretch(tk, chunk, lambda m: fits(block, m))
    major_q = _largest_stretch(tq, chunk, lambda m: fits(block, m))
    while group > 1 and not fits(block, max(major, major_q), group):
        group //= 2
    return block, major, major_q, group


def _schedule(i, block: int, chunk: int, slab: int, total: int,
              causal: bool, up: bool):
    """What block ``i`` visits of a walked operand of ``total`` chunks,
    all of it in one stretch: ``(diagonal, plain)``.  ``i`` may be
    traced; every size and row range is static.

    ``plain`` is the (lo, hi) range of chunks that every row of the block
    sees whole.  ``diagonal`` cuts the block's own square — the columns
    of its own positions — into slabs ``(first column, width, rows,
    masked rows)``: ``rows`` the (lo, hi) rows of the block that see any
    of the slab, a trapezoid that leaves out the rows wholly above the
    diagonal, and ``masked rows`` the rows among them that see it in part
    and need the position mask.  ``up`` says the block is of q rows
    walking kv at or before it (fwd, dq) rather than of kv rows walking q
    at or after it (dkv).  Without ``causal`` there is no diagonal: chunk
    0 leads, unmasked, so that something is assigned before the loop
    adds.  Under a window the band has a second edge and the schedule is
    :func:`_band`'s."""
    whole = (0, block)
    if not causal:
        return [(0, chunk, whole, None)], (1, total)
    per = block // chunk
    diagonal = [(i * block + lo, slab, (lo, block) if up else (0, lo + slab),
                 (lo, lo + slab)) for lo in range(0, block, slab)]
    return diagonal, ((0, i * per) if up else ((i + 1) * per, total))


def _band(block: int, chunk: int, slab: int, window: int, up: bool):
    """What a block visits under a causal ``window`` (query t sees keys s
    with 0 <= t - s < window), as ``(slabs, plain)``, every number static
    and every column counted from the block's own first position.

    The band a block sees has two edges: the diagonal, and ``window - 1``
    behind it the trailing edge, which is the diagonal mirrored.  For a
    block of q rows walking kv (``up``) row r sees columns
    r - window + 1 .. r, so:

    * its own square, columns 0 .. block, is cut into slabs as
      :func:`_schedule` cuts it, each on the rows that see any of it
      (where the window is shorter than the block the rows far below a
      slab have left it behind and stay out);
    * ``plain`` columns straight behind the square, a whole number of
      chunks AND of slabs, are what every row sees whole (nothing unless
      the window is wider than the block): the loop whose body holds no
      mask code;
    * the trailing edge, from there back to column 1 - window, is cut the
      same way: a slab takes the rows from the block's first to the last
      that still sees any of it, and only the rows that see it in part
      compare ``q - k < window``.

    A slab is ``(first column, width, rows, masked, sides)``: ``rows`` the
    (lo, hi) rows that take part, ``masked`` the (lo, hi) among them that
    build a mask (None: none), ``sides`` = (diagonal, trailing) which
    comparisons that mask holds.  Row ranges are rounded outwards to whole
    lane tiles, so a window that is no multiple of anything costs at most
    127 rows a slab and a rounded-in row is always a masked one.  The
    own square's slabs come first.  For a block of kv rows walking q the
    picture is the same one turned round (row r is seen by columns
    r .. r + window - 1): the slabs are mirrored in the block's centre and
    ``plain`` lies straight after the square."""
    def down(x):                       # to whole lane tiles
        return x // _LANES * _LANES

    def up_to(x):
        return -down(-x)

    def mirror(lo, hi):
        return block - hi, block - lo

    step = math.lcm(chunk, slab)
    plain = max(window - block, 0) // step * step
    older = -(-(window - 1 - plain) // slab)       # slabs of trailing edge
    slabs = []
    for c0 in [*range(0, block, slab),
               *(-plain - slab * j for j in range(1, older + 1))]:
        last = c0 + slab - 1               # row r sees r - window + 1 .. r
        r0, r1 = max(c0, 0), min(block, up_to(last + window))
        # rows r0..d1 meet the diagonal, rows e0..r1 the trailing edge
        d1 = min(max(up_to(last), r0), r1)
        e0 = min(max(down(c0 + window), r0), r1)
        sides = (r0 < d1, e0 < r1)
        masked = ((r0, r1) if all(sides) else (r0, d1) if sides[0]
                  else (e0, r1) if sides[1] else None)
        rows = (r0, r1)
        if not up:
            c0, rows = block - c0 - slab, mirror(*rows)
            masked = masked and mirror(*masked)
        slabs.append((c0, slab, rows, masked, sides))
    return slabs, plain


def _count_tiles(tq, tk, block, chunk, slab, causal, whole, window=None):
    """(visited, whole square, masked) in (slab, slab) tiles of one head,
    by the schedule the kernels run; without the ``whole`` operand in
    one stretch every chunk the diagonal crosses takes every row.  Under
    a ``window`` the visits are :func:`_band`'s, in one stretch or in
    several: each slab by the rows that take part in it and masked by the
    rows that build a mask, the slabs that would lie before key 0 left
    out."""
    run = masked = 0
    if window:
        slabs, plain = _band(block, chunk, slab, window, True)
        for first in range(0, tq, block):
            run += min(plain, first) * block
            for c0, width, (r0, r1), rows, _ in slabs:
                if first + c0 >= 0:
                    run += (r1 - r0) * width
                    masked += (rows[1] - rows[0]) * width if rows else 0
        return (run // slab ** 2, (tq // slab) * (tk // slab),
                masked // slab ** 2)
    for i in range(tq // block):
        diagonal, (lo, hi) = _schedule(i, block, chunk, slab, tk // chunk,
                                       causal, True)
        run += (hi - lo) * chunk // slab * (block // slab)
        if causal and not whole:
            run += (block // slab) ** 2
            masked += (block // slab) ** 2
            continue
        for _, width, (r0, r1), rows in diagonal:
            run += (r1 - r0) // slab * (width // slab)
            masked += (rows[1] - rows[0]) // slab if rows else 0
    return run, (tq // slab) * (tk // slab), masked


def tile_plan(tq: int, tk: int, d: int, dtype, causal: bool,
              has_seg: bool = False, *, heads: int = 1,
              kv_heads: Optional[int] = None,
              block_q: Optional[int] = None,
              chunk: Optional[int] = None,
              window: Optional[int] = None, dv: Optional[int] = None,
              v_heads: Optional[int] = None, d2: int = 0,
              k2_heads: Optional[int] = None) -> TilePlan:
    """The one place a flash call's sizes are computed: from the sequence
    lengths, head dim, dtype, the masks in play and the number of heads.
    ``block_q`` / ``chunk`` override the starting sizes (tests only; a
    given chunk is the slab width too).  Pure and static: no device, no
    tracing.

    The block is the largest whole number of chunks, up to
    ``DEFAULT_BLOCK_Q`` rows, that divides the sequences and fits VMEM
    with a fair stretch of the walked operand.  Its own square on the
    diagonal is cut into slabs — the granularity of the causal skip —
    coarser in fwd, whose every slab updates the per-row softmax state,
    than in dq/dkv, which carry none; what lies off the diagonal is
    walked in chunks.  Under segment ids the skip is per (block, chunk)
    pair and wants both as fine as ``DEFAULT_SEG`` rows.  A grid step
    takes up to ``DEFAULT_GROUP`` heads of one batch row where the head
    count divides and VMEM allows.  With fewer key/value heads than query
    heads (``kv_heads``; ``v_heads`` where the values' differ from the
    keys') a step takes one query head, and reads the K/V head it shares
    by an index map.  ``dv`` is the values' head dim where it is not the
    keys' (VMEM is reckoned at the wider).  ``d2`` is the width of a
    second pair of score operands whose product is added to ``q k^T``
    (their lanes are reckoned in VMEM beside q's and k's) and
    ``k2_heads`` the heads its key has: fewer than the query heads make a
    step take one query head, as shared K/V heads do.  Under a causal
    ``window`` a block is no taller than the window (what its rows see of
    older keys is then at most a window wide) and walks its band by the
    two edges
    (:func:`_band`): the trailing edge is cut into the same slabs as the
    diagonal, narrowed where they must be to lie in one stretch whole,
    and the counts are of that schedule."""
    span = math.gcd(tq, tk)
    if window is not None and (not causal or has_seg or window < 1):
        raise ValueError("a window needs causal=True, no segment ids and "
                         f"at least one key (got {window})")
    d_k, d = d, max(d, dv or d)
    fine = DEFAULT_SEG if has_seg else None

    def fit(size, within):             # halve until it divides
        size = min(size, within)
        while size > 128 and within % size:
            size //= 2
        return size

    slabs = (chunk or fine or DEFAULT_SLAB, chunk or fine or DEFAULT_SLAB_BWD)
    # off the diagonal nothing is skipped; with no mask at all the chunk
    # doubles (half as many updates of the per-row state)
    chunk = fit(chunk or fine or DEFAULT_CHUNK * (1 if causal else 2), span)
    cap = min(block_q or fine or DEFAULT_BLOCK_Q, span)
    if window and not block_q:
        cap = min(cap, max(chunk, window - window % chunk))
    # whole numbers of chunks that divide both sequences, largest first;
    # a block smaller than the chunk (tests) takes the chunk down with it
    blocks = [b for b in range(cap - cap % chunk, 0, -chunk)
              if span % b == 0]
    if not blocks:
        blocks = [fit(cap, span)]
        chunk = fit(chunk, blocks[0])
    if span % chunk or span % blocks[-1] or blocks[-1] % chunk:
        raise ValueError(
            f"seq lens ({tq}, {tk}) must divide by blocks "
            f"({blocks[-1]}, {chunk})")
    group = DEFAULT_GROUP
    while heads % group:
        group //= 2
    for shared in (kv_heads, v_heads, k2_heads):
        if shared is not None and shared != heads:
            if heads % shared:
                raise ValueError(f"{heads} query heads do not divide over "
                                 f"{shared} key/value heads")
            group = 1
    # the widest slice a step holds scores of: a chunk, or a forward slab
    # of the smallest block
    wide = max(chunk, fit(slabs[0], blocks[-1]))
    block, major, major_q, group = _clamp_blocks(
        blocks, chunk, tq, tk, d, jnp.dtype(dtype).itemsize, has_seg, group,
        wide, d2)
    # the fused backward holds, beside a step's working set, the float32
    # dq (and dq2) of the group's whole query sequence and, twice, the
    # block they are written out through.  It asks Mosaic for the VMEM
    # that takes, so its walked stretch is cut by THAT limit and not by
    # the budget: whole at the cells' T = 8,192, where the block's own
    # square then runs as slabs on their trapezoids (36 of 64 tiles)
    itemsize = jnp.dtype(dtype).itemsize
    dq_bytes, dq_out = _dq_vmem(group, tq, d_k, d2, itemsize)

    def need(stretch):
        return dq_bytes + dq_out + _vmem_bytes(
            block, wide, stretch, d, itemsize, has_seg, group, d2)

    held = _largest_stretch(tq, chunk, lambda m: need(m) <= _VMEM_FUSED)
    asked = need(held)
    fused = asked <= _VMEM_FUSED
    if fused:
        major_q = held
    # a slab under a window lies in one stretch whole (_walk's sections)
    within = math.gcd(block, major, major_q) if window else block
    slab, slab_bwd = (fit(x, within) for x in slabs)
    run, full, masked = _count_tiles(tq, tk, block, chunk, slab, causal,
                                     major == tk, window)
    run_bwd, full_bwd, _ = _count_tiles(tq, tk, block, chunk, slab_bwd,
                                        causal, major_q == tq, window)
    return TilePlan(block, chunk, slab, slab_bwd, major, major_q, group,
                    run, full, run if has_seg else masked, run_bwd,
                    full_bwd, "fused" if fused else "split",
                    dq_bytes if fused else 0,
                    asked + _VMEM_BUDGET // 3
                    if fused and asked > _VMEM_BUDGET else 0)


def plan_event(name: str, **attrs):
    """One ``name`` event per distinct set of attributes in the tracer's
    ring, recorded while a call is traced (never in a step's hot path);
    nothing without a tracer.  Shared by ``flash.plan``, ``ssd.plan`` and
    ``moe.plan``."""
    from ..observability.trace import active
    tr = active()
    if tr is not None and not any(s.attrs == attrs
                                  for s in tr.spans(name=name)):
        tr.event(name, **attrs)


def _report_plan(plan: TilePlan, tq, tk, d, dtype, causal, has_seg,
                 window=None, dv=None, d2=0, k2_heads=None):
    """``window``, ``dv``, and ``d2`` with ``k2_heads`` are attributes
    only of a call that has a window, values wider than its keys, or a
    second pair of score operands."""
    more = {}
    if d2:
        more.update(d2=int(d2), k2_heads=int(k2_heads))
    if window is not None:
        more["window"] = int(window)
    if dv is not None and dv != d:
        more["dv"] = int(dv)
    plan_event("flash.plan", **plan._asdict(), tq=tq, tk=tk, d=d,
               dtype=jnp.dtype(dtype).name, causal=bool(causal),
               has_seg=bool(has_seg), **more)


def matmul_precision(dtype):
    """The precision a matrix product of ``dtype`` operands states for
    itself: float32 operands keep the package's true-f32 contract
    (``HIGHEST``); bf16 ones take the MXU's single pass — AND SO DO THE
    TRANSPOSED PRODUCTS of the backward pass, whose float32 cotangents
    would otherwise run under the package default ('highest': six
    passes)."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _dot(a, b, ca: int, cb: int):
    """In-kernel 2D contraction of ``a`` dim ``ca`` with ``b`` dim ``cb``,
    accumulated in f32.  The precision is stated HERE, per operand type,
    so the process-wide ``jax_default_matmul_precision`` (the package sets
    'highest') never reaches Mosaic — which refuses an fp32-precision
    ``tpu.matmul`` on bf16 operands.  bf16 operands take the MXU's single
    pass (exact products, f32 accumulation); float32 operands keep the
    package's true-f32 contract (``mxnet_tpu/__init__.py``)."""
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), precision=matmul_precision(a.dtype),
        preferred_element_type=jnp.float32)


def _default_interpret(x) -> bool:
    from ..base import resolve_exec_platform
    return resolve_exec_platform(x) != "tpu"


# ------------------------------------------------------- the walk, shared

def _walk(step, *, causal, up, i, mi, nm, block, chunk, slab, cpm, fold,
          seg, window=None):
    """Run ``step(start, width, rows, masked, fresh, sides)`` over the
    slices ``[start, start + width)`` of this grid step's major stretch
    that block ``i`` has to visit: ``rows`` the block's rows that take
    part, ``masked`` those of them that need the position mask (None:
    none), ``fresh`` those whose accumulators this visit is the first to
    touch, so that it assigns them where later visits add (None: none),
    ``sides`` which comparisons the mask makes (:func:`_keep`).

    With the whole operand in one stretch (``nm == 1``) the visits are
    those of :func:`_schedule`: the diagonal's slabs — a static number —
    run first and straight-line, each on the trapezoid of rows that sees
    it, so that the scheduler overlaps them with each other and with the
    step's prologue and (``fold``) nothing has to be zeroed; then one
    loop, its trip count the causal bound, takes the chunks the block
    sees whole with a body that holds no mask code.  Every row's own
    diagonal column lies in the first slab it takes part in, so the
    running max is finite from a row's first visit on in this order too.
    Over several stretches every visit is a chunk and takes every row:
    a loop over the chunks the diagonal crosses, masked, and one over the
    plain ones, both clipped to the stretch.

    ``seg`` is None or (the block's own segment ids, the walked
    operand's (1, 1, major) id ref): a slice whose id range cannot meet
    the block's is skipped — exact for the packed layout (ids
    non-decreasing along the row), conservative (never skips a slice
    that could match) for arbitrary ids.

    Under a ``window`` the visits are :func:`_band`'s, in one stretch or
    in several: the slabs of the own square and of the trailing edge
    straight-line, each on its own rows, ``step`` told which of the two
    comparisons its masked rows need (``sides``); then the loop over what
    every row sees whole between the two.  Only a slab's start in the stretch
    is traced.  The slabs are run a *section* at a time — an aligned run
    of columns that divides block and stretch, so that it lies in one
    stretch whole — under one branch a section: "is it in this stretch"
    (which also says no to what would lie before key 0 or after the last
    query).  What is older than the window is never visited."""
    whole = (0, block)

    def visit(start, width, rows, masked, fresh, sides=_DIAGONAL):
        if seg is None:
            return step(start, width, rows, masked, fresh, sides)
        mine, ref = seg
        theirs = ref[0, :, pl.ds(start, width)]

        @pl.when(jnp.logical_and(jnp.min(theirs) <= jnp.max(mine),
                                 jnp.max(theirs) >= jnp.min(mine)))
        def _():
            step(start, width, rows, masked, fresh, sides)

    def loop(lo, hi, masked):
        def body(c, carry):
            visit(pl.multiple_of(c * chunk, chunk), chunk, whole, masked,
                  None)
            return carry
        jax.lax.fori_loop(lo, hi, body, 0)

    if window:
        stretch = cpm * chunk
        section = math.gcd(block, stretch)
        slabs, plain = _band(block, chunk, slab, window, up)
        own0 = i * block - mi * stretch    # the block's first row, in here

        def run(slabs):
            for c0, width, rows, masked, sides in slabs:
                visit(pl.multiple_of(own0 + c0, width), width, rows, masked,
                      None, sides)

        for k, held in itertools.groupby(slabs, lambda x: x[0] // section):
            first = own0 + k * section
            own = nm == 1 and 0 <= k * section < block
            _when(own, jnp.logical_and(first >= 0, first < stretch))(
                functools.partial(run, list(held)))
        if plain:                          # straight behind / after the square
            a = (own0 - plain if up else own0 + block) // chunk
            loop(jnp.clip(a, 0, cpm), jnp.clip(a + plain // chunk, 0, cpm),
                 None)
        return
    diagonal, plain = _schedule(i, block, chunk, slab, nm * cpm, causal, up)
    if nm > 1:
        lo, per = mi * cpm, block // chunk
        crossed = (i * per, (i + 1) * per) if causal else (0, 0)
        if not causal:
            plain = (0, plain[1])
        for (a, b), masked in ((crossed, whole), (plain, None)):
            loop(jnp.clip(a - lo, 0, cpm), jnp.clip(b - lo, 0, cpm), masked)
        return
    for j, (start, width, rows, masked) in enumerate(diagonal):
        if not fold:
            fresh = None
        elif up:                         # the first slab holds every row
            fresh = rows if j == 0 else None
        else:                            # each slab brings its last rows
            fresh = masked or rows
        if not isinstance(start, int):
            start = pl.multiple_of(start, width)
        visit(start, width, rows, masked, fresh)
    loop(*plain, None)


def _keep(rows, masked, width, own_is_q, own0, walk0, seg_own, seg_walk,
          window=None, sides=_DIAGONAL):
    """The mask of one visit's score tile — (block rows ``rows``, ``width``
    walked columns) — as ``(keep, sub)``: ``keep`` covers the tile's
    rows ``sub`` = (lo, hi) only, or is None when nothing masks the tile.
    Rows ``masked`` of the block (None: none) lie on the diagonal and
    compare global positions, ``kv <= q``: the block's own run down the
    tile from ``own0``, the walked operand's along it from ``walk0``, and
    ``own_is_q`` says which of the two are q's.  ``sides`` =
    (diagonal, trailing) says which comparisons they make: ``kv <= q``,
    and under a ``window`` ``q - kv < window`` on the band's other edge
    (:func:`_band` knows which rows of a slab meet which).  Segment ids
    (``seg_own`` a (block, 1) column, ``seg_walk`` a (1, width) row) mask
    every row, where the call packs segments."""
    if seg_own is None and masked is None:
        return None, None
    m0, m1 = rows if seg_own is not None else masked
    keep = None
    if masked is not None:
        shape = (m1 - m0, width)
        own = own0 + m0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        walk = walk0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        diagonal, trailing = sides
        if diagonal:
            keep = walk <= own if own_is_q else own <= walk
        if trailing:
            near = (own - walk if own_is_q else walk - own) < window
            keep = near if keep is None else jnp.logical_and(keep, near)
    if seg_own is not None:
        same = seg_own[m0:m1] == seg_walk
        keep = same if keep is None else jnp.logical_and(keep, same)
    return keep, (m0 - rows[0], m1 - rows[0])


def _where_rows(keep, x, fill, sub):
    """``where(keep, x, fill)`` on rows ``sub`` = (lo, hi) of ``x`` (the
    mask covers just those), the other rows as they are."""
    lo, hi = sub
    parts = [x[:lo]] if lo else []
    parts.append(jnp.where(keep, x[lo:hi], fill))
    if hi < x.shape[0]:
        parts.append(x[hi:])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _accumulate(ref, g, rows, fresh, val):
    """Add ``val`` to rows ``rows`` of accumulator ``ref[g]``; rows
    ``fresh`` (the last of them) are assigned: nothing was there yet."""
    r0, r1 = rows
    f0 = r1 if fresh is None else fresh[0]
    if f0 > r0:
        ref[g, r0:f0] += val[:f0 - r0]
    if f0 < r1:
        ref[g, f0:r1] = val[f0 - r0:]


# --------------------------------------------------------------------- fwd
# Per-row statistics (running max, running sum, lse, delta) live as
# (rows, 128) tiles with the row's value in every lane.  A (rows, 1)
# column costs as many vector registers and has to be broadcast along
# lanes again at every use — an XLU permute per register per chunk, which
# at these tile sizes cost more than the matmuls.  ``_lanes`` widens or
# narrows such a tile to a tile's width for free (whole registers repeat).

def _lanes(x, n: int):
    """A lane-replicated (rows, 128) tile as (rows, n)."""
    if n <= _LANES:
        return x[:, :n]
    return jnp.tile(x, (1, n // _LANES))


def _fold(x):
    """Sum a (rows, n) tile's 128-lane column groups: (rows, 128) partial
    sums, one VPU add per register and no cross-lane reduction."""
    out = x[:, :_LANES]
    for i in range(1, x.shape[1] // _LANES):
        out = out + x[:, i * _LANES:(i + 1) * _LANES]
    return out


def _when(static, cond):
    """``pl.when(cond)``, or no branch at all where ``static`` says the
    condition always holds: the body then stays in the caller's region,
    where the scheduler can overlap it with the neighbouring tiles."""
    return (lambda f: f()) if static else pl.when(cond)


def _rows_to_lanes(x):
    """A lane-replicated (rows, 128) tile as the (1, rows) row vector it
    is stored as: one XLU transpose of the tile (a rows-to-lanes relayout
    of a column goes through memory a row at a time)."""
    return x.T[:1]


def _lanes_to_rows(row, rows: int):
    """The (1, rows) row vector as a lane-replicated (rows, 128) tile:
    broadcast down the sublanes, then one XLU transpose."""
    return jnp.broadcast_to(row, (_LANES, rows)).T


def _second(refs, at: int, two: bool):
    """``(q2_ref, k2_ref, the other refs)``: the second pair of score
    operands, which a call that has one hands in at place ``at``."""
    if not two:
        return None, None, refs
    return refs[at], refs[at + 1], refs[:at] + refs[at + 2:]


def _score(own_ref, walked, own2_ref, walk2_ref, g, rows, start, width,
           scale):
    """The (rows, width) score tile of head ``g``: the block's ``rows``
    against ``walked``, the (width, d) slice ``[start, start + width)``
    of the walked operand, times ``scale``; with a second pair of
    operands their product over the same rows and slice is added BEFORE
    the scale: one score over both widths."""
    r0, r1 = rows
    s = _dot(own_ref[g, r0:r1, :], walked, 1, 1)
    if own2_ref is not None:
        s = s + _dot(own2_ref[g, r0:r1, :],
                     walk2_ref[g, pl.ds(start, width), :], 1, 1)
    return s * scale


def _fwd_kernel(*refs, scale, causal, has_seg, block_q, chunk, slab, nm,
                window=None, two=False):
    q2_ref, k2_ref, refs = _second(refs, 3, two)
    if has_seg:
        (q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    qi = pl.program_id(1)
    mi = pl.program_id(2)
    group, major, _ = k_ref.shape
    dv = v_ref.shape[2]
    fold = nm == 1 and not has_seg and not window

    if not fold:
        @pl.when(mi == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, _MASK)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

    qs = qseg_ref[0, 0, :][:, None] if has_seg else None   # (bq, 1)

    def step(start, width, rows, masked, fresh, sides):
        r0, r1 = rows
        keep, sub = _keep(rows, masked, width, True, qi * block_q,
                          mi * major + start, qs,
                          kseg_ref[0, :, pl.ds(start, width)] if has_seg
                          else None, window, sides)
        # the group's heads are independent chains of matmul → softmax →
        # matmul: side by side in one region they fill each other's
        # latencies
        for g in range(group):
            k = k_ref[g, pl.ds(start, width), :]       # (width, d)
            s = _score(q_ref, k, q2_ref, k2_ref, g, rows, start, width,
                       scale)                          # (rows, width)
            if keep is not None:
                s = _where_rows(keep, s, _MASK, sub)
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_prev = jnp.full((r1 - r0, _LANES), _MASK) if fresh \
                else m_ref[g, r0:r1, :]                # (rows, 128)
            m_next = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - _lanes(m_next, width))
            if has_seg:
                # masked-safe exp: a row whose every entry so far is
                # masked (its segment starts in a LATER chunk) has
                # m_next == _MASK, and bare exp(s - m_next) would
                # contribute exp(0)=1 per masked entry.  Zero masked
                # entries explicitly.  The causal mask alone never needs
                # this: every row sees a column of the first chunk it
                # visits (see _walk), so m_next is finite from then on
                # and exp(_MASK - m_next) is exactly 0.  Nor does a
                # window.  A visit takes the rows that see part of it and
                # the few that rounding to lane tiles brings in; such a
                # row can meet a slab it sees nothing of before any key it
                # does see only where its trailing edge lies in an
                # earlier stretch than its own square.  What it gathers
                # there (1 a masked entry, under m == _MASK) is finite,
                # and every row sees its own position: that visit
                # multiplies it by exp(_MASK - m_next) == 0.
                p = jnp.where(keep, p, 0.0)
            v = v_ref[g, pl.ds(start, width), :]
            pv = _dot(p.astype(v.dtype), v, 1, 0)      # (rows, d)
            # l holds per-lane partial sums; _finish adds the lanes up
            if fresh:
                l_ref[g, r0:r1, :] = _fold(p)
                acc_ref[g, r0:r1, :] = pv
            else:
                corr = jnp.exp(m_prev - m_next)        # (rows, 128)
                l_ref[g, r0:r1, :] = corr * l_ref[g, r0:r1, :] + _fold(p)
                acc_ref[g, r0:r1, :] = \
                    acc_ref[g, r0:r1, :] * _lanes(corr, dv) + pv
            m_ref[g, r0:r1, :] = m_next

    _walk(step, causal=causal, up=True, i=qi, mi=mi, nm=nm, block=block_q,
          chunk=chunk, slab=slab, cpm=major // chunk, fold=fold,
          seg=(qs, kseg_ref) if has_seg else None, window=window)

    @_when(nm == 1, mi == nm - 1)
    def _finish():
        for g in range(group):
            l = jnp.sum(l_ref[g], axis=1, keepdims=True)   # (bq, 1)
            # rows with NO matching key anywhere (possible only in
            # degenerate cross-segment cases) get zeros out and a finite
            # lse of _MASK so the backward recompute exp(s - lse) stays 0,
            # never inf
            empty = l <= 0.0
            o_ref[g] = jnp.where(
                empty, 0.0, acc_ref[g] / jnp.where(empty, 1.0, l)
            ).astype(o_ref.dtype)
            lse = jnp.where(empty, _MASK, m_ref[g] + jnp.log(
                jnp.where(empty, 1.0, l)))                 # (bq, 128)
            lse_ref[g] = _rows_to_lanes(lse)


def _own_spec(shape, row=lambda b: b):
    """BlockSpec of an operand a grid step owns a block of: ``shape`` is
    the block shape, the block axis the one of its last two that is not
    the head dim (rows for (g, block, d), lanes for (g, 1, block));
    ``row`` maps the grid's first coordinate to the operand's."""
    if shape[1] == 1:
        return pl.BlockSpec(shape, lambda b, i, m: (row(b), 0, i))
    return pl.BlockSpec(shape, lambda b, i, m: (row(b), i, 0))


def _kv_row(nheads: int, kv_heads: int):
    """Flat query-head index -> flat index of the K/V head it reads
    (query head h of a batch row shares K/V head h // (H / H_kv))."""
    if kv_heads == nheads:
        return lambda b: b
    share = nheads // kv_heads
    return lambda b: (b // nheads) * kv_heads + (b % nheads) // share


def _walked_spec(shape, block, major, causal, up, row=lambda b: b,
                 window=None):
    """BlockSpec of a walked operand: its major stretch, with an index map
    that ignores the block axis (so one head's stretch is fetched once),
    clamped under ``causal`` to the stretches the block's rows can see —
    a grid step that has nothing to visit names the stretch it already
    holds and fetches nothing.  ``shape`` is the block shape with -1 for
    the sequence axis; ``up`` says the walk ends at the diagonal (fwd,
    dq) rather than starts there (dkv); ``row`` maps the grid's first
    coordinate to the operand's.  A ``window`` clamps the other end too,
    to the stretch of the oldest key (the last query) the block meets."""
    axis = shape.index(-1)

    def index(b, i, m):
        if causal and up:
            m = jnp.minimum(m, ((i + 1) * block - 1) // major)
            if window:
                m = jnp.maximum(
                    m, jnp.maximum(i * block - window + 1, 0) // major)
        elif causal:
            m = jnp.maximum(m, (i * block) // major)
            if window:
                m = jnp.minimum(m, ((i + 1) * block + window - 2) // major)
        return tuple(row(b) if a == 0 else m if a == axis else 0
                     for a in range(len(shape)))
    return pl.BlockSpec(tuple(major if n == -1 else n for n in shape), index)


def _seg_specs(rows, block, major, causal, up):
    """BlockSpecs for (B, 1, T) segment-id planes, (the block's own, the
    walked operand's); ``rows`` maps the grid's first coordinate (a group
    of heads of one batch row) to that batch row."""
    return [
        pl.BlockSpec((1, 1, block), lambda b, i, m: (rows(b), 0, i)),
        _walked_spec((1, 1, -1), block, major, causal, up, row=rows),
    ]


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd(q, k, v, q_seg, kv_seg, nheads, causal, scale, plan, interpret,
         window=None, q2=None, k2=None):
    bh, tq, d = q.shape
    kv_row = _kv_row(nheads, k.shape[0] * nheads // bh)
    v_row = _kv_row(nheads, v.shape[0] * nheads // bh)
    tk, dv = k.shape[1], v.shape[2]
    block_q, chunk, major, group = (plan.block_q, plan.chunk, plan.major,
                                    plan.group)
    nm = tk // major
    has_seg = q_seg is not None
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, has_seg=has_seg,
        block_q=block_q, chunk=chunk, slab=plan.slab, nm=nm, window=window,
        two=q2 is not None)
    in_specs = [_own_spec((group, block_q, d)),
                _walked_spec((group, -1, d), block_q, major, causal, True,
                             row=kv_row, window=window),
                _walked_spec((group, -1, dv), block_q, major, causal, True,
                             row=v_row, window=window)]
    args = [q, k, v]
    if q2 is not None:
        d2 = q2.shape[2]
        in_specs += [_own_spec((group, block_q, d2)),
                     _walked_spec((group, -1, d2), block_q, major, causal,
                                  True, window=window, row=_kv_row(
                                      nheads, k2.shape[0] * nheads // bh))]
        args += [q2, k2]
        d += d2                             # the score's width, for the cost
    moved = sum(x.size for x in args)       # q, k, v and a second pair
    if has_seg:
        in_specs += _seg_specs(lambda b: b * group // nheads, block_q,
                               major, causal, True)
        args += [q_seg, kv_seg]
    half = 2 if causal else 1
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bh // group, tq // block_q, nm),
        in_specs=in_specs,
        out_specs=[
            _own_spec((group, block_q, dv)),
            # lse is (bh, 1, tq) so each qi owns its own (g, 1, block_q)
            # tile — TPU block rules demand last-two dims divisible by
            # (8, 128) or equal to the array dims, and a shared full-row
            # block would race across megacore's parallel qi partitions.
            _own_spec((group, 1, block_q)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, block_q, dv), jnp.float32),
            pltpu.VMEM((group, block_q, _LANES), jnp.float32),
            pltpu.VMEM((group, block_q, _LANES), jnp.float32),
        ],
        compiler_params=_PARAMS,
        # what the algorithm needs: under a causal mask half the square
        cost_estimate=pl.CostEstimate(
            flops=2 * bh * tq * tk * (d + dv) // half,
            transcendentals=bh * tq * tk // half,
            bytes_accessed=2 * moved * q.dtype.itemsize),
        interpret=interpret,
    )(*args)
    return out, lse


# --------------------------------------------------------------------- bwd

def _dq_kernel(*refs, scale, causal, has_seg, block_q, chunk, slab, nm,
               window=None, two=False):
    q2_ref, k2_ref, refs = _second(refs, 6, two)
    dq2_ref = acc2_ref = None
    if two:             # the second operand's dq and its accumulator
        *refs, acc2_ref = refs
        dq2_ref = refs.pop(-4)
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         qseg_ref, kseg_ref, dq_ref, acc_ref, lse_b, delta_b) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, acc_ref, lse_b, delta_b) = refs
    qi = pl.program_id(1)
    mi = pl.program_id(2)
    group, major, _ = k_ref.shape
    fold = nm == 1 and not has_seg and not window

    @_when(nm == 1, mi == 0)
    def _init():
        if not fold:
            acc_ref[:] = jnp.zeros_like(acc_ref)
            if two:
                acc2_ref[:] = jnp.zeros_like(acc2_ref)
        # the block's lse and delta, stored along lanes, turned into
        # lane-replicated row tiles once, not at every chunk
        for g in range(group):
            lse_b[g] = _lanes_to_rows(lse_ref[g], block_q)
            delta_b[g] = _lanes_to_rows(delta_ref[g], block_q)

    qs = qseg_ref[0, 0, :][:, None] if has_seg else None

    def step(start, width, rows, masked, fresh, sides):
        r0, r1 = rows
        keep, sub = _keep(rows, masked, width, True, qi * block_q,
                          mi * major + start, qs,
                          kseg_ref[0, :, pl.ds(start, width)] if has_seg
                          else None, window, sides)
        for g in range(group):
            k = k_ref[g, pl.ds(start, width), :]       # (width, d)
            s = _score(q_ref, k, q2_ref, k2_ref, g, rows, start, width,
                       scale)                          # (rows, width)
            p = jnp.exp(s - _lanes(lse_b[g, r0:r1, :], width))
            if keep is not None:
                p = _where_rows(keep, p, 0.0, sub)
            dp = _dot(do_ref[g, r0:r1, :],
                      v_ref[g, pl.ds(start, width), :], 1, 1)
            ds = (p * (dp - _lanes(delta_b[g, r0:r1, :], width))
                  * scale).astype(k.dtype)
            _accumulate(acc_ref, g, rows, fresh, _dot(ds, k, 1, 0))
            if two:
                _accumulate(acc2_ref, g, rows, fresh, _dot(
                    ds, k2_ref[g, pl.ds(start, width), :], 1, 0))

    _walk(step, causal=causal, up=True, i=qi, mi=mi, nm=nm, block=block_q,
          chunk=chunk, slab=slab, cpm=major // chunk, fold=fold,
          seg=(qs, kseg_ref) if has_seg else None, window=window)

    @_when(nm == 1, mi == nm - 1)
    def _finish():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)
        if two:
            dq2_ref[:] = acc2_ref[:].astype(dq2_ref.dtype)


def _bwd_kernel(*refs, scale, causal, has_seg, block_k, chunk, slab, nm,
                window=None, two=False, fused=True):
    """One k/v block of a group of heads against the q/dO stretch it
    walks: ``dk`` and ``dv`` (``dk2``) of the block, and with ``fused``
    the block's share of ``dq`` (``dq2``) too, all from the ONE ``S``,
    ``P``, ``dP``, ``dS`` of every visited tile.

    Tiles are held transposed, (kv rows, q columns): lse and delta are
    stored along lanes, so they broadcast down the tile as they are, and
    both of the block's own accumulating products contract the tile's
    columns with the rows of dO and Q: no transposed operand.  ``dq`` is
    the one product that contracts the tile's ROWS, ``dS^T^T K``; it is
    added into a float32 accumulator that holds the heads' WHOLE query
    sequence and lives across the block axis of the grid (which is
    therefore "arbitrary"): zeroed at a head's first grid step, written
    out in q's type at its last.  No partial ``dq`` goes through HBM.

    ``refs``: q, k, v, do, lse, delta [, q2, k2] [, q's segment ids,
    k's]; then the outputs [dq [, dq2]], dk, dv [, dk2]; then one
    float32 accumulator for each output, in the same order."""
    q2_ref, k2_ref, refs = _second(refs, 6, two)
    ins = 8 if has_seg else 6
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    qseg_ref, kseg_ref = refs[6:ins] if has_seg else (None, None)
    walked = ["dq"] + ["dq2"] * two if fused else []   # the walk's rows'
    own = ["dk", "dv"] + ["dk2"] * two                 # the block's
    names = walked + own
    out = dict(zip(names, refs[ins:]))
    acc = dict(zip(names, refs[ins + len(names):]))
    ki = pl.program_id(1)
    mi = pl.program_id(2)
    group, major, _ = q_ref.shape
    fold = nm == 1 and not has_seg and not window

    if not fold:
        @pl.when(mi == 0)
        def _init():
            for x in own:
                acc[x][:] = jnp.zeros_like(acc[x])

    def over_q(f):
        """``f(rows)`` over the query sequence a block's worth at a time:
        a loop, not megabytes of straight-line code."""
        def body(j, carry):
            f(pl.ds(pl.multiple_of(j * block_k, block_k), block_k))
            return carry
        jax.lax.fori_loop(0, nm * major // block_k, body, 0)

    if fused:
        @pl.when(jnp.logical_and(ki == 0, mi == 0))
        def _init_dq():
            for x in walked:
                def zero(at, a=acc[x]):
                    a[:, at, :] = jnp.zeros((group, block_k, a.shape[2]),
                                            a.dtype)
                over_q(zero)

    ks = kseg_ref[0, 0, :][:, None] if has_seg else None   # (bk, 1)

    def step(start, width, rows, masked, fresh, sides):
        r0, r1 = rows
        keep, sub = _keep(rows, masked, width, False, ki * block_k,
                          mi * major + start, ks,
                          qseg_ref[0, :, pl.ds(start, width)] if has_seg
                          else None, window, sides)
        # the slice's rows of the whole query sequence
        at = pl.ds(pl.multiple_of(mi * major + start, width), width)
        for g in range(group):
            q = q_ref[g, pl.ds(start, width), :]       # (width, d)
            do = do_ref[g, pl.ds(start, width), :]
            lse = lse_ref[g, :, pl.ds(start, width)]   # (1, width)
            delta = delta_ref[g, :, pl.ds(start, width)]
            st = _score(k_ref, q, k2_ref, q2_ref, g, rows, start, width,
                        scale)                         # S^T: (rows, width)
            p = jnp.exp(st - lse)
            if keep is not None:
                p = _where_rows(keep, p, 0.0, sub)
            # dV += P^T @ dO
            _accumulate(acc["dv"], g, rows, fresh,
                        _dot(p.astype(do.dtype), do, 1, 0))
            dp = _dot(v_ref[g, r0:r1, :], do, 1, 1)    # dP^T
            ds = (p * (dp - delta) * scale).astype(q.dtype)
            # dK += dS^T @ Q
            _accumulate(acc["dk"], g, rows, fresh, _dot(ds, q, 1, 0))
            if two:
                _accumulate(acc["dk2"], g, rows, fresh, _dot(
                    ds, q2_ref[g, pl.ds(start, width), :], 1, 0))
            if fused:                                  # dQ += dS @ K
                acc["dq"][g, at, :] += _dot(ds, k_ref[g, r0:r1, :], 0, 0)
                if two:
                    acc["dq2"][g, at, :] += _dot(
                        ds, k2_ref[g, r0:r1, :], 0, 0)

    _walk(step, causal=causal, up=False, i=ki, mi=mi, nm=nm, block=block_k,
          chunk=chunk, slab=slab, cpm=major // chunk, fold=fold,
          seg=(ks, qseg_ref) if has_seg else None, window=window)

    @_when(nm == 1, mi == nm - 1)
    def _finish():
        for x in own:
            out[x][:] = acc[x][:].astype(out[x].dtype)

    if fused:
        @pl.when(jnp.logical_and(ki == pl.num_programs(1) - 1,
                                 mi == nm - 1))
        def _finish_dq():
            for x in walked:
                def write(at, o=out[x], a=acc[x]):
                    o[:, at, :] = a[:, at, :].astype(o.dtype)
                over_q(write)


def _bwd_impl(q, k, v, q_seg, kv_seg, out, lse, do, nheads, causal, scale,
              plan, interpret, window=None, q2=None, k2=None):
    """``(dq, dk, dv)``, and with a second pair of score operands
    ``(dq, dk, dv, dq2, dk2)``: ONE call, ``flash_bwd``, where the plan
    says ``backward="fused"`` (:func:`_bwd_kernel`); where the query
    sequence's float32 ``dq`` would not fit VMEM, ``flash_bwd_dq`` (q
    blocks walking K/V) and ``flash_bwd_dkv`` (the same kernel body
    without its ``dq``), each making the score tiles for itself."""
    bh, tq, d = q.shape
    tk, dv = k.shape[1], v.shape[2]
    block, chunk, group = plan.block_q, plan.chunk, plan.group
    has_seg = q_seg is not None
    fused = plan.backward == "fused"
    kv_heads, v_heads = (x.shape[0] * nheads // bh for x in (k, v))
    kv_row, v_row = _kv_row(nheads, kv_heads), _kv_row(nheads, v_heads)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]               # (bh, 1, tq)
    tile, tile_v = _own_spec((group, block, d)), _own_spec((group, block, dv))
    args = [q, k, v, do, lse, delta]
    two = q2 is not None
    dq_shape = [jax.ShapeDtypeStruct((bh, tq, d), q.dtype)]
    if two:
        d2 = q2.shape[2]
        k2_heads = k2.shape[0] * nheads // bh
        k2_row = _kv_row(nheads, k2_heads)
        tile2 = _own_spec((group, block, d2))
        args += [q2, k2]
        dq_shape.append(jax.ShapeDtypeStruct((bh, tq, d2), q2.dtype))
    if has_seg:
        args += [q_seg, kv_seg]

    def rows(b):
        return b * group // nheads

    def scratch(shapes, length=block):
        return [pltpu.VMEM((group, length, x.shape[2]), jnp.float32)
                for x in shapes]

    if not fused:
        row = _own_spec((group, 1, block))
        dq_in_specs = [tile, _walked_spec(
            (group, -1, d), block, plan.major, causal, True, row=kv_row,
            window=window), _walked_spec(
            (group, -1, dv), block, plan.major, causal, True, row=v_row,
            window=window), tile_v, row, row]
        if two:
            dq_in_specs += [tile2, _walked_spec(
                (group, -1, d2), block, plan.major, causal, True, row=k2_row,
                window=window)]
        if has_seg:
            dq_in_specs += _seg_specs(rows, block, plan.major, causal, True)
        row_tiles = [pltpu.VMEM((group, block, _LANES), jnp.float32)] * 2
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, causal=causal,
                              has_seg=has_seg, block_q=block, chunk=chunk,
                              slab=plan.slab_bwd, nm=tk // plan.major,
                              window=window, two=two),
            name="flash_bwd_dq",
            grid=(bh // group, tq // block, tk // plan.major),
            in_specs=dq_in_specs,
            out_specs=[tile, tile2] if two else [tile],
            out_shape=dq_shape,
            scratch_shapes=(scratch(dq_shape[:1]) + row_tiles
                            + scratch(dq_shape[1:])),
            compiler_params=_PARAMS,
            interpret=interpret,
        )(*args)

    q_walk = _walked_spec((group, -1, d), block, plan.major_q, causal,
                          False, window=window)
    do_walk = _walked_spec((group, -1, dv), block, plan.major_q, causal,
                           False, window=window)
    row_walk = _walked_spec((group, 1, -1), block, plan.major_q, causal,
                            False, window=window)
    # each query head reads the K/V head it shares and writes that head's
    # dK/dV of its own, in float32; the heads of a share are summed after
    shared = kv_heads != nheads or v_heads != nheads
    in_specs = [q_walk, _own_spec((group, block, d), row=kv_row),
                _own_spec((group, block, dv), row=v_row), do_walk,
                row_walk, row_walk]
    out_specs = [tile, tile_v]
    out_shape = [
        jax.ShapeDtypeStruct((bh, tk, d), jnp.float32 if shared else k.dtype),
        jax.ShapeDtypeStruct((bh, tk, dv),
                             jnp.float32 if shared else v.dtype),
    ]
    if two:
        in_specs += [
            _walked_spec((group, -1, d2), block, plan.major_q, causal, False,
                         window=window),
            _own_spec((group, block, d2), row=k2_row)]
        out_specs.append(tile2)
        out_shape.append(jax.ShapeDtypeStruct(
            (bh, tk, d2), k2.dtype if k2_heads == nheads else jnp.float32))
    if has_seg:
        in_specs += _seg_specs(rows, block, plan.major_q, causal,
                               False)[::-1]
    accs, params = scratch(out_shape), _PARAMS
    if fused:
        # the heads' whole query sequence, held across the block axis
        out_specs = [pl.BlockSpec((group, tq, x.shape[2]),
                                  lambda b, i, m: (b, 0, 0))
                     for x in dq_shape] + out_specs
        out_shape = dq_shape + out_shape
        accs = scratch(dq_shape, tq) + accs
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=plan.bwd_vmem or None)
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          has_seg=has_seg, block_k=block, chunk=chunk,
                          slab=plan.slab_bwd, nm=tq // plan.major_q,
                          window=window, two=two, fused=fused),
        name="flash_bwd" if fused else "flash_bwd_dkv",
        grid=(bh // group, tk // block, tq // plan.major_q),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=accs,
        compiler_params=params,
        interpret=interpret,
    )(*args)
    if fused:
        dq, outs = outs[:len(dq_shape)], outs[len(dq_shape):]
    dk, dv_, *dk2 = outs

    def over_share(x, like, held):
        x = x.reshape(bh // nheads, held, nheads // held, tk, x.shape[-1])
        return x.sum(axis=2).reshape(like.shape).astype(like.dtype)

    if shared:
        dk, dv_ = over_share(dk, k, kv_heads), over_share(dv_, v, v_heads)
    if not two:
        return dq[0], dk, dv_
    dk2, = dk2
    if k2_heads != nheads:
        dk2 = over_share(dk2, k2, k2_heads)
    return dq[0], dk, dv_, dq[1], dk2


# ----------------------------------------------------------- custom_vjp glue
# Segment ids travel as primal args (they are data, not static config) and
# return symbolic-zero cotangents of dtype float0, the JAX contract for
# integer primal inputs.

def _int_zero_cotangent(x):
    if x is None:
        return None
    import numpy as _np

    from jax import dtypes as _dtypes
    return _np.zeros(x.shape, _dtypes.float0)


# A windowed block's body is straight-line code for two edges, and a step
# traces it once a CALL: its layers, their backward, the abstract forward
# of a trainer's ``settle``, every trace of the step.  Mellum's three
# windowed layers so added 8 s to a 51 s set-up.  Under ``jax.jit`` a
# kernel's body is traced once a shape; ``inline``, so that the calling
# jaxpr holds the kernels' own equations as it did.  The forward only
# under a window or with a second operand: the same wrappers around a
# call without one cost GPT-2's set-up 1.3 s of tracing on the chip's
# host in every pair of ten read, and with them off it read the parent's
# to 0.2 s (PERF.md §6, PR 44).  The backward always: the one call's body
# holds a block's own square as eight slabs at every length, and Ouro's
# six call sites, each traced for itself, added 2 s to a 39 s set-up
# (PERF.md §6, PR 50).
_fwd_once = jax.jit(_fwd, static_argnums=(5, 6, 7, 8, 9, 10), inline=True)
_bwd_once = jax.jit(_bwd_impl, static_argnums=(8, 9, 10, 11, 12, 13),
                    inline=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, q_seg, kv_seg, nheads, causal, scale, plan, interpret,
           window=None):
    out, _ = (_fwd_once if window else _fwd)(
        q, k, v, q_seg, kv_seg, nheads, causal, scale, plan, interpret,
        window)
    return out


# What a recomputed layer keeps of a flash call (the module docstring says
# why; ``models.transformer.run_blocks`` what it buys).
FLASH_OUT, FLASH_LSE = KEPT_NAMES = ("flash_out", "flash_lse")

_named = contextvars.ContextVar("flash_named_residuals", default=None)


@contextlib.contextmanager
def named_residuals():
    """Yields a list that receives ``(name, bytes)`` for every residual
    :func:`_flash_fwd` names while the body runs, which is while a call
    under it is DIFFERENTIATED (the rule's forward is traced then and
    not before).  ``run_blocks`` reports a checkpoint's ``remat.plan``
    from it; trace time only."""
    found = []
    token = _named.set(found)
    try:
        yield found
    finally:
        _named.reset(token)


def _name(x, name):
    found = _named.get()
    if found is not None:
        found.append((name, x.size * x.dtype.itemsize))
    return checkpoint_name(x, name)


def _flash_fwd(q, k, v, q_seg, kv_seg, nheads, causal, scale, plan,
               interpret, window=None):
    out, lse = (_fwd_once if window else _fwd)(
        q, k, v, q_seg, kv_seg, nheads, causal, scale, plan, interpret,
        window)
    # named HERE and nowhere else (the rule's forward: the one place
    # whose outputs are the backward's residuals): do not tidy away
    out, lse = _name(out, FLASH_OUT), _name(lse, FLASH_LSE)
    return out, (q, k, v, q_seg, kv_seg, out, lse)


def _flash_bwd(nheads, causal, scale, plan, interpret, window, res, do):
    q, k, v, q_seg, kv_seg, out, lse = res
    dq, dk, dv = _bwd_once(q, k, v, q_seg, kv_seg, out, lse, do, nheads,
                           causal, scale, plan, interpret, window)
    return (dq, dk, dv,
            _int_zero_cotangent(q_seg), _int_zero_cotangent(kv_seg))


_flash.defvjp(_flash_fwd, _flash_bwd)


# The call with a second pair of score operands, a rule of its own so that
# the calls without one are traced as they were.  Its bodies go under the
# same ``jax.jit`` wrappers: a stack's layers trace them once a shape.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash2(q, k, v, q2, k2, nheads, causal, scale, plan, interpret):
    out, _ = _fwd_once(q, k, v, None, None, nheads, causal, scale, plan,
                       interpret, None, q2, k2)
    return out


def _flash2_fwd(q, k, v, q2, k2, nheads, causal, scale, plan, interpret):
    out, lse = _fwd_once(q, k, v, None, None, nheads, causal, scale, plan,
                         interpret, None, q2, k2)
    out, lse = _name(out, FLASH_OUT), _name(lse, FLASH_LSE)
    return out, (q, k, v, q2, k2, out, lse)


def _flash2_bwd(nheads, causal, scale, plan, interpret, res, do):
    q, k, v, q2, k2, out, lse = res
    return _bwd_once(q, k, v, None, None, out, lse, do, nheads, causal,
                     scale, plan, interpret, None, q2, k2)


_flash2.defvjp(_flash2_fwd, _flash2_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    segment_ids=None, kv_segment_ids=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None, q2=None, k2=None):
    """Flash attention on (B, T, H, D) inputs → (B, T, H, Dv).

    T must be a multiple of 128 and D one of 64/128/256 (the dispatcher
    in :mod:`mxnet_tpu.ops.attention` guarantees this before routing
    here); a score may be wider than that by a SECOND pair of operands:
    ``q2`` (B, T, H, D2) and ``k2`` (B, T, H2, D2), D2 64 or 128, whose
    product is added to ``q k^T`` inside the tile before the softmax, so
    that the score is over D + D2 dimensions (128 + 64 = 192: latent
    attention's head product plus its rotary product) under the ONE
    ``scale`` (default ``1 / sqrt(D + D2)``).  ``k2`` may have fewer
    heads than ``q2`` (one rotary key head shared by all query heads): it
    is read through the index map at its own heads, never broadcast or
    padded in memory; ``dq2`` and ``dk2`` come from the backward kernel
    beside ``dq`` and ``dk``, ``dk2`` summed over the query heads of a
    share as grouped queries' ``dK`` is.  Such a call takes no window and no
    segment ids.  ``k`` and ``v`` may carry fewer heads than ``q`` (grouped
    queries): query head ``h`` reads K/V head ``h // (H / H_kv)`` through
    the kernels' index maps, nothing is repeated in memory, and dK/dV are
    summed over the query heads of a share after the backward kernel.
    ``v`` may be (B, T, H_v, Dv) with a head dim and a head count of its
    own (a differential head's values are two key heads wide and shared
    by the pair's two score matrices): the result then has ``Dv``.
    ``window`` (with ``causal``): query t sees keys s with
    ``0 <= t - s < window``, and the chunks older than that are never
    visited.  ``interpret`` defaults to True off-TPU so the same kernel is
    unit-testable on the CPU backend.  ``block_q`` / ``block_k`` (the
    block a grid step owns and the chunk its loop walks) are for tests:
    a call leaves them to :func:`tile_plan`.

    ``segment_ids`` (B, Tq) int enables SEQUENCE PACKING in-kernel:
    tokens attend only within their own segment; chunks whose q/k segment
    ranges cannot overlap are skipped (exact skip for the packed
    non-decreasing layout), so packed long-context training keeps the
    O(T) memory AND the sub-quadratic compute of the kernel.
    ``kv_segment_ids`` defaults to ``segment_ids``.  Degenerate rows with
    no matching key anywhere output zeros — as does the XLA reference
    path (``attention.py:_attention_ref`` zeroes fully-masked rows), so
    the two paths are comparable row-for-row.
    """
    b, tq, h, d = q.shape
    tk, h_kv = k.shape[1], k.shape[2]
    h_v, dv = v.shape[2], v.shape[3]
    if causal and tq != tk:
        raise ValueError("causal flash attention requires tq == tk "
                         f"(got {tq} vs {tk})")
    has_seg = segment_ids is not None
    window = None if window is None else int(window)
    d2 = h2 = 0
    if (q2 is None) != (k2 is None):
        raise ValueError("q2 and k2 come together")
    if q2 is not None:
        d2, h2 = q2.shape[3], k2.shape[2]
        if has_seg or window is not None:
            raise ValueError("a second pair of score operands takes no "
                             "window and no segment ids")
        if q2.shape[:3] != (b, tq, h) or k2.shape != (b, tk, h2, d2):
            raise ValueError(f"q2 {q2.shape} / k2 {k2.shape} do not go "
                             f"with q {q.shape} / k {k.shape}")
    scale = float(scale) if scale is not None else 1.0 / ((d + d2) ** 0.5)
    plan = tile_plan(tq, tk, d, q.dtype, causal, has_seg, heads=h,
                     kv_heads=h_kv, block_q=block_q, chunk=block_k,
                     window=window, dv=dv, v_heads=h_v, d2=d2,
                     k2_heads=h2 or None)
    _report_plan(plan, tq, tk, d, q.dtype, causal, has_seg, window, dv, d2,
                 h2)
    if interpret is None:
        interpret = _default_interpret(q)

    q_seg = kv_seg = None
    if has_seg:
        q_seg = jnp.asarray(segment_ids, jnp.int32)[:, None, :]  # (B,1,Tq)
        kv_seg = (jnp.asarray(kv_segment_ids, jnp.int32)[:, None, :]
                  if kv_segment_ids is not None else q_seg)
        if q_seg.shape != (b, 1, tq) or kv_seg.shape != (b, 1, tk):
            raise ValueError(
                f"segment_ids must be (B, Tq)=({b}, {tq}) / "
                f"(B, Tk)=({b}, {tk}); got {segment_ids.shape}"
                + (f" / {kv_segment_ids.shape}"
                   if kv_segment_ids is not None else ""))
    elif kv_segment_ids is not None:
        raise ValueError("kv_segment_ids requires segment_ids")

    def flat(x, t):
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], t,
                                               x.shape[3])

    if q2 is not None:
        out = _flash2(flat(q, tq), flat(k, tk), flat(v, tk), flat(q2, tq),
                      flat(k2, tk), h, causal, scale, plan, bool(interpret))
    else:
        out = _flash(flat(q, tq), flat(k, tk), flat(v, tk), q_seg, kv_seg,
                     h, causal, scale, plan, bool(interpret), window)
    return out.reshape(b, h, tq, dv).transpose(0, 2, 1, 3)
