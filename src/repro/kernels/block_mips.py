"""Pallas TPU kernel: fused block-sparse ProMIPS verification.

The two-phase runtime's old "batched" backend gathers the union of every
query's selected blocks into one dense (R, d) tile (`jnp.take`), scores it,
then rebuilds the sequential Condition-A semantics from a (B, R) score
matrix plus five same-shape boolean intermediates (DESIGN.md §10 has the
traffic math).  This kernel removes ALL of that: the grid walks the selected
blocks of ``x`` **in place** in the paged layout — a scalar-prefetched slot
list steers each grid step's DMAs straight at P 4-KB pages of ``x`` in HBM,
so no gathered tile and no (B, R) intermediates ever exist.  Per step it

  1. scores the step's P pages against the whole query batch (one MXU
     matmul, (B, d) x (d, P·W) for W window rows per page),
  2. emits each slot's per-query >=-threshold hit count (``cnt``),
  3. decides each page's liveness from the carried per-query hit total
     ``h`` (VMEM scratch) plus an exclusive prefix of the step's counts: a
     block is *live* iff the query selected it and ``h_before < k`` (the
     exact sequential-scan Condition-A stop: "at least k rows scoring >=
     threshold in earlier blocks" <=> "running k-th best >= threshold"),
  4. accumulates the logical page / candidate counts for live blocks, and
  5. merges the step's live rows into a per-query streaming top-k by
     insertion: while some query's best remaining row beats its k-th entry
     strictly, that row goes in after every entry scoring >= it. Carried
     entries come before the step's, and the step's pages in slot order,
     rows ascending — the tie rule of `jax.lax.top_k` and
     `search_common.topk_merge`, so the streamed result is bit-identical to
     one global top-k. A step costs one pass per row inserted (at most k),
     and once the top-k has filled most steps insert none.

Grid steps run in layout (ascending block) order, which both preserves the
sequential-scan semantics and matches the coalesced HBM read pattern the
iDistance layout was designed for. Pallas double-buffers every window, so
the next step's pages load while this one computes.

P (`pages_per_step`) follows the shapes: the largest power of two up to
`MAX_PAGES` (and up to the slot count) whose double-buffered windows,
concatenated rows and (B, k + P·W) merge fit `VMEM_BUDGET`. Mosaic layout
rules (DESIGN.md §10 "Mosaic layout"):

* A page is ``page_rows`` rows, and Mosaic only DMAs f32 row windows that
  start on a multiple of 8 rows. When ``page_rows % 8 == 0`` (d = 128) a
  page's window is the page itself; otherwise (d = 300 gives 3-row pages)
  each page is read as the ``_x_tiles(page_rows)`` aligned 8-row tiles that
  cover it, one BlockSpec window each. ``x`` keeps its layout: a manual DMA
  from a (n, 300) f32 ``x`` is refused ("Slice shape along dimension 1 must
  be aligned to tiling (128)").
* Which of a page's W window rows are its own valid rows travels as 16-bit
  window words, one per slot and 16 window rows (`_window_words`),
  scalar-prefetched into SMEM with the slot list. A step gathers its P
  words into a (1, P) vector with a loop and spreads them over its columns
  with one exact one-hot matmul. (Row codes per window row, laid out per
  slot in HBM, padded there 8- to 128-fold.)
* A row the merge takes is carried as a code, -2 minus its column in the
  call's walk; `_walk` turns codes into rows of ``x`` after the call.
* ``sel`` / ``cnt`` stay (B, NS), walked in (B, 128)-lane blocks that hold
  128 / P steps each; a step reads and writes its P lanes through static
  slices (Mosaic slices lanes only at offsets it can prove multiples of
  128). No array the walk adds pads in HBM.
* One ``pallas_call`` walks as many slots as its slot list and window words
  fit `SMEM_BUDGET` (`_slots_per_call`); longer walks run as a chain of
  calls carrying the top-k. The carried hit total restarts from the
  top-k's >=-threshold count, which decides liveness exactly as the
  uninterrupted total would (every hit counted while a query was live was
  merged into its top-k).
* Everything a step does per page is traced once per page, and every
  compiled tile size traces it again: the kernel and its index maps are
  written in ``lax`` ops (`_where`), and P stops at `MAX_PAGES`.

``k`` is capped at `MAX_K` (= 128), the lanes of the merge's carried
top-k: `ops.block_mips` takes the jnp oracle beyond that and counts it in
the ``kernels.block_mips_oracle`` counter.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# The merge carries each query's top-k in one row of `_LANES` lanes; past
# this k, `ops.block_mips` takes the jnp oracle.
MAX_K = _LANES
# SMEM bytes one pallas_call's scalar-prefetched slot list and window words
# may fill (`_slots_per_call`): 32,768 slots at one word each, a quarter of
# v5e's 1 MiB SMEM. Longer walks are chained.
SMEM_BUDGET = 2**18
# VMEM one grid step may fill (`_step_vmem_bytes`): three quarters of the
# 16 MiB of VMEM Mosaic scopes to a v5e kernel by default, the rest left to
# Mosaic's own spills of the merge's temporaries.
VMEM_BUDGET = 12 * 2**20
# Pages per step at most. A v5e walks the dense Yahoo round (208,321 3-row
# pages, B = 64) in 42.2 ms at 32 and 39.8 ms at 64, while each page adds
# `_x_tiles` windows, each traced and lowered with its own index map for
# every compiled tile size.
MAX_PAGES = 32
_SUBLANES = 8


def _x_tiles(page_rows: int) -> int:
    """Aligned 8-row tiles that cover any ``page_rows``-row page (0 when the
    page is itself aligned and is fetched as one block)."""
    if page_rows % _SUBLANES == 0:
        return 0
    return (page_rows + 2 * _SUBLANES - 2) // _SUBLANES


def _window_rows(page_rows: int) -> int:
    """Rows of ``x`` one page's windows bring into VMEM (W)."""
    return _x_tiles(page_rows) * _SUBLANES or page_rows


def _step_vmem_bytes(p: int, b: int, d: int, k: int, page_rows: int) -> int:
    """VMEM of one P-page step: the double-buffered x windows and their
    concatenated copy, the (P·W, P) page one-hot and the transposed copy
    the row expansion reads, and about eight (B, k + P·W) f32 arrays of
    scores, masks and merge candidates."""
    def up(n, m):
        return -(-n // m) * m
    rows = p * _window_rows(page_rows)
    x = 3 * rows * up(d, _LANES) * 4
    seg = 2 * rows * up(p, _LANES) * 4
    work = 8 * up(b, _SUBLANES) * up(rows + k, _LANES) * 4
    return x + seg + work


def pages_per_step(n_slots: int, b: int, d: int, k: int,
                   page_rows: int) -> int:
    """P: the largest power of two <= `MAX_PAGES`, <= ``n_slots`` and <= one
    call's slots whose step fits `VMEM_BUDGET` (1 at least)."""
    p = 1
    while (2 * p <= min(MAX_PAGES, _slots_per_call(page_rows), n_slots)
           and _step_vmem_bytes(2 * p, b, d, k, page_rows) <= VMEM_BUDGET):
        p *= 2
    return p


def _n_words(page_rows: int) -> int:
    """16-bit window words per slot."""
    return -(-_window_rows(page_rows) // 16)


def _slots_per_call(page_rows: int) -> int:
    """Slots one pallas_call walks: its slot list and window words fit
    `SMEM_BUDGET`; a power of two, so a multiple of any step and lane
    block."""
    fit = SMEM_BUDGET // (4 * (1 + _n_words(page_rows)))
    return 1 << (fit.bit_length() - 1)


def _padded_slots(n_slots: int, n_pages: int) -> int:
    """The walk pads its slot list to whole steps, and past one lane block
    to whole lane blocks, with unselected slots, which touch nothing."""
    n = -(-n_slots // n_pages) * n_pages
    return n if n <= _LANES else -(-n // _LANES) * _LANES


def walk_steps(n_slots: int, b: int, d: int, k: int, page_rows: int) -> int:
    """Grid steps `block_mips` takes over ``n_slots`` slots, summed over its
    chained calls."""
    n_pages = pages_per_step(n_slots, b, d, k, page_rows)
    return _padded_slots(n_slots, n_pages) // n_pages


def _where(c, a, b):
    """`jnp.where` as one `lax.select`. jnp's ``where``, ``//`` and ``%``
    are nested jits, which Mosaic lowers one function each: at P pages a
    step the kernel would lower hundreds, seconds per compiled tile size."""
    shape = jnp.broadcast_shapes(jnp.shape(c), jnp.shape(a), jnp.shape(b))
    dt = jnp.result_type(a, b)
    return jax.lax.select(jnp.broadcast_to(c, shape),
                          jnp.broadcast_to(jnp.asarray(a, dt), shape),
                          jnp.broadcast_to(jnp.asarray(b, dt), shape))


def _kernel(slots_ref, words_ref, *refs, k: int, page_rows: int,
            n_pages: int, n_win: int):
    x_refs = refs[:n_pages * n_win]
    (q_ref, sel_ref, chalf_ref, inits_ref, initr_ref,
     tops_ref, topr_ref, cnt_ref, pages_ref, cand_ref,
     h_ref, seg_ref) = refs[n_pages * n_win:]
    i = pl.program_id(0)
    n_slots = slots_ref.shape[0]
    # sel / cnt travel in (B, 128)-lane blocks, several steps to a block;
    # Mosaic slices lanes only at static offsets
    n_off = sel_ref.shape[1] // n_pages
    off = jax.lax.rem(i, n_off)
    exact = jax.lax.Precision.HIGHEST                      # counts, one-hots

    w = _window_rows(page_rows)
    pw = n_pages * w

    @pl.when(i == 0)
    def _init():
        seg_ref[...] = (                                   # column -> page
            jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (pw, n_pages), 0),
                        w)
            == jax.lax.broadcasted_iota(jnp.int32, (pw, n_pages), 1)
        ).astype(jnp.float32)
        tops_ref[...] = inits_ref[...]
        topr_ref[...] = initr_ref[...]
        h_ref[...] = jnp.sum(
            (inits_ref[...] >= chalf_ref[...]).astype(jnp.int32),
            axis=1, keepdims=True)
        pages_ref[...] = jnp.zeros_like(pages_ref)
        cand_ref[...] = jnp.zeros_like(cand_ref)

    x = jnp.concatenate([r[...] for r in x_refs], axis=0)  # (P·W, d)
    q = q_ref[...].astype(jnp.float32)                     # (B, d)
    scores = jax.lax.dot_general(                          # (B, P·W)
        q, x.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    # Validity of each column's row, 0 outside its page: the pages' 16-bit
    # window words, scalars in SMEM, gathered into a (1, P) vector by a loop
    # (traced once, whatever P) and spread over each page's W columns by an
    # exact one-hot matmul.
    seg = seg_ref[...]                                     # (P·W, P) one-hot
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_pages), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, pw), 1)
    within = jax.lax.rem(col, w)
    bits = jnp.zeros((1, pw), jnp.int32)
    for c in range(_n_words(page_rows)):
        def put(p, v, c=c):
            return _where(lane == p,
                          words_ref[c * n_slots + i * n_pages + p], v)
        word = jax.lax.fori_loop(0, n_pages, put,
                                 jnp.zeros((1, n_pages), jnp.int32))
        word = jax.lax.dot_general(                        # (1, P·W)
            word.astype(jnp.float32), seg, (((1,), (1,)), ((), ())),
            precision=exact, preferred_element_type=jnp.float32)
        bits = _where(jax.lax.div(within, 16) == c,
                      word.astype(jnp.int32) >> jax.lax.rem(within, 16), bits)
    valid = (bits & 1) > 0                                 # (1, P·W)
    sel = sum(_where(off == o, sel_ref[:, o * n_pages:(o + 1) * n_pages],
                     0) for o in range(n_off)) > 0         # (B, P)

    # Per-slot >=-threshold hit counts (in SELECTED blocks), then the
    # sequential stop within the step: page p is live iff the hits before
    # it — the carried h plus this step's pages p' < p — are still < k.
    ge = ((scores >= chalf_ref[...]) & valid).astype(jnp.float32)
    cnt = _where(sel, jnp.dot(ge, seg, precision=exact,
                              preferred_element_type=jnp.float32), 0.0)
    for o in range(n_off):
        @pl.when(off == o)
        def _store():
            cnt_ref[:, o * n_pages:(o + 1) * n_pages] = cnt.astype(jnp.int32)

    earlier = (jax.lax.broadcasted_iota(jnp.int32, (n_pages, n_pages), 0)
               < jax.lax.broadcasted_iota(jnp.int32, (n_pages, n_pages), 1))
    h = h_ref[...]                                         # (B, 1)
    before = h + jnp.dot(cnt, earlier.astype(jnp.float32), precision=exact,
                         preferred_element_type=jnp.float32).astype(jnp.int32)
    live = sel & (before < k)                              # (B, P)
    pages_ref[...] += jnp.sum(live.astype(jnp.int32), axis=1, keepdims=True)
    h_ref[...] = h + jnp.sum(cnt.astype(jnp.int32), axis=1, keepdims=True)
    live_rows = jax.lax.dot_general(                       # (B, P·W)
        live.astype(jnp.float32), seg, (((1,), (1,)), ((), ())),
        precision=exact, preferred_element_type=jnp.float32) > 0.5
    mask = live_rows & valid
    cand_ref[...] += jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)

    # Streaming top-k by insertion: while some query's best remaining live
    # row beats its k-th entry strictly, insert that row after every entry
    # scoring >= it (carried entries and rows taken earlier hold lower
    # positions) and drop the k-th. The lowest column wins ties among the
    # step's rows: slot order, rows ascending. A row taken here is stored as
    # the code -2 - (its column in the call's walk), which `_walk` turns
    # into the row of x after the call.
    b = scores.shape[0]
    rank = jax.lax.broadcasted_iota(jnp.int32, (b, _LANES), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    cand = _where(mask, scores, -jnp.inf)

    def pad(top, empty):                                   # (B, k) -> lanes
        if k == _LANES:
            return top
        return jnp.concatenate(
            [top, jnp.full((b, _LANES - k), empty, top.dtype)], axis=1)

    def beats(top_s, best):
        return best > jnp.min(_where(rank < k, top_s, jnp.inf), axis=1,
                              keepdims=True)

    def more(carry):
        top_s, _, _, best, n = carry
        return (n < k) & jnp.any(beats(top_s, best))

    def insert(carry):
        top_s, top_r, cand, best, n = carry
        pos = jnp.min(_where(cand == best, col, scores.shape[1]), axis=1,
                      keepdims=True)
        hit = col == pos
        row = -2 - (i * pw + pos)
        at = jnp.sum((top_s >= best).astype(jnp.int32), axis=1, keepdims=True)
        ins = beats(top_s, best)
        new_s = _where(rank < at, top_s, _where(
            rank == at, best, pltpu.roll(top_s, 1, 1)))
        new_r = _where(rank < at, top_r, _where(
            rank == at, row, pltpu.roll(top_r, 1, 1)))
        cand = _where(hit, -jnp.inf, cand)
        return (_where(ins, new_s, top_s), _where(ins, new_r, top_r),
                cand, jnp.max(cand, axis=1, keepdims=True), n + 1)

    top_s, top_r, _, _, _ = jax.lax.while_loop(more, insert, (
        pad(tops_ref[...], -jnp.inf), pad(topr_ref[...], -1), cand,
        jnp.max(cand, axis=1, keepdims=True), 0))
    tops_ref[...] = top_s[:, :k]
    topr_ref[...] = top_r[:, :k]


# Sketch-scoring grid walks the code table this many blocks per step; the
# per-step working set (codes tile + LUT + one-hot expansion) stays well
# inside VMEM at B = 64, K = 256.
SKETCH_TILE = 512


def _sketch_kernel(codes_ref, lut_ref, est_ref, *, n_codewords: int):
    """One grid step of asymmetric LUT scoring: est[b, t] = sum_s
    lut[b, s, codes[t, s]]. The gather is expressed as a one-hot matmul so
    it lowers to MXU dot_generals (Mosaic has no vector-gather primitive)."""
    codes = codes_ref[...]                                 # (T, M)
    lut = lut_ref[...]                                     # (B, M, K)
    t, m = codes.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (t, n_codewords), 1)
    est = jnp.zeros((lut.shape[0], t), jnp.float32)
    for s in range(m):
        onehot = (codes[:, s][:, None] == iota).astype(jnp.float32)  # (T, K)
        est = est + jax.lax.dot_general(                   # (B, T)
            lut[:, s, :], onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    est_ref[...] = est


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def sketch_scores(
    q: jax.Array,
    codebooks: jax.Array,
    codes: jax.Array,
    *,
    interpret: bool = False,
    tile: int = SKETCH_TILE,
):
    """Estimated block scores from the VMEM-resident PQ sketch.

    q: (B, d); codebooks: (M, K, d/M); codes: (NB, M) int — returns
    (B, NB) float32 est with est[b, n] = <q_b, decode(codes[n])>, computed
    asymmetrically: a per-query LUT of subspace dot products
    (lut[b, s, k] = <q_b[s], codebook[s, k]>) built once outside the grid,
    then accumulated per code. Numerically this sums the same subspace
    products as `ref.sketch_scores_ref`'s decoded-centroid GEMM in a
    different order — parity holds to float tolerance, not bitwise.
    """
    b, d = q.shape
    m, kcb, sub_d = codebooks.shape
    assert d == m * sub_d, (d, m, sub_d)
    nb = codes.shape[0]
    lut = jnp.einsum("bms,mks->bmk", q.reshape(b, m, sub_d).astype(jnp.float32),
                     codebooks.astype(jnp.float32))        # (B, M, K)
    nb_pad = -(-nb // tile) * tile
    codes_p = jnp.pad(codes.astype(jnp.int32), ((0, nb_pad - nb), (0, 0)))
    est = pl.pallas_call(
        functools.partial(_sketch_kernel, n_codewords=kcb),
        grid=(nb_pad // tile,),
        in_specs=[
            pl.BlockSpec((tile, m), lambda i: (i, 0)),
            pl.BlockSpec((b, m, kcb), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((b, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, nb_pad), jnp.float32),
        interpret=interpret,
    )(codes_p, lut)
    return est[:, :nb]


def _window_words(valid, page_rows: int) -> list:
    """n_words (NB,) int32 arrays: per block, the validity of the W rows its
    windows bring in, 16 bits a word (bit j % 16 of word j // 16 holds
    window row j; rows outside the page read 0). One exact matmul of
    ``valid``, laid out whole blocks to a lane-aligned row, against powers
    of two: a gather or a strided slice costs a TPU ~8 ns an element, and
    an (NB, page_rows) array pads 128 / page_rows-fold and compiles slowly
    at NB = 208k. Whole rows of blocks start on a multiple of 8 rows, so a
    block's window offset depends on its place in the row alone."""
    lanes = math.lcm(page_rows, _LANES)
    lanes *= max(1, 1024 // lanes)            # blocks per row: lane-dense
    per_row = lanes // page_rows
    n_words = _n_words(page_rows)
    # powers[lane, c·per_row + blk] = 2^(j % 16) for lane = blk·page_rows + r,
    # window row j = r + the block's window offset, c = j // 16: each lane's
    # column and power are (lanes,) NumPy constants, spread by one compare.
    # A stored (lanes, per_row) matrix is kept in HBM with every compiled
    # program (one per tile size), and integer div / rem over it compile to
    # ~150 KB of code in each.
    blk, r = np.divmod(np.arange(lanes), page_rows)
    j = r + (blk * page_rows % _SUBLANES if _x_tiles(page_rows) else 0)
    col = jnp.asarray((j // 16) * per_row + blk, jnp.int32)[:, None]
    powers = jnp.where(
        col == jax.lax.broadcasted_iota(jnp.int32, (lanes, n_words * per_row),
                                        1),
        jnp.asarray(2.0 ** (j % 16), jnp.bfloat16)[:, None], 0.0
    ).astype(jnp.bfloat16)                                 # powers of two
    v = valid.astype(jnp.bfloat16)                         # 0 / 1: exact
    v = jnp.pad(v, (0, -v.shape[0] % lanes)).reshape(-1, lanes)
    words = jnp.dot(v, powers,
                    preferred_element_type=jnp.float32).astype(jnp.int32)
    return [words[:, c * per_row:(c + 1) * per_row].reshape(-1)
            for c in range(n_words)]


def _walk(x, q, slots, sel, init_s, init_r, c_half, window_words, *, k,
          page_rows, n_pages, interpret):
    """One pallas_call over at most `_slots_per_call` slots, ``n_pages`` per
    grid step (see `block_mips`)."""
    n_slots = slots.shape[0]
    n_steps = n_slots // n_pages
    lanes = min(n_slots, _LANES)
    b, d = q.shape
    n_tiles = _x_tiles(page_rows)
    w = _window_rows(page_rows)
    pw = n_pages * w
    # One index map per window, written in lax: P·n_tiles of them are traced
    # for every compiled tile size, and jnp's operators trace slower.
    lax = jax.lax
    last = -(-x.shape[0] // _SUBLANES) - 1

    def x_map(i, s, v, p, t):
        slot = s[lax.add(lax.mul(i, n_pages), p)]
        if not n_tiles:
            return slot, 0
        tile = lax.add(lax.div(lax.mul(slot, page_rows), _SUBLANES), t)
        return lax.min(tile, last), 0

    x_specs = [pl.BlockSpec((_SUBLANES if n_tiles else page_rows, d),
                            functools.partial(x_map, p=p, t=t))
               for p in range(n_pages) for t in range(max(n_tiles, 1))]
    words = jnp.concatenate([jnp.take(ww, slots) for ww in window_words])
    whole = lambda i, s, v: (0, 0)                         # noqa: E731
    lane_blk = lambda i, s, v: (0, jax.lax.div(i, lanes // n_pages))  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_steps,),
        in_specs=x_specs + [
            pl.BlockSpec((b, d), whole),
            pl.BlockSpec((b, lanes), lane_blk),
            pl.BlockSpec((b, 1), whole),
            pl.BlockSpec((b, k), whole),
            pl.BlockSpec((b, k), whole),
        ],
        out_specs=[
            pl.BlockSpec((b, k), whole),
            pl.BlockSpec((b, k), whole),
            pl.BlockSpec((b, lanes), lane_blk),
            pl.BlockSpec((b, 1), whole),
            pl.BlockSpec((b, 1), whole),
        ],
        scratch_shapes=[pltpu.VMEM((b, 1), jnp.int32),
                        pltpu.VMEM((pw, n_pages), jnp.float32)],
    )
    top_s, top_r, cnt, pages, cand = pl.pallas_call(
        functools.partial(_kernel, k=k, page_rows=page_rows,
                          n_pages=n_pages, n_win=max(n_tiles, 1)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
            jax.ShapeDtypeStruct((b, n_slots), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        interpret=interpret,
    )(slots, words, *([x] * len(x_specs)), q, sel, c_half, init_s, init_r)
    # rows taken in this call come back as -2 - (column in the walk)
    code = -2 - top_r
    start = jnp.take(slots, jnp.clip(code // w, 0, n_slots - 1)) * page_rows
    if n_tiles:
        start -= start % _SUBLANES
    top_r = jnp.where(code >= 0, start + code % w, top_r)
    return top_s, top_r, cnt, pages, cand


@functools.partial(jax.jit, static_argnames=("k", "page_rows", "interpret"))
def block_mips(
    x: jax.Array,
    valid: jax.Array,
    q: jax.Array,
    slots: jax.Array,
    sel: jax.Array,
    init_scores: jax.Array,
    init_rows: jax.Array,
    c_half: jax.Array,
    *,
    k: int,
    page_rows: int,
    interpret: bool = False,
):
    """Fused block-sparse verification round over the paged layout.

    x: (n_pad, d) rows in paged layout; valid: (n_pad,) bool/int (id >= 0);
    q: (B, d); slots: (NS,) int32 block ids to walk, ascending layout order
    (padding slots allowed — their ``sel`` column must be False);
    sel: (B, NS) per-query selection; init_scores/init_rows: (B, k) carried
    top-k, descending (-inf / -1 empties); c_half: (B,) Condition-A
    thresholds.

    Returns (top_scores (B, k), top_rows (B, k) i32, cnt (B, NS) i32,
    pages (B,) i32, cand (B,) i32).  Semantics are exactly one
    `search_device._verify_batched` round restricted to ``slots`` — the
    parity contract `ref.block_mips_ref` pins down.
    """
    assert k <= MAX_K, f"block_mips supports k <= {MAX_K}, got {k}"
    n_slots = slots.shape[0]
    b, d = q.shape
    n_pages = pages_per_step(n_slots, b, d, k, page_rows)
    ns_pad = _padded_slots(n_slots, n_pages)
    slots = jnp.pad(slots.astype(jnp.int32), (0, ns_pad - n_slots))
    sel = jnp.pad(sel.astype(jnp.int32), ((0, 0), (0, ns_pad - n_slots)))
    c_half = c_half.astype(jnp.float32).reshape(-1, 1)
    window_words = _window_words(valid, page_rows)
    chunk = _slots_per_call(page_rows)
    top_s = init_scores.astype(jnp.float32)
    top_r = init_rows.astype(jnp.int32)
    cnts = []
    pages = cand = jnp.zeros((b, 1), jnp.int32)
    for lo in range(0, ns_pad, chunk):
        hi = min(lo + chunk, ns_pad)
        top_s, top_r, cnt, p, c = _walk(
            x, q, slots[lo:hi], sel[:, lo:hi], top_s, top_r, c_half,
            window_words, k=k, page_rows=page_rows, n_pages=n_pages,
            interpret=interpret)
        cnts.append(cnt)
        pages, cand = pages + p, cand + c
    cnt = jnp.concatenate(cnts, axis=1)
    return top_s, top_r, cnt[:, :n_slots], pages[:, 0], cand[:, 0]
