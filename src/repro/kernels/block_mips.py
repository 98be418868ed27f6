"""Pallas TPU kernel: fused block-sparse ProMIPS verification.

The two-phase runtime's old "batched" backend gathers the union of every
query's selected blocks into one dense (R, d) tile (`jnp.take`), scores it,
then rebuilds the sequential Condition-A semantics from a (B, R) score
matrix plus five same-shape boolean intermediates (DESIGN.md §10 has the
traffic math).  This kernel removes ALL of that: the grid walks the selected
blocks of ``x`` **in place** in the paged layout — a scalar-prefetched slot
list steers each grid step's DMA straight at one 4-KB page of ``x`` in HBM,
so no gathered tile and no (B, R) intermediates ever exist.  Per step it

  1. scores one page against the whole query batch (one small MXU matmul),
  2. emits that slot's per-query >=-threshold hit count (``cnt``),
  3. updates the carried per-query hit total ``h`` (VMEM scratch) — a block
     is *live* iff the query selected it and ``h < k`` (the exact
     sequential-scan Condition-A stop: "at least k rows scoring >=
     threshold in earlier blocks" <=> "running k-th best >= threshold"),
  4. accumulates the logical page / candidate counts for live blocks, and
  5. merges the page's live rows into a per-query streaming top-k via a
     rank-select (stable descending order, ties to the lower index — the
     same rule as `jax.lax.top_k` and `search_common.topk_merge`, so the
     streamed result is bit-identical to one global top-k).

Grid steps run in layout (ascending block) order, which both preserves the
sequential-scan semantics and matches the coalesced HBM read pattern the
iDistance layout was designed for.

Mosaic layout rules (DESIGN.md §10 "Mosaic layout"):

* A page is ``page_rows`` rows, and Mosaic only DMAs f32 row windows that
  start on a multiple of 8 rows. When ``page_rows % 8 == 0`` (d = 128) the
  x block is the page itself; otherwise (d = 300 gives 3-row pages) the
  kernel reads the ``_x_tiles(page_rows)`` aligned 8-row tiles that cover
  the page and masks every row outside it. The logical layout, and with it
  the paper's page count, is unchanged.
* Row validity travels as per-slot bit words in SMEM (scalar prefetch),
  because a (n_pad, 1) VMEM block of ``valid`` would be neither aligned
  nor small.
* ``sel`` / ``cnt`` are (B, NS) arrays walked in (B, 128)-lane blocks; step
  ``i`` reads / writes lane ``i % 128`` through a one-hot select.
* SMEM holds 1 MiB, so one ``pallas_call`` walks at most `MAX_SLOTS` slots;
  longer walks run as a chain of calls carrying the top-k. The carried hit
  total restarts from the top-k's >=-threshold count, which decides
  liveness exactly as the uninterrupted total would (every hit counted
  while a query was live was merged into its top-k).

The rank-select holds a (B, k + W)^2 comparison cube in VMEM (W = the x
window rows), so ``k`` is capped at `MAX_K` (= 128) — `ops.block_mips`
takes the jnp oracle beyond that and counts it in the
``kernels.block_mips_oracle`` counter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Streaming-top-k merge cube is (B, k+W, k+W) in VMEM; cap k so it stays
# inside the scoped VMEM limit (see ops.block_mips fallback).
MAX_K = 128
# Slots walked by one pallas_call: the slot list and the validity words are
# scalar-prefetched into SMEM (1 MiB on v5e), so longer walks are chained.
MAX_SLOTS = 32768
# sel / cnt lane block: step i owns lane i % _LANES of block i // _LANES.
_LANES = 128
_SUBLANES = 8


def _x_tiles(page_rows: int) -> int:
    """Aligned 8-row tiles that cover any ``page_rows``-row page (0 when the
    page is itself aligned and is fetched as one block)."""
    if page_rows % _SUBLANES == 0:
        return 0
    return (page_rows + 2 * _SUBLANES - 2) // _SUBLANES


def _rank_topk(comb_s, comb_r, k: int):
    """Stable descending top-k of ``comb_s`` (B, J) with ties to the lower
    index — bit-compatible with `jax.lax.top_k` — via a rank-select that
    needs no sort primitive (Mosaic-friendly: compares + one-hot sums)."""
    j = comb_s.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (j, j), 0)   # j' (compared-to)
    row = jax.lax.broadcasted_iota(jnp.int32, (j, j), 1)   # j  (ranked elem)
    gt = comb_s[:, :, None] < comb_s[:, None, :]           # s[j'] > s[j]
    tie = (comb_s[:, :, None] == comb_s[:, None, :]) & (col < row)[None]
    rank = jnp.sum((gt | tie).astype(jnp.int32), axis=2)   # (B, J), a perm
    slot = jax.lax.broadcasted_iota(jnp.int32, (j, k), 1)[None]
    hit = rank[:, :, None] == slot                          # (B, J, k)
    top_s = jnp.sum(jnp.where(hit, comb_s[:, :, None], 0.0), axis=1)
    top_r = jnp.sum(jnp.where(hit, comb_r[:, :, None], 0), axis=1)
    return top_s, top_r


def _kernel(slots_ref, vbits_ref, *refs, k: int, page_rows: int,
            n_tiles: int, n_words: int):
    x_refs = refs[:max(n_tiles, 1)]
    (q_ref, sel_ref, chalf_ref, inits_ref, initr_ref,
     tops_ref, topr_ref, cnt_ref, pages_ref, cand_ref,
     h_ref) = refs[max(n_tiles, 1):]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        tops_ref[...] = inits_ref[...]
        topr_ref[...] = initr_ref[...]
        h_ref[...] = jnp.sum(
            (inits_ref[...] >= chalf_ref[...]).astype(jnp.int32),
            axis=1, keepdims=True)
        pages_ref[...] = jnp.zeros_like(pages_ref)
        cand_ref[...] = jnp.zeros_like(cand_ref)

    start = slots_ref[i] * page_rows                       # first page row
    if n_tiles:                                            # aligned window
        base = (start // _SUBLANES) * _SUBLANES
        x = jnp.concatenate([r[...] for r in x_refs], axis=0)
    else:
        base = start
        x = x_refs[0][...]
    x = x.astype(jnp.float32)                              # (W, d)
    q = q_ref[...].astype(jnp.float32)                     # (B, d)
    scores = jax.lax.dot_general(                          # (B, W)
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    w = x.shape[0]
    rowid = base + jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    off = rowid - start                                    # row within page
    valid = jnp.zeros((1, w), jnp.bool_)
    for wd in range(n_words):                              # bit r of word
        word = vbits_ref[i * n_words + wd]                 # r//32 = page row r
        bit = (word >> jnp.clip(off - 32 * wd, 0, 31)) & 1
        valid = valid | ((off >= 32 * wd) & (off < 32 * wd + 32) & (bit > 0))

    lane = jax.lax.broadcasted_iota(jnp.int32, sel_ref.shape, 1)
    here = lane == i % sel_ref.shape[1]                    # this slot's lane
    sel = jnp.sum(jnp.where(here, sel_ref[...], 0), axis=1,
                  keepdims=True) > 0                       # (B, 1)
    h = h_ref[...]                                         # (B, 1)

    # Per-slot >=-threshold hit count (in SELECTED blocks; the carried h is
    # n0 + the running cumsum, so "h < k" is exactly ~done_before).
    ge = (scores >= chalf_ref[...]) & valid                # (B, W)
    cnt = jnp.sum(ge.astype(jnp.int32), axis=1,
                  keepdims=True) * sel.astype(jnp.int32)   # (B, 1)
    cnt_ref[...] = jnp.where(here, cnt, cnt_ref[...])

    live = sel & (h < k)                                   # (B, 1)
    pages_ref[...] += live.astype(jnp.int32)
    n_valid = jnp.sum(valid.astype(jnp.int32))
    cand_ref[...] += live.astype(jnp.int32) * n_valid
    h_ref[...] = h + cnt

    # Streaming top-k over this page's live rows.
    mask = valid & live                                    # (B, W)
    masked = jnp.where(mask, scores, -jnp.inf)
    rows = jnp.where(mask, rowid, -1)
    comb_s = jnp.concatenate([tops_ref[...], masked], axis=1)  # (B, k+W)
    comb_r = jnp.concatenate([topr_ref[...], rows], axis=1)
    top_s, top_r = _rank_topk(comb_s, comb_r, k)
    tops_ref[...] = top_s
    topr_ref[...] = top_r


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


def _valid_bits(valid, slots, page_rows: int):
    """Flat (NS * n_words,) int32 validity words of the walked pages: bit
    r % 32 of word r // 32 holds ``valid`` of page row r. Gathered per slot,
    so the cost is the walk's rows, not the corpus'."""
    rows = slots[:, None] * page_rows + jnp.arange(page_rows)[None, :]
    v = jnp.take(valid, rows).astype(jnp.int32)            # (NS, P)
    words = [jnp.sum(v[:, lo:lo + 32]
                     << jnp.arange(min(32, page_rows - lo), dtype=jnp.int32),
                     axis=1, dtype=jnp.int32)
             for lo in range(0, page_rows, 32)]
    return jnp.stack(words, axis=1).reshape(-1)


def _walk(x, q, slots, vbits, sel, init_s, init_r, c_half, *, k, page_rows,
          interpret):
    """One pallas_call over at most `MAX_SLOTS` slots (see `block_mips`)."""
    n_slots = slots.shape[0]
    b, d = q.shape
    lanes = min(n_slots, _LANES)
    n_tiles = _x_tiles(page_rows)
    n_words = vbits.shape[0] // n_slots
    if n_tiles:
        last = -(-x.shape[0] // _SUBLANES) - 1
        x_specs = [pl.BlockSpec(
            (_SUBLANES, d),
            lambda i, s, v, t=t: (jnp.minimum(
                s[i] * page_rows // _SUBLANES + t, last), 0))
            for t in range(n_tiles)]
    else:
        x_specs = [pl.BlockSpec((page_rows, d), lambda i, s, v: (s[i], 0))]
    whole = lambda i, s, v: (0, 0)                         # noqa: E731
    lane_blk = lambda i, s, v: (0, i // lanes)             # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_slots,),
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
        scratch_shapes=[pltpu.VMEM((b, 1), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, k=k, page_rows=page_rows,
                          n_tiles=n_tiles, n_words=n_words),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
            jax.ShapeDtypeStruct((b, n_slots), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        interpret=interpret,
    )(slots, vbits, *([x] * max(n_tiles, 1)), q, sel, c_half,
      init_s, init_r)


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
    b = q.shape[0]
    slots = slots.astype(jnp.int32)
    sel = sel.astype(jnp.int32)
    # A walk longer than one lane block pads to whole lane blocks (the pad
    # slots are unselected, so they touch nothing).
    ns_pad = n_slots if n_slots <= _LANES else -(-n_slots // _LANES) * _LANES
    slots = jnp.pad(slots, (0, ns_pad - n_slots))
    sel = jnp.pad(sel, ((0, 0), (0, ns_pad - n_slots)))
    c_half = c_half.astype(jnp.float32).reshape(-1, 1)
    top_s = init_scores.astype(jnp.float32)
    top_r = init_rows.astype(jnp.int32)
    cnts = []
    pages = cand = jnp.zeros((b, 1), jnp.int32)
    for lo in range(0, ns_pad, MAX_SLOTS):
        hi = min(lo + MAX_SLOTS, ns_pad)
        chunk = slots[lo:hi]
        top_s, top_r, cnt, p, c = _walk(
            x, q, chunk, _valid_bits(valid, chunk, page_rows),
            sel[:, lo:hi], top_s, top_r, c_half, k=k, page_rows=page_rows,
            interpret=interpret)
        cnts.append(cnt)
        pages, cand = pages + p, cand + c
    cnt = jnp.concatenate(cnts, axis=1)[:, :n_slots]
    return top_s, top_r, cnt, pages[:, 0], cand[:, 0]
