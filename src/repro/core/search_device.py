"""Device-mode ProMIPS search: jit-compiled, batched, fixed-budget.

Implements MIP-Search-II (Algorithm 3) with the block-granular TPU
adaptation (DESIGN.md §3):

  quick-probe -> radius r -> sub-partition sphere filter -> block selection
  -> candidate verification -> Condition B test -> compensation round with
     radius r' over the blocks NOT already scanned (the r'-selection strictly
     contains the r-selection, so scanning the difference reproduces
     Algorithm 3's "extend the range").

All condition/radius arithmetic is imported from `search_common` (the
backend-neutral core shared with `HostSearcher`). Selection (Quick-Probe,
Condition-A thresholds, sphere filter, Condition-B compensation masks) is
BATCH-NATIVE and shared by every verification backend — `select_frontend` /
`compensation_masks` below — so the per-round block masks agree across
backends by construction. Verification backends:

``verification="fused"`` (default; DESIGN.md §10/§12) — rounds over the
  fused block-sparse `kernels/block_mips` kernel: the kernel walks the
  selected pages of ``arrays.x`` in place (scalar-prefetched slot list, no
  gathered union tile) with a streaming per-query top-k, and the tile is
  sized to ``next_pow2(union)`` blocks instead of always the full budget.
  Two drivers, bit-identical to each other and to "batched" at EVERY
  budget (the tile cap rule is the same): `core/search_fused.py`
  host-orchestrates the rounds when called eagerly (tiles sized on host,
  O(log NB) jit cache); `core/search_graph.py` is the fully traceable
  driver — pow2 tile buckets precompiled as `lax.switch` branches — that
  THIS function dispatches to, so jit'd callers and `sharded_search`'s
  shard_map run the fused kernel at every scale.

``verification="batched"`` (DESIGN.md §3.2) — the single-graph two-phase
  runtime. Per round, the blocks selected by ANY query in the batch are
  unioned, their rows gathered into one (R, d) tile, and ALL queries are
  scored against the tile in a single `kernels/ops.mips_score` call (Pallas
  on TPU; its jnp oracle off-TPU — interpret mode is a correctness vehicle,
  opt in with use_pallas=True) — one MXU matmul instead of B x budget
  sequential matvecs. The sequential Condition-A semantics are then
  reconstructed EXACTLY from the precomputed scores: "running k-th best
  >= threshold after block t" is equivalent to "at least k rows scoring
  >= threshold in blocks <= t", so at the default full budget the per-query
  stop block, logical page count, candidate count and final top-k are
  bit-identical to the scan backend (the parity test in
  tests/test_search_runtime.py asserts this). With a FINITE budget the two
  backends budget differently: "scan" caps each query's own selection at
  ``budget`` blocks in layout order, "batched" caps the union tile shared
  by the whole batch, keeping the ``budget`` most PROMISING union blocks
  (`truncate_union` on `block_priority`'s projected-IP upper bound — the
  lever the serve degradation ladder pulls, DESIGN.md §16) — queries whose
  selection
  does not fit are flagged ``exhausted``.

``verification="scan"`` — the legacy per-query `lax.scan` of per-block
  matvecs, kept as the semantics reference and for the benchmark baseline.

Shapes are static: `budget` blocks per round. Work for logically-unneeded
blocks is masked rather than skipped (fixed-shape SPMD); `stats.pages`
reports the *logical* page accesses — the number the paper's Fig. 7 counts —
and is what the benchmark harness records.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..kernels import ops
from . import search_common as sc
from .index import IndexArrays, IndexMeta
from .quick_probe import GroupTable, quick_probe_batch


class SearchStats(NamedTuple):
    pages: jnp.ndarray          # logical data-page accesses per query
    candidates: jnp.ndarray     # verified candidate rows per query
    probe_passed: jnp.ndarray   # Quick-Probe Test A hit (bool)
    used_round2: jnp.ndarray    # compensation round triggered (bool)
    radius0: jnp.ndarray        # Quick-Probe radius
    radius1: jnp.ndarray        # compensation radius (0 if unused)
    exhausted: jnp.ndarray      # budget ran out before Condition B held
    rows: jnp.ndarray           # top-k rows in the padded sorted layout (-1 =
                                # empty); lets the runtime rescore candidates
                                # through one shared kernel call

    def to_dict(self) -> dict:
        """Normalized accounting (`core/stats.stats_totals` contract)."""
        from .stats import stats_totals
        return stats_totals(self.pages, self.candidates, self.exhausted)


class TopK(NamedTuple):
    scores: jnp.ndarray  # (k,) descending inner products
    rows: jnp.ndarray    # (k,) rows in the sorted layout (-1 = empty)


def _group_table(arrays: IndexArrays) -> GroupTable:
    return GroupTable(
        code=arrays.g_code,
        min_l1=arrays.g_min_l1,
        rep_proj=arrays.g_rep_proj,
        rep_row=arrays.g_rep_row,
        count=arrays.g_count,
    )


def subpart_distances(arrays: IndexArrays, q_proj):
    """(B, S) projected query -> sub-partition center distances.

    One matmul via the expansion ||c - q||^2 = ||c||^2 - 2 <c, q> + ||q||^2
    (clamped at 0 against cancellation) instead of a (B, S, m) difference
    tensor. Computed ONCE per search and reused by both selection rounds —
    only the radii change between rounds.
    """
    center = arrays.sp_center                                  # (S, m)
    d2 = (jnp.sum(center * center, axis=-1)[None, :]
          - 2.0 * (q_proj @ center.T)
          + jnp.sum(q_proj * q_proj, axis=-1)[:, None])        # (B, S)
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def blocks_from_radii(arrays: IndexArrays, d_sp, radius):
    """Batch-native sphere-overlap filter: sub-partitions -> fixed blocks.

    d_sp: (B, S) from `subpart_distances`. ``radius`` may be (B,)
    (paper-faithful, one radius per query) or (B, S) per-sub-partition radii
    (beyond-paper norm-adaptive mode — see `search_common.adaptive_radii`).
    Entries < 0 deselect the sub-partition outright (Cauchy-Schwarz
    pruning). Returns (B, NB) bool.

    The sub-partition -> block mapping is a gather over the precomputed
    ``block_sp_idx`` (NB, KMAX) table (a block is touched iff ANY of its
    sub-partitions is selected) — equivalent to the old per-query cumsum
    over sp ranges, but O(NB * KMAX) instead of an XLA scan over S. Every
    verification backend (fused / batched / scan) goes through this one
    function, so block selections agree across backends by construction.

    The gather runs one table column at a time: a single (B, NB, KMAX)
    gather takes the TPU compiler minutes at NB = 208k (Yahoo! Music),
    while KMAX one-column gathers compile in about a second.
    """
    if radius.ndim == 1:
        radius = radius[:, None]
    sel_sp = sc.sphere_select(d_sp, arrays.sp_radius[None, :], radius)
    out = jnp.zeros((sel_sp.shape[0], arrays.block_sp_idx.shape[0]), bool)
    for j in range(arrays.block_sp_idx.shape[1]):              # KMAX, static
        sp = arrays.block_sp_idx[:, j]
        out = out | (jnp.take(sel_sp, jnp.maximum(sp, 0), axis=1)
                     & (sp >= 0)[None, :])
    return out


def select_blocks_batch(arrays: IndexArrays, q_proj, radius):
    """`subpart_distances` + `blocks_from_radii` in one call (standalone
    callers; the search paths reuse the distances across rounds)."""
    return blocks_from_radii(arrays, subpart_distances(arrays, q_proj), radius)


def block_priority(arrays: IndexArrays, q_proj):
    """Best-first key for budget truncation: per block, the NEGATED upper
    bound on any batch query's projected inner product with any point in
    the block's sub-partition balls — ``max_b(q_proj . center + |q_proj| *
    radius)`` by Cauchy-Schwarz, maximized over the block's sub-partitions.
    Ascending = more promising.

    Norm-awareness is the whole point: with norm-strata layouts the
    MIPS-dominating high-norm blocks sit at the END of the layout and are
    often FAR from the query in projection space, so both layout order and
    the ball-gap distance (the progressive driver's key, which re-tests
    every block against an adaptive radius and so can afford it) rank them
    last — a truncating budget would shed exactly the blocks that matter.
    Clamped finite so sub-partition-less blocks still rank strictly ahead
    of non-union blocks in `truncate_union`.
    """
    q_norm = jnp.sqrt(jnp.sum(q_proj * q_proj, axis=1))            # (B,)
    ub = (q_proj @ arrays.sp_center.T
          + q_norm[:, None] * arrays.sp_radius[None, :])           # (B, S)
    ub = jnp.max(ub, axis=0)                                       # (S,)
    best = jnp.full(arrays.block_sp_idx.shape[:1], -jnp.inf)       # (NB,)
    for j in range(arrays.block_sp_idx.shape[1]):   # per column: see
        sp = arrays.block_sp_idx[:, j]              # `blocks_from_radii`
        best = jnp.maximum(best, jnp.where(sp >= 0, ub[jnp.maximum(sp, 0)],
                                           -jnp.inf))
    return jnp.minimum(-best, jnp.float32(1e30))


def truncate_union(union, prio, cap: int):
    """Blocks surviving a ``cap``-slot verification tile.

    With ``prio=None`` (full budget — no ranking computed) the union is
    returned unchanged, preserving the historical semantics bit-for-bit.
    With a priority vector, the ``cap`` BEST union blocks survive (ties by
    layout index via the stable sort) instead of the first ``cap`` in
    layout order — a finite budget then sheds the least promising blocks,
    which is what makes it a quality ladder (DESIGN.md §16) rather than an
    arbitrary cut. Callers still lay the surviving set out in layout order,
    so the Condition-A sequential-scan reconstruction is untouched.
    """
    if prio is None:
        return union
    key = jnp.where(union, prio, jnp.inf)
    best = jnp.argsort(key, stable=True)[:cap]
    return jnp.zeros(union.shape[0], bool).at[best].set(True) & union


def adaptive_radii(arrays: IndexArrays, meta: IndexMeta, s_k, q_l2sq, cs_prune: bool):
    """Per-sub-partition norm-adaptive radii (delegates to `search_common`)."""
    return sc.adaptive_radii(arrays.sp_max_l2sq, s_k, q_l2sq, meta.c, meta.x_p,
                             cs_prune=cs_prune, xp=jnp)


# ---------------------------------------------------------------------------
# Batch-native selection frontend (shared by fused / batched / scan)
# ---------------------------------------------------------------------------

def select_frontend(arrays: IndexArrays, meta: IndexMeta, queries):
    """Phase 1 of the two-phase runtime for a whole (B, d) batch at once:
    projection, batched Quick-Probe, Condition-A thresholds and the round-1
    block selection — no per-query `vmap` anywhere.

    Returns (q_proj (B, m), q_l2sq (B,), d_sp (B, S), r0 (B,), probe_ok (B,),
    c_half (B,), mask0 (B, NB)); ``d_sp`` is reused by the compensation
    round so the center-distance matmul runs once per search.

    The `jax.named_scope` labels cost nothing at runtime; they tag the HLO
    so these phases are identifiable in XLA profiles / `jax.profiler.trace`
    captures even for the fully-traced drivers (DESIGN.md §14).
    """
    with jax.named_scope("select_frontend"):
        q_proj = queries @ arrays.a
        q_l1 = jnp.sum(jnp.abs(queries), axis=1)
        q_l2sq = jnp.sum(queries * queries, axis=1)
        with jax.named_scope("quick_probe_batch"):
            _, r0, probe_ok = quick_probe_batch(_group_table(arrays), q_proj,
                                                q_l1, meta.c, meta.x_p)
        c_half = sc.condition_a_threshold(arrays.max_l2sq, q_l2sq, meta.c)
        d_sp = subpart_distances(arrays, q_proj)
        mask0 = blocks_from_radii(arrays, d_sp, r0)
    return q_proj, q_l2sq, d_sp, r0, probe_ok, c_half, mask0


def compensation_masks(arrays: IndexArrays, meta: IndexMeta, d_sp, q_l2sq,
                       s_k, r0, done_a, mask0, norm_adaptive: bool,
                       cs_prune: bool):
    """Condition-B test + compensation-round selection (Algorithm 3 line 12)
    for the whole batch. ``d_sp`` is the frontend's (B, S) center-distance
    matrix. Returns (need2 (B,), r1 (B,), mask1 (B, NB)) with ``mask1``
    already restricted to blocks NOT scanned in round 1.
    """
    with jax.named_scope("compensation_masks"):
        cond_b = sc.condition_b(r0 * r0, s_k, arrays.max_l2sq, q_l2sq,
                                meta.c, meta.x_p, xp=jnp)
        r1 = sc.compensation_radius(s_k, arrays.max_l2sq, q_l2sq,
                                    meta.c, meta.x_p, xp=jnp)
        need2 = ~(cond_b | done_a)
        if norm_adaptive:
            r_comp = sc.adaptive_radii(arrays.sp_max_l2sq[None, :],
                                       s_k[:, None], q_l2sq[:, None], meta.c,
                                       meta.x_p, cs_prune=cs_prune,
                                       xp=jnp)                    # (B, S)
            r_comp = jnp.where(need2[:, None], r_comp, -1.0)
        else:
            r_comp = jnp.where(need2, r1, -1.0)[:, None]          # (B, 1)
        mask1 = blocks_from_radii(arrays, d_sp, r_comp) & ~mask0
    return need2, r1, mask1


def prefilter_round1(arrays: IndexArrays, queries, mask0, k: int,
                     page_rows: int, eps: float,
                     use_pallas: Optional[bool]):
    """Quantized-sketch prefilter, round 1 (DESIGN.md §13): score the block
    sketch for EVERY candidate block before any page is fetched and keep
    only blocks whose upper bound clears the group-max tau. Returns
    (surv (B, NB), est, bnd, bvalid) — est/bnd/bvalid are carried to
    `prefilter_round2` so the compensation round reuses the one sketch
    evaluation. Shared by every backend (host fused driver jit-wraps it,
    the in-graph driver and batched/scan paths call it in-trace), which is
    what keeps all of them bit-identical with the prefilter on."""
    with jax.named_scope("prefilter_round1"):
        est = ops.sketch_scores(queries, arrays.sk_mu, arrays.sk_codebooks,
                                arrays.sk_codes, use_pallas=use_pallas)
        bnd = sc.sketch_margin(queries, arrays.sk_err, eps)
        bvalid = sc.block_valid_from_ids(arrays.ids, page_rows)
        surv = sc.sketch_survivors_round1(mask0, est, bnd, bvalid, k)
    return surv, est, bnd, bvalid


def prefilter_round2(mask1, est, bnd, bvalid, s_k):
    """Compensation-round sketch pruning against the realized k-th score."""
    with jax.named_scope("prefilter_round2"):
        return sc.sketch_survivors_round2(mask1, est, bnd, bvalid, s_k)


def _merge_topk(top: TopK, scores, rows, k: int) -> TopK:
    s, r = sc.topk_merge(top.scores, top.rows, scores, rows, k, xp=jnp)
    return TopK(scores=s, rows=r)


# ---------------------------------------------------------------------------
# Batched two-phase verification (DESIGN.md §3.2)
# ---------------------------------------------------------------------------

def _verify_batched(arrays: IndexArrays, meta: IndexMeta, queries, block_masks,
                    tops: TopK, c_half, k: int, budget: int, use_pallas,
                    prio=None):
    """One verification round for the whole query batch.

    queries: (B, d); block_masks: (B, NB) per-query selected blocks;
    tops: carried-in running top-k, (B, k) leaves; c_half: (B,) Condition-A
    thresholds. Returns (tops', pages (B,), candidates (B,), done_a (B,),
    lost (B,)) with the exact sequential-scan semantics (see module
    docstring); ``lost`` flags queries whose selection did not fit the
    ``budget``-block union tile. ``prio`` (NB,), when given, decides WHICH
    union blocks survive a truncating budget (`truncate_union` — best
    blocks first instead of first-in-layout); the surviving set is still
    walked in layout order.
    """
    n_batch = queries.shape[0]
    page_rows = meta.page_rows
    n_blocks = arrays.block_sp_lo.shape[0]
    budget = min(budget, n_blocks)

    # Union tile: blocks selected by ANY query, in layout order (the
    # sequential-disk pattern the sub-partition layout is designed for).
    union = jnp.any(block_masks, axis=0)                      # (NB,)
    keep = truncate_union(union, prio, budget)
    order = jnp.argsort(~keep, stable=True)                   # kept first
    slots = order[:budget]                                    # (budget,)
    slot_valid = jnp.arange(budget) < jnp.sum(keep.astype(jnp.int32))
    in_tile = jnp.zeros(n_blocks, bool).at[slots].set(slot_valid)

    # Gather candidate rows once and score all queries in one kernel call.
    rows = (slots[:, None] * page_rows + jnp.arange(page_rows)[None, :]).reshape(-1)
    x_tile = jnp.take(arrays.x, rows, axis=0)                 # (R, d)
    ids_tile = jnp.take(arrays.ids, rows)                     # (R,)
    row_valid = (ids_tile >= 0) & jnp.repeat(slot_valid, page_rows)
    scores = ops.mips_score(x_tile, queries, row_valid,
                            use_pallas=use_pallas).T          # (B, R)

    # Reconstruct the sequential Condition-A stop block from the scores:
    # running k-th best >= c_half after block t  <=>  at least k rows
    # (including the carried-in top) score >= c_half within blocks <= t.
    sel_slots = block_masks[:, slots] & slot_valid[None, :]   # (B, budget)
    row_sel = jnp.repeat(sel_slots, page_rows, axis=1)        # (B, R)
    ge = (scores >= c_half[:, None]) & row_sel & row_valid[None, :]
    cnt = ge.reshape(n_batch, budget, page_rows).sum(axis=2)  # (B, budget)
    n0 = jnp.sum(tops.scores >= c_half[:, None], axis=1)      # carried-in hits
    ex_cum = jnp.cumsum(cnt, axis=1) - cnt                    # exclusive cumsum
    done_before = (n0[:, None] + ex_cum) >= k
    live = sel_slots & ~done_before                           # logically-scanned
    pages = jnp.sum(live.astype(jnp.int32), axis=1)

    row_live = jnp.repeat(live, page_rows, axis=1) & row_valid[None, :]
    cand = jnp.sum(row_live.astype(jnp.int32), axis=1)
    done_a = (n0 + jnp.sum(jnp.where(live, cnt, 0), axis=1)) >= k

    masked = jnp.where(row_live, scores, -jnp.inf)            # (B, R)
    row_ids = jnp.where(row_live, rows[None, :], -1)
    merged_s = jnp.concatenate([tops.scores, masked], axis=1)
    merged_r = jnp.concatenate([tops.rows, row_ids], axis=1)
    best_s, idx = jax.lax.top_k(merged_s, k)
    best_r = jnp.take_along_axis(merged_r, idx, axis=1)

    lost = jnp.any(block_masks & ~in_tile[None, :], axis=1)
    return TopK(scores=best_s, rows=best_r), pages, cand, done_a, lost


def _search_batch_batched(arrays, meta, queries, k, budget, budget2,
                          norm_adaptive, cs_prune, use_pallas,
                          prefilter=False, prefilter_eps=1.0):
    """Two-phase runtime: batched selection + one mips_score call per round."""
    n_batch = queries.shape[0]
    n_blocks = arrays.block_sp_lo.shape[0]
    q_proj, q_l2sq, d_sp, r0, probe_ok, c_half, mask0 = select_frontend(
        arrays, meta, queries)
    # best-first truncation key, only materialized when a finite budget can
    # actually truncate (the full-budget graph stays byte-identical)
    prio = (block_priority(arrays, q_proj)
            if min(budget, budget2) < n_blocks else None)
    mask_r1 = mask0
    sk_est = sk_bnd = sk_bvalid = None
    if prefilter:
        mask_r1, sk_est, sk_bnd, sk_bvalid = prefilter_round1(
            arrays, queries, mask0, k, meta.page_rows, prefilter_eps,
            use_pallas)
    empty = TopK(scores=jnp.full((n_batch, k), -jnp.inf),
                 rows=jnp.full((n_batch, k), -1, jnp.int32))
    top, pages1, cand1, done_a, lost1 = _verify_batched(
        arrays, meta, queries, mask_r1, empty, c_half, k, budget, use_pallas,
        prio=prio)
    # Without this barrier XLA CPU re-materializes round-1 fusions inside the
    # round-2 consumers (~2x wall clock); semantically an identity.
    top, done_a, mask0 = jax.lax.optimization_barrier((top, done_a, mask0))

    # Condition B + compensation selection over blocks newly chosen by r'.
    s_k = top.scores[:, k - 1]
    need2, r1, mask1 = compensation_masks(arrays, meta, d_sp, q_l2sq, s_k,
                                          r0, done_a, mask0, norm_adaptive,
                                          cs_prune)
    mask_r2 = mask1
    if prefilter:
        mask_r2 = prefilter_round2(mask1, sk_est, sk_bnd, sk_bvalid, s_k)

    # With an all-False mask1 (every query stopped by A/B in round 1 — the
    # common case) the verification round is an identity on `top` with zero
    # pages/candidates; skip the full tile gather + matmul it would burn.
    def round2(args):
        mask_r2, top = args
        return _verify_batched(arrays, meta, queries, mask_r2, top, c_half, k,
                               budget2, use_pallas, prio=prio)

    def skip2(args):
        _, top = args
        zero = jnp.zeros(top.scores.shape[0], jnp.int32)
        false = jnp.zeros(top.scores.shape[0], bool)
        return top, zero, zero, false, false

    top, pages2, cand2, _, lost2 = jax.lax.cond(
        jnp.any(need2), round2, skip2, (mask_r2, top))

    stats = SearchStats(
        pages=pages1 + pages2,
        candidates=cand1 + cand2,
        probe_passed=probe_ok,
        used_round2=need2,
        radius0=r0,
        radius1=jnp.where(need2, r1, 0.0),
        exhausted=lost1 | (need2 & lost2),
        rows=top.rows,
    )
    ids = jnp.where(top.rows >= 0, arrays.ids[jnp.maximum(top.rows, 0)], -1)
    return ids, top.scores, stats


# ---------------------------------------------------------------------------
# Legacy scan verification (per-query lax.scan of per-block matvecs)
# ---------------------------------------------------------------------------

def _scan_blocks(arrays, meta, q, q_l2sq, block_mask, top: TopK, k: int, budget: int):
    """Budgeted scoring pass over the selected blocks (one while-round).

    Returns (top, pages, candidates, done_a). Blocks are visited in layout
    order (selected-first via stable argsort), matching the sequential-disk
    read pattern the paper's sub-partition layout is designed for.
    """
    page_rows = meta.page_rows
    order = jnp.argsort(~block_mask, stable=True)  # selected block ids first
    n_sel = jnp.sum(block_mask.astype(jnp.int32))
    c_half = sc.condition_a_threshold(arrays.max_l2sq, q_l2sq, meta.c)

    def body(carry, t):
        top, pages, cand, done_a = carry
        blk = order[t]
        live = (t < n_sel) & ~done_a
        base = blk * page_rows
        rows_x = jax.lax.dynamic_slice(arrays.x, (base, 0), (page_rows, arrays.x.shape[1]))
        rows_id = jax.lax.dynamic_slice(arrays.ids, (base,), (page_rows,))
        scores = rows_x @ q  # (page_rows,) — the MXU verification matvec
        valid = live & (rows_id >= 0)
        scores = jnp.where(valid, scores, -jnp.inf)
        row_idx = jnp.where(valid, base + jnp.arange(page_rows), -1)
        top = jax.tree.map(
            lambda new, old: jnp.where(live, new, old),
            _merge_topk(top, scores, row_idx, k),
            top,
        )
        pages = pages + live.astype(jnp.int32)
        cand = cand + jnp.sum(valid.astype(jnp.int32))
        # Condition A on the running k-th best (Theorem 1, c-k-AMIP form).
        done_a = done_a | (top.scores[k - 1] >= c_half)
        return (top, pages, cand, done_a), None

    init = (top, jnp.int32(0), jnp.int32(0), top.scores[k - 1] >= c_half)
    (top, pages, cand, done_a), _ = jax.lax.scan(body, init, jnp.arange(budget))
    return top, pages, cand, done_a


def _search_batch_scan(arrays, meta, queries, k, budget, budget2,
                       norm_adaptive, cs_prune,
                       prefilter=False, prefilter_eps=1.0):
    n_batch = queries.shape[0]
    q_proj, q_l2sq, d_sp, r0, probe_ok, c_half, mask0 = select_frontend(
        arrays, meta, queries)
    mask_r1 = mask0
    sk_est = sk_bnd = sk_bvalid = None
    if prefilter:
        mask_r1, sk_est, sk_bnd, sk_bvalid = prefilter_round1(
            arrays, queries, mask0, k, meta.page_rows, prefilter_eps, None)

    empty = TopK(scores=jnp.full((n_batch, k), -jnp.inf),
                 rows=jnp.full((n_batch, k), -1, jnp.int32))
    top, pages1, cand1, done_a = jax.vmap(
        lambda q, ql2, m, t: _scan_blocks(arrays, meta, q, ql2, m, t, k, budget)
    )(queries, q_l2sq, mask_r1, empty)

    # Condition B + compensation selection (same batch-native functions as
    # the batched/fused backends, so the masks agree bit-for-bit).
    s_k = top.scores[:, k - 1]
    need2, r1, mask1 = compensation_masks(arrays, meta, d_sp, q_l2sq, s_k,
                                          r0, done_a, mask0, norm_adaptive,
                                          cs_prune)
    mask_r2 = mask1
    if prefilter:
        mask_r2 = prefilter_round2(mask1, sk_est, sk_bnd, sk_bvalid, s_k)
    top, pages2, cand2, _ = jax.vmap(
        lambda q, ql2, m, t: _scan_blocks(arrays, meta, q, ql2, m, t, k, budget2)
    )(queries, q_l2sq, mask_r2, top)

    exhausted = (jnp.sum(mask_r1.astype(jnp.int32), axis=1) > budget) | (
        need2 & (jnp.sum(mask_r2.astype(jnp.int32), axis=1) > budget2)
    )
    stats = SearchStats(
        pages=pages1 + pages2,
        candidates=cand1 + cand2,
        probe_passed=probe_ok,
        used_round2=need2,
        radius0=r0,
        radius1=jnp.where(need2, r1, 0.0),
        exhausted=exhausted,
        rows=top.rows,
    )
    ids = jnp.where(top.rows >= 0, arrays.ids[jnp.maximum(top.rows, 0)], -1)
    return ids, top.scores, stats


@functools.partial(
    jax.jit,
    static_argnames=("meta", "k", "budget", "budget2", "norm_adaptive",
                     "cs_prune", "verification", "use_pallas", "prefilter",
                     "prefilter_eps", "dense_frac", "tile_cap"),
)
def search_batch(
    arrays: IndexArrays,
    meta: IndexMeta,
    queries: jnp.ndarray,
    k: int = 10,
    budget: int = 64,
    budget2: int = 64,
    norm_adaptive: bool = False,
    cs_prune: bool = False,
    verification: str = "batched",
    use_pallas: Optional[bool] = None,
    prefilter: bool = False,
    prefilter_eps: float = 1.0,
    dense_frac: float = sc.DENSE_FRAC,
    tile_cap: Optional[int] = None,
):
    """c-k-AMIP search for a batch of queries. queries: (B, d).

    Returns (ids (B, k) original row ids, scores (B, k), SearchStats).
    ``verification`` selects the candidate-scoring backend (module docstring);
    identical results at full budget, "batched" amortizes the whole batch
    into one Pallas matmul per round (budget semantics differ when finite —
    see module docstring). ``prefilter`` enables the quantized-sketch block
    prefilter on every backend (`prefilter_round1/2`, DESIGN.md §13).
    ``dense_frac`` / ``tile_cap`` are the fused tile knobs the offline tuner
    (`repro.tune`) adjusts; the other backends ignore them (their tile is
    always the budget rule).
    """
    if verification == "fused":
        # the in-graph fused driver: pow2 tile buckets as lax.switch
        # branches, so the same block_mips kernel traces under jit and
        # shard_map (the eager host-orchestrated driver lives in
        # `core/search_fused.py` and is dispatched by `core/runtime.search`
        # before this point). Lazy import: search_graph imports this module.
        from .search_graph import search_batch_fused_graph
        return search_batch_fused_graph(arrays, meta, queries, k, budget,
                                        budget2, norm_adaptive, cs_prune,
                                        use_pallas, prefilter, prefilter_eps,
                                        dense_frac, tile_cap)
    if verification == "batched":
        return _search_batch_batched(arrays, meta, queries, k, budget, budget2,
                                     norm_adaptive, cs_prune, use_pallas,
                                     prefilter, prefilter_eps)
    if verification == "scan":
        return _search_batch_scan(arrays, meta, queries, k, budget, budget2,
                                  norm_adaptive, cs_prune,
                                  prefilter, prefilter_eps)
    raise ValueError(f"unknown verification backend: {verification!r}")


@functools.partial(jax.jit, static_argnames=("meta", "k", "budget", "cs_prune"))
def search_batch_progressive(
    arrays: IndexArrays,
    meta: IndexMeta,
    queries: jnp.ndarray,
    k: int = 10,
    budget: int = 64,
    cs_prune: bool = True,
):
    """Beyond-paper progressive device search (see HostSearcher.search_progressive).

    Blocks are visited in ascending "gap" order (projected distance to the
    block's nearest sub-partition surface); each step re-tests the block
    against the CURRENT norm-adaptive radius, so the frontier tightens as the
    running k-th score grows. Per-block tests are conservative (block-level
    max norm / min gap), so no qualified sub-partition is ever skipped.
    """
    page_rows = meta.page_rows

    def one(q):
        q_proj = q @ arrays.a
        q_l2sq = jnp.sum(q * q)

        d_sp = jnp.sqrt(jnp.sum((arrays.sp_center - q_proj[None, :]) ** 2, axis=-1))
        gap_sp = d_sp - arrays.sp_radius  # distance to sub-partition surface
        gathered = jnp.where(
            arrays.block_sp_idx >= 0,
            gap_sp[jnp.maximum(arrays.block_sp_idx, 0)],
            jnp.inf,
        )
        block_gap = jnp.min(gathered, axis=1)  # (NB,)
        order = jnp.argsort(block_gap, stable=True)
        c_half = sc.condition_a_threshold(arrays.max_l2sq, q_l2sq, meta.c)

        def qualify(blk, s_k):
            r_blk = sc.adaptive_radii(arrays.block_max_l2sq[blk], s_k, q_l2sq,
                                      meta.c, meta.x_p, cs_prune=cs_prune, xp=jnp)
            return sc.gap_select(block_gap[blk], r_blk)

        def body(carry, t):
            top, pages, cand, done_a = carry
            blk = order[t]
            live = qualify(blk, top.scores[k - 1]) & ~done_a
            base = blk * page_rows
            rows_x = jax.lax.dynamic_slice(arrays.x, (base, 0), (page_rows, arrays.x.shape[1]))
            rows_id = jax.lax.dynamic_slice(arrays.ids, (base,), (page_rows,))
            scores = rows_x @ q
            valid = live & (rows_id >= 0)
            scores = jnp.where(valid, scores, -jnp.inf)
            row_idx = jnp.where(valid, base + jnp.arange(page_rows), -1)
            top = jax.tree.map(
                lambda new, old: jnp.where(live, new, old),
                _merge_topk(top, scores, row_idx, k),
                top,
            )
            pages = pages + live.astype(jnp.int32)
            cand = cand + jnp.sum(valid.astype(jnp.int32))
            done_a = done_a | (top.scores[k - 1] >= c_half)
            return (top, pages, cand, done_a), None

        empty = TopK(scores=jnp.full((k,), -jnp.inf), rows=jnp.full((k,), -1, jnp.int32))
        init = (empty, jnp.int32(0), jnp.int32(0), jnp.bool_(False))
        (top, pages, cand, done_a), _ = jax.lax.scan(body, init, jnp.arange(budget))

        # any still-qualified block beyond the budget frontier?
        s_k = top.scores[k - 1]
        qual_all = jax.vmap(lambda b: qualify(b, s_k))(jnp.arange(arrays.block_sp_lo.shape[0]))
        visited = jnp.zeros(arrays.block_sp_lo.shape[0], bool).at[order[:budget]].set(True)
        exhausted = jnp.any(qual_all & ~visited) & ~done_a

        stats = SearchStats(
            pages=pages, candidates=cand,
            probe_passed=jnp.bool_(False), used_round2=jnp.bool_(False),
            radius0=jnp.float32(0.0), radius1=jnp.float32(0.0),
            exhausted=exhausted, rows=top.rows,
        )
        ids = jnp.where(top.rows >= 0, arrays.ids[jnp.maximum(top.rows, 0)], -1)
        return ids, top.scores, stats

    return jax.vmap(one)(queries)
