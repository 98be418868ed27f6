"""Host-orchestrated fused two-phase search (``verification="fused"``).

The "batched" backend builds a jit graph whose verification tile is ALWAYS
``budget`` blocks (the full index at the guarantee-default budget): every
round gathers a (budget * page_rows, d) union tile with `jnp.take`, scores
all of it, and reconstructs the sequential semantics through five
(B, R)-shaped boolean intermediates — so at n=8000 the "pruned" path moves
strictly more bytes than the brute-force matmul it is supposed to beat
(DESIGN.md §10 has the traffic accounting).

This driver splits the search into per-round device calls and keeps the
block *selection* on device but the *tile sizing* on host:

  1. `select_frontend` (one jit call) -> per-query round-1 masks (B, NB);
  2. the union of selected blocks is pulled to host (NB bools/query), and
     the verification tile is sized to ``next_pow2(union_count)`` blocks —
     pow2 BUCKETING, so the per-shape jit cache stays O(log n_blocks) —
     instead of always ``budget``;
  3. `kernels/ops.block_mips` (fused kernel on TPU / its lean jnp oracle
     elsewhere) walks exactly those slots in place and returns the
     streaming top-k + per-slot hit counts from which the Condition-A
     stop/pages/candidates accounting is reconstructed;
  4. `compensation_masks` (one jit call) -> Condition B + round-2 masks;
     a compensation round whose union is EMPTY is skipped on host
     outright — no `lax.cond` that still pays a full-tile gather.

Results (ids, scores, and every `SearchStats` field) are bit-identical to
``verification="batched"`` at EVERY budget: the tile-cap rule — the
``budget`` best-priority union blocks (`search_device.truncate_union`),
laid out in layout order — is the same; the bucketed tile only drops slots
the batched tile masks out anyway. The parity suite in
tests/test_fused_verification.py asserts this three-way (fused / batched /
scan) at full budget and pairwise (fused / batched) at finite budgets.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops, ref
from ..obs import metrics as _metrics
from ..obs.trace import span as _span
from .index import IndexArrays, IndexMeta
# DENSE_FRAC lives in search_common (re-exported here for compatibility):
# unions covering at least this fraction of all blocks take the dense path —
# the tile is every block in place (sel still masks per query — exactly the
# batched full tile), skipping the row gather entirely. Since PR 8 it is a
# per-call knob (`dense_frac`), promoted to `RuntimeConfig` and tunable via
# the offline tuner (`repro.tune`); this constant is the hand-picked default.
from .search_common import DENSE_FRAC, next_pow2
from .search_device import (SearchStats, TopK, block_priority,
                            compensation_masks, prefilter_round1,
                            prefilter_round2, select_frontend)


class TraceRing:
    """Bounded record of `_verify` retraces.

    Each jit retrace appends one (n_slots, batch, k, flavor, want_scores)
    tuple. A long-lived serve process retraces whenever a new pow2 bucket /
    batch shape first appears, so the storage is a RING (default 256 — far
    above the O(log n_blocks) bound the tests assert) instead of the old
    unbounded module list, while keeping the list surface those tests use
    (`clear()`, `list(...)`, `len()`, slicing). ``total`` counts every
    retrace ever (monotonic, survives `clear()`) and is exported through
    the metrics registry as the ``fused.verify_retraces`` gauge.
    """

    def __init__(self, capacity: int = 256):
        self.capacity = int(capacity)
        self.total = 0
        self._items: list = []

    def append(self, item) -> None:
        self.total += 1
        self._items.append(item)
        if len(self._items) > self.capacity:
            del self._items[: len(self._items) - self.capacity]

    def clear(self) -> None:
        self._items.clear()

    def __len__(self) -> int:
        return len(self._items)

    def __iter__(self):
        return iter(self._items)

    def __getitem__(self, i):
        return self._items[i]

    def __bool__(self) -> bool:
        return bool(self._items)


# Recorded each time `_verify` RETRACES — the pow2 bucketing's jit-cache
# bound is asserted against this in tests/test_fused_verification.py.
VERIFY_TRACES = TraceRing()

_metrics.register_collector(
    lambda: _metrics.gauge("fused.verify_retraces").set(VERIFY_TRACES.total))


@functools.partial(jax.jit, static_argnames=("meta",))
def _frontend(arrays: IndexArrays, meta: IndexMeta, queries):
    return select_frontend(arrays, meta, queries)


@functools.partial(jax.jit,
                   static_argnames=("k", "page_rows", "dense", "use_pallas",
                                    "want_scores"))
def _verify(arrays: IndexArrays, queries, slots, sel, init_s, init_r, c_half,
            k: int, page_rows: int, dense: bool, use_pallas: Optional[bool],
            want_scores: bool = False):
    """One fused verification round; returns (TopK, pages, cand, done_a,
    scores_cache). ``want_scores`` (dense oracle rounds only) additionally
    returns the full (B, n_pad) score matrix so a later compensation round
    can reuse it instead of re-scoring (`_verify_cached`)."""
    VERIFY_TRACES.append((int(slots.shape[0]), int(queries.shape[0]), k,
                          dense, want_scores))
    valid = arrays.ids >= 0
    top_s, top_r, cnt, pages, cand = ops.block_mips(
        arrays.x, valid, queries, slots, sel, init_s, init_r, c_half,
        k=k, page_rows=page_rows, dense=dense, use_pallas=use_pallas)
    # "running k-th best >= threshold" <=> "n0 + total selected hits >= k"
    # (hits past the stop block only ever re-confirm an already-true stop).
    n0 = jnp.sum(init_s >= c_half[:, None], axis=1)
    done_a = (n0 + jnp.sum(cnt, axis=1)) >= k
    cache = None
    if want_scores:
        # the identical full-matrix product the dense round just consumed
        # (the same expression as `ref.block_mips_ref`) — XLA CSEs it with
        # the in-round matmul, so this costs nothing extra
        cache = ref.mips_score_ref(arrays.x, queries, valid).T
    return TopK(scores=top_s, rows=top_r), pages, cand, done_a, cache


@functools.partial(jax.jit, static_argnames=("k", "page_rows"))
def _verify_cached(arrays: IndexArrays, scores_full, slots, sel, init_s,
                   init_r, c_half, k: int, page_rows: int):
    """Compensation round over a dense previous round's cached scores —
    no new dot products (see `ops.block_mips_cached`)."""
    VERIFY_TRACES.append((int(slots.shape[0]), int(scores_full.shape[0]), k,
                          "cached", False))
    valid = arrays.ids >= 0
    top_s, top_r, cnt, pages, cand = ops.block_mips_cached(
        scores_full, valid, slots, sel, init_s, init_r, c_half,
        k=k, page_rows=page_rows)
    n0 = jnp.sum(init_s >= c_half[:, None], axis=1)
    done_a = (n0 + jnp.sum(cnt, axis=1)) >= k
    return TopK(scores=top_s, rows=top_r), pages, cand, done_a


@functools.partial(jax.jit,
                   static_argnames=("meta", "norm_adaptive", "cs_prune"))
def _round2(arrays: IndexArrays, meta: IndexMeta, d_sp, q_l2sq, s_k, r0,
            done_a, mask0, norm_adaptive: bool, cs_prune: bool):
    return compensation_masks(arrays, meta, d_sp, q_l2sq, s_k, r0, done_a,
                              mask0, norm_adaptive, cs_prune)


# host-side jit wrappers around the shared prefilter stages (the graph
# driver calls the same functions in-trace — bit-parity by construction)
_prefilter1 = jax.jit(prefilter_round1,
                      static_argnames=("k", "page_rows", "eps", "use_pallas"))
_prefilter2 = jax.jit(prefilter_round2)


def _plan_tile(mask: np.ndarray, cap: int, n_blocks: int,
               dense_frac: float = DENSE_FRAC, prio=None):
    """Size one verification tile from the host-side (B, NB) selection.

    Returns (slots (NS,) i32, sel (B, NS) bool, lost (B,) bool, dense,
    union) — ``union`` the number of distinct blocks the batch selected — or
    None when no block is selected (the round is skipped outright — an
    identity on the carried top-k with zero pages/candidates, exactly what
    the batched backend's all-masked tile computes the long way).

    NS = min(next_pow2(union), cap): at most 2x the live work, from a set
    of O(log n_blocks) distinct shapes. When the union would cover nearly
    everything anyway (>= ``dense_frac``) and the cap allows, the tile is
    ALL blocks in place (``dense``) so the kernel/oracle skips the row
    gather — dense and sparse tiles are result-bit-identical, so
    ``dense_frac`` is a pure performance knob (tunable via `repro.tune`).
    ``lost`` flags queries whose selection exceeds the ``cap``-block tile —
    the same union-tile budget rule as ``verification="batched"``;
    ``prio`` (NB,), when given, keeps the BEST union blocks under a
    truncating cap (ties by layout index — `search_device.truncate_union`'s
    rule, applied host-side) instead of the first in layout order.
    """
    union = mask.any(axis=0)
    n_union = int(union.sum())
    if n_union == 0:
        return None
    n_batch = mask.shape[0]
    if n_union >= dense_frac * n_blocks and cap >= n_blocks:
        slots = np.arange(n_blocks, dtype=np.int32)
        return slots, mask, np.zeros(n_batch, bool), True, n_union
    n_slots = min(next_pow2(n_union), cap)
    ublocks = np.nonzero(union)[0]                  # ascending layout order
    if n_union > n_slots:
        if prio is not None:                        # best blocks survive,
            best = np.argsort(prio[ublocks], kind="stable")[:n_slots]
            take = np.sort(ublocks[best])           # ...laid out in order
        else:
            take = ublocks[:n_slots]
        in_tile = np.zeros(n_blocks, bool)
        in_tile[take] = True
        lost = (mask & ~in_tile[None, :]).any(axis=1)
    else:
        take = ublocks
        lost = np.zeros(n_batch, bool)
    slots = np.zeros(n_slots, np.int32)
    slots[: len(take)] = take
    sel = np.zeros((n_batch, n_slots), bool)
    sel[:, : len(take)] = mask[:, take]
    return slots, sel, lost, False, n_union


def search_batch_fused(
    arrays: IndexArrays,
    meta: IndexMeta,
    queries: jnp.ndarray,
    k: int = 10,
    budget: int = 64,
    budget2: int = 64,
    norm_adaptive: bool = False,
    cs_prune: bool = False,
    use_pallas: Optional[bool] = None,
    prefilter: bool = False,
    prefilter_eps: float = 1.0,
    obs: bool = False,
    dense_frac: float = DENSE_FRAC,
    tile_cap: Optional[int] = None,
):
    """c-k-AMIP search, fused backend. Same contract as `search_batch`.

    Eager-only (host-orchestrated): call it outside jit. `core/runtime.search`
    routes ``verification="fused"`` here when not tracing; under an ambient
    trace the bit-identical IN-GRAPH fused driver
    (`core/search_graph.search_batch_fused_graph`) runs instead — same
    kernel, tile buckets selected by `lax.switch` rather than on host.

    ``prefilter`` scores the quantized block sketch for every candidate
    block BEFORE any page is fetched and verifies only the survivors; both
    rounds' selections shrink, the Theorem-1/2 accounting is untouched (the
    survivor rules are lossless at ``prefilter_eps=1``; see DESIGN.md §13).

    ``obs`` activates the per-phase spans and round-shape counters
    (DESIGN.md §14). Off (the default), each phase pays one no-op span
    call; no jit graph differs either way — the instrumentation is pure
    host code between the same device calls. Each device -> host pull has a
    ``pull`` span of its own, the tile planning's numpy work a ``plan``
    span, and each verify round span carries its tile's ``slots``, the
    batch's ``union`` of selected blocks and the kernel's grid ``steps``
    (0 on the oracle route).

    ``dense_frac`` / ``tile_cap`` are the tuner-promoted tile knobs
    (DESIGN.md §15): ``dense_frac`` moves the dense-path threshold
    (result-bit-identical at any value), ``tile_cap`` additionally clamps
    both rounds' verification tiles below the budget rule (``tile_cap >=
    n_blocks`` is a no-op; a cap below a round's union truncates it under
    the SAME first-blocks-in-layout-order rule as a finite budget, flagging
    the affected queries ``exhausted``).
    """
    n_blocks = meta.n_blocks
    n_batch = queries.shape[0]
    cap = min(budget, n_blocks)
    cap2 = min(budget2, n_blocks)
    if tile_cap is not None:
        cap = min(cap, int(tile_cap))
        cap2 = min(cap2, int(tile_cap))

    with _span("select_frontend", active=obs, layer="dispatch") as sp:
        q_proj, q_l2sq, d_sp, r0, probe_ok, c_half, mask0 = _frontend(
            arrays, meta, queries)
        sp.fence(mask0)
    # host-side copy of the shared best-first truncation key (same rule as
    # the batched / in-graph drivers), only when a cap can truncate
    prio_np = None
    if min(cap, cap2) < n_blocks:
        with _span("pull_priority", active=obs, layer="pull"):
            prio_np = np.asarray(block_priority(arrays, q_proj))
    mask_r1 = mask0
    sk_est = sk_bnd = sk_bvalid = None
    if prefilter:
        with _span("prefilter_round1", active=obs, layer="dispatch") as sp:
            mask_r1, sk_est, sk_bnd, sk_bvalid = _prefilter1(
                arrays, queries, mask0, k, meta.page_rows, prefilter_eps,
                use_pallas)
            sp.fence(mask_r1)
    zero = jnp.zeros(n_batch, jnp.int32)
    false = jnp.zeros(n_batch, bool)
    # strong f32 (explicit dtype): round-2 carries _verify's strong-typed
    # output back in, and a weak-typed round-1 init would double every
    # bucket's jit-cache entry
    top = TopK(scores=jnp.full((n_batch, k), -jnp.inf, jnp.float32),
               rows=jnp.full((n_batch, k), -1, jnp.int32))

    def walk_steps(n_slots):          # the kernel's grid steps, when traced
        return ops.block_mips_steps(
            n_slots, n_batch, meta.d, k=k, page_rows=meta.page_rows,
            use_pallas=use_pallas) if obs else 0

    scores_cache = None
    with _span("pull_mask_round1", active=obs, layer="pull"):
        mask_np = np.asarray(mask_r1)
    with _span("plan_tile_round1", active=obs, layer="plan"):
        plan = _plan_tile(mask_np, cap, n_blocks, dense_frac, prio=prio_np)
    if plan is None:
        if obs:
            _metrics.counter("fused.rounds_skipped").inc()
        pages1, cand1, done_a, lost1 = zero, zero, false, false
    else:
        slots, sel, lost_np, dense, n_union = plan
        if obs:
            _metrics.counter("fused.rounds_dense" if dense
                             else "fused.rounds_sparse").inc()
        # A dense oracle round scores the whole corpus in place; keep that
        # (B, n_pad) product so the compensation round needs NO new matmul.
        want_scores = dense and not ops._resolve(use_pallas)
        with _span("verify_round1", active=obs, layer="dispatch",
                   slots=len(slots), union=n_union,
                   steps=walk_steps(len(slots))) as sp:
            top, pages1, cand1, done_a, scores_cache = _verify(
                arrays, queries, jnp.asarray(slots), jnp.asarray(sel),
                top.scores, top.rows, c_half, k, meta.page_rows, dense,
                use_pallas, want_scores)
            sp.fence(top.scores)
        lost1 = jnp.asarray(lost_np)

    with _span("compensation", active=obs, layer="dispatch") as sp:
        s_k = top.scores[:, k - 1]
        need2, r1, mask1 = _round2(arrays, meta, d_sp, q_l2sq, s_k, r0,
                                   done_a, mask0, norm_adaptive, cs_prune)
        sp.fence(mask1)
    mask_r2 = mask1
    if prefilter:
        with _span("prefilter_round2", active=obs, layer="dispatch") as sp:
            mask_r2 = _prefilter2(mask1, sk_est, sk_bnd, sk_bvalid, s_k)
            sp.fence(mask_r2)

    with _span("pull_mask_round2", active=obs, layer="pull"):
        mask_np = np.asarray(mask_r2)
    with _span("plan_tile_round2", active=obs, layer="plan"):
        plan = _plan_tile(mask_np, cap2, n_blocks, dense_frac, prio=prio_np)
    if plan is None:
        if obs:
            _metrics.counter("fused.rounds_skipped").inc()
        pages2, cand2, lost2 = zero, zero, false
    else:
        slots, sel, lost_np, dense, n_union = plan
        with _span("verify_round2", active=obs, layer="dispatch",
                   slots=len(slots), union=n_union,
                   steps=walk_steps(len(slots))) as sp:
            if scores_cache is not None:
                if obs:
                    _metrics.counter("fused.rounds_cached").inc()
                top, pages2, cand2, _ = _verify_cached(
                    arrays, scores_cache, jnp.asarray(slots),
                    jnp.asarray(sel), top.scores, top.rows, c_half, k,
                    meta.page_rows)
            else:
                if obs:
                    _metrics.counter("fused.rounds_dense" if dense
                                     else "fused.rounds_sparse").inc()
                top, pages2, cand2, _, _ = _verify(
                    arrays, queries, jnp.asarray(slots), jnp.asarray(sel),
                    top.scores, top.rows, c_half, k, meta.page_rows, dense,
                    use_pallas, False)
            sp.fence(top.scores)
        lost2 = jnp.asarray(lost_np)

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


__all__ = ["search_batch_fused", "TraceRing", "VERIFY_TRACES", "DENSE_FRAC"]
