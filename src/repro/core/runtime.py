"""Unified two-phase search runtime (DESIGN.md §3.2).

Single entry point the device, sharded and serve layers all call. A
`RuntimeConfig` names the algorithm (`mode`) and the candidate-verification
backend (`verification`); the runtime clamps budgets to the index size and
dispatches to the jit'd implementations in `search_device`:

  mode="two_phase"   Algorithm 3 (Quick-Probe + range + compensation round);
                     verification="fused" (default) runs the fused
                     block-sparse rounds (`kernels/block_mips` walks the
                     selected pages in place, tiles sized to
                     next_pow2(union)) — host-orchestrated when called
                     eagerly (`core/search_fused.py`), and as the fully
                     in-graph `core/search_graph.py` driver under any
                     ambient jit / shard_map trace, so the fused kernel is
                     the one verification path at every scale; "batched" is
                     the single-graph full-tile union path, bit-identical
                     to "fused" at every budget; "scan" is the legacy
                     per-query lax.scan, kept as the semantics reference /
                     benchmark baseline.
                     All three are identical at the default full budget; a
                     finite ``budget`` caps the SHARED union tile under
                     "fused"/"batched" vs each query's own selection under
                     "scan" (affected queries are flagged ``exhausted``).
  mode="progressive" beyond-paper norm-adaptive frontier search.

All modes return the same (ids (B, k), scores (B, k), SearchStats) triple.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..obs import trace as _trace
from ..obs.trace import span as _span
from .index import IndexArrays, IndexMeta
from .search_common import DENSE_FRAC, next_pow2
from .search_device import SearchStats, search_batch, search_batch_progressive
from .search_fused import search_batch_fused


@jax.jit
def _rescore(x, rows, queries):
    """Exact f32 inner products for the returned candidate rows.

    Every search backend reports scores through this one compiled function,
    so "scan" and "batched" verification return BIT-IDENTICAL scores (inside
    a fused search graph XLA may re-associate the verification dots
    differently per backend; the candidate SETS are identical, so one shared
    rescore of the k winners removes the ULP-level noise from the API).
    """
    cand = jnp.take(x, jnp.maximum(rows, 0), axis=0)     # (B, k, d)
    s = jnp.einsum("bkd,bd->bk", cand, queries)
    return jnp.where(rows >= 0, s, -jnp.inf)


def eager() -> bool:
    """True outside every jit / shard_map / vmap trace.

    Host-orchestrated code (the eager fused driver, host spans) may run only
    then. Testing ``queries`` for a `jax.core.Tracer` is not enough: under
    jit / shard_map the index arrays may be traced while the queries are a
    closed-over concrete array, and the eager driver's ``np.asarray`` of a
    traced mask would fail.
    """
    return jax.core.trace_ctx.is_top_level()


VALID_MODES = ("two_phase", "progressive")
VALID_VERIFICATIONS = ("fused", "batched", "scan")


@dataclass(frozen=True)
class RuntimeConfig:
    """Static (hashable) search-runtime configuration.

    Validated EAGERLY: an unknown ``mode``/``verification`` or a
    non-positive ``k``/``budget`` raises `ValueError` at construction (and
    again at `search()` entry, for configs built before this check existed)
    with the valid choices named — instead of failing deep inside the jit'd
    device path.
    """

    k: int = 10
    budget: Optional[int] = None       # None => all blocks (no truncation)
    budget2: Optional[int] = None      # compensation round; None => budget
    mode: str = "two_phase"            # "two_phase" | "progressive"
    verification: str = "fused"        # "fused" | "batched" | "scan"
                                       # (two_phase only)
    norm_adaptive: bool = False
    cs_prune: bool = False
    use_pallas: Optional[bool] = None   # None => Pallas on TPU, jnp oracle off-TPU
    prefilter: bool = False            # quantized-sketch block prefilter
    prefilter_eps: float = 1.0         # sketch-bound scale; 1.0 = lossless,
                                       # smaller prunes harder (DESIGN.md §13)
    obs: bool = False                  # per-call span/metric instrumentation
                                       # (also on whenever obs.trace is
                                       # globally enabled; DESIGN.md §14)
    # Fused tile knobs, promoted from `search_fused` module constants so the
    # offline tuner (`repro.tune`, DESIGN.md §15) can set them per shape.
    # None => consult the tuning cache (results/tune/tuning.json) for this
    # index's (n-bucket, d, platform, jax version) key; a missing key falls
    # back to the hand-picked values (dense_frac=0.9, no extra cap) —
    # bit-identical to the pre-tuner behavior. Explicit values always win;
    # pass ``tile_cap >= n_blocks`` for an explicit "no cap".
    dense_frac: Optional[float] = None  # dense-path threshold (result-
                                        # bit-identical at any value)
    tile_cap: Optional[int] = None      # extra clamp on both rounds' fused
                                        # verification tiles (below budget)

    def __post_init__(self):
        # integer-valued knobs are accepted and coerced (prefilter_eps=1 is
        # the lossless sketch bound, not an error)
        for field_name in ("prefilter_eps", "dense_frac"):
            v = getattr(self, field_name)
            if isinstance(v, (int, np.integer)) and not isinstance(v, bool):
                object.__setattr__(self, field_name, float(v))
        self.validate()

    def validate(self) -> None:
        if self.mode not in VALID_MODES:
            raise ValueError(f"unknown search mode: {self.mode!r}; valid "
                             f"choices: {', '.join(VALID_MODES)}")
        if self.verification not in VALID_VERIFICATIONS:
            raise ValueError(
                f"unknown verification backend: {self.verification!r}; valid "
                f"choices: {', '.join(VALID_VERIFICATIONS)}")
        if not isinstance(self.k, (int, np.integer)) or self.k < 1:
            raise ValueError(f"k must be a positive int, got {self.k!r}")
        for field_name in ("budget", "budget2"):
            v = getattr(self, field_name)
            if v is None:
                continue
            if not isinstance(v, (int, np.integer)) or v < 1:
                raise ValueError(f"{field_name} must be None (= all blocks) "
                                 f"or a positive int, got {v!r}")
        if not isinstance(self.prefilter, bool):
            raise ValueError(f"prefilter must be a bool, got "
                             f"{self.prefilter!r}")
        if not isinstance(self.obs, bool):
            raise ValueError(f"obs must be a bool, got {self.obs!r}")
        eps = self.prefilter_eps
        if not isinstance(eps, (int, float, np.floating)) or isinstance(
                eps, bool) or not 0.0 < float(eps) <= 1.0:
            raise ValueError(f"prefilter_eps must be a float in (0, 1], got "
                             f"{eps!r}")
        df = self.dense_frac
        if df is not None and (
                not isinstance(df, (int, float, np.floating))
                or isinstance(df, bool) or not 0.0 < float(df) <= 1.0):
            raise ValueError(f"dense_frac must be None (= tuned/default) or "
                             f"a float in (0, 1], got {df!r}")
        tc = self.tile_cap
        if tc is not None and (not isinstance(tc, (int, np.integer))
                               or isinstance(tc, bool) or tc < 1):
            raise ValueError(f"tile_cap must be None (= tuned/default) or a "
                             f"positive int, got {tc!r}")


def search(arrays: IndexArrays, meta: IndexMeta, queries,
           cfg: RuntimeConfig = RuntimeConfig()):
    """Run one batched c-k-AMIP search under ``cfg``.

    queries: (B, d). Returns (ids (B, k), scores (B, k), SearchStats).
    Safe to call inside jit / shard_map (the underlying functions are jit'd
    with static meta/config arguments).
    """
    cfg.validate()  # fail fast, naming valid choices, before the jit'd path
    if cfg.prefilter and not meta.sk_subspaces:
        raise ValueError(
            "prefilter=True but the index carries no sketch (built before "
            "the sketch existed?); rebuild the index or disable prefilter")
    if cfg.prefilter and cfg.mode != "two_phase":
        raise ValueError("prefilter is only supported in two_phase mode")
    budget = int(min(cfg.budget if cfg.budget is not None else meta.n_blocks,
                     meta.n_blocks))
    budget2 = int(min(cfg.budget2 if cfg.budget2 is not None else budget,
                      meta.n_blocks))
    # Resolve the tuner-promoted fused tile knobs: explicit cfg values win;
    # None consults the offline tuning cache for this index's shape key and
    # falls back to the hand-picked defaults on a miss (bit-identical to the
    # pre-tuner behavior — guarded by tests/test_tune.py). Pure host-side
    # python over static meta fields, so it is trace-safe.
    dense_frac, tile_cap = cfg.dense_frac, cfg.tile_cap
    if cfg.mode == "two_phase" and cfg.verification == "fused" and (
            dense_frac is None or tile_cap is None):
        from ..tune import cache as _tune_cache
        tuned = _tune_cache.resolved("runtime", meta.n, meta.d)
        if dense_frac is None:
            dense_frac = float(tuned.get("dense_frac", DENSE_FRAC))
        if tile_cap is None:
            tc = tuned.get("tile_cap")
            tile_cap = int(tc) if tc is not None else None
    elif dense_frac is None:
        dense_frac = DENSE_FRAC
    q = jnp.atleast_2d(jnp.asarray(queries, jnp.float32))
    # Host spans only make sense OUTSIDE an ambient trace (inside one they
    # would time jaxpr construction, not work — DESIGN.md §14); the check is
    # shared with the fused-driver routing below.
    clean = eager()
    active = clean and (cfg.obs or _trace.enabled())
    with _span("search", active=active, layer="dispatch") as sp_e2e:
        if cfg.mode == "progressive":
            ids, _, stats = search_batch_progressive(arrays, meta, q,
                                                     k=cfg.k, budget=budget,
                                                     cs_prune=cfg.cs_prune)
        elif cfg.mode == "two_phase":
            if cfg.verification == "fused" and clean:
                # Host-orchestrated fused rounds (tiles sized on host, an
                # empty round skipped outright, the dense-round score cache
                # on the CPU oracle). Under ANY ambient trace (jit /
                # shard_map — even when `queries` itself is a closed-over
                # concrete array, the index arrays may be traced)
                # `search_batch` runs the bit-identical IN-GRAPH fused
                # driver (`core/search_graph.py`) instead: same block_mips
                # kernel, pow2 tile buckets as lax.switch branches.
                ids, _, stats = search_batch_fused(
                    arrays, meta, q, k=cfg.k, budget=budget, budget2=budget2,
                    norm_adaptive=cfg.norm_adaptive, cs_prune=cfg.cs_prune,
                    use_pallas=cfg.use_pallas, prefilter=cfg.prefilter,
                    prefilter_eps=cfg.prefilter_eps, obs=active,
                    dense_frac=dense_frac, tile_cap=tile_cap)
            else:
                ids, _, stats = search_batch(arrays, meta, q, k=cfg.k,
                                             budget=budget, budget2=budget2,
                                             norm_adaptive=cfg.norm_adaptive,
                                             cs_prune=cfg.cs_prune,
                                             verification=cfg.verification,
                                             use_pallas=cfg.use_pallas,
                                             prefilter=cfg.prefilter,
                                             prefilter_eps=cfg.prefilter_eps,
                                             dense_frac=dense_frac,
                                             tile_cap=tile_cap)
        else:
            raise ValueError(f"unknown search mode: {cfg.mode!r}")
        with _span("rescore", active=active, layer="dispatch") as sp:
            scores = sp.fence(_rescore(arrays.x, stats.rows, q))
        sp_e2e.fence((ids, scores))
    return ids, scores, stats


# ---------------------------------------------------------------------------
# Segment-aware entry (streaming index, DESIGN.md §8)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "use_pallas"))
def _merge_segments(base_alive, rows, base_ids, base_scores, delta_x,
                    delta_gids, delta_valid, queries, k, use_pallas):
    """Merge base top-k_base with the exact-scored delta segment.

    ``base_scores`` are the `_rescore`d exact inner products `search` already
    computed; here tombstoned rows are masked to -inf. Every delta row is
    scored exactly in one `ops.mips_score` call (the same verification kernel
    the batched two-phase runtime uses). One `lax.top_k` over the
    concatenation is the same merge rule as `search_common.topk_merge`
    (ties break toward the base entry).
    """
    alive = (rows >= 0) & jnp.take(base_alive, jnp.maximum(rows, 0), axis=0)
    b_scores = jnp.where(alive, base_scores, -jnp.inf)
    b_ids = jnp.where(alive, base_ids, -1)

    d_scores = ops.mips_score(delta_x, queries, delta_valid,
                              use_pallas=use_pallas).T        # (B, cap)
    d_scores = jnp.where(delta_valid[None, :], d_scores, -jnp.inf)
    d_ids = jnp.broadcast_to(jnp.where(delta_valid, delta_gids, -1),
                             d_scores.shape)

    merged_s = jnp.concatenate([b_scores, d_scores], axis=1)
    merged_i = jnp.concatenate([b_ids, d_ids], axis=1)
    best_s, pos = jax.lax.top_k(merged_s, k)
    return jnp.take_along_axis(merged_i, pos, axis=1), best_s


def search_segments(snap, queries, cfg: RuntimeConfig = RuntimeConfig()):
    """Batched c-k-AMIP search over a streaming `stream.segments.Snapshot`.

    Runs the configured base search over the immutable base segment —
    over-fetching ``k + next_pow2(n_base_dead)`` results so tombstoned rows
    cannot crowd live ones out of the top-k (the quantization bounds jit
    recompiles to O(log n) distinct shapes between compactions) — then
    merges in the delta segment's exact scores. On a ``clean`` snapshot
    (no tombstones, empty delta) this is EXACTLY `search` on the base
    arrays: bit-identical ids and scores to a cold-built index.

    Returns (global ids (B, k), scores (B, k), StreamStats).
    """
    from ..stream.segments import StreamStats  # deferred: stream imports us

    q = jnp.atleast_2d(jnp.asarray(queries, jnp.float32))
    meta = snap.meta
    if snap.clean:
        ids, scores, stats = search(snap.arrays, meta, q, cfg)
        return ids, scores, StreamStats(pages=stats.pages,
                                        candidates=stats.candidates,
                                        exhausted=stats.exhausted, base=stats)

    k_base = min(cfg.k + (next_pow2(snap.n_base_dead) if snap.n_base_dead
                          else 0), meta.n_pad)
    ids_b, scores_b, stats = search(snap.arrays, meta, q,
                                    dataclasses.replace(cfg, k=k_base))
    active = (cfg.obs or _trace.enabled()) and eager()
    with _span("segments_merge", active=active, layer="dispatch",
               metric="search.merge_us") as sp:
        ids, scores = _merge_segments(snap.base_alive, stats.rows, ids_b,
                                      scores_b, snap.delta_x, snap.delta_gids,
                                      snap.delta_valid, q, cfg.k,
                                      cfg.use_pallas)
        sp.fence((ids, scores))
    delta_pages = -(-snap.delta_count // meta.page_rows)  # logical delta sweep
    return ids, scores, StreamStats(
        pages=stats.pages + jnp.int32(delta_pages),
        candidates=stats.candidates + jnp.sum(snap.delta_valid.astype(jnp.int32)),
        exhausted=stats.exhausted,
        base=stats,
    )


__all__ = ["RuntimeConfig", "SearchStats", "eager", "next_pow2", "search",
           "search_segments"]
