"""Cutout runner: isolate and time one search stage at a target point.

The tuner never times a stage inside the full pipeline — each stage is cut
out and driven alone on deterministic synthetic data matching the target
``(n, d)`` point (`make_cutout`, the same `mf_factors` family every
benchmark corpus uses), with the interleaved median-of-adjacent-pairs
protocol `benchmarks/run.py` uses for A/B comparisons
(`interleaved_ratio`): candidate and incumbent alternate within one
session, and the reported ratio is the MEDIAN over adjacent pairs, so a
background-noise spike inflates one pair instead of poisoning a whole
arm's mean.

`stage_records` reports, per stage, the measured wall-clock next to
`launch/roofline.kernel_cost`'s compile-time bound and their ratio
(``roofline_frac``). The bound uses the v5e constants and sums every
lax.switch branch (it is flagged ``static_upper_bound``) — on the CPU
container the fraction is a normalization for comparing candidates, not an
achieved-MFU claim; on TPU it approaches the real roofline gap.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import runtime as _runtime
from ..core import search_fused as sf
from ..core.index import IndexArrays, IndexMeta
from ..data.synthetic import mf_factors
from ..launch.roofline import kernel_cost


def make_cutout(n: int, d: int, n_q: int = 64, *, rank: int = 16,
                decay: float = 0.5, norm_tail: float = 0.6, seed: int = 0):
    """Deterministic synthetic (corpus, queries) for one tuning point —
    the same MF-factor family (and, at the default kwargs, the same seeds
    0/1 convention) as the benchmark corpora, so a LARGE_N cutout is the
    LARGE_N bench workload. Bit-reproducible under a fixed ``seed``
    (pinned by tests/test_tune.py)."""
    x = mf_factors(n, d, rank, decay=decay, seed=seed, norm_tail=norm_tail)
    q = mf_factors(n_q, d, rank, decay=decay, seed=seed + 1)
    return x, q


def _block(v):
    jax.block_until_ready(v)
    return v


def time_call(fn, *args, reps: int = 5, warmup: int = 1) -> float:
    """Median wall-clock seconds of ``fn(*args)`` over ``reps`` fenced
    calls (after ``warmup`` compile/cache-warming calls)."""
    for _ in range(max(warmup, 0)):
        _block(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _block(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def interleaved_ratio(fn_a, fn_b, reps: int = 5):
    """(median_t_a, median_t_b, median per-pair t_a/t_b) over ``reps``
    interleaved A/B pairs — host wall clock jitters ±20% on this container,
    so comparisons are made within adjacent pairs, never across sessions.
    Callers warm both arms (compile) before measuring."""
    ta, tb, ratios = [], [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        _block(fn_a())
        t1 = time.perf_counter()
        _block(fn_b())
        t2 = time.perf_counter()
        ta.append(t1 - t0)
        tb.append(t2 - t1)
        ratios.append((t1 - t0) / max(t2 - t1, 1e-12))
    return (float(np.median(ta)), float(np.median(tb)),
            float(np.median(ratios)))


def round1_masks(arrays: IndexArrays, meta: IndexMeta, queries, *,
                 k: int = 10, prefilter: bool = False,
                 prefilter_eps: float = 1.0,
                 use_pallas: Optional[bool] = None):
    """(frontend outputs, round-1 (B, NB) mask after the optional
    prefilter) — the selection the round-1 tile is planned from."""
    qj = jnp.asarray(queries, jnp.float32)
    front = sf._frontend(arrays, meta, qj)
    mask0 = front[6]
    mask_r1 = mask0
    if prefilter and meta.sk_subspaces:
        mask_r1 = sf._prefilter1(arrays, qj, mask0, k, meta.page_rows,
                                 prefilter_eps, use_pallas)[0]
    return front, mask_r1


def round1_union(arrays: IndexArrays, meta: IndexMeta, queries, *,
                 k: int = 10, prefilter: bool = False,
                 prefilter_eps: float = 1.0,
                 use_pallas: Optional[bool] = None) -> int:
    """Number of distinct blocks the round-1 batch union selects — what
    the tile-cap candidate derivation keys off (an exact-fit cap removes
    the next_pow2 padding without truncating anything)."""
    _, mask_r1 = round1_masks(arrays, meta, queries, k=k,
                              prefilter=prefilter,
                              prefilter_eps=prefilter_eps,
                              use_pallas=use_pallas)
    return int(np.asarray(mask_r1).any(axis=0).sum())


def stage_records(arrays: IndexArrays, meta: IndexMeta, queries, *,
                  k: int = 10, prefilter: bool = False,
                  prefilter_eps: float = 1.0, dense_frac: float = 0.9,
                  tile_cap: Optional[int] = None,
                  use_pallas: Optional[bool] = None, reps: int = 5) -> dict:
    """Isolated per-stage timings at one point, against the static roofline
    bound. Stages mirror the host fused driver: `select_frontend`, the
    optional sketch prefilter, one planned fused verification tile, and the
    shared top-k rescore/merge. Returns {stage: {us, flops, bytes,
    roofline_s, roofline_frac, ...}} plus a ``_tile`` record describing the
    planned round-1 tile (union, slots, dense)."""
    qj = jnp.asarray(queries, jnp.float32)
    n_batch = int(qj.shape[0])
    recs: dict = {}

    def rec(name, fn, *args):
        us = time_call(fn, *args, reps=reps) * 1e6
        entry = {"us": us, "us_per_query": us / max(n_batch, 1)}
        try:
            entry.update(kernel_cost(fn, *args))
            entry["roofline_frac"] = entry["roofline_s"] / max(us * 1e-6,
                                                               1e-12)
        except Exception as e:  # cost_analysis is best-effort, never fatal
            entry["cost_error"] = f"{type(e).__name__}: {e}"
        recs[name] = entry

    rec("select_frontend", sf._frontend, arrays, meta, qj)
    front = sf._frontend(arrays, meta, qj)
    c_half, mask0 = front[5], front[6]
    mask_r1 = mask0
    if prefilter and meta.sk_subspaces:
        rec("prefilter_round1", sf._prefilter1, arrays, qj, mask0, k,
            meta.page_rows, prefilter_eps, use_pallas)
        mask_r1 = sf._prefilter1(arrays, qj, mask0, k, meta.page_rows,
                                 prefilter_eps, use_pallas)[0]

    cap = meta.n_blocks if tile_cap is None else min(int(tile_cap),
                                                     meta.n_blocks)
    plan = sf._plan_tile(np.asarray(mask_r1), cap, meta.n_blocks, dense_frac)
    top = sf.TopK(scores=jnp.full((n_batch, k), -jnp.inf, jnp.float32),
                  rows=jnp.full((n_batch, k), -1, jnp.int32))
    if plan is not None:
        slots, sel, _, dense, n_union = plan
        recs["_tile"] = {"n_union": n_union, "tile_slots": int(len(slots)),
                         "dense": bool(dense)}
        rec("fused_verify_tile", sf._verify, arrays, qj, jnp.asarray(slots),
            jnp.asarray(sel), top.scores, top.rows, c_half, k,
            meta.page_rows, dense, use_pallas, False)
        top = sf._verify(arrays, qj, jnp.asarray(slots), jnp.asarray(sel),
                         top.scores, top.rows, c_half, k, meta.page_rows,
                         dense, use_pallas, False)[0]
    rec("topk_rescore", _runtime._rescore, arrays.x, top.rows, qj)
    return recs


__all__ = ["make_cutout", "time_call", "interleaved_ratio", "round1_masks",
           "round1_union", "stage_records"]
