"""Pod-scale ProMIPS: corpus sharded over the `model` mesh axis, one local
index per shard, global top-k by all-gathering the per-shard (k, score)
pairs — k x n_shards values cross the wire instead of n (DESIGN.md §3).

Build: contiguous row ranges -> per-shard build_index (ids are GLOBAL row
ids), padded to common array shapes and stacked on a leading shard axis.
Search: shard_map over the model axis; each shard runs the unified search
runtime (`core/runtime.py` — progressive frontier by default, or the
two-phase mode with fused / batched / scan verification; "fused" runs the
in-graph `core/search_graph.py` driver inside the trace) on its slice; a
tiny all_gather + top_k merges.
"""
from __future__ import annotations

import dataclasses
import functools
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import trace as _trace
from ..obs.trace import span as _span
from .index import IndexArrays, IndexMeta, build_index
from .runtime import RuntimeConfig, eager
from .runtime import search as runtime_search


class ShardedIndex(NamedTuple):
    arrays: IndexArrays      # every leaf has a leading (n_shards,) axis
    meta: IndexMeta          # common (max-padded) meta


class ShardedStats(NamedTuple):
    """Aggregated accounting of one fan-out search (host-merge path).

    Same pages/candidates field contract as `SearchStats` / `HostStats` /
    `StreamStats` (a query counts exhausted if ANY shard exhausted on it);
    totals are pre-aggregated, so ``queries`` is carried explicitly.
    """

    pages: int
    candidates: int
    exhausted: int
    queries: int

    def to_dict(self) -> dict:
        from .stats import stats_totals
        return stats_totals(self.pages, self.candidates, self.exhausted,
                            queries=self.queries)


def _pad_to(arr: np.ndarray, n: int, fill):
    pad = n - arr.shape[0]
    if pad <= 0:
        return arr
    width = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, width, constant_values=fill)


def _build_in_parallel(fn, items: list) -> list:
    """``[fn(item) for item in items]`` on one thread per item. Shard index
    builds are independent NumPy work that mostly runs without the GIL, so
    n shards build in well under n times one shard's time."""
    with ThreadPoolExecutor(max(len(items), 1)) as pool:
        return list(pool.map(fn, items))


def build_sharded(x: np.ndarray, n_shards: int, **kwargs) -> ShardedIndex:
    n = x.shape[0]
    bounds = np.linspace(0, n, n_shards + 1).astype(int)

    def build(s: int):
        lo, hi = bounds[s], bounds[s + 1]
        idx = build_index(x[lo:hi], **kwargs)
        a = idx.arrays._replace(
            ids=np.where(idx.arrays.ids >= 0, idx.arrays.ids + lo, -1).astype(np.int32)
        )
        return a, idx.meta

    parts = _build_in_parallel(build, list(range(n_shards)))

    n_pad = max(m.n_pad for _, m in parts)
    g_max = max(m.n_groups for _, m in parts)
    s_max = max(m.n_subparts for _, m in parts)
    nb_max = max(m.n_blocks for _, m in parts)
    kmax = max(a.block_sp_idx.shape[1] for a, _ in parts)
    kcb_max = max(m.sk_codewords for _, m in parts)
    page_rows = parts[0][1].page_rows

    stacked = {}
    for field in IndexArrays._fields:
        vals = []
        for a, m in parts:
            v = np.asarray(getattr(a, field))
            if field in ("x", "p", "ids", "l2sq"):
                v = _pad_to(v, n_pad, -1 if field == "ids" else 0)
            elif field.startswith("g_"):
                v = _pad_to(v, g_max, 0)
            elif field == "sp_start":
                v = _pad_to(v, s_max + 1, v[-1])
            elif field.startswith("sp_"):
                # unreachable centers (1e30) + zero radius => never selected
                v = _pad_to(v, s_max, 1e30 if field == "sp_center" else 0)
            elif field == "block_sp_idx":
                if v.shape[1] < kmax:
                    v = np.pad(v, ((0, 0), (0, kmax - v.shape[1])), constant_values=-1)
                v = _pad_to(v, nb_max, -1)
            elif field == "sk_codebooks":
                # codeword count tracks min(256, NB_shard): pad small shards'
                # codebooks with zero codewords (never assigned by real codes)
                if v.shape[1] < kcb_max:
                    v = np.pad(v, ((0, 0), (0, kcb_max - v.shape[1]), (0, 0)))
            elif field.startswith("sk_") or field.startswith("block_"):
                # padded blocks decode to the zero sketch with err 0; the
                # prefilter drops them via the ids-derived block validity
                v = _pad_to(v, nb_max, 0)
            vals.append(v)
        stacked[field] = np.stack(vals)
    meta = dataclasses.replace(
        parts[0][1], n=n, n_pad=n_pad, n_blocks=nb_max, n_groups=g_max,
        n_subparts=s_max, page_rows=page_rows, sk_codewords=kcb_max,
    )
    return ShardedIndex(arrays=IndexArrays(**stacked), meta=meta)


def sharded_search(
    sharded: ShardedIndex,
    queries: jnp.ndarray,
    k: int,
    mesh: Mesh,
    *,
    axis: str = "model",
    budget: int = 64,
    cs_prune: bool = True,
    runtime: Optional[RuntimeConfig] = None,
):
    """Global c-k-AMIP over the sharded corpus. queries: (B, d) replicated.

    ``runtime`` selects the per-shard search config (mode / verification
    backend); the default is the progressive norm-adaptive frontier. Pass
    e.g. ``RuntimeConfig(mode="two_phase", verification="fused",
    norm_adaptive=True)`` to run the fused block-sparse verification on
    every shard: inside this shard_map the in-graph fused driver
    (`core/search_graph.py`) sizes its pow2 tile buckets with `lax.switch`,
    so each shard walks only its selected pages — the same kernel and
    bit-identical results as the eagerly-dispatched host-merge path
    (`MutableShardedProMIPS.search`).
    """
    meta = sharded.meta
    # ``budget``/``cs_prune`` are the legacy knobs for the default config; a
    # user-supplied RuntimeConfig is taken as-is (only k is stamped in —
    # budget=None keeps its documented "all blocks" meaning).
    cfg = runtime if runtime is not None else RuntimeConfig(
        mode="progressive", cs_prune=cs_prune, budget=budget)
    cfg = dataclasses.replace(cfg, k=k)
    fn = _sharded_search_fn(meta, k, mesh, axis, cfg)
    active = eager() and (cfg.obs or _trace.enabled())
    with _span("sharded_fanout", active=active, layer="dispatch",
               metric="sharded.fanout_us") as sp:
        return sp.fence(fn(sharded.arrays, jnp.asarray(queries, jnp.float32)))


@functools.lru_cache(maxsize=32)
def _sharded_search_fn(meta: IndexMeta, k: int, mesh: Mesh, axis: str,
                       cfg: RuntimeConfig):
    """One jit'd shard_map per (meta, k, mesh, axis, config).

    Building the shard_map and calling it EAGERLY per search re-runs its
    Python impl every time — the whole per-shard search is re-traced on
    every call, which dominates wall clock (the in-graph fused driver's
    jaxpr is large: one lax.switch branch per pow2 tile bucket). Caching a
    `jax.jit`-wrapped callable makes repeat searches hit the C++ pjit fast
    path: trace + compile once, then zero Python graph work per call. The
    cache is BOUNDED (each entry pins a compiled executable + its mesh):
    callers that churn through many (k, config, rebuilt-meta) combinations
    evict the oldest executables instead of growing without limit.
    """
    def local(arr_shard, q):
        arrays = jax.tree.map(lambda a: a[0], arr_shard)  # drop shard dim
        ids, scores, stats = runtime_search(arrays, meta, q, cfg)
        # gather per-shard winners; merge on every shard (cheap: k x shards)
        all_ids = jax.lax.all_gather(ids, axis)        # (S, B, k)
        all_scores = jax.lax.all_gather(scores, axis)  # (S, B, k)
        s, b, _ = all_ids.shape
        flat_i = jnp.moveaxis(all_ids, 0, 1).reshape(b, s * k)
        flat_s = jnp.moveaxis(all_scores, 0, 1).reshape(b, s * k)
        best_s, pos = jax.lax.top_k(flat_s, k)
        best_i = jnp.take_along_axis(flat_i, pos, axis=1)
        pages = jax.lax.psum(jnp.sum(stats.pages), axis)
        return best_i, best_s, pages

    in_arr_spec = IndexArrays(**{f: P(axis) for f in IndexArrays._fields})
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(in_arr_spec, P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    ))


class MutableShardedProMIPS:
    """Pod-scale streaming index: one `stream.MutableProMIPS` per shard,
    writes routed by contiguous global-ID range (DESIGN.md §8).

    The initial corpus is split into contiguous row ranges exactly like
    `build_sharded`; each shard owns its range's ids plus a private delta
    segment and tombstone bitmap, so churn on one range never touches the
    other shards' immutable bases. Ids past the initial corpus route to the
    last shard (the append range). Search fans out to the per-shard
    segment-merged runtime and merges k x n_shards (id, score) pairs — the
    same wire economics as `sharded_search`. Shard i lives on device
    ``i % len(jax.devices())``, so on n chips each holds 1/n of the corpus
    and the per-shard searches run concurrently.
    """

    def __init__(self, x: np.ndarray, n_shards: int, *,
                 delta_capacity: Optional[int] = None,
                 auto_compact: bool = False, **build_kwargs):
        from ..stream.mutable import MutableProMIPS

        n = x.shape[0]
        self.bounds = np.linspace(0, n, n_shards + 1).astype(int)
        self.shards = _build_in_parallel(
            lambda b: MutableProMIPS(x[b[0]:b[1]], ids=np.arange(b[0], b[1]),
                                     delta_capacity=delta_capacity,
                                     auto_compact=auto_compact,
                                     **build_kwargs),
            list(zip(self.bounds[:-1], self.bounds[1:])))
        self._place()

    def _place(self) -> None:
        devices = jax.devices()
        for i, shard in enumerate(self.shards):
            shard.device = devices[i % len(devices)]

    @property
    def n_alive(self) -> int:
        return sum(s.n_alive for s in self.shards)

    def _route(self, gids: np.ndarray) -> np.ndarray:
        shard = np.searchsorted(self.bounds, gids, side="right") - 1
        return np.clip(shard, 0, len(self.shards) - 1)

    def _by_shard(self, gids):
        gids = np.atleast_1d(np.asarray(gids, np.int64))
        shard = self._route(gids)
        for s in np.unique(shard):
            yield int(s), np.nonzero(shard == s)[0], gids

    def insert(self, ids, rows) -> None:
        rows = np.atleast_2d(np.asarray(rows, np.float32))
        for s, sel, gids in self._by_shard(ids):
            self.shards[s].insert(gids[sel], rows[sel])

    def delete(self, ids) -> None:
        for s, sel, gids in self._by_shard(ids):
            self.shards[s].delete(gids[sel])

    def update(self, ids, rows) -> None:
        rows = np.atleast_2d(np.asarray(rows, np.float32))
        for s, sel, gids in self._by_shard(ids):
            self.shards[s].update(gids[sel], rows[sel])

    def compact(self) -> None:
        for s in self.shards:
            s.compact()

    def search(self, queries, k: int = 10,
               runtime: Optional[RuntimeConfig] = None):
        """Global top-k under churn: per-shard segment-merged search, then a
        k x n_shards host merge (ties break toward the lower shard, matching
        `sharded_search`'s lowest-index-wins top_k). All shard searches are
        dispatched before any result is pulled to host, so the per-shard
        computations overlap under JAX's async dispatch.

        Returns (ids (B, k), scores (B, k), `ShardedStats`)."""
        active = eager() and (
            _trace.enabled() or (runtime is not None and runtime.obs))
        # the dispatch span is deliberately UNFENCED: fencing each launch
        # would serialize the shards and destroy the async-dispatch overlap
        # this loop exists to create (it times enqueue, not device work)
        with _span("sharded_dispatch", active=active, layer="dispatch",
                   metric="sharded.dispatch_us"):
            launched = [shard.search(queries, k=k, runtime=runtime)
                        for shard in self.shards]
        with _span("sharded_merge", active=active, layer="dispatch",
                   metric="sharded.merge_us") as sp:
            ids_all = [np.asarray(ids) for ids, _, _ in launched]
            scores_all = [np.asarray(scores) for _, scores, _ in launched]
            pages = sum(int(np.sum(np.asarray(st.pages)))
                        for _, _, st in launched)
            cand = sum(int(np.sum(np.asarray(st.candidates)))
                       for _, _, st in launched)
            exhausted = int(np.sum(np.any(
                np.stack([np.asarray(st.exhausted) for _, _, st in launched]),
                axis=0)))
            flat_i = np.concatenate(ids_all, axis=1)
            flat_s = np.concatenate(scores_all, axis=1)
            pos = np.argsort(-flat_s, axis=1, kind="stable")[:, :k]
            stats = ShardedStats(pages=pages, candidates=cand,
                                 exhausted=exhausted,
                                 queries=int(flat_i.shape[0]))
            out = sp.fence((np.take_along_axis(flat_i, pos, axis=1),
                            np.take_along_axis(flat_s, pos, axis=1)))
        return out[0], out[1], stats

    # -- persistence (repro.api save/load, DESIGN.md §9) ---------------------
    def state_dict(self) -> tuple[dict, dict]:
        """(arrays, meta): per-shard `MutableProMIPS.state_dict` outputs with
        ``shard{i}_`` key prefixes, plus the global-ID routing bounds."""
        arrays: dict = {"bounds": np.asarray(self.bounds, np.int64)}
        shard_metas = []
        for i, shard in enumerate(self.shards):
            a, m = shard.state_dict()
            arrays.update({f"shard{i}_{key}": v for key, v in a.items()})
            shard_metas.append(m)
        return arrays, dict(n_shards=len(self.shards), shards=shard_metas)

    @classmethod
    def from_state(cls, arrays: dict, meta: dict) -> "MutableShardedProMIPS":
        from ..stream.mutable import MutableProMIPS

        obj = cls.__new__(cls)
        obj.bounds = np.asarray(arrays["bounds"], np.int64)
        obj.shards = []
        for i in range(int(meta["n_shards"])):
            prefix = f"shard{i}_"
            shard_arrays = {key[len(prefix):]: v for key, v in arrays.items()
                            if key.startswith(prefix)}
            obj.shards.append(
                MutableProMIPS.from_state(shard_arrays, meta["shards"][i]))
        obj._place()
        return obj


def device_put_sharded_index(sharded: ShardedIndex, mesh: Mesh, axis: str = "model"):
    """Ship the stacked host index onto ``mesh``, shard axis over ``axis``.
    Host arrays go straight to their `NamedSharding`: each device receives
    only its own slice (no staging of the whole stack on one device)."""
    arrays = jax.device_put(sharded.arrays, NamedSharding(mesh, P(axis)))
    return ShardedIndex(arrays=arrays, meta=sharded.meta)
