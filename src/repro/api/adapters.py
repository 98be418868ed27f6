"""Registered backends: ProMIPS family + the paper's §VIII-A1 baselines.

Each adapter maps one existing engine onto the `Searcher` protocol:

  promips         core/promips.ProMIPS through the unified device runtime
                  (two_phase FUSED block-sparse verification by default —
                  `core/search_fused.py` eagerly, the traceable
                  `core/search_graph.py` driver inside jit/shard_map; opts
                  select mode="progressive", norm_adaptive, cs_prune,
                  verification="batched"/"scan")
  promips-stream  stream/mutable.MutableProMIPS (mutation + compaction)
  sharded         core/sharded.MutableShardedProMIPS (range-routed shards,
                  mutation, host-side k x shards merge)
  exact           baselines/exact.ExactMIPS (ground-truth full scan)
  h2alsh          baselines/h2_alsh.H2ALSH
  pq              baselines/pq.PQBased
  rangelsh        baselines/range_lsh.RangeLSH

The ProMIPS family derives m / radii / budgets from the `GuaranteeConfig`
(m* from the Section V-B cost model unless the caller overrides ``m``;
x_p = Psi_m^{-1}(p0) is computed inside `build_index` from the same (c, p0));
baselines take (c, p0) as tuning hints only and report guaranteed=False.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

import numpy as np

from ..baselines import ExactMIPS, H2ALSH, PQBased, RangeLSH
from ..core.index import IndexArrays, IndexMeta, ProMIPSIndex
from ..core.promips import ProMIPS
from ..core.runtime import RuntimeConfig
from ..core.runtime import search as runtime_search
from ..core.sharded import MutableShardedProMIPS
from ..obs.trace import span as _span
from ..stream.mutable import MutableProMIPS
from .base import Searcher
from .registry import register
from .types import Capabilities, GuaranteeConfig


def _runtime_from_opts(guarantee: GuaranteeConfig, mode: str,
                       verification: Optional[str],
                       norm_adaptive: Optional[bool],
                       cs_prune: Optional[bool], budget, budget2,
                       prefilter: bool = False,
                       prefilter_eps: Optional[float] = None,
                       obs: bool = False,
                       shape: Optional[tuple] = None) -> RuntimeConfig:
    """Map facade opts onto a `RuntimeConfig` with guarantee-safe defaults:
    budgets stay None (scan every selected block — the Theorem-2 bound
    requires no truncation) unless the caller explicitly trades them.
    ``prefilter`` turns on the quantized-sketch block prefilter; at the
    default ``prefilter_eps=1.0`` it is lossless, so the guarantee holds.
    ``obs`` turns on per-call span/metric instrumentation (DESIGN.md §14);
    results are bit-identical either way.

    ``verification=None`` / ``prefilter_eps=None`` consult the offline
    tuning cache for the ``shape=(n, d)`` point (`repro.tune`, DESIGN.md
    §15) and fall back to the hand-picked "fused" / 1.0 on a miss —
    bit-identical to passing them explicitly. The `RuntimeConfig` keeps its
    own None sentinels for dense_frac/tile_cap (resolved per-search)."""
    if mode == "progressive":
        norm_adaptive = True if norm_adaptive is None else norm_adaptive
        cs_prune = True if cs_prune is None else cs_prune
    if verification is None or prefilter_eps is None:
        from ..tune import cache as _tune_cache
        tuned = (_tune_cache.resolved("runtime", *shape) if shape is not None
                 else dict(_tune_cache.space.HAND_PICKED["runtime"]))
        if verification is None:
            verification = str(tuned["verification"])
        if prefilter_eps is None:
            # a tuned eps only ever describes a prefiltered workload; with
            # the prefilter off the knob is dead and stays at lossless 1.0
            prefilter_eps = (float(tuned["prefilter_eps"]) if prefilter
                             else 1.0)
    return RuntimeConfig(
        k=guarantee.k, budget=budget, budget2=budget2, mode=mode,
        verification=verification,
        norm_adaptive=bool(norm_adaptive) if norm_adaptive is not None else False,
        cs_prune=bool(cs_prune) if cs_prune is not None else False,
        prefilter=bool(prefilter), prefilter_eps=float(prefilter_eps),
        obs=bool(obs))


def _pull_answers(ids, scores, stats, cfg: RuntimeConfig):
    """The batch's answers and stats totals on the host: the search path's
    last device -> host pulls, in the ``pull_answers`` span."""
    with _span("pull_answers", active=cfg.obs or None, layer="pull"):
        return np.asarray(ids), np.asarray(scores), stats.to_dict()


@register
class PromipsSearcher(Searcher):
    """Immutable ProMIPS index.

    ``search_path="device"`` (default) runs the unified jit'd runtime
    (`core/runtime.search`, fused block-sparse Pallas verification);
    ``search_path="host"`` runs the paper-faithful sequential NumPy search
    (`HostSearcher`) with the EXACT resident-4KB-page accounting the
    paper's figures count — the accuracy benchmarks select it through
    `METHOD_SPECS`, not by calling a different API.
    """

    name = "promips"
    capabilities = Capabilities(guaranteed=True, prefilter=True)

    def __init__(self, pm: ProMIPS, runtime: RuntimeConfig,
                 search_path: str = "device"):
        if search_path not in ("device", "host"):
            raise ValueError(f"unknown search_path {search_path!r}; valid "
                             "choices: device, host")
        self.pm = pm
        self.runtime = runtime
        self.search_path = search_path

    @classmethod
    def build(cls, x, *, guarantee, seed, page_bytes, m=None,
              mode="two_phase", verification=None, norm_adaptive=None,
              cs_prune=None, budget=None, budget2=None, norm_strata=None,
              prefilter=False, prefilter_eps=None, obs=False,
              search_path="device", **index_opts) -> "PromipsSearcher":
        plan = guarantee.derive(len(x))
        if norm_strata is None:
            # progressive mode's adaptive radii need norm-homogeneous
            # sub-partitions to bite (DESIGN.md §4)
            norm_strata = 4 if mode == "progressive" else 1
        pm = ProMIPS.build(x, m=plan.m if m is None else int(m),
                           c=guarantee.c, p=guarantee.p0,
                           page_bytes=page_bytes, seed=seed,
                           norm_strata=int(norm_strata), **index_opts)
        return cls(pm, _runtime_from_opts(guarantee, mode, verification,
                                          norm_adaptive, cs_prune,
                                          budget, budget2, prefilter,
                                          prefilter_eps, obs,
                                          shape=(len(x), int(x.shape[1]))),
                   search_path)

    def _search_host(self, queries, k, cfg: RuntimeConfig
                     ) -> Tuple[np.ndarray, np.ndarray, dict]:
        queries = np.asarray(queries, np.float32)
        ids = np.full((len(queries), k), -1, np.int64)
        scores = np.full((len(queries), k), -np.inf, np.float32)
        pages = candidates = exhausted = 0
        for i, q in enumerate(queries):
            if cfg.mode == "progressive":
                qi, qs, st = self.pm.search_host_progressive(
                    q, k=k, cs_prune=cfg.cs_prune)
            else:
                qi, qs, st = self.pm.search_host(
                    q, k=k, norm_adaptive=cfg.norm_adaptive,
                    cs_prune=cfg.cs_prune)
            ids[i], scores[i] = qi, qs
            d = st.to_dict()
            pages += d["pages"]
            candidates += d["candidates"]
            exhausted += d["exhausted"]
        return ids, scores, {"pages": pages, "candidates": candidates,
                             "exhausted": exhausted, "queries": len(queries)}

    def _search(self, queries, k, runtime: Optional[RuntimeConfig] = None
                ) -> Tuple[np.ndarray, np.ndarray, dict]:
        cfg = dataclasses.replace(self.runtime if runtime is None else runtime,
                                  k=k)
        if self.search_path == "host":
            return self._search_host(queries, k, cfg)
        ids, scores, stats = runtime_search(self.pm.arrays, self.pm.meta,
                                            queries, cfg)
        return _pull_answers(ids, scores, stats, cfg)

    @property
    def n(self) -> int:
        return self.pm.meta.n

    @property
    def dim(self) -> int:
        return self.pm.meta.d

    @property
    def index_bytes(self) -> int:
        return self.pm.meta.index_bytes

    def state(self) -> Tuple[dict, dict]:
        arrays = {f: np.asarray(getattr(self.pm.index.arrays, f))
                  for f in IndexArrays._fields}
        return arrays, dict(meta=dataclasses.asdict(self.pm.meta),
                            runtime=dataclasses.asdict(self.runtime),
                            search_path=self.search_path)

    @classmethod
    def from_state(cls, arrays, meta) -> "PromipsSearcher":
        index = ProMIPSIndex(
            arrays=IndexArrays(**{f: np.asarray(arrays[f])
                                  for f in IndexArrays._fields}),
            meta=IndexMeta(**meta["meta"]), layout=None)
        return cls(ProMIPS(index), RuntimeConfig(**meta["runtime"]),
                   meta.get("search_path", "device"))


class _MutableMixin:
    """Forwarders for the mutation contract (inner = stream-family object)."""

    def insert(self, ids, rows) -> None:
        self.inner.insert(ids, rows)

    def delete(self, ids) -> None:
        self.inner.delete(ids)

    def update(self, ids, rows) -> None:
        self.inner.update(ids, rows)

    def alive_items(self):
        return self.inner.alive_items()

    def compact(self) -> None:
        self.inner.compact()

    @property
    def n(self) -> int:
        return self.inner.n_alive

    @property
    def dim(self) -> int:
        return self.inner.d


@register
class StreamSearcher(_MutableMixin, Searcher):
    """Streaming ProMIPS (base + delta segments, tombstones, compaction)."""

    name = "promips-stream"
    capabilities = Capabilities(guaranteed=True, supports_mutation=True,
                                prefilter=True)

    def __init__(self, stream: MutableProMIPS, runtime: RuntimeConfig):
        self.inner = stream
        self.runtime = runtime

    @classmethod
    def build(cls, x, *, guarantee, seed, page_bytes, ids=None, m=None,
              mode="two_phase", verification=None, norm_adaptive=None,
              cs_prune=None, budget=None, budget2=None, norm_strata=1,
              prefilter=False, prefilter_eps=None, obs=False,
              delta_capacity=None, auto_compact=False, **index_opts
              ) -> "StreamSearcher":
        plan = guarantee.derive(len(x))
        stream = MutableProMIPS(
            x, ids=ids, delta_capacity=delta_capacity,
            auto_compact=auto_compact, m=plan.m if m is None else int(m),
            c=guarantee.c, p=guarantee.p0, page_bytes=page_bytes, seed=seed,
            norm_strata=int(norm_strata), **index_opts)
        return cls(stream, _runtime_from_opts(guarantee, mode, verification,
                                              norm_adaptive, cs_prune,
                                              budget, budget2, prefilter,
                                              prefilter_eps, obs,
                                              shape=(len(x),
                                                     int(x.shape[1]))))

    def _search(self, queries, k, runtime: Optional[RuntimeConfig] = None
                ) -> Tuple[np.ndarray, np.ndarray, dict]:
        cfg = self.runtime if runtime is None else runtime
        ids, scores, stats = self.inner.search(queries, k=k, runtime=cfg)
        return _pull_answers(ids, scores, stats, cfg)

    def flush(self, timeout=None) -> None:
        self.inner.join_compaction(timeout)

    # -- durability (robust/wal.py, DESIGN.md §16) ---------------------------
    def enable_wal(self, wal_dir: str, fsync: str = "os") -> str:
        """Make this index crash-safe: write an initial checksummed snapshot
        under ``wal_dir/snapshot`` and attach a write-ahead log at
        ``wal_dir/wal.log`` — every subsequent acknowledged mutation is
        logged before it is applied. `repro.robust.recover(wal_dir)`
        restores the exact state after a crash."""
        from ..robust.wal import WriteAheadLog
        self.flush()
        self.save(os.path.join(wal_dir, "snapshot"))
        self.inner.mark_wal_floor()
        self.inner.attach_wal(
            WriteAheadLog(os.path.join(wal_dir, "wal.log"), fsync=fsync,
                          fresh=True))
        self._wal_dir = wal_dir
        return wal_dir

    def checkpoint(self) -> str:
        """Fold the WAL into a fresh snapshot: save (atomic, checksummed),
        then truncate the log. A crash at ANY point is safe — the snapshot
        persists ``wal_seq`` and replay skips records at or below it, so
        dying between the save and the truncate only replays no-ops."""
        if getattr(self, "_wal_dir", None) is None:
            raise RuntimeError("no WAL attached (build with wal_dir= or "
                               "call enable_wal() first)")
        self.flush()
        self.save(os.path.join(self._wal_dir, "snapshot"))
        self.inner.mark_wal_floor()
        self.inner._wal.reset()
        return self._wal_dir

    def wal_lag(self) -> int:
        return self.inner.wal_lag()

    def maintenance_status(self) -> dict:
        """Compaction + WAL health for `engine.health()`."""
        comp = (self.inner.compactor.status()
                if self.inner.compactor is not None else None)
        return {"compaction": comp, "wal_attached": self.inner._wal is not None,
                "wal_lag": self.inner.wal_lag()}

    @property
    def index_bytes(self) -> int:
        base = self.inner.meta.index_bytes
        delta = self.inner._delta
        return base + delta.x.nbytes + delta.gids.nbytes + delta.alive.nbytes

    def state(self) -> Tuple[dict, dict]:
        self.flush()
        arrays, meta = self.inner.state_dict()
        return arrays, dict(meta, runtime=dataclasses.asdict(self.runtime))

    @classmethod
    def from_state(cls, arrays, meta) -> "StreamSearcher":
        runtime = RuntimeConfig(**meta["runtime"])
        return cls(MutableProMIPS.from_state(arrays, meta), runtime)


@register
class ShardedSearcher(_MutableMixin, Searcher):
    """Range-routed multi-shard streaming index (host k x shards merge)."""

    name = "sharded"
    capabilities = Capabilities(guaranteed=True, supports_mutation=True,
                                supports_sharding=True, prefilter=True)

    def __init__(self, sharded: MutableShardedProMIPS, runtime: RuntimeConfig):
        self.inner = sharded
        self.runtime = runtime

    @classmethod
    def build(cls, x, *, guarantee, seed, page_bytes, n_shards=2, m=None,
              mode="two_phase", verification=None, norm_adaptive=None,
              cs_prune=None, budget=None, budget2=None, norm_strata=1,
              prefilter=False, prefilter_eps=None, obs=False,
              delta_capacity=None, auto_compact=False, **index_opts
              ) -> "ShardedSearcher":
        # m* is derived from the PER-SHARD corpus size (each shard owns its
        # own Quick-Probe group table over ~n/n_shards points)
        plan = guarantee.derive(max(len(x) // max(int(n_shards), 1), 1))
        sharded = MutableShardedProMIPS(
            x, int(n_shards), delta_capacity=delta_capacity,
            auto_compact=auto_compact, m=plan.m if m is None else int(m),
            c=guarantee.c, p=guarantee.p0, page_bytes=page_bytes, seed=seed,
            norm_strata=int(norm_strata), **index_opts)
        # shards each hold ~n/n_shards points, which is what the tuned-entry
        # shape key should match (the per-shard search is what runs)
        return cls(sharded, _runtime_from_opts(
            guarantee, mode, verification, norm_adaptive, cs_prune,
            budget, budget2, prefilter, prefilter_eps, obs,
            shape=(max(len(x) // max(int(n_shards), 1), 1),
                   int(x.shape[1]))))

    def _search(self, queries, k, runtime: Optional[RuntimeConfig] = None
                ) -> Tuple[np.ndarray, np.ndarray, dict]:
        cfg = self.runtime if runtime is None else runtime
        ids, scores, stats = self.inner.search(queries, k=k, runtime=cfg)
        return _pull_answers(ids, scores, stats, cfg)

    def alive_items(self):
        gids, rows = [], []
        for shard in self.inner.shards:
            g, r = shard.alive_items()
            gids.append(g)
            rows.append(r)
        return np.concatenate(gids), np.concatenate(rows)

    def flush(self, timeout=None) -> None:
        for shard in self.inner.shards:
            shard.join_compaction(timeout)

    @property
    def dim(self) -> int:
        return self.inner.shards[0].d

    def maintenance_status(self) -> dict:
        """Aggregated per-shard compaction health (`engine.health()` hook):
        worst-case rollup — any shard's latched error surfaces in the
        ``compaction`` rollup; per-shard detail rides along."""
        per = [s.compactor.status() if s.compactor is not None else None
               for s in self.inner.shards]
        live = [p for p in per if p is not None]
        comp = None
        if live:
            comp = {
                "in_flight": any(p["in_flight"] for p in live),
                "runs": sum(p["runs"] for p in live),
                "failures": sum(p["failures"] for p in live),
                "retries": sum(p["retries"] for p in live),
                "error_latched": any(p["error_latched"] for p in live),
                "last_error": next((p["last_error"] for p in live
                                    if p["last_error"]), None),
                "shards": per,
            }
        return {"compaction": comp, "wal_attached": False, "wal_lag": 0}

    @property
    def index_bytes(self) -> int:
        return sum(s.meta.index_bytes for s in self.inner.shards)

    def state(self) -> Tuple[dict, dict]:
        self.flush()
        arrays, meta = self.inner.state_dict()
        return arrays, dict(meta, runtime=dataclasses.asdict(self.runtime))

    @classmethod
    def from_state(cls, arrays, meta) -> "ShardedSearcher":
        runtime = RuntimeConfig(**meta["runtime"])
        return cls(MutableShardedProMIPS.from_state(arrays, meta), runtime)


# ---------------------------------------------------------------------------
# Baselines: deterministic rebuild persistence (raw rows + ctor kwargs + seed)
# ---------------------------------------------------------------------------

class _BaselineSearcher(Searcher):
    """Shared adapter for the numpy baselines (single-query engines).

    Persistence saves the raw rows plus the constructor kwargs (explicit
    seed included); load re-runs the deterministic build, which is
    bit-identical by the seeded-RNG contract — the same trick compaction
    uses for `rebuild_base`.
    """

    inner_cls: type = None           # set by subclasses
    seeded = True                    # inner_cls accepts a ``seed`` kwarg

    def __init__(self, inner, x: np.ndarray, ctor: dict):
        self.inner = inner
        self._x = x
        self._ctor = ctor

    @classmethod
    def build(cls, x, *, guarantee, seed, page_bytes, **opts):
        ctor = dict(opts, page_bytes=int(page_bytes))
        if cls.seeded:
            ctor.setdefault("seed", int(seed))
        return cls(cls.inner_cls(**ctor).build(x), x, ctor)

    def _search(self, queries, k, **_ignored
                ) -> Tuple[np.ndarray, np.ndarray, dict]:
        queries = np.asarray(queries, np.float32)  # numpy engines below
        ids = np.full((len(queries), k), -1, np.int64)
        scores = np.full((len(queries), k), -np.inf, np.float32)
        pages = candidates = 0
        for i, q in enumerate(queries):
            qi, qs, st = self.inner.search(q, k=k)
            ids[i, : len(qi)] = qi
            scores[i, : len(qs)] = qs
            pages += int(st["pages"])
            candidates += int(st["candidates"])
        return ids, scores, {"pages": pages, "candidates": candidates,
                             "exhausted": 0, "queries": len(queries)}

    @property
    def n(self) -> int:
        return len(self._x)

    @property
    def dim(self) -> int:
        return int(self._x.shape[1])

    @property
    def index_bytes(self) -> int:
        return int(self.inner.index_bytes)

    def state(self) -> Tuple[dict, dict]:
        return {"x": self._x}, dict(ctor=self._ctor)

    @classmethod
    def from_state(cls, arrays, meta) -> "_BaselineSearcher":
        x = np.ascontiguousarray(arrays["x"], np.float32)
        ctor = dict(meta["ctor"])
        return cls(cls.inner_cls(**ctor).build(x), x, ctor)


@register
class ExactSearcher(_BaselineSearcher):
    name = "exact"
    # the full scan IS the guarantee (c=1, p0=1) and pays n/page_rows pages
    capabilities = Capabilities(guaranteed=True)
    inner_cls = ExactMIPS
    seeded = False


@register
class H2ALSHSearcher(_BaselineSearcher):
    name = "h2alsh"
    capabilities = Capabilities()
    inner_cls = H2ALSH


@register
class PQSearcher(_BaselineSearcher):
    name = "pq"
    capabilities = Capabilities()
    inner_cls = PQBased


@register
class RangeLSHSearcher(_BaselineSearcher):
    name = "rangelsh"
    capabilities = Capabilities()
    inner_cls = RangeLSH


__all__ = ["PromipsSearcher", "StreamSearcher", "ShardedSearcher",
           "ExactSearcher", "H2ALSHSearcher", "PQSearcher",
           "RangeLSHSearcher"]
