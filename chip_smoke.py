#!/usr/bin/env python3
"""Chip smoke test: the ProMIPS main path on a TPU at a real corpus size.

The corpus is the paper's Yahoo! Music PureSVD proxy at its published size
(n = 624,961 rows, d = 300, generated from ``--seed``); its f32 rows live on
the device. Every answer is compared with the numpy exact top-k
(`baselines.exact.exact_topk`): recall and the Theorem-2 success rate must
reach ``p0 - 3 sqrt(p0 (1 - p0) / n_queries)`` (the floor
tests/test_guarantees.py uses) and the overall ratio must reach c.

  python3 chip_smoke.py              # one chip: build, search, stream
  python3 chip_smoke.py --chips 4    # four chips: the sharded paths only

One chip runs `api.build(backend="promips")` under the default
`GuaranteeConfig`, answers 64-query batches with the sketch prefilter off
and then on (so `block_mips` and `sketch_scores` run as Mosaic kernels),
checks that the compiled fused round holds a ``tpu_custom_call`` and that
the main search never took the jnp-oracle route, checks the returned
scores against numpy f32 inner products, and runs a short insert / delete /
search round on ``backend="promips-stream"``. ``--chips 4`` runs
`sharded_search` under shard_map over a 4-chip mesh and the api
``backend="sharded"`` (one shard per chip), and prints the bytes each chip
holds.

Lines before the last are for reading only: times are host-clock, and a
first batch includes compilation. The last line, printed only when every
phase passed on a TPU, is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``. Exit status: 0
when every phase passed, 1 when a phase failed, 2 when no TPU (or too few
chips) was found.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

K = 10
BATCH = 64
N_BATCHES = 3
STREAM_INSERTS = 4096
STREAM_DELETES = 4096
SCORE_RTOL = 1e-5       # returned scores vs numpy inner products


def log(msg: str) -> None:
    print(msg, flush=True)


class Phases:
    """Runs named phases; a phase that raises or fails a check is recorded
    and the run goes on, so one chip call reports every fault it can."""

    def __init__(self):
        self.failed: list[str] = []

    def check(self, ok: bool, what: str) -> None:
        log(f"  {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(what)

    def run(self, name: str, fn, *args):
        log(f"== {name}")
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:  # reported and counted: the run exits non-zero
            traceback.print_exc(file=sys.stdout)
            self.failed.append(f"{name}: raised")
            out = None
        log(f"   {name}: {time.perf_counter() - t0:.3f} s")
        return out


def quality(ids, scores, eids, escores, c: float, p0: float) -> dict:
    from repro.core import overall_ratio, recall_at_k

    n_q = len(ids)
    s = np.asarray(scores, np.float64)
    e = np.asarray(escores, np.float64)
    # Theorem-2 success: every rank meets the c-approximation (ranks whose
    # exact score is non-positive are vacuous), as tests/test_guarantees.py
    success = float(np.mean(((s >= c * e - 1e-5) | (e <= 0.0)).all(axis=1)))
    return {
        "recall": float(np.mean([recall_at_k(ids[i], eids[i])
                                 for i in range(n_q)])),
        "ratio": float(np.mean([overall_ratio(scores[i], escores[i])
                                for i in range(n_q)])),
        "success": success,
        "floor": p0 - 3.0 * math.sqrt(p0 * (1.0 - p0) / n_q),
    }


def check_quality(ph: Phases, label: str, q: dict, c: float) -> None:
    log(f"  {label}: recall={q['recall']:.4f} ratio={q['ratio']:.6f} "
        f"success={q['success']:.4f} floor={q['floor']:.4f}")
    ph.check(q["recall"] >= q["floor"], f"{label} recall >= Theorem-2 floor")
    ph.check(q["success"] >= q["floor"],
             f"{label} success rate >= Theorem-2 floor")
    ph.check(q["ratio"] >= c, f"{label} overall ratio >= c")


def device_bytes(tree) -> dict:
    """Bytes of ``tree``'s jax arrays held on each device id."""
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return dict(sorted(out.items()))


def oracle_routes() -> int:
    from repro.obs import metrics

    return int(metrics.snapshot().get("kernels.block_mips_oracle", 0))


def corpus(seed: int):
    from repro.data.synthetic import paper_dataset, paper_queries

    x = paper_dataset("yahoo", scaled=False, seed=seed)
    q = paper_queries("yahoo", BATCH * N_BATCHES, seed=seed + 1)
    return x, q


def exact(x, q):
    from repro.baselines.exact import exact_topk

    t0 = time.perf_counter()
    eids, escores = exact_topk(x, q, K)
    log(f"  numpy exact top-{K} of {len(q)} queries: "
        f"{time.perf_counter() - t0:.3f} s")
    return eids, escores


def search_batches(ph: Phases, label: str, s, q, eids, escores, guarantee,
                   **opts):
    """Answer the queries in BATCH-sized calls; check them against exact."""
    n_blocks = s.pm.meta.n_blocks
    ids, scores, secs, pages = [], [], [], 0
    for b in range(N_BATCHES):
        qb = q[b * BATCH:(b + 1) * BATCH]
        t0 = time.perf_counter()
        res = s.search(qb, **opts)
        secs.append(time.perf_counter() - t0)
        ids.append(res.ids)
        scores.append(res.scores)
        pages += res.pages
    steady = min(secs[1:])
    log(f"  {label}: first batch {secs[0]:.3f} s (compiles), steady batch "
        f"{steady:.4f} s = {steady / BATCH * 1e6:.1f} us/query, "
        f"compile ~{secs[0] - steady:.3f} s, pages_frac="
        f"{pages / (len(q) * n_blocks):.4f}")
    ids, scores = np.concatenate(ids), np.concatenate(scores)
    check_quality(ph, label, quality(ids, scores, eids, escores,
                                     guarantee.c, guarantee.p0), guarantee.c)
    return ids, scores


def check_scores(ph: Phases, x, q, ids, scores) -> None:
    """Returned scores are the API's exact f32 inner products: compare with
    numpy, and record what a default-precision device dot would give."""
    import jax.numpy as jnp

    cand = x[np.maximum(ids, 0)]                         # (B, k, d)
    ref = np.einsum("bkd,bd->bk", cand.astype(np.float64),
                    q.astype(np.float64))
    scale = float(np.max(np.abs(ref)))
    err = float(np.max(np.abs(scores - ref))) / scale
    dflt = np.asarray(jnp.einsum("bkd,bd->bk", jnp.asarray(cand),
                                 jnp.asarray(q)))
    err_dflt = float(np.max(np.abs(dflt - ref))) / scale
    log(f"  returned-score error vs numpy: {err:.3e} of max |score| "
        f"(a default-precision device einsum: {err_dflt:.3e})")
    ph.check(err <= SCORE_RTOL, f"returned scores match numpy to "
             f"{SCORE_RTOL:g} relative")


def mosaic_in_fused_round(ph: Phases, s, q) -> None:
    """The compiled fused verification round and the sketch prefilter must
    hold Mosaic kernels (tpu_custom_call), not the jnp oracles."""
    import jax.numpy as jnp

    from repro.core import search_fused as sf

    arrays, meta = s.pm.arrays, s.pm.meta
    ns = min(1024, meta.n_blocks)
    qj = jnp.asarray(q[:BATCH])
    hlo = sf._verify.lower(
        arrays, qj, jnp.arange(ns, dtype=jnp.int32),
        jnp.ones((BATCH, ns), bool), jnp.full((BATCH, K), -jnp.inf),
        jnp.full((BATCH, K), -1, jnp.int32), jnp.zeros((BATCH,)),
        k=K, page_rows=meta.page_rows, dense=False,
        use_pallas=s.runtime.use_pallas).compile().as_text()
    ph.check("tpu_custom_call" in hlo,
             "fused verify round compiles to a Mosaic tpu_custom_call")
    hlo = sf._prefilter1.lower(
        arrays, qj, jnp.ones((BATCH, meta.n_blocks), bool), K,
        meta.page_rows, 1.0, s.runtime.use_pallas).compile().as_text()
    ph.check("tpu_custom_call" in hlo,
             "sketch prefilter compiles to a Mosaic tpu_custom_call")


def one_chip(ph: Phases, seed: int) -> None:
    import jax

    from repro import api
    from repro.baselines.exact import exact_topk
    from repro.data.synthetic import mf_factors

    guarantee = api.GuaranteeConfig()
    x, q = ph.run("corpus", corpus, seed)
    log(f"  yahoo corpus {x.shape} {x.dtype} ({x.nbytes / 2**20:.1f} MiB), "
        f"queries {q.shape}")
    eids, escores = ph.run("exact reference", exact, x, q)

    t0 = time.perf_counter()
    s = ph.run("build promips", lambda: api.build(
        x, backend="promips", guarantee=guarantee, seed=seed))
    if s is None:
        return
    arrays = jax.block_until_ready(s.pm.arrays)
    meta = s.pm.meta
    log(f"  build + ship {time.perf_counter() - t0:.3f} s: m={meta.m} "
        f"page_rows={meta.page_rows} n_blocks={meta.n_blocks} "
        f"sk_subspaces={meta.sk_subspaces}; on device "
        f"{device_bytes(arrays)} bytes, x {arrays.x.nbytes} bytes")

    before = oracle_routes()
    out = ph.run("search, prefilter off", search_batches, ph, "prefilter off",
                 s, q, eids, escores, guarantee)
    ph.check(oracle_routes() == before,
             "main search took no jnp-oracle route (kernels.block_mips_oracle "
             f"{before} -> {oracle_routes()})")
    if out is not None:
        ph.run("score precision", check_scores, ph, x, q[:len(out[0])],
               out[0], out[1])
    rt = dataclasses.replace(s.runtime, prefilter=True)
    ph.run("search, prefilter on", lambda: search_batches(
        ph, "prefilter on", s, q, eids, escores, guarantee, runtime=rt))
    ph.check(oracle_routes() == before,
             "prefiltered search took no jnp-oracle route")
    ph.run("mosaic kernels in the fused round", mosaic_in_fused_round, ph, s,
           q)
    del s, arrays

    def stream():
        st = api.build(x, backend="promips-stream", guarantee=guarantee,
                       seed=seed)
        n = len(x)
        st.insert(np.arange(n, n + STREAM_INSERTS),
                  mf_factors(STREAM_INSERTS, x.shape[1], 32, decay=0.15,
                             norm_tail=0.3, seed=seed + 3))
        rng = np.random.RandomState(seed + 2)
        st.delete(rng.choice(n, STREAM_DELETES, replace=False))
        qb = q[:BATCH]
        routes = oracle_routes()
        t0 = time.perf_counter()
        res = st.search(qb)
        log(f"  stream search ({STREAM_INSERTS} inserted, {STREAM_DELETES} "
            f"deleted): {time.perf_counter() - t0:.3f} s incl. compile; "
            f"kernels.block_mips_oracle {routes} -> {oracle_routes()}")
        gids, rows = st.alive_items()
        eidx, esc = exact_topk(rows, qb, K)
        check_quality(ph, "stream", quality(res.ids, res.scores, gids[eidx],
                                            esc, guarantee.c, guarantee.p0),
                      guarantee.c)

    ph.run("stream insert/delete/search", stream)


def four_chips(ph: Phases, seed: int) -> None:
    import jax

    from repro import api
    from repro.core.runtime import RuntimeConfig
    from repro.core.sharded import (build_sharded, device_put_sharded_index,
                                    sharded_search)
    from repro.launch.mesh import make_mesh_compat

    n_chips = 4
    guarantee = api.GuaranteeConfig()
    x, q = ph.run("corpus", corpus, seed)
    log(f"  yahoo corpus {x.shape} ({x.nbytes} bytes), queries {q.shape}")
    eids, escores = ph.run("exact reference", exact, x, q)

    def shard_mapped():
        plan = guarantee.derive(len(x) // n_chips)
        t0 = time.perf_counter()
        sh = build_sharded(x, n_chips, m=plan.m, c=guarantee.c,
                           p=guarantee.p0, seed=seed)
        mesh = make_mesh_compat((n_chips,), ("model",))
        shd = device_put_sharded_index(sh, mesh)
        per_dev = device_bytes(jax.block_until_ready(shd.arrays))
        log(f"  build_sharded + device_put {time.perf_counter() - t0:.3f} s;"
            f" bytes per device {per_dev}; x per device "
            f"{device_bytes(shd.arrays.x)}")
        ids, scores, secs = [], [], []
        for b in range(N_BATCHES):
            qb = q[b * BATCH:(b + 1) * BATCH]
            t0 = time.perf_counter()
            i, sc, _ = jax.block_until_ready(
                sharded_search(shd, qb, K, mesh, runtime=RuntimeConfig(k=K)))
            secs.append(time.perf_counter() - t0)
            ids.append(np.asarray(i))
            scores.append(np.asarray(sc))
        log(f"  sharded_search: first batch {secs[0]:.3f} s (compiles), "
            f"steady {min(secs[1:]):.4f} s per {BATCH} queries")
        check_quality(ph, "sharded_search", quality(
            np.concatenate(ids), np.concatenate(scores), eids, escores,
            guarantee.c, guarantee.p0), guarantee.c)

    def api_sharded():
        t0 = time.perf_counter()
        s = api.build(x, backend="sharded", guarantee=guarantee, seed=seed,
                      n_shards=n_chips)
        snaps = [shard.snapshot().arrays for shard in s.inner.shards]
        log(f"  api sharded build {time.perf_counter() - t0:.3f} s; bytes "
            f"per device {device_bytes(snaps)}; x per device "
            f"{device_bytes([a.x for a in snaps])}")
        ph.check(len(device_bytes(snaps)) == n_chips,
                 "api sharded places one shard on each chip")
        ids, scores, secs = [], [], []
        for b in range(N_BATCHES):
            qb = q[b * BATCH:(b + 1) * BATCH]
            t0 = time.perf_counter()
            res = s.search(qb)
            secs.append(time.perf_counter() - t0)
            ids.append(res.ids)
            scores.append(res.scores)
        log(f"  api sharded: first batch {secs[0]:.3f} s (compiles), "
            f"steady {min(secs[1:]):.4f} s per {BATCH} queries")
        check_quality(ph, "api sharded", quality(
            np.concatenate(ids), np.concatenate(scores), eids, escores,
            guarantee.c, guarantee.p0), guarantee.c)

    ph.run("sharded_search under shard_map", shard_mapped)
    ph.run("api sharded backend", api_sharded)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths, over four chips")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache(ROOT)
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__}: {len(devices)} x {dev.platform} "
        f"{dev.device_kind}; compile cache {cache}")
    if dev.platform != "tpu":
        print("no TPU found: this smoke test runs on the chip only",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    ph = Phases()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(ph, args.seed)
    log(f"total {time.perf_counter() - t0:.3f} s")
    if ph.failed:
        log(f"FAILED: {ph.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
