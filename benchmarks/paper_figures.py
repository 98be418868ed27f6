"""One benchmark per paper table/figure (§VIII). Each function returns CSV
rows (name, us_per_call, derived). Every method is built and searched
through the unified `repro.api` facade (`common.METHOD_SPECS` names the
registry backends) — no per-backend build/search glue lives here."""
from __future__ import annotations

import time

import numpy as np

from .common import (BENCH_SETS, METHOD_SPECS, build_backend, build_method,
                     evaluate, load)

_built = {}


def _get(name, label):
    key = (name, label)
    if key not in _built:
        _built[key] = build_method(name, label)
    return _built[key]


def fig4a_index_size():
    """Fig. 4(a): index size per method per dataset (MB)."""
    rows = []
    for name in BENCH_SETS:
        for label in METHOD_SPECS:
            s = _get(name, label)
            rows.append((f"fig4a/{name}/{label}", 0.0,
                         f"index_mb={s.index_bytes/1e6:.2f}"))
    return rows


def fig4b_preprocessing_time():
    """Fig. 4(b): pre-processing (build) time per method (s)."""
    rows = []
    for name in BENCH_SETS:
        for label in METHOD_SPECS:
            secs = _get(name, label).build_seconds
            rows.append((f"fig4b/{name}/{label}", secs * 1e6,
                         f"build_s={secs:.2f}"))
    return rows


def _accuracy_fig(metric):
    rows = []
    for name in BENCH_SETS:
        for label in METHOD_SPECS:
            for k in (10, 50, 100):
                m = evaluate(_get(name, label), name, k)
                rows.append((f"{metric}/{name}/{label}/k{k}", m["cpu_us"],
                             f"ratio={m['ratio']:.4f};recall={m['recall']:.3f};"
                             f"pages={m['pages']:.0f};total_us={m['total_us']:.0f}"))
    return rows


def fig5_6_overall_ratio_recall():
    """Figs. 5-6: overall ratio + recall vs k (plus pages/time, reused by 7-9)."""
    return _accuracy_fig("fig5-9")


def fig10_impact_of_c():
    """Fig. 10: ProMIPS accuracy/efficiency vs approximation ratio c."""
    rows = []
    for name in ("netflix", "sift"):
        for c in (0.7, 0.8, 0.9):
            s = build_backend(name, "promips", c=c, search_path="host")
            m = evaluate(s, name, 10)
            rows.append((f"fig10/{name}/c{c}", m["cpu_us"],
                         f"ratio={m['ratio']:.4f};pages={m['pages']:.0f};"
                         f"guarantee_frac={m['guarantee_frac']:.2f}"))
    return rows


def fig11_impact_of_p():
    """Fig. 11: ProMIPS accuracy/efficiency vs guarantee probability p0."""
    rows = []
    for name in ("netflix", "sift"):
        for p0 in (0.3, 0.5, 0.7, 0.9):
            s = build_backend(name, "promips", p0=p0, search_path="host")
            m = evaluate(s, name, 10)
            rows.append((f"fig11/{name}/p{p0}", m["cpu_us"],
                         f"ratio={m['ratio']:.4f};pages={m['pages']:.0f};"
                         f"guarantee_frac={m['guarantee_frac']:.2f}"))
    return rows


def table2_complexity_scaling():
    """Table II: search cost scaling in n (ProMIPS O(d + n log n))."""
    from repro import api
    from repro.data.synthetic import mf_factors
    rows = []
    prev = None
    for n in (2000, 8000, 32000):
        x = mf_factors(n, 128, 24, decay=0.2, seed=0, norm_tail=0.3)
        q = mf_factors(8, 128, 24, decay=0.2, seed=1)
        t0 = time.time()
        s = api.build(x, backend="promips", m=8, mode="progressive",
                      norm_strata=4)
        build_s = time.time() - t0
        s.search(q, k=10)  # compile
        t0 = time.perf_counter()
        s.search(q, k=10)
        us = (time.perf_counter() - t0) / 8 * 1e6
        growth = "" if prev is None else f";time_growth={us/prev:.2f}x_for_4x_n"
        prev = us
        rows.append((f"table2/n{n}", us, f"build_s={build_s:.2f}{growth}"))
    return rows


def ablation_beyond_paper():
    """Beyond-paper ladder: paper-faithful -> +norm-adaptive -> +CS-prune ->
    +progressive (+norm-strata layout). One backend, four option sets —
    the §Perf algorithmic story, expressed as facade build options."""
    variants = [
        ("paper", {}),
        ("+norm-adaptive", dict(norm_adaptive=True)),
        ("+cs-prune", dict(norm_adaptive=True, cs_prune=True)),
        ("+progressive+strata", dict(mode="progressive", norm_strata=4)),
    ]
    rows = []
    for name in ("netflix", "sift"):
        for label, opts in variants:
            s = build_backend(name, "promips", search_path="host", **opts)
            m = evaluate(s, name, 10)
            rows.append((f"ablation/{name}/{label}", m["cpu_us"],
                         f"ratio={m['ratio']:.4f};pages={m['pages']:.0f};"
                         f"guarantee_frac={m['guarantee_frac']:.2f}"))
    return rows


def bench_api(quick: bool = True):
    """Registry sweep (`benchmarks/run.py --api`): for EVERY registered
    backend — build time, index bytes on disk (real npz+json footprint after
    `save`), µs/query on a 64-query batch, and recall@10 vs exact. Writes
    BENCH_api.json at the repo root."""
    import json
    import os
    import shutil
    import tempfile

    from repro import api
    from repro.baselines.exact import exact_topk
    from repro.core import recall_at_k
    from repro.data.synthetic import mf_factors

    n, d, n_q = (8000, 64, 64) if quick else (20000, 96, 64)
    x = mf_factors(n, d, 16, decay=0.5, seed=0, norm_tail=0.3)
    q = mf_factors(n_q, d, 16, decay=0.5, seed=1)
    eids, _ = exact_topk(x, q, 10)
    guarantee = api.GuaranteeConfig(c=0.9, p0=0.6, k=10)

    rec = {"n": n, "d": d, "batch": n_q, "k": 10,
           "guarantee": guarantee.to_dict(), "backends": {}}
    rows = []
    tmp = tempfile.mkdtemp(prefix="bench_api_")
    try:
        for backend in api.backends():
            prune = (dict(norm_adaptive=True, cs_prune=True)
                     if api.get_backend(backend).capabilities.guaranteed
                     and backend != "exact" else {})
            t0 = time.perf_counter()
            s = api.build(x, backend=backend, guarantee=guarantee, seed=0,
                          **prune)
            build_s = time.perf_counter() - t0

            path = os.path.join(tmp, backend)
            s.save(path)
            disk = api.saved_bytes(path)

            s.search(q, k=10)  # warm-up / compile
            reps = 3
            t0 = time.perf_counter()
            for _ in range(reps):
                res = s.search(q, k=10)
            us = (time.perf_counter() - t0) / (reps * n_q) * 1e6
            recall = float(np.mean([recall_at_k(res.ids[i], eids[i])
                                    for i in range(n_q)]))
            cell = dict(build_s=build_s, disk_bytes=disk, us_per_query=us,
                        recall_vs_exact=recall,
                        pages_per_query=res.pages / n_q,
                        capabilities=vars(s.capabilities).copy())
            rec["backends"][backend] = cell
            rows.append((f"api/{backend}", us,
                         f"recall={recall:.3f};disk_mb={disk/1e6:.2f};"
                         f"build_s={build_s:.2f}"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # Large-n point (>= 100k): the regime where pruning pays off — the
    # `promips` facade backend must beat the `exact` dense scan at
    # recall >= 0.95 (PR 4 acceptance). Restricted to those two backends:
    # the numpy LSH/PQ baselines take minutes per 100k-corpus sweep and add
    # nothing to the pruned-vs-dense comparison this point exists for.
    cfg = LARGE_N
    xl, ql = _large_corpus()
    eids_l, _ = exact_topk(xl, ql, cfg["k"])
    large_guarantee = api.GuaranteeConfig(c=cfg["c"], p0=cfg["p0"], k=cfg["k"])
    rec["large_n"] = {"n": cfg["n"], "d": cfg["d"], "batch": cfg["n_q"],
                      "k": cfg["k"], "guarantee": large_guarantee.to_dict(),
                      "backends": {}}
    promips_opts = dict(m=cfg["m"], k_p=cfg["k_p"], k_sp=cfg["k_sp"],
                        norm_strata=cfg["norm_strata"], norm_adaptive=True,
                        cs_prune=True)
    searchers, times = {}, {}
    for backend, opts in (("exact", {}), ("promips", promips_opts)):
        t0 = time.perf_counter()
        s = api.build(xl, backend=backend, guarantee=large_guarantee, seed=0,
                      **opts)
        build_s = time.perf_counter() - t0
        s.search(ql, k=cfg["k"])  # warm-up / compile
        searchers[backend] = (s, build_s)
        times[backend] = []
    # prefilter-on variant reuses the SAME built index (the sketch is built
    # unconditionally) with only the runtime knob flipped — no second
    # 100k-corpus build.
    import dataclasses as _dc
    s_pm, build_pm = searchers["promips"]
    s_pf = type(s_pm)(s_pm.pm,
                      _dc.replace(s_pm.runtime, prefilter=True,
                                  prefilter_eps=PREFILTER_EPS),
                      s_pm.search_path)
    s_pf.search(ql, k=cfg["k"])  # warm-up / compile
    searchers["promips-prefilter"] = (s_pf, build_pm)
    times["promips-prefilter"] = []
    # interleaved reps + medians: both backends see the same host
    # conditions (this box's wall clock jitters +-20% across seconds)
    results = {}
    for _ in range(5):
        for backend, (s, _) in searchers.items():
            t0 = time.perf_counter()
            results[backend] = s.search(ql, k=cfg["k"])
            times[backend].append(time.perf_counter() - t0)
    for backend, (s, build_s) in searchers.items():
        res = results[backend]
        us = float(np.median(times[backend])) / cfg["n_q"] * 1e6
        recall = float(np.mean([recall_at_k(res.ids[i], eids_l[i])
                                for i in range(cfg["n_q"])]))
        rec["large_n"]["backends"][backend] = dict(
            build_s=build_s, us_per_query=us, recall_vs_exact=recall,
            pages_per_query=res.pages / cfg["n_q"])
        rows.append((f"api/large_n{cfg['n']}/{backend}", us,
                     f"recall={recall:.3f};build_s={build_s:.1f}"))
    ratios = [te / tp for te, tp in zip(times["exact"], times["promips"])]
    rec["large_n"]["promips_vs_exact_speedup"] = float(np.median(ratios))
    rec["large_n"]["promips_beats_exact"] = (
        rec["large_n"]["promips_vs_exact_speedup"] > 1.0)
    rows.append(("api/large_n/promips_vs_exact", 0.0,
                 f"x{rec['large_n']['promips_vs_exact_speedup']:.2f}"))
    # prefilter on/off page fractions through the facade (history.jsonl
    # carries these per commit; ci.sh guards the smoke-scale counterpart)
    nb = s_pm.pm.meta.n_blocks
    cells = rec["large_n"]["backends"]
    rec["large_n"]["prefilter_eps"] = PREFILTER_EPS
    rec["large_n"]["prefilter_on_pages_frac"] = (
        cells["promips-prefilter"]["pages_per_query"] / nb)
    rec["large_n"]["prefilter_off_pages_frac"] = (
        cells["promips"]["pages_per_query"] / nb)
    rows.append(("api/large_n/prefilter_pages_frac", 0.0,
                 f"{rec['large_n']['prefilter_on_pages_frac']:.3f} vs "
                 f"{rec['large_n']['prefilter_off_pages_frac']:.3f} off"))

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    with open(os.path.join(root, "BENCH_api.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rows


# Large-n benchmark point (n >= 100k, SIFT-like d=128) where pruning
# actually pays off: strong norm decay + long-tail scales, norm-stratified
# layout, m=16 projections. Shared by `bench_search_runtime` and `bench_api`
# so both --quick and --api record the same regime (recall vs exact is
# 1.000 at these settings; pages ~0.8 of blocks). d=128 matters: the
# per-query dense scan is bandwidth-bound in n*d while the fused batch
# path's non-matmul work is d-independent, so this is the regime the
# index's batched amortization genuinely wins on CPU too.
LARGE_N = dict(n=100_000, d=128, rank=16, decay=0.5, norm_tail=0.6,
               m=16, k_p=8, k_sp=8, norm_strata=8, c=0.9, p0=0.6,
               n_q=64, k=10)

# Sketch-prefilter calibration knob (DESIGN.md §13): eps=0.1 holds recall
# 1.000 at the LARGE_N point while cutting pages_frac 0.84 -> ~0.11; the
# cliff is below ~0.07. The guarantee suite pins this same eps on its grid.
PREFILTER_EPS = 0.1


def _large_corpus():
    from repro.data.synthetic import mf_factors
    cfg = LARGE_N
    x = mf_factors(cfg["n"], cfg["d"], cfg["rank"], decay=cfg["decay"],
                   seed=0, norm_tail=cfg["norm_tail"])
    q = mf_factors(cfg["n_q"], cfg["d"], cfg["rank"], decay=cfg["decay"],
                   seed=1)
    return x, q


def bench_search_runtime(quick: bool = False):
    """Host vs device scan/batched/fused verification — the two-phase
    runtime speedup cells (ISSUE 1: batched >= 2x scan; ISSUE 4: fused >=
    batched, guarded by scripts/ci.sh). Writes BENCH_search.json at the
    repo root with per-query latency + logical pages so the perf trajectory
    is recorded (benchmarks/run.py also appends it to
    results/bench/history.jsonl), including a large-n point (`LARGE_N`)
    where pruning pays off and `promips` must beat the exact full scan.

    Settings are tuned so pruning actually ENGAGES (ISSUE 2): decay-0.5 MF
    norms, an 8-stratum layout and the norm-adaptive + CS-prune radii leave
    pages_mean well under n_blocks (~398/500 at quick sizes, recall 0.997
    vs exact) — the page-access axis measures real work, not a full sweep.
    Both pages_mean and n_blocks are recorded so the engagement is auditable.

    (This bench deliberately reaches below the facade: it compares the
    verification backends INSIDE the "promips" registry entry.)
    """
    import json
    import os

    import jax.numpy as jnp

    from repro.core import ProMIPS
    from repro.data.synthetic import mf_factors

    n, d, n_q = (8000, 64, 64) if quick else (20000, 96, 64)
    x = mf_factors(n, d, 16, decay=0.5, seed=0, norm_tail=0.3)
    q = mf_factors(n_q, d, 16, decay=0.5, seed=1)
    pm = ProMIPS.build(x, m=8, c=0.9, p=0.6, k_p=8, k_sp=12, norm_strata=8)
    qj = jnp.asarray(q, jnp.float32)

    import jax
    backend = ("tpu-pallas" if jax.default_backend() == "tpu"
               else f"{jax.default_backend()}-jnp-oracle")
    rec = {"n": n, "d": d, "batch": n_q, "k": 10,
           "n_blocks": pm.meta.n_blocks, "page_rows": pm.meta.page_rows,
           "backend": backend}
    rows = []

    pm.search_host(q[0], k=10)   # warm-up: lazy HostSearcher build + chi2,
    t0 = time.perf_counter()     # mirroring the device paths' compile call
    for i in range(8):
        _, _, st_h = pm.search_host(q[i], k=10)
    rec["host_us_per_query"] = (time.perf_counter() - t0) / 8 * 1e6
    rows.append(("runtime/host", rec["host_us_per_query"], "queries=8"))

    labels = ("scan", "batched", "fused")

    def one_rep(label):
        t0 = time.perf_counter()
        ids, _, st = pm.search(qj, k=10, verification=label,
                               norm_adaptive=True, cs_prune=True)
        ids.block_until_ready()
        return time.perf_counter() - t0, st

    times = {label: [] for label in labels}
    stats = {}
    for label in labels:
        one_rep(label)  # compile
    # interleaved reps + per-pair ratio medians: the CI guard hard-asserts
    # fused >= batched and this host's wall clock jitters +-20% across
    # seconds, so back-to-back timing blocks would make that ratio a lottery
    for _ in range(5):
        for label in labels:
            dt, stats[label] = one_rep(label)
            times[label].append(dt)
    for label in labels:
        us = float(np.median(times[label])) / n_q * 1e6
        pages = float(np.mean(np.asarray(stats[label].pages)))
        rec[f"device_{label}_us_per_query"] = us
        rec[f"device_{label}_pages_mean"] = pages
        rows.append((f"runtime/device_{label}", us,
                     f"pages={pages:.0f}/{pm.meta.n_blocks}"))

    rec["pages_frac_of_blocks"] = (
        rec["device_batched_pages_mean"] / pm.meta.n_blocks)
    rec["pruning_engaged"] = rec["pages_frac_of_blocks"] < 1.0
    rec["speedup_batched_vs_scan"] = float(np.median(
        [s / b for s, b in zip(times["scan"], times["batched"])]))
    rec["speedup_fused_vs_batched"] = float(np.median(
        [b / f for b, f in zip(times["batched"], times["fused"])]))
    rows.append(("runtime/speedup_batched_vs_scan", 0.0,
                 f"x{rec['speedup_batched_vs_scan']:.2f}"))
    rows.append(("runtime/speedup_fused_vs_batched", 0.0,
                 f"x{rec['speedup_fused_vs_batched']:.2f}"))

    # prefilter on/off page fractions at the smoke scale (ci.sh guards the
    # cut + the recall floor; exact ids from a jit scan, not the index)
    xj = jnp.asarray(x, jnp.float32)
    eids = np.asarray(jax.lax.top_k((xj @ qj.T).T, 10)[1])
    from repro.core import recall_at_k
    for tag, kw in (("off", {}), ("on", dict(prefilter=True,
                                             prefilter_eps=PREFILTER_EPS))):
        ids, _, st = pm.search(qj, k=10, norm_adaptive=True, cs_prune=True,
                               **kw)
        ids = np.asarray(ids)
        rec[f"prefilter_{tag}_pages_frac"] = float(
            np.mean(np.asarray(st.pages))) / pm.meta.n_blocks
        rec[f"prefilter_{tag}_recall"] = float(np.mean(
            [recall_at_k(ids[i], eids[i]) for i in range(n_q)]))
    rec["prefilter_eps"] = PREFILTER_EPS
    rows.append(("runtime/prefilter_pages_frac", 0.0,
                 f"{rec['prefilter_on_pages_frac']:.3f} vs "
                 f"{rec['prefilter_off_pages_frac']:.3f} off; "
                 f"recall={rec['prefilter_on_recall']:.3f}"))

    rec["large_n"] = large = _bench_runtime_large()
    rows.append((f"runtime/large_n{large['n']}/exact",
                 large["exact_us_per_query"], "numpy per-query scan"))
    rows.append((f"runtime/large_n{large['n']}/exact_jit",
                 large["exact_jit_us_per_query"], "jit batch matmul+topk"))
    for label in ("batched", "fused_noprefilter", "fused", "tuned"):
        rows.append((f"runtime/large_n{large['n']}/{label}",
                     large[f"{label}_us_per_query"],
                     f"pages={large[f'{label}_pages_mean']:.0f}"
                     f"/{large['n_blocks']};"
                     f"recall={large[f'{label}_recall']:.3f}"))
    rows.append(("runtime/large_n/speedup_fused_vs_exact", 0.0,
                 f"x{large['speedup_fused_vs_exact']:.2f}"))
    rows.append(("runtime/large_n/speedup_fused_vs_exact_jit", 0.0,
                 f"x{large['speedup_fused_vs_exact_jit']:.2f}"))
    rows.append(("runtime/large_n/speedup_tuned_vs_default", 0.0,
                 f"x{large['speedup_tuned_vs_default']:.2f};"
                 f"config_source={large['config_source']}"))

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    with open(os.path.join(root, "BENCH_search.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rows


def _bench_runtime_large():
    """The large-n cell: fused/batched two-phase vs the exact full scan.

    This is the regime the paper's pitch is about — at n >= 100k the fused
    pruned path must come in UNDER the `exact` backend (the numpy per-query
    scan every accuracy figure compares against; `promips` < `exact` with
    recall >= 0.95). A jit batch matmul+top_k is ALSO recorded
    (``exact_jit_us_per_query``) as the device-side dense upper bound — on
    this CPU container its one sgemm beats everything at ~80% page
    fractions; the fused kernel's page-skipping DMA walk is what closes
    that gap on a real TPU (DESIGN.md §10). Returns the record embedded in
    BENCH_search.json.
    """
    import jax
    import jax.numpy as jnp

    from repro.baselines.exact import ExactMIPS, exact_topk
    from repro.core import ProMIPS, recall_at_k

    cfg = LARGE_N
    x, q = _large_corpus()
    t0 = time.perf_counter()
    pm = ProMIPS.build(x, m=cfg["m"], c=cfg["c"], p=cfg["p0"], k_p=cfg["k_p"],
                       k_sp=cfg["k_sp"], norm_strata=cfg["norm_strata"])
    rec = {"n": cfg["n"], "d": cfg["d"], "batch": cfg["n_q"], "k": cfg["k"],
           "build_s": time.perf_counter() - t0, "n_blocks": pm.meta.n_blocks}
    qj = jnp.asarray(q, jnp.float32)
    eids, _ = exact_topk(x, q, cfg["k"])

    exact = ExactMIPS().build(x)
    exact.search(q[0], k=cfg["k"])

    def exact_rep():
        t0 = time.perf_counter()
        for i in range(cfg["n_q"]):
            exact.search(q[i], k=cfg["k"])
        return time.perf_counter() - t0

    xj = jnp.asarray(x, jnp.float32)

    @jax.jit
    def exact_scan(qj):
        return jax.lax.top_k((xj @ qj.T).T, cfg["k"])
    out = exact_scan(qj)
    out[0].block_until_ready()

    def exact_jit_rep():
        t0 = time.perf_counter()
        out = exact_scan(qj)
        out[0].block_until_ready()
        return time.perf_counter() - t0

    # headline fused = sketch prefilter ON at the DESIGN.md §13-calibrated
    # eps; the no-prefilter fused path is recorded alongside so the page
    # cut is auditable in one record. The hand-picked arms PIN dense_frac
    # and tile_cap explicitly so an installed tuning cache
    # (results/tune/tuning.json) cannot leak into the baseline; the "tuned"
    # arm takes whatever `repro.tune.cache` resolves for this shape — with
    # no entry it degenerates to the hand-picked config (config_source
    # records which happened).
    from repro.tune import cache as tune_cache
    tuned_entry = tune_cache.lookup(cfg["n"], cfg["d"])
    tuned_rt = tune_cache.resolved("runtime", cfg["n"], cfg["d"])
    rec["config_source"] = "tuned" if tuned_entry is not None else "default"
    rec["tuned_runtime"] = dict(tuned_rt)
    pin = dict(dense_frac=0.9, tile_cap=pm.meta.n_blocks)
    tuned_tc = tuned_rt["tile_cap"]
    variants = {
        "batched": dict(verification="batched"),
        "fused_noprefilter": dict(verification="fused", **pin),
        "fused": dict(verification="fused", prefilter=True,
                      prefilter_eps=PREFILTER_EPS, **pin),
        "tuned": dict(verification=tuned_rt["verification"], prefilter=True,
                      prefilter_eps=(float(tuned_rt["prefilter_eps"])
                                     if tuned_entry is not None
                                     else PREFILTER_EPS),
                      dense_frac=float(tuned_rt["dense_frac"]),
                      tile_cap=(int(tuned_tc) if tuned_tc is not None
                                else pm.meta.n_blocks)),
    }
    rec["prefilter_eps"] = PREFILTER_EPS

    def device_rep(label):
        t0 = time.perf_counter()
        ids, _, st = pm.search(qj, k=cfg["k"], norm_adaptive=True,
                               cs_prune=True, **variants[label])
        ids.block_until_ready()
        return time.perf_counter() - t0, ids, st

    for label in variants:
        device_rep(label)  # compile
    # INTERLEAVED exact/exact_jit/batched/fused reps: this host's wall clock
    # drifts +-20% over tens of seconds, so back-to-back blocks of reps make
    # the recorded ratios a lottery; pairing every rep and taking the median
    # per-pair ratio measures all contenders under the same conditions.
    # exact_jit is paired the same way (not timed once in its own block) so
    # speedup_fused_vs_exact_jit is an honest same-conditions ratio.
    t_ex, t_jit = [], []
    times = {label: [] for label in variants}
    outs = {}
    ratios, ratios_jit, ratios_tuned, ratios_tuned_exact = [], [], [], []
    for _ in range(5):
        t_ex.append(exact_rep())
        t_jit.append(exact_jit_rep())
        for label in variants:
            dt, ids, st = device_rep(label)
            times[label].append(dt)
            outs[label] = (ids, st)
        ratios.append(t_ex[-1] / times["fused"][-1])
        ratios_jit.append(t_jit[-1] / times["fused"][-1])
        ratios_tuned.append(times["fused"][-1] / times["tuned"][-1])
        ratios_tuned_exact.append(t_ex[-1] / times["tuned"][-1])
    rec["exact_us_per_query"] = float(np.median(t_ex)) / cfg["n_q"] * 1e6
    rec["exact_jit_us_per_query"] = float(np.median(t_jit)) / cfg["n_q"] * 1e6
    for label in variants:
        ids, st = outs[label]
        ids = np.asarray(ids)
        rec[f"{label}_us_per_query"] = (float(np.median(times[label]))
                                        / cfg["n_q"] * 1e6)
        rec[f"{label}_pages_mean"] = float(np.mean(np.asarray(st.pages)))
        rec[f"{label}_recall"] = float(np.mean(
            [recall_at_k(ids[i], eids[i]) for i in range(cfg["n_q"])]))
    rec["recall"] = rec["fused_recall"]
    rec["recall_noprefilter"] = rec["fused_noprefilter_recall"]
    rec["pages_frac_of_blocks"] = rec["fused_pages_mean"] / rec["n_blocks"]
    rec["pages_frac_noprefilter"] = (rec["fused_noprefilter_pages_mean"]
                                     / rec["n_blocks"])
    rec["pruning_engaged"] = rec["pages_frac_of_blocks"] < 1.0
    rec["speedup_fused_vs_exact"] = float(np.median(ratios))
    rec["speedup_fused_vs_exact_jit"] = float(np.median(ratios_jit))
    # same-session interleaved ratio of the hand-picked fused arm over the
    # cache-resolved arm — the --quick perf guard in scripts/ci.sh asserts
    # this stays above the noise floor when a tuned entry is installed
    rec["speedup_tuned_vs_default"] = float(np.median(ratios_tuned))
    # with a cache entry installed the tuned arm IS the shipped default
    # config, so the exact-scan headline is also recorded against it
    rec["speedup_tuned_vs_exact"] = float(np.median(ratios_tuned_exact))
    rec["roofline"] = _roofline_record(pm, qj, cfg["k"])
    return rec


def _roofline_record(pm, qj, k):
    """Achieved-vs-roofline cost terms of the in-graph fused search
    (prefilter on/off) and the exact jit scan, via XLA's cost_analysis on
    the compiled graphs (`launch/roofline.kernel_cost`). Caveat recorded
    honestly: the in-graph driver compiles EVERY lax.switch tile branch, and
    static cost_analysis sums them all, so these are compile-time upper
    bounds that cannot see the prefilter's runtime branch selection — the
    dynamic traffic cut is what `pages_frac_of_blocks` (vs
    `pages_frac_noprefilter`) audits; this record pins the roofline context
    (memory-bound, and how far the exact sgemm sits from the bound)."""
    import jax
    import jax.numpy as jnp

    from repro.core import RuntimeConfig, runtime_search
    from repro.launch.roofline import kernel_cost

    xj = jnp.asarray(pm.arrays.x)

    def graph(cfg):
        return jax.jit(lambda arrays, q: runtime_search(arrays, pm.meta,
                                                        q, cfg))

    out = {}
    try:
        out["exact_jit"] = kernel_cost(
            lambda q: jax.lax.top_k((xj @ q.T).T, k), qj)
        out["fused"] = kernel_cost(
            graph(RuntimeConfig(k=k, norm_adaptive=True, cs_prune=True,
                                prefilter=True,
                                prefilter_eps=PREFILTER_EPS)),
            pm.arrays, qj)
        out["fused_noprefilter"] = kernel_cost(
            graph(RuntimeConfig(k=k, norm_adaptive=True, cs_prune=True)),
            pm.arrays, qj)
    except Exception as e:  # cost_analysis is backend-dependent; never fatal
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def bench_tune(smoke: bool = True):
    """Autotuner bench (ISSUE 8): runs the budgeted coordinate descent
    end-to-end on a cutout, writes the entry to a TEMP cache (never the
    committed results/tune/tuning.json), then audits the three properties
    scripts/ci.sh guards:

      1. searching with the tuned cache installed is not slower than the
         pinned hand-picked config beyond the noise floor (interleaved
         same-session ratio ``speedup_cached_vs_handpicked``);
      2. the tuned config returns bit-identical (ids, scores) — the parity
         gate's whole point (``tuned_parity``);
      3. an empty/disabled cache changes nothing: default-knob searches
         equal explicit hand-picked ones bitwise (``empty_cache_noop``).

    Writes BENCH_tune.json at the repo root.
    """
    import json
    import os
    import tempfile

    from repro.core import ProMIPS
    from repro.tune import cache as tune_cache
    from repro.tune import cutout as tune_cutout
    from repro.tune import search as tune_search

    n, d, n_q = (4000, 32, 16) if smoke else (20000, 64, 32)
    budget_s = 60.0 if smoke else 300.0
    x, q = tune_cutout.make_cutout(n, d, n_q, seed=0)
    build_opts = dict(m=12, c=0.9, p=0.6, k_p=4, k_sp=4, norm_strata=4,
                      seed=0)
    search_opts = dict(k=10, norm_adaptive=True, cs_prune=True,
                       prefilter=True, prefilter_eps=PREFILTER_EPS)

    tmp_cache = os.path.join(tempfile.mkdtemp(prefix="repro-tune-bench-"),
                             "tuning.json")
    entry = tune_search.tune_point(
        x, q, build_opts=build_opts, search_opts=search_opts,
        budget_s=budget_s, reps=3, include_build=False, write=True,
        path=tmp_cache)
    summary = entry["trace"]["summary"]
    rec = {"n": n, "d": d, "batch": n_q, "smoke": smoke,
           "cache_key": entry["key"], "tuned_runtime": entry["runtime"],
           "baseline_us_per_query": summary["baseline_us_per_query"],
           "best_us_per_query": summary["best_us_per_query"],
           "speedup_tuned_vs_default": summary["speedup_tuned_vs_default"],
           "n_candidates": summary["n_candidates"],
           "tune_elapsed_s": summary["elapsed_s"]}

    pm = ProMIPS.build(x, **build_opts)
    hand = dict(tune_cache.space.HAND_PICKED["runtime"])
    hand["prefilter_eps"] = PREFILTER_EPS
    fn_hand = tune_search._search_fn(pm, q, search_opts, hand)
    res_hand = fn_hand()
    import jax
    jax.block_until_ready(res_hand[1])

    prev = os.environ.get(tune_cache.ENV_VAR)
    try:
        # arm 2: the tuned cache INSTALLED — verification from the entry,
        # dense_frac/tile_cap left as None so runtime.search resolves them
        # from the cache, exactly like a user with the file in place
        os.environ[tune_cache.ENV_VAR] = tmp_cache
        tune_cache.clear_memo()
        tuned_rt = tune_cache.resolved("runtime", n, d)

        def fn_cached():
            return pm.search(q, k=10, norm_adaptive=True, cs_prune=True,
                             verification=tuned_rt["verification"],
                             prefilter=True, prefilter_eps=PREFILTER_EPS)

        res_cached = fn_cached()
        jax.block_until_ready(res_cached[1])
        rec["tuned_parity"] = tune_search._result_parity(res_hand,
                                                         res_cached)
        t_hand, t_cached, ratio = tune_cutout.interleaved_ratio(
            fn_hand, fn_cached, reps=3)
        rec["handpicked_us_per_query"] = t_hand * 1e6 / n_q
        rec["cached_us_per_query"] = t_cached * 1e6 / n_q
        rec["speedup_cached_vs_handpicked"] = ratio

        # arm 3: cache DISABLED — default knobs must change nothing
        os.environ[tune_cache.ENV_VAR] = ""
        tune_cache.clear_memo()
        res_none = pm.search(q, k=10, norm_adaptive=True, cs_prune=True,
                             prefilter=True, prefilter_eps=PREFILTER_EPS)
        jax.block_until_ready(res_none[1])
        rec["empty_cache_noop"] = tune_search._result_parity(res_hand,
                                                             res_none)
    finally:
        if prev is None:
            os.environ.pop(tune_cache.ENV_VAR, None)
        else:
            os.environ[tune_cache.ENV_VAR] = prev
        tune_cache.clear_memo()

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    with open(os.path.join(root, "BENCH_tune.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return [
        ("tune/descent_s", summary["elapsed_s"] * 1e6,
         f"candidates={summary['n_candidates']};"
         f"speedup=x{summary['speedup_tuned_vs_default']:.3f}"),
        ("tune/cached_vs_handpicked", rec["cached_us_per_query"],
         f"x{rec['speedup_cached_vs_handpicked']:.3f};"
         f"parity={rec['tuned_parity']}"),
        ("tune/empty_cache_noop", 0.0, str(rec["empty_cache_noop"])),
    ]


def bench_sharded(quick: bool = True):
    """Sharded fan-out (ISSUE 5): the in-graph fused driver inside
    `sharded_search`'s shard_map vs the batched graph, at the LARGE_N point
    (n=100k) across device counts. Writes BENCH_sharded.json at the repo
    root (benchmarks/run.py appends it to results/bench/history.jsonl).

    Run under ``--xla_force_host_platform_device_count=8`` (benchmarks/run.py
    --sharded sets the flag itself before jax initializes); device counts
    that exceed the actual device count are skipped, so the bench degrades
    gracefully to a single-device point. Per count the corpus is re-sharded
    (shard count == mesh size — `build_sharded`'s contract) and the SAME
    fused-vs-batched interleaved-rep protocol as `bench_search_runtime`
    guards the ratio against this host's wall-clock drift. The CI perf
    guard asserts ``speedup_sharded_fused_vs_batched >= 1`` at the largest
    count (scripts/ci.sh).
    """
    import dataclasses
    import json
    import os

    import jax

    from repro.baselines.exact import exact_topk
    from repro.core import recall_at_k
    from repro.core.runtime import RuntimeConfig
    from repro.core.sharded import (build_sharded, device_put_sharded_index,
                                    sharded_search)
    from repro.launch.mesh import make_mesh_compat

    cfg = LARGE_N
    counts = [c for c in ((1, 2, 8) if quick else (1, 2, 4, 8))
              if c <= jax.device_count()]
    x, q = _large_corpus()
    eids, _ = exact_topk(x, q, cfg["k"])
    cfg_f = RuntimeConfig(mode="two_phase", verification="fused",
                          norm_adaptive=True, cs_prune=True)
    cfg_b = dataclasses.replace(cfg_f, verification="batched")

    rec = {"n": cfg["n"], "d": cfg["d"], "batch": cfg["n_q"], "k": cfg["k"],
           "jax_device_count": jax.device_count(), "device_counts": counts,
           "points": {}}
    rows = []
    for n_dev in counts:
        mesh = make_mesh_compat((n_dev,), ("model",))
        t0 = time.perf_counter()
        sh = build_sharded(x, n_dev, m=cfg["m"], c=cfg["c"], p=cfg["p0"],
                           k_p=cfg["k_p"], k_sp=cfg["k_sp"],
                           norm_strata=cfg["norm_strata"])
        shd = device_put_sharded_index(sh, mesh)
        build_s = time.perf_counter() - t0

        def one_rep(runtime):
            t0 = time.perf_counter()
            ids, scores, pages = sharded_search(shd, q, cfg["k"], mesh,
                                                runtime=runtime)
            ids.block_until_ready()
            return time.perf_counter() - t0, ids, pages

        for runtime in (cfg_f, cfg_b):
            one_rep(runtime)  # compile
        t_f, t_b, ratios = [], [], []
        for _ in range(3):  # interleaved: both contenders see the same drift
            tb, _, _ = one_rep(cfg_b)
            tf, ids, pages = one_rep(cfg_f)
            t_f.append(tf)
            t_b.append(tb)
            ratios.append(tb / tf)
        recall = float(np.mean([recall_at_k(np.asarray(ids)[i], eids[i])
                                for i in range(cfg["n_q"])]))
        point = {
            "build_s": build_s,
            "n_blocks_per_shard": sh.meta.n_blocks,
            "fused_us_per_query": float(np.median(t_f)) / cfg["n_q"] * 1e6,
            "batched_us_per_query": float(np.median(t_b)) / cfg["n_q"] * 1e6,
            "pages_total": int(pages),
            "recall": recall,
            "speedup_fused_vs_batched": float(np.median(ratios)),
        }
        rec["points"][str(n_dev)] = point
        rows.append((f"sharded/devices{n_dev}/fused",
                     point["fused_us_per_query"],
                     f"recall={recall:.3f};pages={int(pages)}"))
        rows.append((f"sharded/devices{n_dev}/batched",
                     point["batched_us_per_query"],
                     f"x{point['speedup_fused_vs_batched']:.2f} fused-vs-batched"))

    top = rec["points"][str(counts[-1])]
    rec["max_devices"] = counts[-1]
    rec["recall"] = top["recall"]
    rec["speedup_sharded_fused_vs_batched"] = top["speedup_fused_vs_batched"]
    rows.append(("sharded/speedup_fused_vs_batched", 0.0,
                 f"x{rec['speedup_sharded_fused_vs_batched']:.2f}"
                 f"@{counts[-1]}dev"))

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    with open(os.path.join(root, "BENCH_sharded.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rows


def bench_stream(quick: bool = True):
    """Streaming index (ISSUE 2): insert throughput, search latency at
    0%/10%/30% delta fraction, and latency right after compaction. Writes
    BENCH_stream.json at the repo root. Built through the facade; the
    mutation calls are the uniform capability-gated Searcher surface."""
    import json
    import os

    from repro import api
    from repro.core.runtime import RuntimeConfig
    from repro.data.synthetic import mf_factors

    n, d, n_q = (8000, 64, 64) if quick else (20000, 96, 64)
    x = mf_factors(n, d, 16, decay=0.5, seed=0, norm_tail=0.3)
    q = mf_factors(n_q, d, 16, decay=0.5, seed=1)
    rng = np.random.RandomState(2)

    s = api.build(x, backend="promips-stream",
                  guarantee=api.GuaranteeConfig(c=0.9, p0=0.6, k=10),
                  m=8, k_p=8, k_sp=12, norm_strata=8, seed=0)
    st = s.inner  # delta watermark introspection below is stream-specific
    cfg = RuntimeConfig(norm_adaptive=True, cs_prune=True)  # pruning engaged
    rec = {"n": n, "d": d, "batch": n_q, "k": 10,
           "delta_capacity": st.delta_capacity}
    rows = []

    def timed_search():
        s.search(q, k=10, runtime=cfg)
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            res = s.search(q, k=10, runtime=cfg)
        return ((time.perf_counter() - t0) / (reps * n_q) * 1e6,
                res.pages / n_q)

    # insert throughput: batched appends into the preallocated delta
    bursts, burst = 16, 64
    gid0 = 10 * n
    t0 = time.perf_counter()
    for i in range(bursts):
        s.insert(np.arange(gid0 + i * burst, gid0 + (i + 1) * burst),
                 rng.randn(burst, d).astype(np.float32))
    dt = time.perf_counter() - t0
    rec["insert_rows_per_s"] = bursts * burst / dt
    rows.append(("stream/insert_throughput", dt / (bursts * burst) * 1e6,
                 f"rows_per_s={rec['insert_rows_per_s']:.0f}"))
    s.delete(np.arange(gid0, gid0 + bursts * burst))  # reset to 0% live
    s.compact()

    for frac in (0.0, 0.1, 0.3):
        want = int(frac / (1 - frac) * n)  # live delta rows for this fraction
        have = st._delta.n_alive
        if want > have:
            s.insert(np.arange(20 * n + have, 20 * n + want),
                     rng.randn(want - have, d).astype(np.float32))
        us, pages = timed_search()
        assert abs(st.delta_fraction - frac) < 0.02, st.delta_fraction
        rec[f"search_us_delta_{int(frac*100)}pct"] = us
        rec[f"pages_delta_{int(frac*100)}pct"] = pages
        rows.append((f"stream/search_delta_{int(frac*100)}pct", us,
                     f"pages={pages:.0f};delta_frac={st.delta_fraction:.2f}"))

    t0 = time.perf_counter()
    s.compact()
    rec["compaction_s"] = time.perf_counter() - t0
    us, pages = timed_search()
    rec["search_us_post_compaction"] = us
    rec["pages_post_compaction"] = pages
    rows.append(("stream/search_post_compaction", us,
                 f"pages={pages:.0f};compaction_s={rec['compaction_s']:.2f}"))

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    with open(os.path.join(root, "BENCH_stream.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rows


def bench_device_throughput():
    """Batched device-mode (jit) search throughput + Pallas kernel check."""
    import jax.numpy as jnp
    from repro.kernels import ops
    rows = []
    name = "netflix"
    s = build_backend(name, "promips", mode="progressive", norm_strata=4)
    x, queries = load(name)
    s.search(queries, k=10)   # compile
    t0 = time.perf_counter()
    for _ in range(3):
        res = s.search(queries, k=10)
    us = (time.perf_counter() - t0) / (3 * len(queries)) * 1e6
    rows.append((f"device/{name}/progressive", us,
                 f"pages={res.pages / len(queries):.0f}"))
    # kernel-level verification scan (backend-aware default: Pallas on TPU,
    # jnp oracle here — mips_topk no longer silently pays interpret mode)
    import jax
    xr = jnp.asarray(x[:2048], jnp.float32)
    valid = jnp.ones(2048, bool)
    t0 = time.perf_counter()
    top, idx = ops.mips_topk(xr, jnp.asarray(queries[:4], jnp.float32), valid,
                             k=10)
    top.block_until_ready()
    us_k = (time.perf_counter() - t0) * 1e6 / 4
    mode = "pallas" if jax.default_backend() == "tpu" else "jnp-oracle"
    rows.append(("device/kernel/mips_topk", us_k, f"mode={mode}"))
    return rows


def bench_obs(quick: bool = True):
    """Observability tier (DESIGN.md §14): the tracer must be FREE when off
    and cheap when on, and the per-phase spans must account for the whole
    end-to-end latency.

    Three interleaved modes at the smoke scale, median-of-adjacent-pair
    ratios (same jitter defense as bench_search_runtime):

      baseline  span call sites monkeypatched to a null lambda — the code
                with no instrumentation at all
      disabled  real `repro.obs.trace.span` with tracing off (one bool
                check + a shared null context manager per site)
      enabled   tracing on, unfenced (the always-on production setting)

    scripts/ci.sh asserts overhead_disabled_frac < 1% and
    overhead_enabled_frac < 5%. Then the LARGE_N fused+prefilter point runs
    FENCED and the spans are grouped into the four pipeline phases
    (frontend / prefilter / verify / merge); their sum must land within 15%
    of the measured end-to-end batch latency (phase_sum_frac), or the spans
    are lying. One fenced batch is exported as a Chrome trace under
    results/obs/ — load it in Perfetto (the §14 worked example).
    """
    import json
    import os

    import jax.numpy as jnp

    from repro.core import ProMIPS
    from repro.core import runtime as rt
    from repro.core import search_fused as sf
    from repro.data.synthetic import mf_factors
    from repro.obs import metrics, trace

    rows = []
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

    # --- smoke-scale overhead: baseline vs disabled vs enabled -------------
    n, d, n_q = 8000, 64, 64
    x = mf_factors(n, d, 16, decay=0.5, seed=0, norm_tail=0.3)
    q = mf_factors(n_q, d, 16, decay=0.5, seed=1)
    pm = ProMIPS.build(x, m=8, c=0.9, p=0.6, k_p=8, k_sp=12, norm_strata=8)
    qj = jnp.asarray(q, jnp.float32)

    real_sf_span, real_rt_span = sf._span, rt._span

    def null_span(name, active=None, metric=None, layer=None, **stats):
        return trace._NULL

    def set_mode(mode):
        sf._span = rt._span = (null_span if mode == "baseline"
                               else trace.span)
        if mode == "enabled":
            trace.enable(fence=False)
        else:
            trace.disable()

    def one_rep():
        t0 = time.perf_counter()
        ids, _, _ = pm.search(qj, k=10, verification="fused",
                              norm_adaptive=True, cs_prune=True)
        ids.block_until_ready()
        return time.perf_counter() - t0

    modes = ("baseline", "disabled", "enabled")
    times = {m: [] for m in modes}
    try:
        for m in modes:
            set_mode(m)
            one_rep()   # compile / warm
        rounds = 12 if quick else 30
        for _ in range(rounds):
            for m in modes:
                set_mode(m)
                times[m].append(one_rep())
    finally:
        sf._span, rt._span = real_sf_span, real_rt_span
        trace.disable()

    base_us = float(np.median(times["baseline"])) * 1e6
    smoke = {"n": n, "d": d, "batch": n_q, "rounds": rounds,
             "baseline_us_per_call": base_us}
    for m in ("disabled", "enabled"):
        # adjacent-pair ratios: mode m vs the baseline rep of the SAME round
        frac = float(np.median(
            [t / b for t, b in zip(times[m], times["baseline"])])) - 1.0
        smoke[f"overhead_{m}_frac"] = frac
        rows.append((f"obs/overhead_{m}", 0.0, f"{frac:+.4f}"))
    rec = {"smoke": smoke}

    # --- LARGE_N fenced per-phase breakdown --------------------------------
    cfg = LARGE_N
    x2, q2 = _large_corpus()
    pm2 = ProMIPS.build(x2, m=cfg["m"], c=cfg["c"], p=cfg["p0"],
                        k_p=cfg["k_p"], k_sp=cfg["k_sp"],
                        norm_strata=cfg["norm_strata"])
    qj2 = jnp.asarray(q2, jnp.float32)
    kw = dict(verification="fused", norm_adaptive=True, cs_prune=True,
              prefilter=True, prefilter_eps=PREFILTER_EPS)

    metrics.reset()
    metrics.enable()
    ids, _, st = pm2.search(qj2, k=cfg["k"], **kw)   # compile / warm
    ids.block_until_ready()
    st.to_dict()   # one pass through the stats_totals -> registry feed
    reps = 3 if quick else 8
    trace.enable(fence=True)
    trace.clear()
    try:
        for _ in range(reps):
            ids, _, _ = pm2.search(qj2, k=cfg["k"], **kw)
            ids.block_until_ready()
        spans = trace.spans()

        per_name: dict = {}
        for s in spans:
            per_name.setdefault(s["name"], []).append(s["dur_us"])
        span_means = {nm: float(np.sum(v)) / reps
                      for nm, v in sorted(per_name.items())}
        PHASES = {
            "frontend": ("select_frontend", "compensation"),
            "prefilter": ("prefilter_round1", "prefilter_round2"),
            "verify": ("pull_priority", "pull_mask_round1",
                       "pull_mask_round2", "plan_tile_round1",
                       "plan_tile_round2", "verify_round1", "verify_round2"),
            "merge": ("rescore",),
        }
        phases = {ph: float(sum(span_means.get(nm, 0.0) for nm in nms))
                  for ph, nms in PHASES.items()}
        e2e = span_means["search"]
        phase_sum_frac = sum(phases.values()) / e2e

        # a fresh single fenced batch as the committed Perfetto example
        trace.clear()
        ids, _, _ = pm2.search(qj2, k=cfg["k"], **kw)
        ids.block_until_ready()
        trace_path = os.path.join("results", "obs",
                                  "trace_large_n_fused.json")
        trace.export_chrome_trace(os.path.join(root, trace_path))
    finally:
        trace.disable()
        metrics.disable()

    snap = metrics.snapshot()
    undeclared = sorted(set(snap) - set(metrics.GLOSSARY))
    rec["large_n"] = {
        "n": cfg["n"], "d": cfg["d"], "batch": cfg["n_q"], "reps": reps,
        "fenced": True, "prefilter_eps": PREFILTER_EPS,
        "e2e_us": e2e, "phases_us": phases,
        "span_means_us": span_means, "phase_sum_frac": phase_sum_frac,
        "chrome_trace": trace_path,
    }
    rec["registered_metrics"] = sorted(snap)
    rec["undeclared"] = undeclared
    for ph, us in phases.items():
        rows.append((f"obs/large_n/{ph}", us / cfg["n_q"],
                     f"{100 * us / e2e:.1f}% of e2e"))
    rows.append(("obs/large_n/e2e", e2e / cfg["n_q"],
                 f"phase_sum_frac={phase_sum_frac:.3f}"))

    with open(os.path.join(root, "BENCH_obs.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rows


def bench_robust(quick: bool = True):
    """Robustness tier (DESIGN.md §16): the three guarantees the robust
    subsystem sells, each with a number ci.sh can guard.

      wal overhead   interleaved mutate+search cycles on a plain vs WAL'd
                     (fsync="os") stream.  The guarded figure is
                     `wal_workload_overhead_frac` — durability cost on the
                     streaming workload (inserts + the queries they serve),
                     asserted <= 5%.  `wal_append_overhead_frac` is the
                     honest *bare* insert-path ratio, reported but NOT
                     guarded: a delta append is a memcpy + id-map update
                     (~0.1 ms/burst) while an acknowledged WAL record costs
                     an unavoidable crc32 + flush-to-OS (~0.3 ms at 1024
                     rows), so the bare ratio sits far above any useful
                     threshold and a guard there would only measure zlib
                     throughput.
      recovery       crash the WAL'd searcher (drop it), `recover()` from
                     snapshot + log; reports wall time, replayed rows/s,
                     and `recovery_bit_parity` — ids AND scores of the
                     recovered searcher exactly equal the live one's.
      degradation    open-loop overload burst into a DecodeEngine with the
                     ladder + deadlines enabled: shed rate, tier
                     transitions, and per-tier search p50/p99 + recall
                     against the full-budget tier, compared with the
                     policy's declared recall floors.

    Writes BENCH_robust.json at the repo root.
    """
    import json
    import os
    import shutil
    import tempfile

    from repro import api
    from repro.data.synthetic import mf_factors
    from repro.robust import recover

    n, d, n_q = (4000, 48, 32) if quick else (12000, 64, 64)
    x = mf_factors(n, d, 16, decay=0.5, seed=0, norm_tail=0.3)
    q = mf_factors(n_q, d, 16, decay=0.5, seed=1)
    rng = np.random.RandomState(2)
    rows_out = []
    rec = {"n": n, "d": d, "batch": n_q, "k": 10, "wal_fsync": "os"}

    tmp = tempfile.mkdtemp(prefix="bench_robust_")
    wal_dir = os.path.join(tmp, "wal")
    build_kw = dict(guarantee=api.GuaranteeConfig(c=0.9, p0=0.6, k=10),
                    m=8, k_p=8, k_sp=12, norm_strata=8, seed=0,
                    delta_capacity=8 * n)   # no auto-compaction mid-timing
    try:
        plain = api.build(x, backend="promips-stream", **build_kw)
        walled = api.build(x, backend="promips-stream", wal_dir=wal_dir,
                           **build_kw)

        # -- WAL overhead: interleaved cycles, median of adjacent ratios --
        cycles, burst = (10, 256) if quick else (16, 512)
        gid0 = 10 * n
        t_plain, t_wal, ta_plain, ta_wal = [], [], [], []
        def timed(fn, *a, **kw):
            t0 = time.perf_counter()
            fn(*a, **kw)
            return time.perf_counter() - t0

        for i in range(cycles):
            g = np.arange(gid0 + i * burst, gid0 + (i + 1) * burst)
            r = rng.randn(burst, d).astype(np.float32)
            # alternate which arm runs first each cycle: the second arm of
            # an adjacent pair sees warm caches/allocator state, so a fixed
            # order biases the ratio (measurably below 1.0 with plain
            # always first)
            if i % 2 == 0:
                ap = timed(plain.insert, g, r)
                aw = timed(walled.insert, g, r)
            else:
                aw = timed(walled.insert, g, r)
                ap = timed(plain.insert, g, r)
            # untimed warmups: a delta-size bucket crossing triggers an XLA
            # recompile (~100ms) on the FIRST search at the new shape;
            # absorbing it here keeps the timed pair at steady state
            plain.search(q, k=10)
            walled.search(q, k=10)
            # searches are pure: best-of-3 per arm discards scheduler
            # jitter (single-shot spread here is ~+-10%, which would drown
            # a 5% guard)
            if i % 2 == 0:
                sp = min(timed(plain.search, q, k=10) for _ in range(3))
                sw = min(timed(walled.search, q, k=10) for _ in range(3))
            else:
                sw = min(timed(walled.search, q, k=10) for _ in range(3))
                sp = min(timed(plain.search, q, k=10) for _ in range(3))
            ta_plain.append(ap)
            ta_wal.append(aw)
            t_plain.append(ap + sp)
            t_wal.append(aw + sw)
        drop = 2                                    # warmup cycles
        app = (np.asarray(ta_wal[drop:]) / np.asarray(ta_plain[drop:]))
        rec["wal_append_overhead_frac"] = float(np.median(app) - 1.0)
        # totals, not median-of-ratios: the search term dominates each
        # cycle and its jitter (~+-10% per pair) swamps the per-pair
        # ratio; summing over the alternating-order cycles averages the
        # order effect AND the jitter out
        rec["wal_workload_overhead_frac"] = float(
            np.sum(t_wal[drop:]) / np.sum(t_plain[drop:]) - 1.0)
        rec["wal_append_us_per_burst"] = float(
            np.mean(ta_wal[drop:]) - np.mean(ta_plain[drop:])) * 1e6
        rows_out.append((
            "robust/wal_workload", float(np.mean(t_wal[drop:])) * 1e6,
            f"overhead_frac={rec['wal_workload_overhead_frac']:.4f}"))
        rows_out.append((
            "robust/wal_append", float(np.mean(ta_wal[drop:])) * 1e6
            / burst,
            f"bare_insert_overhead_frac={rec['wal_append_overhead_frac']:.3f}"
            " (informational; see docstring)"))

        # a delete through the log, so replay covers both row opcodes
        dels = np.arange(gid0, gid0 + burst)
        plain.delete(dels)
        walled.delete(dels)

        # -- recovery: drop the live searcher, restore from snapshot+WAL --
        live_res = walled.search(q, k=10)
        replay_records = walled.wal_lag()
        replay_rows = cycles * burst + burst        # inserts + the delete
        t0 = time.perf_counter()
        recovered = recover(wal_dir, attach=False)
        rec["recovery_s"] = time.perf_counter() - t0
        rec["replay_records"] = int(replay_records)
        rec["replay_rows_per_s"] = replay_rows / rec["recovery_s"]
        got = recovered.search(q, k=10)
        rec["recovery_bit_parity"] = bool(
            np.array_equal(live_res.ids, got.ids)
            and np.array_equal(live_res.scores, got.scores))
        rows_out.append((
            "robust/recovery", rec["recovery_s"] * 1e6,
            f"rows_per_s={rec['replay_rows_per_s']:.0f};"
            f"bit_parity={rec['recovery_bit_parity']}"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # -- degradation ladder under open-loop overload ----------------------
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import DecodeEngine, DegradationPolicy

    cfg = get_config("tinyllama-1.1b").reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    pol = DegradationPolicy(tiers=(1.0, 0.5, 0.25),
                            recall_floors=(0.95, 0.8, 0.5),
                            queue_high=3, queue_low=1, patience=2,
                            recovery=4)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                       logits_mode="promips", degradation=pol, max_queue=6,
                       default_deadline_s=60.0)
    vrng = np.random.RandomState(3)
    n_req = 24 if quick else 64
    admitted = 0
    max_tier = 0
    t0 = time.perf_counter()
    for i in range(n_req):                          # open loop: 3 per step
        r = eng.submit(vrng.randint(1, cfg.vocab, size=5),
                       max_new_tokens=6)
        admitted += r is not None
        if i % 3 == 2:
            eng.step()
            max_tier = max(max_tier, eng.tier)
    while eng.queue or eng.active.any():
        eng.step()
        max_tier = max(max_tier, eng.tier)
    overload_s = time.perf_counter() - t0
    for _ in range(2 * (pol.recovery + 1)):
        eng.step()      # idle calm ticks: the ladder steps back up to full
    rec["overload"] = {
        "requests": n_req, "admitted": admitted, "shed": eng.shed,
        "shed_rate": eng.shed / n_req, "stepdowns": eng.stepdowns,
        "stepups": eng.stepups, "deadline_drops": eng.deadline_drops,
        "max_tier_reached": max_tier, "wall_s": overload_s,
        "final_state": eng.health()["state"],
    }
    rows_out.append((
        "robust/overload", overload_s / n_req * 1e6,
        f"shed_rate={rec['overload']['shed_rate']:.2f};"
        f"stepdowns={eng.stepdowns};max_tier={max_tier}"))

    # -- per-tier latency percentiles + recall vs the full-budget tier ----
    # Measured on the mf_factors stream index (the repo's benchmark MIPS
    # corpus), replicating the engine's tier->budget resolution exactly
    # (float tier = fraction of the index's block count, budget AND budget2
    # — `DecodeEngine._resolve_tier_budgets` / `_tier_runtime`). The floors
    # here are what a DegradationPolicy on this corpus can honestly
    # declare; ci.sh guards measured >= declared. Budget truncation is
    # best-first (`core.search_device.truncate_union`), which is what
    # makes these floors hold — layout-order truncation scores ~0 here.
    import dataclasses

    from repro.core.runtime import RuntimeConfig

    tier_fracs = (1.0, 0.5, 0.25)
    tier_floors = (0.95, 0.85, 0.65)
    nb = plain.inner.meta.n_blocks
    rt0 = RuntimeConfig(mode="two_phase", verification="batched",
                        norm_adaptive=True, cs_prune=True)
    full = plain.search(q, k=10, runtime=rt0)
    tiers = []
    reps = 20 if quick else 50
    for t_i, (frac, floor) in enumerate(zip(tier_fracs, tier_floors)):
        b = None if frac >= 1.0 else max(1, round(nb * frac))
        rt = (rt0 if b is None
              else dataclasses.replace(rt0, budget=b, budget2=b))
        plain.search(q, k=10, runtime=rt)           # warm
        lat = []
        for _ in range(reps):
            t1 = time.perf_counter()
            res = plain.search(q, k=10, runtime=rt)
            lat.append((time.perf_counter() - t1) / n_q * 1e6)
        recall = float(np.mean([
            len(set(a.tolist()) & set(b_.tolist())) / 10
            for a, b_ in zip(res.ids, full.ids)]))
        tiers.append({
            "tier": t_i, "frac": frac, "budget": b,
            "p50_us": float(np.percentile(lat, 50)),
            "p99_us": float(np.percentile(lat, 99)),
            "pages_per_query": float(res.stats["pages"]) / n_q,
            "recall_vs_full": recall, "declared_floor": floor,
            "meets_floor": bool(recall >= floor),
        })
        rows_out.append((
            f"robust/tier{t_i}_search", tiers[-1]["p50_us"],
            f"p99={tiers[-1]['p99_us']:.0f}us;recall={recall:.3f};"
            f"floor={floor}"))
    rec["tiers"] = tiers

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    with open(os.path.join(root, "BENCH_robust.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rows_out


def bench_serve(quick: bool = True):
    """Serve-frontend tier (DESIGN.md §17): the numbers the continuous-
    batching engine + hot-query cache sell, each guarded by ci.sh.

      ramp       open-loop Zipfian load whose arrival rate ramps up until
                 it trips the degradation ladder and the admission cap:
                 p50/p99 request latency + queue wait, completed queries/s,
                 shed/expired fractions, per-tier step occupancy, cache hit
                 rate. Guarded: p99 <= declared bound, queries/s >= floor.
      cache      the same hot Zipfian pool replayed at saturation (arrivals
                 due immediately, so the engine is the bottleneck) through
                 a cache-on and a cache-off engine, alternating order per
                 rep; rep 0 absorbs compiles and is dropped. Guarded:
                 cache-on throughput >= cache-off.
      cold       distinct prompts decoded cache-on and cache-off — token
                 streams must be BIT-identical (all misses: the cache may
                 not change what is decoded). Guarded.
      inactive   one request on a 4-slot engine: the decode search may
                 touch only the active row (searched_rows == decode steps;
                 the pre-§17 engine searched all 4 and counted their
                 pages). Guarded structurally, pages vs a 1-slot engine
                 reported alongside.

    Writes BENCH_serve.json at the repo root.
    """
    import json
    import os

    import jax

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import (DecodeEngine, DegradationPolicy, LoadgenConfig,
                             generate, run_load)

    cfg = get_config("tinyllama-1.1b").reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    pkw = dict(m=8, c=0.95, p=0.95)

    def mk(**kw):
        return DecodeEngine(params, cfg, max_len=64, logits_mode="promips",
                            promips_kwargs=dict(pkw), **kw)

    rows_out = []
    rec = {"model": "tinyllama-1.1b(reduced)", "vocab": int(cfg.vocab),
           "d_model": int(cfg.d_model),
           # declared SLA bounds ci.sh guards the ramp arm against (wide
           # margins over the measured values on this CPU box: the guard
           # catches a serve-path collapse, not scheduler jitter)
           # hot_speedup_floor is 0.9, not 1.0: at vocab=512 the
           # transformer forward dominates the step, so the cache's saved
           # search time sits inside run-to-run scheduler noise (~±5%);
           # the guard pairs it with the STRUCTURAL check that cache-on
           # actually searched fewer rows, which is noise-free.
           # measured on this box: p99 4.5-8.3s, qps 1.1-1.8 across runs
           "declared": {"latency_p99_bound_s": 15.0,
                        "queries_per_s_floor": 0.5,
                        "hot_speedup_floor": 0.9}}

    # -- ramp: trip the ladder + the admission cap on purpose -------------
    n_req = 48 if quick else 160
    # recovery=3: the drain tail after the last arrival is the only calm
    # stretch the ladder gets to climb back in before the run ends, and it
    # is ~6-10 steps long at this request mix
    pol = DegradationPolicy(tiers=(1.0, 0.5, 0.25),
                            recall_floors=(0.95, 0.8, 0.5),
                            queue_high=4, queue_low=1, patience=2,
                            recovery=3)
    eng = mk(batch_slots=4, degradation=pol, max_queue=8, result_cache=256)
    # the reduced engine saturates around ~7 qps on the CPU oracle: start
    # under capacity and ramp to ~3x over it, so the run crosses from "ok"
    # into the ladder + shedding instead of collapsing from t=0
    lg_ramp = LoadgenConfig(
        rate_qps=4.0, n_requests=n_req, zipf_s=1.1, pool_size=12,
        prompt_lens=(4, 8), max_new_tokens_choices=(4, 8),
        deadline_mix=((None, 3.0), (1.0, 1.0)), ramp=5.0, seed=0)
    # replay the identical schedule once UNTIMED first: every (group size,
    # prompt length) prefill shape, every miss-row search width and every
    # ladder tier XLA-compiles on first sight, and those multi-second
    # stalls would otherwise be measured as queue wait / latency. The
    # timed replay below then runs compile-free on a warm engine; ladder
    # and cache counters are reported as deltas across it.
    run_load(eng, generate(lg_ramp, cfg.vocab), max_wall_s=120.0)
    sd0, su0 = eng.stepdowns, eng.stepups
    h0, m0 = eng.qcache.hits, eng.qcache.misses
    ramp = run_load(eng, generate(lg_ramp, cfg.vocab), max_wall_s=120.0)
    ramp["stepdowns"] -= sd0
    ramp["stepups"] -= su0
    ramp["cache"] = dict(eng.qcache.stats())
    dh, dm = eng.qcache.hits - h0, eng.qcache.misses - m0
    ramp["cache"].update(hits=dh, misses=dm,
                         hit_rate=dh / max(dh + dm, 1))
    rec["ramp"] = ramp
    rec["ramp"]["config"] = {"rate_qps": lg_ramp.rate_qps,
                             "ramp": lg_ramp.ramp, "zipf_s": lg_ramp.zipf_s,
                             "pool_size": lg_ramp.pool_size}
    rows_out.append((
        "serve/ramp_p99", ramp["latency_p99_s"] * 1e6,
        f"p50={ramp['latency_p50_s']*1e3:.1f}ms;"
        f"qps={ramp['queries_per_s']:.1f};shed={ramp['shed_frac']:.2f};"
        f"expired={ramp['expired_frac']:.2f};"
        f"hit_rate={ramp['cache']['hit_rate']:.2f};"
        f"max_tier={ramp['max_tier']}"))

    # -- cache on/off throughput at saturation ----------------------------
    reps = 3 if quick else 5
    lg_hot = LoadgenConfig(
        rate_qps=1e5, n_requests=(32 if quick else 96), zipf_s=1.2,
        pool_size=8, prompt_lens=(6, 6), max_new_tokens_choices=(6,),
        ramp=1.0, seed=1)
    eng_on = mk(batch_slots=4, result_cache=512)
    eng_off = mk(batch_slots=4, result_cache=0)
    walls = {"on": [], "off": []}
    for r in range(reps + 1):           # rep 0 = compile warmup, dropped
        order = (("on", eng_on), ("off", eng_off)) if r % 2 == 0 else \
                (("off", eng_off), ("on", eng_on))
        for label, e in order:
            s = run_load(e, generate(lg_hot, cfg.vocab), max_wall_s=120.0)
            if r > 0:
                walls[label].append(s["wall_s"])
            if label == "on":
                hot_on = s
            else:
                hot_off = s
    qps_on = lg_hot.n_requests / float(np.median(walls["on"]))
    qps_off = lg_hot.n_requests / float(np.median(walls["off"]))
    rec["hot"] = {
        "cache_on_qps": qps_on, "cache_off_qps": qps_off,
        "speedup_cache_on_vs_off": qps_on / qps_off,
        "cache_hit_rate": eng_on.qcache.hit_rate,
        "searched_rows_on": eng_on.searched_rows,
        "searched_rows_off": eng_off.searched_rows,
        "zipf_s": lg_hot.zipf_s, "pool_size": lg_hot.pool_size,
        "reps": reps,
    }
    rows_out.append((
        "serve/hot_zipf", 1e6 / qps_on,
        f"qps_on={qps_on:.1f};qps_off={qps_off:.1f};"
        f"speedup=x{qps_on/qps_off:.2f};"
        f"hit_rate={eng_on.qcache.hit_rate:.2f}"))

    # -- cold bit-parity --------------------------------------------------
    prng = np.random.RandomState(5)
    prompts = [prng.randint(1, cfg.vocab, size=6) for _ in range(6)]
    tokens = {}
    for cap in (0, 64):
        e = mk(batch_slots=2, result_cache=cap)
        reqs = [e.submit(p, max_new_tokens=5) for p in prompts]
        e.run()
        tokens[cap] = [r.out_tokens for r in reqs]
    rec["cache_cold_bit_parity"] = bool(tokens[0] == tokens[64])
    rows_out.append(("serve/cold_parity", 0.0,
                     f"bit_parity={rec['cache_cold_bit_parity']}"))

    # -- inactive-slot page accounting ------------------------------------
    prompt = prng.randint(1, cfg.vocab, size=6)
    pages = {}
    for b in (1, 4):
        e = mk(batch_slots=b, result_cache=0)
        r = e.submit(prompt, max_new_tokens=6)
        e.run()
        pages[b] = (e.pages, e.searched_rows, len(r.out_tokens) - 1)
    rec["inactive_slot_pages_zero"] = bool(pages[4][1] == pages[4][2])
    rec["pages_single_req_4slots"] = int(pages[4][0])
    rec["pages_single_req_1slot"] = int(pages[1][0])
    rows_out.append((
        "serve/inactive_pages", 0.0,
        f"zero_inactive={rec['inactive_slot_pages_zero']};"
        f"pages_4slot={pages[4][0]};pages_1slot={pages[1][0]}"))

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    with open(os.path.join(root, "BENCH_serve.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rows_out
