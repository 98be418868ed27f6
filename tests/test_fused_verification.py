"""Fused block-sparse verification (PR 4): three-way backend parity,
finite-budget semantics, pow2 tile bucketing / bounded jit cache, and the
batch-native selection frontend."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ProMIPS, RuntimeConfig, runtime_search
from repro.core import search_fused as sf
from repro.core.quick_probe import quick_probe, quick_probe_batch
from repro.core.search_device import _group_table, select_blocks_batch
from repro.core.search_common import next_pow2
from repro.data.synthetic import mf_factors

STAT_FIELDS = ("pages", "candidates", "probe_passed", "used_round2",
               "radius0", "radius1", "exhausted", "rows")
# vs "scan" the radii are only ULP-equal: its per-block matvec dots
# reassociate differently than the one-matmul backends (the reason PR 1
# introduced the shared `_rescore`), and radius1 is a function of the raw
# running k-th score. ids/scores/pages/candidates/rows are still exact.
SCAN_STAT_FIELDS = tuple(f for f in STAT_FIELDS if f != "radius1")


@pytest.fixture(scope="module")
def built(mf_corpus):
    x, q = mf_corpus
    pm = ProMIPS.build(x, m=8, c=0.9, p=0.5, norm_strata=4, page_bytes=2048)
    return x, jnp.asarray(q, jnp.float32), pm


def _assert_same(out_a, out_b, label, fields=STAT_FIELDS):
    ids_a, scores_a, st_a = out_a
    ids_b, scores_b, st_b = out_b
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b),
                                  err_msg=f"{label}: ids")
    np.testing.assert_array_equal(np.asarray(scores_a), np.asarray(scores_b),
                                  err_msg=f"{label}: scores")
    for field in fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(st_a, field)), np.asarray(getattr(st_b, field)),
            err_msg=f"{label}: stat {field}")


@pytest.mark.parametrize("norm_adaptive,cs_prune",
                         [(False, False), (True, True)])
def test_three_way_parity_full_budget(built, norm_adaptive, cs_prune):
    """fused vs batched vs scan at the guarantee-default full budget:
    bit-identical ids, scores AND every stats field (pages, candidates,
    rows, radii, exhausted)."""
    x, q, pm = built
    outs = {v: pm.search(q, k=10, verification=v,
                         norm_adaptive=norm_adaptive, cs_prune=cs_prune)
            for v in ("scan", "batched", "fused")}
    _assert_same(outs["fused"], outs["batched"], "fused-vs-batched")
    _assert_same(outs["fused"], outs["scan"], "fused-vs-scan",
                 fields=SCAN_STAT_FIELDS)
    np.testing.assert_allclose(
        np.asarray(outs["fused"][2].radius1), np.asarray(outs["scan"][2].radius1),
        rtol=1e-5, err_msg="fused-vs-scan: radius1 (ULP-level only)")


@pytest.mark.parametrize("budget", [4, 37, 128])
def test_fused_equals_batched_at_finite_budget(built, budget):
    """Finite-budget divergence semantics: "fused" caps the SHARED union
    tile at ``budget`` blocks exactly like "batched" (first budget union
    blocks in layout order, over-capped queries flagged ``exhausted``), so
    the two agree bit-for-bit at EVERY budget. "scan" budgets differently —
    each query's own selection is capped — so it is only guaranteed to
    agree at the full budget (test above)."""
    x, q, pm = built
    out_b = pm.search(q, k=10, budget=budget, budget2=budget,
                      verification="batched")
    out_f = pm.search(q, k=10, budget=budget, budget2=budget,
                      verification="fused")
    _assert_same(out_f, out_b, f"budget={budget}")


def test_fused_flags_exhausted_when_budget_truncates(built):
    x, q, pm = built
    _, _, st = pm.search(q, k=10, budget=2, budget2=2, verification="fused")
    assert np.asarray(st.exhausted).any()


def test_runtime_default_is_fused_and_validated(built):
    """RuntimeConfig exposes "fused" (the default) and rejects unknowns by
    name; the facade path dispatches it."""
    x, q, pm = built
    assert RuntimeConfig().verification == "fused"
    with pytest.raises(ValueError, match="fused"):
        RuntimeConfig(verification="nope")
    ids, scores, stats = runtime_search(pm.arrays, pm.meta, q[:4],
                                        RuntimeConfig(k=5))
    assert np.asarray(ids).shape == (4, 5)
    ids_b, scores_b, stats_b = runtime_search(
        pm.arrays, pm.meta, q[:4], RuntimeConfig(k=5, verification="batched"))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_b))
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(scores_b))


def test_plan_tile_pow2_buckets():
    """Tile sizes are pow2-bucketed (or the cap): across EVERY possible
    union count the number of distinct compiled shapes stays O(log NB)."""
    n_blocks, n_batch = 500, 4
    sizes = set()
    for n_union in range(1, n_blocks + 1):
        mask = np.zeros((n_batch, n_blocks), bool)
        mask[:, np.random.RandomState(n_union).permutation(n_blocks)[:n_union]] = True
        plan = sf._plan_tile(mask, n_blocks, n_blocks)
        assert plan is not None
        slots, sel, lost, dense, union = plan
        assert not lost.any() and union == n_union
        sizes.add((len(slots), dense))
    assert len(sizes) <= int(np.ceil(np.log2(n_blocks))) + 2, sizes
    for ns, dense in sizes:
        assert dense or ns == min(next_pow2(ns), n_blocks) or ns == n_blocks
    assert sf._plan_tile(np.zeros((n_batch, n_blocks), bool), 500, 500) is None


def test_verify_jit_cache_stays_bounded(built):
    """End to end: searches over many different query batches (different
    union sizes each round) retrace the verification jits at most once per
    pow2 bucket (per round flavor: plain / dense / cached) — the jit cache
    is bounded by O(log n_blocks), NOT by the number of distinct union
    sizes. A second identical sweep must not add a single retrace."""
    x, q, pm = built
    sf.VERIFY_TRACES.clear()
    rng = np.random.RandomState(7)

    def sweep():
        r = np.random.RandomState(7)
        for i in range(6):
            scale = 0.25 * (i + 1)
            qi = jnp.asarray(scale * r.standard_normal((8, x.shape[1])),
                             jnp.float32)
            pm.search(qi, k=10, verification="fused", norm_adaptive=True,
                      cs_prune=True)

    sweep()
    traces = list(sf.VERIFY_TRACES)
    assert traces, "fused path never traced a verification round"
    assert len(traces) == len(set(traces)), "retraced an already-seen shape"
    # 4 flavors (sparse, dense +- score cache, cached) x O(log NB) buckets
    max_shapes = 4 * (int(np.ceil(np.log2(pm.meta.n_blocks))) + 2)
    assert len(set(traces)) <= max_shapes, traces
    sweep()  # identical unions -> every shape already compiled
    assert len(sf.VERIFY_TRACES) == len(traces), (
        "second identical sweep recompiled", sf.VERIFY_TRACES[len(traces):])


def test_quick_probe_batch_matches_vmap(built):
    """The batch-native Quick-Probe is bit-identical to vmap-of-per-query."""
    import jax

    x, q, pm = built
    arrays, meta = pm.arrays, pm.meta
    table = _group_table(arrays)
    q_proj = q @ arrays.a
    q_l1 = jnp.sum(jnp.abs(q), axis=1)
    rows_b, rad_b, ok_b = quick_probe_batch(table, q_proj, q_l1,
                                            meta.c, meta.x_p)
    rows_v, rad_v, ok_v = jax.vmap(
        lambda qp, ql: quick_probe(table, qp, ql, meta.c, meta.x_p)
    )(q_proj, q_l1)
    np.testing.assert_array_equal(np.asarray(rows_b), np.asarray(rows_v))
    np.testing.assert_array_equal(np.asarray(rad_b), np.asarray(rad_v))
    np.testing.assert_array_equal(np.asarray(ok_b), np.asarray(ok_v))


def test_blocks_from_radii_matches_bruteforce(built):
    """The block_sp_idx gather mapping == brute-force "any selected
    sub-partition in [block_sp_lo, block_sp_hi)" per block."""
    x, q, pm = built
    arrays = pm.arrays
    rng = np.random.RandomState(3)
    q_proj = q[:6] @ arrays.a
    radius = jnp.asarray(np.abs(rng.standard_normal(6)).astype(np.float32) * 3)
    got = np.asarray(select_blocks_batch(arrays, q_proj, radius))

    center = np.asarray(arrays.sp_center)
    d_sp = np.sqrt(np.maximum(
        (center * center).sum(-1)[None, :]
        - 2.0 * np.asarray(q_proj) @ center.T
        + (np.asarray(q_proj) ** 2).sum(-1)[:, None], 0.0))
    sel_sp = d_sp <= np.asarray(radius)[:, None] + np.asarray(arrays.sp_radius)
    lo, hi = np.asarray(arrays.block_sp_lo), np.asarray(arrays.block_sp_hi)
    want = np.stack([
        [bool(sel_sp[b, lo[nb]:hi[nb]].any()) for nb in range(len(lo))]
        for b in range(6)])
    np.testing.assert_array_equal(got, want)


def test_fused_dense_and_sparse_tiles_agree(built):
    """ops.block_mips dense (walk everything in place) vs explicit slot walk
    over the same blocks: identical outputs."""
    from repro.kernels import ops

    x, q, pm = built
    arrays, meta = pm.arrays, pm.meta
    n_blocks = meta.n_blocks
    b, k = 8, 5
    rng = np.random.RandomState(1)
    qj = q[:b]
    sel = jnp.asarray(rng.rand(b, n_blocks) > 0.6)
    init_s = jnp.full((b, k), -jnp.inf)
    init_r = jnp.full((b, k), -1, jnp.int32)
    c_half = jnp.asarray(rng.rand(b).astype(np.float32) * 10)
    valid = arrays.ids >= 0
    slots = jnp.arange(n_blocks, dtype=jnp.int32)
    args = (arrays.x, valid, qj, slots, sel, init_s, init_r, c_half)
    dense_out = ops.block_mips(*args, k=k, page_rows=meta.page_rows,
                               dense=True)
    sparse_out = ops.block_mips(*args, k=k, page_rows=meta.page_rows,
                                dense=False)
    for name, a, b_ in zip(("top_s", "top_r", "cnt", "pages", "cand"),
                           dense_out, sparse_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_),
                                      err_msg=name)


@pytest.mark.parametrize("budget", [None, 4, 37, 128])
def test_fused_in_graph_under_ambient_trace(built, budget):
    """`runtime_search` with verification="fused" inside jit — even with
    CONCRETE queries closed over but traced index arrays — runs the
    IN-GRAPH fused driver (`core/search_graph.py`), bit-identical to the
    eager host-orchestrated driver AND the batched graph at every budget:
    ids, scores and every stats field."""
    import jax

    x, q, pm = built
    q_np = np.asarray(q[:8])
    cfg = RuntimeConfig(k=5, budget=budget, budget2=budget,
                        norm_adaptive=True, cs_prune=True)
    traced = jax.jit(lambda arrays: runtime_search(arrays, pm.meta, q_np, cfg))
    out_t = traced(pm.arrays)
    out_e = runtime_search(pm.arrays, pm.meta, q_np, cfg)
    _assert_same(out_t, out_e, f"jit-fused-vs-eager-fused budget={budget}")
    cfg_b = RuntimeConfig(k=5, budget=budget, budget2=budget,
                          norm_adaptive=True, cs_prune=True,
                          verification="batched")
    out_b = runtime_search(pm.arrays, pm.meta, q_np, cfg_b)
    _assert_same(out_t, out_b, f"jit-fused-vs-batched budget={budget}")


def test_tile_buckets_cover_plan_tile_sizes():
    """The in-graph lax.switch branch list is exactly the set of tile sizes
    the host planner can choose: min(next_pow2(u), cap) for every union
    count u — so bucket selection by searchsorted reproduces the host
    driver's sizing rule, and the branch count stays O(log cap)."""
    from repro.core.search_graph import _tile_buckets

    for cap in (1, 2, 3, 37, 64, 500):
        sizes = _tile_buckets(cap)
        assert sizes[-1] == cap and sorted(set(sizes)) == list(sizes)
        want = {min(next_pow2(u), cap) for u in range(1, cap + 9)}
        assert set(sizes) == want, (cap, sizes, want)
        # searchsorted picks the same size the host planner computes
        for u in range(1, cap + 9):
            idx = int(np.searchsorted(np.asarray(sizes), u))
            idx = min(idx, len(sizes) - 1)
            assert sizes[idx] == min(next_pow2(u), cap), (cap, u)


def test_sharded_and_stream_get_fused_by_default(mf_corpus):
    """Every guaranteed backend rides the fused default: facade-built
    promips / promips-stream / sharded searchers run verification="fused"
    and return identical results to an explicit batched override."""
    from repro import api

    x, q = mf_corpus
    guarantee = api.GuaranteeConfig(c=0.9, p0=0.5, k=10)
    for backend in ("promips", "promips-stream", "sharded"):
        s = api.build(x, backend=backend, guarantee=guarantee, seed=0,
                      m=8, page_bytes=2048)
        assert s.runtime.verification == "fused", backend
        res = s.search(q, k=10)
        cfg_b = RuntimeConfig(k=10, verification="batched")
        res_b = s.search(q, k=10, runtime=cfg_b)
        np.testing.assert_array_equal(res.ids, res_b.ids, err_msg=backend)
        np.testing.assert_array_equal(res.scores, res_b.scores,
                                      err_msg=backend)
        for key in ("pages", "candidates", "exhausted"):
            assert res.stats[key] == res_b.stats[key], (backend, key)
