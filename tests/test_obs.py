"""Observability tier (DESIGN.md §14): span tracer, metrics registry, the
stats-contract choke point, and end-to-end metric-name resolution after one
smoke search per backend."""
import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api.adapters import PromipsSearcher
from repro.core.promips import ProMIPS
from repro.core.runtime import RuntimeConfig
from repro.core.sharded import MutableShardedProMIPS
from repro.core import search_fused as sf
from repro.kernels.block_mips import walk_steps
from repro.obs import metrics, trace
from repro.stream.mutable import MutableProMIPS


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts from tracer-off / empty-registry and leaves the
    process-wide switches the way it found them (off)."""
    trace.disable()
    trace.clear()
    metrics.disable()
    metrics.reset()
    yield
    trace.disable()
    trace.clear()
    trace.configure(capacity=8192)
    metrics.disable()
    metrics.reset()


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1500, 24)).astype(np.float32)
    q = rng.standard_normal((6, 24)).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def pm(corpus):
    x, _ = corpus
    return ProMIPS.build(x, m=8, c=0.9, p=0.6, seed=0, norm_strata=4)


@pytest.fixture(scope="module")
def searcher(corpus):
    """An API index with the sketch prefilter on: every search-path span
    runs, round 2 included, and a tile cap below the block count truncates
    (so the priority pull runs too)."""
    x, _ = corpus
    return api.build(x, backend="promips",
                     guarantee=api.GuaranteeConfig(c=0.9, p0=0.5, k=5),
                     seed=0, prefilter=True)


def capped(searcher, **kw):
    return dataclasses.replace(searcher.runtime,
                               tile_cap=searcher.pm.meta.n_blocks // 2, **kw)


# every span of the search path, by layer
SEARCH_SPANS = {
    "api_search": "api",
    "search": "dispatch", "select_frontend": "dispatch",
    "prefilter_round1": "dispatch", "prefilter_round2": "dispatch",
    "verify_round1": "dispatch", "verify_round2": "dispatch",
    "compensation": "dispatch", "rescore": "dispatch",
    "plan_tile_round1": "plan", "plan_tile_round2": "plan",
    "pull_priority": "pull", "pull_mask_round1": "pull",
    "pull_mask_round2": "pull", "pull_answers": "pull",
}


# -- span tracer -------------------------------------------------------------

def test_disabled_span_is_shared_noop():
    assert trace.span("anything") is trace.span("other")
    assert trace.span("x") is trace._NULL
    with trace.span("x") as sp:
        assert sp.fence(123) == 123
    assert trace.spans() == []


def test_active_override_records_without_global_enable():
    with trace.span("forced", active=True):
        pass
    assert [s["name"] for s in trace.spans()] == ["forced"]
    # and active=False forces the no-op even when globally enabled
    trace.enable()
    assert trace.span("y", active=False) is trace._NULL


def test_ring_is_bounded_and_total_is_monotonic():
    trace.configure(capacity=4)
    trace.enable()
    t0 = trace.total()
    for i in range(10):
        with trace.span(f"s{i}"):
            pass
    assert len(trace.spans()) == 4
    assert [s["name"] for s in trace.spans()] == ["s6", "s7", "s8", "s9"]
    assert trace.total() == t0 + 10
    trace.clear()
    assert trace.spans() == [] and trace.total() == t0 + 10
    with pytest.raises(ValueError):
        trace.configure(capacity=0)


def test_fence_records_flag_and_returns_value(pm, corpus):
    _, q = corpus
    trace.enable(fence=True)
    arr = jnp.arange(4.0)
    with trace.span("fenced_one") as sp:
        out = sp.fence(arr)
    assert out is arr
    assert trace.spans()[-1]["fenced"] is True
    trace.disable()
    trace.enable(fence=False)
    with trace.span("unfenced") as sp:
        sp.fence(arr)
    assert trace.spans()[-1]["fenced"] is False


def test_span_feeds_declared_histogram():
    with trace.span("x", active=True, metric="search.batch_us"):
        pass
    snap = metrics.snapshot()
    assert snap["search.batch_us"]["count"] == 1


def test_export_chrome_trace(tmp_path):
    trace.enable()
    with trace.span("alpha"):
        with trace.span("beta"):
            pass
    path = trace.export_chrome_trace(str(tmp_path / "sub" / "trace.json"))
    doc = json.load(open(path))
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    names = {e["name"] for e in doc["traceEvents"]}
    assert names == {"alpha", "beta"}
    for e in doc["traceEvents"]:
        assert e["ph"] == "X" and "ts" in e and "dur" in e
        assert e["args"]["fenced"] is False


def test_span_records_and_chrome_export_carry_layer_parent_batch_stats(
        tmp_path):
    trace.enable()
    for _ in range(2):
        with trace.span("outer", layer="api"):
            with trace.span("inner", layer="plan", slots=8, union=5):
                pass
    recs = trace.spans()
    assert [r["name"] for r in recs] == ["inner", "outer"] * 2
    inner, outer = recs[0], recs[1]
    assert (inner["layer"], inner["parent"], inner["stats"]) == (
        "plan", "outer", {"slots": 8, "union": 5})
    assert (outer["layer"], outer["parent"], outer["stats"]) == ("api", None,
                                                                 {})
    # the outermost span allocates the batch id; nested spans inherit it
    assert inner["batch"] == outer["batch"]
    assert recs[2]["batch"] == recs[3]["batch"] != outer["batch"]
    doc = json.load(open(trace.export_chrome_trace(str(tmp_path / "t.json"))))
    args = [e["args"] for e in doc["traceEvents"]]
    assert args[0] == {"fenced": False, "layer": "plan", "parent": "outer",
                       "batch": inner["batch"], "slots": 8, "union": 5}
    assert args[1] == {"fenced": False, "layer": "api",
                       "batch": outer["batch"]}


def test_profiler_host_plane_carries_every_search_span_with_its_layer(
        searcher, corpus, tmp_path):
    """One fused search under `jax.profiler.trace`, spans annotated: each
    search-path span is a host event whose stats name its layer, all of one
    batch, the verify rounds with their slot counts."""
    from jax.profiler import ProfileData

    _, q = corpus
    rt = capped(searcher)
    searcher.search(q, runtime=rt)                     # compile outside
    trace.configure(enabled=True, annotate=True)
    with jax.profiler.trace(str(tmp_path)):
        searcher.search(q, runtime=rt)
    trace.configure(enabled=False, annotate=False)
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats)
                    if "layer" in stats:
                        seen[e.name] = stats
    assert set(seen) == set(SEARCH_SPANS)
    assert {n: st["layer"] for n, st in seen.items()} == SEARCH_SPANS
    assert len({st["batch"] for st in seen.values()}) == 1
    assert "parent" not in seen["api_search"]
    assert seen["search"]["parent"] == "api_search"
    assert seen["plan_tile_round1"]["parent"] == "search"
    for rnd in ("verify_round1", "verify_round2"):
        assert 0 < seen[rnd]["union"] and 0 < seen[rnd]["slots"]


def _pulls(monkeypatch):
    """Record, for every device -> host copy of a jax array (numpy reads a
    CPU array through its buffer, anything else through ``_value``), the
    names of the spans open on this thread at that moment (innermost
    last)."""
    from jax._src.array import ArrayImpl

    seen = []
    value, buffer = ArrayImpl._value, ArrayImpl.__buffer__

    def opened():
        seen.append([sp.name for sp in trace._open.stack])

    def spy_value(self):
        opened()
        return value.fget(self)

    def spy_buffer(self, flags):
        opened()
        return buffer(self, flags)

    monkeypatch.setattr(ArrayImpl, "_value", property(spy_value))
    monkeypatch.setattr(ArrayImpl, "__buffer__", spy_buffer)
    return seen


def test_every_pull_of_the_search_path_is_in_a_pull_span(searcher, corpus,
                                                         monkeypatch):
    _, q = corpus
    searcher.search(q, runtime=capped(searcher))       # compile outside
    pulls = _pulls(monkeypatch)
    searcher.search(q, runtime=capped(searcher))
    n_off = len(pulls)
    pulls.clear()
    searcher.search(q, runtime=capped(searcher, obs=True))
    # obs adds no pull of its own, and removes none
    assert len(pulls) == n_off >= 4
    for open_spans in pulls:
        assert open_spans and open_spans[-1].startswith("pull_"), open_spans
        assert not any(n.startswith("plan_") for n in open_spans)
    assert {s[-1] for s in pulls} == {"pull_priority", "pull_mask_round1",
                                      "pull_mask_round2", "pull_answers"}


def test_verify_round_stats_equal_plan_tile_result(searcher, corpus,
                                                   monkeypatch):
    _, q = corpus
    plans = []
    plan_tile = sf._plan_tile

    def spy(mask, *a, **kw):
        plan = plan_tile(mask, *a, **kw)
        plans.append((int(mask.any(axis=0).sum()), plan))
        return plan

    monkeypatch.setattr(sf, "_plan_tile", spy)
    trace.enable()
    # the oracle walks with no grid (0 steps); the kernel (interpreted
    # here) walks several pages per grid step
    for rt in (searcher.runtime, capped(searcher),
               capped(searcher, use_pallas=True)):
        searcher.search(q, runtime=rt)
    rounds = [r for r in trace.spans() if r["name"].startswith("verify_round")]
    assert len(rounds) == len(plans) == 6
    meta = searcher.pm.meta
    for i, (rec, (union, plan)) in enumerate(zip(rounds, plans)):
        slots, _, _, _, n_union = plan
        steps = walk_steps(len(slots), len(q), meta.d, searcher.guarantee.k,
                           meta.page_rows) if i >= 4 else 0
        assert n_union == union
        assert rec["stats"] == {"slots": len(slots), "union": union,
                                "steps": steps}
    assert all(0 < r["stats"]["steps"] < r["stats"]["slots"]
               for r in rounds[4:])


# -- metrics registry --------------------------------------------------------

def test_undeclared_metric_name_raises():
    with pytest.raises(ValueError, match="undeclared"):
        metrics.counter("search.made_up")
    with pytest.raises(ValueError, match="declared as a"):
        metrics.gauge("search.queries")   # declared as a counter


def test_histogram_log2_buckets():
    h = metrics.histogram("search.batch_us")
    assert h.bucket_of(0.5) == 0 and h.bucket_of(1.0) == 0
    assert h.bucket_of(1.5) == 1 and h.bucket_of(2.0) == 1
    assert h.bucket_of(3.0) == 2 and h.bucket_of(1024.0) == 10
    for v in (0.5, 3.0, 3.5, 1000.0):
        h.observe(v)
    d = h.to_dict()
    assert d["count"] == 4 and d["buckets"] == {"0": 1, "2": 2, "10": 1}
    assert d["mean"] == pytest.approx(sum((0.5, 3.0, 3.5, 1000.0)) / 4)


def test_snapshot_only_contains_touched_instruments():
    metrics.counter("stream.deletes").inc(3)
    snap = metrics.snapshot()
    assert snap["stream.deletes"] == 3
    assert "serve.pages" not in snap
    # every live name must be declared (the ci.sh obs-guard invariant)
    assert set(snap) <= set(metrics.GLOSSARY)


def test_observe_search_gated_by_enable():
    metrics.observe_search({"pages": 5, "candidates": 7, "exhausted": 0,
                            "queries": 2})
    assert "search.pages" not in metrics.snapshot()
    metrics.enable()
    metrics.observe_search({"pages": 5, "candidates": 7, "exhausted": 0,
                            "queries": 2})
    snap = metrics.snapshot()
    assert snap["search.pages"] == 5 and snap["search.queries"] == 2


def test_prometheus_text_exposition():
    metrics.counter("search.pages").inc(11)
    h = metrics.histogram("search.batch_us")
    h.observe(3.0)
    h.observe(100.0)
    text = metrics.prometheus_text()
    assert "# HELP repro_search_pages" in text
    assert "# TYPE repro_search_pages counter" in text
    assert "repro_search_pages 11" in text
    assert "# TYPE repro_search_batch_us histogram" in text
    assert 'repro_search_batch_us_bucket{le="+Inf"} 2' in text
    assert "repro_search_batch_us_count 2" in text
    # cumulative buckets are nondecreasing
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("repro_search_batch_us_bucket")]
    assert cums == sorted(cums)


def test_flush_jsonl(tmp_path):
    metrics.counter("search.pages").inc(2)
    path = str(tmp_path / "m" / "metrics.jsonl")
    metrics.flush_jsonl(path, extra={"run": "t1"})
    metrics.flush_jsonl(path, extra={"run": "t2"})
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 2
    assert lines[0]["run"] == "t1"
    assert lines[1]["metrics"]["search.pages"] == 2


# -- stats contract (core/stats.stats_totals choke point) --------------------

def test_all_stats_classes_share_the_normalized_key_set(pm, corpus):
    x, q = corpus
    qj = jnp.asarray(q, jnp.float32)
    expected = {"pages", "candidates", "exhausted", "queries"}

    _, _, device_stats = pm.search(qj, k=5)                    # SearchStats
    _, _, host_stats = pm.search_host(q[0], k=5)               # HostStats
    stream = MutableProMIPS(x[:800], m=8, c=0.9, p=0.6, seed=0)
    _, _, stream_stats = stream.search(qj, k=5)                # StreamStats
    shd = MutableShardedProMIPS(x, 2, m=8, c=0.9, p=0.6, seed=0)
    _, _, sharded_stats = shd.search(qj, k=5)                  # ShardedStats

    for st in (device_stats, host_stats, stream_stats, sharded_stats):
        d = st.to_dict()
        assert set(d) == expected, type(st).__name__
        assert all(isinstance(v, int) for v in d.values()), type(st).__name__
    # pre-aggregated sharded totals must still count the real batch size
    assert sharded_stats.to_dict()["queries"] == len(q)


def test_metrics_resolve_after_one_smoke_search_per_backend(pm, corpus):
    """Every metric name instrumentation emits during a smoke search on
    each backend resolves against the declared glossary, and the core
    search.* set is present."""
    x, q = corpus
    qj = jnp.asarray(q, jnp.float32)
    metrics.enable()
    trace.enable(fence=True)

    for verification in ("fused", "batched"):
        _, _, st = pm.search(qj, k=5, verification=verification,
                             norm_adaptive=True, cs_prune=True)
        st.to_dict()
    _, _, st = pm.search_host(q[0], k=5)                       # host
    st.to_dict()
    stream = MutableProMIPS(x[:800], m=8, c=0.9, p=0.6, seed=0)
    # a dirty snapshot (live delta rows) so the segment-merge span runs
    stream.insert(np.arange(800, 804), x[800:804])
    _, _, st = stream.search(qj, k=5)                          # stream
    st.to_dict()
    shd = MutableShardedProMIPS(x, 2, m=8, c=0.9, p=0.6, seed=0)
    _, _, st = shd.search(qj, k=5)                             # sharded
    st.to_dict()
    PromipsSearcher(pm, RuntimeConfig()).search(q, k=5)        # API facade

    snap = metrics.snapshot()
    assert set(snap) <= set(metrics.GLOSSARY), \
        sorted(set(snap) - set(metrics.GLOSSARY))
    required = {"search.queries", "search.pages", "search.candidates",
                "search.exhausted", "search.batch_us", "sharded.dispatch_us",
                "sharded.merge_us", "search.merge_us",
                "fused.verify_retraces"}
    assert required <= set(snap), sorted(required - set(snap))
    assert snap["search.queries"] > 0
    assert snap["search.batch_us"]["count"] == 1     # the one API call


# -- bounded VERIFY_TRACES ring ----------------------------------------------

def test_verify_trace_ring_is_bounded_with_monotonic_total():
    ring = sf.TraceRing(capacity=3)
    for i in range(7):
        ring.append(("key", i))
    assert len(ring) == 3
    assert list(ring) == [("key", 4), ("key", 5), ("key", 6)]
    assert ring.total == 7
    assert ring[0] == ("key", 4) and ring[len(list(ring)):] == []
    assert bool(ring)
    ring.clear()
    assert len(ring) == 0 and not ring and ring.total == 7
    # the live module-level ring exposes the same surface
    assert isinstance(sf.VERIFY_TRACES, sf.TraceRing)
    assert sf.VERIFY_TRACES.total >= len(sf.VERIFY_TRACES)


def test_retrace_total_surfaces_as_gauge():
    before = sf.VERIFY_TRACES.total
    snap = metrics.snapshot()   # collector pulls the ring total
    assert snap["fused.verify_retraces"] == before


# -- serve-path telemetry ----------------------------------------------------

def test_engine_telemetry_and_shedding():
    import jax

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve.engine import DecodeEngine

    cfg = get_config("tinyllama-1.1b").reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(params, cfg, batch_slots=2, max_len=64,
                       obs=True, max_queue=3)
    rng = np.random.RandomState(0)
    reqs = [eng.submit(rng.randint(1, cfg.vocab, size=8), max_new_tokens=4)
            for _ in range(3)]
    assert all(r is not None for r in reqs)
    assert eng.submit(rng.randint(1, cfg.vocab, size=8)) is None  # shed
    eng.run()

    snap = eng.metrics_snapshot()
    assert snap["steps"] == eng.steps and snap["queue_depth"] == 0
    assert snap["serve.requests_submitted"] == 3
    assert snap["serve.requests_shed"] == 1
    assert snap["serve.requests_completed"] == 3
    assert snap["serve.queue_wait_us"]["count"] == 3
    assert snap["serve.request_us"]["count"] == 3
    assert snap["serve.decode_steps"] == snap["serve.step_us"]["count"] > 0
    assert snap["serve.slot_occupancy"] == 0.0
    for r in reqs:
        assert 0.0 < r.t_submit <= r.t_admit <= r.t_done
    # non-serve engine state keys come from the engine, serve.* from the
    # registry; nothing outside the declared glossary leaks in
    assert {k for k in snap if "." in k} <= set(metrics.GLOSSARY)


# -- RuntimeConfig.obs -------------------------------------------------------

def test_runtime_config_obs_validation():
    with pytest.raises(ValueError, match="obs"):
        RuntimeConfig(obs="yes")
    assert RuntimeConfig(obs=True).obs is True
    assert RuntimeConfig().obs is False


def test_obs_toggle_is_bit_identical_and_records(pm, corpus):
    _, q = corpus
    qj = jnp.asarray(q, jnp.float32)
    kw = dict(k=5, verification="fused", norm_adaptive=True, cs_prune=True)
    ids_off, scores_off, _ = pm.search(qj, **kw)
    assert trace.spans() == []   # obs off: nothing recorded
    n_traces = sf.VERIFY_TRACES.total
    ids_on, scores_on, _ = pm.search(qj, obs=True, **kw)
    # obs compiles no program of its own: the verify jits do not retrace
    assert sf.VERIFY_TRACES.total == n_traces
    assert np.array_equal(np.asarray(ids_off), np.asarray(ids_on))
    assert np.array_equal(np.asarray(scores_off), np.asarray(scores_on))
    names = {s["name"] for s in trace.spans()}
    assert {"search", "select_frontend", "verify_round1"} <= names
    n_spans = trace.total()
    pm.search(qj, **kw)
    assert sf.VERIFY_TRACES.total == n_traces and trace.total() == n_spans
