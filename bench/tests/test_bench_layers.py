"""Device idle time by the program's layers, and the verify rounds' slot
fill, on a made-up trace with known gaps (``data/layered_trace.pbtxt``)."""
import os

import pytest

import run
import trace_layers as tl
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIELDS = ("layer", "parent", "batch")


def load(name):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, name)) as f:
        return ProfileData.from_text_proto(f.read())


def records(profile):
    """The program's span records as its tracer keeps them, from the host
    events that carry a layer."""
    out = []
    for plane in profile.planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "layer" in stats:
                    out.append({"name": e.name, "parent": None,
                                **{k: stats.pop(k) for k in FIELDS
                                   if k in stats}, "stats": stats})
    return out


@pytest.fixture(scope="module")
def layered():
    profile = load("layered_trace.pbtxt")
    return tr.Trace(profile), records(profile)


def a_run(trace, batches=2):
    lo, hi = trace.window()
    win = run.Window(batch=16, latencies=[0.0] * batches)
    return run.Run(window=win, n_blocks=5924, page_rows=3, d=300, itemsize=4,
                   peak={"hbm_bytes_per_s": 819e9}, build_s=1.0, trace=trace,
                   lo=lo, hi=hi)


def test_innermost_layer_of_nested_spans():
    spans = [(0, 100, "api"), (10, 60, "dispatch"), (20, 30, "pull"),
             (30, 40, "plan"), (70, 80, "pull")]
    assert tl.innermost(spans) == [
        (0, 10, "api"), (10, 20, "dispatch"), (20, 30, "pull"),
        (30, 40, "plan"), (40, 60, "dispatch"), (60, 70, "api"),
        (70, 80, "pull"), (80, 100, "api")]
    assert tl.innermost([]) == []


def test_idle_time_by_layer_is_exact(layered):
    trace, recs = layered
    lo, hi = trace.window()
    assert (lo, hi) == (0.0, 100_000.0)
    by = tl.idle_by_layer(trace, lo, hi, tl.layers_of(recs))
    assert by == {"api": 4_000.0, "dispatch": 8_000.0, "plan": 12_000.0,
                  "pull": 36_000.0, tl.UNATTRIBUTED: 4_000.0}
    idle = sum(b - a for a, b in tr.gaps(trace.device["/device:TPU:0"],
                                         lo, hi))
    assert sum(by.values()) == idle == 64_000.0


def test_readers_on_the_layered_trace(layered, monkeypatch):
    trace, recs = layered
    monkeypatch.setattr(tl, "program_spans", lambda: recs)
    r = a_run(trace)
    read = {name: run.metric_reader(name)(r) for name in (
        "plan.idle_ms", "pull.idle_ms", "dispatch.idle_ms",
        "verify.slot_fill", "device.idle_pct")}
    assert read["plan.idle_ms"] == pytest.approx(0.006)
    assert read["pull.idle_ms"] == pytest.approx(0.018)
    assert read["dispatch.idle_ms"] == pytest.approx(0.006)   # api included
    # round 1: 64 of 64 and 60 of 64 slots; round 2: 20 of 32, 16 of 16
    assert read["verify.slot_fill"] == pytest.approx(160 / 176)
    assert tl.round_slots(recs, {7, 8}) == {"verify_round1": [124, 128],
                                            "verify_round2": [36, 48]}
    # the three layers explain the idle time but for the unattributed 4%
    per_batch_ms = read["device.idle_pct"] / 100 * (r.hi - r.lo) / 1e6 / 2
    explained = read["plan.idle_ms"] + read["pull.idle_ms"] + \
        read["dispatch.idle_ms"]
    assert explained / per_batch_ms == pytest.approx(60 / 64)


def test_slot_fill_counts_the_last_batches_and_caps_truncated_rounds():
    def rnd(name, batch, slots, union):
        return {"name": name, "batch": batch, "parent": "search",
                "stats": {"slots": slots, "union": union}}

    recs = [rnd("verify_round1", 1, 8, 3), {"name": "api_search", "batch": 1,
                                            "parent": None, "stats": {}},
            rnd("verify_round1", 2, 16, 40), rnd("verify_round2", 2, 8, 6),
            {"name": "api_search", "batch": 2, "parent": None, "stats": {}}]
    # a tile cap truncated batch 2's round 1: all 16 slots were selected
    assert tl.slot_fill(recs, 1) == pytest.approx(22 / 24)
    assert tl.slot_fill(recs, 2) == pytest.approx(25 / 32)
    assert tl.slot_fill(recs, 0) is None
    assert tl.slot_fill([], 5) is None


def test_a_program_without_layers_reads_nothing(layered, monkeypatch):
    trace, recs = layered
    bare = [{k: v for k, v in r.items() if k not in FIELDS + ("stats",)}
            for r in recs]
    monkeypatch.setattr(tl, "program_spans", lambda: bare)
    r = a_run(trace)
    for name in ("plan.idle_ms", "pull.idle_ms", "dispatch.idle_ms",
                 "verify.slot_fill"):
        assert run.metric_reader(name)(r) is None
    monkeypatch.setattr(tl, "program_spans", lambda: recs)
    r = run.Run(window=run.Window(batch=8), n_blocks=10, page_rows=3, d=300,
                itemsize=4, peak={"hbm_bytes_per_s": 819e9}, build_s=2.0)
    for name in ("plan.idle_ms", "pull.idle_ms", "dispatch.idle_ms",
                 "verify.slot_fill"):
        assert run.metric_reader(name)(r) is None


def test_existing_readers_read_the_recorded_chip_trace_as_before(monkeypatch):
    """The six accepted readers on the trace cut from a chip run, against
    the values they read before layered spans existed; the new readers
    find no layered span there."""
    monkeypatch.setattr(tl, "program_spans", lambda: [])
    chain = tr.Trace(load("chain_trace.pbtxt"))
    lo, hi = chain.window()
    win = run.Window(batch=16, latencies=[0.0, 0.0], pages=2 * 16 * 33334)
    r = run.Run(window=win, n_blocks=33334, page_rows=3, d=300, itemsize=4,
                peak={"hbm_bytes_per_s": 819e9}, build_s=1.0, trace=chain,
                lo=lo, hi=hi)
    read = {name: run.metric_reader(name)(r) for name in (
        "verify.kernel_ms", "verify.roofline_pct", "verify.pages_frac",
        "frontend.device_ms", "device.idle_pct", "build_s",
        "plan.idle_ms", "pull.idle_ms", "dispatch.idle_ms",
        "verify.slot_fill")}
    assert read == {
        "verify.kernel_ms": 42.7425335,
        "verify.roofline_pct": 0.34280391199337057,
        "verify.pages_frac": 1.0,
        "frontend.device_ms": None,
        "device.idle_pct": 4.132226216471768,
        "build_s": 1.0,
        "plan.idle_ms": None, "pull.idle_ms": None,
        "dispatch.idle_ms": None, "verify.slot_fill": None}
