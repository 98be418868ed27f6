"""Trace reduction on a trace recorded on a TPU v5e (``data/``)."""
import os

import pytest

import run
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def chain():
    with open(os.path.join(DATA, "chain_trace.pbtxt")) as f:
        return tr.Trace.from_text_proto(f.read())


def ev(a, b, name="op"):
    return tr.Event(name, float(a), float(b))


def test_union_counts_overlaps_once():
    evs = [ev(0, 10), ev(5, 15), ev(12, 14), ev(20, 30), ev(30, 31)]
    assert tr.union((e.start, e.end) for e in evs) == [(0, 15), (20, 31)]
    assert tr.busy_ns(evs) == 26
    assert tr.gaps(evs, -5, 40) == [(-5, 0), (15, 20), (31, 40)]
    assert tr.busy_ns(tr.clip(evs, 8, 25)) == 12


def test_chained_walk_is_all_kernel_time(chain):
    lo, hi = chain.window()
    (plane, ops), = chain.ops(lo, hi).items()
    assert plane == "/device:TPU:0"
    walk = [e for e in ops if e.name.startswith("%block_mips")]
    # two calls, each one 32,768-slot pallas_call and one for the rest
    assert sorted({tr.op_name(e) for e in walk}) == ["%block_mips.2",
                                                     "%block_mips.3"]
    assert len(walk) == 4
    win = run.Window(batch=16, latencies=[0.0, 0.0])
    r = run.Run(window=win, n_blocks=33334, page_rows=3, d=300, itemsize=4,
                peak={"hbm_bytes_per_s": 819e9}, build_s=1.0, trace=chain,
                lo=lo, hi=hi)
    ms = run.metric_reader("verify.kernel_ms")(r)
    assert ms == pytest.approx(sum(e.dur for e in walk) / 1e6 / 2)
    assert 40.0 < ms < 45.0          # 42.7 ms per walk of 33,334 slots
    busy = tr.busy_ns(ops)
    assert ms * 2e6 < busy < hi - lo
    idle = run.metric_reader("device.idle_pct")(r)
    assert idle == pytest.approx(100.0 * (1.0 - busy / (hi - lo)))
    assert 0.0 < idle < 10.0


def test_roofline_share_is_a_lower_bound(chain):
    lo, hi = chain.window()
    win = run.Window(batch=16, latencies=[0.0, 0.0], pages=2 * 16 * 33334)
    r = run.Run(window=win, n_blocks=33334, page_rows=3, d=300, itemsize=4,
                peak={"hbm_bytes_per_s": 819e9}, build_s=1.0, trace=chain,
                lo=lo, hi=hi)
    pct = run.metric_reader("verify.roofline_pct")(r)
    kernel_s = run.metric_reader("verify.kernel_ms")(r) / 1e3
    assert pct == pytest.approx(100 * 33334 * 3 * 300 * 4 / 819e9 / kernel_s)
    assert 0.0 < pct < 100.0


def test_breakdown_names_ops_by_module(chain):
    lo, hi = chain.window()
    bd = tr.breakdown(chain, lo, hi)
    assert bd["device_ops"][0][0] == "jit_block_mips/%block_mips.2"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    gaps = [g for _, g in bd["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and gaps[0] > 0


def test_no_trace_reads_nothing():
    r = run.Run(window=run.Window(batch=8), n_blocks=10, page_rows=3, d=300,
                itemsize=4, peak={"hbm_bytes_per_s": 819e9}, build_s=2.0)
    for name in ("verify.kernel_ms", "verify.roofline_pct",
                 "frontend.device_ms", "device.idle_pct"):
        assert run.metric_reader(name)(r) is None
    assert run.metric_reader("build_s")(r) == 2.0
