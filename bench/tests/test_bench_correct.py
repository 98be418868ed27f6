"""`correct` on a small corpus on the CPU: a sound run passes; the
bfloat16 control and a timed path broken underneath do not."""
import dataclasses
import time
from types import SimpleNamespace

import numpy as np
import pytest

import readings
import reference
import run

CONFIG = {"name": "tiny", "n": 3000, "d": 300, "dtype": "float32",
          "matmul_precision": "highest", "rank": 32, "decay": 0.15, "norm_tail": 0.3, "data_seed": 5,
          "guarantee": {"c": 0.9, "p0": 0.5, "k": 10}, "api_options": {}}
TRAFFIC = {"loop": "closed", "batch": 16, "queries": "in_subspace_users",
           "stream_queries": 16384, "warmup_batches": 2, "check_sample": 256}
SEED = 2**31 + 99


def cell():
    bench = run._load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    return run.Cell(name="netflix.b16", config=CONFIG, traffic=TRAFFIC,
                    chips=1, end_to_end=bench["end_to_end"],
                    per_layer=bench["per_layer"])


def go(build=run.build_program, trace=False, seconds=0.3):
    return run.run_cell(cell(), SEED, seconds, trace,
                        {"platform": "cpu", "kind": "cpu", "count": 1},
                        {"hbm_bytes_per_s": 819e9}, time.perf_counter(),
                        build=build)


def test_sound_run_is_correct():
    out = go()
    assert out["correct"], out["checks"]
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["failed"] == 0 and out["attempted"] % 16 == 0
    assert set(out["metrics"]) == {"setup_s", "qps", "p95_ms",
                                   "recall_at_10", "peak_hbm_mb"}
    assert out["metrics"]["recall_at_10"]["value"] > 0.8
    assert out["checks"]["score_err"]["value"] < 1e-6
    assert out["checks"]["order_gap"]["value"] < reference.ORDER_LIMIT


def test_warm_up_compiles_every_verify_tile():
    from repro.core import search_fused

    x = np.random.default_rng(0).standard_normal((3000, 300), np.float32)
    program = run.build_program(x, CONFIG)
    sizes = run.tile_sizes(program.n_blocks)
    assert sizes[0] == 1 and sizes[-1] < program.n_blocks <= 2 * sizes[-1]
    n0 = search_fused.VERIFY_TRACES.total
    program.warm(x[:12])          # a batch size no other test compiles
    traced = {t[0] for t in list(search_fused.VERIFY_TRACES)[-(
        search_fused.VERIFY_TRACES.total - n0):]}
    assert set(sizes) <= traced


def test_traced_run_reads_the_layers_it_can():
    out = go(trace=True)
    assert out["correct"]
    # the CPU has no device plane: only the counter and the build time
    assert set(out["metrics"]) == {"verify.pages_frac", "build_s"}
    assert 0.5 < out["metrics"]["verify.pages_frac"]["value"] <= 1.0
    assert "busy_s" in out["device"] and "window_s" in out["device"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_bfloat16_control_is_not_correct():
    out = go(build=readings.build_bf16)
    assert not out["correct"]
    assert out["checks"]["score_err"]["value"] > 10 * \
        reference.SCORE_ERR_LIMIT


def stale(res):          # the search returns its initial, empty top-k
    return SimpleNamespace(ids=np.full_like(res.ids, -1),
                           scores=np.full_like(res.scores, -np.inf),
                           stats=res.stats)


def half(res):           # half of the batch left unanswered
    ids, scores = res.ids.copy(), res.scores.copy()
    ids[len(ids) // 2:] = -1
    scores[len(ids) // 2:] = -np.inf
    return SimpleNamespace(ids=ids, scores=scores, stats=res.stats)


def altered_id(res):     # one answer's row swapped where it is produced
    ids = res.ids.copy()
    ids[0, 0] = (ids[0, 0] + 1) % CONFIG["n"]
    if ids[0, 0] in ids[0, 1:]:
        ids[0, 0] = (ids[0, 0] + 7) % CONFIG["n"]
    return SimpleNamespace(ids=ids, scores=res.scores, stats=res.stats)


def altered_score(res):  # one answer's score altered where it is produced
    scores = res.scores.copy()
    scores[0, -1] *= 1.0 - 1e-3
    return SimpleNamespace(ids=res.ids, scores=scores, stats=res.stats)


def out_of_order(res):   # one row's answers listed out of descending order
    ids, scores = res.ids.copy(), res.scores.copy()
    ids[0, [0, -1]] = ids[0, [-1, 0]]
    scores[0, [0, -1]] = scores[0, [-1, 0]]
    return SimpleNamespace(ids=ids, scores=scores, stats=res.stats)


@pytest.mark.parametrize("fault", [stale, half, altered_id, altered_score,
                                   out_of_order])
def test_broken_timed_path_is_not_correct(fault):
    def build(x, cfg):
        program = run.build_program(x, cfg)
        return dataclasses.replace(program,
                                   search=lambda q: fault(program.search(q)))

    out = go(build=build)
    assert not out["correct"], out["checks"]
