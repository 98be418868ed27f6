"""Offline autotuner (PR 8): tuning-cache round trips, missing-key /
absent-cache fallback bit-identity, tuned-vs-default result parity for the
parity-safe knobs, cutout determinism, and the satellite contracts
(int-eps coercion, `kernel_cost`'s static_upper_bound flag)."""
import json
import os

import numpy as np
import pytest

from repro.core import ProMIPS, RuntimeConfig
from repro.core.search_common import DENSE_FRAC, next_pow2
from repro.tune import cache, cutout, space

STATS_EXACT = ("pages", "candidates", "probe_passed", "used_round2",
               "radius0", "radius1", "exhausted", "rows")


@pytest.fixture(scope="module")
def built(mf_corpus):
    x, q = mf_corpus
    pm = ProMIPS.build(x, m=8, c=0.9, p=0.5, norm_strata=4)
    return x, q, pm


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """Point the tuning cache at a fresh temp file and clear the memo on
    both entry and exit, so tests never see the committed cache (or each
    other's)."""
    path = str(tmp_path / "tuning.json")
    monkeypatch.setenv(cache.ENV_VAR, path)
    cache.clear_memo()
    yield path
    cache.clear_memo()


def _assert_identical(out_a, out_b, label):
    ids_a, scores_a, st_a = out_a
    ids_b, scores_b, st_b = out_b
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b),
                                  err_msg=f"{label}: ids")
    np.testing.assert_array_equal(np.asarray(scores_a), np.asarray(scores_b),
                                  err_msg=f"{label}: scores")
    for field in STATS_EXACT:
        np.testing.assert_array_equal(
            np.asarray(getattr(st_a, field)),
            np.asarray(getattr(st_b, field)),
            err_msg=f"{label}: stat {field}")


# -- cache mechanics --------------------------------------------------------

def test_cache_round_trip(tmp_cache):
    key = cache.save_entry(100_000, 128,
                           runtime={"verification": "fused",
                                    "dense_frac": 0.8, "tile_cap": 96,
                                    "prefilter_eps": 0.1},
                           build={"page_bytes": 8192,
                                  "max_probe_groups": None})
    assert key == space.shape_key(100_000, 128)
    entry = cache.lookup(100_000, 128)
    assert entry is not None
    assert entry["runtime"]["dense_frac"] == 0.8
    assert entry["provenance"]["commit"]
    # shape bucketing: any n in the same pow2 bucket resolves the entry
    assert cache.lookup(90_000, 128) is not None
    assert cache.lookup(100_000, 64) is None
    rt = cache.resolved("runtime", 100_000, 128)
    assert rt["dense_frac"] == 0.8 and rt["tile_cap"] == 96
    bd = cache.resolved("build", 100_000, 128)
    assert bd["page_bytes"] == 8192
    # the on-disk document carries the key/provenance schema DESIGN §15
    # documents
    doc = json.load(open(tmp_cache))
    assert doc["version"] == 1
    assert doc["entries"][key]["key"]["d"] == 128


def test_cache_missing_and_corrupt(tmp_cache):
    # no file at all -> hand-picked everywhere, no exception
    assert cache.lookup(5000, 32) is None
    assert cache.resolved("runtime", 5000, 32) == \
        space.HAND_PICKED["runtime"]
    # corrupt file -> same
    with open(tmp_cache, "w") as f:
        f.write("{not json")
    cache.clear_memo()
    assert cache.lookup(5000, 32) is None
    assert cache.resolved("serve", 5000, 32) == space.HAND_PICKED["serve"]


def test_cache_disabled_by_empty_env(tmp_cache, monkeypatch):
    cache.save_entry(4000, 48, runtime={"dense_frac": 0.5})
    assert cache.lookup(4000, 48) is not None
    monkeypatch.setenv(cache.ENV_VAR, "")
    cache.clear_memo()
    assert cache.lookup(4000, 48) is None


def test_resolved_only_overlays_declared_keys(tmp_cache):
    cache.save_entry(4000, 48, runtime={"dense_frac": 0.5,
                                        "bogus_knob": 123})
    rt = cache.resolved("runtime", 4000, 48)
    assert rt["dense_frac"] == 0.5
    assert "bogus_knob" not in rt
    assert rt["verification"] == space.HAND_PICKED["runtime"]["verification"]


# -- fallback + tuned-entry bit-identity ------------------------------------

def test_absent_cache_bit_identical_to_explicit_defaults(built, tmp_cache):
    """The acceptance bar: with no cache (or no entry for this shape),
    None-knob searches equal the explicit hand-picked config bitwise —
    ids, scores AND stats."""
    x, q, pm = built
    out_none = pm.search(q, k=10, norm_adaptive=True, cs_prune=True)
    out_pin = pm.search(q, k=10, norm_adaptive=True, cs_prune=True,
                        dense_frac=DENSE_FRAC, tile_cap=pm.meta.n_blocks)
    _assert_identical(out_none, out_pin, "absent-cache")


@pytest.mark.parametrize("dense_frac", [0.5, 1.0])
def test_tuned_dense_frac_parity(built, tmp_cache, dense_frac):
    """dense_frac only picks dense vs sparse tile — result-bit-identical
    by construction, so a tuned value must change nothing but time."""
    x, q, pm = built
    cache.save_entry(len(x), x.shape[1],
                     runtime={"dense_frac": dense_frac})
    out_tuned = pm.search(q, k=10, norm_adaptive=True, cs_prune=True)
    os.environ[cache.ENV_VAR] = ""
    cache.clear_memo()
    try:
        out_default = pm.search(q, k=10, norm_adaptive=True, cs_prune=True)
    finally:
        os.environ[cache.ENV_VAR] = tmp_cache
        cache.clear_memo()
    _assert_identical(out_tuned, out_default, f"dense_frac={dense_frac}")


def test_tuned_tile_cap_parity(built, tmp_cache):
    """A tile_cap >= the actual union is lossless (it only removes pow2
    padding), so a tuned cap at n_blocks is bit-identical to uncapped."""
    x, q, pm = built
    cache.save_entry(len(x), x.shape[1],
                     runtime={"tile_cap": int(pm.meta.n_blocks)})
    out_tuned = pm.search(q, k=10, norm_adaptive=True, cs_prune=True)
    out_pin = pm.search(q, k=10, norm_adaptive=True, cs_prune=True,
                        dense_frac=DENSE_FRAC, tile_cap=pm.meta.n_blocks)
    _assert_identical(out_tuned, out_pin, "tile_cap=n_blocks")


def test_explicit_kwargs_beat_cache(built, tmp_cache):
    """An explicit dense_frac must win over an installed tuned entry: the
    two searches still agree bitwise (it's a perf knob), and the installed
    entry must not stop an explicit tile_cap below the union from
    truncating (exhausted flags prove the explicit value was used)."""
    x, q, pm = built
    cache.save_entry(len(x), x.shape[1],
                     runtime={"dense_frac": 0.5,
                              "tile_cap": int(pm.meta.n_blocks)})
    out_explicit = pm.search(q, k=10, norm_adaptive=True, cs_prune=True,
                             dense_frac=1.0, tile_cap=1)
    assert bool(np.asarray(out_explicit[2].exhausted).any()), \
        "tile_cap=1 should truncate; the cache entry must not override it"


def test_tuned_vs_default_parity_every_tuned_point(built, tmp_cache):
    """Every entry the coordinate descent can actually write is parity-
    gated; simulate one per declared runtime knob value and assert the
    resolved search still matches the hand-picked baseline bitwise.
    (verification variants are exercised via their own backend kwarg —
    all backends are bit-identical by the PR-4 parity suite.)"""
    x, q, pm = built
    baseline = pm.search(q, k=10, norm_adaptive=True, cs_prune=True,
                         dense_frac=DENSE_FRAC, tile_cap=pm.meta.n_blocks)
    for dense_frac in space.knob("dense_frac").candidates:
        cache.save_entry(len(x), x.shape[1],
                         runtime={"dense_frac": float(dense_frac),
                                  "tile_cap": int(pm.meta.n_blocks)})
        out = pm.search(q, k=10, norm_adaptive=True, cs_prune=True)
        _assert_identical(out, baseline, f"tuned dense_frac={dense_frac}")


# -- cutout generator -------------------------------------------------------

def test_cutout_deterministic_under_fixed_seed():
    x1, q1 = cutout.make_cutout(2000, 32, 8, seed=7)
    x2, q2 = cutout.make_cutout(2000, 32, 8, seed=7)
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_array_equal(q1, q2)
    x3, _ = cutout.make_cutout(2000, 32, 8, seed=8)
    assert not np.array_equal(x1, x3)


def test_cutout_matches_large_corpus_recipe():
    """The cutout at the LARGE_N shape IS the LARGE_N corpus — tuning
    measures the workload the bench runs."""
    from benchmarks.paper_figures import LARGE_N, _large_corpus
    cfg = LARGE_N
    x_b, q_b = _large_corpus()
    x_c, q_c = cutout.make_cutout(
        cfg["n"], cfg["d"], cfg["n_q"], rank=cfg["rank"],
        decay=cfg["decay"], norm_tail=cfg["norm_tail"], seed=0)
    np.testing.assert_array_equal(x_b, x_c)
    np.testing.assert_array_equal(q_b, q_c)


# -- parameter space / key schema -------------------------------------------

def test_shape_key_buckets_and_schema():
    assert space.n_bucket(100_000) == 131_072
    assert space.n_bucket(131_072) == 131_072
    key = space.shape_key(100_000, 128, platform="cpu", jax_version="0.4.37")
    assert key == "n131072:d128:cpu:jax0.4.37"
    for k in space.KNOBS:
        assert k.section in space.HAND_PICKED
        assert k.name in space.HAND_PICKED[k.section] or k.name == "tile_cap"


# -- satellite contracts ----------------------------------------------------

def test_runtime_config_coerces_int_eps():
    cfg = RuntimeConfig(k=10, prefilter=True, prefilter_eps=1)
    assert isinstance(cfg.prefilter_eps, float) and cfg.prefilter_eps == 1.0
    cfg2 = RuntimeConfig(k=10, dense_frac=1)
    assert isinstance(cfg2.dense_frac, float) and cfg2.dense_frac == 1.0


def test_runtime_config_validates_tune_knobs():
    with pytest.raises(ValueError):
        RuntimeConfig(k=10, dense_frac=0.0)
    with pytest.raises(ValueError):
        RuntimeConfig(k=10, dense_frac=1.5)
    with pytest.raises(ValueError):
        RuntimeConfig(k=10, tile_cap=0)
    with pytest.raises(ValueError):
        RuntimeConfig(k=10, tile_cap=True)


def test_kernel_cost_static_upper_bound_flag():
    import jax
    import jax.numpy as jnp
    from repro.launch.roofline import kernel_cost
    try:
        cost = kernel_cost(lambda a, b: a @ b,
                           jnp.ones((8, 8), jnp.float32),
                           jnp.ones((8, 8), jnp.float32))
    except Exception:
        pytest.skip("cost_analysis unavailable on this backend")
    assert cost["static_upper_bound"] is True


def test_kernel_cost_peaks_keyed_by_device_kind(monkeypatch):
    """No roofline share off a chip; an unknown chip is an error, never the
    v5e peaks by default."""
    import types

    import jax.numpy as jnp
    from repro.launch import roofline
    args = (lambda a, b: a @ b, jnp.ones((8, 8)), jnp.ones((8, 8)))
    cost = roofline.kernel_cost(*args)
    assert cost["device_kind"] == "cpu" and "roofline_s" not in cost
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(roofline.jax, "devices", lambda: [fake])
    with pytest.raises(KeyError, match="TPU v99"):
        roofline.kernel_cost(*args)
    fake.device_kind = "TPU v5 lite"
    cost = roofline.kernel_cost(*args)
    assert cost["roofline_s"] == max(cost["t_compute_s"], cost["t_memory_s"])


def test_max_probe_groups_caps_table():
    from repro.core.quick_probe import build_group_table, pack_codes_np
    rng = np.random.RandomState(0)
    p = rng.randn(500, 6).astype(np.float32)
    codes = pack_codes_np(p)
    l1 = np.abs(rng.randn(500)).astype(np.float32)
    full = build_group_table(codes, l1, p)
    capped = build_group_table(codes, l1, p, max_groups=8)
    assert len(capped.code) == 8 < len(full.code)
    # kept groups are exactly the smallest-min_l1 ones
    assert set(np.asarray(capped.min_l1)) == \
        set(np.sort(np.asarray(full.min_l1))[:8])


def test_tuned_point_smoke_descent():
    """End-to-end descent on a tiny cutout: runs inside budget, every
    candidate carries a status, and the winner passes the parity gate by
    construction (baseline reproduced bitwise)."""
    from repro.tune import search as tsearch
    x, q = cutout.make_cutout(1500, 24, 8, seed=0)
    entry = tsearch.tune_point(
        x, q,
        build_opts=dict(m=8, c=0.9, p=0.6, k_p=4, k_sp=4, norm_strata=2,
                        seed=0),
        search_opts=dict(k=5, norm_adaptive=True, cs_prune=True),
        budget_s=30.0, reps=2, include_build=False, stages=False,
        roofline=False, write=False)
    summary = entry["trace"]["summary"]
    assert summary["elapsed_s"] < 120.0
    assert {"verification", "dense_frac", "tile_cap",
            "prefilter_eps"} <= set(entry["runtime"])
    for rec in entry["trace"]["candidates"]:
        assert "status" in rec
    assert summary["speedup_tuned_vs_default"] > 0.0
