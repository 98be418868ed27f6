"""The benchmark's generator copy: seeded, in-subspace, no repeats."""
import json
import os

import numpy as np
import pytest

import gen
import run

BIG_SEED = 2**31 + 12345


def config(name):
    with open(os.path.join(run.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def small(cfg, n=2000, **kw):
    return dict(cfg, n=n, **kw)


def test_corpus_and_queries_are_deterministic_in_the_seed():
    cfg = small(config("netflix-puresvd"))
    x1, v1, s1 = gen.corpus(cfg)
    x2, v2, s2 = gen.corpus(cfg)
    assert np.array_equal(x1, x2) and np.array_equal(v1, v2)
    q1 = gen.user_queries(v1, s1, 300, BIG_SEED)
    assert np.array_equal(q1, gen.user_queries(v2, s2, 300, BIG_SEED))
    x3, _, _ = gen.corpus(small(cfg, data_seed=BIG_SEED))
    assert not np.array_equal(x1, x3)
    assert not np.array_equal(q1, gen.user_queries(v1, s1, 300, BIG_SEED + 1))
    warm = gen.user_queries(v1, s1, 300, BIG_SEED, stream=gen.WARMUP)
    assert not np.isin(warm, q1).all(axis=1).any()


def test_no_query_repeats_within_a_stream():
    cfg = small(config("netflix-puresvd"))
    _, v, s = gen.corpus(cfg)
    q = gen.user_queries(v, s, 4096, 7)
    assert len(np.unique(q, axis=0)) == len(q)
    with pytest.raises(ValueError, match="repeats"):
        gen.user_queries(np.zeros_like(v), s, 16, 7)


def test_queries_lie_in_the_corpus_subspace():
    cfg = small(config("yahoo-music-puresvd"))
    x, v, s = gen.corpus(cfg)
    q = gen.user_queries(v, s, 64, 3).astype(np.float64)
    basis = np.linalg.qr(v.astype(np.float64).T)[0]           # (d, rank)
    resid = q - (q @ basis) @ basis.T
    assert np.abs(resid).max() < 1e-4 * np.abs(q).max()
    # a fresh latent basis (the program's paper_queries) does not
    _, v_other, _ = gen.corpus(small(cfg, data_seed=4))
    q_other = gen.user_queries(v_other, s, 64, 3).astype(np.float64)
    resid = q_other - (q_other @ basis) @ basis.T
    assert np.linalg.norm(resid) > 0.5 * np.linalg.norm(q_other)


@pytest.mark.parametrize("name", ["netflix-puresvd", "yahoo-music-puresvd"])
def test_shapes_match_the_configuration(name):
    cfg = config(name)
    x, v, s = gen.corpus(cfg)
    assert x.shape == (cfg["n"], cfg["d"])
    assert x.dtype == np.dtype(cfg["dtype"])
    assert v.shape == (cfg["rank"], cfg["d"]) and s.shape == (cfg["rank"],)
    assert np.isfinite(x).all()
    assert gen.user_queries(v, s, 8, 11).shape == (8, cfg["d"])
