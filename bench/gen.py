"""Seeded corpus and query generator for the benchmark.

A copy of the repo's PureSVD-style generator (`data/synthetic.mf_factors`):
rows are ``u diag(spec) V`` with a decaying spectrum ``spec`` over a
``rank``-dim latent basis ``V`` and lognormal per-row norms. Kept here so
that no later change to the program can change the benchmark's inputs.

It differs from the program's ``paper_queries`` in one point: queries are
users of the SAME model, ``u_q diag(spec) V`` with the corpus's own ``V``.
PureSVD user vectors live in the item factor space; a fresh ``V`` would put
the queries in a subspace nearly orthogonal to the corpus at d = 300.

Every draw comes from ``numpy.random.default_rng(SeedSequence([seed,
stream]))``, so any non-negative seed (also beyond 32 bits) works and the
corpus, the query stream and the check's sample are independent streams.
The corpus is the deployment: it is drawn from the configuration's
``data_seed``; a run's ``--seed`` draws its queries.
"""
from __future__ import annotations

import numpy as np

CORPUS, QUERIES, WARMUP, SAMPLE = 0, 1, 2, 3


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64,
                                                         stream]))


def _basis(g: np.random.Generator, rank: int, d: int, decay: float):
    v = g.standard_normal((rank, d)).astype(np.float32)
    spec = np.exp(-decay * np.arange(rank)).astype(np.float32)
    return v, spec


def corpus(cfg: dict):
    """(x (n, d) f32, v (rank, d) f32, spec (rank,) f32) for one config,
    drawn from its ``data_seed``."""
    n, d, rank = cfg["n"], cfg["d"], cfg["rank"]
    g = rng(cfg["data_seed"], CORPUS)
    v, spec = _basis(g, rank, d, cfg["decay"])
    u = g.standard_normal((n, rank), dtype=np.float32)
    x = (u * spec) @ v
    if cfg["norm_tail"] > 0:
        x *= g.lognormal(0.0, cfg["norm_tail"], size=(n, 1)).astype(np.float32)
    return np.ascontiguousarray(x, np.float32), v, spec


def user_queries(v: np.ndarray, spec: np.ndarray, n_queries: int, seed: int,
                 stream: int = QUERIES) -> np.ndarray:
    """(n_queries, d) f32 in-subspace users, ``u diag(spec) V``. Raises if a
    row repeats: no query may be answered twice in one run."""
    g = rng(seed, stream)
    u = g.standard_normal((n_queries, v.shape[0]), dtype=np.float32)
    q = np.ascontiguousarray((u * spec) @ v, np.float32)
    # a repeated row repeats its first value: compare whole rows only there
    vals, counts = np.unique(q[:, 0], return_counts=True)
    same = np.isin(q[:, 0], vals[counts > 1])
    if same.any() and len(np.unique(q[same], axis=0)) != int(same.sum()):
        raise ValueError("query stream repeats a row")
    return q
