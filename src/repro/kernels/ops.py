"""Jit'd public wrappers around the Pallas kernels.

On this CPU container the kernels execute with ``interpret=True`` (the body
runs as traced jnp — bit-exact semantics, validated against ref.py); on a
TPU backend the same calls lower to Mosaic. ``use_pallas=False`` routes to
the pure-jnp oracle, which is what the dry-run lowers (compact HLO; the
kernels are the TPU production path — see DESIGN.md §5).

For the search hot path `mips_score` also accepts ``use_pallas=None``
(backend-aware default): Pallas on TPU, the jnp oracle elsewhere —
interpret mode is a correctness vehicle, an order of magnitude slower than
the oracle on CPU, so production callers should not pay for it off-TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..obs import metrics as _metrics
from . import ref
from .binary_probe import binary_probe_lb as _binary_probe_pallas
from .block_mips import MAX_K as BLOCK_MIPS_MAX_K
from .block_mips import block_mips as _block_mips_pallas
from .block_mips import walk_steps as _block_mips_steps
from .block_mips import sketch_scores as _sketch_scores_pallas
from .decode_attention import decode_attention as _decode_attention_pallas
from .mips_topk import mips_score as _mips_score_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _resolve(use_pallas: Optional[bool]) -> bool:
    return (jax.default_backend() == "tpu") if use_pallas is None else use_pallas


def mips_score(x, q, valid, *, use_pallas: Optional[bool] = None, **block_kwargs):
    if not _resolve(use_pallas):
        return ref.mips_score_ref(x, q, valid)
    return _mips_score_pallas(x, q, valid, interpret=_interpret(), **block_kwargs)


def block_mips(x, valid, q, slots, sel, init_scores, init_rows, c_half, *,
               k: int, page_rows: int, dense: bool = False,
               use_pallas: Optional[bool] = None):
    """Fused block-sparse verification round (the two-phase hot path).

    Walks ``slots`` pages of ``x`` in place and returns (top_scores (B, k),
    top_rows (B, k), cnt (B, NS), pages (B,), cand (B,)) — see
    `block_mips.block_mips`.  Backend-aware default like `mips_score`;
    ``k > BLOCK_MIPS_MAX_K`` (streaming over-fetch) takes the oracle, whose
    VMEM-free merge has no k cap — on the Pallas route that detour is
    counted in ``kernels.block_mips_oracle`` (once per traced program).
    """
    pallas = _resolve(use_pallas)
    if pallas and k > BLOCK_MIPS_MAX_K:
        _metrics.counter("kernels.block_mips_oracle").inc()
    if not pallas or k > BLOCK_MIPS_MAX_K:
        return ref.block_mips_ref(x, valid, q, slots, sel, init_scores,
                                  init_rows, c_half, k=k, page_rows=page_rows,
                                  dense=dense)
    return _block_mips_pallas(x, valid, q, slots, sel, init_scores, init_rows,
                              c_half, k=k, page_rows=page_rows,
                              interpret=_interpret())


def block_mips_steps(n_slots: int, b: int, d: int, *, k: int, page_rows: int,
                     use_pallas: Optional[bool] = None) -> int:
    """Grid steps `block_mips` takes over an ``n_slots`` walk (chained
    calls summed); 0 where the walk takes the oracle, which has no grid."""
    if not _resolve(use_pallas) or k > BLOCK_MIPS_MAX_K:
        return 0
    return _block_mips_steps(n_slots, b, d, k, page_rows)


def sketch_scores(q, sk_mu, codebooks, codes, *,
                  use_pallas: Optional[bool] = None):
    """Estimated block scores for the verification prefilter: (B, NB) with
    est[b, n] = <q_b, decoded block centroid n>.

    Backend-aware like `mips_score`: on TPU the Pallas kernel scores the
    VMEM-resident PQ codes through a per-query LUT (the codebooks + codes
    are ~65x smaller than the decoded centroids, so they stay resident); the
    oracle is one GEMM over the decoded ``sk_mu``, which XLA CPU executes
    two orders of magnitude faster than gather-based LUT accumulation. The
    two paths sum identical subspace products in different orders, so they
    agree to float tolerance rather than bitwise (the prefilter consumes
    est through an eps-scaled error band, which dominates that slack).
    """
    if not _resolve(use_pallas):
        return ref.sketch_scores_ref(q, sk_mu)
    return _sketch_scores_pallas(q, codebooks, codes, interpret=_interpret())


def block_mips_cached(scores_full, valid, slots, sel, init_scores, init_rows,
                      c_half, *, k: int, page_rows: int):
    """Oracle-only compensation round over a cached (B, n_pad) score matrix
    (see `ref.block_mips_cached_ref`). The fused driver uses it when the
    previous round already scored the whole corpus in place — zero new dot
    products; on TPU the kernel streams pages instead, so there is no
    Pallas variant."""
    return ref.block_mips_cached_ref(scores_full, valid, slots, sel,
                                     init_scores, init_rows, c_half,
                                     k=k, page_rows=page_rows)


def mips_topk(x, q, valid, k: int, *, use_pallas: Optional[bool] = None,
              page_rows: int = 32, **block_kwargs):
    """Fused verification scan + top-k: returns (scores (B,k), rows (B,k)).

    Backend-aware default (``use_pallas=None`` => Pallas on TPU, jnp oracle
    elsewhere — previously this defaulted to True, silently putting off-TPU
    callers on interpret mode while `mips_score` did not). On the Pallas
    path the scan is routed through the fused `block_mips` kernel: the
    corpus is walked ``page_rows`` rows a page, several pages a grid step,
    with a streaming top-k, so no (R, B) score matrix is materialized. On
    the fused route rows with fewer than k valid
    candidates come back as -1 with -inf scores; `mips_score` ``block_*``
    kwargs are score-matrix tile sizes, so passing any routes through the
    score+`lax.top_k` pair instead (there they keep their meaning —
    empty slots are then NEG_INF with arbitrary rows, as before this PR).
    """
    if _resolve(use_pallas) and k <= BLOCK_MIPS_MAX_K and not block_kwargs:
        r, d = x.shape
        b = q.shape[0]
        rp = -(-r // page_rows) * page_rows
        xpad = jnp.pad(x, ((0, rp - r), (0, 0)))
        vpad = jnp.pad(valid.astype(jnp.int32), (0, rp - r))
        n_blocks = rp // page_rows
        slots = jnp.arange(n_blocks, dtype=jnp.int32)
        sel = jnp.ones((b, n_blocks), jnp.int32)
        init_s = jnp.full((b, k), -jnp.inf, jnp.float32)
        init_r = jnp.full((b, k), -1, jnp.int32)
        # c_half above any score => cnt never trips the Condition-A stop and
        # every selected page stays live: a plain full-corpus top-k scan.
        c_half = jnp.full((b,), jnp.finfo(jnp.float32).max)
        top, rows, _, _, _ = _block_mips_pallas(
            xpad, vpad, q, slots, sel, init_s, init_r, c_half,
            k=k, page_rows=page_rows, interpret=_interpret())
        return top, rows
    scores = mips_score(x, q, valid, use_pallas=use_pallas, **block_kwargs)  # (R, B)
    top, idx = jax.lax.top_k(scores.T, k)  # (B, k)
    return top, idx


def binary_probe_lb(codes, q_code, q_proj, *, use_pallas: bool = True, **block_kwargs):
    if not use_pallas:
        return ref.binary_probe_lb_ref(codes, q_code, q_proj)
    return _binary_probe_pallas(codes, q_code, q_proj, interpret=_interpret(), **block_kwargs)


def decode_attention(q, k, v, cache_len, *, use_pallas: bool = True, **block_kwargs):
    if not use_pallas:
        return ref.decode_attention_ref(q, k, v, cache_len)
    return _decode_attention_pallas(q, k, v, cache_len, interpret=_interpret(), **block_kwargs)
