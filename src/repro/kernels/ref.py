"""Pure-jnp oracles for every Pallas kernel (the ground truth the shape/dtype
sweep tests assert against)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def mips_score_ref(x: jax.Array, q: jax.Array, valid: jax.Array) -> jax.Array:
    """scores = x @ q.T, -inf on invalid rows. x:(R,D) q:(B,D) valid:(R,)."""
    scores = x.astype(jnp.float32) @ q.astype(jnp.float32).T
    return jnp.where(valid.astype(bool)[:, None], scores, NEG_INF)


def block_mips_ref(x, valid, q, slots, sel, init_scores, init_rows, c_half,
                   *, k: int, page_rows: int, dense: bool = False):
    """Oracle for `block_mips.block_mips`: one fused verification round.

    Same contract (see the kernel docstring); this is also the production
    path off-TPU, so it is written to touch the minimum of full-width
    arrays — one (B, R) score matrix, the >=-threshold test and the live
    row mask — instead of the old batched path's seven (DESIGN.md §10).
    ``dense=True`` promises ``slots == arange(n_blocks)`` so the row gather
    is skipped and ``x`` is scored in place.
    """
    n_slots = sel.shape[1]
    if dense:
        xt, rvalid = x, valid.astype(bool)
        rows_flat = jnp.arange(n_slots * page_rows, dtype=jnp.int32)
    else:
        rows_flat = (slots.astype(jnp.int32)[:, None] * page_rows
                     + jnp.arange(page_rows, dtype=jnp.int32)).reshape(-1)
        # page-granular gather (4-KB contiguous slices) — markedly cheaper
        # on CPU than a row gather, and the access the TPU kernel's per-page
        # DMA performs anyway
        xt = jnp.take(x.reshape(-1, page_rows, x.shape[1]), slots,
                      axis=0).reshape(-1, x.shape[1])
        # row validity stays flat (see the row-mask note in `_verify_core`)
        rvalid = jnp.take(valid, rows_flat).astype(bool)
    # Exactly the batched backend's score expression (`mips_score_ref`, then
    # the transpose): XLA CPU folds a transpose that directly follows a dot
    # into a (B, d) @ (d, R) product whose sums round differently, so any
    # other spelling breaks fused-vs-batched bit parity. Invalid rows are
    # masked again below, so the NEG_INF fill changes nothing.
    scores = mips_score_ref(xt, q, rvalid).T                 # (B, R)
    return _verify_core(scores, rvalid, sel, init_scores, init_rows, c_half,
                        rows_flat, k=k, page_rows=page_rows)


def block_mips_cached_ref(scores_full, valid, slots, sel, init_scores,
                          init_rows, c_half, *, k: int, page_rows: int):
    """Compensation-round oracle over CACHED scores: when the previous round
    scored the whole corpus in place (dense tile), this round's slots are a
    subset of already-computed dot products — slice them out of the
    (B, n_pad) matrix instead of gathering rows and re-running the matmul.
    Bit-identical accounting to `block_mips_ref` over the same slots (the
    scores themselves come from the identical full-matrix matmul)."""
    rows_flat = (slots.astype(jnp.int32)[:, None] * page_rows
                 + jnp.arange(page_rows, dtype=jnp.int32)).reshape(-1)
    scores = jnp.take(scores_full, rows_flat, axis=1)        # (B, R)
    rvalid = jnp.take(valid, rows_flat).astype(bool)
    return _verify_core(scores, rvalid, sel, init_scores, init_rows, c_half,
                        rows_flat, k=k, page_rows=page_rows)


def _verify_core(scores, rvalid, sel, init_scores, init_rows, c_half,
                 rows_flat, *, k: int, page_rows: int):
    """Shared Condition-A accounting + streaming-equivalent top-k merge over
    a (B, R) score tile (see `block_mips_ref`)."""
    b, r = scores.shape
    n_slots = r // page_rows
    sel = sel.astype(bool)
    ge = (scores >= c_half[:, None]) & rvalid[None, :]       # (B, R)
    cnt = (ge.reshape(b, n_slots, page_rows).sum(axis=2).astype(jnp.int32)
           * sel.astype(jnp.int32))                          # (B, NS)
    n0 = jnp.sum(init_scores >= c_half[:, None], axis=1)     # carried-in hits
    # f32 running sum: exact (total hits << 2^24) and much cheaper than the
    # int32 scan XLA CPU emits for integer cumsum
    ex_cum = (jnp.cumsum(cnt.astype(jnp.float32), axis=1)
              - cnt).astype(jnp.int32)                       # exclusive cumsum
    live = sel & ((n0[:, None] + ex_cum) < k)                # ~done_before
    pages = jnp.sum(live.astype(jnp.int32), axis=1)
    # Row masks stay flat (B, R), and a slot's liveness reaches its rows by
    # a gather: at Yahoo size (NS = 208k, 3-row pages) the TPU compiler
    # spends minutes on any (NS, page_rows)-shaped row mask, and seconds on
    # this form.
    row_live = (jnp.take(live, jnp.arange(r) // page_rows, axis=1)
                & rvalid[None, :])                           # (B, R)
    cand = jnp.sum(row_live.astype(jnp.int32), axis=1)
    masked = jnp.where(row_live, scores, -jnp.inf)           # (B, R)
    tile_s, idx = jax.lax.top_k(masked, min(k, masked.shape[1]))
    tile_r = jnp.where(tile_s > -jnp.inf,
                       jnp.take(rows_flat, idx), -1).astype(jnp.int32)
    # Merge with the carried top-k: concat carried-first + top_k reproduces
    # the "ties to the lower index, carried entries first" rule, so the
    # result is bit-identical to one top_k over [carried, all tile rows].
    merged_s = jnp.concatenate([init_scores, tile_s], axis=1)
    merged_r = jnp.concatenate([init_rows.astype(jnp.int32), tile_r], axis=1)
    top_s, pos = jax.lax.top_k(merged_s, k)
    top_r = jnp.take_along_axis(merged_r, pos, axis=1)
    return top_s, top_r, cnt, pages, cand


def sketch_scores_ref(q: jax.Array, sk_mu: jax.Array) -> jax.Array:
    """Oracle for `block_mips.sketch_scores`: estimated block scores from the
    DECODED sketch centroids. q:(B,D) sk_mu:(NB,D) -> (B,NB).

    One GEMM over the decoded centroids — on CPU this beats the per-subspace
    LUT gathers the Pallas kernel performs by two orders of magnitude (XLA
    CPU lowers the (B, NB) gather accumulation to scalar loads). The kernel
    computes the same per-entry dot product as sum of subspace LUT entries;
    results agree to float-associativity tolerance, not bitwise.
    """
    return q.astype(jnp.float32) @ sk_mu.astype(jnp.float32).T


def binary_probe_lb_ref(codes: jax.Array, q_code: jax.Array, q_proj: jax.Array) -> jax.Array:
    """Theorem-3 group lower bounds. codes:(G,) q_code:() q_proj:(m,)."""
    m = q_proj.shape[0]
    shifts = jnp.arange(m, dtype=jnp.uint32)
    bits = (((codes[:, None] ^ q_code) >> shifts) & jnp.uint32(1)).astype(jnp.float32)
    return bits @ jnp.abs(q_proj).astype(jnp.float32) / jnp.sqrt(jnp.float32(m))


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, cache_len: jax.Array) -> jax.Array:
    """Naive softmax decode attention. q:(B,KH,G,dh) k,v:(B,S,KH,dh) len:(B,)."""
    b, kh, g, dh = q.shape
    s = k.shape[1]
    scale = 1.0 / (dh ** 0.5)
    scores = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    mask = jnp.arange(s)[None, None, None, :] < cache_len[:, None, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)
