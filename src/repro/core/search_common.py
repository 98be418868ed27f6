"""Backend-neutral ProMIPS search math — the single source of truth.

Every stopping condition, radius formula and merge rule the three search
paths share (``HostSearcher`` on numpy, ``search_batch`` and
``search_batch_progressive`` on jnp) lives here exactly once, parameterized
over the array namespace ``xp`` (``numpy`` or ``jax.numpy``). The functions
are pure elementwise/broadcastable arithmetic, so the SAME code path traces
under jit and executes eagerly on host — the numpy-vs-jnp agreement test in
``tests/test_search_runtime.py`` asserts bit-for-bit f32 equality.

Paper mapping (arXiv:2104.04406):
  condition_a / condition_a_threshold   Theorem 1 (deterministic stop)
  condition_b_denominator / condition_b Theorem 2, Formula 2/3
  compensation_radius                   Algorithm 3 line 12 (range r')
  adaptive_radii                        beyond-paper per-sub-partition radii
                                        (Theorem 2 applied with the LOCAL
                                        max norm; see DESIGN.md §4)
  sphere_select                         sub-partition sphere-overlap filter
  topk_merge                            running c-k-AMIP top-k merge
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# Scores below this are treated as "no candidate yet" when clamping the
# Condition-B denominator (matches the device paths' -inf guard).
MIN_SCORE = -1e30

# Hand-picked default for the fused drivers' dense-path threshold: unions
# covering at least this fraction of all blocks take the dense in-place
# tile. Promoted to a `RuntimeConfig` field (PR 8) so the offline tuner
# (`repro.tune`) can override it per shape without monkeypatching; this
# module-level value is the fallback when no tuned entry exists.
DENSE_FRAC = 0.9


def next_pow2(t: int) -> int:
    """Shared jit-shape-bucketing quantizer: the fused verification tiles
    (`search_fused`), the streaming segment over-fetch (`runtime`) and the
    snapshot delta-prefix (`stream/mutable.py`) all use it, keeping the
    compiled-shape strategy in one place."""
    return 1 << max(0, int(t) - 1).bit_length()


def condition_a_threshold(max_l2sq, q_l2sq, c: float):
    """Condition A rewritten as a threshold on the inner product itself:

        ||o_M||^2 + ||q||^2 - 2<o,q>/c <= 0   <=>   <o,q> >= c/2 (||o_M||^2 + ||q||^2)

    The device paths compare the running k-th best against this constant.
    """
    return 0.5 * c * (max_l2sq + q_l2sq)


def condition_a(best_ip, max_l2sq, q_l2sq, c: float):
    """Theorem 1 test. True => terminate, result is exact-guaranteed."""
    return max_l2sq + q_l2sq - 2.0 * best_ip / c <= 0.0


def condition_b_denominator(best_ip, max_l2sq, q_l2sq, c: float, xp=jnp):
    """||o_M||^2 + ||q||^2 - 2<o_max,q>/c (the Formula 2 denominator).

    ``best_ip`` is clamped to ``MIN_SCORE`` so an empty running top-k
    (-inf sentinel) yields a huge-but-finite denominator.
    """
    return max_l2sq + q_l2sq - 2.0 * xp.maximum(best_ip, MIN_SCORE) / c


def condition_b(proj_dist_sq, best_ip, max_l2sq, q_l2sq, c: float, x_p, xp=jnp):
    """Theorem 2 test via the static threshold x_p = Psi_m^{-1}(p).

    Psi_m(t) >= p  <=>  t >= x_p (Psi_m is monotone). A non-positive
    denominator is exactly Condition A — already guaranteed.
    """
    denom = condition_b_denominator(best_ip, max_l2sq, q_l2sq, c, xp=xp)
    return (denom <= 0.0) | (proj_dist_sq >= x_p * denom)


def compensation_radius(best_ip, max_l2sq, q_l2sq, c: float, x_p, xp=jnp):
    """r' = sqrt(x_p * (||o_M||^2 + ||q||^2 - 2<o_max,q>/c)).

    The Algorithm 3 expanded range when the Quick-Probe radius failed
    Condition B. Non-positive denominators (Condition A territory) map to 0.
    """
    denom = condition_b_denominator(best_ip, max_l2sq, q_l2sq, c, xp=xp)
    return xp.sqrt(xp.maximum(x_p * denom, 0.0))


def adaptive_radii(local_max_l2sq, best_ip, q_l2sq, c: float, x_p,
                   cs_prune: bool = False, xp=jnp):
    """Beyond-paper norm-adaptive Condition-B radii (DESIGN.md §4).

    Theorem 2's denominator upper-bounds ||o*||^2 by the GLOBAL max norm
    ||o_M||^2; but if o* lives in a region (sub-partition / block) with max
    norm M_loc, searching that region out to

        r_loc = sqrt(x_p * (M_loc^2 + ||q||^2 - 2 best_ip / c))

    preserves P[miss] <= 1-p by the identical argument (the bound is applied
    in the one region that actually contains o*). ``local_max_l2sq`` may be
    a scalar or a vector of per-region max squared norms.

    With ``cs_prune``, regions where even Cauchy-Schwarz's best case
    M_loc * ||q|| cannot beat the running k-th score get radius -1
    (deterministically deselected: such a region can contain neither o* nor
    a top-k improver).
    """
    denom = condition_b_denominator(best_ip, local_max_l2sq, q_l2sq, c, xp=xp)
    r = xp.sqrt(xp.maximum(x_p * denom, 0.0))
    if cs_prune:
        ok = xp.sqrt(local_max_l2sq) * xp.sqrt(q_l2sq) >= best_ip
        r = xp.where(ok, r, -1.0)
    return r


def sphere_select(center_dist, region_radius, radius):
    """Sphere-overlap filter: does the search ball of ``radius`` intersect a
    region at center distance ``center_dist`` with radius ``region_radius``?
    Entries with radius < 0 deselect the region outright (CS pruning)."""
    return (center_dist <= radius + region_radius) & (radius >= 0.0)


def gap_select(gap, radius):
    """`sphere_select` with a precomputed surface gap = center_dist - region_radius."""
    return (gap <= radius) & (radius >= 0.0)


def block_valid_from_ids(ids, page_rows: int, xp=jnp):
    """(NB,) bool: does block b hold at least one real (non-padding) row?

    Derived from ids rather than stored so tombstoning/sharding layers that
    rewrite ids (padding rows carry -1) stay consistent automatically.
    Spelled as a max, not ``any(ids >= 0)``: at 3-row pages and NB = 208k
    the TPU compiler takes ~30 s on that boolean reduce, under 1 s on the
    max.
    """
    nb = ids.shape[0] // page_rows
    return xp.max(ids.reshape(nb, page_rows), axis=1) >= 0


def sketch_margin(queries, sk_err, eps: float, xp=jnp):
    """(B, NB) sketch error band: bnd = eps * ||q|| * err_b.

    Paired with est[b_q, b] = <q, mu~_b> (`kernels.ops.sketch_scores`), at
    eps = 1 every valid row o_r of block b satisfies
    <q, o_r> in [est - bnd, est + bnd] (Cauchy-Schwarz on
    ||o_r - mu~_b|| <= err_b); eps < 1 shrinks the interval as a calibrated
    tightness knob (DESIGN.md §13).
    """
    q_norm = xp.sqrt(xp.sum(queries * queries, axis=1))
    return eps * q_norm[:, None] * sk_err[None, :]


def sketch_survivors_round1(mask, est, bnd, bvalid, k: int, xp=jnp):
    """Round-1 survivor rule: keep candidate blocks whose upper bound clears
    a per-query threshold tau <= (kth-largest lower bound over candidates).

    tau comes from G = min(2k, NB) strided groups: the kth-largest per-group
    max of lb. The top-k group maxes are k DISTINCT lb entries all >= tau, so
    tau lower-bounds the true kth-largest lb — pruning ub < tau is therefore
    lossless at eps = 1 (every pruned block's rows score strictly below k
    candidate rows that survive). Group-max instead of lax.top_k because XLA
    CPU's top_k with dead indices is pathologically slow (~30x).

    When NB < G (tiny index) or fewer than k groups hold a candidate, tau
    degrades to -inf and nothing is pruned — k >= n_alive stays exact.
    """
    nb = est.shape[1]
    g = min(2 * k, nb)
    cand = mask & bvalid[None, :]
    if g < k:
        return cand
    lb = xp.where(cand, est - bnd, -xp.inf)
    pad = (-nb) % g
    if pad:
        fill = xp.full(lb.shape[:1] + (pad,), -xp.inf, lb.dtype)
        lb = xp.concatenate([lb, fill], axis=1)
    gm = xp.max(lb.reshape(lb.shape[0], -1, g), axis=1)
    tau = xp.sort(gm, axis=1)[:, g - k]
    return cand & (est + bnd >= tau[:, None])


def sketch_survivors_round2(mask, est, bnd, bvalid, s_k, xp=jnp):
    """Compensation-round survivor rule: after round 1 the running kth score
    s_k is a realized lower bound, so any block whose upper bound est + bnd
    falls below it cannot improve the top-k. Lossless at eps = 1; queries
    with an empty top-k carry s_k = -inf and keep everything.
    """
    return mask & bvalid[None, :] & (est + bnd >= s_k[:, None])


def topk_merge(top_scores, top_rows, scores, rows, k: int, xp=jnp):
    """Merge new (scores, rows) candidates into a running descending top-k.

    Ties break toward the earlier entry (carried-in top first, then new rows
    in order) on BOTH backends: numpy uses a stable descending argsort,
    jax.lax.top_k picks the lowest index among equals — so host and device
    produce identical ranked ids, and the device hot loop keeps a top-k
    selection instead of a full sort.
    """
    s = xp.concatenate([top_scores, scores])
    r = xp.concatenate([top_rows, rows])
    if xp is np:
        idx = np.argsort(-s, kind="stable")[:k]
        return s[idx], r[idx]
    import jax

    best, idx = jax.lax.top_k(s, k)
    return best, r[idx]
