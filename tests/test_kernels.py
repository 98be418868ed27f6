"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.binary_probe import binary_probe_lb
from repro.kernels.decode_attention import decode_attention
from repro.kernels.mips_topk import mips_score


@pytest.mark.parametrize("r,b,d", [(64, 4, 32), (300, 17, 200), (1024, 1, 128),
                                   (129, 128, 384), (8, 8, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mips_score_sweep(rng, r, b, d, dtype):
    x = jnp.asarray(rng.standard_normal((r, d)), dtype)
    q = jnp.asarray(rng.standard_normal((b, d)), dtype)
    valid = jnp.asarray(rng.rand(r) > 0.2)
    got = mips_score(x, q, valid, interpret=True)
    want = ref.mips_score_ref(x, q, valid)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    rel = jnp.abs(got - want) / (1.0 + jnp.abs(want))
    assert float(rel.max()) < tol


@pytest.mark.parametrize("g,m", [(1, 4), (64, 8), (700, 12), (4096, 16), (33, 30)])
def test_binary_probe_sweep(rng, g, m):
    codes = jnp.asarray(rng.randint(0, 2 ** min(m, 31), g), jnp.uint32)
    qc = jnp.uint32(rng.randint(0, 2 ** min(m, 31)))
    qp = jnp.asarray(rng.standard_normal(m), jnp.float32)
    got = binary_probe_lb(codes, qc, qp, interpret=True)
    want = ref.binary_probe_lb_ref(codes, qc, qp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,kh,g,dh,s,block", [
    (1, 1, 1, 32, 128, 64), (2, 4, 2, 64, 1000, 256),
    (3, 2, 8, 128, 512, 512), (2, 8, 1, 64, 300, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(rng, b, kh, g, dh, s, block, dtype):
    q = jnp.asarray(rng.standard_normal((b, kh, g, dh)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, kh, dh)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, kh, dh)), dtype)
    lens = jnp.asarray(rng.randint(1, s + 1, b), jnp.int32)
    got = decode_attention(q, k, v, lens, block_s=block, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lens)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol, rtol=1e-2)


def test_ops_wrappers_route(rng):
    """ops.* dispatches to ref when use_pallas=False and matches."""
    x = jnp.asarray(rng.standard_normal((100, 64)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    valid = jnp.ones(100, bool)
    a = ops.mips_score(x, q, valid, use_pallas=True)
    b = ops.mips_score(x, q, valid, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    top, idx = ops.mips_topk(x, q, valid, k=7)
    want = np.sort(np.asarray(x @ q.T).T, axis=1)[:, ::-1][:, :7]
    np.testing.assert_allclose(np.asarray(top), want, atol=1e-4)


@pytest.mark.parametrize("nb,p,d,b,k,ns,case", [
    pytest.param(12, 8, 32, 5, 4, 8, "random", id="12-8-32-5-4-8"),
    pytest.param(30, 16, 64, 9, 10, 16, "random", id="30-16-64-9-10-16"),
    pytest.param(6, 8, 48, 3, 12, 6, "random", id="6-8-48-3-12-6"),
    pytest.param(20, 8, 128, 17, 1, 4, "random", id="20-8-128-17-1-4"),
    # 21 slots: no multiple of the 16-page step, pad slots mid-list; d = 300
    pytest.param(40, 3, 300, 4, 10, 21, "pad_mid", id="pad-mid-page3"),
    # every valid row a hit: the Condition-A stop falls inside a step
    pytest.param(40, 8, 128, 6, 2, 33, "stop", id="stop-in-step-page8"),
    pytest.param(40, 3, 300, 6, 3, 24, "stop", id="stop-in-step-page3"),
    # small-integer data: exact equal scores across pages and steps
    pytest.param(40, 3, 300, 3, 5, 24, "ties", id="ties-page3"),
    pytest.param(24, 8, 16, 5, 12, 20, "ties", id="ties-page8"),
    # selected pages whose rows are all invalid
    pytest.param(24, 8, 16, 4, 6, 20, "invalid_page", id="invalid-page8"),
    pytest.param(40, 3, 300, 4, 6, 24, "invalid_page", id="invalid-page3"),
    # one-row pages: one 8-row tile covers each (d >= 1024 in the layout)
    pytest.param(40, 1, 64, 4, 5, 24, "random", id="page1"),
    # a walk longer than one call's slot list: a chain of calls
    pytest.param(60, 3, 300, 5, 10, 50, "chain", id="chain-page3"),
])
def test_block_mips_sweep(rng, monkeypatch, nb, p, d, b, k, ns, case):
    """Fused block-sparse verification kernel (interpret) vs jnp oracle:
    streaming top-k, per-slot hit counts, Condition-A page/candidate
    accounting — with padding slots, invalid rows and carried-in tops, over
    walks of several pages per grid step."""
    from repro.kernels import block_mips as bm

    n = nb * p
    x = rng.standard_normal((n, d))
    valid = rng.rand(n) > 0.15
    q = rng.standard_normal((b, d))
    blocks = np.sort(rng.permutation(nb)[: ns - 1])
    slots = np.concatenate([blocks, [0]])                  # pad slot last
    sel = rng.rand(b, ns) > 0.4
    sel[:, ns - 1] = False
    init_s = -np.sort(-rng.standard_normal((b, k)), axis=1)
    init_r = rng.randint(0, n, (b, k))
    c_half = rng.standard_normal(b) * 2
    if case == "pad_mid":
        for j in (3, 9, 10, 17):
            slots[j], sel[:, j] = 0, False
    elif case == "stop":
        c_half[:] = -1e9
        init_s[:], init_r[:] = -np.inf, -1
    elif case == "ties":
        # page 0 repeated as page 1 (same step) and as the last page (a
        # later step), all three walked
        x = rng.randint(-1, 2, (n, d))
        x[p:2 * p] = x[(nb - 1) * p:] = x[:p]
        slots[:ns - 1] = np.concatenate(
            [[0, 1], np.sort(rng.permutation(np.arange(2, nb - 1))[:ns - 4]),
             [nb - 1]])
        q = rng.randint(-1, 2, (b, d))
        init_s = -np.sort(-rng.randint(-3, 4, (b, k)), axis=1)
    elif case == "invalid_page":
        for s in slots[[1, 4, 5]]:
            valid[s * p:(s + 1) * p] = False
    elif case == "chain":
        walk = bm._walk
        calls = []
        # 16 slots a call: the slot list and one window word each
        monkeypatch.setattr(bm, "SMEM_BUDGET", 16 * 2 * 4)
        monkeypatch.setattr(bm, "_walk", lambda *a, **kw: calls.append(1)
                            or walk(*a, **kw))
    args = (jnp.asarray(x, jnp.float32), jnp.asarray(valid),
            jnp.asarray(q, jnp.float32), jnp.asarray(slots, jnp.int32),
            jnp.asarray(sel), jnp.asarray(init_s, jnp.float32),
            jnp.asarray(init_r, jnp.int32), jnp.asarray(c_half, jnp.float32))

    got = bm.block_mips(*args, k=k, page_rows=p, interpret=True)
    want = ref.block_mips_ref(*args, k=k, page_rows=p)
    assert bm.pages_per_step(ns, b, d, k, p) > 1
    if case == "chain":
        assert len(calls) > 1
    if case == "stop":
        assert 0 < int(got[3].max()) < int(sel.sum(axis=1).max())
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=1e-4)
    if case == "ties":                                     # exact scores
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for name, g, w in zip(("top_r", "cnt", "pages", "cand"),
                          got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def test_mips_topk_defaults_and_fused_route(rng):
    """mips_topk defaults to the backend-aware path (oracle off-TPU, no
    silent interpret mode) and its Pallas route — the fused block_mips
    streaming top-k — matches the oracle's score+lax.top_k result."""
    x = jnp.asarray(rng.standard_normal((300, 64)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((6, 64)), jnp.float32)
    valid = jnp.ones(300, bool)
    top_d, idx_d = ops.mips_topk(x, q, valid, k=5)            # default: None
    top_o, idx_o = ops.mips_topk(x, q, valid, k=5, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(top_d), np.asarray(top_o))
    np.testing.assert_array_equal(np.asarray(idx_d), np.asarray(idx_o))
    top_p, idx_p = ops.mips_topk(x, q, valid, k=5, use_pallas=True,
                                 page_rows=64)
    np.testing.assert_allclose(np.asarray(top_p), np.asarray(top_o),
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(idx_p), np.asarray(idx_o))


def test_flash_train_attention_grads(rng):
    """Training flash attention (custom_vjp) vs naive softmax attention."""
    from repro.models.attention import _flash_causal
    B, S, H, KH, dh = 2, 80, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KH, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KH, dh)), jnp.float32)

    def naive(q, k, v):
        g = H // KH
        qf = q.reshape(B, S, KH, g, dh).astype(jnp.float32) * dh ** -0.5
        scores = jnp.einsum("bskgd,btkd->bkgst", qf, k.astype(jnp.float32))
        mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        w = jax.nn.softmax(scores, -1)
        out = jnp.einsum("bkgst,btkd->bkgsd", w, v.astype(jnp.float32))
        return jnp.moveaxis(out, 3, 1).reshape(B, S, H, dh)

    f1 = lambda *a: jnp.sum(jnp.cos(_flash_causal(*a, block=32)))
    f2 = lambda *a: jnp.sum(jnp.cos(naive(*a)))
    assert abs(float(f1(q, k, v)) - float(f2(q, k, v))) < 1e-2
    g1 = jax.grad(f1, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
