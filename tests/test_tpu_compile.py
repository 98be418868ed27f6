"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: the kernels are lowered and compiled by Mosaic for one chip of
a described (not attached) ``v5e:2x2`` topology, which refuses what
interpret mode accepts — misaligned blocks, too much VMEM or SMEM. Shapes:
the LARGE_N benchmark point (n=100k, d=128, 8-row pages) and the paper's
Yahoo! Music corpus (n=624,961, d=300, 3-row pages), a 64-query batch, and
its Netflix corpus (n=17,770) at a 16-query batch. The
selection stages and the jnp verify oracle also compile at Yahoo size,
where XLA:TPU once spent minutes on them.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.block_mips import (MAX_K, _slots_per_call, block_mips,
                                     pages_per_step, sketch_scores)
from repro.kernels.mips_topk import mips_score

B = 64
HBM_BYTES = 16e9          # one v5e chip
# (n_pad, d, page_rows): LARGE_N and Yahoo! Music (n rounded up to pages)
LARGE_N = (100_000, 128, 8)
YAHOO = (624_963, 300, 3)
NETFLIX = (17_772, 300, 3)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can describe the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, one_chip, *shapes, mosaic=True):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    if mosaic:
        assert "tpu_custom_call" in compiled.as_text()   # a Mosaic kernel ran
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
    return compiled


@pytest.mark.parametrize("n_pad,d,page_rows,b", [
    LARGE_N + (B,), YAHOO + (B,), NETFLIX + (16,)],
    ids=["large_n", "yahoo", "netflix_b16"])
@pytest.mark.parametrize("k", [10, MAX_K])
def test_block_mips_compiles(one_chip, n_pad, d, page_rows, b, k):
    ns = 1024
    _compile(lambda *a: block_mips(*a, k=k, page_rows=page_rows), one_chip,
             ((n_pad, d), jnp.float32), ((n_pad,), jnp.bool_),
             ((b, d), jnp.float32), ((ns,), jnp.int32), ((b, ns), jnp.bool_),
             ((b, k), jnp.float32), ((b, k), jnp.int32), ((b,), jnp.float32))


def test_block_mips_dense_walk_compiles(one_chip):
    """The dense Yahoo round walks every block: more slots than one call's
    SMEM slot list holds, so the walk is a chain of calls."""
    n_pad, d, page_rows = YAHOO
    ns = n_pad // page_rows
    assert ns > _slots_per_call(page_rows)
    # the walk takes several pages per grid step at this size
    assert pages_per_step(ns, B, d, 10, page_rows) > 1
    _compile(lambda *a: block_mips(*a, k=10, page_rows=page_rows), one_chip,
             ((n_pad, d), jnp.float32), ((n_pad,), jnp.bool_),
             ((B, d), jnp.float32), ((ns,), jnp.int32), ((B, ns), jnp.bool_),
             ((B, 10), jnp.float32), ((B, 10), jnp.int32),
             ((B,), jnp.float32))


def test_mips_topk_block_route_compiles(one_chip, monkeypatch):
    """`ops.mips_topk` walks the whole corpus through `block_mips` at its own
    32-row pages: 32 window rows, so two validity words a slot and fewer
    slots a call."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n = 600_000
    assert -(-n // 32) > _slots_per_call(32)      # a chain of calls
    _compile(lambda x, q, v: ops.mips_topk(x, q, v, 10, use_pallas=True),
             one_chip, ((n, 300), jnp.float32), ((B, 300), jnp.float32),
             ((n,), jnp.bool_))


def test_sharded_fused_search_compiles(one_chip, monkeypatch):
    """The in-graph fused driver under shard_map, one shard per chip of the
    described 2x2 v5e: every tile bucket's `block_mips` compiles there."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.core import RuntimeConfig
    from repro.core.sharded import _sharded_search_fn, build_sharded
    from repro.data.synthetic import mf_factors

    from jax.experimental import topologies

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices), ("model",))
    sh = build_sharded(mf_factors(4000, 300, 32, seed=0), 4, m=8, seed=0)
    cfg = RuntimeConfig(mode="two_phase", verification="fused",
                        norm_adaptive=True, use_pallas=True, k=10)
    shard = NamedSharding(mesh, PartitionSpec("model"))
    arrays = type(sh.arrays)(**{
        f: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                sharding=shard)
        for f, a in sh.arrays._asdict().items()})
    q = jax.ShapeDtypeStruct((16, 300), jnp.float32,
                             sharding=NamedSharding(mesh, PartitionSpec()))
    fn = _sharded_search_fn(sh.meta, 10, mesh, "model", cfg)
    text = fn.lower(arrays, q).compile().as_text()
    assert "tpu_custom_call" in text and "block_mips" in text


@pytest.mark.parametrize("d,m,n_blocks", [(128, 16, 12_500), (300, 15, 208_321)],
                         ids=["large_n", "yahoo"])
def test_sketch_scores_compiles(one_chip, d, m, n_blocks):
    _compile(sketch_scores, one_chip, ((B, d), jnp.float32),
             ((m, 256, d // m), jnp.float32), ((n_blocks, m), jnp.int32))


def test_mips_score_compiles(one_chip):
    _compile(mips_score, one_chip, ((4096, 300), jnp.float32),
             ((B, 300), jnp.float32), ((4096,), jnp.bool_))


@pytest.fixture(scope="module")
def yahoo_index(one_chip):
    """Shapes of the Yahoo! Music index (n=624,961, d=300): a small build's
    arrays with every row-, block- and sub-partition-indexed axis stretched
    to full size, so the selection stages compile at the real NB."""
    from repro.core.index import build_index
    from repro.data.synthetic import mf_factors

    idx = build_index(mf_factors(2000, 300, 32, seed=0), m=8, seed=0)
    n_pad, _, page_rows = YAHOO
    nb, n_sp, n_groups = n_pad // page_rows, 8000, 256
    lead = {"x": n_pad, "p": n_pad, "ids": n_pad, "l2sq": n_pad,
            "sp_center": n_sp, "sp_radius": n_sp, "sp_max_l2sq": n_sp,
            "sp_start": n_sp + 1, "sk_mu": nb, "sk_codes": nb, "sk_err": nb}
    shapes = {}
    for f, a in idx.arrays._asdict().items():
        a = np.asarray(a)
        n = lead.get(f, nb if f.startswith("block_") else
                     n_groups if f.startswith("g_") else None)
        shape = (n,) + a.shape[1:] if n is not None else a.shape
        shapes[f] = jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)
    meta = dataclasses.replace(idx.meta, n=624_961, n_pad=n_pad, n_blocks=nb,
                               n_subparts=n_sp, n_groups=n_groups)
    return type(idx.arrays)(**shapes), meta


def test_selection_stages_compile_at_yahoo_size(one_chip, yahoo_index):
    """XLA:TPU took minutes over these stages at NB = 208k before their
    sub-partition gathers and block-validity reduce were rewritten
    (DESIGN.md §10 "Compile time at Yahoo size")."""
    from repro.core import search_fused as sf
    from repro.core.search_common import block_valid_from_ids
    from repro.core.search_device import block_priority

    arrays, meta = yahoo_index
    nb, n_sp = meta.n_blocks, meta.n_subparts

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    sf._frontend.lower(arrays, meta, s((B, meta.d))).compile()
    sf._round2.lower(arrays, meta, s((B, n_sp)), s((B,)), s((B,)), s((B,)),
                     s((B,), jnp.bool_), s((B, nb), jnp.bool_),
                     norm_adaptive=True, cs_prune=True).compile()
    jax.jit(block_priority).lower(arrays, s((B, meta.m))).compile()
    jax.jit(block_valid_from_ids, static_argnums=1).lower(
        arrays.ids, meta.page_rows).compile()


def test_oracle_verify_compiles_at_yahoo_size(one_chip, yahoo_index):
    """The jnp oracle is the chip's route for k > MAX_K (the stream
    over-fetch): its row masks must stay out of (NS, page_rows) shapes."""
    from repro.kernels.ref import block_mips_ref

    arrays, meta = yahoo_index
    k, ns = MAX_K + 1, 4096
    _compile(lambda x, ids, *a: block_mips_ref(
        x, ids >= 0, *a, k=k, page_rows=meta.page_rows), one_chip,
        (arrays.x.shape, jnp.float32), (arrays.ids.shape, jnp.int32),
        ((B, meta.d), jnp.float32), ((ns,), jnp.int32), ((B, ns), jnp.bool_),
        ((B, k), jnp.float32), ((B, k), jnp.int32), ((B,), jnp.float32),
        mosaic=False)
