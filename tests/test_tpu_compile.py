"""The device path's kernels compile for a TPU v5e, at the widths of
``chip_smoke.py``'s DBLP-scale history, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached.  Interpret-mode parity tests cannot see what
Mosaic refuses (blocks off the (8, 128) tiling, casts it has no lowering
for); these compiles can.  The topology is described inside the ``topo``
fixture only: only one process at a time may load the TPU library, and
every test worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.bitmaps import num_words
from repro.kernels import (delta_apply_chain, delta_apply_chain_batched,
                           delta_apply_fused_batched)
from repro.kernels.segment_sum.segment_sum import segment_sum_bucketed
from repro.launch.hlo_analysis import COLLECTIVES
from repro.runtime.jax_exec import make_retrieval_fn

# growing_network(n_events=2_000_000, seed=0, attrs_on_add=False)
NODE_SLOTS, EDGE_SLOTS = 600_437, 1_399_563
W = num_words(EDGE_SLOTS)          # 43,737 packed words
K, B = 16, 16                      # chain-length and batch buckets
BLOCK_N, MAX_BUCKET_EDGES = 128, 2_816   # degree feed: largest node block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        with pytest.MonkeyPatch.context() as mp:
            # the TPU library would otherwise log under /tmp as it loads
            mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR",
                                                    "disabled"))
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_chain_kernel_compiles(one_chip):
    fn = functools.partial(delta_apply_chain, impl="pallas", interpret=False)
    hlo = _hlo(fn, one_chip((W,), jnp.uint32),
               one_chip((K, W), jnp.uint32), one_chip((K, W), jnp.uint32))
    assert "tpu_custom_call" in hlo


def test_batched_chain_compiles(one_chip):
    fn = functools.partial(delta_apply_chain_batched, impl="pallas",
                           interpret=False)
    hlo = _hlo(fn, one_chip((B, W), jnp.uint32),
               one_chip((B, K, W), jnp.uint32),
               one_chip((B, K, W), jnp.uint32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("weighted", [True, False])
@pytest.mark.parametrize("emit_live", [True, False])
def test_fused_kernel_compiles(one_chip, weighted, emit_live):
    args = [one_chip((B, W), jnp.uint32), one_chip((B, K, W), jnp.uint32),
            one_chip((B, K, W), jnp.uint32)]

    def fn(bases, adds, dels, weights=None):
        return delta_apply_fused_batched(bases, adds, dels, weights,
                                         impl="pallas", interpret=False,
                                         emit_live=emit_live)
    if weighted:
        args.append(one_chip((W * 32,), jnp.float32))
    assert "tpu_custom_call" in _hlo(fn, *args)


def test_segment_sum_compiles_at_degree_shape(one_chip):
    NB = -(-NODE_SLOTS // BLOCK_N)
    fn = functools.partial(segment_sum_bucketed, block_n=BLOCK_N,
                           interpret=False)
    hlo = _hlo(fn, one_chip((NB, 1, MAX_BUCKET_EDGES), jnp.float32),
               one_chip((NB, MAX_BUCKET_EDGES), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_sharded_retrieval_compiles_collective_free(topo):
    mesh = jax.sharding.Mesh(topo.devices[:4], ("data",))
    Wp = -(-W // 4)

    def spec(shape, pspec):
        return jax.ShapeDtypeStruct(shape, jnp.uint32,
                                    sharding=NamedSharding(mesh, pspec))
    hlo = make_retrieval_fn(mesh).lower(
        spec((4, Wp), P("data", None)), spec((K, 4, Wp), P(None, "data", None)),
        spec((K, 4, Wp), P(None, "data", None))).compile().as_text()
    assert not [c for c in COLLECTIVES if c in hlo]


def test_bucketed_pagerank_compiles_at_served_shape(one_chip):
    """The PageRank fixpoint program with the Pallas reducer, at the
    served churn history's shapes: 33,333 node slots, live edges padded
    to 196,608."""
    from repro.graph import algorithms as alg
    N, Ec = 33_333, 196_608
    R = alg._row_count(Ec, N)
    lanes = R * alg.ROW_LANES
    hlo = alg._pagerank_fixpoint_kernel.lower(
        one_chip((lanes,), jnp.int32), one_chip((lanes,), jnp.int32),
        one_chip((R,), jnp.int32), one_chip((N,), jnp.float32),
        one_chip((num_words(N),), jnp.uint32), one_chip((N,), jnp.float32),
        one_chip((), jnp.float32), one_chip((), jnp.float32), num_nodes=N,
        max_iters=alg.PAGERANK_MAX_ITERS, impl="pallas",
        interpret=False).compile().as_text()
    assert "tpu_custom_call" in hlo
