"""The serving path's Pallas kernels compile natively for a TPU v5e.

Interpret mode (every other test) cannot show what Mosaic refuses: a
vector gather from VMEM, a bool loop carry, a cumsum. These tests
compile ``shed_partition`` and ``topk_select`` for a described v5e
chip — no chip attached — at the sizes the serving path runs them:

* ``shed_partition`` against the production Trust DB (65536 slots x
  4 ways, ways-leading) in ``budget_is_total`` mode, as the fused
  drain calls it, at micro-batch sizes the launcher forms;
* ``topk_select`` at ``retrieval/shard.py``'s call: a dense shard
  score vector (4096 docs on one replica, 256 per replica on sixteen)
  and the default ``retrieve_top_k`` of 64.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

CACHE_SLOTS, CACHE_WAYS = 65536, 4
RETRIEVE_TOP_K = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_native_kernel(compiled):
    # A Mosaic kernel lowers to a custom call; interpret mode would
    # have inlined plain HLO instead.
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_items", [256, 1600, 4096])
def test_shed_partition_compiles_for_v5e(one_chip, no_persistent_cache,
                                         n_items):
    compiled = ops.shed_partition.lower(
        _spec((n_items,), jnp.uint32, one_chip),
        _spec((n_items,), jnp.bool_, one_chip),
        _spec((CACHE_WAYS, CACHE_SLOTS), jnp.uint32, one_chip),
        _spec((CACHE_WAYS, CACHE_SLOTS), jnp.float32, one_chip),
        u_capacity=800, u_threshold=800, budget_dq=1200,
        budget_is_total=True, interpret=False).compile()
    _assert_native_kernel(compiled)
    mem = compiled.memory_analysis()
    # the Trust DB is an argument in HBM, never copied into temps
    assert mem.temp_size_in_bytes < CACHE_WAYS * CACHE_SLOTS * 4


@pytest.mark.parametrize("n_docs", [256, 4096])
def test_topk_select_compiles_for_v5e(one_chip, no_persistent_cache,
                                      n_docs):
    compiled = ops.topk_select.lower(
        _spec((n_docs,), jnp.float32, one_chip),
        k=RETRIEVE_TOP_K, interpret=False).compile()
    _assert_native_kernel(compiled)
