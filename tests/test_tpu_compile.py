"""The device query programs compile for a described TPU v5e.

No chip is needed: the TPU compiler is installed with jax, and it compiles
for a topology that is described and not attached.  The shapes are the
buckets ``chip_smoke.py`` reports at its default size (the full WSJ1_LIKE
collection): what the chip's compiler refuses, these tests refuse first.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and pytest workers import every test
file.  Keep every such compile in this one file.
"""

import os

import numpy as np
import pytest

# Buckets chip_smoke.py reports on one chip at its default size: the
# largest packed caps of its query log, after the post-freeze ingest and
# the deletes (liveness mask on).
B = 64
F = 4
QN, T = 32, 4
CAPS = (4096, 32)                # packed blocks per query: frozen, delta
FROZEN_BLOCKS = 1_095_518
DELTA_BLOCKS = 131_072
VOCAB = 1 << 19
DOC_CAP = 1 << 17
# The four-chip mesh phase at its default size: per-shard block rows,
# the whole vocabulary, per-term chain cap and collection size.
SHARD_BLOCKS = 264_653
MESH_VOCAB = 421_808
MESH_MAX_BLOCKS = 128
MESH_DOCS = 24_683


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("mode", ["conjunctive", "ranked_tfidf", "bm25"])
def test_fused_query_compiles_for_v5e(one_chip, mode):
    import jax.numpy as jnp

    from repro.core.device_index import DeltaIndex, DeviceIndex
    from repro.kernels.fused_query.ops import fused_query

    def meta(n=8):
        return [_sds((VOCAB,), jnp.int32, one_chip) for _ in range(n)]

    frozen = DeviceIndex(_sds((FROZEN_BLOCKS, B), jnp.uint8, one_chip),
                         *meta(5), num_docs=DOC_CAP, F=F)
    delta = DeltaIndex(_sds((DELTA_BLOCKS, B), jnp.uint8, one_chip),
                       *meta(7), num_docs=DOC_CAP, F=F)
    alive_bytes = -(-(DOC_CAP + 1) // 8)     # 1 bit per docid, packed
    alive_words = -(-alive_bytes // 4)       # padded to uint32 words
    compiled = fused_query.lower(
        (frozen, delta), _sds((QN, T), jnp.int32, one_chip),
        _sds((QN, T), jnp.bool_, one_chip), mode=mode, k=10,
        max_blocks=CAPS,
        doclens=(_sds((DOC_CAP + 1,), jnp.float32, one_chip)
                 if mode == "bm25" else None),
        n_stat=_sds((), jnp.int32, one_chip),
        avg_stat=_sds((), jnp.float32, one_chip),
        alive=_sds((alive_words,), jnp.uint32, one_chip),
        flavor="ref").compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 30
    assert "tpu_custom_call" not in compiled.as_text()   # plain XLA


@pytest.mark.parametrize("mode", ["ranked_sparse", "conjunctive"])
def test_sharded_query_step_compiles_on_four_v5e(topo, mode):
    import jax
    from jax.sharding import AxisType, Mesh

    from repro.core.sharded_index import (make_sharded_query_step,
                                          sharded_input_specs)
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    fn, ins, outs = make_sharded_query_step(
        mesh, k=10, max_blocks=MESH_MAX_BLOCKS, F=F, mode=mode,
        num_docs=MESH_DOCS if mode != "conjunctive" else -(-MESH_DOCS // 4))
    specs = sharded_input_specs(mesh, shard_blocks=SHARD_BLOCKS, B=B,
                                vocab=MESH_VOCAB, qbatch=QN, qterms=T)
    specs = [_sds(s.shape, s.dtype, sh) for s, sh in zip(specs, ins)]
    compiled = jax.jit(fn, in_shardings=ins,
                       out_shardings=outs).lower(*specs).compile()
    if mode == "ranked_sparse":
        assert "all-gather" in compiled.as_text()
