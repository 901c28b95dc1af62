"""Compile the serving kernels for a described TPU v5e, at the shapes
chip_smoke.py runs (Gowalla's 1,280,969 items padded to the 512-row
tile, m=8, b=256, B=32, k=100).  Nothing runs: the TPU compiler,
installed with jax, refuses here what it would refuse on the chip —
unlowerable primitives, misaligned blocks, scoped-VMEM overflow.

Every compile against the described topology lives in this one file
(only one process may load the TPU library at a time, and it keeps it
until it exits), and the topology is described inside a fixture, so
collection never touches it."""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.jpq_scores.jpq_scores import jpq_scores_lut
from repro.kernels.jpq_topk.jpq_topk import (jpq_topk_tiles,
                                             jpq_topk_tiles_pruned)

N, B, M, BC, K, BLOCK_N = 1_280_969, 32, 8, 256, 100, 512
NP = -(-N // BLOCK_N) * BLOCK_N


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


class TestV5eCompile:
    def test_fused_topk(self, one_chip):
        c = jpq_topk_tiles.lower(
            _shape(one_chip, (B, M, BC), jnp.float32),
            _shape(one_chip, (NP, M), jnp.uint8),
            k=K, n_items=N, block_b=B, block_n=BLOCK_N).compile()
        _assert_kernel(c)

    @pytest.mark.parametrize("tie_break_ids", [False, True])
    def test_pruned_topk(self, one_chip, tie_break_ids):
        c = jpq_topk_tiles_pruned.lower(
            _shape(one_chip, (B, M, BC), jnp.float32),
            _shape(one_chip, (NP, M), jnp.uint8),
            _shape(one_chip, (NP,), jnp.int32),
            _shape(one_chip, (NP // BLOCK_N, M, BC), jnp.float32),
            _shape(one_chip, (B, 1), jnp.float32),
            _shape(one_chip, (B, K), jnp.float32),
            _shape(one_chip, (B, K), jnp.int32),
            k=K, n_items=N, n_batch=B, block_b=B, block_n=BLOCK_N,
            tie_break_ids=tie_break_ids).compile()
        _assert_kernel(c)

    def test_scores(self, one_chip):
        c = jpq_scores_lut.lower(
            _shape(one_chip, (B, M, BC), jnp.float32),
            _shape(one_chip, (NP, M), jnp.uint8),
            block_b=B, block_n=BLOCK_N).compile()
        _assert_kernel(c)
        assert c.memory_analysis().output_size_in_bytes >= B * NP * 4
