"""Compile the save path's kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed without a chip, compiles for a
described `v5e:2x2` topology and raises what the chip's compiler would
raise (tiling rules, unsupported ops, memory that does not fit).  The
shapes are the real ones of qwen1.5-0.5b at published widths: the train
bucket (256 KiB chunks → 65,536-word rows, 17,695 rows padded to the
64-row block) and the serve bucket (2 KiB chunks → 512-word rows).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fingerprint import (block_rows, fingerprint_words,
                                       fingerprint_words_cmp)
from repro.kernels.ops import to_words

#: (rows, width, tile) of the full-width train and serve buckets
BUCKETS = {"train": (17728, 65536, 4096), "serve": (24576, 512, 512)}
#: qwen1.5-0.5b's embedding (vocab x d_model), the largest leaf
EMBED = (151936, 1024)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            from jax.experimental import topologies
            try:
                yield topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "plain"])
@pytest.mark.parametrize("bucket", sorted(BUCKETS))
def test_bucket_kernel_compiles(one_chip, bucket, fused):
    C, W, tile = BUCKETS[bucket]
    u32 = jnp.uint32
    if fused:
        fn = functools.partial(fingerprint_words_cmp, interpret=False,
                               tile=tile, rows=block_rows(C))
        shapes = [((C, W), u32), ((C,), u32), ((C, 4), u32)]
    else:
        fn = functools.partial(fingerprint_words, interpret=False,
                               tile=tile, rows=block_rows(C))
        shapes = [((C, W), u32), ((C,), u32)]
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel streams its input: nothing the size of the bucket is
    # allocated beside it
    assert compiled.memory_analysis().temp_size_in_bytes < C * W * 4 // 64


@pytest.mark.parametrize("n_chunks", [3, 1187])
def test_per_leaf_path_compiles(one_chip, n_chunks):
    """The per-leaf oracle path takes its row block from the chunk count:
    a 1-row block of a multi-chunk leaf breaks the (8, 128) rule."""
    fn = functools.partial(fingerprint_words, interpret=False)
    compiled = _compile(fn, one_chip, ((n_chunks, 65536), jnp.uint32),
                        ((n_chunks,), jnp.uint32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_sub_word_to_words_is_lane_dense(one_chip, dtype):
    """Packing sub-word elements into words of the full-width embedding
    keeps every temporary lane-dense: under 2x the leaf's bytes, where a
    narrow minor dimension would tile out to 64x."""
    leaf_bytes = EMBED[0] * EMBED[1] * np.dtype(dtype).itemsize
    mem = _compile(to_words, one_chip, (EMBED, dtype)).memory_analysis()
    assert mem.output_size_in_bytes < leaf_bytes + 4096
    assert mem.temp_size_in_bytes < 2 * leaf_bytes
