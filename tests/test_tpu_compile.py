"""The main path's Pallas kernels compile for a TPU v5e.

Each case lowers a kernel at the serving widths (SuSy D=18, CHist D=32,
k=16, 128-row tiles, |D| = 5,000,000) for one device of a described
``v5e:2x2`` topology and compiles it with the TPU compiler — no chip,
no interpret mode — then checks that the program holds the Mosaic
kernel (``tpu_custom_call``).  This catches what interpret mode cannot:
block shapes Mosaic refuses, and kernel bodies it cannot lower.  The
sparse capacity gate's count program compiles beside them, held to its
memory at CHist's size."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core import grid as grid_lib
from repro.core import sparse_knn as sparse_lib
from repro.core.dense_join import PREFETCH_BLOCK_SLACK
from repro.kernels.bin_hist import kernel as hist_kernel
from repro.kernels.knn_stream import kernel as stream_kernel
from repro.kernels.knn_topk import kernel as topk_kernel
from repro.kernels.pairwise_l2 import kernel as pairwise_kernel

K = 16
BLOCK = 128
N_CORPUS = 5_000_192          # SuSy |D| rounded up to whole 512-row blocks
N_TILES = 128                 # a 16,384-query batch in 128-row tiles
NBLK = 2048 // BLOCK + PREFETCH_BLOCK_SLACK   # default dense_budget


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without one — keep the cache out of it."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for_chip(one_chip, no_compile_cache):
    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compile_(fn, *args, **static):
        lowered = fn.lower(*(shape(*a) for a in args), **static)
        text = lowered.compile().as_text()
        assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compile_


@pytest.mark.parametrize("dim", [18, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_stream_prefetch_compiles(compile_for_chip, dim, dtype):
    """The fused dense engine's scalar-prefetch kernel (default path)."""
    compile_for_chip(
        stream_kernel.knn_stream_topk_prefetch,
        ((N_TILES * BLOCK, dim), dtype),
        ((N_CORPUS, dim), dtype),
        ((N_TILES, NBLK), jnp.int32),
        ((N_TILES * BLOCK,), jnp.int32),
        ((N_TILES, NBLK * BLOCK), jnp.int32),
        ((), jnp.float32),
        k=K, block_q=BLOCK, block_c=BLOCK)


@pytest.mark.parametrize("dim", [18, 32])
def test_stream_padded_compiles(compile_for_chip, dim):
    """The gathered-candidate streaming kernel (legacy fused path)."""
    compile_for_chip(
        stream_kernel.knn_stream_topk_padded,
        ((BLOCK, dim), jnp.float32),
        ((2048, dim), jnp.float32),
        ((BLOCK,), jnp.int32),
        ((2048,), jnp.int32),
        ((), jnp.float32),
        k=K, block_q=BLOCK, block_c=BLOCK)


@pytest.mark.parametrize("dim", [18, 32])
def test_tile_topk_compiles(compile_for_chip, dim):
    """The brute lane's tile top-K kernel (certification backstop)."""
    compile_for_chip(
        topk_kernel.knn_tile_topk,
        ((16_384, dim), jnp.float32),
        ((2048, dim), jnp.float32),
        ((16_384,), jnp.int32),
        ((2048,), jnp.int32),
        k=K)


def test_bin_histogram_compiles(compile_for_chip):
    """ε selection's distance histogram over the full corpus."""
    compile_for_chip(
        hist_kernel.distance_bin_histogram,
        ((256, 18), jnp.float32),
        ((N_CORPUS, 18), jnp.float32),
        ((256,), jnp.int32),
        ((N_CORPUS,), jnp.int32),
        ((), jnp.float32),
        n_bins=256)


def test_pairwise_dyn_shortc_compiles(compile_for_chip):
    """The tiled backend's distance kernel with a runtime SHORTC ε²."""
    compile_for_chip(
        pairwise_kernel.pairwise_sq_l2_dyn_shortc,
        ((BLOCK, BLOCK), jnp.float32),
        ((2048, BLOCK), jnp.float32),
        ((), jnp.float32))


@pytest.mark.parametrize("foreign", [True, False], ids=["foreign", "self"])
def test_finest_counts_compiles_small(one_chip, no_compile_cache, foreign):
    """N₀ over CHist (68,040 x 32, m = 6) for an 8,192-row batch or the
    whole corpus: blockwise, its temporaries stay under 16 MiB."""
    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    level0 = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        functools.partial(grid_lib.build_grid, m=6,
                          materialize_points=False),
        jax.ShapeDtypeStruct((68_040, 32), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32)))
    args = (level0,)
    if foreign:
        args += (on_chip(jax.ShapeDtypeStruct((8192, 32), jnp.float32)),)
    compiled = sparse_lib.finest_counts.lower(
        *args, query_block=BLOCK).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20
