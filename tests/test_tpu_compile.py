"""Compile the main-path Pallas kernels for a TPU v5e, without the chip.

Each case lowers the kernel the engine runs — vmapped over the Reduce
slots, as the ``vmap`` backend batches it — at the per-chunk shapes of
``chip_smoke.py`` (8 slots, 2^18 received rows per slot per chunk, 2^20
pairs per slot in phase A, 65536 clusters, (8,) f32 values) and compiles
it with Mosaic for a described ``v5e:2x2`` topology. A kernel that only
passes in interpret mode fails here: unaligned blocks, scalars read from
vector blocks, more VMEM than a kernel may use.

Everything built from the topology lives in fixtures of this one file,
so only the worker that runs these tests loads the TPU compiler.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.histogram.histogram import histogram_pallas
from repro.kernels.segment_reduce.segment_reduce import segment_reduce_sorted_pallas
from repro.kernels.sketch_hist.sketch_hist import sketch_hist_pallas

SLOTS, ROWS, PAIRS, CLUSTERS, V = 8, 1 << 18, 1 << 20, 65536, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _chunk_reduce(values, order, seg):
    # The engine's kernel path: gather into rank order, sorted segment sum.
    return segment_reduce_sorted_pallas(values[order], seg, CLUSTERS, interpret=False)


def _histogram(ids, w):
    return histogram_pallas(ids, w, CLUSTERS, interpret=False)


def _sketch(ids, w, mult):
    return sketch_hist_pallas(ids, w, mult, 1024, interpret=False)


CASES = {
    "segment_reduce": (jax.vmap(_chunk_reduce),
                       [((SLOTS, ROWS, V), jnp.float32), ((SLOTS, ROWS), jnp.int32),
                        ((SLOTS, ROWS), jnp.int32)]),
    "histogram": (jax.vmap(_histogram),
                  [((SLOTS, PAIRS), jnp.int32), ((SLOTS, PAIRS), jnp.float32)]),
    "sketch_hist": (jax.vmap(_sketch, in_axes=(0, 0, None)),
                    [((SLOTS, PAIRS), jnp.int32), ((SLOTS, PAIRS), jnp.float32),
                     ((4,), jnp.uint32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
