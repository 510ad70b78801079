"""Ahead-of-time compiles of the main-path device programs for a TPU v5e,
at real widths, without a chip: each Pallas kernel must lower through
Mosaic (``tpu_custom_call`` in the compiled program) and each program
must fit one chip's HBM.  The topology is described inside a fixture, so
collection never loads the TPU compiler; where it cannot be described,
the tests skip."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitmap_filter as bf
from repro.kernels import fused_scan as fs
from repro.kernels import ivf_scan as ivf
from repro.kernels import ops as kops
from repro.kernels import pq_adc
from repro.kernels import quantized_scan as qs
from repro.kernels import ref
from repro.kernels import topk_merge as tkm

N = 1 << 20              # scan rows: one chip's share of a TRACY store
NQ = 64                  # query batch
D = 128                  # TRACY embedding width
HBM_BYTES = 16 * 2**30   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Programs compiled for a described chip cannot be read back from
    the persistent cache without one: keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = fn.lower(*args, **static).compile()
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.output_size_in_bytes \
        + ma.temp_size_in_bytes
    assert used < HBM_BYTES, used
    return compiled.as_text()


@pytest.mark.parametrize("k", [10, 128])
def test_fused_scan_compiles(one_chip, k):
    text = _compile(fs.fused_scan_topk, one_chip,
                    ((NQ, D), jnp.float32), ((N, D), jnp.float32),
                    ((NQ, N), jnp.uint8), ((1, N), jnp.int32),
                    ((NQ // fs.BLOCK_Q, N // fs.BLOCK_N), jnp.int32), k=k)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,m", [(10, 8), (128, 8), (40, 128)])
def test_quantized_scan_compiles(one_chip, k, m):
    text = _compile(qs.quantized_scan_topk, one_chip,
                    ((NQ, m * 256), jnp.float32), ((N, m), jnp.uint8),
                    ((NQ, N), jnp.uint8), ((1, N), jnp.int32),
                    ((NQ // fs.BLOCK_Q, N // fs.BLOCK_N), jnp.int32), k=k)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("nq,n", [(16384, 1024), (NQ, 1 << 18)])
def test_ivf_scan_compiles(one_chip, nq, n):
    """k-means assignment chunks and a batched distance scan."""
    text = _compile(ivf.ivf_scan, one_chip,
                    ((nq, D), jnp.float32), ((n, D), jnp.float32))
    assert "tpu_custom_call" in text


def test_pq_adc_compiles(one_chip):
    text = _compile(pq_adc.pq_adc, one_chip,
                    ((N, 8), jnp.uint8), ((8, 256), jnp.float32))
    assert "tpu_custom_call" in text


def test_bitmap_filter_compiles(one_chip):
    text = _compile(bf.bitmap_filter, one_chip,
                    ((N, 4), jnp.float32), ((4, 2), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [10, 128])
def test_batched_topk_merge_compiles(one_chip, k):
    text = _compile(tkm.batched_topk_merge, one_chip,
                    ((NQ, 4, k), jnp.float32), ((NQ, 4, k), jnp.int32), k=k)
    assert "tpu_custom_call" in text


def test_graph_dispatch_program_compiles(one_chip):
    """Graph dispatch runs on a TPU as the jitted XLA twin of the beam
    search (the backend rule's ``GRAPH_PROGRAM``), not a Mosaic kernel."""
    assert kops.GRAPH_PROGRAM == "xla"
    n, beam, hops = 1 << 15, 40, 8
    fn = jax.jit(functools.partial(ref.graph_search_topk_ref,
                                   beam=beam, hops=hops))
    text = _compile(fn, one_chip,
                    ((16, D), jnp.float32), ((n, D), jnp.float32),
                    ((n, 16), jnp.int32), ((1, 32), jnp.int32),
                    ((16, n), jnp.uint8), ((1, n), jnp.int32))
    assert "tpu_custom_call" not in text and "sort" in text
