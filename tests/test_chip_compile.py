"""The main-path search kernels compile for a TPU v5e at SIFT-1M widths,
and the sharded search program compiles across four chips.

Nothing runs: each kernel's public wrapper is lowered with shapes only and
compiled for one chip of a described (not attached) ``v5e:2x2`` topology,
which raises whatever Mosaic or the TPU compiler would refuse on the chip
(unsupported primitives, SMEM/VMEM overflows, unaligned tiles).  The
interpret-mode parity tests cannot see any of that.  The CPU backend stays
the default; the kernels are called with ``interpret=False`` because the
wrappers would otherwise pick interpret mode from the CPU backend.
"""

import os

import pytest

import jax
import jax.numpy as jnp

N = 1_000_000          # SIFT-1M corpus rows
D = 128                # SIFT descriptor width
B = 256                # served micro-batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 - no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_disk_cache(topo):
    """The persistent compilation cache is off meanwhile: entries written
    for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo, no_disk_cache):
    """ShapeDtypeStruct factory placed on one chip of the topology."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _compiled_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("k", [10, 100])
def test_distance_topk_compiles(shape, k):
    from repro.kernels.distance_topk import stream_topk

    hlo = _compiled_hlo(
        lambda Q, X: stream_topk(Q, X, k=k, metric="euclidean",
                                 interpret=False),
        shape((B, D), jnp.float32), shape((N, D), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_rerank_topk_compiles(shape):
    from repro.kernels.rerank_topk import rerank_topk

    hlo = _compiled_hlo(
        lambda Q, X, cand, xsq: rerank_topk(
            Q, X, cand, k=10, metric="euclidean", xsq=xsq, use_kernel=True,
            interpret=False),
        shape((B, D), jnp.float32), shape((N, D), jnp.float32),
        shape((B, 8192), jnp.int32), shape((N,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("metric", ["euclidean", "angular"])
def test_rerank_lists_compiles(shape, metric):
    """The list-slice rerank at the IVF cell's shapes: 64 queries, 8 traced
    probes of lists up to 4,162 rows (the longest of 1,000 k-means lists
    over SIFT-1M).  Starts and sizes are runtime values, so the kernel's
    copies are compiled for any start, 8-aligned or not."""
    from repro.kernels.rerank_topk import rerank_lists

    hlo = _compiled_hlo(
        lambda Q, X, starts, sizes, ids, xsq, n_probes: rerank_lists(
            Q, X, starts, sizes, k=10, metric=metric, ids=ids,
            xsq=xsq if metric == "euclidean" else None, n_probes=n_probes,
            window=4162, interpret=False),
        shape((64, D), jnp.float32), shape((N, D), jnp.float32),
        shape((64, 8), jnp.int32), shape((64, 8), jnp.int32),
        shape((N,), jnp.int32), shape((N,), jnp.float32),
        shape((), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_adc_scan_compiles(shape):
    from repro.kernels.adc_scan import adc_scan

    hlo = _compiled_hlo(
        lambda codes, luts: adc_scan(codes, luts, k=1000, use_kernel=True,
                                     interpret=False),
        shape((N, 16), jnp.uint8), shape((B, 16, 256), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_hamming_topk_compiles(shape):
    from repro.kernels.hamming import hamming_topk

    hlo = _compiled_hlo(
        lambda Q, XT: hamming_topk(Q, XT, k=10, interpret=False),
        shape((B, 128 // 32), jnp.uint32), shape((128 // 32, N), jnp.uint32))
    assert "tpu_custom_call" in hlo


def test_sharded_topk_compiles_for_four_chips(topo, no_disk_cache):
    """The sharded search program (per-shard streaming scan, butterfly
    merge, root psum) compiles across the four chips of the topology."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.ann.sharded import make_sharded_topk

    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    fn = make_sharded_topk(mesh, ("data",), k=10, metric="euclidean",
                           corpus_block=2048)
    n = 4 * 4096
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    hlo = fn.lower(
        jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((n, D), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows),
    ).compile().as_text()
    assert "collective-permute" in hlo and "all-reduce" in hlo
