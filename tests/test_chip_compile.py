"""The four scan kernels of the served path, compiled for a TPU v5e, and
the IVF member scan's plain XLA program.

Each case lowers a kernel through its ``ops.py`` jit wrapper for one chip
of a described (not attached) ``v5e:2x2`` topology and asserts that the
chip's compiler accepted it and kept the Pallas kernel. Interpret mode
cannot show this: it accepts stores at unaligned lane offsets and 1-D
blocks that the chip's compiler refuses. Nothing runs, so results and
times are out of scope here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.common import row_block
from repro.kernels.ivf_scan import _ivf_scan_jit
from repro.kernels.temporal_mask_score.ops import _temporal_topk_jit
from repro.kernels.topk_search.ops import _topk_search_jit, _topk_search_q8_jit

D = 384          # the paper's embedding width
K = 10           # final top-k
K_POOL = 40      # the int8 paths over-fetch rescore_factor * k


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _lower(kernel: str, nq: int, n: int, sharding):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    bn = row_block(n, 512)
    q = sds((nq, D), jnp.float32)
    if kernel in ("topk", "topk_q8"):
        fn = _topk_search_jit if kernel == "topk" else _topk_search_q8_jit
        corpus = sds((n, D), jnp.float32 if kernel == "topk" else jnp.int8)
        k = K if kernel == "topk" else K_POOL
        return fn.lower(q, corpus, sds((n,), jnp.bool_), k=k, bn=bn,
                        mode="pallas")
    q8 = kernel == "temporal_q8"
    corpus = sds((n, D), jnp.int8 if q8 else jnp.float32)
    n_pad = -(-n // bn) * bn      # validity words arrive padded to blocks
    return _temporal_topk_jit.lower(
        q, corpus, sds((4, n_pad), jnp.int32), sds((nq, 4), jnp.int32),
        k=K_POOL if q8 else K, bn=bn, interpret=False, q8=q8)


@pytest.mark.parametrize("nq,n", [(2, 4097), (64, 102400)])
@pytest.mark.parametrize("kernel",
                         ["topk", "topk_q8", "temporal", "temporal_q8"])
def test_kernel_compiles_for_v5e(kernel, nq, n, one_chip):
    compiled = _lower(kernel, nq, n, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("quantized", [False, True], ids=["fp32", "int8"])
def test_ivf_scan_compiles_for_v5e(quantized, one_chip):
    """Two resident segments of the paper cells' size (4,096-row bucket,
    64 partitions) against a batch of 32, as the served path calls it."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, c, nq, segs = 4096, 64, 32, 2
    rows = tuple(sds((n, D), jnp.int8 if quantized else jnp.float32)
                 for _ in range(segs))
    assign = tuple(sds((n,), jnp.int32) for _ in range(segs))
    scales = tuple(sds((D,), jnp.float32) if quantized else None
                   for _ in range(segs))
    k = K_POOL if quantized else K
    compiled = _ivf_scan_jit.lower(
        sds((nq, D), jnp.float32), rows, assign, scales,
        sds((segs, nq, c), jnp.bool_), sds((segs * n,), jnp.bool_),
        ks=(k,) * segs).compile()
    assert compiled.out_info.shape == (2, segs, nq, k)
