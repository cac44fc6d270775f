"""EmbeddingBag Pallas kernel: per-row DMA gather + weighted reduce.

RecSys hot path (DLRM/FM/Wide&Deep): the embedding table is far too large
for VMEM, so it stays in HBM (BlockSpec memory_space=ANY) and the kernel
issues one dynamic row load per bag slot — exactly how a TPU embedding
kernel is structured (row-granular DMA, accumulate in VMEM registers).
Grid is one sample per step; the L bag slots unroll statically (multi-hot
width is a compile-time constant in DLRM-class configs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, w_ref, table_ref, o_ref, *, bag: int, combiner: str):
    d = o_ref.shape[-1]
    acc = jnp.zeros((d,), jnp.float32)
    wsum = jnp.zeros((), jnp.float32)
    for j in range(bag):                       # static multi-hot width
        idx = idx_ref[0, j]
        valid = idx >= 0
        safe = jnp.where(valid, idx, 0)
        row = table_ref[pl.ds(safe, 1), :]                         # (1, d)
        w = jnp.where(valid, w_ref[0, j], 0.0)
        acc = acc + w * row[0].astype(jnp.float32)
        wsum = wsum + w
    if combiner == "mean":
        acc = acc / jnp.maximum(wsum, 1e-9)
    o_ref[0, :] = acc.astype(o_ref.dtype)


def embedding_bag_kernel(table, indices, weights, *, combiner: str = "sum",
                         interpret: bool = False):
    """table: (V, D); indices/weights: (B, L). Returns (B, D)."""
    b, bag = indices.shape
    v, d = table.shape
    kern = functools.partial(_kernel, bag=bag, combiner=combiner)
    return pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, bag), lambda i: (i, 0)),
            pl.BlockSpec((1, bag), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),   # table stays in HBM
        ],
        out_specs=pl.BlockSpec((1, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        interpret=interpret,
    )(indices, weights, table)
