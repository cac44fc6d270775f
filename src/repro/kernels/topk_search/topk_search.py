"""Fused similarity-scoring + streaming top-k Pallas kernel (hot-tier
query hot path; DESIGN.md §2).

The (Q, N) score matrix is NEVER materialized in HBM: corpus blocks of
``bn`` rows stream through VMEM; each grid step computes Q x bn scores on
the MXU, masks inactive slots, and reduces them to a per-block top-k via k
iterative max passes (kernels/common.block_topk — k is small and static).
Per-block candidates land in a (nblocks, Q, k) output; the cheap global
merge over nblocks*k candidates happens in the jit'd wrapper (ops.py).

One body serves the fp32 and the int8 corpus (DESIGN.md §11): an int8
block arrives at 1 byte/element of HBM->VMEM traffic instead of 4 (the
scan is bandwidth-bound, so this is the whole win) and is dequantized
IN-REGISTER by the astype, a no-op for fp32; the per-dimension
quantization scale is already folded into the fp32 queries by the
wrapper, so the dot IS the exact dequantized asymmetric distance.

VMEM working set per step: Q*D (queries, resident) + bn*D (corpus block)
+ Q*bn (scores) floats. Defaults (Q<=256, D=384, bn=512) ~= 1.7 MB — far
inside the ~16 MB/core VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import block_topk


def _kernel(q_ref, c_ref, mask_ref, out_s_ref, out_i_ref, *, k: int):
    bn = c_ref.shape[0]
    scores = jax.lax.dot_general(
        q_ref[...], c_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # (Q, bn)
    scores = jnp.where(mask_ref[...] != 0, scores, -jnp.inf)   # (1, bn) mask
    top_s, top_i = block_topk(scores, k, pl.program_id(0) * bn)
    out_s_ref[...] = top_s[None]
    out_i_ref[...] = top_i[None]


def topk_block_candidates(q: jax.Array, corpus: jax.Array, mask: jax.Array,
                          k: int, bn: int = 512,
                          interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Stage 1: per-corpus-block top-k. q: (Q, D) fp32 (scale-folded for
    an int8 corpus); corpus: (N, D) fp32 or int8 with N % bn == 0; mask:
    (1, N) int32, nonzero = active. The mask rides as a 2-D row so its
    blocks tile like the corpus lanes on the chip.
    Returns (scores (nblocks, Q, k), idx (nblocks, Q, k))."""
    n, d = corpus.shape
    nq = q.shape[0]
    assert n % bn == 0, (n, bn)
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((nq, d), lambda j: (0, 0)),     # queries: resident
            pl.BlockSpec((bn, d), lambda j: (j, 0)),     # corpus block stream
            pl.BlockSpec((1, bn), lambda j: (0, j)),     # active mask block
        ],
        out_specs=[
            pl.BlockSpec((1, nq, k), lambda j: (j, 0, 0)),
            pl.BlockSpec((1, nq, k), lambda j: (j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n // bn, nq, k), jnp.float32),
            jax.ShapeDtypeStruct((n // bn, nq, k), jnp.int32),
        ],
        interpret=interpret,
    )(q, corpus, mask)
