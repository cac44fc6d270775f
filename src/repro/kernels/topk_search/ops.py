"""jit'd wrapper for the fused top-k search kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import numpy as np

from ... import obs
from ..common import (kernel_mode, merge_blocks, pad_to, row_block,
                      to_device)
from .ref import topk_search_q8_ref, topk_search_ref
from .topk_search import topk_block_candidates


def _scan(q, corpus, mask, k: int, bn: int, interpret: bool):
    """Kernel path shared by the fp32 and int8 wrappers: pad the corpus
    to whole blocks (padded rows inactive), carry the mask as a (1, N)
    int32 row, then merge the per-block candidates."""
    corpus_p, _ = pad_to(corpus, 0, bn)
    mask_p, _ = pad_to(mask.astype(jnp.int32), 0, bn)
    s_blk, i_blk = topk_block_candidates(
        q, corpus_p, mask_p[None, :], k, bn=bn, interpret=interpret)
    return merge_blocks(s_blk, i_blk, k)


@functools.partial(jax.jit, static_argnames=("k", "bn", "mode"))
def _topk_search_jit(q, corpus, mask, k: int, bn: int, mode: str):
    if mode == "ref":
        return topk_search_ref(q, corpus, mask, k)
    return _scan(q, corpus, mask, k, bn, mode == "interpret")


def topk_search(q, corpus, mask, k: int, bn: int = 512,
                mode: str | None = None):
    """Masked exact top-k similarity search.

    q: (Q, D) or (D,); corpus: (N, D); mask: (N,) bool. Returns
    (scores (Q, k), idx (Q, k)). Rows with mask=False can never appear
    unless fewer than k rows are active (callers drop -inf entries).
    The outputs are device arrays: the span holds the argument copy and
    the enqueue, and the caller waits for them (``common.to_host``).
    """
    with obs.span("kernel:topk_search"):
        q, corpus, mask = to_device((q, np.float32), (corpus, np.float32),
                                    (mask, bool))
        q = jnp.atleast_2d(q)
        k = int(min(k, corpus.shape[0]))
        bn = row_block(int(corpus.shape[0]), bn)
        return _topk_search_jit(q, corpus, mask, k, bn, kernel_mode(mode))


@functools.partial(jax.jit, static_argnames=("k", "bn", "mode"))
def _topk_search_q8_jit(qs, c8, mask, k: int, bn: int, mode: str):
    if mode == "ref":
        top_s, top_i = topk_search_q8_ref(qs, c8, mask, k)
        return top_s, jnp.where(jnp.isfinite(top_s), top_i, -1)
    top_s, top_i = _scan(qs, c8, mask, k, bn, mode == "interpret")
    # contract: an empty (-inf) pool slot is idx -1 in EVERY mode, so a
    # downstream exact rescore can never resurrect a masked row
    return top_s, jnp.where(jnp.isfinite(top_s), top_i, -1)


def topk_search_q8(q, c8, scale, mask, k: int, bn: int = 512,
                   mode: str | None = None):
    """Masked top-k ASYMMETRIC search over an int8 corpus (DESIGN.md
    §11): candidate generation for the quantized scan fabric.

    q: (Q, D) fp32 queries (UNscaled); c8: (N, D) int8; scale: (D,)
    per-dimension quantization scale; mask: (N,) bool. The scale is
    folded into the queries once, so every mode scores the exact
    dequantized dot product q . (c8 * scale) without materializing a
    fp32 corpus. Returns (scores (Q, k), idx (Q, k)) — callers
    over-fetch (k' = rescore_factor * final_k) and exactly rescore the
    pool in fp32 (index/quant.rescore_topk); the scores returned here
    are the approximate pool scores, not the final ranking.

    Modes: pallas/interpret = the streaming int8 Pallas kernel; ref =
    pure-jnp oracle (``common.kernel_mode``)."""
    mode = kernel_mode(mode)
    with obs.span("kernel:topk_search_q8"):
        q = np.atleast_2d(np.asarray(q, np.float32))
        c8 = np.asarray(c8, np.int8)
        scale = np.asarray(scale, np.float32)
        k = int(min(k, c8.shape[0]))
        if c8.shape[0] == 0 or k == 0:
            return (np.zeros((q.shape[0], 0), np.float32),
                    np.zeros((q.shape[0], 0), np.int32))
        from ...index.quant import fold_scale
        qs = fold_scale(q, scale)
        bn = row_block(int(c8.shape[0]), bn)
        qs, c8, mask = to_device((qs, np.float32), (c8, np.int8),
                                 (mask, bool))
        return _topk_search_q8_jit(qs, c8, mask, k, bn, mode)
