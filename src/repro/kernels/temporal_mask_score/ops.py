"""jit'd wrappers for the temporal validity-masked top-k kernel.

``temporal_window_topk`` is the general fused primitive: one dispatch
scores a (Q, d) query block against the full-history corpus with a
PER-QUERY validity window — no per-timestamp materialized snapshot copy
ever exists. The history is sent host->device on every call.
``temporal_topk`` (point-in-time, one shared ts) is the degenerate
window [ts, ts+1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from ..common import (kernel_mode, merge_blocks, pad_to, row_block,
                      split_i64, to_device)
from .ref import temporal_window_topk_q8_ref, temporal_window_topk_ref
from .temporal_mask_score import temporal_block_candidates


# a padded row's validity interval is empty: it starts after every window
# and ends before every window, so it never overlaps one
_PAD_FROM = np.iinfo(np.int64).max
_PAD_TO = 0


def _split_flip(x_i64: np.ndarray) -> np.ndarray:
    """Host int64 -> (2, n) int32 words (hi, lo ^ 2**31). Flipping the
    low word's top bit makes a signed compare order it like the unsigned
    word, so the kernel compares int32 pairs lexicographically."""
    hi, lo = split_i64(x_i64)
    return np.stack([hi, (lo ^ np.uint32(1 << 31)).view(np.int32)])


def _host_words(valid_from, valid_to, t0s, t1s, bn: int):
    """Validity words (4, N padded to bn) and window words (Q, 4), on
    the host."""
    pad = (-len(valid_from)) % bn
    vf = np.concatenate([np.asarray(valid_from, np.int64),
                         np.full(pad, _PAD_FROM, np.int64)])
    vt = np.concatenate([np.asarray(valid_to, np.int64),
                         np.full(pad, _PAD_TO, np.int64)])
    valid = np.concatenate([_split_flip(vf), _split_flip(vt)])
    win = np.concatenate([_split_flip(t0s), _split_flip(t1s)]).T
    return valid, np.ascontiguousarray(win)


@functools.partial(jax.jit, static_argnames=("k", "bn", "interpret", "q8"))
def _temporal_topk_jit(q, corpus, valid, win, k: int, bn: int,
                       interpret: bool, q8: bool):
    corpus_p, _ = pad_to(corpus, 0, bn)
    s_blk, i_blk = temporal_block_candidates(
        q, corpus_p, valid, win, k, bn=bn, interpret=interpret)
    top_s, top_i = merge_blocks(s_blk, i_blk, k)
    if q8:
        # contract: an empty (-inf) pool slot is idx -1, so a downstream
        # exact rescore can never resurrect an out-of-window row
        top_i = jnp.where(jnp.isfinite(top_s), top_i, -1)
    return top_s, top_i


def temporal_window_topk(q, corpus, valid_from, valid_to, t0s, t1s, k: int,
                         bn: int = 512, mode: str | None = None):
    """Fused window-overlap scoring: filter-before-rank top-k with a
    per-query validity window.

    q: (Q, D); corpus: (N, D); valid_from/valid_to: (N,) int64 host
    arrays; t0s/t1s: (Q,) int64 window bounds (point query i == window
    [ts_i, ts_i + 1)). Returns (scores (Q, k), idx (Q, k)); rows with no
    overlapping candidate come back -inf.
    """
    mode = kernel_mode(mode)
    with obs.span("kernel:temporal_window_topk"):
        q = np.atleast_2d(np.asarray(q, np.float32))
        t0s = np.broadcast_to(np.asarray(t0s, np.int64), (q.shape[0],))
        t1s = np.broadcast_to(np.asarray(t1s, np.int64), (q.shape[0],))
        k = int(min(k, corpus.shape[0]))
        if corpus.shape[0] == 0 or k == 0:
            # empty history: nothing can ever be valid, regardless of window
            return (np.zeros((q.shape[0], 0), np.float32),
                    np.zeros((q.shape[0], 0), np.int32))
        if mode == "ref":
            return temporal_window_topk_ref(q, corpus, valid_from,
                                            valid_to, t0s, t1s, k)
        bn = row_block(int(corpus.shape[0]), bn)
        valid, win = _host_words(valid_from, valid_to, t0s, t1s, bn)
        q, corpus, valid, win = to_device(
            (q, np.float32), (corpus, np.float32), (valid, None),
            (win, None))
        return _temporal_topk_jit(q, corpus, valid, win,
                                  k, bn, mode == "interpret", False)


def temporal_window_topk_q8(q, c8, scale, valid_from, valid_to, t0s, t1s,
                            k: int, bn: int = 512, mode: str | None = None):
    """Quantized fused window-overlap scoring (DESIGN.md §11): the
    candidate-generation half of the temporal tier's quantized scan.

    q: (Q, D) fp32 UNscaled queries; c8: (N, D) int8 resident history;
    scale: (D,) per-dimension quantization scale (folded into the
    queries once — asymmetric distance); validity columns and per-query
    windows exactly as ``temporal_window_topk``. Callers over-fetch
    (k' = rescore_factor * k) and exactly rescore in fp32. The overlap
    filter runs before ranking in EVERY mode, so the leakage guarantee
    is identical to the fp32 path."""
    mode = kernel_mode(mode)
    with obs.span("kernel:temporal_window_topk_q8"):
        q = np.atleast_2d(np.asarray(q, np.float32))
        c8 = np.asarray(c8, np.int8)
        scale = np.asarray(scale, np.float32)
        t0s = np.broadcast_to(np.asarray(t0s, np.int64), (q.shape[0],))
        t1s = np.broadcast_to(np.asarray(t1s, np.int64), (q.shape[0],))
        k = int(min(k, c8.shape[0]))
        if c8.shape[0] == 0 or k == 0:
            return (np.zeros((q.shape[0], 0), np.float32),
                    np.zeros((q.shape[0], 0), np.int32))
        from ...index.quant import fold_scale
        qs = fold_scale(q, scale)
        vf = np.asarray(valid_from, np.int64)
        vt = np.asarray(valid_to, np.int64)
        if mode == "ref":
            s, i = temporal_window_topk_q8_ref(qs, c8, vf, vt, t0s, t1s, k)
            return s, np.where(np.isfinite(s), i, -1)
        bn = row_block(int(c8.shape[0]), bn)
        valid, win = _host_words(vf, vt, t0s, t1s, bn)
        qs, c8, valid, win = to_device(
            (qs, np.float32), (c8, np.int8), (valid, None), (win, None))
        return _temporal_topk_jit(qs, c8, valid, win,
                                  k, bn, mode == "interpret", True)


def temporal_topk(q, corpus, valid_from, valid_to, ts: int, k: int,
                  bn: int = 512, mode: str | None = None):
    """Point-in-time temporal scoring (shared ts for the whole block):
    the degenerate window [ts, ts+1) — with integer-microsecond stamps
    the overlap test is exactly valid_from <= ts < valid_to.
    """
    q = np.atleast_2d(np.asarray(q, np.float32))
    ts = int(ts)
    bounds = np.full(q.shape[0], ts, np.int64)
    return temporal_window_topk(q, corpus, valid_from, valid_to,
                                bounds, bounds + 1, k, bn=bn, mode=mode)
