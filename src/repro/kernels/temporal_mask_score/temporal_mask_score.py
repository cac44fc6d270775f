"""Validity-masked scoring + streaming top-k Pallas kernel (cold-tier
temporal query path; paper §III-D3 enforced AT KERNEL LEVEL).

Identical streaming structure to kernels/topk_search, but the active mask
is replaced by the temporal validity OVERLAP test against a PER-QUERY
half-open window [t0_q, t1_q):

    valid_from < t1_q  AND  t0_q < valid_to

evaluated INSIDE the kernel, before any score can enter the top-k
selection — an invalid (future/superseded/deleted) chunk is -inf before
ranking, so temporal leakage is impossible by construction even when the
full version history is scanned. A point-in-time query at ts is the
window [ts, ts+1) — with integer-microsecond timestamps the overlap test
degenerates to exactly valid_from <= ts < valid_to.

Per-query bounds mean one dispatch serves a whole batch of queries with
DIFFERENT target instants/windows over one full-history corpus: the mask
is (Q, bn), not (bn,).

Timestamps are int64 on the host; TPUs are 32-bit machines, so each
timestamp arrives as an int32 pair (hi, lo ^ 2**31) — the low word with
its top bit flipped, so a SIGNED compare of it orders like the unsigned
low word — and the interval test is a lexicographic compare, exact at
microsecond resolution (see ops._split_flip). The four validity words
of each row ride as one (4, N) int32 array and the four window words of
each query as one (Q, 4) array, so every block is 2-D and lane-aligned.

One body serves the fp32 and the int8 history (DESIGN.md §11): the int8
block is dequantized IN-REGISTER by the astype (a no-op for fp32) and
the per-dimension scale is folded into the fp32 queries by the wrapper.
The temporal-leakage guard is the same for both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import block_topk, lt_i64


def _kernel(q_ref, c_ref, valid_ref, win_ref, out_s_ref, out_i_ref, *,
            k: int):
    bn = c_ref.shape[0]
    scores = jax.lax.dot_general(
        q_ref[...], c_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # (Q, bn)
    # validity rows (1, bn): valid_from hi/lo, valid_to hi/lo
    vf_hi, vf_lo = valid_ref[0:1, :], valid_ref[1:2, :]
    vt_hi, vt_lo = valid_ref[2:3, :], valid_ref[3:4, :]
    # window columns (Q, 1): t0 hi/lo, t1 hi/lo
    t0_hi, t0_lo = win_ref[:, 0:1], win_ref[:, 1:2]
    t1_hi, t1_lo = win_ref[:, 2:3], win_ref[:, 3:4]
    # THE temporal-leakage guard: window overlap, pre-ranking, per query.
    valid = lt_i64(vf_hi, vf_lo, t1_hi, t1_lo) & \
        lt_i64(t0_hi, t0_lo, vt_hi, vt_lo)               # (Q, bn)
    scores = jnp.where(valid, scores, -jnp.inf)
    top_s, top_i = block_topk(scores, k, pl.program_id(0) * bn)
    out_s_ref[...] = top_s[None]
    out_i_ref[...] = top_i[None]


def temporal_block_candidates(q, corpus, valid, win, k: int, bn: int = 512,
                              interpret: bool = False):
    """Per-block streaming candidates. q: (Q, d) fp32 (scale-folded for
    an int8 history); corpus: (N, d) fp32 or int8 with N % bn == 0;
    valid: (4, N) int32 validity words; win: (Q, 4) int32 window words.
    Returns ((N//bn, Q, k) scores, (N//bn, Q, k) global indices).
    """
    n, d = corpus.shape
    nq = q.shape[0]
    assert n % bn == 0, (n, bn)
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((nq, d), lambda j: (0, 0)),     # queries: resident
            pl.BlockSpec((bn, d), lambda j: (j, 0)),     # history block
            pl.BlockSpec((4, bn), lambda j: (0, j)),     # validity words
            pl.BlockSpec((nq, 4), lambda j: (0, 0)),     # windows: resident
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
    )(q, corpus, valid, win)
