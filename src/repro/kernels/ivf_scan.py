"""IVF member scan on the device (DESIGN.md §11).

A sealed IVF segment never changes its rows, so its scan rows live on
the device: the int8 codes and their per-dimension scale (quantized
segments) or the fp32 rows, plus the row -> partition assignment as an
int32 row. They are uploaded once (``upload``, under an ``h2d`` span
counting ``h2d_bytes``) and freed when compaction retires the segment.
Per batch the host hands over only the queries, each segment's (Q, C)
probe mask and its alive-and-visible row mask; ``ivf_scan`` covers every
IVF segment of a catalog in ONE dispatch.

Scores are query . row with f32 products at ``Precision.HIGHEST``; int8
rows are scored against the scale-folded fp32 query, as
``topk_search_q8`` does. A row is a candidate for query q iff its mask
bit is set and ``probe[q, assign[row]]`` holds. Top-k per segment with
ties to the lower row id (``lax.top_k``); an empty slot is idx -1, so a
rescore can never bring back a masked row.

Shapes are bucketed so a new segment size builds no new program: rows
to a power of two >= 1,024, partitions to a power of two >= 8, queries
to a power of two >= 2. Padded rows and queries are masked out.

The program is plain XLA and has no kernel mode: it is the one IVF
member scan, on a TPU and off it alike (DESIGN.md §11.5).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .common import to_device

ROW_FLOOR = 1024
PART_FLOOR = 8
QUERY_FLOOR = 2


def bucket(n: int, floor: int) -> int:
    """The power of two >= max(n, floor)."""
    return max(floor, 1 << max(int(n) - 1, 0).bit_length())


@dataclasses.dataclass
class Resident:
    """One segment's scan rows on the device, padded to a row bucket."""
    rows: jax.Array                 # (n_pad, d) float32 | int8
    assign: jax.Array               # (n_pad,) int32, padded rows -> 0
    scale: Optional[jax.Array]      # (d,) float32 for int8 rows
    n: int                          # real rows
    n_parts: int                    # partitions (C)

    @property
    def n_pad(self) -> int:
        return int(self.rows.shape[0])

    def free(self) -> None:
        for a in (self.rows, self.assign, self.scale):
            if a is not None:
                a.delete()


def upload(rows: np.ndarray, assign: np.ndarray, n_parts: int,
           scale: Optional[np.ndarray] = None) -> Resident:
    """Copy a segment's scan rows to the device once, padded to their
    row bucket, under an ``h2d`` span."""
    n, d = rows.shape
    n_pad = bucket(n, ROW_FLOOR)
    rows_p = np.zeros((n_pad, d), rows.dtype)
    rows_p[:n] = rows
    assign_p = np.zeros(n_pad, np.int32)
    assign_p[:n] = assign
    args = [(rows_p, rows.dtype), (assign_p, np.int32)]
    if scale is not None:
        args.append((scale, np.float32))
    dev = to_device(*args)
    return Resident(dev[0], dev[1], dev[2] if scale is not None else None,
                    int(n), int(n_parts))


@functools.partial(jax.jit, static_argnames=("ks",))
def _ivf_scan_jit(q, rows, assign, scales, probe, mask, ks):
    """q (Qb, d) f32; per segment j: rows[j] (Nj, d), assign[j] (Nj,),
    scales[j] (d,) or None; probe (S, Qb, Cb) bool; mask (sum Nj,) bool.
    Returns one (2, S, Qb, max ks) int32 array, so the host copies back
    once: [0] the float32 scores' bits, [1] the ids (-inf / -1 past a
    segment's own k or its candidates)."""
    kmax = max(ks)
    cb = probe.shape[2]
    out_s, out_i = [], []
    off = 0
    for j, kk in enumerate(ks):
        r, a, sc = rows[j], assign[j], scales[j]
        n = r.shape[0]
        qs = q if sc is None else q * sc[None, :]
        s = jnp.dot(qs, r.astype(jnp.float32).T,
                    precision=jax.lax.Precision.HIGHEST)
        # probe[q, assign[row]] as a 0/1 product with the one-hot of the
        # assignment (exact at any precision: one partition per row)
        onehot = (a[None, :] == jax.lax.broadcasted_iota(
            jnp.int32, (cb, n), 0)).astype(jnp.float32)
        hit = jnp.dot(probe[j].astype(jnp.float32), onehot) > 0.5
        cand = hit & mask[None, off:off + n]
        s = jnp.where(cand, s, -jnp.inf)
        top_s, top_i = jax.lax.top_k(s, kk)
        top_i = jnp.where(jnp.isfinite(top_s), top_i, -1)
        pad = ((0, 0), (0, kmax - kk))
        out_s.append(jnp.pad(top_s, pad, constant_values=-jnp.inf))
        out_i.append(jnp.pad(top_i, pad, constant_values=-1))
        off += n
    bits = jax.lax.bitcast_convert_type(jnp.stack(out_s), jnp.int32)
    return jnp.stack([bits, jnp.stack(out_i)])


def ivf_scan(q: np.ndarray, residents: Sequence[Resident],
             probes: Sequence[np.ndarray],
             masks: Sequence[Optional[np.ndarray]], ks: Sequence[int]):
    """Member scan of several resident segments in one dispatch.

    q: (Q, d) fp32 queries (unscaled); probes[j]: (Q, nprobe) partition
    ids of segment j; masks[j]: (n_j,) bool alive-and-visible rows, or
    None for all; ks[j]: the top-k (or rescore pool) of segment j.
    Returns the program's (2, S, Qb, max ks) device array (``unpack``
    reads it on the host): row q < Q, column c < ks[j] of slice j is
    segment j's answer. The span holds the copy of the queries and masks
    and the enqueue; the caller waits (``common.to_host``)."""
    nq, d = q.shape
    qb = bucket(nq, QUERY_FLOOR)
    cb = bucket(max(r.n_parts for r in residents), PART_FLOOR)
    with obs.span("kernel:ivf_scan"):
        qp = np.zeros((qb, d), np.float32)
        qp[:nq] = q
        pm = np.zeros((len(residents), qb, cb), bool)
        rows_q = np.arange(nq)
        for j, p in enumerate(probes):
            pm[j, np.repeat(rows_q, p.shape[1]), p.ravel()] = True
        mk = np.zeros(sum(r.n_pad for r in residents), bool)
        off = 0
        for r, m in zip(residents, masks):
            mk[off:off + r.n] = True if m is None else m
            off += r.n_pad
        return _ivf_scan_jit(
            qp, tuple(r.rows for r in residents),
            tuple(r.assign for r in residents),
            tuple(r.scale for r in residents), pm, mk,
            ks=tuple(int(k) for k in ks))


def unpack(out: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The host copy of ``ivf_scan``'s result as (float32 scores, int32
    ids), each (S, Qb, max ks)."""
    return out[0].view(np.float32), out[1]
