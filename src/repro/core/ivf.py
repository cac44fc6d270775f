"""IVF (inverted-file) index: the sub-linear hot-tier search path for
larger-than-exact-scan corpora (DESIGN.md §2 — ScaNN/TPU-KNN style).

k-means centroids partition the corpus; a query scores all centroids
(tiny matmul), visits the ``nprobe`` nearest partitions, and runs the
exact top-k only inside them. Recall is controlled by nprobe
(nprobe == n_centroids -> exact).

Where each step runs (DESIGN.md §11.5): the segment's scan rows stay on
the device (``resident``) and ``search_resident`` scores a batch against
several segments in one dispatch of kernels/ivf_scan — one matmul per
segment, masked to the probed partitions, bucketed static shapes — on a
TPU and on any other platform alike. Centroid routing (one (Q, d)·(d, C)
product and a stable argsort) and the int8 path's exact fp32 rescore
stay on the host.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .types import pad_queries


@dataclasses.dataclass
class IVFStats:
    n_centroids: int
    n_vectors: int
    fraction_scanned: float


class IVFIndex:
    def __init__(self, n_centroids: int = 64, n_iters: int = 10,
                 seed: int = 0):
        self.n_centroids = n_centroids
        self.n_iters = n_iters
        self.seed = seed
        self.centroids: np.ndarray | None = None     # (C, d)
        self._vectors: np.ndarray | None = None
        self._assign: np.ndarray | None = None       # partition per row
        self._vq8: np.ndarray | None = None          # quantized scan copy
        self._vscale: np.ndarray | None = None
        self._f32_fetch = None
        self.rescore_factor = 4
        self._dev = None                             # device scan copy

    # -- build ----------------------------------------------------------
    def build(self, vectors: np.ndarray) -> None:
        """Lloyd k-means (deterministic seed), then invert."""
        v = np.asarray(vectors, np.float32)
        n = v.shape[0]
        c = min(self.n_centroids, n)
        rng = np.random.default_rng(self.seed)
        centroids = v[rng.choice(n, c, replace=False)].copy()
        for _ in range(self.n_iters):
            assign = np.argmax(v @ centroids.T, axis=1)
            for j in range(c):
                members = v[assign == j]
                if len(members):
                    centroids[j] = members.mean(0)
            norms = np.linalg.norm(centroids, axis=1, keepdims=True)
            centroids = centroids / np.maximum(norms, 1e-9)
        assign = np.argmax(v @ centroids.T, axis=1)
        self.centroids = centroids
        self._vectors = v
        self._assign = assign

    def restore(self, centroids: np.ndarray, vectors: np.ndarray | None,
                assign: np.ndarray) -> None:
        """Rebuild from persisted state (centroids + per-row partition
        assignment) without re-running k-means — segments are immutable,
        so their partitioning is serialized once at seal time.
        ``vectors`` may be None for a quantized segment whose fp32 rows
        stayed on disk: ``attach_quantized`` supplies the scan copy."""
        self.centroids = np.asarray(centroids, np.float32)
        self._vectors = (None if vectors is None
                         else np.asarray(vectors, np.float32))
        self._assign = np.asarray(assign, np.int64)

    # -- quantized scan (DESIGN.md §11) ---------------------------------
    def attach_quantized(self, q8: np.ndarray, scale: np.ndarray,
                         f32_fetch, rescore_factor: int = 4) -> None:
        """Switch the member scan to int8 asymmetric scoring: member rows
        are read at 1 byte/element and scored against the scale-folded
        query; the over-fetched pool (rescore_factor * k) is exactly
        rescored in fp32 through ``f32_fetch`` (the segment's winners-row
        cache), so returned scores remain fp32-exact."""
        self._vq8 = np.asarray(q8, np.int8)
        self._vscale = np.asarray(scale, np.float32)
        self._f32_fetch = f32_fetch
        self.rescore_factor = int(rescore_factor)

    def release_f32(self) -> None:
        """Drop the resident fp32 rows (quantized path armed)."""
        assert getattr(self, "_vq8", None) is not None
        self._vectors = None

    @property
    def quantized(self) -> bool:
        return self._vq8 is not None

    @property
    def n_rows(self) -> int:
        return len(self._vq8 if self.quantized else self._vectors)

    # -- device residency (DESIGN.md §11) ---------------------------------
    def resident(self):
        """The member scan's rows on the device, uploaded on first use:
        the int8 codes and scale, or the fp32 rows, plus the partition
        assignment. Segments are immutable, so this never changes."""
        if self._dev is None:
            from ..kernels.ivf_scan import upload
            q8 = self.quantized
            self._dev = upload(self._vq8 if q8 else self._vectors,
                               self._assign, self.centroids.shape[0],
                               self._vscale if q8 else None)
        return self._dev

    def free_resident(self) -> None:
        """Release the device copy (the segment is retired)."""
        if self._dev is not None:
            self._dev.free()
            self._dev = None

    def members_scanned(self, probe: np.ndarray,
                        mask: np.ndarray | None) -> int:
        """Member rows a batch scans: for each query, the unmasked rows
        of its probed partitions (padded rows never count)."""
        a = self._assign if mask is None else self._assign[mask]
        counts = np.bincount(a, minlength=self.centroids.shape[0])
        return int(counts[probe].sum())

    # -- search -----------------------------------------------------------
    def route(self, queries: np.ndarray, nprobe: int) -> np.ndarray:
        """(Q, nprobe) partition ids per query: one (Q, C) centroid
        product and a stable argsort, on the host."""
        qp, nq = pad_queries(queries)
        nprobe = min(nprobe, self.centroids.shape[0])
        c_scores = qp @ self.centroids.T                  # (Q, C): routing
        return np.argsort(-c_scores[:nq], axis=1,
                          kind="stable")[:, :nprobe]

    def search(self, queries: np.ndarray, k: int = 5, nprobe: int = 8,
               mask: np.ndarray | None = None
               ) -> tuple[np.ndarray, np.ndarray, IVFStats]:
        """Batched search of this one index: ``search_resident`` over
        it. Returns (scores (Q, k), row ids (Q, k), stats).

        ``mask`` (N,) bool, optional: rows with mask=False (tombstoned
        slots in a sealed segment) are masked before ranking, so they
        can never rank — the segmented index's deletion-vector path.
        """
        assert self.centroids is not None, "build() first"
        return search_resident([self], queries, [k], [nprobe], [mask])[0]

    def recall_at_k(self, queries: np.ndarray, k: int = 10,
                    nprobe: int = 8) -> float:
        """Measured recall vs the exact scan (validation/benchmarks)."""
        q = np.atleast_2d(np.asarray(queries, np.float32))
        _, approx, _ = self.search(q, k=k, nprobe=nprobe)
        vecs = self._vectors
        if vecs is None:                       # quantized, fp32 on disk
            vecs = self._f32_fetch(np.arange(len(self._vq8)))
        exact_scores = q @ vecs.T
        exact = np.argsort(-exact_scores, axis=1)[:, :k]
        hits = sum(len(set(approx[i]) & set(exact[i]))
                   for i in range(q.shape[0]))
        return hits / (q.shape[0] * k)


def search_resident(indexes: list[IVFIndex], queries: np.ndarray,
                    ks: list[int], nprobes: list[int],
                    masks: list[np.ndarray | None]
                    ) -> list[tuple[np.ndarray, np.ndarray, IVFStats]]:
    """Member scans of several IVF indexes against their device-resident
    rows in ONE dispatch and ONE wait (DESIGN.md §11). Per index: the
    host routes the batch (``route``), the device keeps the top ``ks[j]``
    of the probed, unmasked rows — or, for int8 rows, the rescore pool of
    ``pool_k`` rows, scored against the scale-folded fp32 query — and the
    host rescores that pool in exact fp32 (``rescore_topk``). Returns
    ``search``'s (scores, ids, stats) per index."""
    from ..index.quant import pool_k, rescore_topk
    from ..kernels.common import to_host
    from ..kernels.ivf_scan import ivf_scan, unpack
    q = np.atleast_2d(np.asarray(queries, np.float32))
    nq = q.shape[0]
    probes = [ix.route(q, npb) for ix, npb in zip(indexes, nprobes)]
    residents = [ix.resident() for ix in indexes]
    scan_ks = [min(pool_k(k, ix.n_rows, ix.rescore_factor)
                   if ix.quantized else k, r.n_pad)
               for ix, k, r in zip(indexes, ks, residents)]
    (res,) = to_host(ivf_scan(q, residents, probes, masks, scan_ks))
    s_all, i_all = unpack(res)
    out = []
    for j, ix in enumerate(indexes):
        k, mask = ks[j], masks[j]
        scanned = ix.members_scanned(probes[j], mask)
        out_s = np.full((nq, k), -np.inf, np.float32)
        out_i = np.full((nq, k), -1, np.int64)
        ids = i_all[j, :nq, :scan_ks[j]].astype(np.int64)
        if not ix.quantized:
            s = s_all[j, :nq, :scan_ks[j]]
        elif scanned:
            s, ids = rescore_topk(q, ids, ix._f32_fetch, k)
        else:                      # nothing to rescore: every slot empty
            s, ids = out_s[:, :0], out_i[:, :0]
        out_s[:, :s.shape[1]] = s
        out_i[:, :ids.shape[1]] = ids
        out.append((out_s, out_i,
                    IVFStats(ix.centroids.shape[0], ix.n_rows,
                             scanned / max(nq * ix.n_rows, 1))))
    return out
