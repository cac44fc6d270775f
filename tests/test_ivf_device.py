"""The IVF member scan against device-resident segment rows (DESIGN.md
§11.5), the one IVF scan on every platform: parity with a plain NumPy
scan at and past the shape buckets' floors, masking, padding, one
dispatch per batch, residency and its release, and shape buckets shared
across segment sizes."""
import numpy as np
import pytest

from repro import obs
from repro.core.ivf import IVFIndex
from repro.core.types import ChunkRecord
from repro.index.lsm import SegmentedIndex
from repro.index.quant import data_scale, quantize_rows
from repro.index.segment import Segment
from repro.kernels.ivf_scan import ROW_FLOOR, _ivf_scan_jit, bucket

FORMATS = pytest.mark.parametrize("quantized", [False, True],
                                  ids=["fp32", "int8"])
D = 48


def _unit(shape, seed):
    x = np.random.default_rng(seed).standard_normal(shape)
    x = x.astype(np.float32)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def _queries(emb, n, seed):
    """Near-duplicates of stored rows: a clear top-k, as RAG queries."""
    rng = np.random.default_rng(seed)
    q = emb[rng.choice(len(emb), n)] + 0.05 * rng.standard_normal(
        (n, emb.shape[1])).astype(np.float32)
    return q / np.linalg.norm(q, axis=1, keepdims=True)


def _segment(n, quantized, seed=0, tenant_ids=None):
    emb = _unit((n, D), seed)
    seg = Segment("s", emb, np.arange(n), np.arange(n),
                  [f"c{i}" for i in range(n)], ["d"] * n, [""] * n,
                  ivf_min_rows=256, seed=seed, quantized=quantized,
                  f32_fetch=(lambda r, e=emb: e[r]) if quantized else None,
                  tenant_ids=tenant_ids)
    return seg, emb


def _records(emb, start=0, tenant_id=0):
    return [ChunkRecord(chunk_id=f"c{start + i}", doc_id="d",
                        position=start + i, valid_from=1, text=f"t{i}",
                        embedding=emb[i], tenant_id=tenant_id)
            for i in range(len(emb))]


def _numpy_ivf(q, emb, centroids, alive, k, nprobe, scale=None,
               rescore_factor=4):
    """The IVF search in plain NumPy: each query's ``nprobe`` nearest
    centroids, then the top-k among the alive rows of those partitions,
    ties to the lower row id. With an int8 ``scale`` the rows are
    quantized, scored against the scale-folded fp32 query, and the top
    ``rescore_factor * k`` of them rescored against the fp32 rows.
    Returns (scores, ids, fraction of rows x queries scanned)."""
    nq, n = len(q), len(emb)
    assign = np.argmax(emb @ centroids.T, axis=1)
    probe = np.argsort(-(q @ centroids.T), axis=1, kind="stable")
    rows = emb if scale is None else quantize_rows(emb, scale)
    kp = k if scale is None else min(rescore_factor * k, n)
    out_s = np.full((nq, k), -np.inf, np.float32)
    out_i = np.full((nq, k), -1, np.int64)
    scanned = 0
    for qi in range(nq):
        cand = np.nonzero(alive & np.isin(assign, probe[qi, :nprobe]))[0]
        scanned += len(cand)
        qs = q[qi] if scale is None else q[qi] * scale
        s = rows[cand].astype(np.float32) @ qs
        top = cand[np.lexsort((cand, -s))[:kp]]
        exact = emb[top] @ q[qi]
        order = np.argsort(-exact, kind="stable")[:k]
        out_s[qi, :len(order)] = exact[order]
        out_i[qi, :len(order)] = top[order]
    return out_s, out_i, scanned / (nq * n)


def _assert_same(got, want):
    (gs, gi), (ws, wi) = got, want
    np.testing.assert_array_equal(gi, wi)
    np.testing.assert_allclose(gs, ws, rtol=0, atol=1e-6)


@FORMATS
def test_matches_numpy_reference(quantized):
    seg, emb = _segment(3000, quantized)
    for r in range(0, 3000, 7):
        seg.kill(r)                                   # tombstones
    q = _queries(emb, 24, 1)
    s, i, scanned = seg.search(q, 10, nprobe=8)
    ws, wi, frac = _numpy_ivf(q, emb, seg.ivf.centroids, seg.alive, 10,
                              seg.ivf_nprobe(8),
                              seg.scale if quantized else None)
    _assert_same((s, i), (ws, wi))
    assert scanned == int(round(frac * len(seg)))     # same member rows


@FORMATS
@pytest.mark.parametrize("rows,parts,nq", [
    (1023, 7, 1), (1024, 8, 2), (1025, 9, 3), (2049, 17, 33)],
    ids=["below", "at", "past", "far-past"])
def test_matches_numpy_reference_at_bucket_edges(quantized, rows, parts,
                                                 nq):
    """Rows, partitions and queries at their bucket floors and just past
    them: the padded rows, partitions and queries change no answer."""
    emb = _unit((rows, D), rows)
    ix = IVFIndex(n_centroids=parts, seed=rows)
    ix.build(emb)
    scale = None
    if quantized:
        scale = data_scale(emb)
        ix.attach_quantized(quantize_rows(emb, scale), scale,
                            lambda r: emb[r])
        ix.release_f32()
    res = ix.resident()
    assert res.n_pad == bucket(rows, ROW_FLOOR)
    alive = np.ones(rows, bool)
    alive[::5] = False
    q = _queries(emb, nq, rows + 1)
    s, i, stats = ix.search(q, k=10, nprobe=3, mask=alive)
    ws, wi, frac = _numpy_ivf(q, emb, ix.centroids, alive, 10, 3, scale)
    _assert_same((s, i), (ws, wi))
    assert stats.fraction_scanned == pytest.approx(frac, rel=0, abs=1e-12)


@FORMATS
def test_masked_rows_never_returned(quantized):
    n = 2000
    tids = np.arange(n, dtype=np.int32) % 3
    seg, emb = _segment(n, quantized, seed=2, tenant_ids=tids)
    for r in range(0, n, 2):
        seg.kill(r)
    visible = tids == 1                               # one tenant's rows
    q = _queries(emb, 16, 3)
    s, i, _ = seg.search(q, 10, nprobe=8, visible=visible)
    ok = seg.alive & visible
    assert (i >= 0).any()
    assert ok[i[i >= 0]].all()
    assert np.isneginf(s[i < 0]).all()
    # only three rows left: the rest of every answer is empty (idx -1)
    keep = np.nonzero(ok)[0][:3]
    only = np.zeros(n, bool)
    only[keep] = True
    s, i, _ = seg.search(q, 10, nprobe=seg.ivf.centroids.shape[0],
                         visible=only)
    assert (np.sort(i, axis=1)[:, -3:] == np.sort(keep)).all()
    assert (i[:, 3:] == -1).all() and np.isneginf(s[:, 3:]).all()


@FORMATS
def test_padded_rows_never_returned_nor_counted(quantized):
    n = 1100                           # 948 padded rows in its bucket
    seg, emb = _segment(n, quantized, seed=4)
    assert bucket(n, 1024) == 2048
    q = _queries(emb, 4, 5)
    every = seg.ivf.centroids.shape[0]
    _, ids, stats = seg.ivf.search(q, k=200, nprobe=every)
    assert ((ids >= 0) & (ids < n)).all()             # k < n: all filled
    # every row probed: each query scans all n rows, not the bucket
    assert stats.fraction_scanned == 1.0


@FORMATS
def test_one_dispatch_and_one_wait_per_batch(quantized):
    idx = SegmentedIndex(D, mem_capacity=600, ivf_min_rows=500, fanout=10,
                         quantized=quantized)
    emb = _unit((2500, D), 6)
    idx.insert(_records(emb))
    n_ivf = idx.stats()["partitioned_segments"]
    assert n_ivf >= 3
    with obs.trace("t") as root:
        idx.search(_queries(emb, 32, 7), k=10)
    (ivf,) = root.find_prefix("ivf_scan:")
    assert ivf.name == "ivf_scan:device"
    assert ivf.counters["ivf_device_segments"] == n_ivf
    assert len(ivf.find("kernel:ivf_scan")) == 1
    assert len(ivf.find("device_wait")) == 1
    assert len(ivf.find("rescore")) == (n_ivf if quantized else 0)


@FORMATS
def test_residency_uploaded_once(quantized):
    idx = SegmentedIndex(D, mem_capacity=600, ivf_min_rows=500,
                         quantized=quantized)
    emb = _unit((1300, D), 8)
    idx.insert(_records(emb))
    q = _queries(emb, 8, 9)
    bytes_per_batch = []
    for _ in range(3):
        with obs.trace("t") as root:
            idx.search(q, k=5)
        (ivf,) = root.find_prefix("ivf_scan:")
        bytes_per_batch.append(sum(s.counters.get("h2d_bytes", 0)
                                   for s in ivf.find("h2d")))
    seg = next(s for s in idx.segments.values() if s.ivf is not None)
    rows = bucket(len(seg), 1024)
    want = rows * D * (1 if quantized else 4) + rows * 4 + \
        (D * 4 if quantized else 0)
    assert bytes_per_batch == [want * 2, 0, 0]        # two IVF segments


@FORMATS
def test_retired_segment_frees_its_device_copy(quantized):
    idx = SegmentedIndex(D, mem_capacity=600, ivf_min_rows=500, fanout=10,
                         quantized=quantized)
    emb = _unit((1900, D), 10)
    idx.insert(_records(emb))
    q = _queries(emb, 8, 11)
    before = idx.search(q, k=5)
    old = [s for s in idx.segments.values() if s.ivf is not None]
    assert len(old) >= 3
    arrays = [s.ivf._dev.rows for s in old]
    idx.compactor.fanout = 2
    assert idx.maybe_compact() >= 1
    gone = [s for s in old if s.seg_id not in idx.segments]
    assert gone
    for s in gone:
        assert s.ivf._dev is None
    assert all(a.is_deleted() for s, a in zip(old, arrays) if s in gone)
    # the merged segment is partitioned anew and uploaded at its first
    # search; the answers still hold each query's own stored row
    after = idx.search(q, k=5)
    assert all(s.ivf._dev is not None for s in idx.segments.values()
               if s.ivf is not None)
    assert [x[0].chunk_id for x in after] == [x[0].chunk_id for x in before]


@FORMATS
def test_segment_sizes_in_one_bucket_share_a_program(quantized):
    q = _queries(_unit((50, D), 12), 5, 13)
    seen = []
    for n in (1100, 1500, 2000):                      # all bucket 2048
        seg, _ = _segment(n, quantized, seed=n)
        assert bucket(len(seg), 1024) == 2048
        seg.search(q, 10, nprobe=8)
        seen.append(_ivf_scan_jit._cache_size())
    assert seen[1] == seen[2] == seen[0]


def test_auto_mode_runs_the_device_program_off_the_chip(monkeypatch):
    """Under the default mode on a CPU host the hot index scans its IVF
    segments with the device program, as on the chip: one
    ``ivf_scan:device`` span and one ``kernel:ivf_scan``, and no
    per-segment scan."""
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    idx = SegmentedIndex(D, mem_capacity=600, ivf_min_rows=500)
    emb = _unit((700, D), 14)
    idx.insert(_records(emb))
    assert idx.stats()["partitioned_segments"] >= 1
    with obs.trace("t") as root:
        idx.search(_queries(emb, 4, 15), k=5)
    names = [s.name for s in root.find_prefix("ivf_scan:")]
    assert names == ["ivf_scan:device"]
    assert len(root.find("kernel:ivf_scan")) == 1
