"""The IVF member scan against device-resident segment rows (DESIGN.md
§11), run on the CPU by forcing its mode: parity with the host scans,
masking, padding, one dispatch per batch, residency and its release,
and shape buckets shared across segment sizes."""
import numpy as np
import pytest

from repro import obs
from repro.core.types import ChunkRecord
from repro.index.lsm import SegmentedIndex
from repro.index.segment import Segment
from repro.kernels.ivf_scan import _ivf_scan_jit, bucket, on_device

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


@pytest.fixture
def device(monkeypatch):
    """The device path forced on the CPU through the kernel mode."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    assert on_device()


def _both(monkeypatch, fn):
    """``fn()`` on the host scans (auto, on the CPU), then forced onto
    the device path."""
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    assert not on_device()
    host = fn()
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    return host, fn()


@FORMATS
def test_device_matches_host(quantized, monkeypatch):
    seg, emb = _segment(3000, quantized)
    for r in range(0, 3000, 7):
        seg.kill(r)                                   # tombstones
    q = _queries(emb, 24, 1)
    (hs, hi, hn), (ds, di, dn) = _both(
        monkeypatch, lambda: seg.search(q, 10, nprobe=8))
    np.testing.assert_array_equal(hi, di)
    assert hn == dn                                   # same member rows
    if quantized:         # both rescored in exact fp32: the same bits
        np.testing.assert_array_equal(hs, ds)
    else:
        np.testing.assert_allclose(hs, ds, rtol=0, atol=1e-6)


@FORMATS
def test_masked_rows_never_returned(quantized, device):
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
def test_padded_rows_never_returned_nor_counted(quantized, monkeypatch):
    n = 1100                           # 948 padded rows in its bucket
    seg, emb = _segment(n, quantized, seed=4)
    assert bucket(n, 1024) == 2048
    q = _queries(emb, 4, 5)
    every = seg.ivf.centroids.shape[0]
    host, dev = _both(monkeypatch, lambda: seg.ivf.search(
        q, k=200, nprobe=every))
    ids = dev[1]
    assert ((ids >= 0) & (ids < n)).all()             # k < n: all filled
    # every row probed: each query scans all n rows, not the bucket
    assert dev[2].fraction_scanned == host[2].fraction_scanned == 1.0


@FORMATS
def test_one_dispatch_and_one_wait_per_batch(quantized, device):
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
def test_residency_uploaded_once(quantized, device):
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
def test_retired_segment_frees_its_device_copy(quantized, device):
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
def test_segment_sizes_in_one_bucket_share_a_program(quantized, device):
    q = _queries(_unit((50, D), 12), 5, 13)
    seen = []
    for n in (1100, 1500, 2000):                      # all bucket 2048
        seg, _ = _segment(n, quantized, seed=n)
        assert bucket(len(seg), 1024) == 2048
        seg.search(q, 10, nprobe=8)
        seen.append(_ivf_scan_jit._cache_size())
    assert seen[1] == seen[2] == seen[0]


def test_host_scans_stay_the_cpu_default(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    assert not on_device()
    idx = SegmentedIndex(D, mem_capacity=600, ivf_min_rows=500)
    emb = _unit((700, D), 14)
    idx.insert(_records(emb))
    with obs.trace("t") as root:
        idx.search(_queries(emb, 4, 15), k=5)
    names = [s.name for s in root.find_prefix("ivf_scan:")]
    assert names and "ivf_scan:device" not in names
    assert not root.find("kernel:ivf_scan")
