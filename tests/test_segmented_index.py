"""Segmented streaming index: memtable/seal/tombstone semantics,
deterministic compaction, manifest crash recovery (fault-injected
mid-seal and mid-compaction), and the IVF recall regression bar
(DESIGN.md §7)."""
import glob
import os

import numpy as np
import pytest

from repro.core.store import LiveVectorLake
from repro.core.types import ChunkRecord
from repro.index.compaction import SizeTieredCompactor, _tier
from repro.index.lsm import CompactionInterrupted, SegmentedIndex
from repro.index.manifest import Manifest

DIM = 32


def _vec(i, dim=DIM):
    rng = np.random.default_rng(i)
    v = rng.standard_normal(dim).astype(np.float32)
    return v / np.linalg.norm(v)


def _rec(pos, doc="d", seed=None, text=None):
    return ChunkRecord(chunk_id=f"h{doc}{pos}s{seed}", doc_id=doc,
                       position=pos, valid_from=pos + 1,
                       text=text or f"t{pos}",
                       embedding=_vec(seed if seed is not None else pos))


class TestMemtableSeal:
    def test_seal_moves_rows_to_segment(self):
        idx = SegmentedIndex(DIM, mem_capacity=8, ivf_min_rows=10**9)
        idx.insert([_rec(i) for i in range(20)])
        assert len(idx) == 20
        assert len(idx.segments) >= 1
        assert sum(len(s) for s in idx.segments.values()) + len(idx.mem) == 20
        # every key resolves and searches still find the sealed rows
        for pos in (0, 7, 13, 19):
            res = idx.search(_vec(pos), k=1)[0]
            assert res and res[0].position == pos

    def test_search_matches_flat_exact_scan(self):
        idx = SegmentedIndex(DIM, mem_capacity=16, ivf_min_rows=10**9)
        recs = [_rec(i) for i in range(100)]
        idx.insert(recs)
        mat = np.stack([r.embedding for r in recs])
        q = _vec(1234)
        exact = np.argsort(-(mat @ q))[:5]
        got = [r.position for r in idx.search(q, k=5)[0]]
        assert got == [recs[j].position for j in exact]

    def test_overwrite_in_memtable_is_in_place(self):
        idx = SegmentedIndex(DIM, mem_capacity=8)
        idx.insert([_rec(0, seed=1)])
        idx.insert([_rec(0, seed=2, text="new")])
        assert len(idx) == 1 and len(idx.mem) == 1
        assert idx.search(_vec(2), k=1)[0][0].text == "new"


class TestTombstones:
    def test_delete_across_seal_never_returned(self):
        idx = SegmentedIndex(DIM, mem_capacity=4, ivf_min_rows=10**9)
        idx.insert([_rec(i) for i in range(12)])
        assert idx.delete([("d", 2)]) == 1
        for r in idx.search(_vec(2), k=12)[0]:
            assert r.position != 2
        assert len(idx) == 11

    def test_update_shadows_segment_row(self):
        idx = SegmentedIndex(DIM, mem_capacity=4, ivf_min_rows=10**9)
        idx.insert([_rec(i) for i in range(8)])       # pos 0 sealed
        idx.insert([_rec(0, seed=777, text="newest")])
        res = idx.search(_vec(777), k=8)[0]
        hits = [r for r in res if r.position == 0]
        assert len(hits) == 1 and hits[0].text == "newest"

    def test_delete_alone_triggers_tombstone_purge(self):
        """A delete-heavy stream with NO subsequent inserts must still
        reclaim majority-dead segments."""
        idx = SegmentedIndex(DIM, mem_capacity=64, ivf_min_rows=10**9)
        idx.compactor.purge_min_rows = 32
        idx.insert([_rec(i) for i in range(64)])
        idx.seal()
        assert idx.delete([("d", i) for i in range(40)]) == 40
        assert idx.cstats.tombstones_purged >= 40
        assert sum(len(s) - s.n_alive for s in idx.segments.values()) == 0
        assert len(idx) == 24


class TestCompactionPolicy:
    def test_tiering(self):
        assert _tier(0) == 0 and _tier(3) == 0
        assert _tier(4) == 1 and _tier(15) == 1
        assert _tier(16) == 2 and _tier(4096) == 6
        # tier base follows fanout: merging `fanout` same-tier segments
        # must always land in a strictly higher tier
        for fanout in (2, 3, 4):
            for n in (1, 2, 5, 9, 64):
                assert _tier(fanout * n, fanout) > _tier(n, fanout)

    def test_size_tiered_merge_is_deterministic(self):
        a = SegmentedIndex(DIM, mem_capacity=4, ivf_min_rows=10**9)
        b = SegmentedIndex(DIM, mem_capacity=4, ivf_min_rows=10**9)
        recs = [_rec(i) for i in range(50)]
        a.insert(recs)
        for r in recs:
            b.insert([r])                      # different batching
        layout = lambda ix: sorted((len(s), s.n_alive)
                                   for s in ix.segments.values())
        assert layout(a) == layout(b)
        assert sorted(a._by_key) == sorted(b._by_key)

    def test_fanout_merge_triggers(self):
        idx = SegmentedIndex(DIM, mem_capacity=4, ivf_min_rows=10**9,
                             fanout=4)
        # seal is lazy (fires on the insert AFTER the memtable fills), so
        # 20 rows -> 4 sealed segments of 4 -> one fanout merge
        idx.insert([_rec(i) for i in range(20)])
        assert idx.cstats.merges >= 1
        assert idx.cstats.write_amplification > 1.0
        comp = SizeTieredCompactor(fanout=4)
        assert comp.pick(list(idx.segments.values())) == []


class TestRecallRegression:
    def test_ivf_recall_at_k_10k_corpus(self):
        """recall@10 >= 0.95 at nprobe=8 on a clustered 10k corpus while
        scanning sub-linearly — the DESIGN.md §7 acceptance bar."""
        rng = np.random.default_rng(0)
        n, d = 10_000, 64
        centers = rng.standard_normal((48, d)).astype(np.float32)
        corpus = centers[rng.integers(0, 48, n)] + \
            0.3 * rng.standard_normal((n, d)).astype(np.float32)
        corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
        idx = SegmentedIndex(d, mem_capacity=2048, nprobe=8,
                             ivf_min_rows=1024)
        idx.insert([ChunkRecord(chunk_id=f"c{i}", doc_id="v", position=i,
                                valid_from=1, text="", embedding=corpus[i])
                    for i in range(n)])
        assert any(s.ivf is not None for s in idx.segments.values())
        q = corpus[rng.choice(n, 25)] + \
            0.05 * rng.standard_normal((25, d)).astype(np.float32)
        q /= np.linalg.norm(q, axis=1, keepdims=True)
        exact = np.argsort(-(q @ corpus.T), axis=1)[:, :10]
        res = idx.search(q, k=10)
        hits = sum(len({r.position for r in res[i]} & set(exact[i]))
                   for i in range(25))
        assert hits / 250 >= 0.95
        assert idx.stats()["avg_fraction_scanned"] < 0.5

    def test_merged_segment_probes_an_eighth_of_its_partitions(self):
        """A 16k-row segment has 128 partitions: nprobe=8 alone would
        scan 1/16 of it. The segment probes at least 1/8, so a merged
        segment keeps the recall of a memtable-sized one."""
        from repro.index.segment import Segment
        rng = np.random.default_rng(4)
        n, d = 16_384, 16
        emb = rng.standard_normal((n, d)).astype(np.float32)
        emb /= np.linalg.norm(emb, axis=1, keepdims=True)
        seg = Segment("s", emb, np.ones(n, np.int64), np.arange(n),
                      [f"c{i}" for i in range(n)], ["d"] * n, [""] * n)
        assert seg.ivf.centroids.shape[0] == 128
        q = emb[:16]
        _, _, scanned = seg.search(q, k=10, nprobe=8)
        assert scanned >= n // 8 * 0.5       # partitions are uneven
        _, _, scanned_16 = seg.search(q, k=10, nprobe=16)
        assert scanned == scanned_16

    def test_ivf_state_roundtrips_without_kmeans(self, tmp_path, monkeypatch):
        """Segment save/load must reuse the persisted partitioning: same
        search results, and IVFIndex.build (k-means) never runs on load."""
        from repro.core import ivf as ivf_mod
        from repro.index.segment import Segment
        rng = np.random.default_rng(3)
        emb = rng.standard_normal((2048, 16)).astype(np.float32)
        emb /= np.linalg.norm(emb, axis=1, keepdims=True)
        seg = Segment("00000001", emb, np.ones(2048, np.int64),
                      np.arange(2048), [f"c{i}" for i in range(2048)],
                      ["d"] * 2048, [""] * 2048, ivf_min_rows=1024)
        assert seg.ivf is not None
        seg.save(str(tmp_path))
        monkeypatch.setattr(
            ivf_mod.IVFIndex, "build",
            lambda self, v: pytest.fail("k-means re-ran on load"))
        seg2 = Segment.load(str(tmp_path), seg.filename(),
                            ivf_min_rows=1024)
        assert seg2.ivf is not None
        q = emb[:4]
        s1, i1, _ = seg.search(q, k=5)
        s2, i2, _ = seg2.search(q, k=5)
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_allclose(s1, s2, rtol=1e-6)


DOC = "\n\n".join(f"paragraph {{i}} number {j} words" for j in range(3))


def _fill(store, lo, hi, tag="d"):
    for i in range(lo, hi):
        store.ingest(f"{tag}{i}", DOC.format(i=i).replace("{i}", str(i)),
                     ts=(i + 1) * 1_000_000)


def _cold_keys(store):
    snap = store.cold.snapshot()
    return sorted((snap.doc_ids[i], int(snap.position[i]))
                  for i in range(len(snap)))


class TestCrashRecovery:
    def test_manifest_restore_skips_monolithic_insert(self, tmp_path):
        root = str(tmp_path / "lvl")
        store = LiveVectorLake(root, dim=DIM, hot_capacity=4)
        _fill(store, 0, 8)
        before = sorted(store.hot._by_key)
        store2 = LiveVectorLake(root, dim=DIM, hot_capacity=4)
        assert sorted(store2.hot._by_key) == before
        rep = store2.recover()
        # the bulk came back from segments, not a monolithic re-insert
        assert rep["hot_restored_from_segments"] > 0
        assert rep["hot_delta_inserted"] < rep["hot_rebuilt"]

    @pytest.mark.parametrize("fail_at", ["seal:before_manifest",
                                         "seal:after_manifest",
                                         "merge:before_manifest",
                                         "merge:after_manifest"])
    def test_fault_injected_seal_and_compaction(self, tmp_path, fail_at):
        root = str(tmp_path / f"lvl-{fail_at.replace(':', '_')}")
        store = LiveVectorLake(root, dim=DIM, hot_capacity=4)
        _fill(store, 0, 6)
        store.hot.index.fail_at = fail_at
        with pytest.raises(CompactionInterrupted):
            _fill(store, 6, 30, tag="e")
        # restart: manifest + WAL reconcile must yield exactly the cold
        # tier's active set, no pending transactions, queries consistent
        store2 = LiveVectorLake(root, dim=DIM, hot_capacity=4)
        assert not store2.wal.pending()
        assert sorted(store2.hot._by_key) == _cold_keys(store2)
        res = store2.query("paragraph 3 number 1 words", k=3)
        assert res and res[0].tier == "hot"

    def test_orphan_segments_cleaned_on_recover(self, tmp_path):
        root = str(tmp_path / "lvl")
        store = LiveVectorLake(root, dim=DIM, hot_capacity=4)
        _fill(store, 0, 8)
        hot_dir = os.path.join(root, "hot_index")
        orphan = os.path.join(hot_dir, "seg-99999999.npz")
        with open(orphan, "wb") as f:
            f.write(b"leftover from a crashed compaction")
        LiveVectorLake(root, dim=DIM, hot_capacity=4)
        assert not os.path.exists(orphan)

    def test_corrupt_segment_falls_back_to_full_rebuild(self, tmp_path):
        root = str(tmp_path / "lvl")
        store = LiveVectorLake(root, dim=DIM, hot_capacity=4)
        _fill(store, 0, 8)
        seg = glob.glob(os.path.join(root, "hot_index", "seg-*.npz"))[0]
        with open(seg, "r+b") as f:
            f.seek(-1, 2)
            last = f.read(1)
            f.seek(-1, 2)
            f.write(bytes([last[0] ^ 0xFF]))
        store2 = LiveVectorLake(root, dim=DIM, hot_capacity=4)
        assert sorted(store2.hot._by_key) == _cold_keys(store2)

    def test_manifest_atomic_commit_generation(self, tmp_path):
        m = Manifest(str(tmp_path / "idx"))
        assert m.load() is None
        g1 = m.commit([{"name": "seg-1.npz", "checksum": "x", "rows": 4}],
                      seq=1)
        g2 = m.commit([], seq=1)
        assert (g1, g2) == (1, 2)
        assert m.load()["generation"] == 2
        assert m.load()["segments"] == []


class TestHotTierClearReset:
    def test_clear_is_explicit_reset_not_reinit(self, tmp_path):
        """clear() must reset the engine through its own code path — the
        segmented index object survives (no silent identity swap) and the
        persisted manifest is emptied too."""
        store = LiveVectorLake(str(tmp_path / "lvl"), dim=DIM,
                               hot_capacity=4)
        _fill(store, 0, 6)
        engine = store.hot.index
        assert len(store.hot) > 0 and engine.segments
        store.hot.clear()
        assert store.hot.index is engine           # same engine object
        assert len(store.hot) == 0 and not engine.segments
        assert store.hot.capacity == 4
        m = engine.manifest.load()
        assert m is not None and m["segments"] == []
        assert not glob.glob(os.path.join(str(tmp_path / "lvl"),
                                          "hot_index", "seg-*.npz"))
