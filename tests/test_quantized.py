"""ISSUE 5: the quantized scan fabric — round-trip determinism, recall
gates vs the fp32 oracle for fused/IVF/temporal paths, scan-accounting
consistency, and the fp32 winners-row rescore machinery."""
import os
import tempfile

import numpy as np
import pytest

from repro.core.store import LiveVectorLake
from repro.core.types import ChunkRecord
from repro.data.corpus import generate_corpus
from repro.index.lsm import SegmentedIndex
from repro.index.quant import (AppendOnlyF32File, F32Rows, data_scale,
                               dequantize, fixed_scale, quantize_int8,
                               quantize_rows, rescore_topk)
from repro.index.segment import Segment


def _unit(shape, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape).astype(np.float32)
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _records(n, d=64, seed=0, docs=97):
    emb = _unit((n, d), seed)
    return [ChunkRecord(chunk_id=f"c{seed}-{i}", doc_id=f"d{i % docs}",
                        position=i // docs, valid_from=1000 + i,
                        text=f"text {i}", embedding=emb[i])
            for i in range(n)]


# ---------------------------------------------------------------------------
# quantization primitives
# ---------------------------------------------------------------------------
class TestQuantPrimitives:
    def test_quantize_deterministic(self):
        emb = _unit((500, 96), 1)
        q1, s1 = quantize_int8(emb)
        q2, s2 = quantize_int8(emb.copy())
        np.testing.assert_array_equal(q1, q2)
        np.testing.assert_array_equal(s1, s2)

    def test_round_trip_error_bounded(self):
        emb = _unit((200, 128), 2)
        for scale in (data_scale(emb), fixed_scale(128)):
            deq = dequantize(quantize_rows(emb, scale), scale)
            # symmetric rounding: error <= scale/2 per component
            assert np.all(np.abs(deq - emb) <= scale[None, :] / 2 + 1e-7)

    def test_fixed_scale_covers_normalized_rows(self):
        emb = _unit((100, 64), 3)
        q8 = quantize_rows(emb, fixed_scale(64))
        assert q8.min() >= -127 and q8.max() <= 127
        # a saturated one-hot row must hit exactly +-127
        hot = np.zeros((1, 64), np.float32)
        hot[0, 5] = 1.0
        assert quantize_rows(hot, fixed_scale(64))[0, 5] == 127

    def test_rescore_topk_exactness_and_empty_slots(self):
        c = _unit((50, 32), 4)
        q = _unit((2, 32), 5)
        pool = np.array([[3, 7, -1, 12], [1, -1, -1, 2]], np.int64)
        s, i = rescore_topk(q, pool, c, 3)
        for qi in range(2):
            rows = [r for r in pool[qi] if r >= 0]
            want = sorted(((float(q[qi] @ c[r]), r) for r in rows),
                          reverse=True)[:3]
            got = [(float(s[qi, j]), int(i[qi, j]))
                   for j in range(3) if np.isfinite(s[qi, j])]
            assert [r for _, r in want] == [r for _, r in got]
            np.testing.assert_allclose([x for x, _ in want],
                                       [x for x, _ in got],
                                       rtol=1e-5, atol=1e-6)
        assert i[1, 2] == -1 and np.isneginf(s[1, 2])

    @pytest.mark.parametrize("case", ["disjoint", "shared", "duplicates",
                                      "empty_slots", "k_over_live",
                                      "ties"])
    def test_rescore_topk_equals_all_pairs_formula(self, case):
        d = 48
        c = _unit((400, d), 11)
        q = _unit((8, d), 12)
        rng = np.random.default_rng(13)
        k = 5
        if case == "disjoint":                 # U = Q*k'
            pool = rng.permutation(400)[:8 * 12].reshape(8, 12)
        elif case == "shared":                 # U = k'
            base = rng.permutation(400)[:12]
            pool = np.stack([rng.permutation(base) for _ in range(8)])
        elif case == "duplicates":
            pool = rng.permutation(400)[:8 * 12].reshape(8, 12)
            pool[:, 6:] = pool[:, :6]
            pool[3] = 17
        elif case == "empty_slots":
            pool = rng.permutation(400)[:8 * 12].reshape(8, 12)
            pool[rng.random(pool.shape) < 0.4] = -1
            pool[2] = -1
            pool[5, :11] = -1
        elif case == "k_over_live":
            pool = rng.permutation(400)[:8 * 12].reshape(8, 12)
            pool[:, 3:] = -1
            k = 9
        else:                      # two distinct rows: scores all tie
            c[100:148], c[148:196] = c[0], c[1]
            pool = rng.permutation(np.arange(100, 196))[:96].reshape(8, 12)
        pool = pool.astype(np.int64)
        # the all-pairs formula: every query against every unique row
        uniq, inv = np.unique(np.clip(pool, 0, None), return_inverse=True)
        exact = np.einsum("qd,ud->qu", q, c[uniq])
        s = np.take_along_axis(exact, inv.reshape(pool.shape), axis=1)
        s = np.where(pool >= 0, s, -np.inf).astype(np.float32)
        order = np.argsort(-s, axis=1, kind="stable")[:, :k]
        want_s = np.take_along_axis(s, order, axis=1)
        want_i = np.where(np.isfinite(want_s),
                          np.take_along_axis(pool, order, axis=1), -1)
        src = F32Rows(lambda rows: c[rows], d)
        got_s, got_i = rescore_topk(q, pool, src, k)
        np.testing.assert_array_equal(got_s, want_s)
        np.testing.assert_array_equal(got_i, want_i)
        assert src.rows_read == len(uniq)
        if case == "ties":         # the tie order is the pool's order
            assert (want_s[:, :2] == want_s[:, :1]).all()
            pos = np.array([[list(row).index(i) for i in ids]
                            for row, ids in zip(pool, want_i)])
            tied = want_s[:, 1:] == want_s[:, :-1]
            assert (pos[:, 1:] > pos[:, :-1])[tied].all()

    def test_f32rows_passthrough_and_stats(self):
        c = _unit((100, 16), 6)
        fetches = []

        def fetch(rows):
            fetches.append(len(rows))
            return c[rows]

        src = F32Rows(fetch, 16)
        np.testing.assert_array_equal(src.get(np.array([1, 2, 3])),
                                      c[[1, 2, 3]])
        np.testing.assert_array_equal(src.get(np.array([6]))[0], c[6])
        assert src.rows_read == 4 and fetches == [3, 1]
        assert src.nbytes() == 0               # page cache, not resident

    def test_append_only_f32_file(self, tmp_path):
        f = AppendOnlyF32File(str(tmp_path / "spill.bin"), 8)
        a, b = _unit((5, 8), 7), _unit((3, 8), 8)
        f.reset(a)
        f.append(b)
        got = f.fetch(np.array([0, 4, 6]))
        np.testing.assert_array_equal(got[0], a[0])
        np.testing.assert_array_equal(got[1], a[4])
        np.testing.assert_array_equal(got[2], b[1])
        f.reset(b)                              # pure cache: rewrite
        np.testing.assert_array_equal(f.fetch(np.array([2]))[0], b[2])


# ---------------------------------------------------------------------------
# segment persistence round-trip
# ---------------------------------------------------------------------------
class TestSegmentRoundTrip:
    def _seg(self, n, root, quantized, ivf_min_rows=1024):
        emb = _unit((n, 48), n)
        seg = Segment("00000001", emb, np.arange(n), np.arange(n),
                      [f"c{i}" for i in range(n)],
                      [f"d{i}" for i in range(n)],
                      [f"t{i}" for i in range(n)],
                      ivf_min_rows=ivf_min_rows, quantized=quantized)
        name, sha = seg.save(root)
        return seg, emb, name, sha

    @pytest.mark.parametrize("n,ivf_min", [(64, 1024), (2000, 1024)])
    def test_save_load_bit_stable(self, tmp_path, n, ivf_min):
        """quantize -> save -> load -> dequantize is bit-identical: the
        persisted q8 + scale ARE the quantization, never recomputed."""
        root = str(tmp_path)
        seg, emb, name, sha = self._seg(n, root, True, ivf_min)
        loaded = Segment.load(root, name, sha, ivf_min_rows=ivf_min)
        assert loaded.quantized and loaded.emb is None
        np.testing.assert_array_equal(loaded.q8, seg.q8)
        np.testing.assert_array_equal(loaded.scale, seg.scale)
        np.testing.assert_array_equal(dequantize(loaded.q8, loaded.scale),
                                      dequantize(seg.q8, seg.scale))
        # exact fp32 rows come back byte-identical through the sidecar
        rows = np.array([0, n // 2, n - 1])
        np.testing.assert_array_equal(loaded.fetch_f32(rows), emb[rows])

    def test_release_f32_shrinks_resident_bytes(self, tmp_path):
        root = str(tmp_path)
        seg, emb, _, _ = self._seg(256, root, True)
        before = seg.emb_nbytes()
        assert seg.release_f32()
        after = seg.emb_nbytes()
        assert after < before / 3              # fp32 dropped, int8 kept
        np.testing.assert_array_equal(seg.fetch_f32(np.array([7])), emb[7:8])

    def test_corrupt_sidecar_detected(self, tmp_path):
        root = str(tmp_path)
        seg, _, name, sha = self._seg(64, root, True)
        with open(os.path.join(root, seg.f32_filename()), "r+b") as f:
            f.seek(100)
            f.write(b"\xff\xff\xff\xff")
        with pytest.raises(IOError):
            Segment.load(root, name, sha)

    def test_fp32_format_still_loads(self, tmp_path):
        root = str(tmp_path)
        seg, emb, name, sha = self._seg(64, root, False)
        loaded = Segment.load(root, name, sha)
        assert not loaded.quantized
        np.testing.assert_array_equal(loaded.emb, emb)


# ---------------------------------------------------------------------------
# recall gates: quantized vs the fp32 oracle
# ---------------------------------------------------------------------------
class TestRecallGates:
    def _recall(self, res_a, res_b, k):
        vals = []
        for ra, rb in zip(res_a, res_b):
            ids_a = {r.chunk_id for r in ra}
            ids_b = {r.chunk_id for r in rb}
            vals.append(len(ids_a & ids_b) / max(len(ids_a), 1))
        return float(np.mean(vals)) if vals else 1.0

    def test_fused_and_ivf_recall(self):
        """Hot-tier paths: fused memtable+small segments AND IVF
        segments, quantized vs fp32, recall@10 >= 0.99."""
        rs = _records(6000, seed=1)
        q = _unit((16, 64), 9)
        with tempfile.TemporaryDirectory() as r1, \
                tempfile.TemporaryDirectory() as r2:
            a = SegmentedIndex(64, mem_capacity=512, root=r1,
                               ivf_min_rows=400)
            b = SegmentedIndex(64, mem_capacity=512, root=r2,
                               ivf_min_rows=400, quantized=True)
            a.insert(rs)
            b.insert(rs)
            assert b.validate_authority()
            ra, rb = a.search(q, k=10), b.search(q, k=10)
            assert self._recall(ra, rb, 10) >= 0.99
            # exact rescore: scores of shared winners match fp32 bitwise-
            # close (same fp32 dot, possibly different summation shape)
            for row_a, row_b in zip(ra, rb):
                sa = {r.chunk_id: r.score for r in row_a}
                for r in row_b:
                    if r.chunk_id in sa:
                        assert abs(r.score - sa[r.chunk_id]) < 1e-4

    def test_temporal_recall_point_and_window(self):
        corpus = generate_corpus(n_docs=10, n_versions=4, seed=2)
        with tempfile.TemporaryDirectory() as r1, \
                tempfile.TemporaryDirectory() as r2:
            fp = LiveVectorLake(r1, dim=64)
            qz = LiveVectorLake(r2, dim=64, quantized=True)
            for v in range(4):
                for d in corpus.doc_ids():
                    for store in (fp, qz):
                        store.ingest(d, corpus.versions[v][d],
                                     ts=corpus.timestamps[v])
            queries = [f"{f.name} units recorded"
                       for f in list(corpus.facts)[:8]]
            ts = int((corpus.timestamps[1] + corpus.timestamps[2]) // 2)
            w = (int(corpus.timestamps[1]), int(corpus.timestamps[3]))
            at_a = fp.query_batch(queries, k=10, at=ts)
            at_b = qz.query_batch(queries, k=10, at=ts)
            assert self._recall(at_a, at_b, 10) >= 0.99
            for row in at_b:
                qz.temporal.assert_no_leakage(row, ts)
            w_a = fp.query_batch(queries, k=10, window=w)
            w_b = qz.query_batch(queries, k=10, window=w)
            assert self._recall(w_a, w_b, 10) >= 0.99
            for row in w_b:
                qz.temporal.assert_no_window_leakage(row, *w)

    def test_quantized_resident_history_survives_restart(self):
        """Checkpoint sidecar round-trip: a reopened quantized store
        seeds its resident int8 history from the persisted checkpoint
        columns BIT-identically (no re-quantization drift) and serves
        the same temporal results."""
        corpus = generate_corpus(n_docs=6, n_versions=4, seed=3)
        with tempfile.TemporaryDirectory() as root:
            qz = LiveVectorLake(root, dim=64, quantized=True,
                                cold_checkpoint_interval=1)
            for v in range(4):
                for d in corpus.doc_ids():
                    qz.ingest(d, corpus.versions[v][d],
                              ts=corpus.timestamps[v])
            queries = [f"{f.name} units recorded"
                       for f in list(corpus.facts)[:4]]
            ts = int(corpus.timestamps[2]) + 1
            before = qz.query_batch(queries, k=5, at=ts)
            res1 = qz.temporal._resident_history()
            q8_before = res1.emb[:res1.n].copy()

            qz2 = LiveVectorLake(root, dim=64, quantized=True,
                                 cold_checkpoint_interval=1)
            after = qz2.query_batch(queries, k=5, at=ts)
            res2 = qz2.temporal._resident_history()
            np.testing.assert_array_equal(res2.emb[:res2.n], q8_before)
            assert [[(r.chunk_id, round(r.score, 5)) for r in row]
                    for row in before] == \
                   [[(r.chunk_id, round(r.score, 5)) for r in row]
                    for row in after]


# ---------------------------------------------------------------------------
# quantized write-path behavior (mirror, merge, delete)
# ---------------------------------------------------------------------------
class TestQuantizedWritePath:
    def test_mirror_keeps_fused_q8_in_sync(self):
        """Overwriting a memtable key must update the fused int8 block
        copy, not just the fp32 slot array."""
        with tempfile.TemporaryDirectory() as root:
            idx = SegmentedIndex(32, mem_capacity=8, root=root,
                                 ivf_min_rows=10_000, quantized=True)
            idx.insert(_records(20, d=32, seed=4, docs=20))  # seals: smalls
            assert idx._catalog().mirrored
            target = _unit((1, 32), 99)[0]
            rec = ChunkRecord(chunk_id="new", doc_id="d0", position=0,
                              valid_from=99, text="new",
                              embedding=target)
            idx.insert([rec])
            got = idx.search(target[None], k=1)[0][0]
            assert got.chunk_id == "new"
            assert idx.validate_authority()

    def test_merge_requantizes_from_exact_f32(self):
        """Compaction pulls victim rows through fetch_f32 (sidecar), so
        merged segments re-quantize from EXACT fp32 — error never
        compounds across merge generations."""
        with tempfile.TemporaryDirectory() as root:
            idx = SegmentedIndex(32, mem_capacity=64, root=root,
                                 ivf_min_rows=100_000, fanout=2,
                                 quantized=True)
            rs = _records(640, d=32, seed=5, docs=640)
            idx.insert(rs)
            assert idx.cstats.merges > 0
            emb = {r.chunk_id: r.embedding for r in rs}
            for seg in idx.segments.values():
                rows = np.arange(len(seg))
                f32 = seg.fetch_f32(rows)
                for i in rows:
                    np.testing.assert_array_equal(f32[i],
                                                  emb[seg.chunk_ids[i]])
                np.testing.assert_array_equal(
                    seg.q8, quantize_rows(f32, seg.scale))

    def test_scan_accounting_consistent_between_fused_and_ivf(self):
        """ISSUE 5 satellite: the fused block reads each row once per
        BATCH (so its per-query amortized fraction halves at nq=2); IVF
        member scans are per-query (fraction independent of nq)."""
        # fused-only index
        idx = SegmentedIndex(32, mem_capacity=128)
        idx.insert(_records(100, d=32, seed=6, docs=100))
        q = _unit((2, 32), 7)
        idx.search(q[:1], k=3)
        f1 = idx.stats()["avg_fraction_scanned"]
        assert f1 == pytest.approx(1.0)        # nq=1: whole block / rows
        idx._scan_scanned = idx._scan_denom = 0
        idx.search(q, k=3)
        f2 = idx.stats()["avg_fraction_scanned"]
        assert f2 == pytest.approx(0.5)        # one batch read / 2 queries
        # IVF-only index: per-query fraction must NOT depend on nq
        idx2 = SegmentedIndex(32, mem_capacity=256, ivf_min_rows=200)
        idx2.insert(_records(2000, d=32, seed=8, docs=2000))
        idx2.seal()
        idx2._scan_scanned = idx2._scan_denom = 0
        idx2.search(q[:1], k=3)
        g1 = idx2.stats()["avg_fraction_scanned"]
        idx2._scan_scanned = idx2._scan_denom = 0
        idx2.search(np.repeat(q[:1], 2, axis=0), k=3)
        g2 = idx2.stats()["avg_fraction_scanned"]
        assert g1 == pytest.approx(g2, rel=0.05)

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["fp32", "int8"])
    def test_ivf_batch_equals_sequential_under_score_ties(self, quantized):
        """Massive duplicate embeddings force score ties across the pool
        cut (int8) and the top-k cut (fp32); the batched IVF scan must
        still return results BIT-identical to each query running alone:
        ties go to the lower row id."""
        base = _unit((60, 32), 20)
        emb = np.concatenate([np.repeat(base[:4], 40, axis=0), base[4:]])
        rs = [ChunkRecord(chunk_id=f"t{i}", doc_id=f"d{i}", position=0,
                          valid_from=1 + i, text=f"t{i}", embedding=emb[i])
              for i in range(emb.shape[0])]
        idx = SegmentedIndex(32, mem_capacity=64, ivf_min_rows=100,
                             quantized=quantized)
        idx.insert(rs)
        idx.seal()
        q = np.concatenate([base[:2] + 1e-3, _unit((2, 32), 21)])
        q /= np.linalg.norm(q, axis=1, keepdims=True)
        batched = idx.search(q, k=8)
        for qi in range(q.shape[0]):
            solo = idx.search(q[qi][None], k=8)[0]
            assert [(r.chunk_id, r.score) for r in solo] == \
                   [(r.chunk_id, r.score) for r in batched[qi]], qi

    def test_ivf_min_rows_drift_on_reopen(self):
        """Config drift: quantized segments reopened under a RAISED
        ivf_min_rows lose their IVF and fall to the solo scan path
        (their data scale cannot join the fused block); under a LOWERED
        one, k-means rebuilds from the fp32 sidecar. Both must serve
        with recall, not crash or silently mis-scale."""
        rs = _records(2000, d=32, seed=11, docs=2000)
        q = _unit((4, 32), 12)
        with tempfile.TemporaryDirectory() as root:
            idx = SegmentedIndex(32, mem_capacity=256, root=root,
                                 ivf_min_rows=400, quantized=True)
            idx.insert(rs)
            want = [{r.chunk_id for r in row} for row in idx.search(q, k=10)]
            for new_min in (100_000, 50):       # raise, then lower
                idx2 = SegmentedIndex(32, mem_capacity=256, root=root,
                                      ivf_min_rows=new_min, quantized=True)
                idx2.rebuild(rs)
                got = idx2.search(q, k=10)
                rec = np.mean([len(want[i] & {r.chunk_id for r in got[i]})
                               / 10 for i in range(4)])
                assert rec >= 0.9, (new_min, rec)
                assert idx2.validate_authority()

    def test_store_quantized_flag_persists_across_reopen(self):
        """Reopening with the default (quantized=None) must adopt the
        persisted format — never silently materialize fp32 back."""
        with tempfile.TemporaryDirectory() as root:
            qz = LiveVectorLake(root, dim=32, quantized=True)
            qz.ingest("d0", "alpha metrics chunk.\n\nbeta backups chunk.")
            re = LiveVectorLake(root, dim=32)           # flag omitted
            assert re.quantized is True
            assert re.hot.index.quantized is True
            # explicit override still wins (and re-persists)
            fp = LiveVectorLake(root, dim=32, quantized=False)
            assert fp.quantized is False
            assert LiveVectorLake(root, dim=32).quantized is False

    def test_resident_bytes_reduction(self):
        """The headline claim at index level: quantized resident
        embedding bytes ~4x below fp32 once segments dominate."""
        rs = _records(20_000, d=64, seed=10, docs=20_000)
        with tempfile.TemporaryDirectory() as r1, \
                tempfile.TemporaryDirectory() as r2:
            a = SegmentedIndex(64, mem_capacity=1024, root=r1,
                               ivf_min_rows=512)
            b = SegmentedIndex(64, mem_capacity=1024, root=r2,
                               ivf_min_rows=512, quantized=True)
            a.insert(rs)
            b.insert(rs)
            ratio = a.nbytes() / b.nbytes()
            assert ratio >= 3.0, ratio
