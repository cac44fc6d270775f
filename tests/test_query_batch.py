"""Batched query engine tests (DESIGN.md §8): batch/single parity across
temporal intents and index states, the vectorized merge vs the tuple-sort
reference, authority-array invariants, and serving-layer coalescing."""
import numpy as np
import pytest

from repro.core.store import LiveVectorLake
from repro.core.types import ChunkRecord
from repro.index.lsm import SegmentedIndex, merge_topk_candidates

T1, T2, T3 = 1_000_000, 2_000_000, 3_000_000

DOCS = {
    "runbook": [
        "The SLA is four hours.\n\nBackups run nightly.\n\nReviews happen quarterly.",
        "The SLA is two hours.\n\nBackups run nightly.\n\nReviews happen quarterly.",
        "The SLA is two hours.\n\nBackups run hourly.\n\nReviews happen quarterly."
        "\n\nOn-call covers weekends.",
    ],
    "policy": [
        "Passwords rotate yearly.\n\nMFA is optional.",
        "Passwords rotate quarterly.\n\nMFA is mandatory.",
        "Passwords rotate quarterly.\n\nMFA is mandatory.\n\nHardware keys are issued.",
    ],
}

QUERIES = ["incident response SLA", "backup schedule", "password rotation",
           "MFA policy", "hardware keys", "review cadence"]


def _mk_records(vecs, start=0, doc="d", ts=1):
    return [ChunkRecord(chunk_id=f"c{start + i}", doc_id=doc,
                        position=start + i, valid_from=ts,
                        text=f"t{start + i}", embedding=vecs[i])
            for i in range(len(vecs))]


def _unit(rng, n, dim):
    v = rng.standard_normal((n, dim)).astype(np.float32)
    return v / np.linalg.norm(v, axis=1, keepdims=True)


def _assert_parity(store, queries, k=3, **kw):
    batch = store.query_batch(queries, k=k, **kw)
    seq = [store.query(t, k=k, **kw) for t in queries]
    assert batch == seq     # dataclass equality: every field, exact score


class TestIndexBatchParity:
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["fp32", "int8"])
    def test_batch_equals_sequential_with_tombstones_and_segments(
            self, quantized):
        rng = np.random.default_rng(0)
        dim = 64
        idx = SegmentedIndex(dim, mem_capacity=512, nprobe=8,
                             ivf_min_rows=1024, quantized=quantized)
        v = _unit(rng, 6000, dim)
        idx.insert(_mk_records(v))
        idx.delete([("d", i) for i in range(0, 6000, 13)])   # tombstones
        idx.insert(_mk_records(_unit(rng, 200, dim), start=100))  # shadows
        st = idx.stats()
        assert st["segments"] > 1 and st["tombstones"] > 0
        assert st["partitioned_segments"] >= 1        # IVF + small mixed
        q = (v[rng.choice(6000, 16)]
             + 0.02 * rng.standard_normal((16, dim))).astype(np.float32)
        batch = idx.search(q, k=10)
        for i in range(len(q)):
            assert idx.search(q[i], k=10)[0] == batch[i]

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["fp32", "int8"])
    def test_ivf_query_alone_equals_batch_of_32(self, quantized):
        """A served batch of 32 against IVF segments: each query alone
        gives the same ids and the same score bits."""
        rng = np.random.default_rng(4)
        dim = 64
        idx = SegmentedIndex(dim, mem_capacity=1024, ivf_min_rows=1024,
                             quantized=quantized)
        v = _unit(rng, 4000, dim)
        idx.insert(_mk_records(v))
        idx.delete([("d", i) for i in range(0, 4000, 11)])
        assert idx.stats()["partitioned_segments"] >= 2
        q = (v[rng.choice(4000, 32)]
             + 0.02 * rng.standard_normal((32, dim))).astype(np.float32)
        batch = idx.search(q, k=10)
        for i in range(len(q)):
            assert idx.search(q[i], k=10)[0] == batch[i]

    def test_authority_arrays_match_by_key(self):
        rng = np.random.default_rng(1)
        idx = SegmentedIndex(32, mem_capacity=64, ivf_min_rows=128)
        idx.insert(_mk_records(_unit(rng, 500, 32)))
        idx.delete([("d", i) for i in range(0, 500, 7)])
        idx.insert(_mk_records(_unit(rng, 50, 32), start=10))
        assert idx.validate_authority()

    def test_empty_and_tiny_batches(self):
        idx = SegmentedIndex(16, mem_capacity=8)
        assert idx.search(np.zeros((3, 16), np.float32), k=5) == [[], [], []]
        rng = np.random.default_rng(2)
        idx.insert(_mk_records(_unit(rng, 3, 16)))
        res = idx.search(_unit(rng, 5, 16), k=7)
        assert len(res) == 5
        assert all(len(r) == 3 for r in res)          # k > corpus size


class TestVectorizedMerge:
    @staticmethod
    def _merge_ref(scores, gids, authority, k):
        """The old tuple-sort merge: stable sort by -score (ties keep
        candidate order), drop non-authoritative rows, take k."""
        out = []
        for qi in range(scores.shape[0]):
            cands = [(float(scores[qi, j]), int(gids[qi, j]))
                     for j in range(scores.shape[1])]
            picked = []
            for s, g in sorted(cands, key=lambda t: -t[0]):
                if len(picked) == k:
                    break
                if g < 0 or not np.isfinite(s) or not authority[g]:
                    continue
                picked.append((np.float32(s), g))
            out.append(picked)
        return out

    def test_matches_tuple_sort_reference_randomized(self):
        rng = np.random.default_rng(3)
        for trial in range(50):
            nq = int(rng.integers(1, 6))
            w = int(rng.integers(1, 40))
            n_rows = int(rng.integers(1, 60))
            k = int(rng.integers(1, 12))
            # coarse score grid => plenty of exact ties
            scores = rng.integers(-3, 4, (nq, w)).astype(np.float32) / 2.0
            scores[rng.random((nq, w)) < 0.15] = -np.inf
            gids = rng.integers(-1, n_rows, (nq, w))
            authority = rng.random(n_rows) < 0.7
            top_s, top_g = merge_topk_candidates(scores, gids, authority, k)
            ref = self._merge_ref(scores, gids, authority, k)
            for qi in range(nq):
                got = [(top_s[qi, j], int(top_g[qi, j]))
                       for j in range(k) if top_g[qi, j] >= 0]
                assert got == ref[qi], (trial, qi)


class TestStoreBatchParity:
    @pytest.fixture()
    def store(self, tmp_path):
        store = LiveVectorLake(str(tmp_path), dim=96, hot_capacity=4)
        for v, ts in enumerate((T1, T2, T3)):
            for d, versions in DOCS.items():
                store.ingest(d, versions[v], ts=ts)
        return store

    def test_current_parity(self, store):
        _assert_parity(store, QUERIES)

    def test_historical_parity(self, store):
        _assert_parity(store, QUERIES, at=T2 + 500)
        for r in store.query_batch(QUERIES, k=3, at=T1 + 500):
            for hit in r:
                assert hit.valid_from <= T1 + 500 < hit.valid_to

    def test_comparative_parity(self, store):
        _assert_parity(store, QUERIES, window=(T1 + 500, T2 + 500))

    def test_mixed_intent_batch(self, store):
        """One batch containing all three intents (parsed from text)
        routes each query to its tier and returns in input order."""
        mixed = ["incident response SLA",
                 "backup schedule as of 1970-01-01",
                 "MFA policy between 1970-01-01 and 1970-01-02",
                 "password rotation"]
        batch = store.query_batch(mixed, k=3)
        seq = [store.query(t, k=3) for t in mixed]
        assert batch == seq
        assert all(r.tier == "hot" for r in batch[0])
        assert all(r.tier == "cold" for r in batch[2])

    def test_mid_stream_parity_with_tombstones_and_seal(self, store):
        """Parity holds right after updates that tombstone segment rows
        and force a seal mid-stream (hot_capacity=4 seals constantly)."""
        _assert_parity(store, QUERIES)
        store.ingest("runbook", DOCS["runbook"][0], ts=T3 + 1)  # revert
        assert store.hot.index.stats()["segments"] > 0
        _assert_parity(store, QUERIES)
        _assert_parity(store, QUERIES, at=T2 + 500)
        assert store.hot.index.validate_authority()

    def test_batch_is_order_independent(self, store):
        fwd = store.query_batch(QUERIES, k=3)
        rev = store.query_batch(QUERIES[::-1], k=3)
        assert fwd == rev[::-1]

    def test_empty_batch(self, store):
        assert store.query_batch([]) == []

    def test_resident_history_incremental_and_correct(self, store):
        """The fused path seeds its resident full-history arrays ONCE and
        advances them incrementally on commit — repeated point-in-time
        queries and post-ingest queries never re-fold the log."""
        ts = T2 + 500
        store.query_batch(QUERIES, k=3, at=ts)
        assert store.temporal.resident_builds == 1
        d0 = store.temporal.fused_dispatches
        store.query_batch(QUERIES, k=3, at=ts)
        assert store.temporal.fused_dispatches == d0 + 1
        assert store.temporal.resident_builds == 1    # no re-seed

        n0 = store.temporal._resident.n
        store.ingest("policy", DOCS["policy"][0], ts=T3 + 7)
        # ingest advanced the resident columns in place (no rebuild)
        assert store.temporal.resident_builds == 1
        assert store.temporal._resident.n > n0
        _assert_parity(store, QUERIES, at=ts)         # still correct
        # and the resident columns equal the full-history fold exactly
        snap = store.cold.snapshot(include_closed=True, from_scratch=True)
        res = store.temporal._resident
        assert res.n == len(snap)
        emb, vf, vt = res.views()
        np.testing.assert_array_equal(vf, snap.valid_from)
        np.testing.assert_array_equal(vt, snap.valid_to)
        np.testing.assert_array_equal(emb, snap.embeddings)
        assert res.chunk_ids == snap.chunk_ids

    def test_oracle_path_matches_fused(self, tmp_path):
        """The paper-faithful NumPy fold path (temporal_fused=False) and
        the fused kernel path return the same records and scores."""
        fused = LiveVectorLake(str(tmp_path / "f"), dim=96)
        oracle = LiveVectorLake(str(tmp_path / "o"), dim=96,
                                temporal_fused=False)
        for s in (fused, oracle):
            for v, ts in enumerate((T1, T2, T3)):
                for d, versions in DOCS.items():
                    s.ingest(d, versions[v], ts=ts)
        for at in (T1 + 500, T2 + 500, T2):           # incl boundary instant
            rf = fused.query_batch(QUERIES, k=3, at=at)
            ro = oracle.query_batch(QUERIES, k=3, at=at)
            for a, b in zip(rf, ro):
                assert [(r.chunk_id, r.score) for r in a] == \
                    [(r.chunk_id, r.score) for r in b]


class TestServingCoalescing:
    def test_query_batcher_coalesces_current(self, tmp_path):
        store = LiveVectorLake(str(tmp_path), dim=64)
        for d, versions in DOCS.items():
            store.ingest(d, versions[-1], ts=T1)
        b = store.query_batcher(k=3, max_batch=8)
        reqs = [b.submit(q) for q in QUERIES]
        b.drain()
        assert b.stats["batches"] == 1                # ONE hot-tier batch
        assert b.stats["mean_batch_size"] == len(QUERIES)
        assert [r.result for r in reqs] == \
            [store.query(q, k=3) for q in QUERIES]

    def test_query_batcher_buckets_by_intent(self, tmp_path):
        store = LiveVectorLake(str(tmp_path), dim=64)
        for v, ts in enumerate((T1, T2)):
            for d, versions in DOCS.items():
                store.ingest(d, versions[v], ts=ts)
        b = store.query_batcher(k=3, max_batch=8)
        reqs = [b.submit("incident response SLA"),
                b.submit(("backup schedule", T1 + 500, None)),
                b.submit("MFA policy"),
                b.submit(("password rotation", T1 + 500, None))]
        b.drain()
        assert b.stats["batches"] == 2                # current + historical
        assert reqs[0].result == store.query("incident response SLA", k=3)
        assert reqs[1].result == store.query("backup schedule", k=3,
                                             at=T1 + 500)

    def test_query_batcher_mixed_explicit_and_parsed_intent(self, tmp_path):
        """A text-parsed 'as of' request and an explicit-at request with
        the SAME instant share a bucket AND both hit the snapshot — the
        explicit request must not be re-classified as CURRENT when
        coalesced behind the parsed one (regression)."""
        store = LiveVectorLake(str(tmp_path), dim=64)
        for v, ts in enumerate((T1, T2)):
            for d, versions in DOCS.items():
                store.ingest(d, versions[v], ts=ts)
        from repro.core.temporal import _iso_to_us
        iso_ts = _iso_to_us("1970-01-01")
        b = store.query_batcher(k=3, max_batch=8)
        r_parsed = b.submit("backup schedule as of 1970-01-01")
        r_explicit = b.submit(("MFA policy", iso_ts, None))
        b.drain()
        assert b.stats["batches"] == 1                # same intent bucket
        assert r_explicit.result == store.query("MFA policy", k=3,
                                                at=iso_ts)
        assert r_parsed.result == store.query(
            "backup schedule as of 1970-01-01", k=3)

    def test_rag_engine_answer_batch(self, tmp_path):
        from repro.models.transformer import TransformerConfig
        from repro.serve.engine import RAGEngine

        store = LiveVectorLake(str(tmp_path), dim=48)
        for d, versions in DOCS.items():
            store.ingest(d, versions[-1], ts=T1)
        cfg = TransformerConfig(name="tiny", vocab=128, d_model=32,
                                n_layers=2, n_heads=4, n_kv=2, d_head=8,
                                d_ff=64, act="swiglu", remat=False)
        eng = RAGEngine(store, cfg, max_prompt=64, retrieval_k=2)
        qs = ["incident response SLA", "MFA policy"]
        outs = eng.answer_batch(qs, max_new_tokens=2)
        assert eng.retrieval_batcher.stats["batches"] == 1
        for q, out in zip(qs, outs):
            solo = eng.answer(q, k=2, max_new_tokens=2)
            assert out.retrieved == solo.retrieved    # bit-identical ctx
            assert out.token_ids == solo.token_ids
