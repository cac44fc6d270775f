"""Query embedding in the scatter-gather planner (DESIGN.md §10.3).

A fabric batch is embedded once, in the planner, under ``plan`` —
whatever the shard count and on either scatter path — and every shard
scores that one matrix through ``search_batch``. The shard lakes share
the fabric's embedder, so the vector route answers exactly what each
lake's own text route answers: ids and score bits are identical, for
current, point-in-time and window batches, in fp32 and int8.
"""
import tempfile

import numpy as np
import pytest

from repro import obs
from repro.core.embedder import HashProjectionEmbedder
from repro.core.store import LiveVectorLake
from repro.shard import ShardFabric

DIM = 64
HOT_CAP = 64    # small memtable: the hot tier seals IVF segments too
VOCAB = ["alpha", "bravo", "carbon", "delta", "ember", "fjord", "glacier",
         "harbor", "isotope", "jetty", "kernel", "lagoon", "meadow",
         "nebula", "orchid", "plasma", "quartz", "rivet", "summit",
         "timber", "umbra", "vertex", "willow", "xylem", "yonder", "zephyr"]
INTENTS = ["current", "at", "window"]


@pytest.fixture(autouse=True)
def _tracing_on():
    obs.set_enabled(True)
    yield
    obs.set_enabled(True)


def make_stream(seed=0, n_docs=16, n_versions=3, chunks=4, words=6):
    """[(doc_id, text, ts)] with strictly increasing ts; each version
    re-rolls one chunk of every document."""
    rng = np.random.default_rng(seed)
    texts: dict[str, list[str]] = {}
    stream, ts = [], 0
    for _ in range(n_versions):
        for i in range(n_docs):
            doc = f"doc{i}"
            if doc not in texts:
                texts[doc] = [" ".join(rng.choice(VOCAB, words))
                              for _ in range(chunks)]
            else:
                texts[doc][int(rng.integers(chunks))] = \
                    " ".join(rng.choice(VOCAB, words))
            ts += 1_000_000
            stream.append((doc, "\n\n".join(texts[doc]), ts))
    return stream


def make_queries(seed=1, n=6, words=4):
    rng = np.random.default_rng(seed)
    return [" ".join(rng.choice(VOCAB, words)) for _ in range(n)]


def intent_kw(intent: str, stream) -> dict:
    last = stream[-1][2]
    return {"current": {},
            "at": {"at": last // 2},
            "window": {"window": (stream[2][2], last // 2)}}[intent]


def ingest(target, stream):
    for doc, text, ts in stream:
        target.ingest(doc, text, ts=ts)


def keys(batch):
    return [[(r.chunk_id, r.doc_id, r.position, r.valid_from, r.valid_to,
              r.version, r.tier) for r in res] for res in batch]


def scores(batch):
    return [np.array([r.score for r in res], np.float32) for res in batch]


def assert_same_answers(a, b):
    assert keys(a) == keys(b)
    for sa, sb in zip(scores(a), scores(b)):
        np.testing.assert_array_equal(sa, sb)


class CountingEmbedder(HashProjectionEmbedder):
    def __init__(self, dim):
        super().__init__(dim=dim)
        self.calls = 0

    def embed(self, texts):
        self.calls += 1
        return super().embed(texts)


# ---------------------------------------------------------------------------
# one embed span per fabric batch
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scatter", ["sequential", "parallel"])
@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_one_embed_span_under_plan(n_shards, scatter):
    timeout = 60.0 if scatter == "parallel" else None
    queries = make_queries()
    with tempfile.TemporaryDirectory() as root:
        fab = ShardFabric(root, n_shards=n_shards, dim=DIM,
                          hot_capacity=HOT_CAP, shard_timeout_s=timeout)
        ingest(fab, make_stream(n_docs=8, n_versions=2))
        with obs.trace("batch") as tr:
            fab.query_batch(queries, k=3)
        plan, = tr.find("plan")
        embeds = tr.find("embed")
        assert len(embeds) == 1
        assert embeds[0] in plan.children
        assert embeds[0].counters["queries"] == len(queries)
        stores = tr.find("store:query_batch")
        assert len(stores) == n_shards        # every shard still scanned
        assert not any(s.find("embed") for s in stores)


def test_factory_called_once_and_embed_once_per_batch():
    made: list[CountingEmbedder] = []

    def factory():
        made.append(CountingEmbedder(DIM))
        return made[-1]

    queries = make_queries()
    with tempfile.TemporaryDirectory() as root:
        fab = ShardFabric(root, n_shards=3, dim=DIM, hot_capacity=HOT_CAP,
                          embedder_factory=factory)
        ingest(fab, make_stream(n_docs=8, n_versions=2))
        assert len(made) == 1 and fab.embedder is made[0]
        before = made[0].calls
        for i in range(3):
            fab.query_batch(queries[i:], k=3)
        assert made[0].calls - before == 3
        assert len(made) == 1
        ShardFabric(root, embedder_factory=factory)   # a reopen: one more
        assert len(made) == 2


@pytest.mark.parametrize("n_shards", [1, 3])
def test_every_lake_wraps_the_fabric_embedder(n_shards):
    with tempfile.TemporaryDirectory() as root:
        fab = ShardFabric(root, n_shards=n_shards, dim=DIM,
                          hot_capacity=HOT_CAP)
        lakes = [fab.lake(s).store for s in fab.ring.shards]
        assert all(lk.embedder.inner is fab.embedder for lk in lakes)
        # the content-addressed ingest cache stays per lake
        assert len({id(lk.embedder) for lk in lakes}) == n_shards


# ---------------------------------------------------------------------------
# same answers, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("intent", INTENTS)
@pytest.mark.parametrize("quantized", [False, True], ids=["fp32", "int8"])
def test_store_query_batch_equals_search_batch(quantized, intent):
    stream = make_stream()
    queries = make_queries()
    kw = intent_kw(intent, stream)
    with tempfile.TemporaryDirectory() as root:
        lake = LiveVectorLake(root, dim=DIM, hot_capacity=HOT_CAP,
                              quantized=quantized)
        ingest(lake, stream)
        by_text = lake.query_batch(queries, k=5, **kw)
        by_vec = lake.search_batch(queries, lake.embedder.embed(queries),
                                   k=5, **kw)
        assert all(by_text)
        assert_same_answers(by_text, by_vec)


@pytest.mark.parametrize("intent", INTENTS)
@pytest.mark.parametrize("quantized", [False, True], ids=["fp32", "int8"])
def test_fabric_answers_equal_the_per_shard_embedding_route(quantized,
                                                            intent):
    """The planner's one embedding gives what each lake embedding the
    texts itself gave: the old route is each shard's own
    ``query_batch`` on the texts, merged by the same planner merge."""
    stream = make_stream()
    queries = make_queries()
    kw = intent_kw(intent, stream)
    with tempfile.TemporaryDirectory() as root:
        fab = ShardFabric(root, n_shards=3, replicas=2, dim=DIM,
                          hot_capacity=HOT_CAP, quantized=quantized)
        ingest(fab, stream)
        new = fab.query_batch(queries, k=5, **kw)
        per_shard = {s: fab.lake(s).query_batch(queries, k=5, **kw)
                     for s in fab.ring.shards}
        old = fab.planner._merge(queries, per_shard, 5)
        assert all(new)
        assert_same_answers(new, old)


def test_search_batch_rejects_a_matrix_of_the_wrong_shape():
    with tempfile.TemporaryDirectory() as root:
        lake = LiveVectorLake(root, dim=DIM)
        with pytest.raises(ValueError):
            lake.search_batch(["a", "b"], np.zeros((1, DIM), np.float32))
