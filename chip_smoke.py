"""Smoke run of the store's served path on one TPU chip.

    python chip_smoke.py [--seed N]

One process loads a versioned corpus of the paper's shape (384-d
chunks, five versions, ~12% of chunks rewritten per version; 150
documents of 400 paragraphs, cut from 250 for time) into a two-shard
``ShardFabric`` through its normal write path, serves current,
point-in-time and window queries through the fabric's query batcher, and
checks every answer against the plain references:

- point-in-time and window answers equal the NumPy fold oracle (the
  ``temporal_fused=False`` path over the same cold tier);
- current answers reach recall@10 >= 0.99 against an exact NumPy scan
  of the current chunks (the IVF member scan is approximate);
- each of the four scan kernels, called once at the loaded size, returns
  the ids of its ``ref`` mode on the same inputs.

A second, int8 fabric over the first documents of the same corpus runs
the quantized kernels through the same served path.

The run needs a TPU and the Pallas kernels: on any other platform, or
with ``REPRO_KERNEL_MODE`` set to anything but ``pallas``/``auto``, it
exits nonzero before loading anything. Every phase prints its wall time
on the way, as a single smoke run and not as a benchmark figure. The
last line of a run that passed is one JSON object naming the device; a
failed phase ends the run nonzero without it.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

K = 10                  # answers per query
BATCH = 16              # queries per served batch
RECALL_MIN = 0.99       # current queries vs the exact scan
# ~60k current chunks, ~87k history rows. 250 documents (~100k chunks)
# is the size the store is meant for, but host ingest grows with the
# square of history (a compressed checkpoint of the whole cold snapshot
# every 8 commits, the hash store rewritten on every ingest), so 250
# documents does not fit the run's 1200 s limit with room to spare.
FULL_DOCS = 250
CORPUS = dict(n_docs=150, n_versions=5, paras_per_doc=400)
Q8_DOCS = 50            # documents in the int8 fabric
DIM = 384               # the paper's embedding width


class SmokeFailure(RuntimeError):
    """A phase whose result is wrong."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Counts backend compiles and their seconds from JAX's monitoring
    events (persistent-cache hits are not compiles)."""

    def __init__(self):
        import jax
        self.n = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration


def device_check() -> dict:
    """The chip, and the Pallas path on it, or nothing."""
    import jax
    from repro.kernels.common import kernel_mode
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform!r}")
    mode = kernel_mode()
    check(mode == "pallas",
          f"the kernel mode resolves to {mode!r}, not the Pallas kernels")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


# ----------------------------------------------------------------------
# load
# ----------------------------------------------------------------------
def load(fab, corpus, docs) -> float:
    """Ingest every version of ``docs`` through the fabric's write path.
    Version v's writes start at the corpus' v-th timestamp."""
    t = time.perf_counter()
    for v in range(corpus.n_versions):
        for d in docs:
            fab.ingest(d, corpus.versions[v][d], ts=corpus.timestamps[v])
    return time.perf_counter() - t


def current_chunks(corpus, docs, embedder):
    """The exact current state, rebuilt from the corpus text: keys
    (doc, position) and their embeddings."""
    from repro.core.chunking import chunk_document
    keys, texts = [], []
    for d in docs:
        for c in chunk_document(corpus.versions[-1][d]):
            keys.append((d, c.position))
            texts.append(c.text)
    return keys, embedder.embed(texts)


# ----------------------------------------------------------------------
# serve
# ----------------------------------------------------------------------
def query_plan(corpus, docs, rng):
    """Batches of (intent, at, window, texts). Query texts are the first
    half of paragraphs drawn from the version the intent looks at."""
    from repro.core.chunking import chunk_document
    ts, dt = corpus.timestamps, corpus.timestamps[1] - corpus.timestamps[0]

    def texts(v):
        out = []
        for _ in range(BATCH):
            d = docs[rng.integers(len(docs))]
            chunks = chunk_document(corpus.versions[v][d])
            words = chunks[rng.integers(len(chunks))].text.split()
            out.append(" ".join(words[:max(4, len(words) // 2)]))
        return out

    last = corpus.n_versions - 1
    mid = [t + dt // 2 for t in ts]
    return [
        ("current", None, None, texts(last)),
        ("current", None, None, texts(last)),
        ("point", mid[1], None, texts(1)),
        ("point", mid[last - 1], None, texts(last - 1)),
        ("window", None, (mid[0], mid[2]), texts(1)),
        ("window", None, (mid[2], ts[last] + dt), texts(last)),
    ]


def serve(fab, plan):
    """Every batch through ``fab.query_batcher``: each request must
    complete without error and each gather must be whole."""
    batcher = fab.query_batcher(k=K)
    answers = []
    for intent, at, window, texts in plan:
        reqs = [batcher.submit((t, at, window)) for t in texts]
        batcher.drain()
        for r in reqs:
            check(r.done and r.error is None,
                  f"{intent} request {r.req_id} failed: {r.error!r}")
        lg = fab.planner.last_gather
        check(lg is not None and not lg["degraded"] and not lg["failures"]
              and not lg["shards_missing"],
              f"{intent} gather not whole: {lg}")
        tier = "hot" if intent == "current" else "cold"
        check(all(x.tier == tier for r in reqs for x in r.result),
              f"{intent} answers did not come from the {tier} tier")
        answers.append([r.result for r in reqs])
    return answers


def kernel_dispatches(names=("kernel:topk_search", "kernel:topk_search_q8",
                             "kernel:temporal_window_topk",
                             "kernel:temporal_window_topk_q8")) -> dict:
    """Kernel spans in the flight recorder's retained traces."""
    from repro import obs
    counts = dict.fromkeys(names, 0)
    stack = [r["spans"] for r in obs.FLIGHT_RECORDER.records()
             if r.get("spans")]
    while stack:
        s = stack.pop()
        if s.get("name") in counts:
            counts[s["name"]] += 1
        stack.extend(s.get("children", ()))
    return counts


# ----------------------------------------------------------------------
# check against the plain references
# ----------------------------------------------------------------------
def check_current(plan, answers, keys, emb, embedder) -> float:
    """Tie-aware recall@K of the served current answers against an exact
    NumPy scan of the current chunks."""
    import numpy as np
    pos = {k: i for i, k in enumerate(keys)}
    hits = total = 0
    for (intent, _, _, texts), got in zip(plan, answers):
        if intent != "current":
            continue
        scores = embedder.embed(texts) @ emb.T                # (Q, N)
        for qi, res in enumerate(got):
            kth = np.partition(scores[qi], -K)[-K]
            for r in res:
                i = pos.get((r.doc_id, r.position))
                check(i is not None, f"served chunk {r.doc_id}:"
                      f"{r.position} is not a current chunk")
                hits += scores[qi, i] >= kth - 1e-6
            total += K
    return hits / total


def as_of(results, at):
    """Records as a point-in-time answer states them: the fold oracle
    reports the validity known at ``at`` (still open then), the fused
    path the row's final ``valid_to``; both are valid at ``at``."""
    import dataclasses
    from repro.core.types import VALID_TO_OPEN
    if at is None:
        return results
    return [dataclasses.replace(r, valid_to=VALID_TO_OPEN)
            if r.valid_to > at else r for r in results]


def check_temporal(fab, plan, answers) -> int:
    """Every point-in-time and window answer against the fold oracle,
    run through the same planner with each shard's fused path off."""
    from repro.shard import results_equivalent
    engines = [fab.lake(s).store.temporal for s in fab.ring.shards]
    checked = 0
    for e in engines:
        e.fused = False
    try:
        for (intent, at, window, texts), got in zip(plan, answers):
            if intent == "current":
                continue
            ref = fab.query_batch(texts, k=K, at=at, window=window)
            ext = fab.query_batch(texts, k=4 * K, at=at, window=window)
            for qi, (o, f, x) in enumerate(zip(ref, got, ext)):
                check(results_equivalent(as_of(o, at), as_of(f, at),
                                         as_of(x, at)),
                      f"{intent} query {qi} differs from the fold oracle")
                checked += 1
    finally:
        for e in engines:
            e.fused = True
    return checked


def same_ids(name, got, ref, empty_is_minus_one=False) -> None:
    """Kernel vs ref ids, rank for rank; ids may trade places only
    inside a run of equal scores (float noise between the two)."""
    import numpy as np
    (s_k, i_k), (s_r, i_r) = ((np.asarray(a), np.asarray(b))
                              for a, b in (got, ref))
    check(s_k.shape == s_r.shape, f"{name}: shapes {s_k.shape} {s_r.shape}")
    fin = np.isfinite(s_r)
    check(np.array_equal(fin, np.isfinite(s_k)),
          f"{name}: empty slots differ from ref")
    check(np.allclose(s_k[fin], s_r[fin], rtol=1e-5, atol=1e-6),
          f"{name}: scores differ from ref")
    if empty_is_minus_one:
        check(bool((i_k[~fin] == -1).all() and (i_r[~fin] == -1).all()),
              f"{name}: an empty slot's id is not -1")
    for q in range(s_r.shape[0]):
        for j in np.flatnonzero(fin[q] & (i_k[q] != i_r[q])):
            tied = np.isclose(s_r[q], s_k[q, j], rtol=1e-5, atol=1e-6)
            check(bool(tied[j]) and (i_k[q, j] in i_r[q][tied]
                                     or tied[-1]),
                  f"{name}: query {q} rank {j} id {i_k[q, j]} vs ref "
                  f"{i_r[q, j]} across a score gap")


def check_kernels(q, cur_emb, hist, rng, kinds) -> None:
    """Each kernel once at the loaded size, in the mode the served path
    resolves, against its ``ref`` mode on the same inputs."""
    import numpy as np
    from repro.index.quant import fixed_scale, quantize_rows
    from repro.kernels.temporal_mask_score.ops import (
        temporal_window_topk, temporal_window_topk_q8)
    from repro.kernels.topk_search.ops import topk_search, topk_search_q8
    scale = fixed_scale(DIM)
    if "topk" in kinds:
        mask = rng.random(cur_emb.shape[0]) < 0.9
        same_ids("topk_search",
                 topk_search(q, cur_emb, mask, K),
                 topk_search(q, cur_emb, mask, K, mode="ref"))
        c8 = quantize_rows(cur_emb, scale)
        same_ids("topk_search_q8",
                 topk_search_q8(q, c8, scale, mask, 4 * K),
                 topk_search_q8(q, c8, scale, mask, 4 * K, mode="ref"),
                 empty_is_minus_one=True)
    if "temporal" in kinds:
        emb, vf, vt = hist
        # half the queries are points, half windows, spread over history
        lo, hi = int(vf.min()), int(vf.max()) + 1
        t0 = rng.integers(lo, hi, q.shape[0])
        t1 = np.where(np.arange(q.shape[0]) % 2 == 0, t0 + 1,
                      rng.integers(t0 + 1, hi + 1))
        same_ids("temporal_window_topk",
                 temporal_window_topk(q, emb, vf, vt, t0, t1, K),
                 temporal_window_topk(q, emb, vf, vt, t0, t1, K,
                                      mode="ref"))
        c8 = quantize_rows(emb, scale)
        same_ids("temporal_window_topk_q8",
                 temporal_window_topk_q8(q, c8, scale, vf, vt, t0, t1,
                                         4 * K),
                 temporal_window_topk_q8(q, c8, scale, vf, vt, t0, t1,
                                         4 * K, mode="ref"),
                 empty_is_minus_one=True)


def fabric_history(fab):
    """The full history of every shard's cold tier, concatenated:
    (embeddings, valid_from, valid_to)."""
    import numpy as np
    snaps = [fab.lake(s).store.cold.snapshot(include_closed=True)
             for s in fab.ring.shards]
    return tuple(np.concatenate([getattr(x, a) for x in snaps])
                 for a in ("embeddings", "valid_from", "valid_to"))


def history_rows(fab) -> int:
    return sum(fab.lake(s).store.stats()["cold"]["total_records"]
               for s in fab.ring.shards)


# ----------------------------------------------------------------------
def smoke_fabric(label: str, root: str, corpus, docs, seed: int,
                 quantized: bool, kernels: tuple) -> None:
    """Load, serve and check one fabric; print what it did."""
    import numpy as np
    from repro.core.embedder import HashProjectionEmbedder
    from repro.shard import ShardFabric
    rng = np.random.default_rng(seed)
    fab = ShardFabric(root, n_shards=2, replicas=1, dim=DIM,
                      quantized=quantized)
    embedder = HashProjectionEmbedder(dim=DIM)
    keys, cur_emb = current_chunks(corpus, docs, embedder)
    secs = load(fab, corpus, docs)
    rows = history_rows(fab)
    say(f"[{label}] load: {len(docs)} docs x {corpus.n_versions} versions,"
        f" {len(keys)} current chunks, {rows} history rows, ingest "
        f"{secs:.3f} s ({1e3 * secs / rows:.4f} ms per history row)")

    t = time.perf_counter()
    plan = query_plan(corpus, docs, rng)
    answers = serve(fab, plan)
    say(f"[{label}] serve: {len(plan)} batches x {BATCH} queries in "
        f"{time.perf_counter() - t:.3f} s, no request error, no "
        f"degraded gather")

    t = time.perf_counter()
    recall = check_current(plan, answers, keys, cur_emb, embedder)
    check(recall >= RECALL_MIN,
          f"current recall@{K} {recall:.4f} < {RECALL_MIN}")
    n = check_temporal(fab, plan, answers)
    say(f"[{label}] references: current recall@{K} {recall:.4f} vs the "
        f"exact scan; {n} point/window answers equal the fold oracle "
        f"({time.perf_counter() - t:.3f} s)")

    if kernels:
        t = time.perf_counter()
        q = embedder.embed(plan[0][3])
        check_kernels(q, cur_emb, fabric_history(fab), rng, kernels)
        say(f"[{label}] kernels vs ref at the loaded size: same ids "
            f"({time.perf_counter() - t:.3f} s)")


def smoke(seed: int, corpus_kw: dict = CORPUS, q8_docs: int = Q8_DOCS,
          ) -> dict:
    """Both fabrics, every check. Returns the kernel dispatch counts."""
    from repro import obs
    from repro.data.corpus import generate_corpus
    obs.FLIGHT_RECORDER.enable(capacity=100_000, sample_rate=1.0,
                               seed=seed)
    t = time.perf_counter()
    corpus = generate_corpus(seed=seed, **corpus_kw)
    docs = corpus.doc_ids()
    say(f"corpus: {len(docs)} docs, {corpus.n_versions} versions, "
        f"{time.perf_counter() - t:.3f} s")
    if len(docs) < FULL_DOCS:
        say(f"corpus cut from {FULL_DOCS} to {len(docs)} docs: host "
            f"ingest is quadratic in history and {FULL_DOCS} does not "
            f"fit the time limit")
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as tmp:
        smoke_fabric("fp32", f"{tmp}/fp32", corpus, docs, seed,
                     quantized=False, kernels=("topk", "temporal"))
        smoke_fabric("int8", f"{tmp}/int8", corpus, docs[:q8_docs],
                     seed + 1, quantized=True, kernels=())
    counts = kernel_dispatches()
    obs.FLIGHT_RECORDER.disable()
    say("served kernel dispatches: " + ", ".join(
        f"{k} {v}" for k, v in counts.items()))
    check(all(counts.values()),
          f"a scan kernel never ran on the served path: {counts}")
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus, queries and masks")
    args = ap.parse_args(argv)
    t_all = time.perf_counter()
    try:
        import jax
        from repro.launch.cache import enable_compile_cache
        say(f"compile cache: {enable_compile_cache()}")
        compiles = CompileCounter()
        device = device_check()
        say(f"device: {device['kind']} x {device['count']} "
            f"({device['platform']})")
        smoke(args.seed)
        stats = jax.devices()[0].memory_stats() or {}
        say(f"compiles: {compiles.n} backend compiles, "
            f"{compiles.secs:.3f} s")
        say(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
        say(f"total wall: {time.perf_counter() - t_all:.3f} s "
            f"(one smoke run, not a benchmark figure)")
    except Exception:  # noqa: BLE001 — report and fail the run
        traceback.print_exc()
        print("FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
