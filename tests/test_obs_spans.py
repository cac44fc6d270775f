"""The hot index's scan spans (DESIGN.md §12): a fused dispatch splits
into ``kernel:<name>`` (argument copy plus enqueue) with its ``h2d``
child and a ``device_wait`` sibling; every int8 rescore runs under a
``rescore`` span; and under an active trace every span is also a
``jax.profiler`` annotation, so a profile holds the span tree on the
device trace's clock — while with no trace no annotation is made."""
import glob
import os
import subprocess
import sys
import tempfile

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.types import ChunkRecord, pad_queries
from repro.index.lsm import SegmentedIndex

DIM = 16


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.set_enabled(True)
    yield
    obs.set_enabled(True)


def _index(root, n, quantized=False, **kw):
    rng = np.random.default_rng(7)
    idx = SegmentedIndex(DIM, root=root, quantized=quantized, **kw)
    idx.insert([ChunkRecord(
        chunk_id=f"c{i}", doc_id=f"d{i}", position=0, valid_from=1 + i,
        text=f"row {i}", embedding=rng.normal(size=DIM).astype(np.float32))
        for i in range(n)])
    return idx, rng.normal(size=(3, DIM)).astype(np.float32)


def _traced_search(idx, q):
    with obs.trace("batch") as root:
        idx.search(q, k=5)
    return root


def _preorder(span, parent=None):
    yield span, parent
    for c in span.children:
        yield from _preorder(c, span)


class TestFusedSplit:
    def test_fp32_fused_batch_splits_into_h2d_kernel_and_wait(self):
        with tempfile.TemporaryDirectory() as root:
            idx, q = _index(root, 40, mem_capacity=64)
            root_span = _traced_search(idx, q)
            (fused,) = root_span.find("fused_scan")
            assert [c.name for c in fused.children] == \
                ["kernel:topk_search", "device_wait"]
            kernel = fused.children[0]
            assert kernel.counters == {}        # no rows, bytes_streamed
            (h2d,) = kernel.children
            assert h2d.name == "h2d"
            cat = idx._catalog()
            qp, _ = pad_queries(q)
            # queries, fused corpus (f32) and its bool mask, as handed over
            want = qp.nbytes + cat.fused_emb.astype(np.float32).nbytes \
                + cat.fused_emb.shape[0]
            assert h2d.counters == {"h2d_bytes": want}
            assert fused.wall_ms >= kernel.wall_ms + \
                fused.children[1].wall_ms

    def test_int8_batch_rescores_under_fused_and_ivf_scans(self):
        with tempfile.TemporaryDirectory() as root:
            idx, q = _index(root, 300, quantized=True, mem_capacity=64,
                            ivf_min_rows=128)
            assert idx._catalog().ivf, "the index built no IVF segment"
            root_span = _traced_search(idx, q)
            scans = root_span.find("fused_scan") + \
                root_span.find_prefix("ivf_scan:")
            assert {s.name.split(":")[0] for s in scans} == \
                {"fused_scan", "ivf_scan"}
            for scan in scans:
                (rs,) = scan.find("rescore")
                rows = rs.counters["rescore_rows"]
                assert 0 < rows <= len(q) * idx.rescore_factor * 5
                assert rs.counters["rescore_bytes"] == rows * DIM * 4
            (fused,) = root_span.find("fused_scan")
            names = [c.name for c in fused.children]
            assert names == ["kernel:topk_search_q8", "device_wait",
                             "rescore"]

    def test_rescore_span_counts_unique_pool_rows(self):
        from repro.index.quant import rescore_topk
        rows = np.arange(40, dtype=np.float32).reshape(10, 4)
        pool = np.array([[3, 1, -1], [1, 7, 3]])
        with obs.trace("t") as root:
            rescore_topk(np.ones((2, 4), np.float32), pool, rows, 2)
        (rs,) = root.find("rescore")
        # rows 0 (an empty slot's clip), 1, 3 and 7
        assert rs.counters == {"rescore_rows": 4, "rescore_bytes": 64}
        # eleven disjoint pools of 4 (the twelfth repeats the first)
        pool = np.arange(48).reshape(12, 4)
        pool[11] = pool[0]
        rows = np.arange(200, dtype=np.float32).reshape(50, 4)
        with obs.trace("t") as root:
            rescore_topk(np.ones((12, 4), np.float32), pool, rows, 2)
        (rs,) = root.find("rescore")
        assert rs.counters == {"rescore_rows": 44, "rescore_bytes": 704}


class _CountingAnnotation:
    made = 0

    def __init__(self, name):
        type(self).made += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class TestProfilerAnnotations:
    def test_no_annotation_without_a_trace(self, monkeypatch):
        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            _CountingAnnotation)
        _CountingAnnotation.made = 0
        with tempfile.TemporaryDirectory() as root:
            idx, q = _index(root, 40, mem_capacity=64)
            idx.search(q, k=5)                  # no trace active
            obs.set_enabled(False)
            with obs.trace("batch"):            # tracing switched off
                idx.search(q, k=5)
            assert _CountingAnnotation.made == 0
            obs.set_enabled(True)
            root_span = _traced_search(idx, q)
            # one annotation per span of the tree, the root included
            assert _CountingAnnotation.made == \
                len(root_span.find_prefix(""))

    def test_obs_stays_importable_and_traces_without_jax(self):
        code = ("import sys; sys.modules['jax'] = None\n"
                "from repro import obs\n"
                "with obs.trace('batch') as root:\n"
                "    with obs.span('scan') as sp:\n"
                "        sp.add('rows_scanned', 3)\n"
                "print(root.total('rows_scanned'))\n")
        src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
        p = subprocess.run([sys.executable, "-c", code], text=True,
                           capture_output=True, timeout=120,
                           env=dict(os.environ, PYTHONPATH=src))
        assert p.returncode == 0, p.stderr
        assert p.stdout.strip() == "3"

    def test_profile_holds_the_span_tree_on_the_host_plane(self):
        from jax.profiler import ProfileData
        with tempfile.TemporaryDirectory() as root:
            idx, q = _index(root, 300, mem_capacity=64, ivf_min_rows=128)
            _traced_search(idx, q)              # compile outside
            out = os.path.join(root, "profile")
            jax.profiler.start_trace(out)
            try:
                root_span = _traced_search(idx, q)
            finally:
                jax.profiler.stop_trace()
            (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                                recursive=True)
            spans = list(_preorder(root_span))
            names = {s.name for s, _ in spans}
            assert {"fused_scan", "kernel:topk_search", "h2d",
                    "device_wait"} <= names
            assert any(n.startswith("ivf_scan:") for n in names)
            lines = [line for plane in ProfileData.from_file(path).planes
                     if plane.name.startswith("/host:")
                     for line in plane.lines
                     if any(e.name == "batch" for e in line.events)]
            assert len(lines) == 1
            events = sorted(((e.name, e.start_ns, e.duration_ns)
                             for e in lines[0].events if e.name in names),
                            key=lambda e: (e[1], -e[2]))
            # every span is an event of its name, in the tree's order
            assert [e[0] for e in events] == [s.name for s, _ in spans]
            at = {id(s): e for (s, _), e in zip(spans, events)}
            for span, parent in spans:
                _, t0, dur = at[id(span)]
                assert abs(dur / 1e6 - span.wall_ms) <= \
                    max(0.1 * span.wall_ms, 0.1), span.name
                if parent is not None:          # nested as the tree is
                    _, p0, pdur = at[id(parent)]
                    assert p0 <= t0 and t0 + dur <= p0 + pdur, span.name
