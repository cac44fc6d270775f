"""IVF index: recall vs exact scan, nprobe monotonicity, scan fraction —
for fp32 rows and for int8 rows rescored in fp32."""
import numpy as np
import pytest

from repro.core.ivf import IVFIndex
from repro.index.quant import data_scale, quantize_rows

FORMATS = pytest.mark.parametrize("quantized", [False, True],
                                  ids=["fp32", "int8"])


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(0)
    # clustered corpus: 16 clusters in 64-d
    centers = rng.standard_normal((16, 64)).astype(np.float32)
    pts = np.concatenate([
        c + 0.15 * rng.standard_normal((200, 64)).astype(np.float32)
        for c in centers])
    pts /= np.linalg.norm(pts, axis=1, keepdims=True)
    return pts


def _index(corpus, n_centroids, quantized):
    """A built index; an int8 one scans the quantized rows and fetches
    fp32 rows only to rescore, as a quantized segment does."""
    idx = IVFIndex(n_centroids=n_centroids)
    idx.build(corpus)
    if quantized:
        scale = data_scale(corpus)
        idx.attach_quantized(quantize_rows(corpus, scale), scale,
                             lambda r: corpus[r])
        idx.release_f32()
    return idx


@FORMATS
def test_exact_when_nprobe_full(corpus, quantized):
    idx = _index(corpus, 16, quantized)
    assert idx.recall_at_k(corpus[:32], k=10, nprobe=16) == 1.0


@FORMATS
def test_recall_improves_with_nprobe(corpus, quantized):
    idx = _index(corpus, 32, quantized)
    q = corpus[100:140]
    recalls = [idx.recall_at_k(q, k=10, nprobe=p) for p in (1, 4, 16, 32)]
    assert recalls[-1] == 1.0
    assert all(b >= a - 1e-9 for a, b in zip(recalls, recalls[1:]))
    assert recalls[1] >= 0.8             # clustered data: few probes win


@FORMATS
def test_sublinear_scan_fraction(corpus, quantized):
    idx = _index(corpus, 32, quantized)
    _, _, stats = idx.search(corpus[:8], k=5, nprobe=4)
    assert stats.fraction_scanned < 0.4


@FORMATS
def test_self_query_top1(corpus, quantized):
    idx = _index(corpus, 16, quantized)
    s, i, _ = idx.search(corpus[7:8], k=1, nprobe=4)
    assert int(i[0, 0]) == 7
