"""Kernel validation: fused top-k search + temporal masked scoring (fp32
and the int8 asymmetric q8 variants) vs the pure oracles, interpret=True
on CPU, swept over shapes/dtypes."""
import numpy as np
import pytest

from repro.core.types import VALID_TO_OPEN
from repro.index.quant import (data_scale, fixed_scale, quantize_rows,
                               rescore_topk)
from repro.kernels.topk_search.ops import topk_search, topk_search_q8
from repro.kernels.topk_search.ref import topk_search_ref
from repro.kernels.temporal_mask_score.ops import (temporal_topk,
                                                   temporal_window_topk_q8)
from repro.kernels.temporal_mask_score.ref import temporal_topk_ref

# the q8 kernel modes exercised on CPU ("pallas" needs a TPU; "ref" is
# the jnp oracle, "interpret" the lowered Pallas body)
Q8_MODES = ("ref", "interpret")


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape).astype(np.float32)
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


@pytest.mark.parametrize("nq,n,d,k,bn", [
    (1, 256, 128, 5, 128),
    (4, 1000, 384, 10, 256),     # n not a multiple of bn -> padding path
    (8, 512, 64, 3, 512),
    (2, 130, 384, 7, 128),
    (3, 64, 256, 64, 128),       # k == n
])
def test_topk_matches_ref(nq, n, d, k, bn):
    q, c = _rand((nq, d), 1), _rand((n, d), 2)
    mask = np.random.default_rng(3).random(n) > 0.3
    s_ref, i_ref = topk_search_ref(q, c, mask, min(k, n))
    s_k, i_k = topk_search(q, c, mask, k, bn=bn, mode="interpret")
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)
    # indices may differ on exact score ties; verify score-equivalence at
    # every FINITE slot (indices of -inf slots are meaningless)
    finite = np.isfinite(np.asarray(s_ref))
    s_at_k = np.einsum("qd,qkd->qk", q, c[np.asarray(i_k) % n])
    s_at_r = np.einsum("qd,qkd->qk", q, c[np.asarray(i_ref) % n])
    np.testing.assert_allclose(s_at_k[finite], s_at_r[finite],
                               rtol=1e-5, atol=1e-5)


def test_topk_all_masked_returns_neg_inf():
    q, c = _rand((2, 64)), _rand((100, 64))
    s, i = topk_search(q, c, np.zeros(100, bool), 5, mode="interpret")
    assert np.all(np.isneginf(np.asarray(s)))


def test_topk_ref_mode_matches_interpret():
    q, c = _rand((3, 384), 5), _rand((700, 384), 6)
    mask = np.ones(700, bool)
    s_r, _ = topk_search(q, c, mask, 9, mode="ref")
    s_i, _ = topk_search(q, c, mask, 9, mode="interpret")
    np.testing.assert_allclose(np.asarray(s_r), np.asarray(s_i),
                               rtol=1e-5, atol=1e-5)


class TestQ8TopkKernel:
    """Edge parity for the int8 asymmetric top-k kernel across the ref
    and interpret modes, plus pool+rescore exactness."""

    @pytest.mark.parametrize("nq,n,d,k,bn", [
        (1, 256, 128, 5, 128),
        (4, 1000, 384, 10, 256),     # n not a multiple of bn -> padding
        (2, 130, 384, 7, 128),
        (3, 64, 256, 64, 128),       # k == n
    ])
    @pytest.mark.parametrize("mode", Q8_MODES)
    def test_pool_covers_exact_topk(self, nq, n, d, k, bn, mode):
        """With a 4x over-fetched pool and exact fp32 rescore, the q8
        path must recover the fp32 oracle's top-k row set (scores
        fp32-exact by construction)."""
        q, c = _rand((nq, d), 1), _rand((n, d), 2)
        mask = np.random.default_rng(3).random(n) > 0.3
        c8 = quantize_rows(c, data_scale(c))
        kp = min(4 * k, n)
        _, pool = topk_search_q8(q, c8, data_scale(c), mask, kp,
                                 bn=bn, mode=mode)
        s_q, i_q = rescore_topk(q, np.asarray(pool), c, min(k, n))
        s_ref, i_ref = topk_search_ref(q, c, mask, min(k, n))
        s_ref = np.asarray(s_ref)
        fin = np.isfinite(s_ref)
        np.testing.assert_allclose(s_q[fin], s_ref[fin],
                                   rtol=1e-5, atol=1e-5)
        for qi in range(nq):
            want = set(np.asarray(i_ref)[qi][fin[qi]].tolist())
            got = set(i_q[qi][np.isfinite(s_q[qi])].tolist())
            assert got == want, mode

    @pytest.mark.parametrize("mode", Q8_MODES)
    def test_all_masked_returns_neg_inf_and_minus_one(self, mode):
        q, c = _rand((2, 64)), _rand((100, 64))
        c8 = quantize_rows(c, fixed_scale(64))
        s, i = topk_search_q8(q, c8, fixed_scale(64),
                              np.zeros(100, bool), 5, mode=mode)
        assert np.all(np.isneginf(np.asarray(s))), mode
        # the -1 contract: a rescore can never resurrect a masked row
        assert np.all(np.asarray(i) == -1), mode

    @pytest.mark.parametrize("mode", Q8_MODES)
    def test_k_exceeds_valid_candidates(self, mode):
        q, c = _rand((2, 64), 1), _rand((64, 64), 2)
        mask = np.zeros(64, bool)
        mask[:3] = True
        c8 = quantize_rows(c, fixed_scale(64))
        s, i = topk_search_q8(q, c8, fixed_scale(64), mask, 10, mode=mode)
        s, i = np.asarray(s), np.asarray(i)
        for qi in range(2):
            fin = np.isfinite(s[qi])
            assert fin.sum() == 3, mode
            assert set(i[qi][fin]) == {0, 1, 2}, mode
            assert np.all(i[qi][~fin] == -1), mode

    @pytest.mark.parametrize("mode", Q8_MODES)
    def test_empty_corpus(self, mode):
        c8 = np.zeros((0, 32), np.int8)
        s, i = topk_search_q8(_rand((2, 32)), c8, fixed_scale(32),
                              np.zeros(0, bool), 5, mode=mode)
        assert np.asarray(s).shape == (2, 0)

    def test_modes_agree_on_pool_membership(self):
        """ref vs interpret must be score-identical (same math), and the
        exact rescore of each mode's pool must agree on the final
        top-k."""
        q, c = _rand((3, 128), 5), _rand((700, 128), 6)
        scale = data_scale(c)
        c8 = quantize_rows(c, scale)
        mask = np.ones(700, bool)
        s_r, i_r = topk_search_q8(q, c8, scale, mask, 40, mode="ref")
        s_i, i_i = topk_search_q8(q, c8, scale, mask, 40, mode="interpret")
        np.testing.assert_allclose(np.asarray(s_r), np.asarray(s_i),
                                   rtol=1e-5, atol=1e-5)
        for mode in Q8_MODES:
            _, pool = topk_search_q8(q, c8, scale, mask, 40, mode=mode)
            _, i_k = rescore_topk(q, np.asarray(pool), c, 10)
            _, pool_r = topk_search_q8(q, c8, scale, mask, 40, mode="ref")
            _, i_ref = rescore_topk(q, np.asarray(pool_r), c, 10)
            np.testing.assert_array_equal(i_k, i_ref, err_msg=mode)


class TestQ8TemporalKernel:
    """ISSUE 5: edge parity for the int8 temporal validity-masked kernel
    — the leakage guard must be byte-for-byte as strict as fp32."""

    def _corpus(self, n, d=64, seed=0):
        rng = np.random.default_rng(seed)
        c = _rand((n, d), seed)
        base = 1_700_000_000_000_000
        vf = base + rng.integers(0, 10**6, n).astype(np.int64)
        vt = np.where(rng.random(n) < 0.5, VALID_TO_OPEN,
                      vf + rng.integers(1, 10**6, n)).astype(np.int64)
        return c, vf, vt, base

    @pytest.mark.parametrize("mode", Q8_MODES)
    def test_windows_match_fp32_oracle_after_rescore(self, mode):
        from repro.kernels.temporal_mask_score.ref import (
            temporal_window_topk_ref)
        c, vf, vt, base = self._corpus(300, seed=7)
        q = _rand((4, 64), 8)
        scale = fixed_scale(64)
        c8 = quantize_rows(c, scale)
        t0s = np.array([vf.min(), vf.min() + 500_000,
                        vf.max(), vf.min() - 10], np.int64)
        t1s = t0s + np.array([1, 300_000, 10**9, 5], np.int64)
        s_ref, i_ref = temporal_window_topk_ref(q, c, vf, vt, t0s, t1s, 6)
        _, pool = temporal_window_topk_q8(q, c8, scale, vf, vt, t0s, t1s,
                                          24, bn=128, mode=mode)
        s_q, i_q = rescore_topk(q, np.asarray(pool), c, 6)
        fin = np.isfinite(s_ref)
        np.testing.assert_allclose(s_q[fin], s_ref[fin],
                                   rtol=1e-5, atol=1e-5)
        # every returned row must overlap its OWN query's window
        for qi in range(4):
            for j in i_q[qi][np.isfinite(s_q[qi])]:
                assert vf[j] < t1s[qi] and t0s[qi] < vt[j], mode

    @pytest.mark.parametrize("mode", Q8_MODES)
    def test_all_rows_out_of_window(self, mode):
        c, vf, vt, _ = self._corpus(200)
        scale = fixed_scale(64)
        c8 = quantize_rows(c, scale)
        ts = int(vf.min()) - 1
        b = np.full(3, ts, np.int64)
        s, i = temporal_window_topk_q8(_rand((3, 64), 1), c8, scale,
                                       vf, vt, b, b + 1, 7, mode=mode)
        assert np.all(np.isneginf(np.asarray(s))), mode
        assert np.all(np.asarray(i) == -1), mode

    @pytest.mark.parametrize("mode", Q8_MODES)
    def test_k_exceeds_valid(self, mode):
        c, vf, vt, _ = self._corpus(64)
        ts = int(vf.min())
        vf = vf.copy(); vt = vt.copy()
        vf[:] = ts + 1
        vf[:3] = ts
        vt[:3] = VALID_TO_OPEN
        scale = fixed_scale(64)
        b = np.full(2, ts, np.int64)
        s, i = temporal_window_topk_q8(_rand((2, 64), 2),
                                       quantize_rows(c, scale), scale,
                                       vf, vt, b, b + 1, 10, mode=mode)
        s, i = np.asarray(s), np.asarray(i)
        for qi in range(2):
            fin = np.isfinite(s[qi])
            assert fin.sum() == 3, mode
            assert set(i[qi][fin]) == {0, 1, 2}, mode

    @pytest.mark.parametrize("n", [1, 127, 129, 513])
    @pytest.mark.parametrize("mode", ("interpret",))
    def test_non_multiple_of_block_rows(self, n, mode):
        """Padding path: padded int8 rows carry an empty validity
        interval and can never rank."""
        c, vf, vt, _ = self._corpus(n, seed=n)
        scale = fixed_scale(64)
        ts = int(np.median(vf))
        b = np.full(2, ts, np.int64)
        s, i = temporal_window_topk_q8(_rand((2, 64), 4),
                                       quantize_rows(c, scale), scale,
                                       vf, vt, b, b + 1, 5, bn=128,
                                       mode=mode)
        fin = np.isfinite(np.asarray(s))
        assert np.all(np.asarray(i)[fin] < n)         # no padded index
        assert np.all(np.asarray(i)[~fin] == -1)

    @pytest.mark.parametrize("mode", Q8_MODES)
    def test_empty_history(self, mode):
        c8 = np.zeros((0, 32), np.int8)
        empty = np.zeros(0, np.int64)
        s, i = temporal_window_topk_q8(_rand((2, 32), 3), c8,
                                       fixed_scale(32), empty, empty,
                                       np.zeros(2, np.int64),
                                       np.ones(2, np.int64), 5, mode=mode)
        assert np.asarray(s).shape == (2, 0)


class TestTemporalKernel:
    def _setup(self, n=600, d=384, seed=0):
        rng = np.random.default_rng(seed)
        c = _rand((n, d), seed)
        base = 1_700_000_000_000_000          # realistic unix micros
        vf = base + rng.integers(0, 10**9, n).astype(np.int64)
        vt = np.where(rng.random(n) < 0.5, VALID_TO_OPEN,
                      vf + rng.integers(1, 10**9, n)).astype(np.int64)
        return c, vf, vt, base

    @pytest.mark.parametrize("k,bn,offset", [(5, 128, 5 * 10**8),
                                             (10, 256, 0),
                                             (3, 512, 2 * 10**9)])
    def test_matches_ref(self, k, bn, offset):
        c, vf, vt, base = self._setup()
        q = _rand((2, 384), 9)
        ts = base + offset
        s_ref, i_ref = temporal_topk_ref(q, c, vf, vt, ts, k)
        s_k, i_k = temporal_topk(q, c, vf, vt, ts, k, bn=bn, mode="interpret")
        np.testing.assert_allclose(np.asarray(s_k), s_ref, rtol=1e-5, atol=1e-5)

    def test_no_leakage_microsecond_boundaries(self):
        """Exactness at the validity boundary: ts == valid_from is valid,
        ts == valid_to is NOT (half-open interval), at 1us resolution."""
        d = 64
        c = _rand((4, d), 3)
        vf = np.array([100, 200, 200, 300], np.int64) + 1_700_000_000_000_000
        vt = np.array([200, 300, 201, VALID_TO_OPEN], np.int64)
        vt[:3] += 1_700_000_000_000_000 - np.int64(1_700_000_000_000_000)
        vt = np.array([vf[0] + 100, vf[1] + 100, vf[2] + 1, VALID_TO_OPEN],
                      np.int64)
        q = _rand((1, d), 4)
        for mode in ("ref", "interpret"):
            s, i = temporal_topk(q, c, vf, vt, int(vf[1]), 4, mode=mode)
            s = np.asarray(s)[0]
            i = np.asarray(i)[0]
            valid_rows = {j for j in range(4)
                          if vf[j] <= vf[1] < vt[j]}
            got = {int(i[j]) for j in range(4) if np.isfinite(s[j])}
            assert got == valid_rows, mode

    def test_future_chunks_never_returned(self):
        c, vf, vt, base = self._setup(300)
        q = _rand((1, 384), 11)
        ts = int(np.quantile(vf.astype(np.float64), 0.2))
        for mode in ("ref", "interpret"):
            s, i = temporal_topk(q, c, vf, vt, ts, 20, mode=mode)
            i = np.asarray(i)[0][np.isfinite(np.asarray(s)[0])]
            assert np.all(vf[i] <= ts), mode
            assert np.all(ts < vt[i]), mode


class TestTemporalKernelEdgeCases:
    """ISSUE 3 satellite: kernel parity vs ref.py on the degenerate
    shapes the full-history fused path can hit in production."""

    def _corpus(self, n, d=64, seed=0):
        rng = np.random.default_rng(seed)
        c = _rand((n, d), seed)
        base = 1_700_000_000_000_000
        vf = base + rng.integers(0, 10**6, n).astype(np.int64)
        vt = np.where(rng.random(n) < 0.5, VALID_TO_OPEN,
                      vf + rng.integers(1, 10**6, n)).astype(np.int64)
        return c, vf, vt, base

    def test_all_rows_masked(self):
        """Every row invalid at ts: all slots -inf, no index leaks."""
        c, vf, vt, base = self._corpus(200)
        ts = int(vf.min()) - 1                # before any validity starts
        for mode in ("ref", "interpret"):
            s, i = temporal_topk(_rand((3, 64), 1), c, vf, vt, ts, 7,
                                 mode=mode)
            assert np.all(np.isneginf(np.asarray(s))), mode

    def test_k_exceeds_valid_candidates(self):
        """k > number of valid rows: finite slots carry exactly the valid
        rows, the rest are -inf, in both modes."""
        c, vf, vt, _ = self._corpus(64)
        # make exactly 3 rows valid at ts
        ts = int(vf.min())
        vf = vf.copy(); vt = vt.copy()
        vf[:] = ts + 1
        vf[:3] = ts
        vt[:3] = VALID_TO_OPEN
        for mode in ("ref", "interpret"):
            s, i = temporal_topk(_rand((2, 64), 2), c, vf, vt, ts, 10,
                                 mode=mode)
            s, i = np.asarray(s), np.asarray(i)
            for qi in range(2):
                fin = np.isfinite(s[qi])
                assert fin.sum() == 3, mode
                assert set(i[qi][fin]) == {0, 1, 2}, mode

    def test_empty_history(self):
        """N == 0 corpus: empty result block, no kernel dispatch crash."""
        c = np.zeros((0, 32), np.float32)
        empty = np.zeros(0, np.int64)
        for mode in (None, "ref"):
            s, i = temporal_topk(_rand((2, 32), 3), c, empty, empty,
                                 1000, 5, mode=mode)
            assert np.asarray(s).shape == (2, 0)
            assert np.asarray(i).shape == (2, 0)

    @pytest.mark.parametrize("n", [1, 127, 129, 500, 513])
    def test_non_multiple_of_block_rows(self, n):
        """Row counts that don't divide the block size exercise the
        padding path; padded rows must never rank (empty validity)."""
        c, vf, vt, base = self._corpus(n, seed=n)
        ts = int(np.median(vf))
        q = _rand((2, 64), 4)
        s_ref, i_ref = temporal_topk_ref(q, c, vf, vt, ts, min(5, n))
        s_k, i_k = temporal_topk(q, c, vf, vt, ts, 5, bn=128,
                                 mode="interpret")
        np.testing.assert_allclose(np.asarray(s_k), s_ref,
                                   rtol=1e-5, atol=1e-5)
        fin = np.isfinite(np.asarray(s_k))
        assert np.all(np.asarray(i_k)[fin] < n)       # no padded index

    def test_per_query_windows_match_ref(self):
        """The window kernel's PER-QUERY bounds: each query row gets its
        own overlap mask inside one dispatch."""
        from repro.kernels.temporal_mask_score.ops import temporal_window_topk
        from repro.kernels.temporal_mask_score.ref import (
            temporal_window_topk_ref)
        c, vf, vt, base = self._corpus(300, seed=7)
        q = _rand((4, 64), 8)
        t0s = np.array([vf.min(), vf.min() + 500_000,
                        vf.max(), vf.min() - 10], np.int64)
        t1s = t0s + np.array([1, 300_000, 10**9, 5], np.int64)
        s_ref, i_ref = temporal_window_topk_ref(q, c, vf, vt, t0s, t1s, 6)
        s_k, i_k = temporal_window_topk(q, c, vf, vt, t0s, t1s, 6,
                                        bn=128, mode="interpret")
        np.testing.assert_allclose(np.asarray(s_k), s_ref,
                                   rtol=1e-5, atol=1e-5)
        # returned rows must overlap their OWN query's window
        s_k, i_k = np.asarray(s_k), np.asarray(i_k)
        for qi in range(4):
            fin = np.isfinite(s_k[qi])
            for j in np.asarray(i_k[qi][fin]):
                assert vf[j] < t1s[qi] and t0s[qi] < vt[j]

    def test_point_equals_window_of_one_microsecond(self):
        """temporal_topk(ts) must equal temporal_window_topk([ts, ts+1))
        exactly — the degenerate-window identity the engine relies on."""
        from repro.kernels.temporal_mask_score.ops import temporal_window_topk
        c, vf, vt, base = self._corpus(256, seed=9)
        q = _rand((3, 64), 10)
        ts = int(np.median(vf))
        b = np.full(3, ts, np.int64)
        for mode in ("ref", "interpret"):
            s_p, i_p = temporal_topk(q, c, vf, vt, ts, 5, mode=mode)
            s_w, i_w = temporal_window_topk(q, c, vf, vt, b, b + 1, 5,
                                            mode=mode)
            np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_w))
            np.testing.assert_array_equal(np.asarray(i_p), np.asarray(i_w))
