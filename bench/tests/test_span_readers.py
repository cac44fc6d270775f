"""The readers of the hot index's fused-scan and rescore spans
(``metrics/hot.h2d_ms``, ``hot.h2d_bytes``, ``hot.device_wait_ms``,
``hot.rescore_ms``) on recorded flight-recorder span trees: their values,
None where the store has no such span (an fp32 store's rescore, or a
program that predates the spans), and the cell lists they are declared
for."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
METRICS = ("hot.h2d_ms", "hot.h2d_bytes", "hot.device_wait_ms",
           "hot.rescore_ms")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA / "spans_small.json") as f:
        return json.load(f)


def _run(records):
    run = type("R", (), {})()
    run.spans = records
    return run


def _strip(span: dict, names: set) -> dict:
    out = dict(span)
    out["children"] = [_strip(c, names) for c in span.get("children", ())
                       if c["name"] not in names]
    return out


@pytest.mark.parametrize("store", ["fp32", "int8"])
@pytest.mark.parametrize("metric", METRICS)
def test_reader_values(recorded, store, metric):
    got = spec.metric_reader(metric)(_run(recorded[store]))
    want = recorded["expect"][store][metric]
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)


def test_rescore_is_none_on_fp32_and_read_from_every_scan_source(
        recorded):
    read = spec.metric_reader("hot.rescore_ms")
    assert read(_run(recorded["fp32"])) is None
    # the int8 trees rescore under both the fused block and IVF segments
    sources = set()
    stack = [r["spans"] for r in recorded["int8"]]
    while stack:
        s = stack.pop()
        if any(c["name"] == "rescore" for c in s.get("children", ())):
            sources.add(s["name"].split(":")[0])
        stack.extend(s.get("children", ()))
    assert sources == {"fused_scan", "ivf_scan"}
    assert read(_run(recorded["int8"])) > 0


@pytest.mark.parametrize("store", ["fp32", "int8"])
def test_readers_find_nothing_in_a_tree_without_the_spans(recorded, store):
    recs = copy.deepcopy(recorded[store])
    for r in recs:
        r["spans"] = _strip(r["spans"], {"h2d", "device_wait", "rescore"})
    for metric in METRICS:
        assert spec.metric_reader(metric)(_run(recs)) is None, metric
    assert spec.metric_reader("hot.fused_scan_ms")(_run(recs)) > 0


def test_h2d_bytes_cover_what_the_roofline_counts(recorded):
    # per batch, the bytes handed over are at least the bytes the hot
    # scan roofline counts (rows scanned x (row + mask word) + queries)
    dims = {"fp32": 4, "int8": 1}
    for store, eb in dims.items():
        for r in recorded[store]:
            stack, counted, handed = [r["spans"]], 0, 0
            while stack:
                s = stack.pop()
                if s["name"].startswith("intent:"):
                    nq = s["counters"]["queries"]
                    for f in (c for c in s["children"]
                              if c["name"] == "fused_scan"):
                        counted += f["counters"]["rows_scanned"] * \
                            (384 * eb + 4) + nq * 384 * 4
                if s["name"] == "h2d":
                    handed += s["counters"]["h2d_bytes"]
                stack.extend(s.get("children", ()))
            assert handed >= counted > 0, (store, handed, counted)


def test_new_metrics_are_declared_for_the_cells_that_read_them():
    bench = spec.load_benchmark()
    per = {m["name"]: m for m in bench["per_layer"]}
    both = {"paper-fp32.closed", "paper-int8.closed"}
    for metric in METRICS[:3]:
        assert set(per[metric]["workloads"]) == both
        assert per[metric]["source"] == "program_span"
        assert per[metric]["moves"] == "queries_per_s"
    assert per["hot.rescore_ms"]["workloads"] == ["paper-int8.closed"]
