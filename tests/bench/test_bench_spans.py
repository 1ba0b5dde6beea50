"""The readers of the program's own spans and counters: the arithmetic on
a hand-built span list, and the five metrics in traced tiny cells."""
import json
import math
import os
import types

import pytest

import bench_tiny
from bench import program_spans, run, spec
from repro.spans import Span

NEW = {"gcn-tiny.full": {"report_ms.graph", "dispatch_ms.graph"},
       "sage-tiny.query": {"queue_wait_ms.over", "launch_ms.over",
                           "harvest_ms.over"}}


def hand_built():
    """A tick (0-100 ns) holding a launch (10-40, with a fill and a
    dispatch inside) and two harvests, the first with a wait and a report
    inside, the second with neither."""
    return [Span("serve.tick", 0, 100, None, {}),
            Span("serve.launch", 10, 40, 0, {"wave": 1}),
            Span("serve.fill", 15, 25, 1, {"wave": 1}),
            Span("runtime.dispatch", 25, 38, 1, {}),
            Span("serve.harvest", 50, 90, 0, {"wave": 1}),
            Span("runtime.wait", 55, 75, 4, {}),
            Span("runtime.report", 75, 85, 4, {}),
            Span("serve.harvest", 92, 99, 0, {"wave": 2})]


def test_self_time_is_the_span_less_what_its_children_cover():
    assert program_spans.self_ns(hand_built()) == [
        100 - 30 - 40 - 7, 30 - 10 - 13, 10, 13, 40 - 20 - 10, 20, 10, 7]


def test_self_time_counts_overlapping_children_once_and_clips_them():
    spans = [Span("serve.tick", 0, 100, None, {}),
             Span("a", 10, 50, 0, {}), Span("b", 30, 60, 0, {}),
             Span("c", 90, 130, 0, {})]
    assert program_spans.self_ns(spans)[0] == 100 - 50 - 10


def test_by_name_and_less_descendants():
    spans = hand_built()
    harvest = program_spans.by_name(spans)["serve.harvest"]
    assert harvest["n"] == 2
    assert harvest["mean_ms"] == pytest.approx((40 + 7) / 2 * 1e-6)
    assert harvest["self_ms"] == pytest.approx((10 + 7) / 2 * 1e-6)
    assert program_spans.less_descendants_ns(
        spans, "serve.harvest", "runtime.wait") == [20.0, 7.0]
    # a wait nested in another wait is not taken twice
    nested = spans + [Span("runtime.wait", 60, 70, 5, {})]
    assert program_spans.less_descendants_ns(
        nested, "serve.harvest", "runtime.wait") == [20.0, 7.0]


def test_readers_return_none_where_the_program_records_nothing(monkeypatch):
    traced = types.SimpleNamespace(trace=object())
    monkeypatch.setattr(program_spans, "snapshot", lambda: None)
    for names in NEW.values():
        for name in names:
            assert spec.load_module("metrics", name).read(traced) is None
    monkeypatch.setattr(program_spans, "snapshot", lambda: ([], {}))
    for names in NEW.values():
        for name in names:
            assert spec.load_module("metrics", name).read(traced) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny cells, with the query cell's graph and mix set so that a
    wave launches inside the traced last 0.5 s of every run: about 125
    seed vertices arrive there, drawn evenly from 20,000, so the vertex
    cache cannot answer them all."""
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench"),
                                os.path.join(spec.ROOT, "BENCHMARK.json"))
    sage = dict(bench_tiny.SAGE, graph=dict(bench_tiny.SAGE["graph"],
                                            vertices=20000, edges=100000))
    bench_tiny.write(root, "bench/configs/sage-tiny.json", sage)
    bench_tiny.write(root, "bench/traffic/tiny-query.json",
                     dict(bench_tiny.QUERY, rate_per_s=100.0,
                          zipf_exponent=0.0))
    return root


@pytest.mark.parametrize("workload,trace", [
    ("gcn-tiny.full", True), ("gcn-tiny.full", False),
    ("sage-tiny.query", True), ("sage-tiny.query", False)])
def test_traced_tiny_cells_report_the_program_span_metrics(root, workload,
                                                           trace):
    r = run.run_cell(workload, 2**31 + 11, 2.0, trace, root=root,
                     platform="cpu", peaks=bench_tiny.CPU_PEAKS)
    assert r["correct"] is True
    json.dumps(r)
    if not trace:
        assert not NEW[workload] & set(r["metrics"])
        return
    assert NEW[workload] <= set(r["metrics"])
    for name in NEW[workload]:
        m = r["metrics"][name]
        assert m["unit"] == "ms"
        assert math.isfinite(m["value"]) and m["value"] >= 0
