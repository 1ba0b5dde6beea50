"""The program's span and counter recorder (``repro.spans``) and the
stable scope names of the fused program's device operations."""
import glob
import os
import re
import time

import jax

from repro import spans


def _profile(tmp_path, name="trace"):
    """``jax.profiler.trace`` without the Python tracer, as the benchmark
    runs it."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(tmp_path / name), profiler_options=opts)


def _busy(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_without_a_profiler_a_span_is_the_shared_null_context(tmp_path):
    with _profile(tmp_path):
        with spans.span("earlier"):
            pass
    before = spans.snapshot()
    assert [s.name for s in before[0]] == ["earlier"]
    assert spans.span("a") is spans.span("b", wave=3) is spans._NULL
    with spans.span("serve.tick"):
        spans.count("serve.requests_launched", 4)
    assert spans.snapshot() == before        # nothing recorded while off


def test_nested_spans_keep_parents_and_ids_and_counters_add(tmp_path):
    with _profile(tmp_path):
        with spans.span("serve.tick"):
            with spans.span("serve.launch", wave=7, bucket=64):
                with spans.span("serve.fill", wave=7):
                    spans.count("serve.requests_launched", 3)
                spans.count("serve.requests_launched", 2)
            with spans.span("serve.harvest", wave=7):
                spans.count("serve.queue_wait_s", 0.25)
        with spans.span("serve.submit", query=1):
            pass
    recorded, counters = spans.snapshot()
    names = [s.name for s in recorded]
    assert names == ["serve.tick", "serve.launch", "serve.fill",
                     "serve.harvest", "serve.submit"]
    tick, launch, fill, harvest, submit = recorded
    assert tick.parent is None and submit.parent is None
    assert launch.parent == 0 and harvest.parent == 0 and fill.parent == 1
    assert launch.ids == {"wave": 7, "bucket": 64}
    assert fill.ids == harvest.ids == {"wave": 7}
    assert submit.ids == {"query": 1}
    for s in recorded:
        assert s.start_ns <= s.end_ns
    assert tick.start_ns <= launch.start_ns <= fill.start_ns
    assert fill.end_ns <= launch.end_ns <= harvest.start_ns <= tick.end_ns
    assert counters == {"serve.requests_launched": 5,
                        "serve.queue_wait_s": 0.25}


def test_spans_match_the_host_events_of_the_profilers_trace(tmp_path):
    with _profile(tmp_path):
        for i in range(3):
            with spans.span("runtime.dispatch", wave=i):
                _busy(0.002)
            with spans.span("runtime.report"):
                with spans.span("runtime.wait"):
                    _busy(0.001)
                _busy(0.003)
    recorded, _ = spans.snapshot()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = [ev for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("runtime.")]
    events.sort(key=lambda ev: ev.start_ns)
    assert [ev.name for ev in events] == [s.name for s in recorded]
    assert len(recorded) == 9
    for ev, s in zip(events, recorded):
        mine = s.end_ns - s.start_ns
        assert abs(ev.duration_ns - mine) <= max(0.05 * mine, 50_000)


def test_a_second_session_replaces_the_first(tmp_path):
    with _profile(tmp_path, "one"):
        with spans.span("first"):
            spans.count("c", 1)
    with _profile(tmp_path, "two"):
        with spans.span("second"):
            spans.count("c", 10)
    recorded, counters = spans.snapshot()
    assert [s.name for s in recorded] == ["second"]
    assert counters == {"c": 10}


def test_a_span_the_profiler_stops_inside_is_left_out(tmp_path):
    jax.profiler.start_trace(str(tmp_path / "t"))
    try:
        with spans.span("outer"):
            with spans.span("inner"):
                pass
            jax.profiler.stop_trace()
    finally:
        if spans.enabled():
            jax.profiler.stop_trace()
    recorded, _ = spans.snapshot()
    assert [(s.name, s.parent) for s in recorded] == [("inner", None)]


def test_the_cap_counts_into_spans_dropped(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "MAX_SPANS", 5)
    with _profile(tmp_path):
        for i in range(8):
            with spans.span("serve.launch", wave=i):
                pass
    recorded, counters = spans.snapshot()
    assert [s.ids["wave"] for s in recorded] == [0, 1, 2, 3, 4]
    assert counters == {"spans.dropped": 3}


def test_the_fused_program_names_its_kernels_and_phases():
    """The scope names reach the HLO's metadata, where a device trace
    finds them: the kernels and the planner, the writeback profile and,
    with format-aware planning, the dense-to-sparse conversion."""
    from repro.core import perf_model, runtime
    from repro.models import gnn as gnn_models

    b = gnn_models.build_dense("gcn", "CO", scale=0.12, seed=2)
    ex = runtime.FusedModelExecutor(model=perf_model.TPUCostModel())
    fn, needed = ex._program(b.compiled, b.tensors)
    hlo = fn.lower(b.tensors, ex._input_counts(needed, b.tensors)).as_text(
        debug_info=True)
    for scope in ("aggregate", "update", "k2p_plan", "writeback_profile",
                  "d2s"):
        assert re.search(rf'["/]{scope}/', hlo), scope
