"""The published-GAT cell at toy sizes on the CPU: the benchmark's GAT
reference agrees with the program's, the closed-loop driver that takes
its parameters and spec from the model module runs a tiny cell correct,
the float8 control fails its limit, and the attention's device time is
reduced by name scope from a small recorded trace."""
import dataclasses
import json
import os
import types

import numpy as np
import pytest

import bench_tiny
from bench import graphs, reference, run, spec
from bench import trace as tracing

GAT = {
    "name": "gat-tiny", "source": "tiny", "model":
        {"name": "gat", "layers": 3, "heads": [2, 2, 3],
         "head_widths": [4, 4, 3], "combine": ["concat", "concat", "mean"],
         "skip": [2], "activation": "elu", "att_slope": 0.2,
         "att_threshold": 0.0, "classes": 3},
    "graph": {"vertices": 96, "edges": 300, "degree_exponent": 2.5,
              "degree_offset": 3, "locality": 0.5, "seed": 96},
    "features": {"width": 12, "density": 1.0, "seed": 12},
    "limits": {"logit_rms": 0.01, "missing": 0},
}
NEW = {"attention_ms.whole", "attention_roofline.whole",
       "attention_kept_share.whole"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny layout with one more cell, ``gat-tiny.whole``, standing
    where ``gat-ppi.whole`` stands in the real ``BENCHMARK.json``."""
    root = bench_tiny.make_root(tmp_path_factory.mktemp("bench"),
                                os.path.join(spec.ROOT, "BENCHMARK.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "gat-tiny", "source": "tiny",
                             "file": "bench/configs/gat-tiny.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": "gat-tiny.whole",
                               "config": "gat-tiny", "traffic": "whole",
                               "chips": 1, "why": "tiny"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w.replace("gat-ppi", "gat-tiny")
                              for w in m["workloads"]]
    bench_tiny.write(root, "BENCHMARK.json", bench)
    bench_tiny.write(root, "bench/configs/gat-tiny.json", GAT)
    return root


def _inputs(config, seed=5):
    g = graphs.make_graph(config["graph"])
    a_gcn, a_mean = graphs.normalized(g.dense())
    h0 = graphs.make_features(config["features"], g.n_vertices)
    return a_gcn, a_mean, h0, reference.model(config).init_weights(config,
                                                                     seed)


def test_bench_reference_agrees_with_the_program_reference():
    """Two float32 forwards at ``HIGHEST`` written apart: they differ only
    by the order of float32 roundings, ~1e-7 of the logits' scale."""
    from repro.models import gnn

    model = reference.model(GAT)
    a_gcn, a_mean, h0, params = _inputs(GAT)
    ours, _ = reference.forward_fn(GAT)(params, {"gcn": a_gcn,
                                                 "mean": a_mean}, h0)
    theirs = gnn.reference_forward(
        model.program_spec(GAT),
        {"A": a_gcn, "A_mean": a_mean, "H0": h0, **params})
    _, rel = gnn.reference_error(ours, theirs)
    assert rel < 1e-5
    # and the threshold is not ignored: the program's reference with one
    # drops weights and moves the logits far beyond that
    cut = gnn.reference_forward(
        dataclasses.replace(model.program_spec(GAT), att_threshold=0.3),
        {"A": a_gcn, "A_mean": a_mean, "H0": h0, **params})
    assert gnn.reference_error(cut, theirs)[1] > 1e-2


def test_parameters_are_named_and_shaped_as_the_program_names_them():
    from repro.models import gnn

    model = reference.model(GAT)
    ours = model.init_weights(GAT, 7)
    theirs = gnn.init_spec_weights(model.program_spec(GAT))
    assert {k: v.shape for k, v in ours.items()} == \
        {k: v.shape for k, v in theirs.items()}
    assert ours["P2"].shape == (8, 8) and "P1" not in ours
    again = model.init_weights(GAT, 7)
    assert all(np.array_equal(ours[k], again[k]) for k in ours)
    other = model.init_weights(GAT, 2**31 + 8)
    assert not np.array_equal(ours["Wg1h1"], other["Wg1h1"])


def test_configuration_is_checked_against_the_published_combination():
    model = reference.model(GAT)
    assert model.dims(GAT) == [12, 8, 8, 3]
    bad = json.loads(json.dumps(GAT))
    bad["model"]["combine"] = ["concat", "mean", "mean"]
    with pytest.raises(ValueError, match="as published"):
        model.program_spec(bad)


def test_attention_bytes_by_hand():
    model = reference.model(GAT)
    # per head 4 nnz + 4 n f + 8 n; heads 2 x 4 wide, 2 x 4, 3 x 3
    n, nnz = 10, 30
    head = {4: 4 * nnz + 4 * n * 4 + 8 * n, 3: 4 * nnz + 4 * n * 3 + 8 * n}
    assert model.attention_bytes(GAT, n, nnz) == 4 * head[4] + 3 * head[3]


@pytest.mark.parametrize("trace", [False, True])
def test_tiny_gat_cell_runs_correct_and_reports_its_metrics(root, trace):
    r = run.run_cell("gat-tiny.whole", 2**31 + 13, 1.0, trace, root=root,
                     platform="cpu", peaks=bench_tiny.CPU_PEAKS)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["window"]["compiles"] == 0
    cell = spec.load_cell("gat-tiny.whole", root)
    names = {m["name"] for m in (cell.per_layer if trace
                                 else cell.end_to_end)}
    assert set(r["metrics"]) <= names
    json.dumps(r)
    if not trace:
        assert set(r["metrics"]) == {"graph_ms", "setup_s"}
        return
    assert NEW <= {m["name"] for m in cell.per_layer}
    # the CPU trace has no device plane, so no attention device time; the
    # program's counters are there: the exact softmax keeps every entry
    assert r["metrics"]["attention_kept_share.whole"]["value"] == 100.0
    assert r["metrics"]["block_steps.graph"]["value"] > 0
    assert r["setup_phases"]["scopes_s"] > 0


def test_control_in_the_programs_place_is_not_correct(root):
    cell = spec.load_cell("gat-tiny.whole", root)
    mod = spec.load_module("drivers", cell.traffic["kind"], root)
    drv = mod.Driver(cell, 3, tracing.Tracer(None))
    drv.window(0.5, 0.0)
    drv.close()
    program = run.limits_check(cell, drv.compare("reference"))["logit_rms"]
    control = run.limits_check(cell, drv.compare("control"))["logit_rms"]
    assert program["value"] <= program["limit"] < control["value"]


# -- the attention's device time, by name scope -----------------------------

HLO = """\
ENTRY %main {
  %fusion.1 = f32[8,8] fusion(%p), kind=kLoop, calls=%c1, metadata={op_name="jit(fused)/attention/jit(attention_adjacency)/div" stack_frame_id=3}
  ROOT %fusion = f32[8,4] fusion(%a, %b), kind=kOutput, calls=%c0, metadata={op_name="jit(fused)/aggregate/dot_general"}
  %copy.2 = f32[8,8] copy(%a), metadata={op_name="a"}
  %bitcast.1 = f32[8,8] bitcast(%a)
}"""


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=dur)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=evs) for n, evs in lines])


def test_op_scopes_reads_the_compiled_programs_metadata():
    drv = spec.load_module("drivers", "closed_whole_graph_model")
    scopes = drv.op_scopes(HLO)
    assert "attention" in scopes["fusion.1"]
    assert "aggregate" in scopes["fusion"]
    assert scopes["copy.2"] == {"a"} and "bitcast.1" not in scopes


def test_scope_seconds_sums_the_scope_inside_the_window_over_devices():
    drv = spec.load_module("drivers", "closed_whole_graph_model")
    scopes = drv.op_scopes(HLO)
    host = _plane("/host:CPU", [("python", [
        _ev(tracing.WINDOW, 1000, 9000)])])
    dev = [_plane(f"/device:TPU:{d}", [(tracing.OPS_LINE, [
        _ev("%fusion.1 = f32[8,8] fusion(f32[8,8] %p)", 500, 1000 + d),
        _ev("%fusion = f32[8,4] fusion(f32[8,8] %a)", 2000, 3000),
        _ev("%fusion.1 = f32[8,8] fusion(f32[8,8] %p)", 6000, 5000)])])
        for d in (0, 200)]
    # device 0: 500 ns inside the window + 4000 ns cut at its end;
    # device 1: 700 + 4000
    got = drv.scope_seconds([host] + dev, scopes, "attention")
    assert got == pytest.approx(((500 + 4000) + (700 + 4000)) / 2 * 1e-9)
    assert drv.scope_seconds([host] + dev, scopes, "aggregate") == \
        pytest.approx(3000e-9)
    with pytest.raises(ValueError, match="bench.window"):
        drv.scope_seconds(dev, scopes, "attention")


def test_attention_readers_return_none_where_nothing_was_read():
    empty = types.SimpleNamespace(record={}, trace=None, peaks={})
    for name in NEW:
        assert spec.load_module("metrics", name).read(empty) is None
    timed = types.SimpleNamespace(
        record={"attention_s": 2e-3, "attention_bytes": 8.19e6},
        trace=object(), peaks={"hbm_bytes_per_s": 819e9})
    assert spec.load_module("metrics", "attention_ms.whole").read(
        timed) == pytest.approx(2.0)
    assert spec.load_module("metrics", "attention_roofline.whole").read(
        timed) == pytest.approx(0.5)


def test_configured_gat_graph_meets_its_counts_and_recorded_degrees():
    with open(os.path.join(spec.ROOT, "bench/configs/gat-ppi.json")) as f:
        cfg = json.load(f)
    g = graphs.make_graph(cfg["graph"])
    stats = graphs.degree_stats(g)
    assert (stats["vertices"], stats["edges"]) == (2762, 39711)
    assert {k: stats[k] for k in cfg["realised"]} == cfg["realised"]
    model = reference.model(cfg)
    assert model.dims(cfg) == [50, 1024, 1024, 121]
    shapes = model.param_shapes(cfg)
    assert shapes["P2"] == (1024, 1024) and shapes["Wg3h6"] == (1024, 121)
    assert len([k for k in shapes if k.startswith("Wg")]) == 14
