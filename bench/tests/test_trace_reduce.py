import json
from pathlib import Path

import pytest

import trace_reduce as R

# the first 50 ms of the traced window of a delaunay_n17.multilevel run
# on one TPU v5e, as load_xplane normalized it (--save-trace)
DATA = Path(__file__).resolve().parent / "data" / "trace_small.json"


def _trace():
    # host: window [0, 100); a solve [0, 90) holding init [5, 40) and
    # continuation [40, 85); device ops at [10, 20), [15, 30), [50, 60),
    # [60, 70) and [95, 120) (clipped to the window)
    return {"host": [[0, 100, "bench.window"], [0, 90, "bench.solve"],
                     [5, 35, "init"], [40, 45, "continuation"]],
            "device": {"/device:TPU:0": [[10, 10, "fusion.1"],
                                         [15, 15, "fusion.2"],
                                         [50, 10, "fusion.1"],
                                         [60, 10, "while"],
                                         [95, 25, "fusion.3"]]}}


def test_union_merges_overlaps_and_touching_intervals():
    assert R.union([(15, 30), (10, 20), (50, 60), (60, 70)]) == \
        [(10, 30), (50, 70)]


def test_busy_idle_and_gap_attribution():
    out = R.reduce_trace(_trace())
    # busy: [10,30) + [50,70) + [95,100) = 45 ns of 100
    assert out["busy_s"] == pytest.approx(45e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["idle_share"] == pytest.approx(0.55)
    # gaps: [0,10) mid 5 -> init (starts at 5), [30,50) mid 40 ->
    # continuation, [70,95) mid 82.5 -> continuation
    gaps = dict(out["idle_gaps"])
    assert gaps == pytest.approx({"init": 10e-9, "continuation": 45e-9})
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(20e-9)
    assert ops["fusion.3"] == pytest.approx(5e-9)


def test_gap_outside_every_span_is_labelled_none():
    tr = {"host": [[0, 10, "bench.window"]],
          "device": {"/device:TPU:0": [[0, 4, "a"]]}}
    (label, secs), = R.reduce_trace(tr)["idle_gaps"]
    assert label == "bench.window" and secs == pytest.approx(6e-9)
    assert R.label_points([20.0], tr["host"]) == ["(none)"]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        R.reduce_trace({"host": [], "device": {}})


def test_long_hlo_names_are_cut_to_the_op_name():
    assert R.op_name("%while.451 = (s32[], f32[8]) while(...)") == \
        "while.451"
    assert R.op_name("fusion.3") == "fusion.3"


def test_clip_keeps_the_events_of_a_sub_window():
    tr = R.clip_trace(_trace(), 40, 65)
    assert R.window_of(tr) == (40, 65)
    out = R.reduce_trace(tr)
    assert out["busy_s"] == pytest.approx(15e-9)      # [50, 65)


def test_recorded_chip_trace_reduces_within_its_window():
    trace = json.loads(DATA.read_text())
    out = R.reduce_trace(trace)
    assert out["n_device_planes"] == 1
    assert 0.0 < out["busy_s"] <= out["window_s"]
    assert 0.0 <= out["idle_share"] < 1.0
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    idle = sum(s for _, s in dict(out["idle_gaps"]).items())
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-9)
