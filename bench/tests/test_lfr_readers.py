"""The readers of the skewed-degree cell's per-layer metrics on a canned
``layer_run``, and silent where the program recorded nothing."""
import pytest

from harness import spec as S

LAYOUT = {"n": 1000, "nnz": 20000, "runs": 34, "stored_slots": 21500,
          "classes": [512, 256]}
PROBE = {"n": 1000, "nnz": 20000, "k": 4, "hvp_s": 0.002,
         "short_s": [0.004], "long_s": [0.044]}
PEAKS = {"hbm_bytes_per_s": 1e9}


def _run(**kw):
    run = {"sell_layout": LAYOUT, "hvp_probe": PROBE, "peaks": PEAKS}
    run.update(kw)
    return run


def test_sell_runs_and_fill():
    assert S.metric_reader("sell_runs")(_run()) == 34
    assert S.metric_reader("sell_fill")(_run()) == pytest.approx(1.075)


def test_sellcs_hvp_roofline():
    # (20000 * 8 + 3 * 1000 * 4 * 4) bytes in 2 ms of device time a
    # product, over 1 GB/s
    want = 100.0 * (160_000 + 48_000) / 0.002 / 1e9
    got = S.metric_reader("sellcs_hvp_roofline")(_run())
    assert got == pytest.approx(want) and got <= 100.0


@pytest.mark.parametrize("name", ["sell_runs", "sell_fill",
                                  "sellcs_hvp_roofline"])
def test_readers_are_silent_without_their_inputs(name):
    read = S.metric_reader(name)
    assert read({}) is None
    assert read(_run(sell_layout=None, hvp_probe=None, peaks=None)) is None


def test_roofline_is_silent_without_device_time():
    # the CPU's trace has no device plane, and a difference of medians
    # at or under 0 is no reading
    read = S.metric_reader("sellcs_hvp_roofline")
    for hvp_s in (None, 0.0, -1e-6):
        assert read(_run(hvp_probe=dict(PROBE, hvp_s=hvp_s))) is None


def test_probe_device_busy_per_span():
    # two spans of each program; overlapping ops count once, and an op
    # that straddles a span's end counts only inside it
    busy_in = S.system("psc_solve_lfr").device_busy_in
    trace = {"host": [[0, 100, "bench.window"], [0, 40, "p.1"],
                      [50, 40, "p.21"], [95, 5, "p.1"]],
             "device": {"/device:TPU:0": [[5, 10, "a"], [10, 10, "b"],
                                          [60, 35, "c"], [96, 10, "d"]]}}
    assert busy_in(trace, "p.1") == pytest.approx([15e-9, 4e-9])
    assert busy_in(trace, "p.21") == pytest.approx([30e-9])
    assert busy_in({"host": trace["host"], "device": {}}, "p.1") == []
