"""The entry point refuses to measure without a TPU, and without the
program beside it; a cell, a configuration, a traffic mix and a metric
can be added as data alone."""
import json

from ._run import BENCH, add_entries, checkout, run


def test_exits_nonzero_without_a_tpu(tmp_path):
    rc, result, err = run(["--workload", "delaunay_n17.multilevel", "--seed", "1",
                           "--seconds", "1", "--trace", "0"], tmp_path,
                          timeout=300)
    assert rc != 0 and result is None
    assert "no TPU" in err


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    root = checkout(tmp_path, with_src=False)
    rc, result, err = run(["--workload", "delaunay_n17.multilevel", "--seed", "1",
                           "--seconds", "1", "--trace", "0", "--rehearse"],
                          tmp_path / "cache",
                          script=root / "bench" / "run.py", timeout=300)
    assert rc != 0 and result is None


def test_a_cell_added_as_data_only_runs(tmp_path):
    root = checkout(tmp_path)
    b = root / "bench"
    conf = json.loads((b / "configs" / "delaunay_n17.json").read_text())
    conf.update(name="tiny_delaunay", rehearse={"graph": {"log2_n": 10}})
    (b / "configs" / "tiny_delaunay.json").write_text(json.dumps(conf))
    (b / "traffic" / "one_client.json").write_text(json.dumps(
        {"arrivals": "back_to_back", "route": "flat"}))
    (b / "metrics" / "solves_in_window.py").write_text(
        "def read(run):\n    return float(len(run['solves']))\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_delaunay", "source": "x",
                            "file": "bench/configs/tiny_delaunay.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny_delaunay.one_client",
                              "config": "tiny_delaunay",
                              "traffic": "one_client", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "solves_in_window", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "solver drivers",
                              "moves": "solve_s",
                              "workloads": ["tiny_delaunay.one_client"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, result, err = run(["--workload", "tiny_delaunay.one_client",
                           "--seed", "9", "--seconds", "1", "--trace", "1",
                           "--rehearse"], tmp_path / "cache",
                          script=b / "run.py", timeout=600)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["checks"]
    assert result["metrics"]["solves_in_window"]["value"] >= 1
    # a metric without a workloads list applies to every cell; one with
    # a list only to the cells it names
    assert "graph_build_s" in result["metrics"]
    assert "init_s" not in result["metrics"]


def test_an_arrival_process_added_as_a_file_only_drives_the_stream(tmp_path):
    root = checkout(tmp_path)
    b = root / "bench"
    # a burst: every request of the window due at once, at its start
    (b / "arrivals" / "all_at_once.py").write_text(
        "from harness import traffic as T\n"
        "LOOP = 'open'\n"
        "def schedule(params, config, seconds, seed):\n"
        "    n = int(params['requests'])\n"
        "    return [0.0] * n, T.request_sizes(config, n, T.rng(seed, 1))\n")
    (b / "traffic" / "burst.json").write_text(json.dumps(
        {"arrivals": "all_at_once", "requests": 5, "drain_s": 60.0}))
    add_entries(root, workloads=[{
        "name": "gn_sbm_stream.burst", "config": "gn_sbm_stream",
        "traffic": "burst", "chips": 1, "why": "test"}])
    for m in ("graphs_per_s", "latency_p95_s"):
        spec = json.loads((root / "BENCHMARK.json").read_text())
        for e in spec["end_to_end"]:
            if e["name"] == m:
                e["workloads"].append("gn_sbm_stream.burst")
        (root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, result, err = run(["--workload", "gn_sbm_stream.burst", "--seed",
                           "31", "--seconds", "1", "--trace", "0",
                           "--rehearse"], tmp_path / "cache",
                          script=b / "run.py", timeout=600)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] == 5
    assert "latency_p95_s" in result["metrics"]


def test_a_mix_naming_an_unknown_arrival_process_is_refused(tmp_path):
    root = checkout(tmp_path)
    b = root / "bench"
    (b / "traffic" / "odd.json").write_text(json.dumps(
        {"arrivals": "no_such_process", "rate_per_s": 1.0}))
    add_entries(root, workloads=[{
        "name": "gn_sbm_stream.odd", "config": "gn_sbm_stream",
        "traffic": "odd", "chips": 1, "why": "test"}])
    rc, result, err = run(["--workload", "gn_sbm_stream.odd", "--seed",
                           "31", "--seconds", "1", "--trace", "0",
                           "--rehearse"], tmp_path / "cache",
                          script=b / "run.py", timeout=300)
    assert rc != 0 and result is None
    assert "no_such_process" in err
