"""Share of the traced window in which no operation ran on the device
(bench/trace_reduce.py over the profiler trace), in %.  Moves latency_p95_s."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr["n_device_planes"] or tr["idle_share"] is None:
        return None
    return 100.0 * tr["idle_share"]
