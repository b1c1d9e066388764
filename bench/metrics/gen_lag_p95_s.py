"""95th percentile of how late the load generator submitted requests
behind their due times (bench clock).  Moves latency_p95_s."""

from harness.window import percentile


def read(run):
    lags = run.get("gen_lags")
    return percentile(lags, 95) if lags else None
