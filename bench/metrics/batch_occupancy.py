"""Requests per bucket launch over ``max_batch``, in %: how full the
serve engine's batches run.  Moves latency_p95_s."""


def read(run):
    if not run.get("launches"):
        return None
    return 100.0 * run["bucket_results"] / (run["launches"] *
                                            run["max_batch"])
