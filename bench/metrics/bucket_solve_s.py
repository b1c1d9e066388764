"""Mean seconds per bucket launch in the fenced ``serve.bucket_solve``
span: one vmapped batched solve (serve/psc_engine.py).  Moves
latency_p95_s."""


def read(run):
    spans = run.get("bucket_solve_spans") or []
    return sum(spans) / len(spans) if spans else None
