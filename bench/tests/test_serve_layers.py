"""The stream cell's traced window (``psc_serve.window`` under the
harness's ``AnnotatingTracer``) records the serve engine's request-path
spans, and the serve cell's per-layer readers read what
``psc_serve.layer_run`` collects from it, on the CPU, with no runner
and no chip."""
import pytest

from harness import spec as S
from harness import traffic as T

from ._run import ROOT

READERS = ("batch_occupancy", "bucket_solve_s", "gen_lag_p95_s",
           "graph_build_s")


class _Ctx:
    trace = True
    traffic = {"drain_s": 60.0}


@pytest.fixture(scope="module")
def traced():
    serve = S.system("psc_serve")
    conf = S.load_cell(ROOT, "gn_sbm_stream.poisson")["config"]
    n = conf["vertex_counts"][0]
    due = [0.02 * i for i in range(10)]
    mats = [serve._matrix(serve._graph(conf, n, T.rng(11, 2, i)), n)
            for i in range(len(due))]
    st = {"engine": serve._engine(conf), "mats": mats, "due": due,
          "graph_build_s": 0.0}
    rec = serve.window(_Ctx(), st)
    return serve.layer_run(_Ctx(), st, rec), rec


def _within(inner, outer):
    return outer.t0 <= inner.t0 and \
        inner.t0 + inner.dur <= outer.t0 + outer.dur


def test_traced_window_records_the_request_path_spans(traced):
    run, rec = traced
    res = [r for r in rec["results"].values() if r.ok]
    assert len(res) == len(rec["due"])
    assert all(r.stats.lane == "bucket" for r in res)

    spans = rec["tracer"].spans
    polls = [s for s in spans if s.name == "serve.poll"]
    solves = [s for s in spans if s.name == "serve.bucket_solve"]
    finishes = [s for s in spans if s.name == "serve.finish"]
    assert len(solves) == run["launches"] == len(run["bucket_solve_spans"])
    assert 1 <= len(polls) <= len(solves)
    for r in res:
        assert sum(s.attrs["req_ids"].count(r.req_id) for s in solves) == 1
        assert sum(s.attrs["req_id"] == r.req_id for s in finishes) == 1
    for s in solves + finishes:
        assert any(_within(s, p) for p in polls)
    assert all(s.dur > 0.0 for s in finishes)


@pytest.mark.parametrize("name", READERS)
def test_serve_readers_read_the_traced_window(traced, name):
    run, _ = traced
    value = S.metric_reader(name)(run)
    assert value is not None and value >= 0.0
