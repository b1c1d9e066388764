"""The harness's ``AnnotatingTracer`` opens one profiler annotation per
program span and closes it when the span closes, in nesting order, so
the outer spans that ``trace_reduce`` labels idle gaps with keep their
full extent on the profiler's clock."""
import jax
import pytest

from harness import tracing
from repro.obs import trace as obs_trace


@pytest.fixture
def annotations(monkeypatch):
    """Replace ``jax.profiler.TraceAnnotation`` by a recorder of its
    enters and exits, in order."""
    log = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Note)
    return log


def _nested(tr):
    with obs_trace.use(tr):
        with tr.span("psc"):
            with tr.span("init"):
                with tr.span("continuation"):
                    pass
            with tr.span("kmeans"):
                pass
        with tr.span("serve.poll"):
            with tr.span("serve.bucket_solve"):
                pass
            for _ in range(2):
                with tr.span("serve.finish"):
                    pass


def test_annotating_tracer_closes_each_annotation_with_its_span(annotations):
    tr = tracing.annotating_tracer()
    _nested(tr)
    assert annotations == [
        ("enter", "psc"), ("enter", "init"), ("enter", "continuation"),
        ("exit", "continuation"), ("exit", "init"),
        ("enter", "kmeans"), ("exit", "kmeans"), ("exit", "psc"),
        ("enter", "serve.poll"),
        ("enter", "serve.bucket_solve"), ("exit", "serve.bucket_solve"),
        ("enter", "serve.finish"), ("exit", "serve.finish"),
        ("enter", "serve.finish"), ("exit", "serve.finish"),
        ("exit", "serve.poll")]
    assert len(tr.spans) == 8 and tr._stack == []


def test_annotating_tracer_leaves_no_annotation_open_on_a_misnested_exit(
        annotations):
    tr = tracing.annotating_tracer()
    with obs_trace.use(tr):
        with tr.span("root"):
            outer, inner = tr.span("b"), tr.span("c")
            outer.__enter__()
            inner.__enter__()
            outer.__exit__(None, None, None)    # b exits while c is open
            inner.__exit__(None, None, None)
    enters = [n for kind, n in annotations if kind == "enter"]
    exits = [n for kind, n in annotations if kind == "exit"]
    assert enters == ["root", "b", "c"]
    assert sorted(exits) == sorted(enters) and exits[-1] == "root"
    assert tr._stack == []
