"""Host spans on the profiler's clock, and the profiler session.

``AnnotatingTracer`` is the program's own span recorder
(``repro.obs.trace.Tracer``) that also opens a
``jax.profiler.TraceAnnotation`` for every span, so the program's
fenced spans (``psc``, ``init``, ``continuation``, ``kmeans``,
``multilevel.*``, ``serve.bucket_solve``) land in the device trace,
where ``trace_reduce`` labels idle gaps with them.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile


def annotating_tracer():
    import jax
    from repro.obs import trace as obs_trace

    class AnnotatingTracer(obs_trace.Tracer):
        def __init__(self):
            super().__init__(obs_trace.TraceConfig(capacity=1 << 22))
            self._notes = []

        def _open(self, sp):
            super()._open(sp)
            note = jax.profiler.TraceAnnotation(sp.name)
            note.__enter__()
            self._notes.append(note)

        def _close(self, sp):
            if self._notes:
                self._notes.pop().__exit__(None, None, None)
            super()._close(sp)

    return AnnotatingTracer()


def span_durations(tracer, name: str) -> list:
    return [s.dur for s in tracer.spans if s.name == name]


@contextlib.contextmanager
def use_tracer(tracer):
    """Install ``tracer`` as the program's active span recorder."""
    from repro.obs import trace as obs_trace

    with obs_trace.use(tracer):
        yield tracer


class Profile:
    """A jax profiler session into a private temporary directory; the
    ``.xplane.pb`` path is kept until ``close``."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_profile_")
        self.path = None
        self.active = False

    def start(self):
        import jax

        jax.profiler.start_trace(self.dir)
        self.active = True

    def stop(self):
        import jax

        if self.active:
            jax.profiler.stop_trace()
            self.active = False
            found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            self.path = found[0] if found else None

    def close(self):
        self.stop()
        shutil.rmtree(self.dir, ignore_errors=True)


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)
