"""Reduce a jax profiler trace to the benchmark's device numbers.

``load_xplane`` turns an ``.xplane.pb`` into a small normalized dict:

    {"device": {plane: [[start_ns, dur_ns, op], ...]},   # one per chip
     "host":   [[start_ns, dur_ns, name], ...]}          # main thread

Device events are the ``XLA Ops`` line of each ``/device:`` plane (all
of its lines where there is no such line).  Host events are those of
the host thread that carries the ``bench.window`` annotation: the
benchmark's own spans and the program's spans, which the benchmark's
tracer writes into the trace with ``jax.profiler.TraceAnnotation``.

``reduce_trace`` then gives, over the traced window (the
``bench.window`` span):

* ``busy_s``: length of the union of device-op intervals, averaged over
  the chips that ran anything; ``window_s``; ``idle_share`` = 1 -
  busy / window;
* ``device_ops``: the ops that took most device time;
* ``idle_gaps``: idle device time summed by the innermost host span open
  at the middle of each gap, largest first.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


def op_name(name: str) -> str:
    """``%while.451 = (s32[], ...) while(...)`` -> ``while.451``."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            evs = [[e.start_ns, e.duration_ns, op_name(e.name)]
                   for ln in ops for e in ln.events if e.duration_ns > 0]
            if evs:
                device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = [[e.start_ns, e.duration_ns, e.name]
                       for e in ln.events]
                if any(e[2] == WINDOW_SPAN for e in evs):
                    host = [e for e in evs if e[1] > 0]
    return {"device": device, "host": host}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge [start, end) intervals; returns them sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    """The idle intervals of [lo, hi) between disjoint sorted ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_points(points: List[float], host: List[list]) -> List[str]:
    """For each time in ``points`` (sorted), the innermost host span open
    there ("(none)" if none).  Spans of one thread nest, so one sweep
    with a stack does."""
    spans = sorted(((s, s + d, name) for s, d, name in host),
                   key=lambda x: (x[0], -x[1]))
    labels, stack, j = [], [], 0
    for t in points:
        while j < len(spans) and spans[j][0] <= t:
            s = spans[j][0]
            while stack and stack[-1][1] <= s:
                stack.pop()
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        labels.append(stack[-1][2] if stack else "(none)")
    return labels


def clip_trace(trace: dict, t0: float, t1: float) -> dict:
    """The events overlapping [t0, t1), clipped to it, with the
    window span set to [t0, t1) (for keeping a small sample)."""
    def clip(evs):
        out = []
        for s, d, name in evs:
            a, b = max(s, t0), min(s + d, t1)
            if b > a and name != WINDOW_SPAN:
                out.append([a, b - a, name])
        return out

    return {"host": [[t0, t1 - t0, WINDOW_SPAN]] + clip(trace["host"]),
            "device": {k: clip(v) for k, v in trace["device"].items()}}


def window_of(trace: dict) -> Optional[Tuple[float, float]]:
    for s, d, name in trace["host"]:
        if name == WINDOW_SPAN:
            return s, s + d
    return None


def reduce_trace(trace: dict, top: int = 10) -> dict:
    win = window_of(trace)
    if win is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = win
    planes = {k: v for k, v in trace["device"].items() if v}
    busy_ns, per_op = [], {}
    first_busy = None
    for name, evs in sorted(planes.items()):
        iv = []
        for s, d, op in evs:
            a, b = _clip(s, s + d, lo, hi)
            if b > a:
                iv.append((a, b))
                per_op[op] = per_op.get(op, 0.0) + (b - a)
        merged = union(iv)
        busy_ns.append(sum(b - a for a, b in merged))
        if first_busy is None:
            first_busy = merged
    n_planes = max(len(planes), 1)
    window_ns = hi - lo
    busy = sum(busy_ns) / n_planes
    idle = gaps(first_busy or [], lo, hi)
    mids = [(a + b) / 2.0 for a, b in idle]
    by_label: Dict[str, float] = {}
    for (a, b), lab in zip(idle, label_points(mids, trace["host"])):
        by_label[lab] = by_label.get(lab, 0.0) + (b - a)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gl = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy * 1e-9, "window_s": window_ns * 1e-9,
            "idle_share": 1.0 - busy / window_ns if window_ns > 0 else None,
            "n_device_planes": len(planes),
            "device_ops": [[k, v * 1e-9 / n_planes] for k, v in ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in gl]}
