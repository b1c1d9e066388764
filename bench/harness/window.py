"""Window arithmetic, free of jax and of any clock of its own: every
function takes the clock (and sleep) it uses, so tests inject them.

* ``whole_solve_window``: solves start back to back until ``seconds``
  have passed since the first began; each runs to its end.
* ``open_loop``: requests due at fixed offsets are submitted when due
  (late if the host was busy), the server is polled in between, and each
  request is timed from its due time to its result.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence


def whole_solve_window(solve: Callable[[int], object], seconds: float,
                       clock: Callable[[], float]) -> dict:
    """Run ``solve(i)`` for i = 0, 1, ... while fewer than ``seconds``
    have passed since solve 0 began.  Returns the results, each solve's
    (start, end) and ``solve_s`` = (last end - first start) / count."""
    results, spans = [], []
    t0 = clock()
    while not spans or clock() - t0 < seconds:
        s = clock()
        results.append(solve(len(spans)))
        spans.append((s, clock()))
    total = spans[-1][1] - spans[0][0]
    return {"results": results, "spans": spans, "window_s": total,
            "solve_s": total / len(spans)}


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]) of all values."""
    vals = sorted(values)
    if not vals:
        return float("nan")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[rank - 1])


def open_loop(due: Sequence[float], submit: Callable[[int], int],
              poll: Callable[[], Dict[int, object]],
              clock: Callable[[], float], sleep: Callable[[float], None],
              idle_s: float = 0.002, drain_s: float = 60.0) -> dict:
    """Submit request i at offset ``due[i]`` (seconds after the start),
    poll between submissions, and wait up to ``drain_s`` past the last
    due time for the rest.  ``submit(i)`` returns the server's request
    id; ``poll()`` returns the results that came back since its last
    call, keyed by that id.

    Returns per-request due, submit and done times (offsets from the
    start; done is None for a request that never came back), the
    server's id of each request, and the window: first due time to the
    last result."""
    n = len(due)
    start = clock()
    sent_at: List[float] = [math.nan] * n
    ids: List = [None] * n
    done_at: List = [None] * n
    rid_of: Dict[int, int] = {}
    nxt = 0
    pending = 0
    deadline = (due[-1] if n else 0.0) + drain_s
    while nxt < n or pending:
        now = clock() - start
        while nxt < n and due[nxt] <= now + 1e-6:
            rid = submit(nxt)
            rid_of[rid] = nxt
            ids[nxt] = rid
            sent_at[nxt] = clock() - start
            nxt += 1
            pending += 1
        results = poll()
        now = clock() - start
        for rid in results:
            i = rid_of.pop(rid, None)
            if i is not None:
                done_at[i] = now
                pending -= 1
        if nxt >= n and now > deadline:
            break
        if nxt < n:
            sleep(max(0.0, min(due[nxt] - now, idle_s)))
        elif pending:
            sleep(idle_s)
    finished = [t for t in done_at if t is not None]
    end = max(finished) if finished else math.nan
    first = due[0] if n else 0.0
    return {"due": list(due), "sent": sent_at, "done": done_at,
            "ids": ids, "window_s": end - first}


def latencies(rec: dict) -> List[float]:
    """Due-to-result seconds; a request that never came back counts as
    infinitely late."""
    return [(d - t) if d is not None else math.inf
            for t, d in zip(rec["due"], rec["done"])]


def generator_lags(rec: dict) -> List[float]:
    return [s - t for t, s in zip(rec["due"], rec["sent"])]
