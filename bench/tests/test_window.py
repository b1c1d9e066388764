import math

import pytest

from harness import spec as S
from harness import traffic as T
from harness.window import (generator_lags, latencies, open_loop,
                            percentile, whole_solve_window)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def test_whole_solves_start_until_the_window_has_passed():
    clk = FakeClock()

    def solve(i):
        clk.t += 4.0
        return i

    rec = whole_solve_window(solve, seconds=10.0, clock=clk)
    # starts at 0, 4, 8; the third starts before 10 s and runs to 12 s
    assert rec["results"] == [0, 1, 2]
    assert rec["window_s"] == pytest.approx(12.0)
    assert rec["solve_s"] == pytest.approx(4.0)


def test_a_window_holds_at_least_one_solve():
    clk = FakeClock()

    def solve(i):
        clk.t += 30.0

    rec = whole_solve_window(solve, seconds=1.0, clock=clk)
    assert len(rec["results"]) == 1 and rec["solve_s"] == 30.0


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 95, 10),
    (list(range(1, 101)), 95, 95),
    ([5.0], 95, 5.0),
    ([3, 1, math.inf, 2], 50, 2),
    ([3, 1, math.inf, 2], 95, math.inf),
])
def test_percentile_is_nearest_rank_over_all_values(values, q, want):
    assert percentile(values, q) == want


class Server:
    """Finishes each request ``service`` seconds of fake time after the
    poll that sees it; polling costs ``poll_cost``."""

    def __init__(self, clk, service, poll_cost=0.0, drop=()):
        self.clk, self.service, self.poll_cost = clk, service, poll_cost
        self.queue, self.next_id, self.drop = [], 0, set(drop)

    def submit(self, i):
        rid = self.next_id
        self.next_id += 1
        self.queue.append((rid, i))
        return rid

    def poll(self):
        self.clk.t += self.poll_cost
        out = {}
        for rid, i in self.queue:
            self.clk.t += self.service
            if i not in self.drop:
                out[rid] = i
        self.queue = []
        return out


def test_requests_are_timed_from_their_due_time():
    clk = FakeClock()
    srv = Server(clk, service=1.0)
    due = [0.0, 0.5, 0.6]
    rec = open_loop(due, srv.submit, srv.poll, clk, clk.sleep, drain_s=5)
    lat = latencies(rec)
    # request 0 runs 0 -> 1; requests 1 and 2 wait for it, are
    # submitted late (at 1.0) and both come back from the poll that
    # ends at 3.0
    assert lat[0] == pytest.approx(1.0)
    assert lat[1] == pytest.approx(3.0 - 0.5)
    assert lat[2] == pytest.approx(3.0 - 0.6)
    lags = generator_lags(rec)
    assert lags[0] == pytest.approx(0.0)
    assert lags[1] == pytest.approx(0.5) and lags[2] == pytest.approx(0.4)
    assert rec["window_s"] == pytest.approx(3.0)
    assert rec["ids"] == [0, 1, 2]


def test_a_request_that_never_returns_counts_as_missing():
    clk = FakeClock()
    srv = Server(clk, service=0.1, drop={1})
    rec = open_loop([0.0, 0.1, 0.2], srv.submit, srv.poll, clk, clk.sleep,
                    drain_s=1.0)
    lat = latencies(rec)
    assert rec["done"][1] is None and math.isinf(lat[1])
    assert percentile(lat, 95) == math.inf
    assert clk.t - 100.0 >= 1.0          # it waited out the drain


def test_poisson_schedule_fixes_count_and_sizes_for_every_seed():
    poisson = S.arrivals("poisson")
    params = {"rate_per_s": 10.0}
    sizes = [128, 256, 512, 1024]
    conf = {"vertex_counts": sizes, "vertex_count_weights": [8, 4, 2, 1]}
    seen = set()
    for seed in (0, 1, 2 ** 40 + 3):
        due, got = poisson.schedule(params, conf, 30.0, seed)
        assert len(due) == 300 and due == sorted(due)
        assert 0.0 <= due[0] and due[-1] < 30.0
        assert {n: got.count(n) for n in sizes} == \
            {128: 160, 256: 80, 512: 40, 1024: 20}
        seen.add(tuple(got))
    assert len(seen) == 3                # another order for each seed
    assert poisson.schedule(params, conf, 30.0, 5) == \
        poisson.schedule(params, conf, 30.0, 5)


def test_an_unknown_arrival_process_is_refused():
    with pytest.raises(S.SpecError):
        S.arrivals("no_such_process")


def test_derived_seeds_fit_31_bits_and_repeat():
    a = T.derived_seeds(2 ** 40 + 11, 5)
    assert a == T.derived_seeds(2 ** 40 + 11, 5)
    assert all(0 <= s < 2 ** 31 for s in a) and len(set(a)) == 5
