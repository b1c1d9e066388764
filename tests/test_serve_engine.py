"""ServeEngine: batched generation is finite, deterministic (greedy)
and respects the KV-cache semantics (engine output == step-by-step).
ClusterServeEngine: the request path's spans (``serve.poll``,
``serve.bucket_solve``, ``serve.finish``) and the per-request split of
its time (``ServeStats.queue_s``, ``solve_s``, ``finish_s``)."""
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced_config
from repro.core import PSCConfig
from repro.graphs import ring_of_cliques
from repro.grblas.containers import SparseMatrix
from repro.models import model as M
from repro.obs import TraceConfig, Tracer, use
from repro.serve import ClusterServeEngine, ServeEngine, GenerationConfig


@pytest.mark.parametrize("arch", ["gemma-2b", "mamba2-780m"])
def test_generate_greedy_deterministic(arch):
    cfg = get_reduced_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_len=48)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (2, 8)).astype(np.int32)
    gen = GenerationConfig(max_new_tokens=6, temperature=0.0)
    a = engine.generate(prompts, gen)
    b = engine.generate(prompts, gen)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (2, 6)
    assert (a >= 0).all() and (a < cfg.vocab).all()


def test_generate_matches_teacher_forcing():
    """Greedy engine tokens == argmax of the parallel forward, step by
    step (validates cache reuse through the engine path)."""
    cfg = get_reduced_config("gemma-2b")
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    engine = ServeEngine(cfg, params, max_len=32)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab, (1, 6)).astype(np.int32)
    out = engine.generate(prompt, GenerationConfig(max_new_tokens=4))

    from repro.models import layers as L
    seq = prompt.copy()
    for i in range(4):
        x, _ = M.forward_train(cfg, params, jnp.asarray(seq))
        logits = L.unembed_logits(params["embed"], x[:, -1:],
                                  real_vocab=cfg.vocab)
        nxt = int(jnp.argmax(logits[0, -1]))
        assert nxt == int(out[0, i]), f"step {i}"
        seq = np.concatenate([seq, [[nxt]]], axis=1)


# ------------------------------------------- clustering engine request path

def _ancestors(tr, span):
    by_sid = {s.sid: s for s in tr.spans}
    while span.parent is not None:
        span = by_sid[span.parent]
        yield span


def test_traced_poll_spans_and_the_split_of_a_request_time():
    cfg = PSCConfig(k=4, reorder="none", newton_iters=6, tcg_iters=4)
    eng = ClusterServeEngine(cfg, max_batch=4, max_wait_s=3600.0)
    W, _ = ring_of_cliques(4, 10)
    tr = Tracer(TraceConfig())
    before, after = {}, {}

    def submit(scale, k=None):
        t = time.monotonic()
        rid = eng.submit(W.with_vals(np.asarray(W.vals) * scale), k=k)
        before[rid] = t

    def poll(now=None):
        """Poll; stamp and return the ids answered since the last poll
        (``eng.poll`` returns every result not yet taken)."""
        new = set(eng.poll(now)) - set(after)
        after.update(dict.fromkeys(new, time.monotonic()))
        return new

    with use(tr):
        for i in range(3):
            submit(1.0 + 0.01 * i)
        assert poll() == set()              # nothing due
        submit(1.03)
        submit(1.04)                        # the bucket fills: one launch
        submit(1.0, k=1)                    # the solo lane
        assert len(poll()) == 5
        assert poll() == set()              # the fifth waits its deadline
        assert len(poll(now=time.monotonic() + 3601.0)) == 1
    done = eng.poll()
    assert len(done) == 6 and all(r.ok for r in done.values())

    polls = [s for s in tr.spans if s.name == "serve.poll"]
    assert [s.attrs["launches"] for s in polls] == [2, 1]
    assert [s.attrs["queue_depth"] for s in polls] == [6, 1]

    bucket = [rid for rid, r in done.items() if r.stats.lane == "bucket"]
    assert len(bucket) == 5
    solves = [s for s in tr.spans if s.name == "serve.bucket_solve"]
    finishes = [s for s in tr.spans if s.name == "serve.finish"]
    for rid in bucket:
        assert sum(s.attrs["req_ids"].count(rid) for s in solves) == 1
        assert sum(s.attrs["req_id"] == rid for s in finishes) == 1
    assert len(finishes) == len(done)
    for s in finishes:
        assert "serve.poll" in [a.name for a in _ancestors(tr, s)]
        res = done[s.attrs["req_id"]]
        assert s.attrs["lane"] == res.stats.lane
        if res.stats.lane == "bucket":
            solve = next(b for b in solves
                         if s.attrs["req_id"] in b.attrs["req_ids"])
            assert solve.attrs["req_ids"][s.attrs["lane_index"]] == \
                s.attrs["req_id"]

    for rid, res in done.items():
        st = res.stats
        assert st.queue_s >= 0.0 and st.finish_s > 0.0
        assert st.queue_s + st.solve_s + st.finish_s <= \
            after[rid] - before[rid]
    # the deadline launch: queued across the poll that launched nothing
    late = max(bucket)
    assert done[late].stats.queue_s > done[min(bucket)].stats.queue_s


def test_failed_request_queue_s_is_time_to_failure_and_no_finish():
    eng = ClusterServeEngine(PSCConfig(k=2, reorder="none"),
                             validate_inputs=True)
    bad = SparseMatrix.from_coo([0, 1], [1, 0], [np.nan, np.nan], (4, 4))
    t = time.monotonic()
    rid = eng.submit(bad)
    res = eng.take(rid)
    assert not res.ok and res.stats.finish_s == 0.0
    assert 0.0 <= res.stats.queue_s <= time.monotonic() - t
