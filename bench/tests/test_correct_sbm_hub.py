"""The sbm_hub_4x32k.flat rehearsal on the CPU (an LFR-style graph on
the SELL-C-σ layout): correct on sound runs, not correct under the
control or the faults a flat solve can have, each caught by the number
that is there to catch it; a SELL-C-σ layout from a program that does
not bound its width runs is refused at set-up."""
import pytest

from harness import spec as S

from ._run import BENCH, over_limit, run

CASES = [("none", None, True, [], 1),
         ("none", "bf16", False, ["ortho_err", "mxm_reals_rel_err"], 0),
         ("answer_altered", None, False, ["fval_rel_err", "fval_ratio"], 0),
         ("p2_start", None, False, ["fval_ratio"], 0)]


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


@pytest.mark.parametrize("fault,control,want,caught_by,trace", CASES)
def test_correct_reads_sound_runs_true_and_broken_runs_false(
        cache, fault, control, want, caught_by, trace):
    args = [fault, "--workload", "sbm_hub_4x32k.flat", "--seed",
            "4242424242424", "--seconds", "2", "--trace", str(trace),
            "--rehearse"]
    if control:
        args += ["--control", control]
    rc, result, err = run(args, cache, script=BENCH / "tests" /
                          "fault_driver.py")
    assert rc == 0, err[-3000:]
    assert result is not None, err[-3000:]
    assert result["correct"] is want, result["checks"]
    assert set(caught_by) <= set(over_limit(result)), result["checks"]
    if trace:
        # the traced sound run reports the layout and the solver layers;
        # the CPU has no peaks entry, so the roofline share stays out
        m = result["metrics"]
        assert 1 <= m["sell_runs"]["value"] <= 40
        assert 1.0 <= m["sell_fill"]["value"] <= 1.25
        assert m["hvps_per_solve"]["value"] > 0 and "init_s" in m
        assert "sellcs_hvp_roofline" not in m


@pytest.mark.parametrize("runs,bound,refused", [
    (223, None, True),       # a program without width classes
    (41, 40, True),
    (33, 40, False),
    (40, 40, False)])
def test_unbounded_width_runs_are_refused_at_set_up(runs, bound, refused):
    why = S.system("psc_solve_lfr").refusal(runs, bound)
    assert (why is not None) is refused
    if refused:
        assert f"{runs} SELL-C-σ width runs" in why
