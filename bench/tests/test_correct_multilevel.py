"""The delaunay_n17.multilevel rehearsal on the CPU: correct on sound runs, and
not correct under the control or any fault the cell can have, each
caught by the number that is there to catch it."""
import pytest

from ._run import BENCH, over_limit, run

CASES = [("none", None, True, []),
         ("none", "bf16", False, ["fval_rel_err", "ortho_err"]),
         ("answer_altered", None, False, ["fval_rel_err", "fval_ratio"]),
         ("half_batch", None, False, ["rcut_rel_err"]),
         ("p2_start", None, False, ["fval_ratio"])]


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


@pytest.mark.parametrize("fault,control,want,caught_by", CASES)
def test_correct_reads_sound_runs_true_and_broken_runs_false(
        cache, fault, control, want, caught_by):
    args = [fault, "--workload", "delaunay_n17.multilevel", "--seed",
            "4242424242424", "--seconds", "2", "--trace", "0", "--rehearse"]
    if control:
        args += ["--control", control]
    rc, result, err = run(args, cache, script=BENCH / "tests" /
                          "fault_driver.py")
    assert rc == 0, err[-3000:]
    assert result is not None, err[-3000:]
    assert result["correct"] is want, result["checks"]
    assert set(caught_by) <= set(over_limit(result)), result["checks"]
    assert list(result)[-1] == "checks"
