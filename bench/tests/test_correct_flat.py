"""Whole flat solves of delaunay_n17 (the ``flat`` traffic of a
``psc_solve`` configuration), rehearsed on the CPU in a checkout that
adds the cell: correct on sound runs, and not correct under the control
or the answer_altered fault.  The benchmark has no such cell: the
configuration's fval_ratio limit is the multilevel cell's, 1.1, which a
flat p2_start reads under (about 1.0)."""
import pytest

from ._run import add_entries, checkout, over_limit, run

CASES = [("none", None, True, []),
         ("none", "bf16", False, ["fval_rel_err", "ortho_err"]),
         ("answer_altered", None, False, ["fval_rel_err", "fval_ratio"])]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = checkout(tmp_path_factory.mktemp("flat"))
    add_entries(root, workloads=[{
        "name": "delaunay_n17.flat", "config": "delaunay_n17",
        "traffic": "flat", "chips": 1, "why": "flat solves"}])
    return root


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


@pytest.mark.parametrize("fault,control,want,caught_by", CASES)
def test_correct_reads_sound_runs_true_and_broken_runs_false(
        root, cache, fault, control, want, caught_by):
    args = [fault, "--workload", "delaunay_n17.flat", "--seed",
            "4242424242424", "--seconds", "2", "--trace", "0", "--rehearse"]
    if control:
        args += ["--control", control]
    rc, result, err = run(args, cache, script=root / "bench" / "tests" /
                          "fault_driver.py")
    assert rc == 0, err[-3000:]
    assert result is not None, err[-3000:]
    assert result["correct"] is want, result["checks"]
    assert set(caught_by) <= set(over_limit(result)), result["checks"]
    if fault == "none" and control is None:
        assert result["attempted"] >= 1
