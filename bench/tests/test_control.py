"""The controls fail the comparisons that decide `correct`, with the
cells' committed limits, at a size a test run can hold."""
import pytest

import cell_small
import control


@pytest.mark.parametrize("workload", ["higgs.hist", "higgs.exact",
                                      "covertype.hist.deep",
                                      "higgs.exact.4chip"])
def test_bfloat16_gains_fail_the_tree_check(workload):
    cell = cell_small.load(workload, rows=4000)
    res = control.train_control(cell, seed=2 ** 31 + 5, trees=2)
    assert res["gain_gap"] > cell["traffic"]["limits"]["gain_gap"]


def test_bfloat16_descent_fails_the_answer_check():
    cell = cell_small.load("higgs.serve")
    res = control.serve_control(cell, seed=2 ** 31 + 5, requests=50)
    assert res["proba_err"] > cell["traffic"]["limits"]["proba_err"]
