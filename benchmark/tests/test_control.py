"""The control (the reference in bfloat16, one step below the f32 the
configurations state) comes out as not correct, at a size a test can hold."""

import os

import pytest
from conftest import CELLS, SERVED, load


@pytest.mark.parametrize("cell", CELLS + (SERVED,), indirect=True)
def test_control_is_not_correct(copy, capsys, cell):
    control = load(os.path.join(copy.bench, "tests", "control.py"),
                   "rehearsal_control")
    control.harness = copy.harness
    for seed in (3, 2**31 + 5, 77):
        numbers = control.control_numbers(cell, seed)
        assert numbers["control_correct"] is False, numbers
        assert numbers["fails"], numbers
    capsys.readouterr()


def test_reference_in_float64_agrees_with_itself(copy, capsys):
    """The same comparison passes the reference against itself, so the
    control's failure is the precision's and not the comparison's."""
    h = copy.harness
    config = h.load_json("configs", "tpch_sf1.json")
    paths = h.ensure_data(h.load_module("datagen", "tpch.py"), "tpch",
                          config["scale_rows"], 3)
    ref = h.load_module("reference", "tpch.py")
    compare = h.load_module("compare.py")
    for q in ("q1", "q6"):
        r = compare.compare_tables(ref.QUERIES[q](paths),
                                   ref.QUERIES[q](paths))
        assert r["exact_mismatches"] == 0 and not any(r["gaps"].values())
    capsys.readouterr()
