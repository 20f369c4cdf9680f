"""The four-chip cell end to end on the CPU backend, over eight forced host
devices of which the configuration takes four: the result line is
``correct``, names every metric ``BENCHMARK.json`` lists for the cell that
needs no device trace, and counts exchanges, bytes and the three phases of
a mesh fragment; the bfloat16 control comes out not correct."""

import json
import os

# the mesh needs more than one device, and the CPU backend has as many as
# this flag says when it starts, which is after every test file is collected
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import pytest  # noqa: E402
from conftest import load  # noqa: E402
from test_rehearsal import expected_metrics  # noqa: E402

CELL = "tpch_sf1_mesh4.shuffle"
CHIPS = 4
MESH_METRICS = ("ici_bytes_per_query", "ici_exchanges_per_query",
                "ici_ingest_ms_per_query", "ici_collective_ms_per_query",
                "ici_gather_ms_per_query")


@pytest.fixture()
def mesh_copy(copy):
    import jax
    if len(jax.devices()) < CHIPS:
        pytest.skip(f"the CPU backend started with {len(jax.devices())} "
                    f"device(s); the cell needs {CHIPS}")
    copy.harness.device_gate = lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": chips}
    return copy


@pytest.mark.parametrize("trace", (0, 1))
def test_result_line(mesh_copy, capsys, trace):
    result = mesh_copy.run(capsys, CELL, trace=trace)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert result["device"]["count"] == CHIPS
    assert set(result["metrics"]) == expected_metrics(mesh_copy, CELL, trace)
    compared = result["compared"]
    for name in ("exact_mismatches", "unanswered", "off_device_nodes"):
        assert compared[name] == {"value": 0, "limit": 0}, name
    with open(os.path.join(mesh_copy.bench, "configs",
                           "tpch_sf1_mesh4.json")) as fh:
        limits = json.load(fh)["guarantees"]["float_rel_gap_limits"]
    assert compared["gap.q3.revenue"]["limit"] == limits["q3.revenue"]
    if trace:
        values = {k: v["value"] for k, v in result["metrics"].items()}
        for name in MESH_METRICS:
            assert values[name] > 0, name
        # q3: two joins (two collectives each) and one aggregate
        assert values["ici_exchanges_per_query"] == 5
        assert values["query_s.q3"] > 0
        # CPU programs are seen by no device plane: the trace's readers
        # have nothing to read, and say so by leaving their metrics out
        assert "hbm_roofline_share.mesh4" not in values
        assert "device_idle_share.mesh4" not in values


def test_control_is_not_correct(mesh_copy, capsys):
    control = load(os.path.join(mesh_copy.bench, "tests", "control.py"),
                   "rehearsal_control_mesh4")
    control.harness = mesh_copy.harness
    for seed in (3, 2**31 + 5, 77):
        numbers = control.control_numbers(CELL, seed)
        assert numbers["control_correct"] is False, numbers
        assert "q3.revenue" in numbers["fails"]
    capsys.readouterr()


def test_control_fails_q18_too(mesh_copy, capsys):
    """Q18 is cut from the cell's stream for time, and keeps its builder,
    its reference and its limits: where it answers a row, the control's
    ``o_totalprice`` (a DOUBLE rounded to bfloat16) is over its limit."""
    h = mesh_copy.harness
    config = h.load_json("configs", "tpch_sf1_mesh4.json")
    ref = h.load_module("reference", "tpch_joins.py")
    compare = h.load_module("compare.py")
    limit = config["guarantees"]["float_rel_gap_limits"]["q18.o_totalprice"]
    answered = 0
    for seed in (3, 5, 7):
        paths = h.ensure_data(h.load_module("datagen", "tpch.py"), "tpch",
                              config["scale_rows"], seed)
        exact = ref.QUERIES["q18"](paths)
        if exact.num_rows:
            answered += 1
            r = compare.compare_tables(ref.QUERIES["q18"](paths, "bfloat16"),
                                       exact)
            assert r["exact_mismatches"] or \
                r["gaps"]["o_totalprice"] > limit, (seed, r)
    assert answered
    capsys.readouterr()


def test_reference_agrees_with_itself(mesh_copy, capsys):
    h = mesh_copy.harness
    config = h.load_json("configs", "tpch_sf1_mesh4.json")
    paths = h.ensure_data(h.load_module("datagen", "tpch.py"), "tpch",
                          config["scale_rows"], 3)
    ref = h.load_module("reference", "tpch_joins.py")
    compare = h.load_module("compare.py")
    for q in ("q3", "q18"):
        r = compare.compare_tables(ref.QUERIES[q](paths),
                                   ref.QUERIES[q](paths))
        assert r["exact_mismatches"] == 0 and not any(r["gaps"].values())
    capsys.readouterr()
