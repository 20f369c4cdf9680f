#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place and computed one precision below the f32 the configurations state
for the device (bfloat16, ``reference/<suite>.py``), compared with the
float64 reference by the benchmark's own comparison at the cell's own size.
It has to come out as not correct.  The benchmark's runs never run this;
``test_control.py`` keeps it at a size a test run can hold.

    python3 benchmark/tests/control.py --workload <cell> --seed <n> [--seed <n> ...]

prints one JSON line per seed with the numbers compared, and exits 1 if the
control passes on any seed.  It needs no chip: it reuses the data of the
seed where a run left it, and makes it otherwise.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402


def control_numbers(workload: str, seed: int, requests: int = 40) -> dict:
    """The comparison's numbers for the low-precision control on the
    answers of one pass (``stream``) or of the first ``requests`` requests
    of every stream (``served``)."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(w for w in bench["workloads"] if w["name"] == workload)
    cell = harness.load_json("workloads", f"{workload}.json")
    config = harness.load_json("configs", f"{entry['config']}.json")
    datagen = harness.load_module("datagen", config["suite"] + ".py")
    ref = harness.load_module(
        "reference", harness.query_suite(cell, config) + ".py")
    compare = harness.load_module("compare.py")
    paths = harness.ensure_data(datagen, config["suite"],
                                config["scale_rows"], seed)
    if cell["kind"] == "stream":
        pairs = [(q, ref.QUERIES[q](paths, "bfloat16"), ref.QUERIES[q](paths))
                 for q in cell["queries"]]
    else:
        traffic = harness.load_module("traffic.py").ServedTraffic(
            cell, os.path.join(HERE, "queries"), seed)
        sent = {rq for i in range(traffic.streams)
                for rq in itertools.islice(traffic.stream(i), requests)}
        pairs = [(n, ref.TEMPLATES[n](paths, p, "bfloat16"),
                  ref.TEMPLATES[n](paths, p)) for n, p in sorted(sent)]
    g = config["guarantees"]
    total = compare.worst((name, compare.compare_tables(
        low, exact, floor=g["float_floor"])) for name, low, exact in pairs)
    failed = [k for k, v in total["gaps"].items()
              if v > compare.gap_limit(g, k)]
    if total["exact_mismatches"] > g["exact_mismatches_limit"]:
        failed.append("exact_mismatches")
    return {"workload": workload, "seed": seed, "answers": len(pairs),
            "control": total, "fails": failed, "control_correct": not failed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    passed = False
    for seed in args.seed:
        numbers = control_numbers(args.workload, seed)
        print(json.dumps(numbers), flush=True)
        passed = passed or numbers["control_correct"]
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
