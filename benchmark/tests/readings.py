#!/usr/bin/env python3
"""The readings a limit is set from ("How correct is decided", steps 4-5):
the program's numbers and the control's on many seeds, in one process so
that set-up and compilation are paid once.

    python3 benchmark/tests/readings.py --workload <cell> --seconds <s> --seed <n> [--seed <n> ...]

For each seed: one run of ``run.py``'s ``main`` (same data, traffic, timed
path and comparison as the benchmark's own run; ``--seconds`` may be short,
a ``stream`` cell still makes one whole pass), then ``control.py``'s numbers
on the same data.  One JSON line per seed: ``program`` and ``control`` hold
the numbers compared, ``executions`` the seconds of each timed query.  Needs the chip, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control  # noqa: E402

harness = control.harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    for seed in args.seed:
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = harness.main(["--workload", args.workload, "--seed",
                               str(seed), "--seconds", str(args.seconds),
                               "--trace", "0"])
        if rc:
            return rc
        with contextlib.redirect_stdout(io.StringIO()):
            low = control.control_numbers(args.workload, seed)
        lines = [json.loads(ln) for ln in out.getvalue().splitlines()]
        result = lines[-1]
        window = next(ln for ln in lines if ln.get("phase") == "window")
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": result["correct"], "attempted": result["attempted"],
            "executions": window["executions"],
            "off_device_nodes": window["off_device_nodes"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "program": {k: v["value"]
                        for k, v in result["compared"].items()},
            "control": {"exact_mismatches":
                        low["control"]["exact_mismatches"],
                        **{f"gap.{k}": v
                           for k, v in low["control"]["gaps"].items()}},
            "control_fails": low["fails"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
