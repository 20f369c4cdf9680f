"""Fixtures for the benchmark's own tests (not tier-1):

    python -m pytest benchmark/tests -q

They run on the CPU at 60 k lineitem rows, in a temporary copy of
``BENCHMARK.json`` and ``benchmark/``, with the device gate lifted by the
test and never by an option of ``run.py``.
"""

import importlib.util
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
REHEARSAL_ROWS = 60_000
CELLS = ("tpch_sf1.agg",)
# a throw-away cell of kind ``served``, made of files and entries alone in
# the temporary copy (``Copy.add_served_cell``), as a later PR would bring one
SERVED = "tpch_sf1_q6served.q6_streams"
SERVED_METRICS = ("query_s", "query_s.q6", "compiles_in_window.served")


def load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


class Copy:
    """A temporary checkout that holds only the benchmark, cut to the
    rehearsal's size; the engine comes from the real repo."""

    def __init__(self, root: str):
        self.root = root
        self.bench = os.path.join(root, "benchmark")
        shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
        shutil.copytree(BENCH, self.bench, ignore=shutil.ignore_patterns(
            ".data", ".trace", "__pycache__"))
        datagen = load(os.path.join(self.bench, "datagen", "tpch.py"),
                       "rehearsal_datagen")
        for name in os.listdir(os.path.join(self.bench, "configs")):
            path = os.path.join(self.bench, "configs", name)
            with open(path) as fh:
                config = json.load(fh)
            config["scale_rows"] = REHEARSAL_ROWS
            config["rows"] = datagen.table_rows(REHEARSAL_ROWS)
            with open(path, "w") as fh:
                json.dump(config, fh)
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        self.harness = load(os.path.join(self.bench, "run.py"),
                            "rehearsal_run")
        self.harness.device_gate = lambda chips: {
            "platform": "cpu", "kind": "cpu", "count": 1}

    def run(self, capsys, workload: str, trace: int = 0, seed: int = 7,
            seconds: float = 2.0) -> dict:
        """One run of the command; its last line, parsed, with what it
        wrote to standard error and its phase lines by phase."""
        capsys.readouterr()
        rc = self.harness.main(["--workload", workload, "--seed", str(seed),
                                "--seconds", str(seconds), "--trace",
                                str(trace)])
        out, err = capsys.readouterr()
        assert rc == 0
        lines = [json.loads(ln) for ln in out.strip().splitlines()]
        result = lines[-1]
        result["stderr"] = err
        result["phases"] = {ln["phase"]: ln for ln in lines if "phase" in ln}
        return result

    def add_served_cell(self) -> str:
        """Two closed-loop streams of the Q6 template through
        ``session.server()``: a configuration, a cell, and its name in the
        ``workloads`` of metrics that are there."""
        config_name, traffic = SERVED.split(".")
        with open(os.path.join(self.bench, "configs", "tpch_sf1.json")) as fh:
            config = json.load(fh)
        config.update(name=config_name, entry="server")
        self.write_json(f"benchmark/configs/{config_name}.json", config)
        self.write_json(f"benchmark/workloads/{SERVED}.json", {
            "config": config_name, "kind": "served", "streams": 2,
            "cycle": ["q6"], "traced_cycles": 2, "why": "throw-away"})

        def add(bench):
            bench["configs"].append({
                "name": config_name, "source": "throw-away",
                "file": f"benchmark/configs/{config_name}.json",
                "reduced": [], "why": "throw-away"})
            bench["workloads"].append({
                "name": SERVED, "config": config_name,
                "traffic": traffic, "chips": 1, "why": "throw-away"})
            for m in bench["end_to_end"] + bench["per_layer"]:
                if m["name"] in SERVED_METRICS:
                    m["workloads"].append(SERVED)

        self.edit_json("BENCHMARK.json", add)
        return SERVED

    def write_json(self, relative: str, data) -> None:
        with open(os.path.join(self.root, relative), "w") as fh:
            json.dump(data, fh)

    def edit_json(self, relative: str, change) -> None:
        path = os.path.join(self.root, relative)
        with open(path) as fh:
            data = json.load(fh)
        change(data)
        with open(path, "w") as fh:
            json.dump(data, fh)


@pytest.fixture()
def copy(tmp_path):
    return Copy(str(tmp_path))


@pytest.fixture()
def cell(copy, request):
    """The cell's name, the throw-away one made first."""
    return copy.add_served_cell() if request.param == SERVED \
        else request.param
