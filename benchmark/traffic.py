"""The one general generator: from a cell's data file and ``--seed`` to the
requests of a run.

A ``stream`` cell replays its ordered list of queries.  A ``served`` cell
gives each client stream the cell's cycle of template names, started
``stream_offset`` places further on for each stream, and binds every
request from the template's ``<name>.params.json``: the full grid of its
draws, shuffled by the seed and dealt to the streams without replacement,
so that every seed sends the same set of bindings in another order.  The
grid's last binding warms the template; the streams reach it last.
"""

from __future__ import annotations

import datetime as dt
import itertools
import json
import os
import random
from typing import Dict, Iterator, List, Tuple


def _values(spec: dict) -> list:
    if "choice" in spec:
        values = list(spec["choice"])
    else:
        lo, hi, step = spec["range"]
        values = list(range(lo, hi + 1, step))
    values = [v * spec.get("scale", 1) + spec.get("offset", 0)
              for v in values]
    return [round(v, spec["round"]) for v in values] \
        if "round" in spec else values


def _bind(entry: dict, draw: Dict[str, object]):
    v = draw[entry["draw"]] + entry.get("add", 0)
    if "round" in entry:
        v = round(v, entry["round"])
    kind = entry["as"]
    if kind == "date_jan1":
        return dt.date(int(v), 1, 1)
    if kind == "float":
        return float(v)
    if kind == "int":
        return int(v)
    raise ValueError(f"unknown binding kind {kind!r}")


def binding_grid(params_path: str, seed: int) -> List[tuple]:
    """Every binding of the template, once, in an order drawn from the
    seed."""
    with open(params_path) as fh:
        spec = json.load(fh)
    names = sorted(spec["draws"])
    grid = [tuple(_bind(e, dict(zip(names, combo))) for e in spec["bind"])
            for combo in itertools.product(
                *(_values(spec["draws"][n]) for n in names))]
    random.Random(seed).shuffle(grid)
    return grid


class ServedTraffic:
    """The requests of each client stream of a ``served`` cell."""

    def __init__(self, cell: dict, queries_dir: str, seed: int):
        self.cycle: List[str] = list(cell["cycle"])
        self.streams = int(cell["streams"])
        self.offset = int(cell.get("stream_offset", 0))
        self.templates = sorted(set(self.cycle))
        self.sql = {}
        self.grid = {}
        for i, name in enumerate(self.templates):
            with open(os.path.join(queries_dir, f"{name}.sql")) as fh:
                self.sql[name] = " ".join(fh.read().split())
            self.grid[name] = binding_grid(
                os.path.join(queries_dir, f"{name}.params.json"),
                seed * 31 + i)
            need = 1 + self.streams
            if len(self.grid[name]) < need:
                raise ValueError(f"template {name}: {len(self.grid[name])} "
                                 f"bindings, {need} needed")

    def warm(self, name: str) -> tuple:
        """The binding that warms the template."""
        return self.grid[name][-1]

    def stream(self, index: int) -> Iterator[Tuple[str, tuple]]:
        """``(template, params)`` for stream ``index``, without end."""
        mine = {n: g[:-1][index::self.streams] for n, g in self.grid.items()}
        taken = dict.fromkeys(self.templates, 0)
        for pos in itertools.count(index * self.offset):
            name = self.cycle[pos % len(self.cycle)]
            yield name, mine[name][taken[name] % len(mine[name])]
            taken[name] += 1
