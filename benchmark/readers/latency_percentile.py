"""Nearest-rank percentile of submit-to-result seconds over the requests
of the window (of one template with ``query``).  A request that failed or
never answered counts as the slowest one seen."""

import math


def read(run, p, query=None):
    mine = [e for e in run.executions if query in (None, e["name"])]
    took = [e["end"] - e["start"] for e in mine if e["ok"]]
    if not took:
        return None
    took += [max(took)] * (len(mine) - len(took))
    took.sort()
    return took[max(0, math.ceil(p / 100 * len(took)) - 1)]
