"""``engine_stat_delta``'s arithmetic for a counter that a program may not
have yet: growth of one counter of ``engine_stats()`` over the window,
divided by the queries completed (``per="queries"``) or by the growth of
another counter (``per``, a path too), times ``scale``.  Where either
snapshot lacks a path there is nothing to read, and the metric is left out
of the line (``engine_stat_delta`` raises there).  A path is dotted:
``programs.dispatches``."""


def growth(run, path):
    """Growth of ``path`` over the window, or ``None`` where a snapshot
    lacks it."""
    ends = []
    for stats in (run.stats_before, run.stats_after):
        for key in path.split("."):
            if not isinstance(stats, dict) or key not in stats:
                return None
            stats = stats[key]
        ends.append(stats)
    return ends[1] - ends[0]


def read(run, path, per=None, scale=1):
    grown = growth(run, path)
    if grown is None:
        return None
    if per is None:
        return grown * scale
    by = sum(e["ok"] for e in run.executions) if per == "queries" \
        else growth(run, per)
    return grown * scale / by if by else None
