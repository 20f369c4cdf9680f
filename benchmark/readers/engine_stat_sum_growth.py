"""``engine_stat_growth``'s arithmetic over a SUM of counters: the growth
over the window of every counter of ``engine_stats()`` in ``paths``, added
up, divided by the queries completed (``per="queries"``) or by the growth of
another counter (``per``, a path too), times ``scale``.
``fk_join_share.joins`` is ``join.fk`` + ``join.fk_dense`` over
``join.joins``.  Where a snapshot lacks any of the paths (a program from
before the counter) there is nothing to read, and the metric is left out of
the line.  A path is dotted: ``join.fk``."""


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


def read(run, paths, per=None, scale=1):
    grown = [growth(run, path) for path in paths]
    if None in grown:
        return None
    if per is None:
        return sum(grown) * scale
    by = sum(e["ok"] for e in run.executions) if per == "queries" \
        else growth(run, per)
    return sum(grown) * scale / by if by else None
