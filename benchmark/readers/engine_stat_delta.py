"""Growth of one counter of ``engine_stats()`` over the window, divided by
the growth of another (``per``, a path too) or by the queries completed
(``per="queries"``), times ``scale``.  A path is dotted: ``d2h.pulls``."""


def _at(stats, path):
    for key in path.split("."):
        stats = stats[key]
    return stats


def read(run, path, per=None, scale=1):
    grown = _at(run.stats_after, path) - _at(run.stats_before, path)
    if per is None:
        return grown * scale
    if per == "queries":
        by = sum(e["ok"] for e in run.executions)
    else:
        by = _at(run.stats_after, per) - _at(run.stats_before, per)
    return grown * scale / by if by else None
