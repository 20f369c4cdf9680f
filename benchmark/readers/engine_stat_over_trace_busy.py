"""100 x growth of one counter of ``engine_stats()`` over the window, times
``scale`` (to seconds), over the seconds the profiler saw the device busy
(``trace/reduce.py``): how much of the profiler's busy time the engine's own
device clock accounts for.  No trace, a device that never ran an operation,
or a program without the counter: nothing to read."""


def read(run, path, scale=1):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    ends = []
    for stats in (run.stats_before, run.stats_after):
        for key in path.split("."):
            if not isinstance(stats, dict) or key not in stats:
                return None
            stats = stats[key]
        ends.append(stats)
    return 100.0 * (ends[1] - ends[0]) * scale / run.trace["busy_s"]
