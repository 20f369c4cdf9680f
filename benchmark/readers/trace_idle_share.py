"""100 x (1 - union of the device's operation intervals / traced span),
from the profiler's trace (``trace/reduce.py``)."""


def read(run):
    if run.trace is None or not run.trace["events"]:
        return None  # no trace, or one with no device plane: nothing to read
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
