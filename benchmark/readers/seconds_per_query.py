"""Seconds from the start of the window to the last completion, divided by
the queries completed in it: all the work over all the time."""


def read(run):
    done = [e for e in run.executions if e["ok"]]
    if not done:
        return None
    return (max(e["end"] for e in done) - run.window_start) / len(done)
