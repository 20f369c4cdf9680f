"""Requests completed divided by the seconds from the start of the window
to the last completion."""


def read(run):
    done = [e for e in run.executions if e["ok"]]
    if not done:
        return None
    return len(done) / (max(e["end"] for e in done) - run.window_start)
