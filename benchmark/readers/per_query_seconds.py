"""Mean seconds of the executions of one query (all of them without
``query``), on the harness's clock around the call that returns the
answer."""


def read(run, query=None):
    took = [e["end"] - e["start"] for e in run.executions
            if e["ok"] and query in (None, e["name"])]
    return sum(took) / len(took) if took else None
