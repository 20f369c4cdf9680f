"""Share of the HBM roofline over the traced span: the bytes that
``rooflines/<config>.json`` says each traced query must read once (its
columns x the configuration's rows x the configuration's bytes per row),
over the chip's peak bytes/s from ``trace/peaks.json``, over the seconds the
device was busy.  The bytes never come from what ran, so the share reads the
same work whatever implements it.  No trace, or a device that never ran an
operation: nothing to read."""


def query_bytes(config, roofline, query):
    """Bytes ``query`` has to read once."""
    return sum(config["rows"][table] * config["column_bytes"][table][column]
               for table, column in roofline[query])


def read(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    done = [e["name"] for e in run.executions if e["ok"]]
    if not done or any(q not in run.roofline for q in done):
        return None
    least_s = sum(query_bytes(run.config, run.roofline, q) for q in done) \
        / run.load_peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace["busy_s"]
