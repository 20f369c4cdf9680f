"""Share of the HBM roofline over the traced span, for a configuration of
several chips: the bytes that ``rooflines/<config>.json`` says each traced
query must read once (its columns x the configuration's rows x the
configuration's bytes per row), over the configuration's ``chips`` times one
chip's peak bytes/s from ``trace/peaks.json``, over the seconds the device was
busy.

How a four-chip roofline is read: ``trace/reduce.py`` gives ``busy_s`` as the
MEAN over the chips' device planes, so the divisor is the bytes the whole
host could have moved while its chips were busy as long as they were.  Four
chips busy a quarter of a second each read the same share as one chip busy
for the whole second, and bytes that are read once can never read over 100 %,
however they are divided over the chips.  ``trace_roofline_share`` divides by
ONE chip's peak over the same mean and would read up to four times that.
The bytes never come from what ran, so the share reads the same work
whatever implements it.  No trace, or a device that never ran an operation:
nothing to read."""


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
    peak = run.config["chips"] * run.load_peaks()["hbm_bytes_per_s"]
    least_s = sum(query_bytes(run.config, run.roofline, q)
                  for q in done) / peak
    return 100.0 * least_s / run.trace["busy_s"]
