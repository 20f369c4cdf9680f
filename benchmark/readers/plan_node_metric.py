"""One per-node metric summed over the plans executed in the window,
divided by the number of nodes whose description starts with ``nodes``,
times ``scale``: ``scanCacheHits`` over the scan nodes is the share of
scans the device scan cache answered."""


def read(run, metric, nodes, scale=1):
    total = count = 0
    for plan in run.plans:
        for node in plan:
            if node["describe"].startswith(nodes):
                count += 1
                total += node["metrics"].get(metric, 0)
    return total * scale / count if count else None
