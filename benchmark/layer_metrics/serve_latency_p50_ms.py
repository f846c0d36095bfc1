"""50th percentile of request latency from the moment each request was DUE
(open loop), over every request due in the window; a failed request counts
as window + drain. A per-layer metric, not an end-to-end one: at 0.8 x knee
its run-to-run spread on this machine is far wider than any bound the
benchmark may set (PERF.md section 2). Layer: serving. Source: host clock."""

from benchmark import stats


def read(view):
    latencies = view.result.get("latency_s")
    if not latencies:
        return None
    return stats.percentile(latencies, 50) * 1e3
