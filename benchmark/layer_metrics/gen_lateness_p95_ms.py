"""How late the benchmark's own generator sent requests: 95th percentile of
send time minus due time. A starved generator must not read as a fast
server. Layer: the benchmark's generator. Source: host clock."""

from benchmark import stats


def read(view):
    late = view.result.get("lateness_s")
    if not late:
        return None
    return stats.percentile(late, 95) * 1e3
